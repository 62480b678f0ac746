"""Per-layer metrics that are one line of arithmetic on the run's record
(``harness.py`` documents its keys). The metric's file names which:
``{"reader": "derived", "what": "<name below>"}``."""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark import flops, harness


def _counter_delta(record: Dict[str, Any], sink: str, key: str) -> float:
    """Growth of a counter over the window, summed over every group that
    lived in it (``start`` is empty for one born inside the window)."""
    return sum(
        s[sink].get(key, 0.0) - s["start"].get(sink, {}).get(key, 0.0)
        for s in record["sinks"]
    )


def _bare_step_ms(r):
    return None if not r.get("bare") else \
        harness.median(r["bare"]["step_s"]) * 1e3


def _ft_over_bare(r):
    if not r.get("bare") or not r.get("ft_tokens_per_s_per_chip"):
        return None
    return r["ft_tokens_per_s_per_chip"] / r["bare"]["tokens_per_s"]


def _bare_mfu(r):
    if not r.get("bare"):
        return None
    return flops.mfu(r["bare"]["tokens_per_s"], r["flops_per_token"],
                     r["device_kind"])


def _wire_mb_per_step(r):
    steps = _counter_delta(r, "manager", "steps_committed")
    if steps <= 0:
        return None
    return _counter_delta(r, "manager", "comm_raw_bytes") / steps / 1e6


_WHAT = {
    "boot_s": lambda r: r["boot_s"],
    "first_step_s": lambda r: r["first_step_s"],
    "compiles_in_window": lambda r: float(r["compiles_in_window"]),
    "bare_step_ms": _bare_step_ms,
    "ft_over_bare": _ft_over_bare,
    "bare_mfu": _bare_mfu,
    "window_over_blocks": lambda r: r.get("window_over_blocks"),
    "wire_mb_per_step": _wire_mb_per_step,
    "survivor_stall_s": lambda r: harness.median(
        k["survivor_stall_s"] for k in r.get("kills", [])
        if k.get("survivor_stall_s") is not None
    ),
    "recover_s": lambda r: harness.median(
        k["recover_s"] for k in r.get("kills", [])
        if k.get("recover_s") is not None
    ),
    "device_idle_share": lambda r: r["trace"]["idle_share"],
}


def read(record: Dict[str, Any], spec: Dict[str, Any]) -> Optional[float]:
    value = _WHAT[spec["what"]](record)
    return None if value is None else float(value)
