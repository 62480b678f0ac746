"""The one general traffic generator: what a training job is fed, and
when its groups are killed, both pure functions of ``--seed`` and the
parameters in a ``traffic/<mix>.json`` file. The program receives only
the generated batches."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np


class BatchSource:
    """Batch ``i`` of stream ``(seed, group, incarnation)``: ``rows``
    sequences of ``seq_len`` token ids drawn uniformly below
    ``vocab_draw``, with next-token targets (the row rolled left by one,
    as ``examples/train_ddp.py`` feeds its loop). Indexable, so a plain
    run and an FT run can be fed the same batches."""

    def __init__(self, seed: int, group: int, incarnation: int, rows: int,
                 seq_len: int, vocab_draw: int) -> None:
        self._key = (int(seed), int(group), int(incarnation))
        self.rows, self.seq_len, self.vocab_draw = rows, seq_len, vocab_draw

    @property
    def tokens_per_batch(self) -> int:
        return self.rows * self.seq_len

    def host_batch(self, i: int) -> Tuple[np.ndarray, np.ndarray]:
        rng = np.random.default_rng((*self._key, int(i)))
        tokens = rng.integers(
            0, self.vocab_draw, (self.rows, self.seq_len), dtype=np.int32
        )
        return tokens, np.roll(tokens, -1, axis=1)

    def device_batch(self, i: int, device: Any) -> Tuple[Any, Any]:
        import jax

        with jax.profiler.TraceAnnotation("bm.input"):
            return tuple(jax.device_put(self.host_batch(i), device))


def kill_schedule(seed: int, seconds: float, traffic: Dict[str, Any],
                  n_groups: int) -> List[Tuple[float, int]]:
    """``[(seconds into the window, victim group), ...]``: the first kill
    at ``first_kill_s``, then one every ``kill_every_s`` while the window
    lasts; victims are a seeded shuffle of all groups, repeated as often
    as needed."""
    first = float(traffic["first_kill_s"])
    every = float(traffic["kill_every_s"])
    times = []
    t = first
    while t <= seconds:
        times.append(t)
        t += every
    rng = np.random.default_rng((int(seed), 0x6B696C6C))
    order: List[int] = []
    while len(order) < len(times):
        order.extend(int(g) for g in rng.permutation(n_groups))
    return list(zip(times, order))
