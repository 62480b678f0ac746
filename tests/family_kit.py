"""One kit for the tests of the model families (``benchmark/families/*.py``
and the models under them): what thirteen files each built for themselves,
built once a process. Not collected as tests.

A family's tier-1 cost is compiling its tiny programs. Every test of a
family therefore takes its model, its seeded weights and its jitted
programs from here, where each is made once a process and a key:

- :func:`tiny` — the family's model over ``benchmark/tests/tiny-*.json``
  with the edits its tests made (``_TINY``), one object a (family,
  overrides);
- :func:`train_step`, :func:`grad_step` — the family's step programs for
  a model: one jitted object a model (the library's store keys on the
  optimizer by identity), so that tests of one file compile a program
  once. A test that counts compiles from zero or reads a program's text
  asks for a model of its own: :func:`fresh_tiny`;
- :func:`seeded_params`, :func:`batch`, :func:`bias_leaves` — the seeded
  weights (balance biases away from zero) and batches;
- :func:`ft_steps` and :func:`two_groups_one_healed` — the two loop
  scenarios: lighthouse, ``ReplicaGroup``s on the shared programs, threads,
  waits and teardown, with the asserts that hold for every family. A
  family's test keeps its name, its docstring and the asserts about ITS
  leaves, biases and gauges;
- :func:`sound` — a fault test's sound side, evaluated once a key.

Faults and the sound side. A ``test_a_fault_fails…`` case patches the
SYSTEM and compares it with a sound side. Where that side is the plain
float32 reference of ``benchmark/reference`` (which imports nothing from the
program, so no patch reaches it), it is evaluated once a (cfg, seed):
through :func:`sound` in ``test_lfm2``, ``test_joyai`` and
``test_nemotron_h_family`` (sixteen, eight and eighteen cases, one evaluation
each) and in ``test_kimi_linear`` (the scan's own sound comparison, beside
the file's ``_reference_at``); through the files' own one-entry caches in
``test_smallthinker`` (``_sound``) and ``test_olmo_hybrid``
(``both_sides``); ``test_olmoe``'s reference is a module-level jit.
``test_laguna_family`` goes through the family's ``per_token_errors``, which
traces system and reference in ONE program while the fault's patches are
in place: those cases keep their own evaluation.
"""

import contextlib
import functools
import importlib
import json
import os
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# family -> its tiny file and the edits its tests make before
# ``family.build``: ``rows`` of the job; ``bias_rate``, a balance-bias
# rate that moves the bias visibly within a few steps; ``layers``, the
# first layers alone (the loop's tests compile the step), which cuts the
# ``per_layer`` lists with it.
_TINY = {
    "joyai": dict(file="tiny-joyai.json", rows=2, bias_rate=0.01),
    "kimi_linear": dict(file="tiny-kimi.json", rows=2, bias_rate=0.01),
    "lfm2": dict(file="tiny-lfm2.json", rows=2, bias_rate=0.01),
    "nemotron_h": dict(file="tiny-nemotron.json", rows=2, bias_rate=0.01),
    "olmoe": dict(file="tiny-olmoe.json", rows=2),
    # one period: every kind of layer
    "smallthinker": dict(
        file="tiny-smallthinker.json", rows=2, bias_rate=0.01, layers=4,
        per_layer=("sliding_window_layout", "rope_layout")),
    "olmo_hybrid": dict(file="tiny-olmo-hybrid.json"),
    # full and dense, then sliding and sparse
    "laguna": dict(
        file="tiny-laguna.json", bias_rate=0.01, layers=2,
        per_layer=("layer_types", "mlp_layer_types",
                   "num_attention_heads_per_layer")),
    # one period: three delta-rule layers and a full one, every one sparse
    "qwen3_next": dict(file="tiny-qwen3next.json", bias_rate=0.01),
    # two layers, each choosing 12 of up to 64 keys
    "keye": dict(file="tiny-keye.json", bias_rate=0.01),
    # one layer of each kind (M A of M A M M), sixteen heads on one B and C
    "granite_hybrid": dict(file="tiny-granite.json", layers=2,
                           per_layer=("layer_types",)),
    # two layers run four times on one set of weights
    "ouro": dict(file="tiny-ouro.json"),
}
# the families with a loop scenario in tier-1 (``gpt``'s are
# tests/test_step_programs.py's; ``phi4flash`` has none: ROADMAP.md)
LOOP_FAMILIES = tuple(_TINY)


def family(name):
    return importlib.import_module(f"benchmark.families.{name}")


def tiny_config(name, **overrides):
    """The family's tiny file as a dict, with ``_TINY``'s edits and the
    caller's (``rows``, ``layers``) on top."""
    edits = dict(_TINY[name], **overrides)
    with open(os.path.join(ROOT, "benchmark", "tests", edits["file"])) as f:
        config = json.load(f)
    if "rows" in edits:
        config["job"]["rows"] = edits["rows"]
    if "layers" in edits:
        config["num_hidden_layers"] = edits["layers"]
        for key in edits["per_layer"]:
            config[key] = config[key][:edits["layers"]]
    if "bias_rate" in edits:
        config["optimizer"]["balance_bias_rate"] = edits["bias_rate"]
    return config


_FAMILY_OF = {}                 # id(model) -> (family's name, model)


def tiny(name, **overrides):
    """``family.build`` over the family's tiny configuration: the same
    object for the same (family, overrides) all through a process."""
    return _tiny(name, tuple(sorted(overrides.items())))


@functools.lru_cache(maxsize=None)
def _tiny(name, overrides):
    return fresh_tiny(name, **dict(overrides))


def fresh_tiny(name, **overrides):
    """A model nobody else holds (its optimizer is its own, and so are its
    step programs): for a test that counts compiles from zero or reads a
    program's text."""
    model = family(name).build(tiny_config(name, **overrides))
    _FAMILY_OF[id(model)] = (name, model)
    return model


def family_of(model):
    return family(_FAMILY_OF[id(model)][0])


def train_step(model):
    """The family's fused step. ``models.make_train_step`` hands out ONE
    ``StepProgram`` for equal (cfg, tx, loss) within a process, ``tx`` by
    identity: whoever shares the :func:`tiny` model shares the program and
    its one compile. A test that counts compiles from zero builds a model
    of its own (:func:`fresh_tiny`)."""
    return family_of(model).make_train_step(model)


def grad_step(model):
    return family_of(model).make_grad_step(model)


_SOUND = {}


def sound(key, evaluate):
    """A fault test's sound side (a reference no patch reaches, or the
    sound system's result) evaluated once a ``key``."""
    if key not in _SOUND:
        _SOUND[key] = evaluate()
    return _SOUND[key]


def seeded_params(module, cfg, seed, bias_std=0.1):
    """``module.init_params`` on ``seed`` with the balance biases away
    from zero, so that a system that ignored them would route
    differently. The leaves are made once a key; the dicts around them are
    the caller's own (some tests set a leaf in place)."""
    return jax.tree_util.tree_map(
        lambda x: x, _seeded_params(module, cfg, seed, bias_std))


@functools.lru_cache(maxsize=None)
def _seeded_params(module, cfg, seed, bias_std):
    params = module.init_params(cfg, jax.random.key(seed))
    key = jax.random.key(1000 + seed)

    def leaf(path, x):
        if getattr(path[-1], "key", None) != module.BALANCE_BIAS:
            return x
        return bias_std * jax.random.normal(
            jax.random.fold_in(key, len(jax.tree_util.keystr(path))), x.shape)

    return jax.tree_util.tree_map_with_path(leaf, params)


def batch(seed, rows=2, seq=64, vocab=512):
    tokens = jax.random.randint(jax.random.key(100 + seed), (rows, seq), 0,
                                vocab)
    return tokens, jnp.roll(tokens, -1, axis=1)


def bias_leaves(tree, name="balance_bias"):
    return [x for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]
            if getattr(path[-1], "key", None) == name]


# -- the loop scenarios ------------------------------------------------------

# One group alone: ``min_replicas=1`` lets it form its own quorum, and
# nobody else heartbeats, so ``join_timeout_ms`` is never waited out (all
# healthy members have asked the moment it asks); 100 ms is the files' old
# value.
_SOLO_LIGHTHOUSE = dict(min_replicas=1, join_timeout_ms=100)
# Two groups, the second joining behind. A quorum of one cannot form while
# the other heartbeats (the majority rule), and the previous quorum's
# members all asking is served at once, so ``join_timeout_ms`` (200, the
# files' old value) decides nothing here either: with TWO groups there is
# no third to rotate (tests/test_sharded_e2e.py has the case of three).
# ``heartbeat_timeout_ms=5000``, the files' old value too: a group whose
# heartbeat thread is starved for a second while the other compiles must
# not read as dead, or the other commits alone and ``both`` comes short.
_PAIR_LIGHTHOUSE = dict(min_replicas=1, join_timeout_ms=200,
                        heartbeat_timeout_ms=5000)


@contextlib.contextmanager
def ft_steps(model, seed=7, steps=3):
    """The cell's ``plain_worker`` check at the small size: ``steps`` plain
    steps of the shared :func:`train_step`, then the same batches through
    one ``ReplicaGroup`` on it. Checked here, for every family: each step
    commits on the fused path, the losses and every leaf of the state
    equal the plain steps' bit for bit, and the step program was compiled
    once for both. Yields a namespace — ``group`` (still up: torn down on
    exit), ``records``, ``losses``, the plain steps' ``params`` and
    ``opt``, ``source``, ``device``, ``train_step`` — for the family's own
    asserts."""
    from benchmark.group import ReplicaGroup
    from benchmark.traffic_gen import BatchSource
    from torchft_tpu.control import Lighthouse

    fam = family_of(model)
    device = jax.devices()[0]
    source = BatchSource(seed, 0, 0, model.rows, model.seq_len,
                         model.vocab_draw)
    step = train_step(model)
    state = fam.init_state(model, seed, device)
    params, opt = state["params"], state["opt"]
    losses = []
    for i in range(steps):
        params, opt, loss = step(params, opt, *source.device_batch(i, device))
        losses.append(float(loss))
    lighthouse = Lighthouse(**_SOLO_LIGHTHOUSE)
    group = None
    try:
        group = ReplicaGroup(0, 0, model, fam, device, 0,
                             lighthouse.address(), seed, source,
                             train_step=step)
        records = [group.step(*source.device_batch(i, device))
                   for i in range(steps)]
        assert all(r["committed"] and r["path"] == "fused" for r in records)
        assert [float(r["loss"]) for r in records] == losses
        for a, b in zip(jax.tree_util.tree_leaves(group.state),
                        jax.tree_util.tree_leaves({"params": params,
                                                   "opt": opt})):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert step._cache_size() == 1
        yield types.SimpleNamespace(
            group=group, records=records, losses=losses, params=params,
            opt=opt, source=source, device=device, train_step=step,
            steps=steps)
    finally:
        if group is not None:
            group.teardown()
        lighthouse.shutdown()


def routing_gauges(run, until=12, key="moe_held_share"):
    """The optimizer wrapper's routing gauges (or, by ``key``, a model's
    step statistics) arrive on its sink without a wait: they are read at a
    later commit than the one that asked, so the group of :func:`ft_steps`
    steps on (at most to ``until``) until the first is there. Returns the
    sink's snapshot."""
    group = run.group
    for i in range(run.steps, until):
        if key in group.opt.metrics.snapshot():
            break
        jax.block_until_ready(group.state)
        group.step(*run.source.device_batch(i, run.device))
    return group.opt.metrics.snapshot()


@contextlib.contextmanager
def two_groups_one_healed(model, tail=3):
    """grad -> average_gradients -> step across two replica groups that see
    different batches. The first runs alone (on the fused path) to step 2;
    the second starts from other weights, behind, and gets to the first's
    state only by a heal; ``tail`` steps after the joiner's first commit
    both stop on one step. Checked here, for every family: nothing raised,
    the joiner healed, the steps two wide ran the classic path (at least
    ``tail - 1`` of them), both are at rest on one step and the sha256 of
    parameters and optimizer state are equal. Yields a namespace —
    ``first``, ``second``, ``groups`` (torn down on exit), ``both`` — for
    the family's own asserts. Both groups run the shared programs, each on
    a device of its own."""
    from benchmark.group import ReplicaGroup
    from benchmark.traffic_gen import BatchSource
    from torchft_tpu.control import Lighthouse

    fam = family_of(model)
    devices = jax.devices()
    lighthouse = Lighthouse(**_PAIR_LIGHTHOUSE)
    stop_at = [None]

    def keep_going(group):
        return stop_at[0] is None or group.manager.current_step() < stop_at[0]

    groups, threads = [], []

    def start(gid, seed):
        source = BatchSource(11, gid, 0, model.rows, model.seq_len,
                             model.vocab_draw)
        group = ReplicaGroup(gid, 0, model, fam, devices[gid], gid,
                             lighthouse.address(), seed, source,
                             train_step=train_step(model))
        group.grad_step = grad_step(model)
        thread = threading.Thread(target=group.run, args=(keep_going,),
                                  daemon=True)
        groups.append(group)
        threads.append(thread)
        thread.start()
        return group

    def wait_for(cond, what):
        deadline = time.monotonic() + 120          # a hung run, no more
        while not cond():
            assert all(g.error is None for g in groups), [
                repr(g.error) for g in groups]
            assert time.monotonic() < deadline, what
            time.sleep(0.02)

    try:
        first = start(0, 1)
        wait_for(lambda: first.manager.current_step() >= 2, "solo steps")
        solo = [r for r in list(first.records) if r["committed"]]
        assert solo and all(r["path"] == "fused" for r in solo)
        second = start(1, 2)          # other weights, a zero bias, behind
        wait_for(lambda: any(r["committed"] for r in list(second.records)),
                 "the joiner's first commit")
        stop_at[0] = max(g.manager.current_step() for g in groups) + tail
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
        assert all(g.error is None for g in groups), [g.error for g in groups]
        jax.block_until_ready([g.state for g in groups])
        assert any(r["healed"] for r in second.records)
        both = [r for r in first.records
                if r["committed"] and r["participants"] == 2]
        assert len(both) >= max(1, tail - 1)
        assert all(r["path"] == "classic" for r in both)
        assert first.manager.current_step() == second.manager.current_step()
        assert first.digest() == second.digest()
        yield types.SimpleNamespace(first=first, second=second,
                                    groups=groups, both=both)
    finally:
        for g in groups:
            g.teardown()
        lighthouse.shutdown()


def assert_built_once(name):
    """The guard that keeps a family's tier-1 cost from growing back by
    building its tiny programs a test: :func:`tiny` hands out one object,
    and after the family's loop scenario ran on it the shared step program
    holds one compiled entry."""
    model = tiny(name)
    assert tiny(name) is model
    assert train_step(model) is train_step(model)
    assert train_step(fresh_tiny(name)) is not train_step(model)
    with ft_steps(model):
        pass
    assert train_step(model)._cache_size() == 1
