"""The Qwen3-Next family (benchmark/families/qwen3_next.py) at the small
size of tests/test_qwen3_next.py, which holds the model to its reference:
the cell's own three comparisons and their verdicts, the configuration the
family builds, the FLOP and byte counts, and the model through the one
step maker, the one optimizer and the fault-tolerant loop, with the
routing gauges of the optimizer wrapper's sink. A file of its own so that
the two run on two of tier-1's workers."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_kit as kit

from benchmark import qwen3_next_flops
from benchmark.families import qwen3_next as family
from torchft_tpu.models import qwen3_next
from torchft_tpu.models.qwen3_next import FULL, LINEAR
from torchft_tpu.ops.attention import causal_attention
from torchft_tpu.ops.kda import gdn_scan

# the model's tests are not about how many heads share a grid step
pytestmark = pytest.mark.usefixtures("one_head_a_step")
CFG = qwen3_next.QWEN3_NEXT_CONFIGS["qwen3_next_tiny"]
CFG32 = dataclasses.replace(CFG, dtype=jnp.float32)
BIAS = qwen3_next.BALANCE_BIAS
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S = 64


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-80b-a3b-ep16.json")) as f:
        return json.load(f)


def test_the_cells_own_check_of_the_flash_call() -> None:
    """``flash_comparison`` + ``judge_flash`` at the small size, 6 query
    heads on 2 key/value heads: the sound call passes leaf by leaf (bf16
    operands: the one rounding of each result), every leaf has a limit
    that judges it alone, and it is the WORST head that is judged."""
    (q, k, v), do = family.flash_inputs(CFG, np.uint32(3), 2, S)
    assert q.shape == do.shape == (2, S, 6, 16)
    assert k.shape == v.shape == (2, S, 2, 16)
    sound = jax.device_get(jax.jit(family.flash_comparison(CFG, 2, S))(
        np.uint32(3)))
    assert set(sound) == set(family.FLASH_LEAVES)
    verdict = family.judge_flash(sound)
    assert verdict["ok"] and verdict["flash_over"] == []
    for name in family.FLASH_LEAVES:
        over = dict(sound, **{name: 1.5 * family.FLASH_REL_L2_MAX[name]})
        assert family.judge_flash(over)["flash_over"] == [name]

    def one_head_off(q, k, v):
        # the last query head alone reads its key/value head's neighbour
        o = causal_attention(q, k, v)
        other = causal_attention(q[:, :, -1:], k[:, :, :1], v[:, :, :1])
        return o.at[:, :, -1].set(other[:, :, 0])

    off = jax.device_get(jax.jit(family.flash_comparison(
        CFG32, 2, S, one_head_off))(np.uint32(3)))
    assert not family.judge_flash(off)["ok"]


def test_the_cells_own_check_of_the_scan() -> None:
    """``gdn_comparison`` + ``judge_gdn`` at the small size, as the model
    calls the scan: q and k drawn at the 2 key heads and copied to the 4
    value heads, two sequences; the sound scan passes leaf by leaf, every
    leaf has a limit that judges it alone, and one (sequence, head) off
    fails."""
    args, do = family.gdn_inputs(CFG32, np.uint32(5), 2, S)
    q, k, v, g, beta = args
    assert q.shape == k.shape == (2, S, 4, 12) and v.shape == (2, S, 4, 24)
    assert g.shape == beta.shape == (2, S, 4) and do.shape == v.shape
    # value heads 2j and 2j + 1 read key head j's q and k
    assert np.array_equal(q[:, :, 0], q[:, :, 1])
    assert np.array_equal(k[:, :, 2], k[:, :, 3])
    assert not np.array_equal(q[:, :, 1], q[:, :, 2])
    assert float(jnp.min(beta)) > 0 and float(jnp.max(beta)) < 1
    assert float(jnp.max(g)) <= 0
    sound = jax.device_get(jax.jit(family.gdn_comparison())(args, do))
    assert set(sound) == set(family.GDN_LEAVES)
    verdict = family.judge_gdn(sound)
    assert verdict["ok"] and verdict["gdn_over"] == [], verdict
    for name in family.GDN_LEAVES:
        over = dict(sound, **{name: 1.5 * family.GDN_REL_L2_MAX[name]})
        assert family.judge_gdn(over)["gdn_over"] == [name]

    def one_head_off(q, k, v, g, beta):
        # the last head of the second sequence alone does not decay
        o = gdn_scan(q, k, v, g, beta)
        other = gdn_scan(q[1:, :, -1:], k[1:, :, -1:], v[1:, :, -1:],
                         jnp.zeros_like(g[1:, :, -1:]), beta[1:, :, -1:])
        return o.at[1, :, -1].set(other[0, :, 0])

    off = jax.device_get(jax.jit(family.gdn_comparison(one_head_off))(
        args, do))
    assert not family.judge_gdn(off)["ok"]


def test_the_cells_own_check_of_the_sparse_sublayer() -> None:
    """``moe_comparison`` + ``judge_moe`` at the small size in f32: on one
    stream the two routers choose alike and the sublayer's part agrees to
    rounding; a shared expert without its gate fails the worst token's
    limit, and a router that chose on other logits the flips'."""
    import benchmark.tests.qwen3next_faults as faults
    from torchft_tpu.ops import moe

    params = qwen3_next.init_params(CFG32, jax.random.key(6))
    run = lambda: jax.device_get(jax.jit(  # noqa: E731 - traced anew a patch
        family.moe_comparison(CFG32), static_argnums=2)(
            params, np.uint32(6), S))
    sound = run()
    assert float(sound["flips"]) == 0.0 and float(sound["rel_l2"]) < 1e-4
    assert family.judge_moe(sound)["ok"]
    patches, *_ = faults.fault("shared_gate_dropped", CFG32)
    with faults.patched(patches):
        ungated = run()
    assert float(ungated["rel_l2"]) > family.MOE_REL_L2_MAX
    assert not family.judge_moe(ungated)["ok"]
    real = moe.top_k_routing
    with faults.patched(((moe, "top_k_routing", lambda s, k, **kw: real(
            jnp.round(s), k, **kw)),)):
        coarse = run()
    assert float(coarse["flips"]) > family.MOE_FLIPS_MAX
    assert not family.judge_moe(coarse)["ok"]


def test_check_reference_is_all_three_comparisons(monkeypatch) -> None:
    """The family's ``check_reference`` — what ``jobs/steady.py`` calls —
    carries the whole model's verdict, the scan's, the flash call's and
    the gauges, and is ``ok`` only where all are (the tiny configuration,
    bf16 compute; the whole model's limits are set for the cell's
    size)."""
    monkeypatch.setattr(family, "HIDDEN_REL_L2_RMS_MAX", 0.1)
    monkeypatch.setattr(family, "HIDDEN_REL_L2_MAX", 0.3)
    monkeypatch.setattr(family, "TOP_K_DISAGREEMENT_MAX", 0.2)
    monkeypatch.setattr(family, "REFERENCE_LOSS_ATOL", 5e-2)
    monkeypatch.setattr(family, "GDN_REL_L2_MAX", {
        n: 0.02 for n in family.GDN_LEAVES})
    monkeypatch.setattr(family, "MOE_REL_L2_MAX", 0.05)
    model, device = kit.tiny("qwen3_next"), jax.devices()[0]
    params = family.init_state(model, 5, device)["params"]
    seen = family.check_reference(model, params, 5, device)
    assert seen["ok"], seen
    assert {"rms", "worst", "top10_disagreement", "held_share",
            "load_max_over_mean", "beta_decay", "gdn_rel_l2", "flash_rel_l2",
            "moe"} <= set(seen)
    assert seen["gdn_over"] == seen["flash_over"] == []
    # the sublayer alone: flips and the worst token, each beside its limit
    assert seen["moe"][1::2] == [family.MOE_FLIPS_MAX, family.MOE_REL_L2_MAX]
    assert seen["moe"][0] <= family.MOE_FLIPS_MAX
    assert len(seen["held_share"]) == 4           # every layer is sparse
    assert seen["tokens"] == family.REFERENCE_SEQUENCES * model.seq_len
    # benchmark/run.py prints 600 characters of a check
    assert len(json.dumps({k: v for k, v in seen.items() if k != "ok"})) < 600
    # the second verdict judges the same readings under one limit less
    monkeypatch.setattr(family, "FLASH_REL_L2_MAX", dict(
        family.FLASH_REL_L2_MAX, dk=0.0))
    again = family.check_reference(model, params, 5, device)
    assert not again["ok"]
    assert again["flash_over"] == ["dk"] and again["gdn_over"] == []
    assert again["rms"] == seen["rms"]


def test_the_cells_own_comparison_at_the_small_size() -> None:
    """In f32 the system is the reference to rounding, the reference's own
    top-k sets are the system's, and what the check seeds is seeded on
    both sides: a side left at its constants fails."""
    params = qwen3_next.init_params(CFG32, jax.random.key(4))
    tokens, targets = kit.batch(4, vocab=CFG.vocab_size)
    seen = family.per_token_errors(CFG32, params, params, tokens, targets, 4)
    verdict = family.judge(seen)
    assert verdict["ok"] and verdict["top10_disagreement"] == 0.0
    assert verdict["tokens"] == 128 and verdict["worst"] < 1e-4
    assert len(verdict["held_share"]) == 4
    assert all(0 < s < 1 for s in verdict["held_share"])
    seeded = family.seed_check_weights(params, 4)
    moved = [jax.tree_util.keystr(p) for (p, a), b in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree_util.tree_leaves(seeded)) if a is not b]
    # four biases, and of the norms: 2 a layer, the final one, w_V of the
    # three linear layers, q_norm and k_norm of the full one
    assert len(moved) == 4 + 8 + 1 + 3 + 2
    assert all(p.endswith("['scale']") or p.endswith(f"['{BIAS}']")
               for p in moved)
    # a system whose norms took ``w`` for ``1 + w`` shows only because the
    # check seeds ``w`` away from 0
    import benchmark.tests.qwen3next_faults as faults

    patches, *_ = faults.fault("norm_plain_weight", CFG32)
    with faults.patched(patches):
        wrong = family.per_token_errors(CFG32, params, params, tokens,
                                        targets, 4)
    assert not family.judge(wrong)["ok"]


def test_the_family_builds_the_configuration_and_refuses_what_it_cannot():
    config = _config()
    model = family.build(config)
    cfg = model.cfg
    assert (cfg.n_routed_experts, cfg.first_expert, cfg.n_experts_held) == (
        512, 0, 32)
    assert cfg.layer_types == (LINEAR, LINEAR, LINEAR, FULL)
    assert (cfg.n_layers, cfg.init_depth) == (4, 48)
    assert (cfg.d_model, cfg.n_key_heads, cfg.n_value_heads, cfg.key_dim,
            cfg.value_dim, cfg.conv_kernel) == (2048, 16, 32, 128, 128, 4)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.rope_theta,
            cfg.partial_rotary, cfg.rotary_lanes) == (16, 2, 256, 1e7, 0.25,
                                                      64)
    assert (cfg.d_expert, cfg.d_shared, cfg.top_k, cfg.routed_scale,
            cfg.rms_eps, cfg.vocab_size) == (512, 512, 10, 1.0, 1e-6, 19072)
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512, "vocab_size": 151936}
    assert config["share"] == {
        "chips_sharing_a_layer": 16, "first_expert": 0, "router_width": 512,
        "vocab_ways": 8, "first_vocab_row": 0}
    assert (model.seq_len, cfg.remat, cfg.xent_chunks) == (8192, True, 4)
    assert model.rows in (4, 3, 2)
    assert model.tx.held_experts == (0, 32)
    # every number of the catalog row's config stands under its own key
    for key, value in (
            ("decoder_sparse_step", 1), ("full_attention_interval", 4),
            ("head_dim", 256), ("hidden_size", 2048),
            ("intermediate_size", 5120), ("linear_conv_kernel_dim", 4),
            ("linear_key_head_dim", 128), ("linear_num_key_heads", 16),
            ("linear_num_value_heads", 32), ("linear_value_head_dim", 128),
            ("max_position_embeddings", 262144),
            ("moe_intermediate_size", 512), ("num_attention_heads", 16),
            ("num_experts_per_tok", 10), ("num_key_value_heads", 2),
            ("partial_rotary_factor", 0.25), ("rms_norm_eps", 1e-6),
            ("rope_theta", 10000000),
            ("shared_expert_intermediate_size", 512),
            ("mlp_only_layers", []), ("rope_scaling", None),
            ("norm_topk_prob", True), ("tie_word_embeddings", False),
            ("use_sliding_window", False), ("hidden_act", "silu")):
        assert config[key] == value, key
    for name in ("published", "share", "deployment", "sizing", "assumed",
                 "departures"):
        assert config[name], name
    for said in ("mtp", "bias", "column_order", "balance_bias", "vocab_size",
                 "initializer_range", "optimizer", "balance_rule"):
        assert config["assumed"][said], said
    shapes = jax.eval_shape(
        lambda: qwen3_next.init_params(cfg, jax.random.key(0)))

    def size(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))

    # the issue's count: 625.99 M, a linear layer 138.58 M, a full one
    # 132.13 M, table and head 78.12 M
    assert size(shapes) == pytest.approx(625.99e6, rel=1e-4)
    assert size(shapes["layers_0"]) == pytest.approx(138.58e6, rel=1e-4)
    assert size(shapes["layers_3"]) == pytest.approx(132.13e6, rel=1e-4)
    assert size(shapes["layers_0"]["gdn"]) == pytest.approx(33.72e6, rel=1e-3)
    assert size(shapes["layers_3"]["attn"]) == pytest.approx(27.26e6,
                                                             rel=1e-3)
    assert size(shapes["layers_0"]["moe"]) == pytest.approx(104.86e6,
                                                            rel=1e-4)
    gdn = shapes["layers_0"]["gdn"]
    assert gdn["qkvz_proj"]["kernel"].shape == (2048, 12288)
    assert gdn["ba_proj"]["kernel"].shape == (2048, 64)
    assert gdn["conv"]["kernel"].shape == (4, 8192)
    assert gdn["o_proj"]["kernel"].shape == (4096, 2048)
    attn = shapes["layers_3"]["attn"]
    assert attn["q_proj"]["kernel"].shape == (2048, 8192)
    assert attn["k_proj"]["kernel"].shape == (2048, 512)
    assert attn["o_proj"]["kernel"].shape == (4096, 2048)
    assert shapes["layers_1"]["moe"]["up_proj"]["kernel"].shape == (
        32, 2048, 512)
    assert shapes["layers_1"]["moe"]["router"]["kernel"].shape == (2048, 512)
    assert shapes["wte"]["embedding"].shape == (19072, 2048)
    assert shapes["lm_head"]["kernel"].shape == (2048, 19072)
    for key, value in (("hidden_act", "gelu"), ("tie_word_embeddings", True),
                       ("norm_topk_prob", False), ("decoder_sparse_step", 2),
                       ("mlp_only_layers", [0]),
                       ("rope_scaling", {"type": "yarn"}),
                       ("use_sliding_window", True)):
        with pytest.raises(ValueError, match=key):
            family.build(dict(config, **{key: value}))
    # the tiny configuration is the same family at other numbers
    tiny = kit.tiny("qwen3_next")
    assert tiny.cfg == dataclasses.replace(
        CFG, vocab_size=512, remat=True, xent_chunks=4, embed_std=0.125)
    assert (tiny.rows, tiny.seq_len, tiny.vocab_draw) == (2, 64, 512)


def test_the_flop_and_byte_counts_are_the_issues() -> None:
    """``benchmark/qwen3_next_flops.py`` against ISSUE 63's hand count:
    462 MFLOP a token forward — delta-rule projections 44 %, the scan 2 %,
    attention projections 12 %, the 256-wide causal core 15 %, router +
    shared + held experts 11 %, the head 17 % —, the scan's bytes with q
    and k at the KEY heads, and the flash call counted as
    ``smallthinker_flops`` counts a full call."""
    config = _config()
    parts = qwen3_next_flops.train_flops_per_token(
        **qwen3_next_flops.config_dims(config))
    assert parts["total"] / 3 == pytest.approx(462e6, rel=2e-3)
    assert family.build(config).flops_per_token == parts["total"]
    sparse = parts["router"] + parts["routed_held"] + parts["shared"]
    for part, share in ((parts["gdn_proj"], 0.44), (parts["gdn_core"], 0.02),
                        (parts["gqa_proj"], 0.12), (parts["full_core"], 0.15),
                        (sparse, 0.11), (parts["head"], 0.17)):
        assert part / parts["total"] == pytest.approx(share, abs=0.006)
    assert parts["full_core"] == 3 * 2 * 16 * 512 * 8193 / 2
    assert parts["routed_held"] == 6 * 4 * (10 * 32 / 512) * 3 * 2048 * 512
    assert qwen3_next_flops.layer_types(config) == [LINEAR] * 3 + [FULL]
    assert qwen3_next_flops.layer_types(
        dict(config, num_hidden_layers=8)).count(FULL) == 2
    state = dict(n_value_heads=32, key_dim=128, value_dim=128)
    dims = dict(state, n_key_heads=16)
    assert qwen3_next_flops.gdn_flops_per_token("gdn_fwd", **state) == (
        32 * 7 * 128 * 128)
    assert qwen3_next_flops.gdn_flops_per_token("gdn_bwd", **state) == (
        2 * 32 * 7 * 128 * 128)
    # q and k at 16 heads, v and o at 32, 8 bytes of g and beta a state head
    fwd = 16 * 2 * 128 * 2 + 32 * 128 * 2 + 32 * 8 + 32 * 128 * 2
    assert qwen3_next_flops.gdn_bytes_per_token("gdn_fwd", **dims) == fwd
    assert qwen3_next_flops.gdn_bytes_per_token("gdn_bwd", **dims) == (
        2 * fwd - 32 * 128 * 2)
    assert qwen3_next_flops.flash_flops_per_call(
        4 * 16, 8192, 256, 256) == 64 * (8192 * 8193 / 2) * 2 * 512
    assert qwen3_next_flops.flash_bytes_per_call(
        "flash_fwd", 4 * 16, 8192, 256, 256) == 64 * 8192 * (4 * 256 * 2 + 4)


def test_the_optimizer_decays_matrices_alone_behind_a_warm_up() -> None:
    model = kit.tiny("qwen3_next")
    params = qwen3_next.init_params(model.cfg, jax.random.key(0))
    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    state = model.tx.init(params)
    updates, state = model.tx.update(zero, state, params)
    # a zero gradient moves what the weight decay reaches, and nothing else
    gdn, attn = updates["layers_0"]["gdn"], updates["layers_3"]["attn"]
    moe = updates["layers_0"]["moe"]
    for moved in (updates["lm_head"]["kernel"], updates["wte"]["embedding"],
                  gdn["qkvz_proj"]["kernel"], gdn["ba_proj"]["kernel"],
                  gdn["o_proj"]["kernel"], attn["q_proj"]["kernel"],
                  moe["down_proj"]["kernel"], moe["router"]["kernel"],
                  moe["shared"]["gate"]["kernel"]):
        assert np.any(moved)
    # the zero-centred norm weights are zero: nothing to decay either way;
    # w_V is one and takes none
    for still in (gdn["conv"]["kernel"], gdn["A_log"], gdn["dt_bias"],
                  gdn["o_norm"]["scale"], updates["ln_f"]["scale"],
                  updates["layers_0"]["norm_1"]["scale"],
                  attn["q_norm"]["scale"]):
        assert not np.any(still)


def test_three_ft_steps_equal_three_plain_steps_bit_for_bit() -> None:
    """The cell's ``plain_worker`` check at the small size; and the
    optimizer wrapper's routing gauges arrive on its sink without a wait
    (read at a later commit than the one that asked)."""
    with kit.ft_steps(kit.tiny("qwen3_next")) as run:
        biases = kit.bias_leaves(run.params)
        assert len(biases) == 4 and all(np.any(b) for b in biases)
        # a norm's zero-centred weight has moved off zero too
        assert np.any(run.params["layers_0"]["norm_1"]["scale"])
        seen = kit.routing_gauges(run)
        assert 0.0 < seen["moe_held_share"] < 1.0
        assert seen["moe_load_max_over_mean"] >= 1.0
        assert seen["moe_row_buffer_share"] == 1.0


def test_two_replica_groups_train_in_lockstep_and_one_heals() -> None:
    with kit.two_groups_one_healed(kit.tiny("qwen3_next")) as run:
        # the healed group's balance biases are the first's, to the bit
        for a, b in zip(kit.bias_leaves(run.first.state["params"]),
                        kit.bias_leaves(run.second.state["params"])):
            assert np.array_equal(np.asarray(a), np.asarray(b))
            assert np.any(np.asarray(a))


def test_the_loop_scenarios_built_one_step_program() -> None:
    kit.assert_built_once("qwen3_next")
