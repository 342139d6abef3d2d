"""LFM2-8B-A1B's blocks at test size on the CPU, float32, seeded weights: a
mixer kind per layer (a gated short convolution, or grouped-query attention
with an RMSNorm on every query and key head), leading dense layers, a router
whose bias chooses and whose sigmoid weighs, every expert held, and a head that
is the embedding table. Against the plain reference
(``perfbench/reference/lfm2_moe.py``), which knows nothing of the program."""

import dataclasses
import json
import os

import numpy as np
import pytest

import thunder_tpu
import thunder_tpu.torch as ttorch
from thunder_tpu.core import dtypes
from thunder_tpu.models import gpt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, T = 2, 64

with open(os.path.join(REPO, "perfbench", "configs", "lfm2-8b-a1b.json"), encoding="utf-8") as _f:
    _FILE = json.load(_f)
# The configuration file's keys at test widths: the stand-in's pattern (a dense
# conv layer, then attention, conv, conv with experts), 4 query heads of 8 on
# one key-value head, 8 experts of 16 and 2 a token.
KEYS = {**_FILE, **_FILE["stand_in"], "hidden_size": 32, "intermediate_size": 64, "moe_intermediate_size": 16,
        "vocab_size": 96, "max_position_embeddings": 64}
# The stand-in itself: head size 64 as published (``--rehearse``'s sizes).
REHEARSAL_KEYS = {**_FILE, **_FILE["stand_in"]}


def built(keys=KEYS, seed=5):
    """(the program's config, its parameters, the same numbers stacked for the
    reference, the tree's shapes), the router's bias drawn as the cell's job draws it."""
    import jax

    from perfbench import weights
    from perfbench.jobs import forward_conv_moe, gpt_model

    cfg = gpt_model.gpt_config(keys, rehearse=True)
    shapes = jax.eval_shape(lambda: gpt.init_params(cfg, dtype=dtypes.float32, device_init=True))
    return (cfg, forward_conv_moe.with_bias_drawn(weights.make_system_weights(shapes, seed)),
            forward_conv_moe.with_bias_drawn(weights.make_reference_weights(shapes, seed)), shapes)


def batch(seed=0, vocab=96):
    idx = np.random.RandomState(seed).randint(0, vocab, (B, T)).astype(np.int32)
    return idx, np.roll(idx, -1, axis=1).astype(np.int32)


def test_the_registry_lists_the_model_at_its_published_sizes():
    """Every published key of the configuration file is the registry's: the
    benchmark lays only its two cuts over the entry, and the layer pattern it
    runs is the first 14 of the published list."""
    from perfbench import manifest
    from perfbench.jobs import gpt_model

    cell = manifest.load_cell("lfm2-8b-a1b.fwd")
    cfg = gpt_model.gpt_config(manifest.published(cell))
    listed = gpt.name_to_config("LFM2-8B-A1B")
    assert cfg == dataclasses.replace(listed, n_layer=14, block_size=4096)
    assert (listed.n_layer, listed.n_embd, listed.n_head, listed.n_query_groups, listed.head_size) == (24, 2048, 32, 8, 64)
    assert (listed.n_expert, listed.held_experts, listed.n_expert_per_token, listed.moe_intermediate_size) == (32, 32, 4, 1792)
    assert (listed.intermediate_size, listed.padded_vocab_size, listed.block_size) == (7168, 65536, 128000)
    assert listed.layer_types == tuple(_FILE["layer_types"]) and len(listed.layer_types) == 24
    assert [listed.layer_types.count(kind) for kind in ("conv", "full_attention")] == [18, 6]
    mixers = [cfg.layer_mixer(i) for i in range(cfg.n_layer)]
    assert mixers == ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 3
    assert [cfg.layer_mlp_class(i) for i in (0, 1, 2, 13)] == ["LLaMAMLP", "LLaMAMLP", "SharedRoutedMoE", "SharedRoutedMoE"]
    # an entry without the pattern has attention everywhere, as before
    assert gpt.name_to_config("llama-2-7b").layer_types == () and gpt.name_to_config("llama-2-7b").layer_mixer(3) == "full_attention"


def test_forward_through_jit_agrees_with_the_reference():
    import jax.numpy as jnp

    from perfbench.reference import lfm2_moe

    cfg, params, stacked, _ = built()
    idx, _ = batch()
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    got, want = np.asarray(jfn(params, idx)), np.asarray(lfm2_moe.forward(stacked, jnp.asarray(idx), KEYS))
    assert got.shape == (B, T, 96)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-5
    names = [b.sym.name for b in thunder_tpu.last_traces(jfn)[-1].bound_symbols]
    assert "grouped_mm" in names  # the routed experts were dispatched, not computed for every token
    assert names.count("scaled_dot_product_attention") + names.count("sdpa_fwd_res") <= 1  # one attention layer of four


def test_the_tied_head_is_the_embedding_table_and_no_leaf_of_its_own():
    """One leaf holds the vocabulary: the head reads ``wte``. Each layer has the
    parameters of its own mixer and of no other."""
    cfg, params, stacked, _ = built()
    assert "lm_head_w" not in params and not any("lm_head" in kind for kind in stacked)
    idx, _ = batch()
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    base = np.asarray(jfn(params, idx))
    twice = {**params, "wte": params["wte"] * 2.0}
    assert np.abs(np.asarray(jfn(twice, idx)) - base).max() > 1e-3
    blocks = params["dense_blocks"] + params["moe_blocks"]
    assert [("conv" in b, "attn" in b) for b in blocks] == [(True, False), (False, True), (True, False), (True, False)]
    assert set(blocks[1]["attn"]) == {"qkv_w", "proj_w", "q_norm", "k_norm"}
    assert blocks[1]["attn"]["q_norm"]["weight"].shape == (8,)  # one weight of head_size for all heads
    assert blocks[1]["mlp"]["router_bias"].dtype == np.float32 and "router_bias" not in blocks[0]["mlp"]
    untied = dataclasses.replace(cfg, tie_embeddings=False)
    assert "lm_head_w" in gpt.init_params(untied, dtype=dtypes.float32, seed=0)


def test_loss_and_gradients_through_build_train_step_agree_with_the_reference():
    """``m = (1 - b1) g`` after one AdamW step from zero moments, every leaf,
    against ``jax.grad`` of the reference's loss. The table's gradient has the
    embedding's and the head's part; the bias's is none: it is a buffer that
    takes part in a choice."""
    import jax
    import jax.numpy as jnp

    from perfbench import weights
    from perfbench.reference import lfm2_moe
    from thunder_tpu import parallel

    cfg, params, stacked, shapes = built()
    idx, targets = batch()
    b1 = 0.9
    step, opt = parallel.build_train_step(cfg, params, idx, targets, b1=b1, donate=False)
    _, opt, loss = step(params, opt, idx, targets)
    want_loss, want = jax.value_and_grad(
        lambda w: lfm2_moe.loss(w, jnp.asarray(idx), jnp.asarray(targets), KEYS))(stacked)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    moments = jax.tree_util.tree_leaves(opt["m"])
    kinds = weights.leaf_kinds(shapes)
    assert len(moments) == len(kinds)
    for (kind, layer, _), m in zip(kinds, moments):
        g, w = np.asarray(m) / (1 - b1), np.asarray(want[kind] if layer is None else want[kind][layer])
        assert np.linalg.norm(g - w) <= 2e-4 * np.linalg.norm(w) + 1e-9, kind
        if kind.endswith("router_bias"):
            assert not g.any() and not w.any()
        else:
            assert np.linalg.norm(w) > 0, kind


# -----------------------------------------------------------------------------
# The gated short convolution
# -----------------------------------------------------------------------------


def _conv_inputs(t, c=8, k=3, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randn(2, t, 3 * c).astype(np.float32), rng.randn(c, k).astype(np.float32)


@pytest.mark.parametrize("t,k", [(13, 3), (1, 3), (2, 3), (37, 4), (5, 1)])
def test_short_conv_against_the_grouped_convolution_and_the_references_shifts(t, k):
    """The composite (a pad, K slices, multiplies and adds) against XLA's own
    depthwise convolution with left padding K - 1 and against the reference's
    shifted products, at lengths that are a multiple of nothing."""
    import jax
    import jax.numpy as jnp

    from perfbench.reference import lfm2_moe

    c = 8
    bcu, w = _conv_inputs(t, c, k)
    got = np.asarray(thunder_tpu.jit(ttorch.short_conv)(bcu, w))
    assert got.shape == (2, t, c)
    gate_in, gate_out, u = np.split(bcu, 3, axis=-1)
    conv = jax.lax.conv_general_dilated(
        jnp.asarray(gate_in * u), jnp.asarray(w.T[:, None, :]), window_strides=(1,), padding=[(k - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=c, precision="highest")
    np.testing.assert_allclose(got, gate_out * np.asarray(conv), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got, np.asarray(lfm2_moe.short_conv(jnp.asarray(bcu), jnp.asarray(w))), rtol=1e-5, atol=1e-6)


def test_short_conv_is_causal_and_reaches_back_two_positions():
    """A change at position t leaves every output before t alone, and moves the
    outputs at t, t + 1 and t + 2 and none after."""
    bcu, w = _conv_inputs(21)
    fn = thunder_tpu.jit(ttorch.short_conv)
    base = np.asarray(fn(bcu, w))
    at = 9
    moved = bcu.copy()
    moved[:, at, :] += 1.0
    changed = np.abs(np.asarray(fn(moved, w)) - base).max(axis=(0, 2)) > 0
    assert not changed[:at].any() and changed[at:at + 3].all() and not changed[at + 3:].any()


def test_short_conv_refuses_an_input_that_is_not_three_times_the_filters_channels():
    bcu, w = _conv_inputs(8)
    with pytest.raises(Exception, match="short_conv"):
        thunder_tpu.jit(ttorch.short_conv)(bcu[..., :-1], w)


# -----------------------------------------------------------------------------
# The router with a bias
# -----------------------------------------------------------------------------


def _route_before(x, router_w, top_k, n_group=1, topk_group=1, routed_scaling_factor=1.0):
    """``ttorch.moe_route`` as it stood before it took a bias (PR 27's lines)."""
    import thunder_tpu.clang as clang

    N, E = x.shape[0], router_w.shape[0]
    scores = ttorch.sigmoid(ttorch.linear(clang.maybe_convert_to_dtype(x, dtypes.float32),
                                          clang.maybe_convert_to_dtype(router_w, dtypes.float32)))
    choose_from = scores
    if n_group > 1:
        grouped = ttorch.reshape(scores, (N, n_group, E // n_group))
        best_two, _ = ttorch.topk(grouped, 2, -1)
        _, kept = ttorch.topk(ttorch.sum(best_two, -1), topk_group, -1)
        groups = clang.arange(0, n_group, 1, device=x.device, dtype=dtypes.int64)
        keep = ttorch.sum(ttorch.unsqueeze(kept, -1) == groups, 1) > 0
        keep = ttorch.expand(ttorch.unsqueeze(keep, -1), (N, n_group, E // n_group))
        choose_from = ttorch.reshape(ttorch.where(keep, grouped, clang.full_like(grouped, 0.0)), (N, E))
    _, top_i = ttorch.topk(choose_from, top_k, -1)
    top_w = ttorch.take_along_dim(scores, top_i, 1)
    return top_i, top_w / (ttorch.sum(top_w, -1, True) + 1e-20) * routed_scaling_factor


@pytest.mark.parametrize("groups", [(1, 1, 1.0), (4, 2, 2.5)], ids=["no-groups", "group-limited"])
def test_the_router_without_a_bias_is_what_it_was_bit_for_bit(groups):
    n_group, topk_group, scale = groups
    rng = np.random.RandomState(1)
    x, w = rng.randn(40, 32).astype(np.float32), rng.randn(16, 32).astype(np.float32)
    now = thunder_tpu.jit(lambda x, w: ttorch.moe_route(x, w, 4, n_group, topk_group, scale))
    before = thunder_tpu.jit(lambda x, w: _route_before(x, w, 4, n_group, topk_group, scale))
    for got, want in zip(now(x, w), before(x, w)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    lines = lambda fn: [b.sym.name for b in thunder_tpu.last_traces(fn)[-1].bound_symbols]
    assert lines(now) == lines(before)  # and the same program, symbol for symbol


def _sorted_by_expert(top_i, top_w):
    order = np.argsort(np.asarray(top_i), -1)
    return np.take_along_axis(np.asarray(top_i), order, -1), np.take_along_axis(np.asarray(top_w), order, -1)


def test_the_routers_bias_takes_part_in_the_choice_and_not_in_the_weight():
    import jax.numpy as jnp

    from perfbench.reference import lfm2_moe

    rng = np.random.RandomState(2)
    x, w = rng.randn(200, 32).astype(np.float32), rng.randn(8, 32).astype(np.float32) * 0.2
    bias = (rng.randn(8) * 0.3).astype(np.float32)
    hp = lfm2_moe.hyper(KEYS)
    got_i, got_w = _sorted_by_expert(*thunder_tpu.jit(
        lambda x, w, b: ttorch.moe_route(x, w, 2, 1, 1, 1.0, b, 1e-6))(x, w, bias))
    want_i, want_w, margin = lfm2_moe.route(jnp.asarray(x), jnp.asarray(w), jnp.asarray(bias), hp)
    want_i, want_w = _sorted_by_expert(want_i, want_w)
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_allclose(got_w, want_w, rtol=1e-5)
    assert (np.asarray(margin) > 0).all()
    # the weights are the unbiased scores over their sum plus 1e-6 ...
    s = 1.0 / (1.0 + np.exp(-(x @ w.T).astype(np.float64)))
    chosen = np.take_along_axis(s, got_i, 1)
    np.testing.assert_allclose(got_w, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-5)
    # ... and the choice is another one than without the bias for some tokens, not for all
    plain_i, _ = _sorted_by_expert(*thunder_tpu.jit(lambda x, w: ttorch.moe_route(x, w, 2))(x, w))
    differs = (plain_i != got_i).any(-1)
    assert 0.1 < differs.mean() < 0.9
    best_biased = np.sort(np.argsort(-(s + bias), -1)[:, :2], -1)
    np.testing.assert_array_equal(got_i, best_biased)


def test_router_counts_are_the_rows_each_expert_gets_and_the_choices_the_bias_changed():
    cfg, params, _, _ = built()
    idx, _ = batch()
    rows, changed = (np.asarray(a) for a in thunder_tpu.jit(lambda p, i: gpt.router_counts(p, i, cfg))(params, idx))
    assert rows.shape == (3, 8) and changed.shape == (3,)  # three expert layers, eight experts, all held
    assert (rows.sum(-1) == B * T * 2).all()  # every (token, choice) pair lands here: 2 a token
    assert ((0 < changed) & (changed < B * T * 2)).all()
    np.testing.assert_array_equal(np.asarray(thunder_tpu.jit(lambda p, i: gpt.routed_rows(p, i, cfg))(params, idx)), rows)
    # with the bias at zero the router without it chooses the same, pair for pair
    zeroed = {**params, "moe_blocks": [{**b, "mlp": {**b["mlp"], "router_bias": b["mlp"]["router_bias"] * 0.0}}
                                       for b in params["moe_blocks"]]}
    assert not np.asarray(thunder_tpu.jit(lambda p, i: gpt.router_counts(p, i, cfg))(zeroed, idx)[1]).any()
    # a model without the bias has no such count
    axk1 = gpt.name_to_config("axk1-tiny")
    plain = gpt.init_params(axk1, dtype=dtypes.float32, seed=0)
    assert thunder_tpu.jit(lambda p, i: gpt.router_counts(p, i, axk1))(plain, idx % 96)[1] is None


# -----------------------------------------------------------------------------
# The claimed dispatch with every expert held
# -----------------------------------------------------------------------------


def masked_dense(x, top_i, top_w, gate, up, down):
    """Every expert for every token, masked by the selection."""
    out = np.zeros_like(x)
    for e in range(gate.shape[0]):
        h = x @ gate[e]
        y = (h / (1 + np.exp(-h)) * (x @ up[e])) @ down[e]
        out += np.where(top_i == e, top_w, 0.0).sum(-1)[:, None] * y
    return out


@pytest.mark.parametrize("load", ["uneven", "one-expert-idle-one-with-a-quarter"])
def test_the_claimed_one_buffer_dispatch_at_32_experts_and_4_a_token(load):
    """Claimed by the pallas executor (megablox gmm, interpreted here) with
    every expert held: one buffer of k * N rows, no branch, and the masked
    dense form's answer under uneven load, an idle expert among the 32."""
    import jax
    import jax.numpy as jnp

    n, c, h, k, total = 256, 128, 128, 4, 32
    rng = np.random.RandomState(4)
    bf16 = lambda a: jnp.asarray(a, jnp.bfloat16)
    x, gate, up = bf16(rng.randn(n, c)), bf16(rng.randn(total, c, h) * 0.1), bf16(rng.randn(total, c, h) * 0.1)
    down = bf16(rng.randn(total, h, c) * 0.1)
    p = np.arange(total, 0, -1, dtype=np.float64) ** 2
    if load != "uneven":
        p[7] = 0.0
    top_i = np.stack([rng.choice(total, size=k, replace=False, p=p / p.sum()) for _ in range(n)]).astype(np.int64)
    if load != "uneven":
        top_i[:, 0] = np.where(top_i[:, 1:].min(-1) > 0, 0, top_i[:, 0])  # expert 0 in nearly every token
    top_w = rng.rand(n, k).astype(np.float32)
    fn = thunder_tpu.jit(lambda *a: ttorch.moe_experts(*a, 0, total))
    got = np.asarray(fn(x, top_i, top_w, gate, up, down).astype(jnp.float32))
    run = thunder_tpu.last_traces(fn)[-1]
    owners = {b.sym.name: b.sym.executor.name for b in run.bound_symbols if b.sym.executor is not None}
    assert owners.get("moe_experts") == "pallas"
    steps = [eqn.primitive.name for eqn in jax.make_jaxpr(run.python_callable())(x, top_i, top_w, gate, up, down).eqns]
    assert steps.count("cond") == 0
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    want = masked_dense(f32(x), top_i, top_w, f32(gate), f32(up), f32(down))
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 2e-2
    counts = np.bincount(top_i.reshape(-1), minlength=total)
    assert counts.max() > 2 * counts.mean() and (load == "uneven" or counts[7] == 0)


@pytest.mark.parametrize("width,tile", [(1792, 896), (2048, 1024), (7168, 1024), (128, 128), (1536, 768), (2304, 768),
                                        (1000, 1000), (1100, 1024), (1408, 1024), (2560, 640)])
def test_the_grouped_matmuls_tiles_divide_the_width_where_a_multiple_of_the_lanes_does(width, tile):
    """A tile of 1024 over experts 1792 wide leaves the second tile a quarter
    empty and the down projection's contraction masked; the tile is the largest
    multiple of the 128 lanes, up to 1024, that divides the width."""
    from thunder_tpu.executors import pallasex

    assert pallasex._gmm_tile(width) == tile


# -----------------------------------------------------------------------------
# Mutations: a missing term is no rounding
# -----------------------------------------------------------------------------


def _bias_left_out_of_the_choice(monkeypatch, cfg, params):
    real = ttorch.moe_route
    monkeypatch.setattr(ttorch, "moe_route", lambda x, w, k, g, tg, scale, bias, eps, *how: real(x, w, k, g, tg, scale, None, eps, *how))
    return cfg, params


def _bias_added_into_the_weights(monkeypatch, cfg, params):
    def route(x, w, k, g, tg, scale, bias, eps, *how):  # how: the score function and normalising, this model's the defaults
        biased = ttorch.sigmoid(ttorch.linear(x.to(dtypes.float32), w.to(dtypes.float32))) + bias
        top_w, top_i = ttorch.topk(biased, k, -1)
        return top_i, top_w / (ttorch.sum(top_w, -1, True) + eps) * scale

    monkeypatch.setattr(ttorch, "moe_route", route)
    return cfg, params


def _with_conv_leaves(params, change):
    mixed = lambda b: {**b, "conv": {**b["conv"], **change(b["conv"])}} if "conv" in b else b
    return {**params, "dense_blocks": [mixed(b) for b in params["dense_blocks"]],
            "moe_blocks": [mixed(b) for b in params["moe_blocks"]]}


def _taps_reversed(monkeypatch, cfg, params):
    return cfg, _with_conv_leaves(params, lambda conv: {"conv_w": conv["conv_w"][:, ::-1]})


def _b_and_c_exchanged(monkeypatch, cfg, params):
    def swapped(conv):  # in_proj's first two thirds of rows change places
        c = conv["in_proj_w"].shape[1]
        w = conv["in_proj_w"]
        return {"in_proj_w": np.concatenate([w[c:2 * c], w[:c], w[2 * c:]], 0)}

    return cfg, _with_conv_leaves(params, swapped)


def _qk_norm_skipped(monkeypatch, cfg, params):
    return dataclasses.replace(cfg, qk_norm=False), params


def _norm_after_the_rope(monkeypatch, cfg, params):
    """The heads' norms are held back and applied to what the rope returns."""
    real_norm, real_rope, held = ttorch.rms_norm, gpt._apply_rope, []

    def norm(x, shape, weight=None, eps=None):
        if tuple(shape) != (cfg.head_size,):
            return real_norm(x, shape, weight, eps=eps)
        held.append((weight, eps))
        return x

    def rope(x, cos, sin, config):
        weight, eps = held.pop(0)
        return real_norm(real_rope(x, cos, sin, config), (cfg.head_size,), weight, eps=eps)

    monkeypatch.setattr(ttorch, "rms_norm", norm)
    monkeypatch.setattr(gpt, "_apply_rope", rope)
    return cfg, params


def _one_expert_skipped(monkeypatch, cfg, params):
    real = ttorch.moe_experts

    def skipping(x, top_i, top_w, *rest):
        return real(x, top_i, ttorch.where(top_i == 1, 0.0, top_w), *rest)

    monkeypatch.setattr(ttorch, "moe_experts", skipping)
    return cfg, params


def _normaliser_without_its_epsilon(monkeypatch, cfg, params):
    return dataclasses.replace(cfg, router_norm_eps=1e-20), params


MUTATIONS = {"bias-left-out-of-the-choice": _bias_left_out_of_the_choice,
             "bias-added-into-the-weights": _bias_added_into_the_weights,
             "taps-reversed": _taps_reversed, "b-and-c-exchanged": _b_and_c_exchanged,
             "qk-norm-skipped": _qk_norm_skipped, "norm-after-the-rope": _norm_after_the_rope,
             "one-expert-skipped": _one_expert_skipped,
             "normaliser-without-its-1e-6": _normaliser_without_its_epsilon}
# The weights' normaliser is the sum of two to four sigmoid scores, of the order
# of 1: the 1e-6 beside it moves a weight by a millionth, under float32's own
# rounding through the layers. Dropping it is allowed to pass, and does.
PASSES = {"normaliser-without-its-1e-6"}
# Weights of a size at which a block's output is of the order of its input (at
# N(0, 0.02) and a width of 256 the residual stream is the embedding and no
# block shows), so that what a mutation does to a layer reaches the logits as
# it does at the published widths.
MUTATION_STD = 0.08


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_a_mutated_system_fails_the_cells_comparison_at_rehearsal_size(monkeypatch, name):
    """Each departure from the published mathematics fails the comparison the
    cell's check makes (``perfbench/checks_conv_moe.py``: the block's error, or
    the share of the rows whose routing is settled that are off by more than a
    row's limit) at the stand-in's sizes, where the unmutated system is within a
    thousandth of the limit and has no row off."""
    import jax.numpy as jnp

    from perfbench import checks_conv_moe, weights
    from perfbench.reference import lfm2_moe

    monkeypatch.setattr(weights, "STD", MUTATION_STD)
    cfg, params, stacked, _ = built(REHEARSAL_KEYS)
    idx, _ = batch(vocab=REHEARSAL_KEYS["vocab_size"])
    want, margin = (np.asarray(out) for out in lfm2_moe.forward_and_margin(stacked, jnp.asarray(idx), REHEARSAL_KEYS))
    clean = np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))(params, idx))
    sound = checks_conv_moe.compare_logits(clean, want, margin)
    assert sound["ok"] and sound["logits_rel_l2"] < 1e-3 * sound["logits_rtol"] and sound["settled_rows_over"] == 0
    assert sound["settled_rows"] > B * T // 4
    mutated, changed = MUTATIONS[name](monkeypatch, cfg, params)
    got = np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, mutated))(changed, idx))
    verdict = checks_conv_moe.compare_logits(got, want, margin)
    assert verdict["ok"] == (name in PASSES), verdict
    assert np.abs(got - clean).max() > 0  # the mutation did reach the logits


def test_the_models_regions_are_named_in_the_generated_program_and_in_the_hlo():
    """``conv`` around each gated short convolution with its two projections,
    ``attn.qk_norm`` around the heads' norms, and the expert layers' regions:
    one ``with`` a region a layer in the generated program, in the layers' order."""
    import jax

    cfg, params, _, _ = built()
    idx, _ = batch()
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    jfn(params, idx)
    run = thunder_tpu.last_traces(jfn)[-1]
    opened = [line.strip() for line in run.python().splitlines() if line.strip().startswith("with __region(")]
    experts = ["with __region('moe.route'):", "with __region('moe.experts'):"]
    conv = ["with __region('conv'):"]
    assert opened == conv + ["with __region('attn.qk_norm'):", "with __region('attn.full'):"] + experts + (conv + experts) * 2
    hlo = jax.jit(run.python_callable()).lower(*jax.tree_util.tree_leaves((params, idx))).as_text(debug_info=True)
    assert all(f"/{name}/" in hlo for name in ("conv", "attn.qk_norm", "moe.route", "moe.experts"))
