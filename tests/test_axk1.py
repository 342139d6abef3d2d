"""A.X-K1's blocks at test size on the CPU, float32, seeded weights: latent
attention with query-key heads wider than value heads under YaRN, a leading
dense layer, sigmoid group-limited routing, a shared expert, and routed experts
of which a share is held. Against the plain reference
(``perfbench/reference/axk1.py``), which knows nothing of the program."""

import dataclasses
import re
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

with open(os.path.join(REPO, "perfbench", "configs", "a.x-k1.json"), encoding="utf-8") as _f:
    _FILE = json.load(_f)
# The configuration file's keys at test widths: heads of 16 + 8 and 16, 4 groups
# of 4 experts of which 2 stay, 4 experts a token, experts 4 to 7 held here.
KEYS = {**_FILE, **_FILE["stand_in"], "hidden_size": 32, "intermediate_size": 64, "moe_intermediate_size": 16,
        "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "vocab_size": 96}


def built(keys=KEYS, seed=5):
    """(the program's config, its parameters, the same numbers stacked for the reference)."""
    import jax

    from perfbench import weights
    from perfbench.jobs import gpt_model

    cfg = gpt_model.gpt_config(keys, rehearse=True)
    shapes = jax.eval_shape(lambda: gpt.init_params(cfg, dtype=dtypes.float32, device_init=True))
    return cfg, weights.make_system_weights(shapes, seed), weights.make_reference_weights(shapes, seed), shapes


def batch(seed=0, vocab=96):
    idx = np.random.RandomState(seed).randint(0, vocab, (B, T)).astype(np.int32)
    return idx, np.roll(idx, -1, axis=1).astype(np.int32)


def reference_logits(stacked, idx, keys=KEYS):
    import jax.numpy as jnp

    from perfbench.reference import axk1

    return np.asarray(axk1.forward(stacked, jnp.asarray(idx), keys))


def test_the_registry_lists_the_model_at_its_published_sizes():
    """Every published width of the configuration file is the registry's: the
    benchmark lays only its cuts over the entry, and refuses a width that differs."""
    from perfbench import manifest
    from perfbench.jobs import gpt_model

    cell = manifest.load_cell("a.x-k1.fwd")
    cfg = gpt_model.gpt_config(manifest.published(cell))
    listed = gpt.name_to_config("A.X-K1")
    cut = {"n_layer": 7, "experts_held": 12, "padded_vocab_size": 20480, "block_size": 4096}
    assert cfg == dataclasses.replace(listed, **cut)
    assert (listed.n_layer, listed.n_expert, listed.padded_vocab_size, listed.block_size) == (61, 192, 163840, 131072)
    assert (listed.qk_head_dim, listed.v_head_dim, listed.held_experts) == (192, 128, 192)
    assert listed.softmax_scale == pytest.approx(192 ** -0.5 * 1.3466 ** 2, rel=1e-4)
    assert [listed.layer_mlp_class(i) for i in (0, 1, 60)] == ["LLaMAMLP", "SharedRoutedMoE", "SharedRoutedMoE"]


def test_forward_through_jit_agrees_with_the_reference():
    cfg, params, stacked, _ = built()
    idx, _ = batch()
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    got, want = np.asarray(jfn(params, idx)), reference_logits(stacked, idx)
    assert got.shape == (B, T, 96)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-5
    names = {b.sym.name for b in thunder_tpu.last_traces(jfn)[-1].bound_symbols}
    assert "grouped_mm" in names  # the routed experts were dispatched, not computed for every token


def test_no_phantom_layer_of_experts_is_drawn():
    """The dense and the expert blocks are lists of their own, so the stacked
    draw has one dense layer without experts and two expert layers without a
    dense MLP."""
    _, _, stacked, _ = built()
    assert stacked["dense_blocks/*/mlp/fc_1_w"].shape == (1, 64, 32)
    assert stacked["moe_blocks/*/mlp/experts_gate"].shape == (2, 4, 32, 16)
    assert not any(k.startswith("dense_blocks/*/mlp/experts") or k.startswith("moe_blocks/*/mlp/fc_") for k in stacked)


def test_loss_and_gradients_through_build_train_step_agree_with_the_reference():
    """``m = (1 - b1) g`` after one AdamW step from zero moments, every leaf,
    against ``jax.grad`` of the reference's loss."""
    import jax
    import jax.numpy as jnp

    from perfbench import weights
    from perfbench.reference import axk1
    from thunder_tpu import parallel

    cfg, params, stacked, shapes = built()
    idx, targets = batch()
    b1 = 0.9
    step, opt = parallel.build_train_step(cfg, params, idx, targets, b1=b1, donate=False)
    _, opt, loss = step(params, opt, idx, targets)
    want_loss, want = jax.value_and_grad(lambda w: axk1.loss(w, jnp.asarray(idx), jnp.asarray(targets), KEYS))(stacked)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    moments = jax.tree_util.tree_leaves(opt["m"])
    kinds = weights.leaf_kinds(shapes)
    assert len(moments) == len(kinds)
    for (kind, layer, _), m in zip(kinds, moments):
        g, w = np.asarray(m) / (1 - b1), np.asarray(want[kind] if layer is None else want[kind][layer])
        assert np.linalg.norm(g - w) <= 2e-4 * np.linalg.norm(w) + 1e-9, kind


# -----------------------------------------------------------------------------
# The routed-expert operation
# -----------------------------------------------------------------------------


def masked_dense(x, top_i, top_w, gate, up, down, offset):
    """Every held expert for every token, masked by the selection."""
    out = np.zeros_like(x)
    for e in range(gate.shape[0]):
        h = x @ gate[e]
        y = (h / (1 + np.exp(-h)) * (x @ up[e])) @ down[e]
        out += np.where(top_i == e + offset, top_w, 0.0).sum(-1)[:, None] * y
    return out


ROUTED_CASES = {
    # held, offset, k, total: a share in the middle; a share of fewer experts than a
    # token chooses (the buffer is held * N rows); every expert held (mixtral's use)
    "4-of-16-from-4": (4, 4, 4, 16), "2-of-16-k-4": (2, 6, 4, 16), "all-8-k-2": (8, 0, 2, 8),
    "1-of-16": (1, 15, 4, 16),
}


@pytest.mark.parametrize("case", sorted(ROUTED_CASES))
def test_routed_experts_against_the_masked_dense_form_under_uneven_routing(case):
    held, offset, k, total = ROUTED_CASES[case]
    rng = np.random.RandomState(3)
    n, c, h = 48, 16, 24
    x = rng.randn(n, c).astype(np.float32)
    gate, up = (rng.randn(held, c, h).astype(np.float32) * 0.3 for _ in range(2))
    down = rng.randn(held, h, c).astype(np.float32) * 0.3
    # Uneven by construction: most tokens prefer the low experts.
    top_i = np.stack([rng.choice(total, size=k, replace=False, p=np.arange(total, 0, -1) / (total * (total + 1) / 2))
                      for _ in range(n)]).astype(np.int64)
    lands_here = min(k, held)
    top_i[0, :lands_here] = offset + np.arange(lands_here)  # a token whose choices land here, as many as can
    others = [e for e in range(total) if not offset <= e < offset + held]
    if len(others) >= k:
        top_i[1] = others[:k]  # and one with none here
    top_w = rng.rand(n, k).astype(np.float32)
    fn = thunder_tpu.jit(lambda *a: ttorch.moe_experts(*a, offset))
    got = np.asarray(fn(x, top_i, top_w, gate, up, down))
    want = masked_dense(x, top_i, top_w, gate, up, down, offset)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    if len(others) >= k:
        assert not got[1].any() and np.abs(got[0]).max() > 0


def test_routed_experts_compute_the_rows_routed_here_and_drop_none():
    """The grouped matmuls' group sizes are the rows routed to each held expert,
    and they are all computed even when every token chooses one expert."""
    n, c, h, held = 32, 8, 8, 3
    rng = np.random.RandomState(0)
    x = rng.randn(n, c).astype(np.float32)
    gate, up = (rng.randn(held, c, h).astype(np.float32) for _ in range(2))
    down = rng.randn(held, h, c).astype(np.float32)
    top_i = np.tile(np.array([[1, 7]], np.int64), (n, 1))  # every token to expert 1 (held) and 7 (not)
    top_w = np.ones((n, 2), np.float32)
    got = np.asarray(thunder_tpu.jit(lambda *a: ttorch.moe_experts(*a, 0))(x, top_i, top_w, gate, up, down))
    np.testing.assert_allclose(got, masked_dense(x, top_i, top_w, gate, up, down, 0), rtol=2e-5, atol=2e-5)


def test_router_is_the_published_group_limited_top_k():
    from perfbench.reference import axk1

    rng = np.random.RandomState(1)
    x, w = rng.randn(40, 32).astype(np.float32), rng.randn(16, 32).astype(np.float32)
    top_i, top_w = thunder_tpu.jit(lambda x, w: ttorch.moe_route(x, w, 4, 4, 2, 2.5))(x, w)
    want_i, want_w, margin = axk1.route(x, w, axk1.hyper(KEYS))
    order = np.argsort(np.asarray(top_i), -1)
    want_order = np.argsort(np.asarray(want_i), -1)
    np.testing.assert_array_equal(np.take_along_axis(np.asarray(top_i), order, -1),
                                  np.take_along_axis(np.asarray(want_i), want_order, -1))
    np.testing.assert_allclose(np.take_along_axis(np.asarray(top_w), order, -1),
                               np.take_along_axis(np.asarray(want_w), want_order, -1), rtol=1e-5)
    groups = np.asarray(top_i) // 4
    assert all(len(set(row)) <= 2 for row in groups)  # no token reaches beyond its two best groups
    np.testing.assert_allclose(np.asarray(top_w).sum(-1), 2.5, rtol=1e-5)
    del margin


@pytest.mark.parametrize("held", ["a-share-held", "every-expert-held"])
def test_the_references_margin_is_how_far_the_scores_may_move_before_a_held_choice_changes(held):
    """Moving every score by less than a quarter of a token's margin (a group's
    score is the sum of two) changes no token's choice among the experts held
    here; moving them by more changes some."""
    import jax

    from perfbench.reference import axk1

    hp = axk1.hyper(KEYS if held == "a-share-held" else {**KEYS, "n_routed_experts": 16, "expert_offset": 0})
    here = lambda chosen: [sorted(e for e in row if hp["offset"] <= e < hp["offset"] + hp["held"])
                           for row in np.asarray(chosen).tolist()]
    rng = np.random.RandomState(1)
    x, w = rng.randn(200, 32).astype(np.float32), rng.randn(16, 32).astype(np.float32) * 0.1
    want_i, _, margin = axk1.route(x, w, hp)
    margin = np.asarray(margin)
    assert (margin > 0).all() and np.median(margin) < 0.1
    s = np.asarray(jax.nn.sigmoid(x @ w.T), np.float64)
    as_input = lambda scores: (np.log(scores / (1 - scores)).astype(np.float32), np.eye(16, dtype=np.float32))
    near = s + rng.uniform(-0.24, 0.24, s.shape) * margin[:, None]
    assert here(axk1.route(*as_input(near), hp)[0]) == here(want_i)
    far = np.clip(s + rng.uniform(-3, 3, s.shape) * margin[:, None], 1e-6, 1 - 1e-6)
    assert here(axk1.route(*as_input(far), hp)[0]) != here(want_i)


@pytest.mark.parametrize("shares", [1, 4, 16])
def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer(shares):
    """The share ties to the model: over the chips that share a layer, the
    routed parts each computes, with the shared expert counted once, add up to
    what the uncut reference gives for the layer."""
    import jax

    from perfbench.reference import axk1

    total, c, h = 16, 32, 16
    held = total // shares
    rng = np.random.RandomState(2)
    x = rng.randn(B, T, c).astype(np.float32)
    whole = {"mlp/router_w": rng.randn(total, c).astype(np.float32) * 0.2,
             "mlp/experts_gate": rng.randn(total, c, h).astype(np.float32) * 0.2,
             "mlp/experts_up": rng.randn(total, c, h).astype(np.float32) * 0.2,
             "mlp/experts_down": rng.randn(total, h, c).astype(np.float32) * 0.2,
             "mlp/shared/fc_1_w": rng.randn(h, c).astype(np.float32) * 0.2,
             "mlp/shared/fc_2_w": rng.randn(h, c).astype(np.float32) * 0.2,
             "mlp/shared/proj_w": rng.randn(c, h).astype(np.float32) * 0.2}
    uncut = np.asarray(axk1.expert_layer(x, whole, {**KEYS, "n_routed_experts": total, "expert_offset": 0}))

    base = dataclasses.replace(gpt.name_to_config("axk1-tiny"), experts_held=held)
    p = {"router_w": whole["mlp/router_w"],
         "shared": {k: whole["mlp/shared/" + k] for k in ("fc_1_w", "fc_2_w", "proj_w")}}
    summed = np.zeros_like(uncut)
    for chip in range(shares):
        cfg = dataclasses.replace(base, expert_offset=chip * held, n_shared_experts=1 if chip == 0 else 0)
        mine = {**p, **{"experts_" + k: whole["mlp/experts_" + k][chip * held:(chip + 1) * held]
                        for k in ("gate", "up", "down")}}
        summed += np.asarray(thunder_tpu.jit(lambda x, q, cfg=cfg: gpt._shared_routed_moe(x, q, cfg))(x, mine))
    np.testing.assert_allclose(summed, uncut, rtol=2e-4, atol=2e-5)
    del jax


def test_routed_rows_counts_what_the_router_sends_here():
    cfg, params, _, _ = built()
    idx, _ = batch()
    rows = np.asarray(thunder_tpu.jit(lambda p, i: gpt.routed_rows(p, i, cfg))(params, idx))
    assert rows.shape == (2, 4)  # two expert layers, four held experts
    # 4 a token over 16 experts in 4 groups of which 2 stay: a quarter lands here on average
    assert 0 < rows.sum() <= 2 * B * T * 4 and rows.sum() / (2 * B * T) == pytest.approx(1.0, abs=0.5)


# -----------------------------------------------------------------------------
# Mutations: a missing term is no rounding
# -----------------------------------------------------------------------------


def _no_shared_expert(monkeypatch, cfg):
    return dataclasses.replace(cfg, n_shared_experts=0)


def _no_routed_scale(monkeypatch, cfg):
    return dataclasses.replace(cfg, routed_scaling_factor=1.0)


def _no_group_limit(monkeypatch, cfg):
    return dataclasses.replace(cfg, n_expert_groups=1, n_limited_groups=1)


def _softmax_for_sigmoid(monkeypatch, cfg):
    monkeypatch.setattr(ttorch, "sigmoid", lambda a: ttorch.softmax(a, -1))
    return cfg


def _scale_without_m_squared(monkeypatch, cfg):
    monkeypatch.setattr(gpt, "_yarn_mscale", lambda factor, mscale: 1.0)
    return cfg


def _rope_on_k_nope(monkeypatch, cfg):
    real_rope, real_sdpa, seen = ttorch.apply_rope, ttorch.scaled_dot_product_attention, {}

    def rope(x, cos, sin):
        seen["tables"] = (cos, sin)
        return real_rope(x, cos, sin)

    def sdpa(q, k, v, **kw):
        cos, sin = seen["tables"]
        dr = cos.shape[-1]
        k = ttorch.cat([k[..., :dr], real_rope(k[..., dr:2 * dr], cos, sin), k[..., 2 * dr:]], -1)
        return real_sdpa(q, k, v, **kw)

    monkeypatch.setattr(ttorch, "apply_rope", rope)
    monkeypatch.setattr(ttorch, "scaled_dot_product_attention", sdpa)
    return cfg


def _one_held_expert_skipped(monkeypatch, cfg):
    real = ttorch.moe_experts

    def skipping(x, top_i, top_w, *rest):
        return real(x, top_i, ttorch.where(top_i == cfg.expert_offset + 1, 0.0, top_w), *rest)

    monkeypatch.setattr(ttorch, "moe_experts", skipping)
    return cfg


def _bf16_router(monkeypatch, cfg):
    """What a router left in the model's bf16 computes: rounded inputs, scores
    rounded before and after the sigmoid (the MXU accumulates in float32 anyway)."""
    real_sigmoid, real_route, bf16 = ttorch.sigmoid, ttorch.moe_route, dtypes.bfloat16
    monkeypatch.setattr(ttorch, "sigmoid", lambda a: real_sigmoid(a.to(bf16)).to(bf16).to(dtypes.float32))
    monkeypatch.setattr(ttorch, "moe_route", lambda x, w, *rest: real_route(x.to(bf16), w.to(bf16), *rest))
    return cfg


MUTATIONS = {"no-shared-expert": _no_shared_expert, "no-2.5": _no_routed_scale, "no-group-limit": _no_group_limit,
             "softmax-for-sigmoid": _softmax_for_sigmoid, "scale-without-m-squared": _scale_without_m_squared,
             "rope-on-k-nope": _rope_on_k_nope, "one-held-expert-skipped": _one_held_expert_skipped,
             "bf16-router": _bf16_router}


# The mutations' model: five layers, and weights of a size at which a block's
# output is of the order of its input (at N(0, 0.02) and a width of 32 the
# residual stream is the embedding and no block shows), so that what a mutation
# does to a layer reaches the logits as it does at real widths. With every
# expert held, and with the share the cell has in kind: 4 of 16 from the 4th.
MUTATION_KEYS = {"every-expert-held": {**KEYS, "num_hidden_layers": 5, "n_routed_experts": 16, "expert_offset": 0},
                 "a-share-held": {**KEYS, "num_hidden_layers": 5}}


# A router left in bf16 flips choices that were nearly tied, which is what the system's bf16 hidden
# states do anyway and what the settled rows leave out: where only a share of the experts is held
# it reads as a few more flips (0.056 of the block, under the limit), and only with every expert
# held, where every flip shows, does it fail.
MUTATION_CASES = [(name, held) for name in sorted(MUTATIONS) for held in sorted(MUTATION_KEYS)
                  if (name, held) != ("bf16-router", "a-share-held")]


@pytest.mark.parametrize("name,held", MUTATION_CASES, ids=["-".join(c) for c in MUTATION_CASES])
def test_a_mutated_system_fails_the_cells_comparison(monkeypatch, name, held):
    """Each departure from the published mathematics fails the comparison the
    cell's check makes (the whole block's error, or the share of the rows whose
    routing is settled that are off by more than a row's limit), where the
    unmutated system is within a thousandth of the limit and has no row off."""
    import jax.numpy as jnp

    from perfbench import checks_mla_moe, weights
    from perfbench.reference import axk1

    monkeypatch.setattr(weights, "STD", 0.15)
    keys = MUTATION_KEYS[held]
    cfg, params, stacked, _ = built(keys)
    idx, _ = batch()
    want, margin = (np.asarray(out) for out in axk1.forward_and_margin(stacked, jnp.asarray(idx), keys))
    clean = np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))(params, idx))
    sound = checks_mla_moe.compare_logits(clean, want, margin)
    assert sound["ok"] and sound["logits_rel_l2"] < 1e-3 * sound["logits_rtol"] and sound["settled_rows_over"] == 0
    assert sound["settled_rows"] > B * T // 4
    mutated = MUTATIONS[name](monkeypatch, cfg)
    got = np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, mutated))(params, idx))
    verdict = checks_mla_moe.compare_logits(got, want, margin)
    assert not verdict["ok"], verdict


def test_the_cache_entry_does_not_keep_the_first_calls_weights_alive():
    """The prologue lays the unpacking out from the first call's containers and
    lets them go: the check of a cell that fills the chip frees the system's
    weights before it draws the reference's."""
    import gc
    import weakref

    import jax.numpy as jnp

    params = {"w": [jnp.ones((8, 8)), {"b": jnp.ones((8,))}]}
    alive = [weakref.ref(params["w"][0]), weakref.ref(params["w"][1]["b"])]
    jfn = thunder_tpu.jit(lambda p, x: ttorch.linear(x, p["w"][0], p["w"][1]["b"]))
    out = np.asarray(jfn(params, jnp.ones((2, 8))))
    assert out.shape == (2, 8) and thunder_tpu.cache_misses(jfn) == 1
    del params
    gc.collect()
    assert all(ref() is None for ref in alive)
    again = {"w": [jnp.ones((8, 8)), {"b": jnp.ones((8,))}]}
    np.testing.assert_array_equal(np.asarray(jfn(again, jnp.ones((2, 8)))), out)  # and the function still serves


@pytest.mark.parametrize("load", ["fits-the-short-buffer", "needs-the-worst-case-buffer", "every-expert-held"])
def test_the_claimed_routed_experts_take_the_short_buffer_or_the_worst_case_and_drop_nothing(load):
    """Claimed by the pallas executor (megablox gmm, interpreted here), the
    dispatch runs on a buffer of twice the rows an even router sends here when
    the rows routed here fit it, on the worst case when they do not: the same
    answer as the masked dense form either way. Where every expert is held the
    short buffer would be no shorter, and the program has one buffer and no branch."""
    import jax
    import jax.numpy as jnp

    n, c, h, k = 256, 128, 128, 4
    held, offset, total = (4, 0, 4) if load == "every-expert-held" else (4, 4, 16)
    rng = np.random.RandomState(4)
    bf16 = lambda a: jnp.asarray(a, jnp.bfloat16)
    x, gate, up = bf16(rng.randn(n, c)), bf16(rng.randn(held, c, h) * 0.1), bf16(rng.randn(held, c, h) * 0.1)
    down = bf16(rng.randn(held, h, c) * 0.1)
    if load == "fits-the-short-buffer":  # a quarter of the pairs land here: 256 rows of the short buffer's 512
        top_i = np.stack([rng.permutation(total)[:k] for _ in range(n)]).astype(np.int64)
    else:  # every pair lands here: all 1024 rows of the worst case
        top_i = np.stack([offset + rng.permutation(held) for _ in range(n)]).astype(np.int64)
    top_w = rng.rand(n, k).astype(np.float32)
    rows_here = int(((top_i >= offset) & (top_i < offset + held)).sum())
    short = min(2 * k * n * held // total, k * n)
    assert (rows_here <= short) == (load == "fits-the-short-buffer") or short == k * n
    fn = thunder_tpu.jit(lambda *a: ttorch.moe_experts(*a, offset, total))
    got = np.asarray(fn(x, top_i, top_w, gate, up, down).astype(jnp.float32))
    run = thunder_tpu.last_traces(fn)[-1]
    owners = {b.sym.name: b.sym.executor.name for b in run.bound_symbols if b.sym.executor is not None}
    assert owners.get("moe_experts") == "pallas"
    steps = [eqn.primitive.name for eqn in jax.make_jaxpr(run.python_callable())(x, top_i, top_w, gate, up, down).eqns]
    assert steps.count("cond") == (0 if load == "every-expert-held" else 1)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    want = masked_dense(f32(x), top_i, top_w, f32(gate), f32(up), f32(down), offset)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 2e-2


def test_the_models_regions_are_named_in_the_generated_program_and_in_the_hlo():
    """``mla``, ``moe.route``, ``moe.experts`` and ``moe.shared`` are opened in
    the model's code (``core.trace.region``), whichever executor runs the lines:
    one ``with`` a region a layer in the generated program, and the names in
    the metadata of the HLO that jax makes of it. A model without them has no
    such line."""
    import jax

    cfg, params, _, _ = built()
    idx, _ = batch()
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    jfn(params, idx)
    run = thunder_tpu.last_traces(jfn)[-1]
    opened = [line.strip() for line in run.python().splitlines() if line.strip().startswith("with __region(")]
    layer = ["with __region('moe.route'):", "with __region('moe.experts'):", "with __region('moe.shared'):"]
    assert opened == ["with __region('mla'):"] * 2 + layer + ["with __region('mla'):"] + layer
    hlo = jax.jit(run.python_callable()).lower(*jax.tree_util.tree_leaves((params, idx))).as_text(debug_info=True)
    assert all(f"/{name}/" in hlo for name in ("mla", "moe.route", "moe.experts", "moe.shared"))

    tiny = gpt.name_to_config("llama-tiny")
    plain = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, tiny))
    plain(gpt.init_params(tiny, dtype=dtypes.float32, seed=0), idx % tiny.padded_vocab_size)
    # a plain model names its causal attention and nothing else (since PR 38)
    assert re.findall(r"with __region\('([^']+)'\)", thunder_tpu.last_traces(plain)[-1].python()) == ["attn.full"] * tiny.n_layer
