"""LongCat-Flash's blocks at test size on the CPU, float32, seeded weights: double
layers of two latent-attention sublayers (both latents scaled) and two dense
FFNs with one routed layer on a shortcut across them, a softmax router over real
and zero-compute experts whose chosen weights stay as they are, a share of the
real experts held. Against the plain reference
(``perfbench/reference/longcat_flash.py``), which knows nothing of the program,
and against the router's equations written here."""

import contextlib
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

with open(os.path.join(REPO, "perfbench", "configs", "longcat-flash-omni.json"), encoding="utf-8") as _f:
    _FILE = json.load(_f)
# The stand-in (``--rehearse``'s sizes): 2 double layers 128 wide, 2 heads of 128 + 64 and 128, 16 experts of 128
# and 8 zero-compute ones, 4 a token, experts 4 to 7 held.
KEYS = {**_FILE, **_FILE["stand_in"]}
T = 96


def built(keys=KEYS, seed=5, std=None, monkeypatch=None):
    """(the program's config, its parameters, the same arrays as the reference takes them)."""
    import jax

    from perfbench import weights
    from perfbench.jobs import forward_scmoe, gpt_model

    if std is not None:
        monkeypatch.setattr(weights, "STD", std)
    cfg = gpt_model.gpt_config(keys, rehearse=True)
    shapes = jax.eval_shape(lambda: gpt.init_params(cfg, dtype=dtypes.float32, device_init=True))
    how = (shapes, seed, keys["router_bias_std"], keys["experts_down_scale"])
    tree = forward_scmoe.drawn_for_reference(*how)
    return cfg, forward_scmoe.draw(*how), {**tree, "layers": list(tree["layers"])}


def batch(t=T, seed=0, b=1):
    return np.random.RandomState(seed).randint(0, KEYS["vocab_size"], (b, t)).astype(np.int32)


def rel(got, want):
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(np.asarray(got, np.float64) - want) / np.linalg.norm(want))


@contextlib.contextmanager
def patched(module, **attributes):
    saved = {name: getattr(module, name) for name in attributes}
    for name, value in attributes.items():
        setattr(module, name, value)
    try:
        yield
    finally:
        for name, value in saved.items():
            setattr(module, name, value)


# -----------------------------------------------------------------------------
# The model
# -----------------------------------------------------------------------------


def test_the_registry_lists_the_model_at_its_published_sizes():
    """Every published key of the configuration file is the registry's: the
    benchmark lays only the cut (depth, the experts held, the vocabulary's slice,
    the positions declared) over the entry."""
    from perfbench import manifest
    from perfbench.jobs import gpt_model

    cell = manifest.load_cell("longcat-flash-omni.fwd-t16k")
    cfg = gpt_model.gpt_config(manifest.published(cell))
    listed = gpt.name_to_config("LongCat-Flash-Omni")
    assert cfg == dataclasses.replace(listed, n_layer=4, experts_held=16, padded_vocab_size=16384, block_size=16384)
    assert (listed.n_layer, listed.n_embd, listed.n_head, listed.mlp_hidden, listed.expert_hidden) == (28, 6144, 64, 12288, 2048)
    assert (listed.n_expert, listed.zero_expert_num, listed.router_outputs, listed.n_expert_per_token) == (512, 256, 768, 12)
    assert (listed.q_lora_rank, listed.kv_lora_rank, listed.qk_nope_head_dim, listed.qk_rope_head_dim, listed.v_head_dim) == (
        1536, 512, 128, 64, 128)
    assert (listed.scoring_func, listed.norm_topk_prob, listed.router_bias, listed.routed_scaling_factor) == ("softmax", False, True, 6.0)
    assert (listed.mla_scale_q_lora, listed.mla_scale_kv_lora, listed.yarn, listed.rope_base) == (True, True, None, 10_000_000)
    assert listed.softmax_scale == pytest.approx(192 ** -0.5 * 2.0)  # q's sqrt(6144 / 1536) rides the softmax scale
    assert (listed.n_shared_experts, listed.tie_embeddings, listed.embedding_scale) == (0, False, 1.0)
    assert [cfg.layer_mlp_class(i) for i in range(4)] == ["ShortcutMoE"] * 4
    assert _FILE["reduced"] == ["num_layers", "n_routed_experts", "vocab_size", "max_position_embeddings"]
    assert (_FILE["deployment_chips_per_layer"], _FILE["n_routed_experts_published"], _FILE["expert_offset"]) == (32, 512, 0)
    # every default is yesterday's program
    other = gpt.name_to_config("A.X-K1")
    assert (other.scoring_func, other.norm_topk_prob, other.zero_expert_num, other.mla_scale_q_lora, other.mla_scale_kv_lora) == (
        "sigmoid", True, 0, False, False)
    assert other.softmax_scale == pytest.approx(192 ** -0.5 * (0.1 * np.log(32.0) + 1.0) ** 2)


def test_the_published_widths_build_and_count_the_issues_parameters():
    """``init_params`` at the published widths, shapes only: two ``attn``, two
    dense ``mlp``, one routed layer and four norms a block; 638.8 M outside the
    experts a layer; the cut 5,172 M, 10.34 GB in bf16."""
    import jax

    listed = gpt.name_to_config("LongCat-Flash-Omni")
    cut = dataclasses.replace(listed, n_layer=4, experts_held=16, padded_vocab_size=16384)
    shapes = jax.eval_shape(lambda: gpt.init_params(cut, device_init=True))
    block = shapes["blocks"][0]
    assert sorted(block) == ["moe", "sub_0", "sub_1"] and len(shapes["blocks"]) == 4
    for sub in (block["sub_0"], block["sub_1"]):
        assert sorted(sub) == ["attn", "mlp", "norm_1", "norm_2"]
        assert sub["attn"]["q_b_w"].shape == (64 * 192, 1536) and sub["attn"]["kv_a_w"].shape == (512 + 64, 6144)
        assert sub["attn"]["kv_b_w"].shape == (64 * 256, 512) and sub["attn"]["proj_w"].shape == (6144, 64 * 128)
        assert sub["mlp"]["fc_1_w"].shape == (12288, 6144) and sub["mlp"]["proj_w"].shape == (6144, 12288)
    moe = block["moe"]
    assert sorted(moe) == ["experts_down", "experts_gate", "experts_up", "router_bias", "router_w"]
    assert moe["router_w"].shape == (768, 6144) and moe["router_bias"].shape == (768,) and moe["router_bias"].dtype == np.float32
    assert moe["experts_gate"].shape == (16, 6144, 2048) and moe["experts_down"].shape == (16, 2048, 6144)
    count = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(tree))
    matrices = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(tree) if len(leaf.shape) >= 2)
    experts = sum(count(moe[k]) for k in ("experts_gate", "experts_up", "experts_down"))
    assert round(matrices(block["sub_0"]["attn"]) / 1e6, 2) == 90.57 and experts == 16 * 37_748_736
    outside = matrices(block) - experts
    assert round(outside / 1e6, 1) == 638.8  # ISSUE 40: two MLA of 90.57 M, two dense FFNs of 226.49 M, the router 4.72 M
    assert 5_172_000_000 <= count(shapes) < 5_173_000_000 and "lm_head_w" in shapes  # ISSUE 40: 5,172 M
    whole = jax.eval_shape(lambda: gpt.init_params(dataclasses.replace(listed, n_layer=1), device_init=True))
    assert whole["blocks"][0]["moe"]["experts_gate"].shape == (512, 6144, 2048) and whole["wte"].shape == (131072, 6144)


@pytest.mark.parametrize("t", [T, 40], ids=["T96", "T40"])
def test_forward_through_jit_agrees_with_the_reference(t):
    import jax.numpy as jnp

    from perfbench.reference import longcat_flash

    cfg, params, tree = built()
    idx = batch(t, b=2)
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    got = np.asarray(jfn(params, idx))
    want, margin = (np.asarray(a) for a in longcat_flash.forward_and_margin(tree, jnp.asarray(idx), KEYS))
    assert got.shape == want.shape == (2, t, KEYS["vocab_size"]) and margin.shape == (2, t, 2)  # held experts', zero-compute outputs'
    assert rel(got, want) < 2e-5
    ids = [str(b.sym.id) for b in thunder_tpu.last_traces(jfn)[0].bound_symbols]
    assert (ids.count("torch.scaled_dot_product_attention"), ids.count("torch.moe_route"), ids.count("torch.moe_experts")) == (4, 2, 2)
    last = np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg, last=24))(params, idx))
    np.testing.assert_allclose(last, got[:, -24:], rtol=1e-5, atol=1e-5)


def test_every_sublayers_output_is_the_references(monkeypatch):
    """One double layer on a random x: the two mixers' outputs, the routed
    layer's, the two FFNs' and the block's, each against the reference's
    (``layer_and_margin``'s h1, s, h2, h3, y)."""
    import jax.numpy as jnp

    from perfbench.reference import longcat_flash

    cfg, params, tree = built(std=0.08, monkeypatch=monkeypatch)
    x = np.random.RandomState(3).randn(2, T, cfg.n_embd).astype(np.float32)

    def block_and_its_parts(x, p):
        parts = []
        record = lambda fn: (lambda *a, **k: (parts.append(fn(*a, **k)), parts[-1])[1])
        with patched(gpt, _mix=record(gpt._mix), _swiglu=record(gpt._swiglu), _shared_routed_moe=record(gpt._shared_routed_moe)):
            cos, sin = gpt._rope_cache(T, cfg, x.device, x.dtype)
            return (gpt._block(x, p, cos, sin, "ShortcutMoE", cfg), *parts)

    y, mix_0, s, ffn_0, mix_1, ffn_1 = (np.asarray(a) for a in thunder_tpu.jit(block_and_its_parts)(x, params["blocks"][0]))
    want: dict = {}
    longcat_flash.layer_and_margin(jnp.asarray(x), tree["layers"][0], longcat_flash.hyper(KEYS), want)
    want = {k: np.asarray(v, np.float64) for k, v in want.items()}
    for name, got, ref in (("MLA_0", mix_0, want["h1"] - x), ("Routed", s, want["s"]), ("FFN_0", ffn_0, want["h2"] - want["h1"]),
                           ("MLA_1", mix_1, want["h3"] - want["h2"]), ("FFN_1", ffn_1, want["y"] - want["s"] - want["h3"]),
                           ("y", y, want["y"])):
        assert rel(got, ref) < 3e-5, name
    assert min(np.linalg.norm(part) / np.linalg.norm(want["y"]) for part in (mix_0, s, ffn_0, mix_1, ffn_1)) > 0.02


def test_the_models_regions_are_named_in_the_generated_program_and_in_the_hlo():
    import jax

    from perfbench.jobs import forward_scmoe
    from perfbench.layer_metrics import _regions

    cfg, params, _ = built()
    idx = batch()
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    jfn(params, idx)
    run = thunder_tpu.last_traces(jfn)[-1]
    opened = [line.strip()[len("with __region('"):-len("'):")] for line in run.python().splitlines()
              if line.strip().startswith("with __region(")]
    assert [r for r in opened if r.startswith("moe.")] == ["moe.route", "moe.experts", "moe.zero"] * 2
    assert opened.count("mla") == 4
    compiled = jax.jit(run.python_callable()).lower(*jax.tree_util.tree_leaves((params, idx))).compile()
    found = _regions.of_instructions(compiled.as_text(), forward_scmoe.REGIONS)
    assert set(found.values()) == set(forward_scmoe.REGIONS)


# -----------------------------------------------------------------------------
# The router
# -----------------------------------------------------------------------------


def routed_by_hand(m, router_w, bias, k, scale):
    """The equations as numpy writes them: (chosen, weights, softmax scores)."""
    logits = np.asarray(m, np.float64) @ np.asarray(router_w, np.float64).T
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    chosen = np.argsort(-(p + bias), axis=-1, kind="stable")[:, :k]
    return chosen, scale * np.take_along_axis(p, chosen, 1), p


def test_the_router_scores_by_a_softmax_over_every_output_and_leaves_the_weights_as_they_are():
    rng = np.random.RandomState(7)
    m, w = rng.randn(64, 32).astype(np.float32), rng.randn(24, 32).astype(np.float32) * 0.3
    bias = (rng.randn(24) * 0.02).astype(np.float32)
    route = thunder_tpu.jit(lambda m, w, b: ttorch.moe_route(m, w, 4, 1, 1, 6.0, b, 1e-20, "softmax", False))
    top_i, top_w = (np.asarray(a) for a in route(m, w, bias))
    chosen, weights, p = routed_by_hand(m, w, bias, 4, 6.0)
    assert (np.sort(top_i, -1) == np.sort(chosen, -1)).all() and top_w.dtype == np.float32
    order = np.argsort(top_i, -1)
    np.testing.assert_allclose(np.take_along_axis(top_w, order, 1), np.take_along_axis(weights, np.argsort(chosen, -1), 1), rtol=1e-5)
    # the bias chooses and does not weigh; a token's weights add up to 6 times the chosen scores' sum, each token its own
    unbiased = np.argsort(-p, axis=-1, kind="stable")[:, :4]
    assert (np.sort(unbiased, -1) != np.sort(chosen, -1)).any()
    np.testing.assert_allclose(top_w.sum(-1), 6.0 * np.take_along_axis(p, top_i, 1).sum(-1), rtol=1e-5)
    assert np.ptp(top_w.sum(-1)) > 0.1 and (top_w.sum(-1) < 6.0).all()
    # a softmax over all outputs: leaving a few out is another router
    fewer_i, fewer_w = (np.asarray(a) for a in route(m, w[:16], bias[:16]))
    assert np.abs(fewer_w.sum(-1) - top_w.sum(-1)).max() > 0.05
    with pytest.raises(Exception, match="scoring function"):
        thunder_tpu.jit(lambda m, w: ttorch.moe_route(m, w, 4, 1, 1, 6.0, None, 1e-20, "tanh", False))(m, w)


def test_a_chosen_zero_compute_expert_weighs_the_routers_input_itself():
    """One routed layer with every real expert held, against the equations: the
    real experts' SwiGLUs weighed ``6 p_e``, and ``(sum of 6 p_e over the chosen
    zero-compute outputs) m``. The counts say how many pairs chose one."""
    cfg, params, _ = built()
    cfg = dataclasses.replace(cfg, experts_held=None, expert_offset=0)
    rng = np.random.RandomState(11)
    p = {k: np.asarray(v) for k, v in params["blocks"][0]["moe"].items()}
    p.update({k: (rng.randn(cfg.n_expert, *p[k].shape[1:]) * 0.1).astype(np.float32) for k in ("experts_gate", "experts_up", "experts_down")})
    m = rng.randn(1, 48, cfg.n_embd).astype(np.float32)
    counts = {"rows": [], "changed": [], "zero": []}
    got = np.asarray(thunder_tpu.jit(lambda m, p: gpt._shared_routed_moe(m, p, cfg, counts))(m, p))[0]
    chosen, weights, _ = routed_by_hand(m[0], p["router_w"], p["router_bias"], cfg.n_expert_per_token, cfg.routed_scaling_factor)
    silu = lambda a: a / (1 + np.exp(-a))
    want = np.zeros((48, cfg.n_embd))
    for n in range(48):
        for e, w_e in zip(chosen[n], weights[n]):
            want[n] += w_e * ((silu(m[0, n] @ p["experts_gate"][e]) * (m[0, n] @ p["experts_up"][e])) @ p["experts_down"][e]
                              if e < cfg.n_expert else m[0, n])
    assert rel(got, want) < 2e-5 and (chosen >= cfg.n_expert).sum() > 20
    zero_only = np.where(chosen >= cfg.n_expert, weights, 0.0).sum(-1, keepdims=True) * m[0]
    assert rel(zero_only, want) > 0.05 and np.linalg.norm(zero_only) / np.linalg.norm(want) > 0.05


def test_router_counts_walk_the_double_layers():
    from thunder_tpu.executors import pallasex

    cfg, params, _ = built()
    idx = batch(b=2)
    rows, changed, zero = (np.asarray(a) for a in thunder_tpu.jit(lambda p, i: gpt.router_counts(p, i, cfg))(params, idx))
    assert rows.shape == (2, cfg.held_experts) and changed.shape == zero.shape == (2,)
    pairs = 2 * T * cfg.n_expert_per_token
    assert (0 < rows.sum(1)).all() and (rows.sum(1) + zero < pairs).all()  # the rest went to experts held elsewhere
    assert (0.15 * pairs < zero).all() and (zero < 0.6 * pairs).all()      # 8 of 24 outputs: a third when even
    assert (changed > 0).all()
    np.testing.assert_array_equal(np.asarray(thunder_tpu.jit(lambda p, i: gpt.routed_rows(p, i, cfg))(params, idx)), rows)
    idle = {**params, "blocks": [{**b, "moe": {**b["moe"], "router_bias": b["moe"]["router_bias"] * 0}} for b in params["blocks"]]}
    assert (np.asarray(thunder_tpu.jit(lambda p, i: gpt.router_counts(p, i, cfg))(idle, idx)[1]) == 0).all()
    # a router without zero-compute experts answers as it did: two counts
    assert len(thunder_tpu.jit(lambda p, i: gpt.router_counts(p, i, gpt.name_to_config("lfm2-tiny")))(
        gpt.init_params(gpt.name_to_config("lfm2-tiny"), dtype=dtypes.float32), batch(32) % 96)) == 2
    # the buffer the even load is reckoned over is the router's outputs, zero-compute ones among them
    assert pallasex.expert_buffer_rows(16384, 12, 16, 768) == 8192 and pallasex.expert_buffer_rows(16384, 12, 16, 512) == 12288
    assert [pallasex.expert_buffer_passes(r, 16384, 12, 16, 768) for r in (0, 4096, 8192, 8193, 196608)] == [1, 1, 1, 2, 24]


def test_the_shares_add_up_to_the_uncut_references_layer():
    """The guide's test: the stand-in's 16 real experts over 4 shares of 4. The
    four routed parts (the program's, each share with the zero-compute term left
    out), plus the zero-compute term and the dense path, each counted once, are
    the uncut reference's layer (every expert held)."""
    import jax.numpy as jnp

    from perfbench.reference import longcat_flash

    cfg, params, tree = built()
    rng = np.random.RandomState(13)
    full = {k: (rng.randn(cfg.n_expert, *np.asarray(v).shape[1:]) * 0.1).astype(np.float32)
            for k, v in params["blocks"][0]["moe"].items() if k.startswith("experts_")}
    moe = {**params["blocks"][0]["moe"], **full}
    x = rng.randn(1, 64, cfg.n_embd).astype(np.float32)
    uncut_keys = {**KEYS, "n_routed_experts": cfg.n_expert, "expert_offset": 0}
    layer = {**tree["layers"][0], **{"moe/" + k: v for k, v in full.items()}}
    want: dict = {}
    longcat_flash.layer_and_margin(jnp.asarray(x), layer, longcat_flash.hyper(uncut_keys), want)
    m = np.asarray(want["m"])

    held, parts = 4, []
    for chip in range(cfg.n_expert // held):
        share = dataclasses.replace(cfg, experts_held=held, expert_offset=chip * held, zero_expert_num=0)
        q = {**moe, **{k: full[k][chip * held:(chip + 1) * held] for k in full}}
        parts.append(np.asarray(thunder_tpu.jit(lambda m, q, share=share: gpt._shared_routed_moe(m, q, share))(m, q)))
    nothing_held = {**moe, **{k: full[k][:4] for k in full}}   # a share whose experts no token can choose: the zero term alone
    elsewhere = dataclasses.replace(cfg, experts_held=4, expert_offset=10_000)
    zero_term = np.asarray(thunder_tpu.jit(lambda m, q: gpt._shared_routed_moe(m, q, elsewhere))(m, nothing_held))
    np.testing.assert_allclose(sum(parts) + zero_term, np.asarray(want["s"]), rtol=2e-4, atol=2e-5)
    dense_path = np.asarray(want["y"]) - np.asarray(want["s"])  # x + both mixers + both FFNs: what every chip computes alike
    np.testing.assert_allclose(dense_path + sum(parts) + zero_term, np.asarray(want["y"]), rtol=2e-4, atol=2e-5)
    assert all(rel(part, want["s"]) > 0.3 for part in parts) and rel(zero_term, want["s"]) > 0.3  # no one part is the layer
    # and the cell's share (experts 4 to 7 with the zero term) is the reference's at the same share
    got = np.asarray(thunder_tpu.jit(lambda m, q: gpt._shared_routed_moe(m, q, cfg))(m, {**moe, **{k: full[k][4:8] for k in full}}))
    np.testing.assert_allclose(got, parts[1] + zero_term, rtol=2e-4, atol=2e-5)
    shared: dict = {}
    cut_layer = {**layer, **{"moe/" + k: v[4:8] for k, v in full.items()}}
    longcat_flash.layer_and_margin(jnp.asarray(x), cut_layer, longcat_flash.hyper(KEYS), shared)
    np.testing.assert_allclose(got, np.asarray(shared["s"]), rtol=2e-4, atol=2e-5)


# -----------------------------------------------------------------------------
# Mutations: a missing term is no rounding
# -----------------------------------------------------------------------------


def _without_the_zero_compute_term(monkeypatch, cfg, params):
    return dataclasses.replace(cfg, zero_expert_num=0), params  # the router keeps its outputs: the parameters say so


def _block_with(read_second_sublayer: bool, join_early: bool):
    def block(x, p, cos, sin, config, counts=None, layer=0):
        a, b = p["sub_0"], p["sub_1"]
        h1 = x + gpt._mix(gpt._norm(x, a["norm_1"], config), a, cos, sin, config, layer, counts)
        m = gpt._norm(h1, a["norm_2"], config)
        h2 = h1 + gpt._swiglu(m, a["mlp"])
        n = gpt._norm(h2, b["norm_1"], config)
        s = gpt._shared_routed_moe(n if read_second_sublayer else m, p["moe"], config, counts)
        if join_early:
            h2 = h2 + s
            n = gpt._norm(h2, b["norm_1"], config)
        h3 = h2 + gpt._mix(n, b, cos, sin, config, layer, counts)
        out = h3 + gpt._swiglu(gpt._norm(h3, b["norm_2"], config), b["mlp"])
        return out if join_early else out + s

    return block


def _routed_reads_the_second_sublayers_input(monkeypatch, cfg, params):
    monkeypatch.setattr(gpt, "_shortcut_block", _block_with(True, False))
    return cfg, params


def _routed_joins_at_h2(monkeypatch, cfg, params):
    monkeypatch.setattr(gpt, "_shortcut_block", _block_with(False, True))
    return cfg, params


def _without_q_scale(monkeypatch, cfg, params):
    return dataclasses.replace(cfg, mla_scale_q_lora=False), params


def _without_kv_scale(monkeypatch, cfg, params):
    return dataclasses.replace(cfg, mla_scale_kv_lora=False), params


def _weights_renormalised(monkeypatch, cfg, params):
    return dataclasses.replace(cfg, norm_topk_prob=True), params


def _softmax_over_the_real_experts_only(monkeypatch, cfg, params):
    real = ttorch.moe_route
    monkeypatch.setattr(ttorch, "moe_route", lambda x, w, k, g, tg, scale, bias, *how: real(
        x, w[:cfg.n_expert], k, g, tg, scale, bias[:cfg.n_expert], *how))
    return cfg, params


def _one_experts_down_projection_zeroed_in_one_layer(monkeypatch, cfg, params):
    rows = np.asarray(thunder_tpu.jit(lambda p, i: gpt.routed_rows(p, i, cfg))(params, batch(b=1)))
    blocks = list(params["blocks"])
    down = np.array(blocks[1]["moe"]["experts_down"])
    down[int(rows[1].argmax())] = 0.0  # the held expert most of the checked sequence's rows go to in that layer
    blocks[1] = {**blocks[1], "moe": {**blocks[1]["moe"], "experts_down": down}}
    return cfg, {**params, "blocks": blocks}


def _bias_weighs(monkeypatch, cfg, params):
    def route(x, w, k, g, tg, scale, bias, eps, *how):
        biased = ttorch.softmax(ttorch.linear(x.to(dtypes.float32), w.to(dtypes.float32)), -1) + bias
        top_w, top_i = ttorch.topk(biased, k, -1)
        return top_i, top_w * scale

    monkeypatch.setattr(ttorch, "moe_route", route)
    return cfg, params


MUTATIONS = {"zero-compute-term-left-out": _without_the_zero_compute_term,
             "routed-reads-the-second-sublayers-input": _routed_reads_the_second_sublayers_input,
             "routed-joins-at-h2": _routed_joins_at_h2,
             "q-lora-scale-left-out": _without_q_scale, "kv-lora-scale-left-out": _without_kv_scale,
             "weights-renormalised-over-the-chosen": _weights_renormalised,
             "softmax-over-the-real-experts-only": _softmax_over_the_real_experts_only,
             "one-experts-down-projection-zeroed-in-one-layer": _one_experts_down_projection_zeroed_in_one_layer,
             "bias-added-into-the-weights": _bias_weighs}
# Weights of a size at which a block's output is of the order of its input (at N(0, 0.02) and a width of 128 the
# residual stream is the embedding and no block shows), so that what a mutation does to a layer reaches the logits.
MUTATION_STD = 0.08


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_a_mutated_system_fails_the_cells_comparison_at_rehearsal_size(monkeypatch, name):
    """Each departure from the published mathematics fails the comparison the
    cell's check makes (``perfbench/checks_scmoe.py``) at the stand-in's sizes,
    in float32, where the unmutated system is a thousandth of the limits."""
    import jax.numpy as jnp

    from perfbench import checks_scmoe
    from perfbench.reference import longcat_flash

    cfg, params, tree = built(std=MUTATION_STD, monkeypatch=monkeypatch)
    idx = batch(b=1)
    want, margin = (np.asarray(a) for a in longcat_flash.forward_and_margin(tree, jnp.asarray(idx), KEYS))
    sound = checks_scmoe.compare_logits(np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))(params, idx)), want, margin)
    assert sound["ok"] and sound["logits_rel_l2"] < 1e-4 and sound["row_max"] < 1e-3
    mutated, changed = MUTATIONS[name](monkeypatch, cfg, params)
    got = np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, mutated))(changed, idx))
    verdict = checks_scmoe.compare_logits(got, want, margin)
    assert not verdict["ok"], verdict
