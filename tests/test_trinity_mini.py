"""Trinity-Mini's blocks at test size on the CPU, float32, seeded weights: two
attention kinds of one head layout that differ by layer in mask and rope
(within a window and roped, or over everything without positions), every head
normed and gated, four norms a block, a biased sigmoid router over experts that
are all held beside a shared one, the embedding scaled, a head on the last
positions. Against the plain reference (``perfbench/reference/afmoe.py``), which
knows nothing of the program, and against masked softmaxes written here."""

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

with open(os.path.join(REPO, "perfbench", "configs", "trinity-mini.json"), encoding="utf-8") as _f:
    _FILE = json.load(_f)
# The stand-in (``--rehearse``'s sizes): window, window, global, window; 256 wide, 4 query heads of 128 on 2
# key-value heads, a window of 128, one dense layer, then 8 experts, 2 a token, beside the shared one.
KEYS = {**_FILE, **_FILE["stand_in"]}
T = 256


def built(keys=KEYS, seed=5):
    """(the program's config, its parameters, the same arrays as the reference takes them)."""
    import jax

    from perfbench.jobs import forward_window_moe, gpt_model

    cfg = gpt_model.gpt_config(keys, rehearse=True)
    shapes = jax.eval_shape(lambda: gpt.init_params(cfg, dtype=dtypes.float32, device_init=True))
    params = forward_window_moe.draw(shapes, seed)
    return cfg, params, forward_window_moe.for_reference(params, keys["num_dense_layers"])


def batch(t=T, seed=0, b=1):
    return np.random.RandomState(seed).randint(0, KEYS["vocab_size"], (b, t)).astype(np.int32)


def qkv(t, heads=4, groups=2, d=16, seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    return tuple(rng.randn(1, n, t, d).astype(dtype) for n in (heads, groups, groups))


def masked_softmax_attention(q, k, v, window):
    """The equations as numpy writes them: scores over every pair, the mask ``0 <= i - j < window``."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    rep = q.shape[1] // k.shape[1]
    k, v = np.repeat(k, rep, 1), np.repeat(v, rep, 1)
    t = q.shape[2]
    ahead = np.arange(t)[:, None] - np.arange(t)[None, :]
    s = np.where((ahead >= 0) & (ahead < window), np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1]), -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)


def rel(got, want):
    return float(np.linalg.norm(np.asarray(got, np.float64) - want) / np.linalg.norm(want))


# -----------------------------------------------------------------------------
# The model
# -----------------------------------------------------------------------------


def test_the_registry_lists_the_model_at_its_published_sizes():
    """Every published key of the configuration file is the registry's: the
    benchmark lays only the cut in depth over the entry, and the layer pattern
    it runs is the first 7 of the published list."""
    from perfbench import manifest
    from perfbench.jobs import gpt_model

    cell = manifest.load_cell("trinity-mini.fwd-t32k")
    cfg = gpt_model.gpt_config(manifest.published(cell))
    listed = gpt.name_to_config("Trinity-Mini")
    assert cfg == dataclasses.replace(listed, n_layer=7)
    assert (listed.n_layer, listed.n_embd, listed.n_head, listed.query_groups, listed.head_size) == (32, 2048, 32, 4, 128)
    assert (listed.intermediate_size, listed.expert_hidden, listed.n_expert, listed.n_expert_per_token) == (6144, 1024, 128, 8)
    assert (listed.padded_vocab_size, listed.block_size, listed.tie_embeddings) == (200192, 131072, False)
    assert listed.layer_types == tuple(_FILE["layer_types"]) and len(listed.layer_types) == 32
    assert [listed.layer_types.count(k) for k in ("sliding_attention", "full_attention")] == [24, 8]
    assert [cfg.layer_mixer(i) for i in range(7)] == ["sliding_attention"] * 3 + ["full_attention"] + ["sliding_attention"] * 3
    assert [cfg.layer_mlp_class(i) for i in range(7)] == ["LLaMAMLP"] * 2 + ["SharedRoutedMoE"] * 5
    assert (listed.sliding_window, listed.attn_rope) == (_FILE["sliding_window"], False)
    assert (listed.qk_norm, listed.attn_output_gate, listed.sandwich_norms, listed.router_bias) == (True,) * 4
    assert (listed.routed_scaling_factor, listed.router_norm_eps, listed.n_shared_experts) == (_FILE["route_scale"], 1e-20, 1)
    assert listed.embedding_scale == pytest.approx(_FILE["hidden_size"] ** 0.5) and listed.norm_eps == _FILE["rms_norm_eps"]
    assert (listed.n_expert_groups, listed.n_limited_groups, listed.held_experts) == (1, 1, 128)
    # every default is yesterday's program: two norms, no window, a head as wide as its share of the model
    plain = gpt.name_to_config("llama-2-7b")
    assert (plain.sandwich_norms, plain.sliding_window, plain.head_dim, plain.head_size) == (False, None, None, 128)


def test_the_parameter_tree_has_four_norms_a_block_and_the_issues_count_of_parameters():
    import jax

    cfg = dataclasses.replace(gpt.name_to_config("Trinity-Mini"), n_layer=7)
    shapes = jax.eval_shape(lambda: gpt.init_params(cfg, device_init=True))
    assert len(shapes["dense_blocks"]) == 2 and len(shapes["moe_blocks"]) == 5
    dense, expert = shapes["dense_blocks"][0], shapes["moe_blocks"][0]
    for block in (dense, expert):
        assert sorted(k for k in block if "norm" in k) == ["norm_1", "norm_2", "post_attn_norm", "post_mlp_norm"]
        assert sorted(block["attn"]) == ["gate_w", "k_norm", "proj_w", "q_norm", "qkv_w"]
        assert block["attn"]["qkv_w"].shape == ((32 + 2 * 4) * 128, 2048) and block["attn"]["proj_w"].shape == (2048, 4096)
        assert block["attn"]["gate_w"].shape == (4096, 2048) and block["attn"]["q_norm"]["weight"].shape == (128,)
    assert dense["mlp"]["fc_1_w"].shape == (6144, 2048)
    assert expert["mlp"]["experts_gate"].shape == (128, 2048, 1024) and expert["mlp"]["experts_down"].shape == (128, 1024, 2048)
    assert expert["mlp"]["router_bias"].shape == (128,) and expert["mlp"]["router_bias"].dtype == np.float32
    assert expert["mlp"]["shared"]["fc_1_w"].shape == (1024, 2048) and shapes["lm_head_w"].shape == (200192, 2048)
    count = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(tree))
    assert count(dense["attn"]) == 27_263_232  # ISSUE 38: 27.26 M a layer
    assert round(count(dense) / 1e6, 1) == 65.0 and round(count(expert) / 1e6, 1) == 839.1
    assert 5_145_600_000 <= count(shapes) < 5_145_700_000  # 5,145.6 M: 10.29 GB in bf16


@pytest.mark.parametrize("t", [T, 200, 100], ids=["two-windows", "unaligned-past-the-window", "under-the-window"])
def test_forward_through_jit_agrees_with_the_reference(t):
    """Window, global and ``T <= W`` all occur: at 256 and 200 positions the
    window layers see 128 keys of up to 256, at 100 every layer is causal."""
    import jax.numpy as jnp

    from perfbench.reference import afmoe

    cfg, params, tree = built()
    idx = batch(t, b=2)
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    got = np.asarray(jfn(params, idx))
    want, margin = (np.asarray(a) for a in afmoe.forward_and_margin(tree, jnp.asarray(idx), KEYS))
    assert got.shape == want.shape == (2, t, KEYS["vocab_size"]) and margin.shape == (2, t)
    assert rel(got, want) < 2e-5
    ids = [str(b.sym.id) for b in thunder_tpu.last_traces(jfn)[0].bound_symbols]
    windows = ids.count("torch.window_attention")
    assert (windows, ids.count("torch.scaled_dot_product_attention")) == ((3, 1) if t > KEYS["sliding_window"] else (0, 4))


def test_forward_last_is_the_last_rows_of_forward():
    cfg, params, _ = built()
    idx = batch(b=2)
    whole = np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))(params, idx))
    last = np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg, last=24))(params, idx))
    assert last.shape == (2, 24, KEYS["vocab_size"])
    np.testing.assert_allclose(last, whole[:, -24:], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t", [T, 100], ids=["past-the-window", "under-the-window"])
def test_the_window_layers_are_roped_and_the_global_layer_is_not(t, monkeypatch):
    """Two rope calls (q, k) a window layer and none in the global one, whatever
    the length; and the positions reach the logits through the window layers only."""
    cfg, params, _ = built()
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    jfn(params, batch(t))
    lines = [b for b in thunder_tpu.last_traces(jfn)[0].bound_symbols]
    ids = [str(b.sym.id) for b in lines]
    assert ids.count("torch.apply_rope") == 2 * 3
    attention = [i for i, name in enumerate(ids) if name in ("torch.window_attention", "torch.scaled_dot_product_attention")]
    ropes_before = [sum(1 for name in ids[a:b] if name == "torch.apply_rope") for a, b in zip([0] + attention, attention)]
    assert ropes_before == [2, 2, 0, 2]  # window, window, global, window
    real = gpt._qkv_heads  # the mutation: the layers' own rope argument overruled, and no other rope call is left
    monkeypatch.setattr(gpt, "_qkv_heads", lambda x, p, h, g, cos, sin, config, rope: real(x, p, h, g, cos, sin, config, False))
    one = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    one(params, batch(t))
    assert "torch.apply_rope" not in [str(b.sym.id) for b in thunder_tpu.last_traces(one)[0].bound_symbols]


def test_the_models_regions_are_named_in_the_generated_program_and_in_the_hlo():
    import jax

    from perfbench.jobs import forward_window_moe
    from perfbench.layer_metrics import _regions

    cfg, params, _ = built()
    idx = batch()
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    jfn(params, idx)
    run = thunder_tpu.last_traces(jfn)[-1]
    opened = [line.strip()[len("with __region('"):-len("'):")] for line in run.python().splitlines()
              if line.strip().startswith("with __region(")]
    attention = [r for r in opened if r.startswith("attn.") and r != "attn.qk_norm"]
    assert attention == ["attn.window", "attn.window", "attn.full", "attn.window"]
    assert [r for r in opened if r.startswith("moe.")] == ["moe.route", "moe.experts", "moe.shared"] * 3
    compiled = jax.jit(run.python_callable()).lower(*jax.tree_util.tree_leaves((params, idx))).compile()
    found = _regions.of_instructions(compiled.as_text(), forward_window_moe.REGIONS)
    assert set(found.values()) == set(forward_window_moe.REGIONS)


def test_each_chips_share_of_the_experts_sums_to_the_uncut_layer_with_the_shared_expert_once():
    """The guide's test: the routed experts split over chips (``experts_held``
    from ``expert_offset``), the shared expert on the first, add up to the layer
    that holds them all, which is what this cell runs."""
    cfg, params, _ = built()
    p = params["moe_blocks"][0]["mlp"]
    x = np.random.RandomState(3).randn(2, 24, cfg.n_embd).astype(np.float32)
    whole = np.asarray(thunder_tpu.jit(lambda x, p: gpt._shared_routed_moe(x, p, cfg))(x, p))
    held, total = 2, cfg.n_expert
    parts = []
    for chip in range(total // held):
        share = dataclasses.replace(cfg, experts_held=held, expert_offset=chip * held, n_shared_experts=1 if chip == 0 else 0)
        q = {**p, **{k: p[k][chip * held:(chip + 1) * held] for k in ("experts_gate", "experts_up", "experts_down")}}
        parts.append(np.asarray(thunder_tpu.jit(lambda x, q, share=share: gpt._shared_routed_moe(x, q, share))(x, q)))
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-5)
    assert rel(parts[0], whole) > 0.1  # and no one chip's share is the layer


def test_router_counts_are_eight_rows_a_token_whatever_the_ids_and_the_bias_changes_choices():
    from perfbench.jobs import forward_window_moe

    cfg, params, _ = built()
    idx = batch(b=2)
    rows, changed = (np.asarray(a) for a in thunder_tpu.jit(lambda p, i: gpt.router_counts(p, i, cfg))(params, idx))
    assert rows.shape == (3, 8) and (rows.sum(1) == 2 * T * cfg.n_expert_per_token).all()
    assert changed.shape == (3,) and (changed > 0).all()  # a bias of N(0, 0.1) is not idle
    idle = {**params, "moe_blocks": [{**b, "mlp": {**b["mlp"], "router_bias": b["mlp"]["router_bias"] * 0}}
                                     for b in params["moe_blocks"]]}
    assert (np.asarray(thunder_tpu.jit(lambda p, i: gpt.router_counts(p, i, cfg))(idle, idx)[1]) == 0).all()
    assert forward_window_moe.BIAS_STD == 0.1


# -----------------------------------------------------------------------------
# Attention within a window
# -----------------------------------------------------------------------------


@pytest.mark.parametrize("t,window,groups", [(40, 7, 2), (40, 1, 4), (33, 40, 1), (64, 16, 2)],
                         ids=["T40-W7", "only-its-own-key", "window-past-the-sequence", "T64-W16"])
def test_the_window_symbols_decomposition_is_the_masked_softmax(t, window, groups):
    q, k, v = qkv(t, groups=groups)
    got = np.asarray(thunder_tpu.jit(lambda q, k, v: ttorch.window_attention(q, k, v, window=window))(q, k, v))
    assert rel(got, masked_softmax_attention(q, k, v, window)) < 1e-5
    if window >= t:  # and then it is causal attention
        causal = thunder_tpu.jit(lambda q, k, v: ttorch.scaled_dot_product_attention(q, k, v, is_causal=True, enable_gqa=True))
        np.testing.assert_allclose(got, np.asarray(causal(q, k, v)), rtol=1e-5, atol=1e-6)


def test_the_window_symbol_is_causal_and_forgets_what_left_the_window():
    q, k, v = qkv(48)
    f = thunder_tpu.jit(lambda q, k, v: ttorch.window_attention(q, k, v, window=8))
    base = np.asarray(f(q, k, v))
    k2, v2 = k.copy(), v.copy()
    k2[:, :, 20], v2[:, :, 20] = 9.0, -9.0
    moved = np.abs(np.asarray(f(q, k2, v2)) - base).max(axis=(0, 1, 3)) > 1e-6
    assert moved.nonzero()[0].tolist() == list(range(20, 28))  # the 8 queries whose window holds key 20


@pytest.mark.parametrize("window", [5, 64], ids=["W5", "window-past-the-sequence"])
def test_the_window_symbols_gradients_are_the_masked_softmaxs(window):
    """The trace VJP differentiates the decomposition as it stands."""
    import jax
    import jax.numpy as jnp

    q, k, v = qkv(24)
    weigh = np.random.RandomState(9).randn(1, 4, 24, 16).astype(np.float32)

    def plain(q, k, v):
        kk, vv = jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1)
        ahead = jnp.arange(24)[:, None] - jnp.arange(24)[None, :]
        s = jnp.where((ahead >= 0) & (ahead < window), jnp.einsum("bhqd,bhkd->bhqk", q, kk) / 4.0, -jnp.inf)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vv) * weigh)

    want = jax.grad(plain, argnums=(0, 1, 2))(q, k, v)
    got = thunder_tpu.grad(lambda q, k, v: ttorch.sum(ttorch.window_attention(q, k, v, window=window) * weigh),
                           argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert rel(g, np.asarray(w, np.float64)) < 1e-5


@pytest.mark.parametrize("t,window,heads,groups,d,owner", [
    (300, 100, 4, 2, 128, "flash"), (384, 129, 2, 2, 128, "flash"), (256, 128, 4, 1, 64, "flash"), (200, 1000, 2, 1, 128, "flash"),
    # pallasex's own kernel (PR 42), tiles of 256 x 256: one, two and eight query heads a key-value head
    (512, 300, 2, 2, 128, "pallas"), (768, 256, 4, 2, 128, "pallas"), (512, 1000, 8, 1, 128, "pallas"),
    (768, 300, 2, 1, 128, "pallas"), (256, 128, 4, 1, 128, "pallas"), (512, 1, 2, 1, 128, "pallas"), (512, 2048, 2, 1, 256, "pallas")],
    ids=["T300-W100", "T384-W129-no-tile-divides-it", "T256-W128-heads-of-64", "window-past-the-sequence",
         "own-window-off-both-tiles", "own-window-of-one-tile", "own-window-past-the-sequence-eight-heads-a-key-head",
         "own-a-rows-first-tile-wholly-masked", "own-T256-W128-the-stand-ins", "own-only-its-own-key", "own-heads-of-256"])
def test_the_claimed_kernel_in_interpret_mode_against_the_decomposition(monkeypatch, t, window, heads, groups, d, owner):
    """bf16, on the CPU only when forced. ``pallas`` is asked first and takes
    heads of whole lane groups on a sequence its tiles of 256 divide: the
    window's far edge falls inside a key tile, or on its border, or before the
    sequence (the loop is then the causal one), and at T = 768 under a window of
    300 the last query tile's first key tile holds no key of its later rows.
    ``flash`` takes the rest and runs splash under the local mask, the window's
    edge inside a tile and the sequence padded to the lanes. Interpreted."""
    import jax.numpy as jnp

    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    q, k, v = (jnp.asarray(a, jnp.bfloat16) for a in qkv(t, heads, groups, d=d, seed=t))
    jfn = thunder_tpu.jit(lambda q, k, v: ttorch.window_attention(q, k, v, window=window))
    got = np.asarray(jfn(q, k, v).astype(jnp.float32))
    line = thunder_tpu.last_traces(jfn)[-1].bound_symbols[0]
    assert (str(line.sym.id), line.sym.executor.name) == ("torch.window_attention", owner)
    want = masked_softmax_attention(*(np.asarray(a.astype(jnp.float32)) for a in (q, k, v)), window)
    assert np.isfinite(got).all() and rel(got, want) < 1e-2
    monkeypatch.delenv("THUNDER_FLASH_FORCE")
    plain = thunder_tpu.jit(lambda q, k, v: ttorch.window_attention(q, k, v, window=window))
    assert rel(np.asarray(plain(q, k, v).astype(jnp.float32)), want) < 1e-2
    assert thunder_tpu.last_traces(plain)[-1].bound_symbols[0].sym.executor.name not in ("flash", "pallas")


def test_the_own_kernel_takes_a_scale_or_finds_it_folded(monkeypatch):
    """The layout pass hands q scaled and ``scale=1.0``; any other scale is q's before the call, as splash has it."""
    import jax.numpy as jnp

    from thunder_tpu.executors import pallasex

    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    q, k, v = (jnp.asarray(a, jnp.bfloat16) for a in qkv(512, 4, 2, d=128, seed=3))
    want = masked_softmax_attention(*(np.asarray(a.astype(jnp.float32)) for a in (q, k, v)), 200)
    folded = (q * jnp.asarray(128 ** -0.5, jnp.bfloat16)).astype(jnp.bfloat16)
    for got in (pallasex._window_attend_impl(folded, k, v, window=200, scale=1.0),
                pallasex._window_attend_impl(q * 2, k, v, window=200, scale=0.5 * 128 ** -0.5)):
        assert rel(np.asarray(got.astype(jnp.float32)), want) < 1e-2


def _like(shape, dtype):
    return type("P", (), {"shape": shape, "dtype": dtype})()


def test_what_the_window_claim_declines_is_the_decompositions(monkeypatch):
    from thunder_tpu.executors import flashex

    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    like = _like
    bf16 = lambda *shape: like(shape, dtypes.bfloat16)
    assert flashex._window_checker(bf16(1, 4, 256, 128), bf16(1, 2, 256, 128), bf16(1, 2, 256, 128), window=64)
    assert not flashex._window_checker(like((1, 4, 256, 128), dtypes.float32), like((1, 2, 256, 128), dtypes.float32),
                                       like((1, 2, 256, 128), dtypes.float32), window=64)  # float32 keeps its precision
    assert not flashex._window_checker(bf16(1, 4, 32, 128), bf16(1, 2, 32, 128), bf16(1, 2, 32, 128), window=8)  # too short to pay
    assert not flashex._window_checker(bf16(1, 4, 256, 128), bf16(1, 2, 512, 128), bf16(1, 2, 512, 128), window=64)
    monkeypatch.delenv("THUNDER_FLASH_FORCE")
    assert not flashex._window_checker(bf16(1, 4, 256, 128), bf16(1, 2, 256, 128), bf16(1, 2, 256, 128), window=64)


OWN_DECLINES = {
    # what the own kernel's checker sees at trinity's size -> who has the symbol then, on a call small enough to run here
    "float32": (dict(dtype=dtypes.float32), "neither"),
    "float16": (dict(dtype=dtypes.float16), "flash"),
    "heads-of-64": (dict(d=64), "flash"),
    "no-tile-divides-T": (dict(t=32768 + 128, small_t=384), "flash"),
    "a-windows-span-past-the-vmem": (dict(window=16384, scope=1024 * 1024), "flash"),
}


def test_the_own_kernels_checker_takes_trinitys_window_layers(monkeypatch):
    from thunder_tpu.executors import pallasex

    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    shapes = lambda t, dtype=dtypes.bfloat16: [_like((1, h, t, 128), dtype) for h in (32, 4, 4)]
    assert pallasex._window_attend_checker(*shapes(32768), window=2048)
    # nine key tiles of 256 of k and of v, twice; q's and the output's blocks, twice; q turned and the accumulator; scores
    assert pallasex._window_attend_vmem(32768, 2048, 8, 128, 2) == 2359296 + 2097152 + 1572864 + 1048576 <= 3 * 16 * 2 ** 20 // 4
    assert pallasex._window_attend_checker(*shapes(131072), window=2048)  # a step holds its window's span, whatever T is
    assert pallasex._window_attend_checker(*shapes(32768), window=4096)
    assert not pallasex._window_attend_checker(*shapes(32768), window=16384)  # 16,640 keys a step: splash's, as before
    assert not pallasex._window_attend_checker(*shapes(32768), window=32768)  # causal over 32,768: splash's
    q, k, v = shapes(32768)
    assert not pallasex._window_attend_checker(q, k, _like((1, 4, 32768, 64), dtypes.bfloat16), window=2048)
    assert not pallasex._window_attend_checker(q, k, _like((1, 2, 32768, 128), dtypes.bfloat16), window=2048)
    monkeypatch.delenv("THUNDER_FLASH_FORCE")
    assert not pallasex._window_attend_checker(*shapes(32768), window=2048)  # no chip and nothing forced: nobody's


@pytest.mark.parametrize("case", OWN_DECLINES)
def test_where_the_own_kernels_checker_declines_the_parents_claim_stands(monkeypatch, case):
    """What the checker refuses at trinity's size, and at a size that runs here
    who the execution trace names for the same refusal: ``flash`` for bf16 and
    float16, the decomposition for float32, as before PR 42."""
    import jax.numpy as jnp

    from thunder_tpu.executors import pallasex

    how, owner = OWN_DECLINES[case]
    dtype, d = how.get("dtype", dtypes.bfloat16), how.get("d", 128)
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    big = [_like((1, h, how.get("t", 32768), d), dtype) for h in (32, 4, 4)]
    assert not pallasex._window_attend_checker(*big, window=how.get("window", 2048))
    if "scope" in how:  # a scope of VMEM that holds less: at 256 positions the span no longer fits what the checker reckons
        monkeypatch.setattr(pallasex, "_SCOPED_VMEM_DEFAULT", how["scope"])
    t = how.get("small_t", 256)
    q, k, v = (jnp.asarray(a, dtypes.to_jax_dtype(dtype)) for a in qkv(t, 4, 2, d=d, seed=1))
    jfn = thunder_tpu.jit(lambda q, k, v: ttorch.window_attention(q, k, v, window=100))
    got = np.asarray(jfn(q, k, v).astype(jnp.float32))
    name = thunder_tpu.last_traces(jfn)[-1].bound_symbols[0].sym.executor.name
    assert (name if name in ("flash", "pallas") else "neither") == owner
    want = masked_softmax_attention(*(np.asarray(a.astype(jnp.float32)) for a in (q, k, v)), 100)
    assert rel(got, want) < (1e-5 if owner == "neither" else 1e-2)


@pytest.mark.parametrize("t,window,tiles", [(32768, 2048, 93), (8192, 2048, 21), (4096, 1024, 7), (4096, 1025, 7), (4096, 1026, 9), (2048, 4096, 3)],
                         ids=lambda x: str(x))
def test_the_tiles_the_kernel_visits_by_hand(t, window, tiles):
    """splash's tiles of 1024: a query tile visits its own key tile and those its
    window reaches back into; the count is the kernel's own table's."""
    from thunder_tpu.executors import flashex

    assert flashex._fit_block(t) == 1024
    assert flashex.splash_window_tiles(t, window) == tiles * 1024 * 1024
    by_hand = sum(1 for i in range(t // 1024) for j in range(t // 1024)
                  if j <= i and (i * 1024 - (j * 1024 + 1023)) < window)
    assert by_hand == tiles


@pytest.mark.parametrize("t,window,tiles", [(32768, 2048, 36 + 120 * 9), (8192, 2048, 36 + 24 * 9), (4096, 1024, 10 + 12 * 5),
                                            (4096, 1025, 10 + 12 * 5), (4096, 1281, 15 + 11 * 6), (2048, 4096, 36), (256, 128, 1)],
                         ids=lambda x: str(x))
def test_the_tiles_the_own_kernel_visits_by_hand(t, window, tiles):
    """Tiles of 256 x 256: a query tile walks from the key tile that holds its
    first query's oldest key to its own, nine at a window of 2,048 (2,304 keys
    for 2,048) and fewer in the first eight. ``flashex.window_tiles`` is that
    count where the own kernel takes bf16 heads of 128 at that length and
    window, and splash's where it does not."""
    from thunder_tpu.executors import flashex, pallasex

    assert pallasex._WINDOW_ATTEND_TILES == (256, 256)
    assert pallasex.window_attend_tiles(t, window) == tiles * 256 * 256
    by_hand = sum(1 for i in range(t // 256) for j in range(t // 256) if j <= i and (i * 256 - (j * 256 + 255)) < window)
    assert by_hand == tiles
    assert flashex.window_tiles(t, window) == tiles * 256 * 256
    if (t, window) == (32768, 2048):
        pairs = t * window - window * (window - 1) // 2
        assert round(tiles * 256 * 256 / pairs, 4) == 1.125 and round(flashex.splash_window_tiles(t, window) / pairs, 4) == 1.5
    if t <= 4096:  # splash's table of 128-wide tiles is slow to build past that
        assert flashex.window_tiles(t + 128, window) == flashex.splash_window_tiles(t + 128, window)  # no tile divides it


def test_the_counter_is_splashs_where_the_own_kernel_declines_the_window():
    from thunder_tpu.executors import flashex, pallasex

    assert not pallasex.window_attend_fits(16384, 16384, 1, 128, 2)  # causal over 16,384: a span past the scope of VMEM
    assert flashex.window_tiles(16384, 16384) == flashex.splash_window_tiles(16384, 16384) == 136 * 1024 * 1024


def test_the_causal_kernel_is_yesterdays_whatever_the_window_cache_holds():
    from thunder_tpu.executors import flashex

    causal = flashex._splash_kernel(2, 256, 256, True, 0, True, True)
    local = flashex._splash_kernel(2, 256, 256, True, 0, True, True, window=100)
    assert causal is flashex._splash_kernel(2, 256, 256, True, 0, True, True) and causal is not local
    assert np.asarray(causal.fwd_mask_info.block_mask).shape == np.asarray(local.fwd_mask_info.block_mask).shape


# -----------------------------------------------------------------------------
# Mistral's declared window
# -----------------------------------------------------------------------------


@pytest.mark.parametrize("t", [48, 16, 10], ids=["past-the-window", "the-windows-length", "under-the-window"])
def test_mistrals_sibling_passes_its_window(t):
    """``mistral-7b`` declares a window of 4096 and its sequences stop there,
    where window and causal agree: its program is the causal one. The tiny
    sibling has room beyond its window of 16 and attends within it."""
    big, tiny = gpt.name_to_config("mistral-7b"), gpt.name_to_config("mistral-tiny")
    assert (big.sliding_window, big.block_size, set(big.layer_types), len(big.layer_types)) == (4096, 4096, {"sliding_attention"}, 32)
    assert (tiny.sliding_window, set(tiny.layer_types)) == (16, {"sliding_attention"})
    params = gpt.init_params(tiny, dtype=dtypes.float32)
    idx = np.random.RandomState(t).randint(0, 96, (2, t)).astype(np.int32)
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, tiny))
    got = np.asarray(jfn(params, idx))
    lines = [b for b in thunder_tpu.last_traces(jfn)[0].bound_symbols if "attention" in str(b.sym.id)]
    if t > 16:
        assert [(str(b.sym.id), b.kwargs["window"]) for b in lines] == [("torch.window_attention", 16)] * 2
    else:
        assert [str(b.sym.id) for b in lines] == ["torch.scaled_dot_product_attention"] * 2
    causal = dataclasses.replace(tiny, layer_types=(), sliding_window=None)
    plain = np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, causal))(params, idx))
    # the first 16 positions see the same keys either way; beyond them the window forgets
    np.testing.assert_allclose(got[:, :16], plain[:, :16], rtol=1e-4, atol=1e-5)
    assert (t <= 16) or rel(got[:, 16:], plain[:, 16:]) > 1e-3


# -----------------------------------------------------------------------------
# The comparison that decides ``correct``
# -----------------------------------------------------------------------------


def _the_window_layers_run_causal(monkeypatch, cfg, params):
    return dataclasses.replace(cfg, sliding_window=10 ** 9), params


def _rope_on_the_global_layer(monkeypatch, cfg, params):
    return dataclasses.replace(cfg, attn_rope=True), params


def _a_norm_left_out(which):
    def mutate(monkeypatch, cfg, params):
        ones = lambda block: {**block, which: {"weight": block[which]["weight"] * 0 + 1}}
        real = gpt._norm
        monkeypatch.setattr(gpt, "_norm", lambda x, p, config: x if p.get("left_out") else real(x, p, config))
        mark = lambda block: {**block, which: {**block[which], "left_out": True}}
        return cfg, {**params, "dense_blocks": [mark(b) for b in params["dense_blocks"]],
                     "moe_blocks": [mark(b) for b in params["moe_blocks"]]}
    return mutate


def _the_gate_left_out(monkeypatch, cfg, params):
    return dataclasses.replace(cfg, attn_output_gate=False), params


def _one_experts_down_projection_zeroed(monkeypatch, cfg, params):
    def zeroed(block):
        down = block["mlp"]["experts_down"]
        return {**block, "mlp": {**block["mlp"], "experts_down": down.at[1].set(0.0)}}
    return cfg, {**params, "moe_blocks": [zeroed(b) for b in params["moe_blocks"]]}


def _the_bias_left_out_of_the_choice(monkeypatch, cfg, params):
    return dataclasses.replace(cfg, router_bias=False), {**params, "moe_blocks": [
        {**b, "mlp": {k: v for k, v in b["mlp"].items() if k != "router_bias"}} for b in params["moe_blocks"]]}


def _the_embedding_not_scaled(monkeypatch, cfg, params):
    return dataclasses.replace(cfg, embedding_scale=1.0), params


MUTATIONS = {"the-window-layers-run-causal": _the_window_layers_run_causal,
             "rope-on-the-global-layer": _rope_on_the_global_layer,
             "the-post-attention-norm-left-out": _a_norm_left_out("post_attn_norm"),
             "the-post-mlp-norm-left-out": _a_norm_left_out("post_mlp_norm"),
             "the-gate-left-out": _the_gate_left_out,
             "one-experts-down-projection-zeroed": _one_experts_down_projection_zeroed,
             "the-bias-left-out-of-the-choice": _the_bias_left_out_of_the_choice,
             "the-embedding-not-scaled": _the_embedding_not_scaled}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_a_mutated_system_fails_the_cells_comparison_at_rehearsal_size(monkeypatch, name):
    """Each departure from the equations fails the comparison the cell's check
    makes (``perfbench/checks_window_moe.py``) at the stand-in's sizes in
    float32, where the unmutated system is within a hundredth of the limits."""
    import jax.numpy as jnp

    from perfbench import checks_window_moe
    from perfbench.reference import afmoe

    cfg, params, tree = built()
    idx = batch()
    last = 64
    want, margin = (np.asarray(a) for a in afmoe.forward_and_margin(tree, jnp.asarray(idx), KEYS, last=last))
    clean = np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg, last=last))(params, idx))
    sound = checks_window_moe.compare_logits(clean, want, margin)
    assert sound["ok"] and sound["logits_rel_l2"] < 1e-2 * sound["logits_rtol"], sound
    mutated, changed = MUTATIONS[name](monkeypatch, cfg, params)
    got = np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, mutated, last=last))(changed, idx))
    verdict = checks_window_moe.compare_logits(got, want, margin)
    assert not verdict["ok"], verdict
