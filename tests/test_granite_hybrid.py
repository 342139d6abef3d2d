"""Granite 4.0-H's blocks at test size on the CPU, float32, seeded weights:
Mamba-2 mixers (a packed projection, a causal convolution with a bias and a
SiLU, a state-space recurrence whose decay each token sets, a gated norm) beside
rope-less grouped-query attention under its own softmax scale, SwiGLU MLPs, the
four multipliers, a head that is the embedding and runs on the last positions.
Against the plain reference (``perfbench/reference/granite_hybrid.py``), which
knows nothing of the program and carries the state a position at a time, and
against the recurrence written here in numpy."""

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

with open(os.path.join(REPO, "perfbench", "configs", "granite-4.0-h-micro.json"), encoding="utf-8") as _f:
    _FILE = json.load(_f)
# The stand-in (``--rehearse``'s sizes): mamba, attention, mamba, mamba, attention, mamba; 1024 wide, 32 state-space heads of 64 on a
# state of 128, 16 query heads of 64 on 4 key-value heads, the program's chunk 64.
KEYS = {**_FILE, **_FILE["stand_in"]}
T = 256


def built(keys=KEYS, seed=5):
    """(the program's config, its parameters as the cell draws them, the same arrays as the reference takes them)."""
    import jax

    from perfbench.jobs import forward_ssm, forward_window_moe, gpt_model

    cfg = gpt_model.gpt_config(keys, rehearse=True)
    shapes = jax.eval_shape(lambda: gpt.init_params(cfg, dtype=dtypes.float32, device_init=True))
    params = forward_ssm.with_ssm_draw(forward_window_moe.draw(shapes, seed), keys)
    return cfg, params, forward_window_moe.for_reference(params, 0)


def batch(t=T, seed=0, b=1):
    return np.random.RandomState(seed).randint(0, KEYS["vocab_size"], (b, t)).astype(np.int32)


def rel(got, want):
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(np.asarray(got, np.float64) - want) / np.linalg.norm(want))


def scan_inputs(t, heads=4, width=8, groups=1, state=16, b=2, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(b, t, heads, width).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (b, t, heads))).astype(np.float32)
    A = -rng.uniform(1, 16, (heads,)).astype(np.float32)
    B, C = (rng.randn(b, t, groups, state).astype(np.float32) for _ in range(2))
    return x, dt, A, B, C, rng.randn(heads).astype(np.float32)


def recurrence(x, dt, A, B, C, D):
    """The equations as numpy writes them, float64, a position at a time: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    B_t^T``, ``y_t = S_t C_t + D x_t``; a group's B and C for each of its heads."""
    x, dt, A, B, C, D = (np.asarray(a, np.float64) for a in (x, dt, A, B, C, D))
    b, t, heads, width = x.shape
    rep = heads // B.shape[2]
    B, C = np.repeat(B, rep, 2), np.repeat(C, rep, 2)
    state, y = np.zeros((b, heads, width, B.shape[-1])), np.zeros_like(x)
    for i in range(t):
        state = np.exp(dt[:, i] * A)[..., None, None] * state + (dt[:, i, :, None] * x[:, i])[..., None] * B[:, i, :, None, :]
        y[:, i] = (state * C[:, i, :, None, :]).sum(-1) + D[:, None] * x[:, i]
    return y


# -----------------------------------------------------------------------------
# The model
# -----------------------------------------------------------------------------


def test_the_registry_lists_the_model_at_its_published_sizes():
    """Every published key of the configuration file is the registry's and
    nothing is cut: the benchmark lays no key over the entry."""
    from perfbench import manifest
    from perfbench.jobs import forward_ssm, gpt_model

    cell = manifest.load_cell("granite-4.0-h-micro.fwd-t16k")
    listed = gpt.name_to_config("granite-4.0-h-micro")
    assert gpt_model.gpt_config(manifest.published(cell)) == listed and _FILE["reduced"] == []
    assert (listed.n_layer, listed.n_embd, listed.n_head, listed.query_groups, listed.head_size) == (40, 2048, 32, 8, 64)
    assert (listed.mlp_hidden, listed.padded_vocab_size, listed.block_size, listed.tie_embeddings) == (8192, 100352, 131072, True)
    assert listed.layer_types == tuple(forward_ssm.MIXERS[m] for m in _FILE["layer_types"]) and len(listed.layer_types) == 40
    assert [i for i, m in enumerate(listed.layer_types) if m == "full_attention"] == [5, 15, 25, 35]
    assert listed.layer_types.count("mamba") == 36
    assert (listed.ssm_n_head, listed.ssm_head_dim, listed.ssm_state, listed.ssm_groups, listed.ssm_conv_kernel) == (64, 64, 128, 1, 4)
    assert listed.ssm_inner == _FILE["mamba_expand"] * _FILE["hidden_size"] == 4096 and listed.ssm_conv_channels == 4352
    assert (listed.attention_scale, listed.attn_rope) == (_FILE["attention_multiplier"], False)
    assert (listed.embedding_scale, listed.residual_scale, listed.logit_divisor) == (12.0, 0.22, 8.0)
    assert (listed.norm_class, listed.norm_eps, listed.bias, listed.mlp_class) == ("RMSNorm", 1e-5, False, "LLaMAMLP")
    # the chunk is the program's own and published: the torch module's constant unless a config says otherwise
    assert listed.ssm_chunk is None and listed.ssm_chunk_size == ttorch.SSM_SCAN_CHUNK == 256
    assert gpt.name_to_config("granite-h-tiny").ssm_chunk_size == 64
    # every default is yesterday's program: no scale handed to sdpa, no state-space head
    plain = gpt.name_to_config("llama-2-7b")
    assert (plain.attention_scale, plain.ssm_n_head, plain.ssm_inner) == (None, 0, 0)


def test_the_parameter_tree_has_the_mamba_leaves_and_the_issues_count_of_parameters():
    import jax

    cfg = gpt.name_to_config("granite-4.0-h-micro")
    shapes = jax.eval_shape(lambda: gpt.init_params(cfg, device_init=True))
    assert len(shapes["blocks"]) == 40 and "lm_head_w" not in shapes and shapes["wte"].shape == (100352, 2048)
    mamba, attn = shapes["blocks"][0], shapes["blocks"][5]
    assert sorted(mamba) == ["mamba", "mlp", "norm_1", "norm_2"] and sorted(attn) == ["attn", "mlp", "norm_1", "norm_2"]
    m = mamba["mamba"]
    assert sorted(m) == ["A_log", "D", "conv_b", "conv_w", "dt_bias", "in_proj_w", "norm", "out_proj_w"]
    assert m["in_proj_w"].shape == (4096 + 4352 + 64, 2048) and m["out_proj_w"].shape == (2048, 4096)
    assert m["conv_w"].shape == (4352, 4) and m["conv_b"].shape == (4352,) and m["norm"]["weight"].shape == (4096,)
    for leaf in ("A_log", "dt_bias", "D"):  # float32 whatever the weights are: the decay is computed in float32
        assert m[leaf].shape == (64,) and m[leaf].dtype == np.float32
    assert m["in_proj_w"].dtype == attn["attn"]["qkv_w"].dtype == np.dtype("bfloat16")
    assert sorted(attn["attn"]) == ["proj_w", "qkv_w"] and attn["attn"]["qkv_w"].shape == ((32 + 2 * 8) * 64, 2048)
    assert mamba["mlp"]["fc_1_w"].shape == (8192, 2048) and mamba["mlp"]["proj_w"].shape == (2048, 8192)
    count = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(tree))
    assert round(count(mamba) / 1e6, 2) == 76.18 and round(count(attn) / 1e6, 2) == 60.82  # ISSUE 44
    assert round(count(shapes) / 1e9, 2) == 3.19  # 6.38 GB in bf16


def test_the_host_draw_of_the_state_space_leaves_is_mamba_2s():
    params = gpt.init_params(gpt.name_to_config("granite-h-tiny"), dtype=dtypes.float32, seed=3)
    m = params["blocks"][0]["mamba"]
    A = np.exp(np.asarray(m["A_log"]))
    step = np.log1p(np.exp(np.asarray(m["dt_bias"], np.float64)))  # softplus: the step a zero input gives
    assert ((1 <= A) & (A <= 16)).all() and ((0.000999 <= step) & (step <= 0.1001)).all()
    assert (np.asarray(m["D"]) == 1).all() and not np.asarray(m["conv_b"]).any()


@pytest.mark.parametrize("t", [T, 200, 48], ids=["four-chunks", "unaligned", "under-a-chunk"])
def test_forward_through_jit_agrees_with_the_reference(t):
    import jax.numpy as jnp

    from perfbench.reference import granite_hybrid

    cfg, params, tree = built()
    idx = batch(t, b=2)
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    got = np.asarray(jfn(params, idx))
    want = np.asarray(granite_hybrid.forward(tree, jnp.asarray(idx), KEYS))
    assert got.shape == want.shape == (2, t, KEYS["vocab_size"]) and rel(got, want) < 2e-5
    assert thunder_tpu.cache_info(jfn)["degradation_level"] == 0


def test_forward_last_is_the_last_rows_of_forward():
    cfg, params, _ = built()
    idx = batch(96)
    whole = np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))(params, idx))
    last = np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg, last=16))(params, idx))
    np.testing.assert_allclose(last, whole[:, -16:], rtol=1e-5, atol=1e-6)


def test_the_models_regions_are_named_in_the_generated_program_and_in_the_hlo():
    import jax

    from perfbench.jobs import forward_ssm
    from perfbench.layer_metrics import _regions

    cfg, params, _ = built()
    idx = batch()
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    jfn(params, idx)
    run = thunder_tpu.last_traces(jfn)[-1]
    opened = [line.strip()[len("with __region('"):-len("'):")] for line in run.python().splitlines()
              if line.strip().startswith("with __region(")]
    mamba = ["ssm.conv", "ssm.scan", "ssm.gate_norm"]
    assert opened == mamba + ["attn.full"] + mamba * 2 + ["attn.full"] + mamba
    compiled = jax.jit(run.python_callable()).lower(*jax.tree_util.tree_leaves((params, idx))).compile()
    found = _regions.of_instructions(compiled.as_text(), forward_ssm.REGIONS)
    assert set(found.values()) == set(forward_ssm.REGIONS)


def test_the_attention_layers_hand_sdpa_the_published_scale_and_no_rope():
    cfg, params, _ = built()
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    jfn(params, batch(64))
    first = thunder_tpu.last_traces(jfn)[0].bound_symbols
    calls = [b for b in first if str(b.sym.id) == "torch.scaled_dot_product_attention"]
    assert len(calls) == 2 and all(c.kwargs["scale"] == 0.015625 and c.kwargs["is_causal"] is True for c in calls)
    assert "torch.apply_rope" not in {str(b.sym.id) for b in first}
    # a model without the field hands sdpa nothing: its trace is yesterday's
    tiny = gpt.name_to_config("llama-tiny")
    plain = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, tiny))
    plain(gpt.init_params(tiny, dtype=dtypes.float32, seed=0), np.zeros((1, 8), np.int32))
    call = next(b for b in thunder_tpu.last_traces(plain)[0].bound_symbols if str(b.sym.id) == "torch.scaled_dot_product_attention")
    assert "scale" not in call.kwargs


def test_flash_claims_the_attention_layers_under_the_published_scale(monkeypatch):
    """``flashex`` takes the bf16 call with its ``scale`` (interpreted here) and gives the decomposition's numbers."""
    import jax
    import jax.numpy as jnp

    cfg, params, _ = built()
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 or a.shape[0] > 64 else a, params)
    idx = batch(128)
    plain = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg, last=32))(params, idx)
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg, last=32))
    claimed = jfn(params, idx)
    owners = [b.sym.executor.name for b in thunder_tpu.last_traces(jfn)[-1].bound_symbols
              if b.sym.executor is not None and b.sym.executor.name in ("flash", "pallas")]
    assert owners == ["flash", "flash"]
    assert rel(np.asarray(claimed.astype(jnp.float32)), np.asarray(plain.astype(jnp.float32))) < 2e-2


def _bf16_sibling(chunk=128):
    """The stand-in with its matrices in bf16 and a chunk the kernel takes (its own 64 is the decomposition's)."""
    import jax
    import jax.numpy as jnp

    cfg, params, _ = built()
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 or a.shape[0] > 64 else a, params)
    return dataclasses.replace(cfg, ssm_chunk=chunk), params


def test_pallas_claims_every_scan_at_the_kernels_shapes(monkeypatch):
    """bf16, 32 heads of 64 on a state of 128, 256 positions in chunks of 128: ``pallas`` owns the four scans
    (interpreted here; as ``ssm_conv_scan``, which convolves in the scan's call on the projection's columns:
    ``tests/test_ssm_layout.py``), ``flash`` the two attention calls, and the logits are
    the unclaimed program's."""
    import jax.numpy as jnp

    cfg, params = _bf16_sibling()
    idx = batch(256)
    plain = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg, last=32))(params, idx)
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg, last=32))
    claimed = jfn(params, idx)
    owners = [(b.sym.name, b.sym.executor.name) for b in thunder_tpu.last_traces(jfn)[-1].bound_symbols
              if b.sym.executor is not None and b.sym.executor.name in ("flash", "pallas")]
    scan, attention = ("ssm_conv_scan", "pallas"), ("scaled_dot_product_attention", "flash")
    assert owners == [scan, attention, scan, scan, attention, scan]
    assert rel(np.asarray(claimed.astype(jnp.float32)), np.asarray(plain.astype(jnp.float32))) < 2e-2
    # at the stand-in's own chunk of 64 the scans are the decomposition's, as before
    own = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, dataclasses.replace(cfg, ssm_chunk=64), last=32))
    own(params, idx)
    assert "ssm_scan" not in [b.sym.name for b in thunder_tpu.last_traces(own)[-1].bound_symbols]


def test_the_trace_vjp_differentiates_the_decomposition_where_the_forward_would_be_claimed(monkeypatch):
    """With the kernel registered and its shapes met, ``value_and_grad`` still descends into ``ssm_scan``'s
    decomposition (there is no backward kernel): nothing of the step is the scan kernel's, and the loss and the
    gradients are those of the step with nothing forced."""
    import jax

    cfg, params = _bf16_sibling()
    idx = batch(256)
    targets = np.roll(idx, -1, 1)
    step = lambda: thunder_tpu.value_and_grad(lambda p, i, t: gpt.loss_fn(p, i, t, cfg))
    want_loss, want = step()(params, idx, targets)
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    forced = step()
    loss, got = forced(params, idx, targets)
    names = [b.sym.name for trace in thunder_tpu.last_traces(forced)[-1:] for b in trace.bound_symbols]
    assert "ssm_scan" not in names
    assert abs(float(loss) - float(want_loss)) < 2e-2 * abs(float(want_loss))
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(g, np.float32)).all() and g.shape == w.shape


def test_a_roped_sibling_keeps_its_scale_through_the_layout_pass(monkeypatch):
    """``fold_attention_layouts`` moves the softmax scale onto q's head call: the
    published one where the model has one, not ``head_size ** -0.5``."""
    import jax.numpy as jnp

    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    cfg = dataclasses.replace(gpt.name_to_config("llama-tiny"), attention_scale=0.03125, n_layer=1, n_embd=128, n_head=2,
                              n_query_groups=1, intermediate_size=256)
    params = gpt.init_params(cfg, dtype=dtypes.bfloat16, seed=0)
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    idx = batch(128) % cfg.padded_vocab_size
    got = jfn(params, idx)
    run = thunder_tpu.last_traces(jfn)[-1].bound_symbols
    assert sorted(float(b.args[5]) for b in run if str(b.sym.id) == "torch.apply_rope_heads") == [0.03125, 1.0]  # q's, k's
    call = next(b for b in run if "scaled_dot_product_attention" in str(b.sym.id))
    assert call.kwargs["scale"] == 1.0 and call.sym.executor.name == "flash"
    monkeypatch.delenv("THUNDER_FLASH_FORCE")
    want = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg), executors=["jax"])(params, idx)
    assert rel(np.asarray(got.astype(jnp.float32)), np.asarray(want.astype(jnp.float32))) < 2e-2


# -----------------------------------------------------------------------------
# The composites
# -----------------------------------------------------------------------------


@pytest.mark.parametrize("t,chunk,heads,groups", [(64, 16, 4, 1), (70, 16, 4, 2), (16, 16, 4, 1), (10, 16, 4, 4), (96, 32, 2, 1)],
                         ids=["four-chunks", "unaligned-two-groups", "one-chunk", "under-a-chunk", "three-chunks"])
def test_the_scans_decomposition_is_the_recurrence(t, chunk, heads, groups):
    x, dt, A, B, C, D = scan_inputs(t, heads=heads, groups=groups)
    jfn = thunder_tpu.jit(lambda *a: ttorch.ssm_scan(*a, chunk=chunk))
    got = np.asarray(jfn(x, dt, A, B, C, D))
    assert got.shape == x.shape and got.dtype == np.float32 and rel(got, recurrence(x, dt, A, B, C, D)) < 1e-5
    # without the skip the D term is gone and nothing else
    bare = np.asarray(thunder_tpu.jit(lambda *a: ttorch.ssm_scan(*a, None, chunk=chunk))(x, dt, A, B, C))
    np.testing.assert_allclose(got - bare, D[:, None] * x, rtol=1e-4, atol=1e-5)


def test_two_chunk_sizes_give_the_same_numbers_and_the_default_is_the_published_one():
    x, dt, A, B, C, D = scan_inputs(300, heads=2, width=16, state=8, b=1, seed=3)
    small, large, default = (np.asarray(thunder_tpu.jit(lambda *a, c=c: ttorch.ssm_scan(*a, chunk=c))(x, dt, A, B, C, D))
                             for c in (32, 128, None))
    assert rel(small, large) < 1e-5 and rel(default, large) < 1e-5  # 300 positions at the default: 256 and a padded 44
    assert rel(large, recurrence(x, dt, A, B, C, D)) < 1e-5


def test_the_scan_is_causal_and_remembers_past_a_chunk():
    x, dt, A, B, C, D = scan_inputs(64, heads=2, width=4, state=4, b=1)
    dt = np.full_like(dt, 1e-3)  # a slow head: exp(-1e-3 A) a step
    jfn = thunder_tpu.jit(lambda *a: ttorch.ssm_scan(*a, chunk=16))
    base = np.asarray(jfn(x, dt, A, B, C, D))
    moved = x.copy()
    moved[:, 5] += 1.0
    got = np.asarray(jfn(moved, dt, A, B, C, D))
    np.testing.assert_array_equal(got[:, :5], base[:, :5])          # nothing before the change hears it
    assert np.abs(got[:, 60] - base[:, 60]).max() > 1e-6            # three chunks on, the state still does


def test_no_exponent_of_the_scan_is_positive_so_fast_heads_do_not_overflow():
    x, dt, A, B, C, D = scan_inputs(64, heads=2, width=4, state=4, b=1)
    dt, A = np.full_like(dt, 5.0), np.full_like(A, -16.0)  # exp(-80) a step: anything exponentiated the wrong way is inf
    got = np.asarray(thunder_tpu.jit(lambda *a: ttorch.ssm_scan(*a, chunk=16))(x, dt, A, B, C, D))
    assert np.isfinite(got).all() and rel(got, recurrence(x, dt, A, B, C, D)) < 1e-5


def test_the_convolution_is_causal_depthwise_with_its_bias_and_silu():
    rng = np.random.RandomState(0)
    x, w, b = rng.randn(2, 20, 6).astype(np.float32), rng.randn(6, 4).astype(np.float32), rng.randn(6).astype(np.float32)
    padded = np.concatenate([np.zeros((2, 3, 6), np.float32), x], 1)
    c = sum(padded[:, j:j + 20] * w[:, j] for j in range(4)) + b
    want = c / (1 + np.exp(-c))
    got = np.asarray(thunder_tpu.jit(ttorch.causal_conv_silu)(x, w, b))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    unbiased = np.asarray(thunder_tpu.jit(lambda x, w: ttorch.causal_conv_silu(x, w))(x, w))
    np.testing.assert_allclose(unbiased, (c - b) / (1 + np.exp(-(c - b))), rtol=1e-5, atol=1e-6)
    assert got.dtype == np.float32 and np.abs(got[:, 0] - want[:, 0]).max() < 1e-6  # position 0 sees zeros before it


def test_the_gated_norm_gates_first_and_norms_all_features():
    rng = np.random.RandomState(0)
    y, z, w = rng.randn(2, 5, 12).astype(np.float32), rng.randn(2, 5, 12).astype(np.float32), rng.rand(12).astype(np.float32) + 0.5
    g = y * (z / (1 + np.exp(-z)))
    want = g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5) * w
    got = np.asarray(thunder_tpu.jit(lambda y, z, w: ttorch.gated_rms_norm(y, z, w, 1e-5))(y, z, w))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_bf16_operands_keep_the_decay_in_float32():
    """The cell's dtypes: x, B and C bf16, dt and A float32. Against the float64
    recurrence on the same rounded operands the error is bf16's, not the decay's."""
    import jax.numpy as jnp

    x, dt, A, B, C, D = scan_inputs(128, heads=2, width=16, state=16, b=1, seed=2)
    low = lambda a: jnp.asarray(a, jnp.bfloat16)
    got = thunder_tpu.jit(lambda *a: ttorch.ssm_scan(*a, chunk=32))(low(x), dt, A, low(B), low(C), D)
    assert got.dtype == jnp.bfloat16
    rounded = [np.asarray(low(a).astype(jnp.float32)) for a in (x, B, C)]
    assert rel(np.asarray(got.astype(jnp.float32)), recurrence(rounded[0], dt, A, rounded[1], rounded[2], D)) < 2e-2


# -----------------------------------------------------------------------------
# The comparison that decides ``correct``
# -----------------------------------------------------------------------------


def _patched(name, make):
    def mutate(monkeypatch, cfg):
        monkeypatch.setattr(ttorch, name, make(getattr(ttorch, name)))
        return cfg
    return mutate


def _no_carry(real):
    def scan(x, dt, A, B, C, D=None, chunk=None):  # every chunk starts from an empty state
        return ttorch.cat([real(x[:, c:c + chunk], dt[:, c:c + chunk], A, B[:, c:c + chunk], C[:, c:c + chunk], D, chunk)
                           for c in range(0, x.shape[1], chunk)], 1)
    return scan


def _constant_decay(real):
    def scan(x, dt, A, B, C, D=None, chunk=None):  # a head decays by its mean step; what a token adds keeps its own
        mean = ttorch.mean(dt, 1, True)
        y = real((x * ttorch.unsqueeze(dt / mean, -1)).to(x.dtype), ttorch.expand(mean, dt.shape), A, B, C, None, chunk)
        return (y + x * ttorch.reshape(D, (1, 1, -1, 1))).to(x.dtype)
    return scan


MUTATIONS = {
    "the-state-not-carried-between-chunks": _patched("ssm_scan", _no_carry),
    "the-decay-a-constant-a-head": _patched("ssm_scan", _constant_decay),
    "D-dropped": _patched("ssm_scan", lambda real: lambda x, dt, A, B, C, D=None, chunk=None: real(x, dt, A, B, C, None, chunk)),
    "the-convolution-dropped": _patched("causal_conv_silu", lambda real: lambda x, w, b=None: ttorch.silu(x * w[:, -1] + b)),
    "the-convolutions-bias-dropped": _patched("causal_conv_silu", lambda real: lambda x, w, b=None: real(x, w, None)),
    "the-gate-dropped": _patched("gated_rms_norm", lambda real: lambda y, z, w, eps: ttorch.rms_norm(y, (y.shape[-1],), w, eps)),
    "softplus-dropped": _patched("softplus", lambda real: lambda a, *args, **kwargs: a),
    "attention-multiplier-at-sdpas-default": lambda monkeypatch, cfg: dataclasses.replace(cfg, attention_scale=None),
    "residual-multiplier-dropped": lambda monkeypatch, cfg: dataclasses.replace(cfg, residual_scale=1.0),
}


@pytest.fixture(scope="module")
def sound():
    """(config, parameters, ids, the reference's logits of the last 64 positions) at the stand-in's sizes."""
    import jax.numpy as jnp

    from perfbench.reference import granite_hybrid

    cfg, params, tree = built()
    idx = batch()
    return cfg, params, idx, np.asarray(granite_hybrid.forward(tree, jnp.asarray(idx), KEYS, last=64))


def test_the_unmutated_system_is_within_a_thousandth_of_the_limit(sound):
    from perfbench import checks_ssm

    cfg, params, idx, want = sound
    got = np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg, last=64))(params, idx))
    verdict = checks_ssm.compare_logits(got, want)
    assert verdict["ok"] and verdict["logits_rel_l2"] < 1e-3 * verdict["logits_rtol"], verdict


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_a_mutated_system_fails_the_cells_comparison_at_rehearsal_size(monkeypatch, sound, name):
    """Each departure from the equations fails the comparison the cell's check
    makes (``perfbench/checks_ssm.py``) at the stand-in's sizes in float32."""
    from perfbench import checks_ssm

    cfg, params, idx, want = sound
    mutated = MUTATIONS[name](monkeypatch, cfg)
    got = np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, mutated, last=64))(params, idx))
    verdict = checks_ssm.compare_logits(got, want)
    assert not verdict["ok"], verdict


# -----------------------------------------------------------------------------
# Gradients through the trace VJP
# -----------------------------------------------------------------------------

GRAD_T = 96  # a chunk of 64 and a padded one


@pytest.fixture(scope="module")
def gradients():
    """{leaf kind: [(the trace VJP's gradient, ``jax.grad`` of the reference's)] a layer} of the tiny sibling's
    next-token loss, one compile each."""
    import jax
    import jax.numpy as jnp

    from perfbench import weights
    from perfbench.jobs import forward_ssm, forward_window_moe
    from perfbench.reference import granite_hybrid

    cfg = gpt.name_to_config("granite-h-tiny")
    keys = {**KEYS, "hidden_size": 128, "num_attention_heads": 2, "num_key_value_heads": 1, "mamba_n_heads": 4,
            "mamba_d_state": 32, "vocab_size": 96, "shared_intermediate_size": 256, "intermediate_size": 256}
    shapes = jax.eval_shape(lambda: gpt.init_params(cfg, dtype=dtypes.float32, device_init=True))
    params = forward_ssm.with_ssm_draw(forward_window_moe.draw(shapes, 9), keys)
    idx = np.random.RandomState(1).randint(0, 96, (2, GRAD_T)).astype(np.int32)
    targets = np.roll(idx, -1, 1)

    _, got = thunder_tpu.value_and_grad(lambda p, i, t: gpt.loss_fn(p, i, t, cfg))(params, idx, targets)

    def loss(tree):
        logits = granite_hybrid.forward(tree, jnp.asarray(idx), keys)
        logp = jax.nn.log_softmax(logits.reshape(-1, logits.shape[-1]), -1)
        return -jnp.take_along_axis(logp, jnp.asarray(targets).reshape(-1, 1), 1).mean()

    want = jax.grad(loss)(forward_window_moe.for_reference(params, 0))
    by_kind: dict = {}
    for (kind, layer, _), g in zip(weights.leaf_kinds(params), got):
        ref = want[kind] if layer is None else want["layers"][layer][kind[len("blocks/*/"):]]
        by_kind.setdefault(kind, []).append((np.asarray(g), np.asarray(ref)))
    return by_kind


GRAD_KINDS = ["wte", "ln_f/weight", "blocks/*/norm_1/weight", "blocks/*/norm_2/weight", "blocks/*/mamba/in_proj_w",
              "blocks/*/mamba/conv_w", "blocks/*/mamba/conv_b", "blocks/*/mamba/dt_bias", "blocks/*/mamba/A_log",
              "blocks/*/mamba/D", "blocks/*/mamba/norm/weight", "blocks/*/mamba/out_proj_w", "blocks/*/attn/qkv_w",
              "blocks/*/attn/proj_w", "blocks/*/mlp/fc_1_w", "blocks/*/mlp/fc_2_w", "blocks/*/mlp/proj_w"]


def test_every_kind_of_leaf_has_a_gradient_case(gradients):
    assert sorted(gradients) == sorted(GRAD_KINDS)
    assert len(gradients["blocks/*/mamba/A_log"]) == 4 and len(gradients["blocks/*/attn/qkv_w"]) == 2


@pytest.mark.parametrize("kind", GRAD_KINDS)
def test_gradients_through_the_trace_vjp_are_the_references(gradients, kind):
    """The trace VJP differentiates the composites' decompositions as they stand
    (the chunked scan with its carried state, the shifted products, the gated norm)."""
    for got, want in gradients[kind]:
        assert got.shape == want.shape and np.linalg.norm(want) > 0
        assert np.linalg.norm(got - want) <= 2e-4 * np.linalg.norm(want), kind
