"""transforms/attention_layout.py: q, k and v leave their projection
head-major. The pass rewrites the idiom ``models/gpt.py::_attention`` writes
(and the part of it that latent attention has, and ``_linear_attention``'s),
declines whatever it cannot prove is that idiom, and the rewritten program
computes what was written."""

import math

import numpy as np
import pytest

import thunder_tpu
import thunder_tpu.clang as clang
import thunder_tpu.torch as ttorch
from thunder_tpu import pipeline
from thunder_tpu.api import trace_program
from thunder_tpu.core import dtypes
from thunder_tpu.extend import resolve_executors
from thunder_tpu.transforms import attention_layout
from thunder_tpu.transforms.common import dce

B, T, C = 2, 128, 64
FOLDED = attention_layout.FOLDED_TAG


@pytest.fixture(autouse=True)
def _flash_claims_on_the_cpu(monkeypatch):
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")


def _bf16(*shape, seed=0, scale=0.5):
    import jax.numpy as jnp

    return jnp.asarray(np.random.RandomState(seed + sum(shape)).randn(*shape) * scale, dtype=jnp.bfloat16)


def _tables(n, dtype="bfloat16"):
    import jax.numpy as jnp

    theta = 10000.0 ** (-np.arange(0, n // 2) * 2.0 / n)
    emb = np.tile(np.arange(T)[:, None] * theta[None, :], (1, 2))
    return jnp.asarray(np.cos(emb), dtype), jnp.asarray(np.sin(emb), dtype)


def _heads_first(x, heads, hs):
    return ttorch.permute(ttorch.reshape(x, (B, T, heads, hs)), (0, 2, 1, 3))


def _fused_qkv(H, G, hs, n, bias, layers=1, spare=0, q_read_twice=False, tables="bfloat16", norm=None, window=None,
               normed_read_twice=False, linear=None, **sdpa):
    """``_attention`` of ``models/gpt.py``, ``layers`` times over: (program, arguments).
    ``spare`` widens the projection by columns that no slice reads. ``n`` 0: no
    rope (the tables go unread). ``norm``: which of q and k pass through an
    ``rms_norm`` before the rope ("qk", or "q" alone), over a head's ``hs``
    features or, "qk_pairs", over pairs of them. ``window``: the call is
    ``window_attention``. ``linear``: the call is ``linear_attention`` with a
    decay a head and these keywords (``_linear_attention``'s site)."""
    width = (H + 2 * G) * hs + spare
    args = [_bf16(B, T, C), *_tables(n or hs, tables)]
    q_w, k_w = 1 + _bf16(hs, seed=11, scale=0.2), 1 + _bf16(hs, seed=12, scale=0.2)

    def normed(x, which, w):
        if which not in (norm or ""):
            return x
        if norm == "qk_pairs":  # a norm, but not of a head: over each pair of features
            return ttorch.reshape(ttorch.rms_norm(ttorch.reshape(x, (*x.shape[:-1], hs // 2, 2)), (2,), w[:2], eps=1e-5), x.shape)
        return ttorch.rms_norm(x, (hs,), w, eps=1e-5)
    for i in range(layers):
        args += [_bf16(width, C, seed=3 * i + 1, scale=0.1), _bf16(width, seed=3 * i + 2) if bias else None,
                 _bf16(C, H * hs, seed=3 * i + 3, scale=0.1)]

    def program(x, cos, sin, *weights):
        for qkv_w, qkv_b, proj_w in zip(weights[0::3], weights[1::3], weights[2::3]):
            qkv = ttorch.linear(x, qkv_w, qkv_b)
            q = _heads_first(qkv[..., : H * hs], H, hs)
            k = _heads_first(qkv[..., H * hs: (H + G) * hs], G, hs)
            v = _heads_first(qkv[..., (H + G) * hs: (H + 2 * G) * hs], G, hs)
            q, k = normed(q, "q", q_w), normed(k, "k", k_w)
            q_normed = q
            if n:
                q, k = ttorch.apply_rope(q, cos, sin), ttorch.apply_rope(k, cos, sin)
            if linear is not None:
                decay = clang.tensor_from_sequence([0.25 * (h + 1) for h in range(H)], device=x.device, dtype=dtypes.float32)
                y = ttorch.linear_attention(q, k, v, decay, **linear)
            elif window:
                y = ttorch.window_attention(q, k, v, window=window, **sdpa)
            else:
                y = ttorch.scaled_dot_product_attention(q, k, v, **{"is_causal": True, "enable_gqa": G != H, **sdpa})
            x = ttorch.linear(ttorch.reshape(ttorch.permute(y, (0, 2, 1, 3)), (B, T, H * hs)), proj_w)
            if q_read_twice:
                x = x + ttorch.sum(q)
            if normed_read_twice:
                x = x + ttorch.sum(q_normed)
        return x

    return program, args


def _latent_q(H=4, dn=128, dr=64, dv=128, R=96, scale=192 ** -0.5 * 1.3466 ** 2):
    """``_mla_attention``'s q path: a projection of its own into heads, rope on
    the first ``dr`` lanes, the published softmax scale; k and v come otherwise."""
    args = [_bf16(B, T, R), *_tables(dr), _bf16(H * (dr + dn), R, seed=1, scale=0.1),
            _bf16(B, 1, T, dr, seed=2), _bf16(B, H, T, dn + dv, seed=3)]

    def program(c_q, cos, sin, q_w, k_pe, kv):
        q = ttorch.apply_rope(_heads_first(ttorch.linear(c_q, q_w), H, dr + dn), cos, sin)
        k = ttorch.cat([ttorch.expand(ttorch.apply_rope(k_pe, cos, sin), (B, H, T, dr)), kv[..., :dn]], -1)
        return ttorch.scaled_dot_product_attention(q, k, kv[..., dn:], is_causal=True, scale=scale)

    return program, args


def _transforms_record(fn):
    program = thunder_tpu.compile_stats(fn).cache_entries[-1].compile_id
    (record,) = [r for r in thunder_tpu.compile_phases() if r["program"] == program and r["phase"] == "transforms"]
    return record


def _folded_trace(program, args, executors=None, edit=lambda trc: trc):
    """The pass on the program's trace, as ``api._compile_entry_impl`` places it."""
    _, trc = trace_program(program, tuple(args), {})
    return attention_layout.fold_attention_layouts(edit(dce(trc)), resolve_executors(executors))


IDIOMS = {
    # name: (program and arguments, attention sites, heads a lane group, the same bits as written)
    # pythia-410m: every head its own key, heads of 64 (two to the 128 lanes), a quarter of the head rotary, biases
    "pythia_mha_partial_rotary_bias": (lambda: _fused_qkv(4, 4, 64, 16, bias=True), 1, 2, True),
    # mistral-7b's shape of it: four query heads a key head, heads of 128, full rotary, no bias
    "gqa_32_8_heads_of_128_full_rotary": (lambda: _fused_qkv(32, 8, 128, 128, bias=False), 1, 1, False),
    "full_rotary_heads_of_64_and_a_scale_the_program_gave": (lambda: _fused_qkv(4, 2, 64, 64, bias=False, scale=0.125), 1, 2, True),
    "an_odd_count_of_heads_of_64_stays_one_a_group": (lambda: _fused_qkv(3, 3, 64, 16, bias=True), 1, 1, True),
    "four_heads_of_32_a_group": (lambda: _fused_qkv(8, 4, 32, 8, bias=False, scale=0.25), 1, 4, True),
    "two_layers": (lambda: _fused_qkv(2, 2, 64, 16, bias=True, layers=2), 2, 2, True),
    "axk1_q_path": (_latent_q, 1, 1, False),
    # the heads normed between the permute and the rope (PR 39): the call norms, ropes and scales in one pass
    "trinity_window_layer_normed_and_roped_gqa_at_128": (lambda: _fused_qkv(8, 2, 128, 128, bias=False, norm="qk", window=32), 1, 1, False),
    "trinity_global_layer_normed_without_rope": (lambda: _fused_qkv(8, 2, 128, 0, bias=False, norm="qk"), 1, 1, False),
    "normed_and_roped_under_causal_attention": (lambda: _fused_qkv(4, 1, 128, 128, bias=False, norm="qk"), 1, 1, False),
    "normed_without_rope_under_a_window": (lambda: _fused_qkv(4, 2, 128, 0, bias=True, norm="qk", window=48, scale=0.125), 1, 1, False),
    "lfm2_normed_heads_of_64_two_a_lane_group": (lambda: _fused_qkv(4, 2, 64, 64, bias=False, norm="qk"), 1, 2, False),
    "normed_heads_of_64_partial_rotary_under_a_window": (lambda: _fused_qkv(4, 4, 64, 16, bias=True, norm="qk", window=32), 1, 2, False),
    "roped_without_norm_under_a_window": (lambda: _fused_qkv(4, 2, 64, 64, bias=False, window=32), 1, 2, True),
    # linear attention as a consumer (PR 41): MiniCPM-SALA's linear layers, q and k normed and roped, as many key heads as
    # query heads; the call keeps its own scale, which it applies to its float32 output
    "minicpm_sala_linear_layer_normed_and_roped_at_128": (lambda: _fused_qkv(8, 8, 128, 128, bias=False, norm="qk", linear={}), 1, 1, False),
    "linear_normed_heads_of_64_with_a_scale_and_a_chunk_of_its_own": (lambda: _fused_qkv(4, 4, 64, 64, bias=False, norm="qk", linear=dict(scale=0.2, chunk=32)), 1, 2, False),
    "linear_roped_without_norm": (lambda: _fused_qkv(4, 4, 64, 64, bias=False, linear=dict(chunk=64)), 1, 2, True),
    "linear_normed_without_rope_two_layers": (lambda: _fused_qkv(2, 2, 128, 0, bias=True, norm="qk", layers=2, linear={}), 2, 1, False),
}


@pytest.mark.parametrize("idiom", IDIOMS)
def test_rewrites_the_idiom_and_computes_what_was_written(monkeypatch, idiom):
    make, sites, split, same_bits = IDIOMS[idiom]
    program, args = make()
    folded = thunder_tpu.jit(program)
    got = folded(*args)
    src = thunder_tpu.last_traces(folded)[-1].python()
    assert _transforms_record(folded)[FOLDED] == sites
    latent = idiom == "axk1_q_path"  # there k's rope part keeps the call it had
    assert src.count("jax_linear_heads(") == sites and src.count("pallas_apply_rope(") == (1 if latent else 0)
    assert src.count("pallas_apply_rope_heads(") == (sites if latent else 2 * sites)
    assert src.count("pallas_split_heads(") == (sites if split > 1 else 0)  # v, where a group's lanes hold several heads
    if "linear" in idiom:  # nobody claims the consumer, so its decomposition follows q's head call, the first layer's last new line
        front = "pallas_apply_rope_heads(".join(src.split("pallas_apply_rope_heads(")[:2])
        assert "scale=1.0" not in src and "flash_" not in src  # both head calls take 1.0: the scale stays in the call
    else:
        call = "flash_window_attention(" if "window" in idiom else "flash_scaled_dot_product_attention("
        assert src.count(call) == src.count("scale=1.0") == sites
        front = src.split(call)[0]
    # no token-major q, k or v, no slice of the last dimension and no norm of its own is left in front of attention
    assert "jax_transpose" not in front and "rsqrt" not in front
    assert src.count("norm_weight=") == (2 * sites if "normed" in idiom else 0)

    with monkeypatch.context() as m:
        m.setattr(pipeline, "REWRITES", tuple(r for r in pipeline.REWRITES if r is not attention_layout.fold_attention_layouts))
        written = thunder_tpu.jit(program)
        want = written(*args)
    assert FOLDED not in _transforms_record(written)
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if same_bits:  # the scale is a power of two: scaling before the rounding or after it is the same bits
        assert np.array_equal(got, want)
    else:  # no softmax bounds a linear site's output (it reads in the tens here): the same tolerance at its scale
        at = max(1.0, float(np.abs(want).max())) if "linear" in idiom else 1.0
        np.testing.assert_allclose(got, want, atol=2e-2 * at, rtol=2e-2)


def test_a_mosaic_claim_that_fails_later_runs_the_program_as_written():
    """Each new symbol's decomposition is what it replaced."""
    program, args = _fused_qkv(4, 4, 64, 16, bias=True)
    trc = _folded_trace(program, args)
    new = {b.sym.id: b for b in trc.bound_symbols}
    assert [s.sym.id for s in new["torch.linear_heads"].subsymbols] == ["torch.linear", "torch.reshape", "torch.permute"]
    q_rope = [b for b in trc.bound_symbols if b.sym.id == "torch.apply_rope_heads" and b.args[3] == 0][0]
    assert [s.sym.id for s in q_rope.subsymbols] == ["torch.split_heads", "torch.apply_rope", "torch.mul"]
    # two heads of 64 lie in a group's 128 lanes: taken apart, then the slice the program wrote
    assert [s.sym.id for s in new["torch.split_heads"].subsymbols] == ["torch.reshape", "torch.permute", "torch.reshape", "torch.getitem"]
    assert q_rope.args[5:] == (1 / math.sqrt(64), 2) and new["torch.linear_heads"].output.shape == (B, 6, T, 128)
    want = thunder_tpu.jit(program, executors=["jax"])(*args)
    got = thunder_tpu.jit(trc.python_callable(), executors=["jax"])(*[a for a in args if a is not None])
    assert np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def _in_regions(inner, **region_of):
    """``inner`` with each named ``ttorch`` symbol called inside its region, as ``models/gpt.py`` opens them."""
    import unittest.mock as mock
    from contextlib import ExitStack

    from thunder_tpu.core.trace import region

    def inside(name, fn):
        def wrapped(*args, **kwargs):
            with region(name):
                return fn(*args, **kwargs)
        return wrapped

    def program(*a):
        with ExitStack() as stack:
            for symbol, name in region_of.items():
                stack.enter_context(mock.patch.object(ttorch, symbol, inside(name, getattr(ttorch, symbol))))
            return inner(*a)

    return program


def test_a_normed_sites_lines_decompose_into_the_program_as_written_and_keep_their_regions():
    """The norm-rope call's decomposition is ``split_heads -> rms_norm -> apply_rope -> mul``, each step only where
    the site has it, and it stays in the region of the norm it stands for: the attention call alone carries its own."""
    inner, args = _fused_qkv(8, 2, 128, 128, bias=False, norm="qk", window=32)
    program = _in_regions(inner, rms_norm="attn.qk_norm", window_attention="attn.window")
    trc = _folded_trace(program, args)
    assert trc.tags[FOLDED] == 1
    lines = {(b.sym.id, b.args[3] if b.sym.id == "torch.apply_rope_heads" else None): b for b in trc.bound_symbols}
    q, k = lines["torch.apply_rope_heads", 0], lines["torch.apply_rope_heads", 8]
    assert [s.sym.id for s in q.subsymbols] == ["torch.split_heads", "torch.rms_norm", "torch.apply_rope", "torch.mul"]
    assert [s.sym.id for s in k.subsymbols] == ["torch.split_heads", "torch.rms_norm", "torch.apply_rope"]
    assert q.args[5:] == (1 / math.sqrt(128), 1) and q.kwargs["eps"] == k.kwargs["eps"] == 1e-5
    assert q.kwargs["norm_weight"].shape == (128,) and q.kwargs["norm_weight"] is not k.kwargs["norm_weight"]
    assert q.region == k.region == "attn.qk_norm" and lines["torch.linear_heads", None].region is None
    assert lines["torch.getitem", None].region is None  # v, a slice of the head-major projection
    call = lines["torch.window_attention", None]
    assert call.region == "attn.window" and call.kwargs == {"window": 32, "scale": 1.0}
    want = thunder_tpu.jit(inner, executors=["jax"])(*args)
    got = thunder_tpu.jit(trc.python_callable(), executors=["jax"])(*[a for a in args if a is not None])
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=2e-2, rtol=2e-2)


HEADS_KERNEL_STEPS = [(norm, rope, scale, split) for norm in (False, True) for rope in (0, 16, 64)
                      for scale in (1.0, 0.25) for split in (1, 2) if norm or rope or scale != 1.0]


@pytest.mark.parametrize("norm,rope,scale,split", HEADS_KERNEL_STEPS,
                         ids=[f"norm{int(n)}-rope{r}-scale{s}-split{k}" for n, r, s, k in HEADS_KERNEL_STEPS])
def test_the_heads_kernel_against_its_decomposition(norm, rope, scale, split):
    """``pallasex``'s one body (interpreted) with each step on or off against ``ttorch.apply_rope_heads``'s
    decomposition on the ``jax`` executor: heads of 64, one or two a lane group, a rotary of none, a quarter or
    all of the head, read from the middle of the array."""
    import jax.numpy as jnp

    from thunder_tpu.executors import pallasex

    hs, first, heads = 64, 2, 4
    x = _bf16(B, 8 // split, T, hs * split, scale=2.0)
    cos, sin = _tables(rope) if rope else (None, None)
    how = dict(norm_weight=1 + _bf16(hs, seed=5, scale=0.3), eps=1e-5) if norm else {}
    assert pallasex._rope_heads_checker(x, cos, sin, first, heads, scale, split, **how)
    got = pallasex._rope_heads_impl(x, cos, sin, first, heads, scale, split, **how)
    written = thunder_tpu.jit(lambda x, cos, sin, w: ttorch.apply_rope_heads(x, cos, sin, first, heads, scale, split, w, how.get("eps")),
                              executors=["jax"])
    operands = (x, cos, sin, how.get("norm_weight"))
    want = written(*operands)
    assert got.shape == want.shape == (B, heads, T, hs) and got.dtype == want.dtype == jnp.bfloat16
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if not norm:  # every rounding is the decomposition's (the scale is a power of two)
        assert np.array_equal(got, want)
        return
    # the norm's weight is applied in float32: one rounding fewer than the program as written, none more
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    exact = np.asarray(written(*(None if a is None else a.astype(jnp.float32) for a in operands)))
    assert np.abs(got - exact).mean() <= np.abs(want - exact).mean()


def _sds(*shape, dtype="bfloat16"):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def _rope(x, cos, sin):
    from thunder_tpu.executors import pallasex

    return pallasex._rope_impl(x, cos, sin)


def _heads(first, heads, scale, split):
    from thunder_tpu.executors import pallasex

    return lambda x, *tables: (pallasex._rope_heads_impl(x, *tables, first, heads, scale, split) if tables
                               else pallasex._split_heads_impl(x, first, heads, split))


# (the call, its operands at a cell's shapes, sha256 of its jaxpr as the three bodies of PR 30 traced it)
UNNORMED_CALLS = {
    "mistral-7b.train_k": (_rope, [_sds(1, 8, 4096, 128), _sds(4096, 128), _sds(4096, 128)], "c8f573b235378cc6"),
    "pythia-410m.train_q": (_rope, [_sds(4, 16, 2048, 64), _sds(2048, 16), _sds(2048, 16)], "657d3c3ae8d71828"),
    "float32": (_rope, [_sds(1, 8, 512, 128, dtype="float32")] + [_sds(512, 128, dtype="float32")] * 2, "bc8349cdcc12dbe0"),
    "pythia-410m.fwd_q": (_heads(0, 16, 0.125, 2), [_sds(8, 24, 2048, 128), _sds(2048, 16), _sds(2048, 16)], "3fcfcff97cfd81c5"),
    "pythia-410m.fwd_k": (_heads(16, 16, 1.0, 2), [_sds(8, 24, 2048, 128), _sds(2048, 16), _sds(2048, 16)], "9461413da72fb50f"),
    "pythia-410m.fwd_v": (_heads(32, 16, 1.0, 2), [_sds(8, 24, 2048, 128)], "1162be5b5c5857db"),
    "a.x-k1.fwd_q": (_heads(0, 64, 0.1, 1), [_sds(2, 64, 4096, 192), _sds(4096, 64), _sds(4096, 64)], "7ea38d08b9aeb434"),
    "mistral-7b_q_head_major": (_heads(0, 32, 0.088, 1), [_sds(1, 48, 4096, 128), _sds(4096, 128), _sds(4096, 128)], "778e709ef6e7e238"),
}


@pytest.mark.parametrize("call", UNNORMED_CALLS)
def test_a_call_without_a_norm_traces_to_the_body_it_had(call):
    """One body took the place of three (PR 39: a full rotary's, a partial one's, the plain split's). Where a call
    asks for no norm its jaxpr, grid and blocks are to the letter what those three gave at the shapes of the cells
    that run them. Whoever changes what an un-normed call computes changes these and measures ``pythia-410m.fwd``,
    ``a.x-k1.fwd`` and ``mistral-7b.train`` on the chip; a new jax prints another text, and then they are taken anew
    from the commit before."""
    import hashlib
    import re

    import jax

    fn, operands, sha = UNNORMED_CALLS[call]
    text = str(jax.make_jaxpr(fn)(*operands))
    text = re.sub(r"name=\w+", "name=K", text)  # the body's name, which is all that changed
    text = re.sub(r" at [^\n]*\.py:\d+", "", text)
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == sha


def _with_dropout(trc):
    at = [i for i, b in enumerate(trc.bound_symbols) if b.sym.id == attention_layout._SDPA][0]
    sdpa = trc.bound_symbols[at]
    trc.bound_symbols[at] = sdpa.from_bsym(kwargs={**sdpa.kwargs, "dropout_p": 0.1})
    return trc


def _masked(**how):
    import jax.numpy as jnp

    program, args = _fused_qkv(4, 4, 64, 16, bias=True, is_causal=False,
                               attn_mask=jnp.ones((B, 1, 1, T), jnp.bool_), **how)
    return program, args


DECLINES = {
    "attn_mask": lambda: _folded_trace(*_masked()),
    "dropout": lambda: _folded_trace(*_fused_qkv(4, 4, 64, 16, bias=True), edit=_with_dropout),
    "a_second_reader_of_q": lambda: _folded_trace(*_fused_qkv(4, 4, 64, 16, bias=True, q_read_twice=True)),
    "slices_that_do_not_tile": lambda: _folded_trace(*_fused_qkv(4, 4, 64, 16, bias=True, spare=64)),
    "no_kernel_executors": lambda: _folded_trace(*_fused_qkv(4, 4, 64, 16, bias=True), executors=["jax"]),
    # float32 tables promote q in the decomposition, so the rope kernel's checker says no
    "a_rope_call_the_kernel_declines": lambda: _folded_trace(*_fused_qkv(4, 4, 64, 16, bias=True, tables="float32")),
    "a_norm_on_q_only": lambda: _folded_trace(*_fused_qkv(4, 4, 64, 16, bias=True, norm="q")),
    "a_norm_that_is_not_over_a_head": lambda: _folded_trace(*_fused_qkv(4, 4, 64, 16, bias=True, norm="qk_pairs")),
    "a_second_reader_of_the_normed_q": lambda: _folded_trace(*_fused_qkv(4, 4, 64, 16, bias=True, norm="qk", normed_read_twice=True)),
    "neither_norm_nor_rope": lambda: _folded_trace(*_fused_qkv(4, 4, 64, 0, bias=True)),
    "a_mask_on_a_normed_site": lambda: _folded_trace(*_masked(norm="qk")),
    # linear attention's sites (PR 41) decline for the reasons the others do
    "a_second_reader_of_a_linear_sites_q": lambda: _folded_trace(*_fused_qkv(4, 4, 64, 64, bias=False, norm="qk", linear={}, q_read_twice=True)),
    "a_linear_site_whose_q_and_k_differ_in_their_steps": lambda: _folded_trace(*_fused_qkv(4, 4, 64, 64, bias=False, norm="q", linear={})),
    "a_linear_sites_heads_call_the_kernel_declines": lambda: _folded_trace(*_fused_qkv(4, 4, 64, 64, bias=False, norm="qk", linear={}, tables="float32")),
    "a_linear_site_without_pallas": lambda: _folded_trace(*_fused_qkv(4, 4, 64, 64, bias=False, norm="qk", linear={}), executors=["flash", "jax"]),
    "a_linear_site_with_neither_norm_nor_rope": lambda: _folded_trace(*_fused_qkv(4, 4, 64, 0, bias=False, linear={})),
}


@pytest.mark.parametrize("why", DECLINES)
def test_declines_and_leaves_the_program_as_written(why):
    trc = DECLINES[why]()
    assert trc.tags[FOLDED] == 0
    ids = [b.sym.id for b in trc.bound_symbols]
    assert "torch.linear_heads" not in ids and ("torch.apply_rope" in ids or "neither_norm_nor_rope" in why)


@pytest.mark.parametrize("consumer", ["causal", "linear"])
def test_declines_on_a_grad_trace_through_the_api(consumer):
    program, args = _fused_qkv(4, 4, 64, 16, bias=False, **({"linear": {}} if consumer == "linear" else {}))
    args = [a for a in args if a is not None]
    loss = lambda x, cos, sin, qkv_w, proj_w: ttorch.sum(program(x, cos, sin, qkv_w, None, proj_w).float())
    vg = thunder_tpu.value_and_grad(loss, argnums=(0, 3))
    vg(*args)
    assert _transforms_record(vg)[FOLDED] == 0
    src = thunder_tpu.last_traces(vg)[-1].python()
    assert "linear_heads" not in src and "pallas_apply_rope(" in src


def test_a_linear_site_folds_without_flash_and_its_call_keeps_scale_decay_and_chunk(monkeypatch):
    """The consumer is XLA's decomposition, which nobody claims and the pass asks nobody to: ``pallas`` alone is
    enough. The call stays as written on the new q, k and v, its scale on its float32 output; both head calls take
    1.0. The head calls stand in the norm's region, the call alone in its own, v and the projection in none."""
    monkeypatch.delenv("THUNDER_FLASH_FORCE")
    inner, args = _fused_qkv(4, 4, 128, 128, bias=False, norm="qk", linear=dict(scale=0.2, chunk=32))
    program = _in_regions(inner, rms_norm="attn.qk_norm", linear_attention="attn.linear")
    written = {b.sym.id: b for b in _folded_trace(program, args, executors=["jax"]).bound_symbols}["torch.linear_attention"]
    trc = _folded_trace(program, args, executors=["pallas", "jax"])
    assert trc.tags[FOLDED] == 1
    lines = {(b.sym.id, b.args[3] if b.sym.id == "torch.apply_rope_heads" else None): b for b in trc.bound_symbols}
    q, k, call = lines["torch.apply_rope_heads", 0], lines["torch.apply_rope_heads", 4], lines["torch.linear_attention", None]
    assert q.args[5:] == k.args[5:] == (1.0, 1)
    assert [s.sym.id for s in q.subsymbols] == [s.sym.id for s in k.subsymbols] == ["torch.split_heads", "torch.rms_norm", "torch.apply_rope"]
    assert call.args[:3] == (q.output, k.output, lines["torch.getitem", None].output)
    assert call.args[3].name == written.args[3].name and call.kwargs == written.kwargs == {"scale": 0.2, "chunk": 32}
    assert call.output.name == written.output.name
    assert q.region == k.region == "attn.qk_norm" and call.region == "attn.linear"
    assert lines["torch.linear_heads", None].region is None and lines["torch.getitem", None].region is None
    assert not {"torch.rms_norm", "torch.apply_rope", "torch.permute"} & {b.sym.id for b in trc.bound_symbols[:trc.bound_symbols.index(call)]}


def test_declines_without_the_flash_claim(monkeypatch):
    """A CPU run without the kernels keeps the program: the checkers are asked, not the list of names."""
    monkeypatch.delenv("THUNDER_FLASH_FORCE")
    program, args = _fused_qkv(4, 4, 64, 16, bias=True)
    jfn = thunder_tpu.jit(program)
    jfn(*args)
    assert _transforms_record(jfn)[FOLDED] == 0
    assert "linear_heads" not in thunder_tpu.last_traces(jfn)[-1].python()


MODELS = {
    # registry entry (cut where given): (attention sites, of them under a window, normed heads)
    "pythia-410m": (dict(n_layer=3, n_embd=128, n_head=2, intermediate_size=256, padded_vocab_size=256, vocab_size=256),
                    3, 0, False),
    "trinity-tiny": ({}, 4, 3, True),   # three window layers with rope, a global one without, every head normed
    "lfm2-tiny": ({}, 1, 0, True),      # one attention layer among three conv mixers, its heads normed
    # three linear layers, normed and roped, behind a sparse layer whose consumer the pass does not know (PR 41)
    "minicpm-sala-tiny": ({}, 3, 0, True),
}


@pytest.mark.parametrize("name", MODELS)
def test_models_gpt_forward_counts_its_layers(name):
    """The counter on the ``transforms`` record is the number of attention sites rewritten."""
    import dataclasses

    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt

    cut, sites, windows, normed = MODELS[name]
    cfg = dataclasses.replace(gpt.name_to_config(name), **cut)
    params = gpt.init_params(cfg, dtype=dtypes.bfloat16, device_init=True)
    T = min(128, cfg.block_size)
    idx = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, T)).astype(np.int32)
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    assert np.isfinite(np.asarray(jfn(params, idx), np.float32)).all()
    assert _transforms_record(jfn)[FOLDED] == sites
    src = thunder_tpu.last_traces(jfn)[-1].python()
    assert src.count("flash_window_attention(") == windows and src.count("jax_linear_heads(") == sites
    assert src.count("norm_weight=") == (2 * sites if normed else 0)


@pytest.mark.parametrize("T", [128, 256])
def test_a_sparse_and_linear_model_folds_its_linear_layers_and_computes_what_was_written(monkeypatch, T):
    """MiniCPM-SALA's stand-in (sparse, linear, linear, linear; ``qk_norm``, ``linear_rope``): the linear layers' sites
    fold and ``sparse_block_attention``'s does not (its q has two readers in the counting program, and a scale on q
    would enter the selection: PERF.md section 7). The folded program's logits are the written one's."""
    from thunder_tpu.models import gpt

    sites = 3  # the linear layers
    cfg = gpt.name_to_config("minicpm-sala-tiny")
    params = gpt.init_params(cfg, dtype=dtypes.bfloat16, device_init=True)
    idx = np.random.RandomState(1).randint(0, cfg.vocab_size, (2, T)).astype(np.int32)
    folded = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    got = folded(params, idx)
    assert _transforms_record(folded)[FOLDED] == sites
    src = thunder_tpu.last_traces(folded)[-1].python()
    assert src.count("jax_linear_heads(") == sites and src.count("pallas_apply_rope_heads(") == 2 * sites
    assert src.count("pallas_split_heads(") == sites and "pallas_apply_rope(" not in src
    assert src.count("norm_weight=") == 2 * sites  # of the four layers' eight normed q and k, the linear layers' six
    with monkeypatch.context() as m:
        m.setattr(pipeline, "REWRITES", tuple(r for r in pipeline.REWRITES if r is not attention_layout.fold_attention_layouts))
        written = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
        want = written(params, idx)
    assert thunder_tpu.last_traces(written)[-1].python().count("pallas_apply_rope(") == 2 * sites
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=2e-2, rtol=2e-2)
