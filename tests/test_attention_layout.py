"""transforms/attention_layout.py: q, k and v leave their projection
head-major. The pass rewrites the idiom ``models/gpt.py::_attention`` writes
(and the part of it that latent attention has), declines whatever it cannot
prove is that idiom, and the rewritten program computes what was written."""

import math

import numpy as np
import pytest

import thunder_tpu
import thunder_tpu.torch as ttorch
from thunder_tpu.api import trace_program
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


def _fused_qkv(H, G, hs, n, bias, layers=1, spare=0, q_read_twice=False, tables="bfloat16", **sdpa):
    """``_attention`` of ``models/gpt.py``, ``layers`` times over: (program, arguments).
    ``spare`` widens the projection by columns that no slice reads."""
    width = (H + 2 * G) * hs + spare
    args = [_bf16(B, T, C), *_tables(n, tables)]
    for i in range(layers):
        args += [_bf16(width, C, seed=3 * i + 1, scale=0.1), _bf16(width, seed=3 * i + 2) if bias else None,
                 _bf16(C, H * hs, seed=3 * i + 3, scale=0.1)]

    def program(x, cos, sin, *weights):
        for qkv_w, qkv_b, proj_w in zip(weights[0::3], weights[1::3], weights[2::3]):
            qkv = ttorch.linear(x, qkv_w, qkv_b)
            q = _heads_first(qkv[..., : H * hs], H, hs)
            k = _heads_first(qkv[..., H * hs: (H + G) * hs], G, hs)
            v = _heads_first(qkv[..., (H + G) * hs: (H + 2 * G) * hs], G, hs)
            q, k = ttorch.apply_rope(q, cos, sin), ttorch.apply_rope(k, cos, sin)
            y = ttorch.scaled_dot_product_attention(q, k, v, **{"is_causal": True, "enable_gqa": G != H, **sdpa})
            x = ttorch.linear(ttorch.reshape(ttorch.permute(y, (0, 2, 1, 3)), (B, T, H * hs)), proj_w)
            if q_read_twice:
                x = x + ttorch.sum(q)
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
    assert src.count("flash_scaled_dot_product_attention(") == src.count("scale=1.0") == sites
    # no token-major q, k or v, and no slice of the last dimension, is left in front of attention
    assert "jax_transpose" not in src.split("flash_scaled_dot_product_attention(")[0]

    with monkeypatch.context() as m:
        m.setattr(attention_layout, "fold_attention_layouts", lambda trc, executors: trc)
        written = thunder_tpu.jit(program)
        want = written(*args)
    assert FOLDED not in _transforms_record(written)
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if same_bits:  # the scale is a power of two: scaling before the rounding or after it is the same bits
        assert np.array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


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


def _with_dropout(trc):
    at = [i for i, b in enumerate(trc.bound_symbols) if b.sym.id == attention_layout._SDPA][0]
    sdpa = trc.bound_symbols[at]
    trc.bound_symbols[at] = sdpa.from_bsym(kwargs={**sdpa.kwargs, "dropout_p": 0.1})
    return trc


def _masked():
    import jax.numpy as jnp

    program, args = _fused_qkv(4, 4, 64, 16, bias=True, is_causal=False,
                               attn_mask=jnp.ones((B, 1, 1, T), jnp.bool_))
    return program, args


DECLINES = {
    "attn_mask": lambda: _folded_trace(*_masked()),
    "dropout": lambda: _folded_trace(*_fused_qkv(4, 4, 64, 16, bias=True), edit=_with_dropout),
    "a_second_reader_of_q": lambda: _folded_trace(*_fused_qkv(4, 4, 64, 16, bias=True, q_read_twice=True)),
    "slices_that_do_not_tile": lambda: _folded_trace(*_fused_qkv(4, 4, 64, 16, bias=True, spare=64)),
    "no_kernel_executors": lambda: _folded_trace(*_fused_qkv(4, 4, 64, 16, bias=True), executors=["jax"]),
    # float32 tables promote q in the decomposition, so the rope kernel's checker says no
    "a_rope_call_the_kernel_declines": lambda: _folded_trace(*_fused_qkv(4, 4, 64, 16, bias=True, tables="float32")),
}


@pytest.mark.parametrize("why", DECLINES)
def test_declines_and_leaves_the_program_as_written(why):
    trc = DECLINES[why]()
    assert trc.tags[FOLDED] == 0
    ids = [b.sym.id for b in trc.bound_symbols]
    assert "torch.linear_heads" not in ids and "torch.apply_rope" in ids


def test_declines_on_a_grad_trace_through_the_api():
    program, args = _fused_qkv(4, 4, 64, 16, bias=False)
    args = [a for a in args if a is not None]
    loss = lambda x, cos, sin, qkv_w, proj_w: ttorch.sum(program(x, cos, sin, qkv_w, None, proj_w).float())
    vg = thunder_tpu.value_and_grad(loss, argnums=(0, 3))
    vg(*args)
    assert _transforms_record(vg)[FOLDED] == 0
    src = thunder_tpu.last_traces(vg)[-1].python()
    assert "linear_heads" not in src and "pallas_apply_rope(" in src


def test_declines_without_the_flash_claim(monkeypatch):
    """A CPU run without the kernels keeps the program: the checkers are asked, not the list of names."""
    monkeypatch.delenv("THUNDER_FLASH_FORCE")
    program, args = _fused_qkv(4, 4, 64, 16, bias=True)
    jfn = thunder_tpu.jit(program)
    jfn(*args)
    assert _transforms_record(jfn)[FOLDED] == 0
    assert "linear_heads" not in thunder_tpu.last_traces(jfn)[-1].python()


def test_models_gpt_forward_counts_its_layers():
    """The counter on the ``transforms`` record is the number of attention sites rewritten."""
    import dataclasses

    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt

    cfg = dataclasses.replace(gpt.name_to_config("pythia-410m"), n_layer=3, n_embd=128, n_head=2,
                              intermediate_size=256, padded_vocab_size=256, vocab_size=256)
    params = gpt.init_params(cfg, dtype=dtypes.bfloat16, device_init=True)
    idx = np.random.RandomState(0).randint(0, 256, (2, 128)).astype(np.int32)
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    jfn(params, idx)
    assert _transforms_record(jfn)[FOLDED] == 3
