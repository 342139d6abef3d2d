"""The JAX/XLA operator executor: every prim lowered to jax.numpy / lax.

Reference parity: this executor occupies the seats of both ``torchex``
(thunder/executors/torchex.py:40 — the default operator executor covering
all prims) and ``nvfuserex`` (thunder/executors/nvfuserex_impl.py — fusion):
on TPU the claimed trace is staged whole under ``jax.jit``, so XLA performs
the fusion, layout assignment, and scheduling that nvFuser did for CUDA, and
the compiled-executable cache takes the seat of descriptor-keyed nvFuser
caching and CUDA graphs.

Numeric notes:
- ``jax_enable_x64`` is turned on by the runtime so the torch-facing dtype
  semantics (int64 indices, float64 when requested) hold exactly; all hot
  compute is explicitly bf16/f32 in the traces, so this costs nothing on TPU.
- ``prims.div`` is true division for floats and *floor* division for
  integers (clang routes int true-division through a float convert).
"""

from __future__ import annotations

import math
from numbers import Number
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from thunder_tpu.core import dtypes
from thunder_tpu.core.prims import PrimIDs
from thunder_tpu.extend import OperatorExecutor, add_default_executor, register_executor
from thunder_tpu.observability import metrics as obsm

ex = OperatorExecutor("jax")
register_executor(ex)
add_default_executor(ex, front=False)


def _jd(d: dtypes.dtype):
    return dtypes.to_jax_dtype(d)


def _reg(prim_id: PrimIDs, fn, checker=None):
    ex.register_implementation(prim_id, fn=fn, checker=checker)


# -- data movement ------------------------------------------------------------


def _convert_element_type(a, dtype):
    if isinstance(a, Number):
        return dtypes.dtype_to_numbertype(dtype)(a)
    return lax.convert_element_type(a, _jd(dtype))


_reg(PrimIDs.CONVERT_ELEMENT_TYPE, _convert_element_type)
_reg(PrimIDs.DEVICE_PUT, lambda a, device: a)
_reg(PrimIDs.ITEM, lambda a: a.item())
_reg(PrimIDs.SHALLOW_COPY, lambda a: a)
_reg(PrimIDs.STOP_GRADIENT, lax.stop_gradient)
_reg(PrimIDs.OPTIMIZATION_BARRIER, lax.optimization_barrier)
_reg(PrimIDs.COPY_, lambda src, dst: jnp.broadcast_to(src, dst.shape).astype(dst.dtype))


# -- creation -----------------------------------------------------------------

_reg(PrimIDs.FULL, lambda shape, v, *, device, dtype: jnp.full(tuple(shape), v, dtype=_jd(dtype)))
_reg(
    PrimIDs.IOTA,
    lambda length, *, start, step, device, dtype: (jnp.arange(int(length), dtype=_jd(dtype)) * step + start).astype(
        _jd(dtype)
    ),
)
_reg(PrimIDs.TENSOR_FROM_SEQUENCE, lambda seq, *, device, dtype: jnp.asarray(seq, dtype=_jd(dtype) if dtype else None))


def _uniform_keyed(shape, minval, maxval, key, salt, *, device, dtype):
    k = jax.random.fold_in(key, salt)
    return jax.random.uniform(k, tuple(shape), dtype=_jd(dtype), minval=minval, maxval=maxval)


def _randn_keyed(shape, key, salt, *, device, dtype):
    k = jax.random.fold_in(key, salt)
    return jax.random.normal(k, tuple(shape), dtype=_jd(dtype))


_reg(PrimIDs.UNIFORM_KEYED, _uniform_keyed)
_reg(PrimIDs.RANDN_KEYED, _randn_keyed)

# Unkeyed RNG only executes eagerly (outside jit); the rng functionalization
# pass rewrites these away before staging.
_host_rng = {"seed": 0}


def _eager_key():
    _host_rng["seed"] += 1
    return jax.random.PRNGKey(_host_rng["seed"])


_reg(
    PrimIDs.UNIFORM,
    lambda shape, minval, maxval, *, device, dtype: jax.random.uniform(
        _eager_key(), tuple(shape), dtype=_jd(dtype), minval=minval, maxval=maxval
    ),
)
_reg(PrimIDs.RANDN, lambda shape, *, device, dtype: jax.random.normal(_eager_key(), tuple(shape), dtype=_jd(dtype)))


# -- shape --------------------------------------------------------------------

_reg(PrimIDs.BROADCAST_IN_DIM, lambda a, shape, bdims: lax.broadcast_in_dim(a, tuple(int(s) for s in shape), tuple(bdims)))
_reg(PrimIDs.CAT, lambda tensors, dim: jnp.concatenate(tensors, axis=dim))
_reg(PrimIDs.FLIP, lambda a, dims: jnp.flip(a, axis=tuple(dims)))


def _pad(a, padding_value, padding_config):
    pv = jnp.asarray(padding_value, dtype=a.dtype)
    return lax.pad(a, pv, [(int(lo), int(hi), int(d)) for lo, hi, d in padding_config])


_reg(PrimIDs.PAD, _pad)
_reg(PrimIDs.RESHAPE, lambda a, shape: jnp.reshape(a, tuple(int(s) for s in shape)))
_reg(
    PrimIDs.SLICE,
    lambda a, starts, ends, strides=None: lax.slice(
        a, tuple(int(s) for s in starts), tuple(int(e) for e in ends), tuple(int(s) for s in strides) if strides else None
    ),
)
def _setitem(a, key, value):
    # Explicit cast to the target dtype: torch setitem truncates (7.5 into
    # an int32 tensor stores 7); jax's implicit unsafe-scatter cast is
    # deprecated and will become an error.
    return a.at[key].set(jnp.asarray(value, a.dtype))


_reg(PrimIDs.SETITEM, _setitem)


_reg(PrimIDs.SQUEEZE, lambda a, dims: lax.squeeze(a, tuple(dims)))
_reg(PrimIDs.TRANSPOSE, lambda a, perm: lax.transpose(a, tuple(perm)))
_reg(PrimIDs.TAKE, lambda a, idx, dim: jnp.take(a, idx, axis=dim))
_reg(PrimIDs.TAKE_ALONG_AXIS, lambda a, idx, dim: jnp.take_along_axis(a, idx, axis=dim))
_reg(PrimIDs.GATHER, lambda a, idx, dim: jnp.take_along_axis(a, idx, axis=dim))


def _scatter_add(a, idx, val, dim):
    grids = jnp.indices(idx.shape, sparse=True)
    index_tuple = tuple(idx if d == dim else grids[d] for d in range(a.ndim))
    return a.at[index_tuple].add(val)


_reg(PrimIDs.SCATTER_ADD, _scatter_add)


def _index_put(a, indices, values, accumulate):
    idx = tuple(indices)
    if accumulate:
        return a.at[idx].add(values)
    return a.at[idx].set(values)


_reg(PrimIDs.INDEX_PUT, _index_put)
_reg(PrimIDs.ARGSORT, lambda a, dim, descending: jnp.argsort(a, axis=dim, descending=descending))


def _sort(a, dim, descending):
    v = jnp.sort(a, axis=dim, descending=descending)
    i = jnp.argsort(a, axis=dim, descending=descending)
    return v, i


_reg(PrimIDs.SORT, _sort)


def _cumsum(a, dim):
    if jnp.issubdtype(a.dtype, jnp.bool_) or jnp.issubdtype(a.dtype, jnp.integer):
        return jnp.cumsum(a, axis=dim, dtype=jnp.int64)
    return jnp.cumsum(a, axis=dim)


_reg(PrimIDs.CUMSUM, _cumsum)


def _cumprod(a, dim):
    if jnp.issubdtype(a.dtype, jnp.bool_) or jnp.issubdtype(a.dtype, jnp.integer):
        return jnp.cumprod(a, axis=dim, dtype=jnp.int64)
    return jnp.cumprod(a, axis=dim)


_reg(PrimIDs.CUMPROD, _cumprod)


def _topk(a, k, dim, largest, sorted):
    a_m = jnp.moveaxis(a, dim, -1)
    if largest:
        v, i = lax.top_k(a_m, k)
    else:
        v, i = lax.top_k(-a_m, k)
        v = -v
    return jnp.moveaxis(v, -1, dim), jnp.moveaxis(i, -1, dim).astype(jnp.int64)


_reg(PrimIDs.TOPK, _topk)


# -- elementwise unary --------------------------------------------------------

from jax.scipy import special as jsp  # noqa: E402

_unary_table = {
    PrimIDs.ABS: jnp.abs,
    PrimIDs.ACOS: jnp.arccos,
    PrimIDs.ACOSH: jnp.arccosh,
    PrimIDs.ASIN: jnp.arcsin,
    PrimIDs.ASINH: jnp.arcsinh,
    PrimIDs.ATAN: jnp.arctan,
    PrimIDs.ATANH: jnp.arctanh,
    PrimIDs.BITWISE_NOT: lambda a: jnp.logical_not(a) if a.dtype == jnp.bool_ else jnp.invert(a),
    PrimIDs.CEIL: jnp.ceil,
    PrimIDs.COS: jnp.cos,
    PrimIDs.COSH: jnp.cosh,
    PrimIDs.DIGAMMA: jsp.digamma,
    PrimIDs.ERF: jsp.erf,
    PrimIDs.ERFC: jsp.erfc,
    PrimIDs.ERFINV: jsp.erfinv,
    PrimIDs.EXP: jnp.exp,
    PrimIDs.EXP2: jnp.exp2,
    PrimIDs.EXPM1: jnp.expm1,
    PrimIDs.FLOOR: jnp.floor,
    PrimIDs.ISFINITE: jnp.isfinite,
    PrimIDs.ISINF: jnp.isinf,
    PrimIDs.ISNAN: jnp.isnan,
    PrimIDs.LGAMMA: jsp.gammaln,
    PrimIDs.LOG: jnp.log,
    PrimIDs.LOG10: jnp.log10,
    PrimIDs.LOG1P: jnp.log1p,
    PrimIDs.LOG2: jnp.log2,
    PrimIDs.NEG: jnp.negative,
    PrimIDs.RECIPROCAL: jnp.reciprocal,
    PrimIDs.ROUND: jnp.round,
    PrimIDs.RSQRT: lax.rsqrt,
    PrimIDs.SIGN: jnp.sign,
    PrimIDs.SIGNBIT: jnp.signbit,
    PrimIDs.SIN: jnp.sin,
    PrimIDs.SINH: jnp.sinh,
    PrimIDs.SQRT: jnp.sqrt,
    PrimIDs.TAN: jnp.tan,
    PrimIDs.TANH: jnp.tanh,
    PrimIDs.TRUNC: jnp.trunc,
    PrimIDs.REAL: jnp.real,
    PrimIDs.IMAG: jnp.imag,
}
for pid, fn in _unary_table.items():
    _reg(pid, fn)


# -- elementwise binary -------------------------------------------------------


def _div(a, b):
    if jnp.issubdtype(jnp.result_type(a), jnp.integer) and jnp.issubdtype(jnp.result_type(b), jnp.integer):
        return jnp.floor_divide(a, b)
    return jnp.true_divide(a, b)


def _bool_aware(int_fn, bool_fn):
    def fn(a, b):
        if jnp.result_type(a) == jnp.bool_:
            return bool_fn(a, b)
        return int_fn(a, b)

    return fn


_binary_table = {
    PrimIDs.ADD: jnp.add,
    PrimIDs.ATAN2: jnp.arctan2,
    PrimIDs.BITWISE_AND: _bool_aware(jnp.bitwise_and, jnp.logical_and),
    PrimIDs.BITWISE_OR: _bool_aware(jnp.bitwise_or, jnp.logical_or),
    PrimIDs.BITWISE_XOR: _bool_aware(jnp.bitwise_xor, jnp.logical_xor),
    PrimIDs.BITWISE_LEFT_SHIFT: jnp.left_shift,
    PrimIDs.BITWISE_RIGHT_SHIFT: jnp.right_shift,
    PrimIDs.DIV: _div,
    PrimIDs.EQ: jnp.equal,
    PrimIDs.FMOD: jnp.fmod,
    PrimIDs.GE: jnp.greater_equal,
    PrimIDs.GT: jnp.greater,
    PrimIDs.LE: jnp.less_equal,
    PrimIDs.LT: jnp.less,
    PrimIDs.MAXIMUM: jnp.maximum,
    PrimIDs.MINIMUM: jnp.minimum,
    PrimIDs.MUL: jnp.multiply,
    PrimIDs.NE: jnp.not_equal,
    PrimIDs.NEXTAFTER: jnp.nextafter,
    PrimIDs.POW: jnp.power,
    PrimIDs.REMAINDER: jnp.remainder,
    PrimIDs.SUB: jnp.subtract,
    PrimIDs.COPYSIGN: jnp.copysign,
    PrimIDs.ZETA: lambda a, b: jsp.zeta(a, b),
}
for pid, fn in _binary_table.items():
    _reg(pid, fn)

_reg(PrimIDs.WHERE, jnp.where)


# -- reductions ---------------------------------------------------------------


def _sum(a, dims):
    if jnp.issubdtype(a.dtype, jnp.bool_) or jnp.issubdtype(a.dtype, jnp.integer):
        return jnp.sum(a, axis=tuple(dims), dtype=jnp.int64)
    return jnp.sum(a, axis=tuple(dims))


def _prod(a, dims):
    if jnp.issubdtype(a.dtype, jnp.bool_) or jnp.issubdtype(a.dtype, jnp.integer):
        return jnp.prod(a, axis=tuple(dims), dtype=jnp.int64)
    return jnp.prod(a, axis=tuple(dims))


_reg(PrimIDs.AMAX, lambda a, dims: jnp.max(a, axis=tuple(dims)))
_reg(PrimIDs.AMIN, lambda a, dims: jnp.min(a, axis=tuple(dims)))
_reg(PrimIDs.SUM, _sum)
_reg(PrimIDs.PROD, _prod)
_reg(PrimIDs.VAR, lambda a, dims, *, correction: jnp.var(a, axis=tuple(dims), ddof=int(correction)))
_reg(
    PrimIDs.VAR_MEAN,
    lambda a, dims, *, correction: (
        jnp.var(a, axis=tuple(dims), ddof=int(correction)),
        jnp.mean(a, axis=tuple(dims)),
    ),
)
_reg(PrimIDs.ARGMAX, lambda a, dim: jnp.argmax(a, axis=dim).astype(jnp.int64))
_reg(PrimIDs.ARGMIN, lambda a, dim: jnp.argmin(a, axis=dim).astype(jnp.int64))


# -- linear algebra / NN ------------------------------------------------------


# Float32 matmul precision, mirroring torch.set_float32_matmul_precision:
# "highest" = true f32 (6-pass bf16 on the MXU), "high" ≈ tf32 (3-pass),
# "medium" = 1-pass bf16. bf16/f16 inputs are unaffected — that is the hot
# path for training and runs the MXU natively.
_f32_matmul_precision = {"value": lax.Precision.HIGHEST}
_PRECISION_MAP = {
    "highest": lax.Precision.HIGHEST,
    "high": lax.Precision.HIGH,
    "medium": lax.Precision.DEFAULT,
}


def set_float32_matmul_precision(mode: str) -> None:
    _f32_matmul_precision["value"] = _PRECISION_MAP[mode]


def _dot_precision(*operands):
    if any(o.dtype in (jnp.float32, jnp.float64) for o in operands):
        return _f32_matmul_precision["value"]
    return None


def _matmul(a, b):
    return jnp.matmul(a, b, precision=_dot_precision(a, b))


_reg(PrimIDs.MATMUL, _matmul)


def _grouped_mm(a, b, group_sizes):
    # XLA's own ragged dot: on the TPU a grouped-matmul kernel that walks the
    # groups and skips the rows beyond their sum; on the CPU a masked dense form.
    return lax.ragged_dot(a, b, group_sizes.astype(jnp.int32), precision=_dot_precision(a, b))


_GROUPED_DW_DIMS = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())), lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _grouped_mm_dw(a, g, group_sizes):
    return lax.ragged_dot_general(a, g, group_sizes.astype(jnp.int32), _GROUPED_DW_DIMS,
                                  precision=_dot_precision(a, g))


_reg(PrimIDs.GROUPED_MM, _grouped_mm)
_reg(PrimIDs.GROUPED_MM_DW, _grouped_mm_dw)


def _linear(a, w, bias):
    # x @ w.T via dot_general: contract a's last dim with w's dim 1 —
    # a single MXU-friendly contraction, no materialized transpose.
    out = lax.dot_general(a, w, (((a.ndim - 1,), (1,)), ((), ())), precision=_dot_precision(a, w))
    if bias is not None:
        out = out + bias
    return out


_reg(PrimIDs.LINEAR, _linear)


# A projection with the head dimension in the dot's own output
# (transforms/attention_layout.py): XLA writes a head-major array with no
# transpose only where the dot itself has the heads; ``linear -> reshape ->
# permute`` leaves it a copy. Heads narrower than the lanes cost the dot twice
# its time (29.5 ms against 14.6 in pythia-410m.fwd), so the caller asks for
# them side by side: 24 of 128 for 48 of 64 read 15.8 (PERF.md, PR 30).


def _linear_heads(a, w, bias=None, heads=1):
    hs = w.shape[0] // heads
    out = jnp.einsum("btc,hdc->bhtd", a, w.reshape(heads, hs, w.shape[1]), precision=_dot_precision(a, w))
    if bias is not None:
        out = out + bias.reshape(heads, 1, hs)
    return out


ex.register_implementation("torch.linear_heads", fn=_linear_heads)


# Block-sparse attention whose blocks the data chooses, over a long sequence
# (``torch.sparse_block_select`` and ``torch.sparse_block_attend``). Their
# decompositions go through the queries in chunks unrolled into the program,
# each against the keys up to its own end: at 32,768 positions that is 160
# passes a layer, every one of a shape of its own, which the TPU's compiler
# takes six minutes over and schedules so that several passes' scores are alive
# at once (15.4 GB for ten layers; no chip, PR 33). Here the same passes are
# iterations of ``lax.map``: the queries of a span of ``SPARSE_LOOP_SPAN`` share
# one key length (the span's end), so a layer compiles a loop body a span, and a
# loop keeps one pass's temporaries alive. The arithmetic of a pass is the
# decomposition's, operation for operation, but for the selection's last step.
# The decomposition ends in ``lax.top_k``, which XLA runs as a full sort of a
# query's up to 512 block scores: 1.5 ms a pass, 196 of minicpm-sala.fwd-t32k's
# 1,668 ms a call. Of the 64 blocks a query keeps, 33 are forced and tie at
# +inf, so a pass here writes those by their numbers and takes the best free
# block left, in 31 turns of a row maximum that order nothing they reject: the
# same ids in the same places, 0.05 ms a pass (PERF.md, PR 36; a threshold by
# bisection over the scores' bits finds the same set in less, and pays more to
# put it in order). The selection runs here; the attention over the chosen
# blocks runs here only where ``pallas`` declines it
# (``pallasex._sparse_attend_checker``: heads that are not whole lanes, keys and
# values the device's VMEM does not hold): its kernel, ahead in the executors'
# order, keeps the scores these passes write to HBM once and read twice on the
# chip (PERF.md, PR 34).

SPARSE_LOOP_SPAN = 4096     # queries whose passes share a key length, a compiled body and a loop
SPARSE_LOOP_SELECT = 512    # queries a pass
SPARSE_LOOP_ATTEND = 256


def _sparse_loop_checker(q, *args, query_chunk=None, **kwargs):
    """Sequences of whole spans, two or more, where the caller leaves the chunking open."""
    T = q.shape[2]
    return query_chunk is None and T % SPARSE_LOOP_SPAN == 0 and T >= 2 * SPARSE_LOOP_SPAN


def _by_spans(T: int, n: int, one_pass, query_axis: int):
    """``one_pass(t0, keys_end)`` for every ``n`` queries from ``t0``, those of a span
    under one ``lax.map``; each output has its ``n`` queries on ``query_axis``."""
    spans = []
    for s0 in range(0, T, SPARSE_LOOP_SPAN):
        out = lax.map(lambda i: one_pass(s0 + i * n, s0 + SPARSE_LOOP_SPAN), jnp.arange(SPARSE_LOOP_SPAN // n, dtype=jnp.int32))
        out = jnp.moveaxis(out, 0, query_axis)                       # (.., passes, n, ..)
        spans.append(out.reshape(*out.shape[:query_axis], -1, *out.shape[query_axis + 2:]))
    return jnp.concatenate(spans, query_axis)


def _best_in_turn(score, rounds):
    """(.., rounds) int32: ``lax.top_k(score, rounds)``'s ids, the lower on a tie and -1 in place of a -inf,
    as ``rounds`` turns of taking a row's best and striking it out: nothing that is not kept is ordered."""
    b = jnp.arange(score.shape[-1], dtype=jnp.int32)

    def turn(i, carry):
        left, ids = carry
        at = jnp.argmax(left, -1).astype(jnp.int32)
        ids = lax.dynamic_update_index_in_dim(ids, jnp.where(left.max(-1) > -jnp.inf, at, -1), i, -1)
        return jnp.where(b == at[..., None], -jnp.inf, left), ids

    ids = jnp.full((*score.shape[:-1], rounds), -1, jnp.int32)
    return lax.fori_loop(0, rounds, turn, (score, ids))[1] if rounds else ids


def _sparse_block_select_loops(q, k, *, kernel_size, kernel_stride, block_size, topk, init_blocks, local_blocks,
                               scale=None, query_chunk=None):
    B, H, T, d = q.shape
    G = k.shape[1]
    R, r, per = H // G, kernel_size // kernel_stride, block_size // kernel_stride
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    n_pool = (T - kernel_size) // kernel_stride + 1
    strides = n_pool + r - 1
    parts = k[:, :, :strides * kernel_stride].astype(jnp.float32).reshape(B, G, strides, kernel_stride, d).sum(3)
    pooled = sum(parts[:, :, i:i + n_pool] for i in range(r))
    pooled = jnp.swapaxes(pooled * (1.0 / kernel_size), -2, -1)                        # (B, G, d, n_pool)
    qg = q.reshape(B, G, R, T, d)
    n = SPARSE_LOOP_SELECT

    def one_pass(t0, keys_end):
        nb, pools = -(-keys_end // block_size), (keys_end - kernel_size) // kernel_stride + 1
        t = t0 + jnp.arange(n, dtype=jnp.int32)
        qc = (lax.dynamic_slice_in_dim(qg, t0, n, 3).astype(jnp.float32) * scale).reshape(B, G, R * n, d)
        s = _matmul(qc, pooled[..., :pools]).reshape(B, G, R, n, pools)
        past = (kernel_size + kernel_stride * jnp.arange(pools, dtype=jnp.int32))[None, :] <= (t + 1)[:, None]
        s = jnp.where(past, s, -jnp.inf)
        top = s.max(-1, keepdims=True)
        e = jnp.exp(s - top)
        p = jnp.where(top == -jnp.inf, 0.0, e / e.sum(-1, keepdims=True))               # a query with no pooled key in its past: zeros
        P = jnp.pad(p.sum(2), ((0, 0), (0, 0), (0, 0), (r - 1, per * (nb + 1) - (r - 1) - pools)))
        score = P[..., :per * nb].reshape(B, G, n, nb, per).max(-1)
        after = P[..., per:].reshape(B, G, n, nb, per)
        for i in range(r - 1):
            score = jnp.maximum(score, after[..., i])
        b, own = jnp.arange(nb, dtype=jnp.int32)[None, :], (t // block_size)[:, None]
        forced = (b < init_blocks) | (b > own - local_blocks)
        # The forced blocks tie at +inf and so come first, the lower first: of the first ``init_blocks`` and the
        # ``local_blocks`` that end at the query's own, those there are. Only a query that has them all has a free block.
        kept = min(topk, nb)
        place = jnp.arange(min(init_blocks + local_blocks, kept), dtype=jnp.int32)[None, :]
        n_forced = jnp.minimum(own + 1, init_blocks) + jnp.clip(own + 1 - init_blocks, 0, local_blocks)
        first = jnp.where(place < n_forced, jnp.where(place < init_blocks, place, own + 1 - n_forced + place), -1)
        rest = _best_in_turn(jnp.where(forced | (b > own), -jnp.inf, score), kept - place.shape[-1])
        ids = jnp.concatenate([jnp.broadcast_to(first, (B, G, *first.shape)), rest], -1)
        return jnp.pad(ids, ((0, 0), (0, 0), (0, 0), (0, topk - kept)), constant_values=-1)

    return _by_spans(T, n, one_pass, 2)


def _sparse_block_attend_loops(q, k, v, block_ids, *, block_size, scale=None, query_chunk=None):
    B, H, T, d = q.shape
    G = k.shape[1]
    R = H // G
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg, kT = q.reshape(B, G, R, T, d), jnp.swapaxes(k, -2, -1)
    n = SPARSE_LOOP_ATTEND

    def one_pass(t0, keys_end):
        nb = -(-keys_end // block_size)
        t = t0 + jnp.arange(n, dtype=jnp.int32)
        ids = lax.dynamic_slice_in_dim(block_ids, t0, n, 2)
        chosen = (ids[..., None] == jnp.arange(nb, dtype=ids.dtype)).any(-2)            # (B, G, n, nb)
        keys = jnp.broadcast_to(chosen[..., None], (B, G, n, nb, block_size)).reshape(B, G, n, nb * block_size)
        mask = (keys[..., :keys_end] & (jnp.arange(keys_end, dtype=jnp.int32)[None, :] <= t[:, None]))[:, :, None]
        s = _matmul(lax.dynamic_slice_in_dim(qg, t0, n, 3).reshape(B, G, R * n, d), kT[..., :keys_end])
        s = jnp.where(mask, (s.astype(jnp.float32) * scale).reshape(B, G, R, n, keys_end), -jnp.inf)
        e = jnp.exp(s - s.max(-1, keepdims=True))
        o = _matmul(e.astype(v.dtype).reshape(B, G, R * n, keys_end), v[:, :, :keys_end])
        return (o.reshape(B, G, R, n, d).astype(jnp.float32) / e.sum(-1, keepdims=True)).astype(q.dtype)

    return _by_spans(T, n, one_pass, 3).reshape(B, H, T, d)


ex.register_implementation("torch.sparse_block_select", fn=_sparse_block_select_loops, checker=_sparse_loop_checker)
ex.register_implementation("torch.sparse_block_attend", fn=_sparse_block_attend_loops, checker=_sparse_loop_checker)


def _convolution(a, weight, bias, stride, padding, dilation, groups):
    spatial = a.ndim - 2
    stride = tuple(stride[i] if i < len(stride) else stride[-1] for i in range(spatial))
    padding_seq = tuple(
        (padding[i] if i < len(padding) else padding[-1],) * 2 for i in range(spatial)
    )
    dilation = tuple(dilation[i] if i < len(dilation) else dilation[-1] for i in range(spatial))
    spec = "NC" + "DHW"[3 - spatial :]
    wspec = "OI" + "DHW"[3 - spatial :]
    dn = lax.conv_dimension_numbers(a.shape, weight.shape, (spec, wspec, spec))
    out = lax.conv_general_dilated(
        a,
        weight,
        window_strides=stride,
        padding=padding_seq,
        rhs_dilation=dilation,
        dimension_numbers=dn,
        feature_group_count=groups,
        precision=_dot_precision(a, weight),
    )
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * spatial)
    return out


_reg(PrimIDs.CONVOLUTION, _convolution)


def _convolution_bwd(g, a, weight, stride, padding, dilation, groups):
    _, vjp = jax.vjp(lambda x, w: _convolution(x, w, None, stride, padding, dilation, groups), a, weight)
    return vjp(g)


_reg(PrimIDs.CONVOLUTION_BWD, _convolution_bwd)
_reg(PrimIDs.EMBEDDING, lambda idx, w: jnp.take(w, idx, axis=0))


def _embedding_backward(grad, idx, num_weights, embed_dim):
    out = jnp.zeros((num_weights, embed_dim), dtype=grad.dtype)
    return out.at[idx.reshape(-1)].add(grad.reshape(-1, embed_dim))


_reg(PrimIDs.EMBEDDING_BACKWARD, _embedding_backward)
_reg(PrimIDs.POLYGAMMA, lambda n, a: jsp.polygamma(n, a))


def _pool_fwd_fn(a, kind, window, strides, padding):
    """reduce_window over the trailing len(window) dims — XLA's native
    pooling; avg divides by the full window size (count_include_pad=True,
    torch's default)."""
    k = len(window)
    full_window = (1,) * (a.ndim - k) + tuple(window)
    full_strides = (1,) * (a.ndim - k) + tuple(strides)
    full_pad = ((0, 0),) * (a.ndim - k) + tuple((int(lo), int(hi)) for lo, hi in padding)
    if kind == "max":
        init = -jnp.inf if jnp.issubdtype(a.dtype, jnp.floating) else jnp.iinfo(a.dtype).min
        return lax.reduce_window(a, jnp.asarray(init, a.dtype), lax.max, full_window, full_strides, full_pad)
    s = lax.reduce_window(a, jnp.asarray(0, a.dtype), lax.add, full_window, full_strides, full_pad)
    return s / math.prod(window)


def _pool_bwd_fn(g, a, kind, window, strides, padding):
    """Direct pooling adjoints (this jax build cannot differentiate
    reduce_window under jit at all — Linearization failure — so jax.vjp is
    not an option here).

    avg: the transpose of a strided window-sum is a stride-1 window-sum over
    the base-dilated cotangent (XLA's own transpose rule), divided by the
    window size. max: torch semantics (grad to the FIRST max element of each
    window) via a single int64 reduce_window over (monotonic-value, reversed-
    index) packed keys, then scatter-add of g at each window's argmax."""
    k = len(window)
    lead = a.shape[: a.ndim - k]
    spatial = a.shape[a.ndim - k:]

    if kind == "avg":
        full_window = (1,) * (a.ndim - k) + tuple(window)
        pads = []
        for s_in, kk, tt, (lo, hi) in zip(spatial, window, strides, padding):
            d = (g.shape[g.ndim - k + len(pads)] - 1) * tt + 1
            pl = kk - 1 - lo
            ph = s_in + lo - d
            pads.append((pl, ph))
        full_pads = ((0, 0),) * (a.ndim - k) + tuple(pads)
        base_dil = (1,) * (a.ndim - k) + tuple(strides)
        adj = lax.reduce_window(
            g, jnp.asarray(0, g.dtype), lax.add, full_window, (1,) * a.ndim,
            full_pads, base_dilation=base_dil,
        )
        return adj / math.prod(window)

    # max: pack (monotonic value bits, reversed linear index) into int64 so a
    # single reduce_window max yields each window's first-argmax index. The
    # packing needs real int64 — enable x64 locally so the adjoint works even
    # when the caller never went through jit()'s _ensure_runtime.
    with jax.enable_x64(True):
        return _max_pool_bwd_x64(g, a, window, strides, padding, lead, spatial)


def _max_pool_bwd_x64(g, a, window, strides, padding, lead, spatial):
    k = len(window)
    n_spatial = math.prod(spatial)
    b = math.prod(lead) if lead else 1
    if a.dtype == jnp.float64:
        # The packed argmax key holds 32 value bits; two f64 values inside a
        # window that differ only below f32 precision would pick the wrong
        # winner and silently misroute the whole cotangent. Refuse rather
        # than be subtly wrong (torch-parity surface is f32/bf16 pooling).
        raise NotImplementedError(
            "max-pool backward for float64 inputs is not supported (argmax "
            "key packing is exact only to float32); cast to float32"
        )
    af = a.astype(jnp.float32) if a.dtype != jnp.float32 else a
    bits = lax.bitcast_convert_type(af, jnp.int32).astype(jnp.int64)
    mono = jnp.where(bits < 0, ~bits, bits | jnp.int64(0x80000000))
    # Center to [-2^31, 2^31) so the <<32 below cannot overflow int64.
    mono = mono - (jnp.int64(1) << 31)
    idx = jnp.arange(n_spatial, dtype=jnp.int64).reshape((1,) * len(lead) + spatial)
    packed = (mono << 32) | (jnp.int64(n_spatial) - idx)  # larger = earlier index
    full_window = (1,) * (a.ndim - k) + tuple(window)
    full_strides = (1,) * (a.ndim - k) + tuple(strides)
    full_pad = ((0, 0),) * (a.ndim - k) + tuple((int(lo), int(hi)) for lo, hi in padding)
    winner = lax.reduce_window(
        jnp.broadcast_to(packed, a.shape), jnp.iinfo(jnp.int64).min, lax.max,
        full_window, full_strides, full_pad,
    )
    win_idx = jnp.int64(n_spatial) - (winner & jnp.int64(0xFFFFFFFF))
    flat_idx = win_idx.reshape(b, -1)
    flat_g = g.reshape(b, -1)
    grad = jnp.zeros((b, n_spatial), g.dtype).at[
        jnp.arange(b)[:, None], flat_idx
    ].add(flat_g)
    return grad.reshape(a.shape)


_reg(PrimIDs.POOL, _pool_fwd_fn)
_reg(PrimIDs.POOL_BWD, _pool_bwd_fn)


def _uniform_philox(shape, minval, maxval, *, seed, offset, device, dtype):
    key = jax.random.fold_in(jax.random.PRNGKey(seed), offset)
    return jax.random.uniform(key, tuple(shape), dtype=_jd(dtype), minval=minval, maxval=maxval)


_reg(PrimIDs.UNIFORM_PHILOX, _uniform_philox)


# =============================================================================
# Bucketed staging (cache="symbolic values", core/bucketing.py)
#
# One XLA executable serves a whole shape bucket: marked input dims are
# zero-padded up to the bucket ceiling here, at the jax.jit boundary, and
# outputs are cropped back by the dispatcher (api._run_entry). The padded
# buffers are dispatch-time temporaries, so they are DONATED to XLA (off-CPU):
# the executable reuses their memory instead of copying.
# =============================================================================


def _donation_active() -> bool:
    # Narrow catch (ISSUE 6 satellite): jax raises RuntimeError when no
    # backend can initialize — the one legitimate "answer conservatively"
    # case. Anything else (ImportError from a broken install, a TypeError
    # from an API change) is a real bug and must propagate, not be
    # swallowed into silently-disabled donation.
    try:
        return jax.default_backend() != "cpu"
    except RuntimeError as e:
        from thunder_tpu.common import sharp_edge

        sharp_edge(
            f"jax backend unavailable while resolving donation "
            f"({type(e).__name__}: {e}); buffer donation disabled"
        )
        return False


def stage_bucketed(trace_callable, donate_leaves: Sequence[int], *, donate: bool = True):
    """jax.jit a trace callable whose ``donate_leaves`` argument positions
    receive freshly padded (dispatch-owned) buffers. Donation is skipped on
    CPU, where jax does not implement it (and would warn per call), and at
    de-opt ladder level ≥ 1 (``donate=False`` — resilience/deopt.py).

    The actual donation decision is stamped on the staged callable
    (``_thunder_donated_argnums``): api._compile_entry_impl reconciles the
    claimed trace's ``donated_inputs`` tag against it after staging, and
    it is the introspection point for anyone holding only the jitted
    callable. The caller's ``donate`` must already be the full predicate
    (api's ``donate_buckets``); this function only adds the backend checks
    it owns (CPU has no donation)."""
    donating = bool(donate and _donation_active() and donate_leaves)
    jfn = (
        jax.jit(trace_callable, donate_argnums=tuple(donate_leaves))
        if donating
        else jax.jit(trace_callable)
    )
    try:
        jfn._thunder_donated_argnums = tuple(donate_leaves) if donating else ()
    except Exception:  # jit wrapper without attribute support
        pass
    return jfn


def pad_to_bucket(inps: list, sym_spec) -> list:
    """Zero-pad marked dims of the (jax) input leaves up to their bucket
    ceilings. Always returns buffers safe to donate for marked leaves: a leaf
    already at the ceiling is copied, so the caller's array is never donated
    out from under it.

    With metrics enabled, the padded-minus-true element count per call is
    accumulated into ``thunder_tpu_padding_waste_elements_total`` — the
    bucket-policy tuning signal (too-coarse buckets show up as waste, not
    just as fewer compiles)."""
    donating = _donation_active()
    track_waste = obsm.enabled()
    waste = 0
    out = list(inps)
    for li, dims in sym_spec.marks.items():
        x = out[li]
        widths = [(0, 0)] * x.ndim
        padded = False
        for d, (_lo, hi, _cid) in dims.items():
            delta = int(hi) - int(x.shape[d])
            if delta > 0:
                widths[d] = (0, delta)
                padded = True
        if padded:
            if track_waste:
                true_elems = math.prod(int(s) for s in x.shape)
                padded_elems = math.prod(
                    int(s) + w[1] for s, w in zip(x.shape, widths)
                )
                waste += padded_elems - true_elems
            out[li] = jnp.pad(x, widths)
        elif donating:
            out[li] = jnp.array(x, copy=True)
    if track_waste and waste:
        obsm.PADDING_WASTE_ELEMENTS.inc(waste)
    return out


def crop_to_extents(out, sym_spec, true_extents: dict):
    """Slice padded output dims back to the call's true extents, per the
    provenance crop plan (transforms/padmask.py): each listed flat output
    leaf is sliced exactly on its tracked dims. The plan is always derived —
    from the masked trace, or re-analyzed after grad/autocast transforms —
    so no shape-coincidence guessing happens here."""
    from thunder_tpu.core.pytree import tree_flatten, tree_unflatten

    if not sym_spec.crop_plan:
        return out
    flat, spec = tree_flatten(out)

    def slice_dim(x, d, n):
        if int(x.shape[d]) == int(n):
            return x
        ix = [slice(None)] * x.ndim
        ix[d] = slice(0, int(n))
        return x[tuple(ix)]

    for i, dims in sym_spec.crop_plan:
        if i < len(flat) and isinstance(flat[i], jax.Array):
            for d, cid in dims.items():
                flat[i] = slice_dim(flat[i], d, true_extents[cid])
    return tree_unflatten(spec, flat)
