"""First-party Pallas TPU kernels: fused cross-entropy.

Reference parity: the reference's only in-repo kernel-DSL code is its
Triton cross-entropy (thunder/executors/triton_crossentropy.py:53-343, four
@triton.jit kernels) plus the apex seat (apex_entropyex.py:38). This module
is the TPU equivalent: Pallas/Mosaic kernels fusing max/logsumexp/pick. The
(N, V≈32-160k) logits matrix is the largest activation in LM training; the
forward reads it from HBM once and the backward reads it once and writes its
gradient once, in the dtype the logits have (float32, or the bfloat16 the
head wrote: transforms/cross_entropy_upcast.py folds a program's upcast
before the loss into the claim), where the decomposed path makes ~5 passes.
A call holds a block of rows in VMEM and walks it in chunks of lanes, upcast
to float32 there, with a running maximum and sum: the float32 temporaries
are (rows, chunk) whatever the vocabulary. ``_ce_block_n`` budgets the block
and those against the VMEM of the device's generation (``_ce_vmem_limit``),
so the checker declines on a chip where nothing would compile.

Claims ``torch.cross_entropy`` and the ``torch.cross_entropy_bwd``
composite emitted by the autodiff rule, under each reduction: "mean", "sum",
or "none", a loss a row and a cotangent a row. Falls back to the decomposition
when shapes don't block-align or the dtype is neither (checker), exactly
like the reference's executor checkers.

Under a device mesh every ``pallas_call`` runs per batch shard inside
``jax.shard_map`` (executors/kernel_mesh.py), its blocks sized on the
shard's rows; reductions over rows (the loss sum) stay outside, where the
partitioner turns them into collectives.
"""

from __future__ import annotations

from functools import lru_cache, partial
from types import SimpleNamespace

from thunder_tpu.core import dtypes
from thunder_tpu.core.devices import TPU_SPECS, tpu_generation
from thunder_tpu.core.proxies import pyval
from thunder_tpu.executors.kernel_mesh import batch_shards, per_batch_shard
from thunder_tpu.extend import OperatorExecutor, add_default_executor, register_executor
from thunder_tpu.resilience import chaos

ex = OperatorExecutor("pallas")
register_executor(ex)
add_default_executor(ex, front=True)

_LANE = 128


def _interpret() -> bool:
    import jax

    return jax.default_backend() == "cpu"


# Measured on the v5e at (8192, 50304) (PERF.md, PR 28): bf16 logits reach 1.14 ms forward and 2.56 backward
# from blocks of 64 rows and chunks of 4096 lanes on; 32 rows and 2048 lanes read 1.86 and 2.68, 16 rows 2.79
# and 4.14 (each chunk ends in reductions across lanes that the next one waits for). float32 logits sit on
# their bytes (2.22 and 5.06 ms) from 16 rows on.
_CE_CHUNK = 4096  # lanes a step of the walk over the vocabulary; not by dtype, so every dtype sums in one order
_SCOPED_VMEM_DEFAULT = 16 * 1024 * 1024  # what Mosaic gives a call that asks for nothing, on every generation


def _device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def _ce_vmem_limit() -> int:
    """The VMEM a cross-entropy call asks of Mosaic: half of what the device's
    generation has, and never under the default scope. A device whose
    generation the table lacks (the CPU's interpret mode, a newer chip) gets
    the default scope: every TPU holds that, and the kernels lived in it
    before they walked the vocabulary."""
    try:
        return max(_SCOPED_VMEM_DEFAULT, TPU_SPECS[tpu_generation(_device_kind())].vmem_bytes // 2)
    except ValueError:  # no TPU generation of the table
        return _SCOPED_VMEM_DEFAULT


def _ce_block_n(N: int, V: int, itemsize: int = 4):
    """Row-block size for the CE kernels on this device, or None when unclaimable.

    What a call holds in VMEM is the row block of the logits and (backward)
    of their gradient, each twice for the pipeline, in the logits' dtype, and
    some eight float32 temporaries of (block, _CE_CHUNK) whatever V is; three
    quarters of ``_ce_vmem_limit()`` may go to them. 16-bit logits take
    blocks of whole (16, 128) tiles, so 16 rows at least."""
    budget = 3 * _ce_vmem_limit() // 4
    for bn in (64, 32, 16) if itemsize < 4 else (64, 32, 16, 8):
        if N % bn == 0 and 4 * bn * V * itemsize + 8 * bn * min(V, _CE_CHUNK) * 4 <= budget:
            return bn
    return None


def _ce_shapes_ok(input, target) -> bool:
    if len(getattr(input, "shape", ())) != 2:
        return False
    dtype = dtypes.to_dtype(input.dtype)
    if dtype not in (dtypes.float32, dtypes.bfloat16):  # Mosaic loads no float16 vector on the v5e
        return False
    N, V = input.shape
    shards = batch_shards()
    return (V % _LANE == 0 and N % shards == 0
            and _ce_block_n(int(N) // shards, int(V), dtype.bytes) is not None)


_CE_REDUCTIONS = ("mean", "sum", "none")  # "none": the loss a row, and a row's own cotangent in the backward


def _ce_checker(input, target, weight=None, ignore_index=-100, reduction="mean", label_smoothing=0.0):
    return (
        weight is None
        and float(pyval(label_smoothing)) == 0.0
        and reduction in _CE_REDUCTIONS
        and _ce_shapes_ok(input, target)
    )


def _ce_bwd_checker(g, input, target, ignore_index=-100, reduction="mean"):
    return reduction in _CE_REDUCTIONS and _ce_shapes_ok(input, target)


# =============================================================================
# Kernels
# =============================================================================


# Lane-width padding: Mosaic requires the last (lane) dim of every VMEM
# block to be 128-aligned, so per-row scalars (targets, loss, row scales)
# travel as (N, 128) with only lane 0 meaningful.
#
# A kernel has its row block of the logits in VMEM, in the dtype the program
# wrote them, and walks it in chunks of _CE_CHUNK lanes: each chunk is upcast
# to float32 there (exact for bfloat16), so the arithmetic is float32 whatever
# came in and the temporaries are (block, chunk).


def _ce_walk(V: int, step, carry):
    """``carry = step(start, cols, carry)`` over the chunks of V lanes in turn:
    the whole chunks in one loop, the ragged last one after it. ``cols`` is
    the chunk's own lane index, (1, size)."""
    import jax
    from jax.experimental import pallas as pl

    lane = lambda size: jax.lax.broadcasted_iota(jax.numpy.int32, (1, size), 1)
    whole, rest = divmod(V, _CE_CHUNK)
    if whole:
        cols = lane(_CE_CHUNK)
        carry = jax.lax.fori_loop(
            0, whole, lambda c, k: step(pl.multiple_of(c * _CE_CHUNK, _CE_CHUNK), cols, k), carry)
    if rest:
        carry = step(whole * _CE_CHUNK, lane(rest), carry)
    return carry


def _ce_chunk(logits_ref, start, cols):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    return logits_ref[:, pl.ds(start, cols.shape[1])].astype(jnp.float32)


def _ce_row_stats(logits_ref, tgt=None):
    """Per row of the block, kept running over the chunks and each (rows, 1):
    the maximum m, sum(exp(x - m)) and, given the targets, the target's logit."""
    import jax.numpy as jnp

    n, V = logits_ref.shape

    def step(start, cols, carry):
        m, l, picked = carry
        x = _ce_chunk(logits_ref, start, cols)
        m_new = jnp.maximum(m, jnp.max(x, axis=1, keepdims=True))
        at = jnp.where(m_new == -jnp.inf, 0.0, m_new)  # a chunk of -inf adds nothing
        l = l * jnp.exp(m - at) + jnp.sum(jnp.exp(x - at), axis=1, keepdims=True)
        if tgt is not None:
            picked = picked + jnp.sum(jnp.where(cols == tgt - start, x, 0.0), axis=1, keepdims=True)
        return m_new, l, picked

    col = lambda v: jnp.full((n, 1), v, dtype=jnp.float32)
    return _ce_walk(V, step, (col(-jnp.inf), col(0.0), col(0.0)))


def _ce_fwd_kernel(logits_ref, tgt_ref, loss_ref, *, ignore_index: int):
    import jax.numpy as jnp

    tgt = tgt_ref[:, 0:1]  # (rows, 1) int32
    m, l, picked = _ce_row_stats(logits_ref, tgt)
    valid = (tgt != ignore_index).astype(jnp.float32)
    loss_ref[:] = jnp.broadcast_to((jnp.log(l) + m - picked) * valid, loss_ref.shape)


def _ce_bwd_kernel(logits_ref, tgt_ref, scale_ref, dlogits_ref, *, ignore_index: int):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    m, l, _ = _ce_row_stats(logits_ref)
    inv_l = 1.0 / l
    tgt = tgt_ref[:, 0:1]
    scale = scale_ref[:, 0:1]

    def write(start, cols, carry):  # the second walk, over the block still in VMEM
        p = jnp.exp(_ce_chunk(logits_ref, start, cols) - m) * inv_l
        onehot = (cols == tgt - start).astype(jnp.float32)
        dlogits_ref[:, pl.ds(start, cols.shape[1])] = ((p - onehot) * scale).astype(dlogits_ref.dtype)
        return carry

    _ce_walk(logits_ref.shape[1], write, 0)


# =============================================================================
# Host-side wrappers
# =============================================================================


def _ce_call(kernel, out_lanes, out_dtype, logits, *extra):
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def rows(logits, *extra):  # one batch shard's rows
        N, V = logits.shape
        bn = _ce_block_n(int(N), int(V), logits.dtype.itemsize)
        if bn is None:
            raise ValueError(
                f"CE kernel called with unclaimable shape ({N}, {V}) {logits.dtype} — the checker "
                "must gate this (a floored grid would leave tail rows unwritten)"
            )
        in_specs = [pl.BlockSpec((bn, V), lambda i: (i, 0), memory_space=pltpu.VMEM)]
        for _ in extra:
            in_specs.append(pl.BlockSpec((bn, _LANE), lambda i: (i, 0), memory_space=pltpu.VMEM))
        return pl.pallas_call(
            kernel,
            grid=(N // bn,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bn, out_lanes), lambda i: (i, 0), memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((N, out_lanes), out_dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",), vmem_limit_bytes=_ce_vmem_limit()),
            interpret=_interpret(),
        )(logits, *extra)

    # Mosaic's index maths is 32-bit; scope out the runtime's x64 mode so the
    # grid index maps don't trace to i64 (which fails to legalize).
    with jax.enable_x64(False):
        return per_batch_shard(rows, logits, *extra)


def _lanes(col):
    """(N,) per-row values → (N, 128) lane-padded array."""
    import jax.numpy as jnp

    return jnp.broadcast_to(col.reshape(-1, 1), (col.shape[0], _LANE))


def _ce_impl(input, target, weight=None, ignore_index=-100, reduction="mean", label_smoothing=0.0):
    chaos.kernel_seam("pallas", "cross_entropy")
    import jax.numpy as jnp

    N, V = input.shape
    tgt = _lanes(target.astype(jnp.int32))
    loss = _ce_call(
        partial(_ce_fwd_kernel, ignore_index=int(ignore_index)), _LANE, jnp.float32, input, tgt
    )[:, 0]
    if reduction == "none":  # zero where the target is ignore_index: the kernel weighs each row by it
        return loss
    total = jnp.sum(loss)
    if reduction == "sum":
        return total
    count = jnp.maximum(jnp.sum((target != ignore_index).astype(jnp.float32)), 1.0)
    return total / count


def _ce_bwd_impl(g, input, target, ignore_index=-100, reduction="mean"):
    chaos.kernel_seam("pallas", "cross_entropy_bwd")
    import jax.numpy as jnp

    N, V = input.shape
    tgt = _lanes(target.astype(jnp.int32))
    valid = (target != ignore_index).astype(jnp.float32)
    if reduction == "mean":
        count = jnp.maximum(jnp.sum(valid), 1.0)
        row_scale = _lanes(g.astype(jnp.float32) * valid / count)
    else:  # sum, or none: g a scalar or (N,)
        row_scale = _lanes(g.astype(jnp.float32) * valid)
    return _ce_call(
        partial(_ce_bwd_kernel, ignore_index=int(ignore_index)), V, input.dtype, input, tgt, row_scale
    )


ex.register_implementation("torch.cross_entropy", fn=_ce_impl, checker=_ce_checker)
ex.register_implementation("torch.cross_entropy_bwd", fn=_ce_bwd_impl, checker=_ce_bwd_checker)


# =============================================================================
# Fused rotary embedding (rotate-half ROPE)
# =============================================================================
#
# The decomposed rotate-half runs as slices and lane-dim concatenations of
# pieces narrower than a vector register: 50-lane halves at head size 100 (r4
# profile: ~14 ms/iter of (.., 50)-shaped fusions plus relayouts on the 3B
# bench), and with partial rotary 8-, 16- and 48-lane pieces that cost
# pythia-410m's forward more than its flash attention (PERF.md, PR 25). The
# kernel does the whole thing in one HBM pass per tensor, whatever the rotary
# share n <= hs; the backward is the same kernel with -sin (see the
# torch.apply_rope VJP rule).


_ROPE_BT = 2048  # sequence rows per block


def _rope_checker(x, cos, sin):
    if len(getattr(x, "shape", ())) != 4 or len(getattr(cos, "shape", ())) != 2:
        return False
    T, n = cos.shape
    if not (x.dtype == cos.dtype == sin.dtype):
        return False  # mixed dtypes promote in the decomposition; don't alter semantics
    D = x.shape[-1]
    if n != D:
        # partial rotary: what Mosaic was seen to compile for the v5e (float16
        # has no matmul there; wider rows overflow scoped VMEM at bt=2048)
        dt = dtypes.to_dtype(x.dtype)
        if n > D or dt not in (dtypes.bfloat16, dtypes.float32) or D * dt.bytes > 512:
            return False
    # bt shrinks to a divisor of T
    return x.shape[-2] == T and n % 2 == 0 and T % 8 == 0 and x.shape[0] % batch_shards() == 0


def _rope_rows(ref):
    """The index of a block's (bt, D) rows: ``_rope_impl``'s blocks are
    (1, bt, D), ``_heads_call``'s input blocks (1, 1, bt, D)."""
    return (0,) * (len(ref.shape) - 2)


def _rope_write(out_ref, rows, scale: float = 1.0):
    """``rows * scale`` in float32 and then the one rounding to the output's
    dtype; at ``scale`` 1.0 the kernel is what it was before there was one.
    Where the output block is (1, split, bt, hs), ``rows`` hold ``split`` heads
    side by side in their lanes, and each goes to its own (bt, hs)."""
    import jax.numpy as jnp

    if scale != 1.0:
        rows = rows.astype(jnp.float32) * scale
    hs = out_ref.shape[-1]
    if rows.shape[-1] == hs:
        out_ref[_rope_rows(out_ref)] = rows.astype(out_ref.dtype)
        return
    for i in range(rows.shape[-1] // hs):
        out_ref[0, i] = rows[:, i * hs:(i + 1) * hs].astype(out_ref.dtype)


def _head_norm(rows, weight, *, eps: float, hs: int):
    """``rms_norm`` of each ``hs`` of the rows' D lanes (D // hs heads side by
    side, each by its own mean square) times ``weight`` (1, D), in float32 and
    left there for the steps that follow. Heads narrower than the rows are
    told apart by a lane mask, so nothing narrower than the block is ever cut
    out of it."""
    import jax
    import jax.numpy as jnp

    x = rows.astype(jnp.float32)
    sq, D = x * x, x.shape[-1]
    if hs == D:
        ms = jnp.sum(sq, -1, keepdims=True)
    else:
        head = jax.lax.broadcasted_iota(jnp.int32, sq.shape, 1) // hs
        ms = jnp.zeros_like(sq)
        for i in range(D // hs):
            ms = jnp.where(head == i, jnp.sum(jnp.where(head == i, sq, 0.0), -1, keepdims=True), ms)
    return x * jax.lax.rsqrt(ms * (1.0 / hs) + eps) * weight


def _rotate_half(x, cos_ref, sin_ref):
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    x1 = x[..., :half]
    x2 = x[..., half:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos_ref[...] + rotated * sin_ref[...]


def _rotate_part(x, cos_ref, sin_ref, *, n: int, hs: int):
    """Rotary on the first ``n`` of every ``hs`` of D lanes (D // hs heads side
    by side) without a slice narrower than the block: ``cos`` comes padded with
    ones and ``sin`` with zeros, and rotate-half is a product with the D x D
    signed permutation (one nonzero a column, f32 accumulation: exact), whose
    columns beyond a head's ``n`` are zero. So those lanes leave as
    ``x * 1 + 0 * 0``. A row that holds an inf or a NaN comes out NaN in every
    lane, where the decomposition keeps it to its pair."""
    import jax
    import jax.numpy as jnp

    D, half = x.shape[-1], n // 2
    src = jax.lax.broadcasted_iota(jnp.int32, (D, D), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (D, D), 1)
    at = dst  # a column's place in its head
    if hs != D:
        at = dst % hs
    perm = jnp.where((src == dst + half) & (at < half), -1.0,
                     jnp.where((src == dst - half) & (at >= half) & (at < n), 1.0, 0.0)).astype(x.dtype)
    exact = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None  # bf16 operands are exact as they are
    rotated = jnp.dot(x, perm, preferred_element_type=jnp.float32, precision=exact).astype(x.dtype)
    return x * cos_ref[...] + rotated * sin_ref[...]


def _heads_kernel(x_ref, *refs, hs: int, eps=None, rotate=None, scale: float = 1.0):
    """A block of rows with the steps the call asks for, in the program's
    order: each head's norm (a weight ref and ``eps``), the rotation (cos and
    sin refs and ``rotate``), the scale. Normed rows are float32 from there
    to the one rounding at the write, under a full rotary or none; rows that
    are not normed are rotated in their own dtype, as the rope call has always
    run. With no step it hands the rows on."""
    *refs, out_ref = refs
    rows = x_ref[_rope_rows(x_ref)]
    if eps is not None:
        rows = _head_norm(rows, refs[0][...], eps=eps, hs=hs)
    if rotate is not None:
        if rotate is not _rotate_half:
            # The one place normed rows leave float32 before the write: a partial rotary's permutation product
            # is exact, and one MXU pass, in the tables' dtype only. They are rounded twice there, not once.
            rows = rows.astype(refs[-1].dtype)
        rows = rotate(rows, *refs[-2:])
    _rope_write(out_ref, rows, scale)


def _rope_block(T: int) -> int:
    """Sequence rows a block: ``_ROPE_BT``, shrunk to a divisor of T."""
    bt = _ROPE_BT
    while T % bt:
        bt //= 2
    return bt


def _rotation_and_tables(T: int, D: int, cos, sin, split: int = 1):
    """(the rotation, cos, sin) for rows of D lanes that hold ``split`` heads
    side by side, under (T, n) tables. All but one head of full rotary take
    tables of full width, ones and zeros beyond a head's n, which keeps the
    call's operands."""
    import jax.numpy as jnp

    n, hs = cos.shape[-1], D // split
    if n == D:
        return _rotate_half, cos, sin
    if n != hs:
        cos = jnp.concatenate([cos, jnp.ones((T, hs - n), cos.dtype)], axis=-1)
        sin = jnp.concatenate([sin, jnp.zeros((T, hs - n), sin.dtype)], axis=-1)
    if split != 1:
        cos, sin = jnp.tile(cos, (1, split)), jnp.tile(sin, (1, split))
    return partial(_rotate_part, n=n, hs=hs), cos, sin


def _rope_impl(x, cos, sin):
    chaos.kernel_seam("pallas", "apply_rope")
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def shard(x, cos, sin):
        B, H, T, D = x.shape
        # The VJP needs cos and sin only, never x, so where the rotary is
        # partial x is dead after the call: in place.
        in_place = {0: 0} if cos.shape[-1] != D else {}
        bt = _rope_block(T)
        rotate, cos, sin = _rotation_and_tables(T, D, cos, sin)
        out = pl.pallas_call(
            partial(_heads_kernel, hs=D, rotate=rotate),
            grid=(B * H, T // bt),
            in_specs=[
                pl.BlockSpec((1, bt, D), lambda i, j: (i, j, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((bt, D), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((bt, D), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((1, bt, D), lambda i, j: (i, j, 0), memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((B * H, T, D), x.dtype),
            input_output_aliases=in_place,
            interpret=_interpret(),
        )(x.reshape(B * H, T, D), cos, sin)
        return out.reshape(B, H, T, D)

    with jax.enable_x64(False):
        return per_batch_shard(shard, x, cos.astype(x.dtype), sin.astype(x.dtype), replicated=(1, 2))


ex.register_implementation("torch.apply_rope", fn=_rope_impl, checker=_rope_checker)


# Heads out of a head-major array, for transforms/attention_layout.py: the fused
# qkv projection writes (B, P, T, L) in one dot, and q, k and v are read out of
# it by the block index, so no slice of it is ever made. Heads narrower than the
# 128 lanes lie ``split`` side by side in L = split * hs lanes (two of 64), so
# the projection writes whole tiles and the calls read them whole; each head
# leaves to a (T, hs) of its own, which is what the attention kernel takes. q
# leaves times the softmax scale, which costs attention a pass over q otherwise.
# Where the model norms its heads (an RMSNorm over each head's hs features
# between the projection and the rope: LFM2, Trinity) the same call takes the
# norm's weight and norms the block it holds, in float32, before it rotates;
# where a layer has no rope (Trinity's global ones) it takes no tables and
# rotates nothing. Token-major, the norm costs XLA a float32 copy of q to put
# a head's features in the lanes and three more passes (PERF.md, PR 39).


def heads_per_lane_group(hs: int, *head_counts: int) -> int:
    """How many heads of ``hs`` lanes the projection should lay side by side:
    as many as fill the lane width, where every count of heads divides by it."""
    split = _LANE // hs if hs < _LANE and _LANE % hs == 0 else 1
    return split if all(h % split == 0 for h in head_counts) else 1


def _heads_checker(x, first, heads, split) -> bool:
    if len(getattr(x, "shape", ())) != 4 or x.shape[0] % batch_shards():
        return False
    _, P, T, L = x.shape
    dt = dtypes.to_dtype(x.dtype)
    return (0 <= first and 0 < heads and first + heads <= P * split and T % 8 == 0
            and L % split == first % split == heads % split == 0
            # what Mosaic was seen to compile for the v5e where lanes are split on the way out
            and (split == 1 or (dt in (dtypes.bfloat16, dtypes.float32) and L * dt.bytes <= 512)))


def _rope_heads_checker(x, cos, sin, first, heads, scale=1.0, split=1, norm_weight=None, eps=None):
    """``_rope_checker``'s word on the heads that are read, where they are
    roped; a norm's weight is one head's, in the heads' dtype (another
    promotes in the decomposition)."""
    first, heads, split = int(pyval(first)), int(pyval(heads)), int(pyval(split))
    if not _heads_checker(x, first, heads, split) or (cos is None) != (sin is None):
        return False
    B, _, T, L = x.shape
    if norm_weight is not None and not (tuple(getattr(norm_weight, "shape", ())) == (L // split,)
                                        and norm_weight.dtype == x.dtype and eps is not None):
        return False
    return cos is None or _rope_checker(SimpleNamespace(shape=(B, heads, T, L // split), dtype=x.dtype), cos, sin)


def _split_heads_checker(x, first, heads, split):
    return _heads_checker(x, int(pyval(first)), int(pyval(heads)), int(pyval(split)))


def _heads_call(x, first: int, heads: int, split: int, *, cos=None, sin=None, norm_weight=None, eps=None,
                scale: float = 1.0):
    """One call over (batch, lane groups read, blocks of the sequence): group
    ``first // split + g`` of x (B, P, T, L) in, heads ``split * g`` and on of
    (B, heads, T, L // split) out, normed, roped and scaled where asked.
    Operands: x, then the norm's weight as float32 (1, L), then cos and sin."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    normed, roped = norm_weight is not None, cos is not None

    def shard(x, *rows):
        B, P, T, L = x.shape
        bt, rotate, operands, in_specs = _rope_block(T), None, [], []
        if normed:
            operands.append(jnp.tile(rows[0].astype(jnp.float32), split).reshape(1, L))
            in_specs.append(pl.BlockSpec((1, L), lambda b, g, j: (0, 0), memory_space=pltpu.VMEM))
        if roped:
            rotate, *tables = _rotation_and_tables(T, L, *rows[-2:], split=split)
            operands += tables
            in_specs += [pl.BlockSpec((bt, L), lambda b, g, j: (j, 0), memory_space=pltpu.VMEM)] * 2
        return pl.pallas_call(
            partial(_heads_kernel, hs=L // split, eps=float(eps) if normed else None, rotate=rotate, scale=float(scale)),
            grid=(B, heads // split, T // bt),
            in_specs=[pl.BlockSpec((1, 1, bt, L), lambda b, g, j: (b, first // split + g, j, 0),
                                   memory_space=pltpu.VMEM), *in_specs],
            out_specs=pl.BlockSpec((1, split, bt, L // split), lambda b, g, j: (b, g, j, 0), memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((B, heads, T, L // split), x.dtype),
            input_output_aliases={0: 0} if (heads, split) == (P, 1) else {},  # every head: x's own buffer, as _rope_impl's
            interpret=_interpret(),
        )(x, *operands)

    rows = [a.astype(x.dtype) for a in (norm_weight, cos, sin) if a is not None]
    with jax.enable_x64(False):
        return per_batch_shard(shard, x, *rows, replicated=tuple(range(1, 1 + len(rows))))


def _rope_heads_impl(x, cos, sin, first, heads, scale=1.0, split=1, norm_weight=None, eps=None):
    chaos.kernel_seam("pallas", "apply_rope_heads")
    return _heads_call(x, int(first), int(heads), int(split), cos=cos, sin=sin, norm_weight=norm_weight, eps=eps,
                       scale=float(scale))


def _split_heads_impl(x, first, heads, split):
    chaos.kernel_seam("pallas", "split_heads")
    return _heads_call(x, int(first), int(heads), int(split))


ex.register_implementation("torch.apply_rope_heads", fn=_rope_heads_impl, checker=_rope_heads_checker)
ex.register_implementation("torch.split_heads", fn=_split_heads_impl, checker=_split_heads_checker)


# =============================================================================
# Routed experts: dispatch, jax's megablox grouped matmul, and a short buffer
# =============================================================================
#
# The decomposition of torch.moe_experts (sort the pairs by held expert, gather,
# one grouped matmul a projection, bring the rows back) runs under XLA with a
# buffer of the router's worst case, min(k, held) * N rows. On the v5e that
# buffer is what costs: at 12 of 192 experts held a sixteenth of its rows hold
# work, and XLA's gather of 65,536 rows of 7168 takes 13.9 ms a layer where the
# grouped matmuls take 5 to 6 (PERF.md, PR 27). Claimed here, the same steps run
# on a short buffer, twice what an even router sends here, 2 * k * N * held /
# n_expert rows (expert_buffer_rows). When the rows routed here fit it, once;
# when they do not, in passes over that same buffer (PR 40): a loop over
# ceil(rows here / buffer) chunks of the sorted pairs, each chunk's group sizes
# clipped to it, its rows weighed into a float32 (N, C) sum. One lax.cond on a
# count the router just made chooses, so no token is ever dropped, no call
# fails, and nothing is sized by the worst case: at 16 of 768 outputs held, 12 a
# token and 16,384 tokens the worst case was 196,608 rows, 8 GB of temporaries
# for a branch an even router never takes, where the buffer is 8,192 rows.
# Where the short buffer is no shorter than the worst case (every expert held:
# mixtral) there is one buffer and no branch. The grouped matmul is megablox's
# gmm (30.2 ms a call of a.x-k1.fwd against 37.4 for XLA's own ragged dot, same
# seed).
#
# The dispatch moves each row once each way (PR 32). The pairs lie choice-major
# (pair j * N + n), so the way back is k gathers of (N, C) and never a (rows, C)
# array relaid as (N, k, C), which puts k in the sublanes and costs a copy. No
# gather asks for jnp.take's fill: order is a permutation and slot is clamped,
# and the fill is a pass of its own over the buffer. One fusion reads the k
# gathers: it selects, converts, weighs in float32 and sums over k in order, so
# no float32 copy of the buffer is written. The sum is written term by term
# behind a barrier because of what XLA makes of the other forms in the cells'
# programs: open to the caller's fusions, lfm2-8b-a1b.fwd keeps k converts of
# (N, C) standing alone; as a reduce over the gathers stacked (k, N, C),
# a.x-k1.fwd writes the stack, 940 MB a layer.
# Where some experts are held elsewhere the rows beyond the groups are whatever
# gmm left there and may be NaN: the mask is a select, on bf16, inside that
# fusion, and never a multiply by 0. Where every expert is held every pair has
# a computed row and there is no mask.

_GMM_TILING = (512, 1024, 1024)
_SHORT_BUFFER_OVER_EVEN_LOAD = 2


def _gmm_tile(width: int, most: int = _GMM_TILING[1]) -> int:
    """The grouped matmul's tile along a dimension of ``width``: the largest
    multiple of the lanes between half of ``most`` and ``most`` that divides it
    (896 for experts 1792 wide, where a tile of 1024 leaves the second a quarter
    empty and masks the down projection's contraction), else ``most`` or the
    width, as before: a last tile partly empty costs less than many small ones."""
    for tile in range(min(most, width) // _LANE * _LANE, most // 2 - 1, -_LANE):
        if width % tile == 0:
            return tile
    return min(most, width)


def _moe_experts_checker(x, top_i, top_w, w_gate, w_up, w_down, expert_offset=0, n_expert=None):
    if dtypes.to_dtype(x.dtype) is not dtypes.bfloat16 or dtypes.to_dtype(w_gate.dtype) is not dtypes.bfloat16:
        return False
    if len(x.shape) != 2 or batch_shards() != 1:  # a sort over all tokens is no per-shard kernel
        return False
    (N, C), k, (held, _, H) = x.shape, top_i.shape[1], w_gate.shape
    return (min(k, held) * N) % _GMM_TILING[0] == 0 and C % _LANE == 0 and H % _LANE == 0


def expert_buffer_rows(N: int, k: int, held: int, n_expert=None) -> int:
    """Rows of the buffer the claimed ``moe_experts`` works on for N tokens of k
    choices among ``n_expert`` outputs, ``held`` of them here: twice the even
    load in whole row tiles, and never more than the worst case."""
    even = -(-k * N * held // (n_expert or held))  # the rows an even router sends here
    short = -(-_SHORT_BUFFER_OVER_EVEN_LOAD * even // _GMM_TILING[0]) * _GMM_TILING[0]
    return min(short, min(k, held) * N)


def expert_buffer_passes(rows_here: int, N: int, k: int, held: int, n_expert=None) -> int:
    """How often the claimed ``moe_experts`` goes over its buffer when the router sends ``rows_here`` rows here."""
    return max(1, -(-int(rows_here) // expert_buffer_rows(N, k, held, n_expert)))


def _moe_experts_impl(x, top_i, top_w, w_gate, w_up, w_down, expert_offset=0, n_expert=None):
    chaos.kernel_seam("pallas", "moe_experts")
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    (N, C), k, held = x.shape, top_i.shape[1], w_gate.shape[0]
    tm = _GMM_TILING[0]
    rows = expert_buffer_rows(N, k, held, n_expert)

    def grouped(a, b, sizes):
        return gmm(a, b, sizes, preferred_element_type=a.dtype,
                   tiling=(tm, _gmm_tile(a.shape[1]), _gmm_tile(b.shape[2])), interpret=_interpret())

    # Every expert held: every pair has a computed row, and the way back needs no mask.
    masked = not (int(expert_offset) == 0 and n_expert in (None, held) and k <= held)
    in_bounds = "promise_in_bounds"  # order is a permutation and slot is clamped: no gather needs a fill

    with jax.enable_x64(False):
        local = top_i.astype(jnp.int32) - int(expert_offset)
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held).T.reshape(k * N)  # choice-major: pair j * N + n
        order = jnp.argsort(key, stable=True)  # held pairs first, by expert
        slot = jnp.zeros(k * N, jnp.int32).at[order].set(jnp.arange(k * N, dtype=jnp.int32))
        sizes = jnp.sum(key[:, None] == jnp.arange(held, dtype=jnp.int32), axis=0, dtype=jnp.int32)
        weight = top_w.astype(jnp.float32)

        def experts(xs, sizes):
            gate = grouped(xs, w_gate, sizes).astype(jnp.float32)
            h = (jax.nn.silu(gate) * grouped(xs, w_up, sizes).astype(jnp.float32)).astype(x.dtype)
            return grouped(h, w_down, sizes)

        def weighed(ys, came, keep, out):
            """``out`` plus the k gathers of ys (rows, C) by came (k, N), each weighed in float32; ``keep`` (N, k)
            or None selects, never multiplies: a row the grouped matmul did not compute may be NaN."""
            for j in range(k):
                back = ys.at[came[j]].get(mode=in_bounds)
                if keep is not None:
                    back = jnp.where(keep[:, j, None], back, jnp.zeros((), back.dtype))
                out = out + back.astype(jnp.float32) * weight[:, j, None]
            return out

        def once():
            ys = experts(x.at[order[:rows] % N].get(mode=in_bounds), sizes)
            out = weighed(ys, jnp.minimum(slot, rows - 1).reshape(k, N), here if masked else None, 0.0)
            # The barrier keeps the k converts in the sum's fusion: a reshape after the call draws the sum into
            # the caller's next fusion otherwise and leaves each behind, a float32 (N, C) written and read.
            return jax.lax.optimization_barrier(out.astype(x.dtype))

        def in_passes():
            """The rows routed here are more than the buffer: chunk p is the sorted pairs [p rows, (p + 1) rows),
            an expert's group in it what of its run of the sorted pairs lies inside. A chunk's rows go back a choice
            at a time, in a loop, so that one gather of (N, C) is alive beside the float32 sum and not k of them
            (2.4 GB at 12 choices of 16,384 tokens of 6144). The sum adds a token's k terms in another order than
            ``once`` does, and nothing else differs."""
            ends = jnp.cumsum(sizes)
            at, kept, by_choice = slot.reshape(k, N), here.T, weight.T

            def one(p, out):
                lo = p * rows
                inside = jnp.clip(ends, lo, lo + rows) - jnp.clip(ends - sizes, lo, lo + rows)
                pairs = order.at[jnp.minimum(lo + jnp.arange(rows, dtype=jnp.int32), k * N - 1)].get(mode=in_bounds)
                ys = experts(x.at[pairs % N].get(mode=in_bounds), inside)

                def one_choice(j, out):
                    back = ys.at[jnp.clip(at[j] - lo, 0, rows - 1)].get(mode=in_bounds)
                    keep = kept[j] & (at[j] >= lo) & (at[j] < lo + rows)  # selected out, never multiplied: may be NaN
                    back = jnp.where(keep[:, None], back, jnp.zeros((), back.dtype))
                    return out + back.astype(jnp.float32) * by_choice[j][:, None]

                return jax.lax.fori_loop(0, k, one_choice, out)

            out = jax.lax.fori_loop(0, -(-ends[-1] // rows), one, jnp.zeros((N, C), jnp.float32))
            return jax.lax.optimization_barrier(out.astype(x.dtype))

        if rows == min(k, held) * N:
            return once()
        return jax.lax.cond(jnp.sum(sizes) <= rows, once, in_passes)


ex.register_implementation("torch.moe_experts", fn=_moe_experts_impl, checker=_moe_experts_checker)


# =============================================================================
# Block-sparse attention over the blocks the data chose (torch.sparse_block_attend)
# =============================================================================
#
# A flash-style forward over a mask that block ids give: no score, no exp and no
# mask element reaches HBM, where jaxex's lax.map passes write every bf16 score
# once and read it twice (PERF.md, PR 33: some 500 of minicpm-sala.fwd-t32k's
# 2,106 ms). All R = H / G query heads of a key-value head share one selection,
# so a tile of tq consecutive queries is R tq rows against one mask.
#
# A call holds the keys and values of one key-value head whole in VMEM (8 MB
# each at 32,768 positions of 128) and walks, for each tile of tq queries, the
# tiles of tk keys up to the tile's causal frontier, with a running maximum and
# sum a row. Scores are held keys-major, (tk, tq) a head: the reductions over
# keys are then sums of whole registers, the running maximum and sum are rows of
# lanes, and the mask of a pair of tiles is built from the tile's (n, tq) ids by
# nbt = tk / block_size comparisons and broadcasts along sublanes, once for the
# R heads. The values come transposed, (d, tk) a tile, so that the accumulator
# is (d, tq); it is transposed once, when a query tile is done.
#
# It adapts to the ids: a key tile none of whose blocks any query of the query
# tile chose is skipped whole, by a flag a pair that XLA makes from the ids
# (``_sparse_pair_flags``) and the call reads in SMEM.

# Measured on the v5e at minicpm-sala.fwd-t32k's shapes, a layer (PERF.md, PR 34): 256 x 1024 reads 63.9 ms, 256 x 2048
# 63.2, 256 x 512 66.5, 256 x 256 67.6, 512 x 1024 67.2, 512 x 512 69.6, 128 x 256 72.7, 128 x 1024 and 128 x 2048 80.6,
# 128 x 512 84.0, 512 x 2048 78.3: 3.7 ps a causal score where the MXU's least is 2.6.
_SPARSE_ATTEND_TILES = (256, 1024)  # queries and keys a tile
# The flags a call reads in SMEM, of which the v5e's compiler gives a call 1 MiB (no chip: 262,144 flags ran out by 1.1 K).
_SPARSE_ATTEND_FLAGS_MOST = 64 * 1024


def _sparse_pair_flags(block_ids, block_size: int, tq: int, tk: int):
    """(B, G, T // tq, T // tk) bool: whether any query of a query tile chose a
    block of a key tile. ``block_ids`` (B, G, T, n), -1 for none."""
    import jax.numpy as jnp

    B, G, T, n = block_ids.shape
    tile_of = jnp.where(block_ids >= 0, block_ids * block_size // tk, -1).reshape(B, G, T // tq, tq * n)
    return (tile_of[..., None] == jnp.arange(T // tk, dtype=block_ids.dtype)).any(-2)


def visited_key_tiles(block_ids, block_size: int, tq: int | None = None, tk: int | None = None):
    """(visited, causal): of the pairs of a tile of ``tq`` queries and a tile of
    ``tk`` keys at or before its causal frontier, summed over batch and
    key-value heads, how many the kernel computes (some query of the tile chose
    a block of the key tile) and how many there are. A pure function of the
    ids and the two tile sizes (the kernel's own by default)."""
    import jax.numpy as jnp

    tq, tk = tq or _SPARSE_ATTEND_TILES[0], tk or _SPARSE_ATTEND_TILES[1]
    B, G, T, _ = block_ids.shape
    frontier = -(-(jnp.arange(1, T // tq + 1) * tq) // tk)                       # key tiles a query tile can see
    causal = jnp.arange(T // tk)[None, :] < frontier[:, None]
    visited = _sparse_pair_flags(block_ids, block_size, tq, tk) & causal
    return jnp.sum(visited), B * G * jnp.sum(causal)


def _sparse_attend_vmem(T: int, R: int, d: int, itemsize: int) -> int:
    """What a call holds in VMEM: k and v whole and the blocks of q and the
    output, each twice for the pipeline; the accumulator; and a head's float32
    scores, their exp and its copy in v's dtype, some four (tk, tq) at once.
    Three quarters of ``_ce_vmem_limit()``, which this call asks for too, may
    go to them."""
    tq, tk = _SPARSE_ATTEND_TILES
    return 4 * T * d * itemsize + 4 * R * tq * d * itemsize + R * d * tq * 4 + 4 * tk * tq * 4


def _sparse_attend_checker(q, k, v, block_ids, *, block_size, scale=None, query_chunk=None):
    if query_chunk is not None or any(len(getattr(a, "shape", ())) != 4 for a in (q, k, v, block_ids)):
        return False
    dtype = dtypes.to_dtype(q.dtype)
    if dtype not in (dtypes.bfloat16, dtypes.float32) or any(dtypes.to_dtype(a.dtype) is not dtype for a in (k, v)):
        return False
    (B, H, T, d), G = q.shape, k.shape[1]
    tq, tk = _SPARSE_ATTEND_TILES
    block_size = int(pyval(block_size))
    return (d % _LANE == 0 and H % G == 0 and T % tq == 0 and T % tk == 0 and tk % block_size == 0
            and block_size % 8 == 0 and tuple(k.shape) == tuple(v.shape) == (B, G, T, d) and B % batch_shards() == 0
            and B // batch_shards() * G * (T // tq) * (T // tk) <= _SPARSE_ATTEND_FLAGS_MOST
            and _sparse_attend_vmem(int(T), H // G, int(d), dtype.bytes) <= 3 * _ce_vmem_limit() // 4)


def _sparse_attend_kernel(flags_ref, q_ref, k_ref, vt_ref, ids_ref, out_ref, m_scr, l_scr, acc_scr, *,
                          scale: float, block_size: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    R, tq = q_ref.shape[2], q_ref.shape[3]
    nk, tk = k_ref.shape[2], k_ref.shape[3]
    b, g, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    first_flag = ((b * pl.num_programs(1) + g) * pl.num_programs(2) + qi) * nk
    f32 = jnp.float32

    m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, f32)
    l_scr[...] = jnp.zeros(l_scr.shape, f32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, f32)
    ids = ids_ref[0, 0]                                                          # (n, tq): a query's ids down its lane
    ahead = (qi * tq + jax.lax.broadcasted_iota(jnp.int32, (tk, tq), 1)
             - jax.lax.broadcasted_iota(jnp.int32, (tk, tq), 0))                 # query's position - key's place in its tile

    def pair(kt, carry):
        @pl.when(flags_ref[first_flag + kt] != 0)
        def _():
            # The mask of the pair, once for the R heads: a key is kept if its block is among its query's ids and it
            # is not after the query.
            first_block = kt * (tk // block_size)
            chosen = [jnp.max(jnp.where(ids == first_block + i, 1.0, 0.0), axis=0, keepdims=True)
                      for i in range(tk // block_size)]
            chosen = jnp.concatenate([jnp.broadcast_to(row, (block_size, tq)) for row in chosen], axis=0)
            bias = jnp.where((chosen > 0.0) & (ahead >= kt * tk), 0.0, -jnp.inf)
            keys, values = k_ref[0, 0, kt], vt_ref[0, 0, kt]                      # (tk, d), (d, tk)
            for r in range(R):
                s = jax.lax.dot_general(keys, q_ref[0, 0, r], (((1,), (1,)), ((), ())), preferred_element_type=f32)
                s = s * scale + bias                                             # (tk, tq)
                m_was = m_scr[r]
                m = jnp.maximum(m_was, jnp.max(s, axis=0, keepdims=True))
                at = jnp.where(m == -jnp.inf, 0.0, m)                            # a row with no key yet adds nothing
                shrink = jnp.exp(m_was - at)
                p = jnp.exp(s - at)
                l_scr[r] = l_scr[r] * shrink + jnp.sum(p, axis=0, keepdims=True)
                acc_scr[r] = acc_scr[r] * shrink + jnp.dot(values, p.astype(values.dtype), preferred_element_type=f32)
                m_scr[r] = m
        return carry

    jax.lax.fori_loop(0, ((qi + 1) * tq + tk - 1) // tk, pair, 0)
    for r in range(R):
        out_ref[0, 0, r] = (acc_scr[r] / l_scr[r]).T.astype(out_ref.dtype)


def _sparse_attend_impl(q, k, v, block_ids, *, block_size, scale=None, query_chunk=None):
    chaos.kernel_seam("pallas", "sparse_block_attend")
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_size = int(block_size)
    scale = float(scale) if scale is not None else q.shape[-1] ** -0.5
    tq, tk = _SPARSE_ATTEND_TILES

    def shard(q, k, v, block_ids):
        (B, H, T, d), G, n = q.shape, k.shape[1], block_ids.shape[-1]
        R, nq, nk = H // G, T // tq, T // tk
        flags = _sparse_pair_flags(block_ids, block_size, tq, tk).astype(jnp.int32).reshape(-1)
        whole = lambda *block: pl.BlockSpec((1, 1, *block), lambda b, g, i, flags: (b, g, 0, 0, 0), memory_space=pltpu.VMEM)
        rows = pl.BlockSpec((1, 1, R, tq, d), lambda b, g, i, flags: (b, g, 0, i, 0), memory_space=pltpu.VMEM)
        out = pl.pallas_call(
            partial(_sparse_attend_kernel, scale=scale, block_size=block_size),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B, G, nq),
                in_specs=[rows, whole(nk, tk, d), whole(nk, d, tk),
                          pl.BlockSpec((1, 1, n, tq), lambda b, g, i, flags: (b, g, 0, i), memory_space=pltpu.VMEM)],
                out_specs=rows,
                scratch_shapes=[pltpu.VMEM((R, 1, tq), jnp.float32), pltpu.VMEM((R, 1, tq), jnp.float32),
                                pltpu.VMEM((R, d, tq), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((B, G, R, T, d), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel"), vmem_limit_bytes=_ce_vmem_limit()),
            name="sparse_attend_fwd",
            interpret=_interpret(),
        )(flags, q.reshape(B, G, R, T, d), k.reshape(B, G, nk, tk, d),
          jnp.swapaxes(v.reshape(B, G, nk, tk, d), -2, -1), jnp.swapaxes(block_ids, -2, -1))
        return out.reshape(B, H, T, d)

    with jax.enable_x64(False):
        return per_batch_shard(shard, q, k, v, block_ids.astype(jnp.int32))


ex.register_implementation("torch.sparse_block_attend", fn=_sparse_attend_impl, checker=_sparse_attend_checker)


# =============================================================================
# Attention within a window (torch.window_attention, ahead of flash)
# =============================================================================
#
# The body above under a window's mask, for the layers whose queries see their
# own key and the ``window - 1`` before it. splash skips a tile only whole and
# pays a grid step a pair of tiles, so its tiles are 1024 and a window of 2,048
# visits three key tiles a query tile, half again the pairs the window keeps.
# Here a grid step is a tile of tq queries of the R = H / G heads that share a
# key-value head, and the key tiles its window touches are walked in a
# ``fori_loop`` inside the step: small tiles cost no grid step, and nine key
# tiles of 256 (2,304 keys for 2,048) are 1.125 times the pairs. k and v come
# as ``window_attention`` hands them, (B, G, T, d), and are read once for the
# R query heads: nothing expands them. A step holds the span of keys its
# window touches and no more (``pl.Element`` indexing: neighbouring steps'
# spans overlap), so the call lives in the default scope of VMEM whatever T
# is. A key tile that every query of the tile sees whole takes no mask; the
# tile at the window's far edge and the tiles on the diagonal compare
# positions, once for the R heads. A query none of whose keys lie in a masked
# tile keeps a maximum of -inf there and adds nothing, as above.
#
# The checker takes bf16 heads of a multiple of 128 whose T the tiles divide
# and whose span fits the VMEM it reckons; every other call is splash's
# (``flashex``), as before.

# Measured on the v5e at trinity-mini.fwd-t32k's shapes, a layer of 32 on 4 heads of 128, 32,768 positions, window 2,048
# (PERF.md, PR 42; splash at 1024 x 1024 reads 12.28 ms and 13.22 with k and v expanded in front of it): 256 x 256 reads
# 9.52 ms, 256 x 128 9.60, 256 x 512 10.23, 512 x 512 10.64, 128 x 256 11.23, 256 x 1024 11.80, 512 x 256 11.84,
# 128 x 128 12.17: 3.93 ps a score visited, and key tiles of 256 visit 1.125 times the pairs where 512 visit 1.25. With q
# contracted on its last dimension in every pair (as the body above has it) 256 x 256 read 10.91, with every tile masked
# 10.96 for that, with k and v whole in VMEM and v turned in front of the call 9.58.
_WINDOW_ATTEND_TILES = (256, 256)  # queries and keys a tile


def _window_attend_walk(T: int, window: int):
    """The key tiles each query tile walks, from the one that holds its first
    query's oldest key to its causal frontier."""
    tq, tk = _WINDOW_ATTEND_TILES
    return [-(-(qi + 1) * tq // tk) - max(qi * tq - (window - 1), 0) // tk for qi in range(T // tq)]


def window_attend_tiles(T: int, window: int) -> int:
    """Score elements of the tiles the kernel computes for one head of ``T`` positions."""
    tq, tk = _WINDOW_ATTEND_TILES
    return sum(_window_attend_walk(T, window)) * tq * tk


def _window_attend_vmem(T: int, window: int, R: int, d: int, itemsize: int) -> int:
    """What a call holds in VMEM, reckoned as ``_sparse_attend_vmem`` does: the
    spans of k and v and the blocks of q and the output, each twice for the
    pipeline; q turned and the accumulator; some four (tk, tq) of float32 at
    once."""
    tq, tk = _WINDOW_ATTEND_TILES
    span = max(_window_attend_walk(T, window)) * tk
    return 4 * span * d * itemsize + 4 * R * tq * d * itemsize + R * d * tq * (itemsize + 4) + 4 * tk * tq * 4


def window_attend_fits(T: int, window: int, R: int, d: int, itemsize: int) -> bool:
    """Whether the call's shapes are the kernel's: heads of whole lane groups,
    a sequence both tiles divide, and a window's span of k and v within three
    quarters of the default scope of VMEM, which is all the call asks for."""
    tq, tk = _WINDOW_ATTEND_TILES
    return (d % _LANE == 0 and T % tq == 0 and T % tk == 0
            and _window_attend_vmem(T, window, R, d, itemsize) <= 3 * _SCOPED_VMEM_DEFAULT // 4)


def _window_attend_checker(q, k, v, *, window, scale=None) -> bool:
    from thunder_tpu.executors import flashex

    if not flashex._on_tpu() or any(len(getattr(a, "shape", ())) != 4 for a in (q, k, v)):
        return False
    if any(dtypes.to_dtype(a.dtype) is not dtypes.bfloat16 for a in (q, k, v)):  # float32 keeps its precision
        return False
    (B, H, T, d), G, window = q.shape, k.shape[1], int(pyval(window))
    return (window >= 1 and H % G == 0 and tuple(k.shape) == tuple(v.shape) == (B, G, T, d)
            and B % batch_shards() == 0 and window_attend_fits(int(T), window, H // G, int(d), 2))


def _window_attend_kernel(q_ref, k_ref, v_ref, out_ref, _, __, qt_scr, m_scr, l_scr, acc_scr, *,
                          window: int, tk: int, T: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    R, tq = q_ref.shape[2], q_ref.shape[3]
    first_query = pl.program_id(2) * tq
    first_tile = jnp.maximum(first_query - (window - 1), 0) // tk
    held_from = jnp.minimum(first_tile * tk, T - k_ref.shape[2])                  # the first key of the span this step holds
    f32 = jnp.float32

    m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, f32)
    l_scr[...] = jnp.zeros(l_scr.shape, f32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, f32)
    for r in range(R):  # q turned once a step: contracted on its last dimension it is turned again for every key tile (+14%)
        qt_scr[r] = q_ref[0, 0, r].astype(f32).T.astype(qt_scr.dtype)

    def pair(kt, masked: bool):
        first_key = kt * tk
        rows = pl.ds(pl.multiple_of(first_key - held_from, tk), tk)
        keys = k_ref[0, 0, rows, :]                                              # (tk, d)
        values = v_ref[0, 0, rows, :].astype(f32).T.astype(v_ref.dtype)          # (d, tk), turned once for the R heads
        if masked:  # the mask of the pair, once for the R heads: a key is kept if its query is 0 to window - 1 ahead of it
            ahead = (first_query - first_key + jax.lax.broadcasted_iota(jnp.int32, (tk, tq), 1)
                     - jax.lax.broadcasted_iota(jnp.int32, (tk, tq), 0))
            seen = (ahead >= 0) & (ahead < window)
        for r in range(R):
            s = jnp.dot(keys, qt_scr[r], preferred_element_type=f32)              # (tk, tq)
            if masked:
                s = jnp.where(seen, s, -jnp.inf)
            m_was = m_scr[r]
            m = jnp.maximum(m_was, jnp.max(s, axis=0, keepdims=True))
            at = jnp.where(m == -jnp.inf, 0.0, m) if masked else m               # a row with no key yet adds nothing
            shrink = jnp.exp(m_was - at)
            p = jnp.exp(s - at)
            l_scr[r] = l_scr[r] * shrink + jnp.sum(p, axis=0, keepdims=True)
            acc_scr[r] = acc_scr[r] * shrink + jnp.dot(values, p.astype(values.dtype), preferred_element_type=f32)
            m_scr[r] = m

    def step(kt, carry):
        # every query of the tile sees every key of the tile: none is after its query, none has left the window
        whole = (kt * tk + tk - 1 <= first_query) & (first_query + tq - 1 - kt * tk < window)
        pl.when(whole)(lambda: pair(kt, False))
        pl.when(jnp.logical_not(whole))(lambda: pair(kt, True))
        return carry

    jax.lax.fori_loop(first_tile, (first_query + tq + tk - 1) // tk, step, 0)
    for r in range(R):
        out_ref[0, 0, r] = (acc_scr[r] / l_scr[r]).T.astype(out_ref.dtype)


def _window_attend_impl(q, k, v, *, window, scale=None):
    chaos.kernel_seam("pallas", "window_attention")
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from thunder_tpu.executors import flashex

    scale = float(scale) if scale is not None else q.shape[-1] ** -0.5
    window = int(window)
    tq, tk = _WINDOW_ATTEND_TILES

    def shard(q, k, v):
        (B, H, T, d), G = q.shape, k.shape[1]
        R, span = H // G, max(_window_attend_walk(T, window)) * tk

        def held(b, g, i):  # the keys a step holds, by their first: neighbouring steps' spans overlap
            first = jnp.maximum(i * tq - (window - 1), 0) // tk * tk
            return b, g, pl.multiple_of(jnp.minimum(first, T - span), tk), 0

        keys = pl.BlockSpec((pl.Element(1), pl.Element(1), pl.Element(span), pl.Element(d)), held, memory_space=pltpu.VMEM)
        rows = pl.BlockSpec((1, 1, R, tq, d), lambda b, g, i: (b, g, 0, i, 0), memory_space=pltpu.VMEM)
        like_q = jax.ShapeDtypeStruct((B, G, R, T, d), q.dtype)
        out = pl.pallas_call(
            partial(_window_attend_kernel, window=window, tk=tk, T=T),
            grid=(B, G, T // tq),
            in_specs=[rows, keys, keys],
            # Two results that nothing writes or reads stand where splash's call had k and v expanded to the query
            # heads. They cost no time, and the attention layers are not the program's peak; but without two more
            # arrays of q's size alive at the call XLA's heap plans trinity-mini.fwd-t32k's temporaries 248 MB
            # larger, though fewer bytes are alive at their peak (PERF.md, PR 42; section 7).
            out_specs=[rows, pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
            out_shape=[like_q, like_q, like_q],
            scratch_shapes=[pltpu.VMEM((R, d, tq), q.dtype), pltpu.VMEM((R, 1, tq), jnp.float32),
                            pltpu.VMEM((R, 1, tq), jnp.float32), pltpu.VMEM((R, d, tq), jnp.float32)],
            compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel")),
            name="window_attend_fwd",
            interpret=_interpret(),
        )(q.reshape(B, G, R, T, d), k, v)[0]
        return out.reshape(B, H, T, d)

    with jax.enable_x64(False):
        return per_batch_shard(shard, flashex._scaled(q, scale), k, v)  # the softmax scale is q's, as splash has it


ex.register_implementation("torch.window_attention", fn=_window_attend_impl, checker=_window_attend_checker)


# =============================================================================
# The state-space recurrence whose decay each token sets (torch.ssm_scan)
# =============================================================================
#
# ``ssm_scan``'s chunked decomposition with the state carried in VMEM. XLA runs
# the decomposition as some ten float32 arrays of the activation's size a layer,
# turned between token-major and head-major, with the chunks' summaries and the
# entering states in HBM and an (n, n) product over them (PERF.md, PR 44: 7.5
# ms a layer of granite-4.0-h-micro.fwd-t16k for a bound of 0.34). Here a grid
# step is one chunk of positions of every head: x is seen as (B, T, H P), the
# heads side by side in the lanes as the model's projection wrote them, so x,
# dt, B and C are read token-major, once, and y is written token-major, once.
# The grid walks a sequence's chunks in order and the state, (N, P) a head and
# float32, stays in a VMEM scratch from chunk to chunk: the entering state's
# part of the output is one matmul against it, and it leaves the step as
# ``exp(total) S + (B^T * dt_j exp(cum_end - cum_j)) x``.
#
# Within a step: the running sum ``cum`` of ``dt A`` for every head by a product
# with a lower-triangular matrix of ones, ``C B^T`` once a group, and then a
# loop over the heads, ``_SSM_SCAN_HEADS`` a turn. Two heads of 64 fill a lane
# group of x, and a lane group is what the MXU is given: a head's masked form
# ``C B^T * exp(cum_i - cum_j) * dt_j``, made in tiles of 128 queries by 128
# keys at and under the diagonal (the tiles above it are never made) and
# rounded to x's dtype, goes against both heads' x as it lies in VMEM, and the
# head's own lanes of the result are kept; likewise ``B^T * dt_j exp(cum_end -
# cum_j)`` for the state. dt and the decays are a number a position and head,
# and x holds positions down its sublanes: to scale x's rows by them each
# would have to be spread over the lanes, which goes through the XLU, 7 cycles
# a register on one of three units (a first form that scaled x that way spent
# 85% of the XLU and ran a third slower by the compiler's own schedule). So
# everything that belongs to a key is folded into the masked form, where keys
# lie along the lanes and a head's row spreads over sublanes for nothing, and
# only the queries' ``cum_i`` is spread over lanes, 32 registers a head and
# chunk. ``cum`` and ``dt`` are turned, (H, L), by a product with the identity.
# The loop's index chooses the heads, so a turn's columns and rows lie in
# scratches of their own, found by their first index.
#
# Precision is the decomposition's or better: decays, sums and the state in
# float32, the matmuls' operands in x's dtype with float32 accumulation; x
# itself goes to the MXU unrounded where the decomposition rounds ``dt x``,
# and where the decomposition rounds ``C B^T``, the summaries and the entering
# state to x's dtype between two matmuls, nothing is rounded here. Every
# exponent is a sum of ``dt A <= 0``.
#
# The checker takes bf16 x, B and C, heads of 64 on a state of 64 or 128, an even
# count of heads a group, whole chunks of a multiple of 128 positions, and a
# step whose blocks and state fit the VMEM it asks for; every other call is the
# decomposition's. The trace VJP differentiates the decomposition: there is no
# backward kernel.
#
# x, B and C are the three parts of one array, the convolution's ``[x | B | C]``,
# and a custom call's operand lies whole in HBM: handed three slices, XLA writes
# x out as a copy in front of every call (0.41 ms a layer in that cell: PERF.md,
# PR 46). ``torch.ssm_scan_packed`` (``transforms/ssm_layout.py`` writes it where
# the program cut the three out of one array for this call alone) is the same
# call on the packed array, given three times, each ``BlockSpec`` at its block's
# index along the last dimension: block 0 of H P columns, blocks ``H P / (G N)``
# and one more of G N. That wants G N whole lane groups and H P a multiple of
# G N; a packed call that is not so is its decomposition's, the three slices
# and ``ssm_scan``.
#
# That array is the causal convolution's result, and the scan its one reader:
# written by XLA's pass and read back once by the kernel, 143 MB each way a
# layer of that cell, in a pass at a third of its bound (PERF.md: 38 ms of a
# 750 ms call). ``torch.ssm_conv_scan`` (written where the convolution
# reads a column range of its projection for this call alone) is the same call
# on the projection: x, B and C are blocks of it at their columns' index, and a
# step convolves B and C before the turns and each pair's lane group of x in
# the turn that reads it, into VMEM scratches the body reads where it read the
# blocks. A causal convolution wants the K - 1 rows before the chunk: the grid
# walks a sequence's chunks in order, so the last 8 input rows of a chunk stay
# in a scratch for the next one, zeroed at chunk 0 as the state is. A lane
# group at a time, its input rows in float32 below those 8 in a scratch of
# their own, each tap is a load of that scratch from its row, and the sum (in
# the decomposition's order, tap 0 first and the bias last), the SiLU with an
# exact division and the one rounding are the decomposition's. That wants K - 1
# <= 7, x's columns at a multiple of H P and B's at a multiple of G N; any
# other site keeps the packed call. By the compiler's schedule a step of that
# cell is 24,255 bundles (the packed call's 19,324): 27 vector operations a
# float32 register of the convolution, 13 of them the exact division, which
# the turns' matmuls leave room for; convolved first, in a loop of its own,
# 27,815; with the next turn's x convolved in each turn, 25,145 to 27,564.

# Measured on the v5e at granite-4.0-h-micro.fwd-t16k's shapes, a layer's call alone with the two copies that turn x
# and y between (T, 64, 64) and (T, 4096) around it (PERF.md, PR 45; XLA's decomposition reads 8.38 ms there): a turn of
# 8 heads 1.734 ms, of 4 heads 1.797, of 2 heads 1.932 (an earlier form); at a chunk of 128 2.097, of 512 1.837, the
# chunk being the call's. By the compiler's schedule for the described chip (`--xla_jf_dump_to` writes the final
# bundles): a turn of 8 heads 2,184 bundles, the MXU's 384 pushes of 16 rows 70% of them; a turn of 4 1,120. In the
# cell's program the call reads 0.876 ms a layer (a first form that scaled x's rows, 2,835 bundles a turn, 1.254).
_SSM_SCAN_HEADS = 8  # heads a turn of the loop inside a step, unrolled
_SSM_SCAN_HEAD = 64  # the head the kernel is written for: two fill a lane group


def _ssm_scan_turn(H: int, G: int):
    """Heads a turn of the loop takes, all of one group and whole lane groups
    of x, or None where the heads do not fall that way."""
    R = H // G
    u = min(_SSM_SCAN_HEADS, R)
    return u if R % u == 0 and u % 2 == 0 else None


_SSM_CONV_HALO = 8  # input rows a step keeps for the next: one sublane tile, so K - 1 <= 7 taps look back


def _ssm_scan_vmem(L: int, H: int, N: int, G: int, itemsize: int, convolves: bool = False) -> int:
    """What a step holds in VMEM: the blocks of x and y, of dt (its heads padded
    to the lanes) and of B and C, each twice for the pipeline; the state, the
    turns' columns and rows, ``C B^T`` and B turned in float32 and C a group;
    the triangle and the running sums before the loop, and some twelve float32
    arrays of a tile of queries by a lane group inside it. Where the step
    convolves too: the taps and the bias, twice; a turn's x and B and C
    convolved; the kept rows of every column, and a pair's rows below them in
    float32; and some eight float32 arrays of a lane group's rows."""
    u, P = _ssm_scan_turn(H, G) or 2, _SSM_SCAN_HEAD
    blocks = 4 * L * H * P * itemsize + 2 * L * max(H, _LANE) * 4 + 4 * L * max(G * N, _LANE) * itemsize
    scratch = (N * H * P * 4 + (H // u) * L * (_LANE + 3 * 8) * 4 + G * (L * L + max(N, 8) * L) * 4
               + G * L * max(N, _LANE) * itemsize)
    needed = blocks + scratch + 2 * L * L * 4 + 6 * L * max(H, _LANE) * 4 + 12 * _LANE * _LANE * 4
    if convolves:
        W = H * P + 2 * G * N
        needed += (4 * _SSM_CONV_HALO * W * 4 + L * (u * P + 2 * G * N) * itemsize + _SSM_CONV_HALO * W * 4
                   + u // 2 * (_SSM_CONV_HALO + L) * _LANE * 4 + 8 * L * _LANE * 4)
    return needed


def _ssm_scan_scope(needed: int) -> int:
    """The scope of VMEM a call lives in whose step holds ``needed`` bytes: the
    default where three quarters of it hold the step, else what
    ``_ce_vmem_limit()`` asks of the device's generation; the checker holds
    the step to three quarters of either."""
    return _SCOPED_VMEM_DEFAULT if needed <= 3 * _SCOPED_VMEM_DEFAULT // 4 else _ce_vmem_limit()


def _ssm_scan_chunk(chunk, T: int) -> int:
    import thunder_tpu.torch as ttorch

    return min(int(chunk) if chunk else ttorch.SSM_SCAN_CHUNK, int(T))  # as the decomposition reads its argument


def _ssm_scan_checker(x, dt, A, B, C, D=None, chunk=None) -> bool:
    from thunder_tpu.executors import flashex

    if not flashex._on_tpu() or len(getattr(x, "shape", ())) != 4 or len(getattr(B, "shape", ())) != 4:
        return False
    if any(dtypes.to_dtype(a.dtype) is not dtypes.bfloat16 for a in (x, B, C)):  # float32 keeps its precision
        return False
    (Bn, T, H, P), (G, N) = x.shape, B.shape[2:]
    L = _ssm_scan_chunk(pyval(chunk), T)
    if not (P == _SSM_SCAN_HEAD and N in (64, 128) and H % G == 0 and _ssm_scan_turn(int(H), int(G)) is not None
            and L % _LANE == 0 and T % L == 0 and Bn % batch_shards() == 0):
        return False
    return _ssm_scan_fits(L, int(H), int(N), int(G))


def _ssm_scan_fits(L: int, H: int, N: int, G: int, convolves: bool = False) -> bool:
    needed = _ssm_scan_vmem(L, H, N, G, 2, convolves)
    return needed <= 3 * _ssm_scan_scope(needed) // 4


def _ssm_conv_lanes(new, at, w_ref, bias_ref, halo_scr, ext):
    """One lane group of the chunk's ``silu(conv(x) + bias)`` in float32, to be
    rounded once: ``new`` its input rows (L, 128), ``at`` its first column of
    ``[x | B | C]`` (of the taps ``w_ref`` (K, W) and the bias (1, W) in
    float32, and of the last rows of the chunk before in ``halo_scr``). The rows
    go below the kept ones in ``ext`` (8 + L, 128), tap j is the load that
    starts ``K - 1 - j`` rows above the chunk's first, and the group's last rows
    are kept for the next chunk."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    K, L, halo = w_ref.shape[0], new.shape[0], _SSM_CONV_HALO
    cols = pl.ds(at, _LANE)
    ext[0:halo, :] = halo_scr[:, cols]
    ext[halo:, :] = new.astype(jnp.float32)
    taps = w_ref[:, cols]
    c = ext[pl.ds(halo - (K - 1), L), :] * taps[0:1]
    for j in range(1, K):
        c = c + ext[pl.ds(halo - (K - 1) + j, L), :] * taps[j:j + 1]
    if bias_ref is not None:
        c = c + bias_ref[:, cols]
    halo_scr[:, cols] = ext[L:, :]
    return c * (1.0 / (jnp.exp(-c) + 1.0))


def _ssm_scan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, *refs, N: int, G: int, u: int, conv: int = 0):
    """``conv``: the convolution's operands ahead of D, 0 where the step does not
    convolve, 1 (the taps) or 2 (the taps and the bias)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    conv_refs, refs = refs[:conv], refs[conv:]
    if conv:
        refs, (xs_scr, bc_scr, halo_scr, ext_scr) = refs[:-4], refs[-4:]
    *d_ref, y_ref, s_scr, cols_scr, rows_scr, cb_scr, bt_scr, c_scr = refs
    d_ref = d_ref[0] if d_ref else None  # D, (H,) in SMEM, where the call has one
    L, H = dt_ref.shape[1], dt_ref.shape[2]
    R, P, tile = H // G, _SSM_SCAN_HEAD, _LANE
    f32, lowp = jnp.float32, x_ref.dtype
    iota = lambda shape, axis: jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    identity = lambda n, dtype: (iota((n, n), 0) == iota((n, n), 1)).astype(dtype)
    nn, nt = (((1,), (0,)), ((), ())), (((1,), (1,)), ((), ()))  # a @ b, a @ b^T

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[...] = jnp.zeros(s_scr.shape, f32)
        if conv:
            halo_scr[...] = jnp.zeros(halo_scr.shape, f32)

    if not conv:  # x, B and C are the blocks
        keys_of, queries_of = (lambda g, ref=ref: ref[0, :, g * N:(g + 1) * N] for ref in (b_ref, c_ref))
    else:  # the blocks are the convolution's input: B and C convolved here, x a lane group before a pair of heads reads it
        w_ref, bias_ref = (*conv_refs, None)[:2]
        GN = G * N
        for k in range(2 * GN // tile):
            src, lanes = (b_ref, c_ref)[k * tile // GN], slice(k * tile % GN, k * tile % GN + tile)
            bc_scr[:, k * tile:(k + 1) * tile] = _ssm_conv_lanes(
                src[0, :, lanes], H * P + k * tile, w_ref, bias_ref, halo_scr, ext_scr.at[k % ext_scr.shape[0]]).astype(lowp)
        keys_of, queries_of = (lambda g, at=at: bc_scr[:, at + g * N:at + (g + 1) * N] for at in (0, GN))

    def summed(ones, terms, dims):
        """``ones`` (exact in bf16) against float32 ``terms``, exact to the float32 accumulation: the terms split
        into three bf16 parts that sum to them, a pass of the MXU each, where ``Precision.HIGHEST`` makes six."""
        total = 0.0
        for _ in range(3):
            part = terms.astype(jnp.bfloat16)
            total = total + jax.lax.dot_general(ones, part, dims, preferred_element_type=f32)
            terms = terms - part.astype(f32)
        return total

    # Every head's running sum of dt A down the chunk, (L, H): a sum of float32 terms under a triangle of ones. A
    # query's side of the mask wants it down the sublanes, a column a head; everything that belongs to a key wants its
    # positions along the lanes, a row a head (a row spreads over sublanes for nothing, a column over lanes through the
    # XLU, which is what a step has least of): so cum and dt are turned, (H, L), by a product with the identity.
    dt = dt_ref[0]
    ones_below = (iota((L, L), 0) >= iota((L, L), 1)).astype(jnp.bfloat16)
    cum = summed(ones_below, dt * a_ref[...], nn)
    cum_t, dt_t = (summed(identity(H, jnp.bfloat16), a, nt) for a in (cum, dt))
    to_end = dt_t * jnp.exp(cum_t[:, L - 1:L] - cum_t)                          # dt_j times the decay from j to the chunk's end
    for o in range(H // u):  # a turn's heads: their columns of cum side by side, their rows of cum, dt and to_end together
        cols_scr[o, :, 0:u] = cum[:, o * u:(o + 1) * u]
        for k, rows in enumerate((cum_t, dt_t, to_end)):
            rows_scr[o, k, 0:u, :] = rows[o * u:(o + 1) * u, :]
    for g in range(G):
        keys, queries = keys_of(g), queries_of(g)
        cb_scr[g] = jax.lax.dot_general(queries, keys, nt, preferred_element_type=f32)
        # B turned, (N, L), by a product with the identity: one nonzero a sum, exact, and no transpose to lay out
        bt_scr[g] = jax.lax.dot_general(identity(N, lowp), keys, nt, preferred_element_type=f32)
        c_scr[g] = queries

    left = iota((1, tile), 1) < P  # the first head's lanes of a lane group
    spread = lambda a, b: jnp.where(left, a, b)  # each of two heads' values over its own lanes
    on_or_below = iota((tile, tile), 0) >= iota((tile, tile), 1)
    tiles = [slice(t * tile, (t + 1) * tile) for t in range(L // tile)]

    def turn(o, carry):
        first = o * u
        g = jax.lax.div(first, jnp.int32(R))  # (`//` on a traced index goes through 64 bits where the runtime has x64 on)
        cum_j, dt_j, to_end_j = rows_scr[o, 0], rows_scr[o, 1], rows_scr[o, 2]  # (8, L) each, the first u rows
        for pair in range(0, u, 2):
            heads = (pair, pair + 1)
            lanes = pl.ds(pl.multiple_of((first + pair) * P, tile), tile)
            if not conv:
                x_of = lambda rows, lanes=lanes: x_ref[0, rows, lanes]
            else:  # the pair's lane group of x, convolved where the turn's matmuls leave the vector unit room
                mine = slice(pair * P, pair * P + tile)
                xs_scr[:, mine] = _ssm_conv_lanes(x_ref[0, :, lanes], lanes.start, w_ref, bias_ref, halo_scr,
                                                  ext_scr.at[pair // 2]).astype(lowp)
                x_of = lambda rows, mine=mine: xs_scr[rows, mine]
            state = s_scr[:, lanes]                                             # (N, 128)
            entering = state.astype(lowp)
            for r in range(L // tile):  # a tile of queries
                cum_i = [jnp.broadcast_to(cols_scr[o, tiles[r], h:h + 1], (tile, tile)) for h in heads]
                y = jnp.dot(c_scr[g, tiles[r], :], entering, preferred_element_type=f32) * jnp.exp(spread(*cum_i))
                within = []
                for h, down in zip(heads, cum_i):  # a head's masked form against both heads' x: its own lanes of the result are kept
                    for t in range(r + 1):  # the tiles of keys at and before the queries'
                        ahead = down - cum_j[h:h + 1, tiles[t]]
                        if t == r:
                            ahead = jnp.where(on_or_below, ahead, -jnp.inf)
                        m = (jnp.exp(ahead) * cb_scr[g, tiles[r], tiles[t]] * dt_j[h:h + 1, tiles[t]]).astype(lowp)
                        part = jnp.dot(m, x_of(tiles[t]), preferred_element_type=f32)
                        acc = part if t == 0 else acc + part
                    within.append(acc)
                y = y + spread(*within)
                if d_ref is not None:
                    y = y + x_of(tiles[r]).astype(f32) * spread(*(d_ref[first + h] for h in heads))
                y_ref[0, tiles[r], lanes] = y.astype(y_ref.dtype)
            # what the chunk adds to each head's state: B^T (to_end x), to_end along B^T's lanes
            adds = [jnp.dot((bt_scr[g] * to_end_j[h:h + 1, :]).astype(lowp), x_of(slice(None)), preferred_element_type=f32)
                    for h in heads]
            decay = spread(*(jnp.exp(cum_j[h:h + 1, L - 1:L]) for h in heads))  # a chunk's whole decay, (1, 128)
            s_scr[:, lanes] = state * decay + spread(*adds)
        return carry

    jax.lax.fori_loop(0, H // u, turn, 0)


@lru_cache(maxsize=32)
def _ssm_scan_call(L: int, u: int, scope: int, interpret: bool, packed: tuple | None = None,
                   conv: tuple | None = None):
    """The call for chunks of L positions and turns of u heads in a scope of
    VMEM, as one jitted function: a model's layers call it one after another
    with the same shapes, and jax traces the kernel's body and lowers it for
    Mosaic once for all of them (traced anew a layer, the 36 of
    granite-4.0-h-micro.fwd-t16k cost its set-up 5 s: PERF.md, PR 45).
    Everything the trace reads outside its operands is in the cache's key.
    ``packed`` (G, N): x, B and C are one array, ``[x | B | C]`` (B, T, H P + 2 G
    N), given three times; the same three blocks are read out of it by their
    index along its last dimension, and the kernel sees the refs it always saw.
    ``conv`` (operands, a): the array is the projection whose columns from a on
    are the convolution's input ``[x | B | C]``, the taps (K, H P + 2 G N) and
    the bias (1, H P + 2 G N) where ``operands`` is 2 follow A, and each step
    convolves its chunk before it scans it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def call(x, dt, B, C, A, *rest):
        Bn, T, H = dt.shape
        weights, D = (rest[:conv[0]], rest[conv[0]:]) if conv else ((), rest)
        if packed is None:
            P, (G, N), x_at, b_at, c_at = x.shape[3], B.shape[2:], 0, 0, 0
            x, B, C = x.reshape(Bn, T, H * P), B.reshape(Bn, T, G * N), C.reshape(Bn, T, G * N)
        else:  # x is a block of H P columns; B and C are the two blocks of G N columns behind it
            G, N = packed
            lo, W = (conv[1], weights[0].shape[1]) if conv else (0, x.shape[2])
            P = (W - 2 * G * N) // H
            x_at, b_at = lo // (H * P), (lo + H * P) // (G * N)
            c_at = b_at + 1
        a_chunk = lambda width, at=0: pl.BlockSpec((1, L, width), lambda b, c: (b, c, at), memory_space=pltpu.VMEM)
        whole = lambda a: pl.BlockSpec(a.shape, lambda b, c: (0, 0), memory_space=pltpu.VMEM)
        in_specs = [a_chunk(H * P, x_at), a_chunk(H), whole(A.reshape(1, H)), a_chunk(G * N, b_at),
                    a_chunk(G * N, c_at), *map(whole, weights), *([pl.BlockSpec(memory_space=pltpu.SMEM)] if D else [])]
        scratch = [pltpu.VMEM((N, H * P), jnp.float32), pltpu.VMEM((H // u, L, _LANE), jnp.float32),
                   pltpu.VMEM((H // u, 3, 8, L), jnp.float32), pltpu.VMEM((G, L, L), jnp.float32),
                   pltpu.VMEM((G, N, L), jnp.float32), pltpu.VMEM((G, L, N), x.dtype)]
        if conv:  # a turn's x and B and C convolved, the rows kept for the next chunk, a pair's rows below them
            W = H * P + 2 * G * N
            scratch += [pltpu.VMEM((L, u * P), x.dtype), pltpu.VMEM((L, 2 * G * N), x.dtype),
                        pltpu.VMEM((_SSM_CONV_HALO, W), jnp.float32),
                        pltpu.VMEM((u // 2, _SSM_CONV_HALO + L, _LANE), jnp.float32)]
        out = pl.pallas_call(
            partial(_ssm_scan_kernel, N=N, G=G, u=u, **({"conv": conv[0]} if conv else {})),
            grid=(Bn, T // L),
            in_specs=in_specs,
            out_specs=a_chunk(H * P),
            out_shape=jax.ShapeDtypeStruct((Bn, T, H * P), x.dtype),
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                **({} if scope == _SCOPED_VMEM_DEFAULT else {"vmem_limit_bytes": scope})),
            name="ssm_scan_fwd",
            interpret=interpret,
        )(x, dt, A.reshape(1, H), B, C, *weights, *D)
        return out.reshape(Bn, T, H, P)

    return jax.jit(call)


def _ssm_scan_impl(x, dt, A, B, C, D=None, chunk=None, packed=None, conv=()):
    """``conv``: (the taps (K, W), the bias (1, W) or None, the first column) in
    float32 where x, B and C are the convolution's input."""
    chaos.kernel_seam("pallas", "ssm_scan")
    import jax
    import jax.numpy as jnp

    weights = tuple(a for a in conv[:2] if a is not None)
    how = (len(weights), conv[2]) if conv else None

    def shard(x, dt, B, C, A, *rest):
        (_, T, H), (G, N) = dt.shape, packed or B.shape[2:]
        L = _ssm_scan_chunk(chunk, T)
        scope = _ssm_scan_scope(_ssm_scan_vmem(L, H, N, G, x.dtype.itemsize, bool(weights)))
        return _ssm_scan_call(L, _ssm_scan_turn(H, G), scope, _interpret(), packed, how)(x, dt, B, C, A, *rest)

    f32 = jnp.float32
    skip = () if D is None else (D.astype(f32),)
    with jax.enable_x64(False):
        return per_batch_shard(shard, x, dt.astype(f32), B, C, A.astype(f32), *weights, *skip,
                               replicated=tuple(range(4, 6 + len(weights))))


def _ssm_scan_packed_checker(xbc, dt, A, D=None, *, heads, groups, state, chunk=None) -> bool:
    """``_ssm_scan_checker`` on the x, B and C that ``[x | B | C]`` holds, and what
    reading them by block index needs besides: B's and C's G N columns whole lane
    groups, and x's H P a multiple of them. Any other packed call is its
    decomposition's, and so ``ssm_scan``'s own claim on the three slices."""
    if len(getattr(xbc, "shape", ())) != 3:
        return False
    (Bn, T, W), H, G, N = xbc.shape, int(pyval(heads)), int(pyval(groups)), int(pyval(state))
    inner = W - 2 * G * N
    if inner <= 0 or inner % H or (G * N) % _LANE or inner % (G * N):
        return False
    part = lambda *shape: SimpleNamespace(shape=(Bn, T, *shape), dtype=xbc.dtype)
    return _ssm_scan_checker(part(H, inner // H), dt, A, part(G, N), part(G, N), D, chunk)


def _ssm_scan_packed_impl(xbc, dt, A, D=None, *, heads, groups, state, chunk=None):
    return _ssm_scan_impl(xbc, dt, A, xbc, xbc, D, chunk, packed=(groups, state))


def _ssm_conv_scan_checker(lin, w, bias, dt, A, D=None, *, columns, heads, groups, state, chunk=None) -> bool:
    """``_ssm_scan_packed_checker`` on the convolution's result, and what
    convolving in the step needs besides: taps that look back at most 7 rows, x's
    columns at a multiple of H P and B's at a multiple of G N in the projection,
    and the step's VMEM with the convolution's scratches. Any other call is its
    decomposition's, and so the packed call's."""
    if len(getattr(lin, "shape", ())) != 3 or len(getattr(w, "shape", ())) != 2:
        return False
    (Bn, T, _), (lo, hi), (W, K) = lin.shape, (int(pyval(c)) for c in columns), w.shape
    H, G, N = int(pyval(heads)), int(pyval(groups)), int(pyval(state))
    inner = W - 2 * G * N
    if (hi - lo != W or not 1 <= K <= _SSM_CONV_HALO or inner <= 0 or lo % inner or (lo + inner) % (G * N)
            or (bias is not None and tuple(bias.shape) != (W,))):
        return False
    xbc = SimpleNamespace(shape=(Bn, T, W), dtype=lin.dtype)
    if not _ssm_scan_packed_checker(xbc, dt, A, D, heads=H, groups=G, state=N, chunk=chunk):
        return False
    return _ssm_scan_fits(_ssm_scan_chunk(pyval(chunk), T), H, N, G, convolves=True)


def _ssm_conv_scan_impl(lin, w, bias, dt, A, D=None, *, columns, heads, groups, state, chunk=None):
    import jax.numpy as jnp

    f32 = jnp.float32
    conv = (w.astype(f32).T, None if bias is None else bias.astype(f32).reshape(1, -1), int(columns[0]))
    return _ssm_scan_impl(lin, dt, A, lin, lin, D, chunk, packed=(groups, state), conv=conv)


ex.register_implementation("torch.ssm_scan", fn=_ssm_scan_impl, checker=_ssm_scan_checker)
ex.register_implementation("torch.ssm_scan_packed", fn=_ssm_scan_packed_impl, checker=_ssm_scan_packed_checker)
ex.register_implementation("torch.ssm_conv_scan", fn=_ssm_conv_scan_impl, checker=_ssm_conv_scan_checker)
