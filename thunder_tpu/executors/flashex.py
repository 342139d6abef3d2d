"""Flash-attention executor: Pallas TPU splash-attention kernels claiming SDPA whole.

Reference parity: the cuDNN/sdpa executor seats
(thunder/executors/cudnnex.py:44 — fused SDPA fwd/bwd via cuDNN's graph
API, including the attn-mask bias input at cudnnex.py:81-92; sdpaex.py:26 —
flash/mem-efficient backend selection, incl. the head-dim padding at
sdpaex.py:49). Here the fused kernels are JAX's production splash-attention
Pallas TPU kernels (block-sparse flash with native causal skipping), an
external kernel library in exactly the sense cuDNN is to the reference.

Claims:
- ``torch.scaled_dot_product_attention`` (forward) — online-softmax flash;
  no (B, H, S, S) score materialization.
- ``torch.sdpa_bwd`` (backward composite emitted by the autodiff rule) —
  splash backward kernels via the kernel's custom VJP.
- ``torch.window_attention`` (forward) — the same kernel under splash's local
  mask (``window - 1`` keys to the left, none to the right): a tile of
  ``_BLOCK`` queries visits the key tiles its window touches, three at a
  window of 2048, and no others; no (T, T) mask is built. Since PR 42 this
  claim is the second asked: ``pallas`` stands in front of ``flash`` and its
  ``window_attend_fwd`` (``pallasex._window_attend_checker``) takes a call of
  bf16 heads of a multiple of 128 whose sequence its tiles of 256 divide and
  whose window's span of k and v fits the default scope of VMEM (windows to
  some 7,000 keys at eight query heads a key-value head); every other call
  (heads of 64, float16, a sequence that needs padding, a longer window) comes
  here as before, and float32 goes to the decomposition. ``window_tiles``
  counts what the kernel that takes bf16 heads of 128 visits,
  ``splash_window_tiles`` what this one does.

Mask support (the reference's cudnnex builds its graph with a bias input;
splash is mask-structured instead, so masks are handled by shape class):
- ``attn_mask=None`` (+ optional ``is_causal``): claimed directly.
- Key-padding masks — bool/additive of shape (S,), (B, 1, 1, S),
  (1, 1, 1, S) (the torch-broadcast shapes that are constant over the
  query axis; a 2D (X, S) mask aligns X with the QUERY dim in torch, so it
  is NOT key-padding and takes the decomposition): lowered to splash
  segment-ids. Additive key-padding masks are runtime-verified (entries
  must be 0 or very negative), and any row with no valid key falls back —
  torch's safe-softmax zeros vs kernel-defined output; on mismatch a
  ``lax.cond`` falls back to the exact decomposed SDPA, so claiming is
  always value-correct.
- 4D float/bool masks (B, 1, Sq, Skv) — the shape HF builds for padded
  causal batches: the kv-validity row is extracted at runtime, the mask is
  rebuilt as causal∧padding (and full∧padding), and compared; the flash
  path executes only when the rebuild matches (other masks — e.g. ALiBi
  biases — take the decomposed branch of the same ``lax.cond``).
  Positions whose query is padding are undefined in the flash branch
  (finite garbage, exactly like the reference's flash kernels) — HF-style
  consumers never read them.
- Unequal q/kv lengths and lengths not divisible by 128 are handled by
  in-executor padding with segment-ids (reference bar: sdpaex.py:49 pads
  head dims to stay on the fast path).

Under a device mesh (``parallel.build_train_step(mesh=...)``) every kernel
call runs per batch shard inside ``jax.shard_map`` (executors/kernel_mesh.py):
the SPMD partitioner cannot split a Mosaic custom call.

Every block is ``_BLOCK`` (1024) and the backward is splash's fused kernel:
the one sweep on the chip found nothing at 512 (PERF.md section 7).
"""

from __future__ import annotations

import math
import os
from functools import lru_cache

import numpy as np

from thunder_tpu.core.proxies import TensorProxy, pyval
from thunder_tpu.executors.kernel_mesh import per_batch_shard
from thunder_tpu.extend import OperatorExecutor, add_default_executor, register_executor
from thunder_tpu.resilience import chaos

ex = OperatorExecutor("flash")
register_executor(ex)
add_default_executor(ex, front=True)

_PAD = 128  # sequence alignment quantum (Mosaic lane width)
_NEG_BIG = -1e9  # additive-mask entries at or below this count as "masked"
_BLOCK = 1024  # every splash block, forward and backward, fitted to the sequence


def _interpret() -> bool:
    import jax

    return jax.default_backend() == "cpu"


def _on_tpu() -> bool:
    import jax

    # THUNDER_FLASH_FORCE=1 lets tests exercise the splash path on the CPU
    # mesh via Pallas interpret mode.
    if os.environ.get("THUNDER_FLASH_FORCE") == "1":
        return True
    return jax.default_backend() != "cpu"


def _sdpa_bound(args, kwargs) -> dict:
    names = ("query", "key", "value", "attn_mask", "dropout_p", "is_causal", "scale", "enable_gqa")
    defaults = {"attn_mask": None, "dropout_p": 0.0, "is_causal": False, "scale": None, "enable_gqa": False}
    b = dict(zip(names, args))
    b.update(kwargs)
    for k, v in defaults.items():
        b.setdefault(k, v)
    return b


# =============================================================================
# Mask classification (shape-level; value checks happen at runtime)
# =============================================================================


def _is_bool(x) -> bool:
    from thunder_tpu.core import dtypes

    return dtypes.is_boolean_dtype(x.dtype)


def _mask_kind(m, q, k) -> str:
    """'none' | 'keypad' | 'keypad_verify' | 'verify4d' | 'no'."""
    if m is None:
        return "none"
    if not (isinstance(m, TensorProxy) or hasattr(m, "shape")):
        return "no"
    if getattr(m, "requires_grad", False):
        return "no"  # no mask cotangent from the fused kernel
    B, Tq = q.shape[0], q.shape[-2]
    Tkv = k.shape[-2]
    shp = tuple(m.shape)
    # torch-legal key-padding shapes: broadcastable to (B, H, Sq, Skv) while
    # constant over the query axis.
    keypad_shapes = {(Tkv,), (B, 1, 1, Tkv), (1, 1, 1, Tkv)}
    if shp in keypad_shapes:
        return "keypad" if _is_bool(m) else "keypad_verify"
    if len(shp) == 4 and shp[0] in (1, B) and shp[1] == 1 and shp[2] == Tq and shp[3] == Tkv:
        return "verify4d"
    return "no"


def _pad_amt(t: int) -> int:
    return (-t) % _PAD


def _dtype_ok(q, k, v) -> bool:
    """Half-precision only, like the reference's fused-SDPA executors
    (cudnnex.py:60 / sdpaex.py checkers reject fp32): the TPU kernel's
    internal MXU passes are bf16, so claiming f32 would silently lose the
    HIGHEST-precision semantics the decomposition provides."""
    from thunder_tpu.core import dtypes

    def half(t):
        dt = dtypes.to_dtype(t.dtype)
        return dt in (dtypes.bfloat16, dtypes.float16)

    return half(q) and half(k) and half(v)


def _shapes_ok(q, k, v) -> bool:
    """The value heads may have a width of their own (latent attention: 192-wide
    queries and keys, 128-wide values); splash takes it from v."""
    if not all(isinstance(t, TensorProxy) or hasattr(t, "shape") for t in (q, k, v)):
        return False
    if len(q.shape) != 4 or len(k.shape) != 4 or len(v.shape) != 4:
        return False
    S, L, D = q.shape[-2], k.shape[-2], q.shape[-1]
    if D > 256 or v.shape[-1] > 256 or k.shape[-1] != D or v.shape[-2] != L:
        return False
    # Below half a block of real work, padding waste dominates any kernel
    # win — keep the cheap decomposition.
    return S >= _PAD // 2 and L >= _PAD // 2


def _sdpa_checker(*args, **kwargs) -> bool:
    b = _sdpa_bound(args, kwargs)
    q, k = b["query"], b["key"]
    if not (_on_tpu() and float(pyval(b["dropout_p"])) == 0.0 and _shapes_ok(q, k, b["value"])
            and _dtype_ok(q, k, b["value"])):
        return False
    kind = _mask_kind(b["attn_mask"], q, k)
    if kind == "no":
        return False
    if kind != "none" and b["is_causal"]:
        return False  # torch: is_causal and attn_mask are mutually exclusive
    return True


def _bwd_checker(g, query, key, value, attn_mask=None, is_causal=False, scale=None, enable_gqa=False) -> bool:
    if not (_on_tpu() and _shapes_ok(query, key, value) and _dtype_ok(query, key, value)):
        return False
    return _mask_kind(attn_mask, query, key) != "no"


# =============================================================================
# splash kernel construction (cached per static configuration)
# =============================================================================


def _fit_block(t: int) -> int:
    b = min(_BLOCK, t)
    b -= b % _PAD
    b = max(b, _PAD)
    while t % b:
        b -= _PAD
    return max(b, _PAD)


@lru_cache(maxsize=64)
def _splash_kernel(H: int, Tq: int, Tkv: int, causal: bool, offset: int, interpret: bool,
                   downcast: bool, save_res: bool = False, window: int | None = None):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    bq, bkv = _fit_block(Tq), _fit_block(Tkv)
    block_sizes = sk.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkv,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkv,
        use_fused_bwd_kernel=True,
    )
    if window is not None:  # causal within the window: keys q - (window - 1) to q
        head_mask = sm.LocalMask((Tq, Tkv), window_size=(window - 1, 0), offset=offset)
    elif causal:
        head_mask = sm.CausalMask((Tq, Tkv), offset=offset)
    else:
        head_mask = sm.FullMask((Tq, Tkv))
    mask = sm.MultiHeadMask([head_mask for _ in range(H)])
    import jax

    # The kernel object (mask-info arrays) is cached across jit traces —
    # build it outside the ambient trace so no tracer leaks into the cache.
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mha(
            mask=mask, head_shards=1, q_seq_shards=1, block_sizes=block_sizes,
            interpret=interpret, downcast_smem_data=downcast, save_residuals=save_res,
        )


def _scaled(q, scale: float):
    """q times the softmax scale, which splash leaves to its caller. At 1.0 q
    came scaled (transforms/attention_layout.py folds the scale into the rope
    call that writes q) and a pass over it would multiply nothing."""
    import jax.numpy as jnp

    return q if scale == 1.0 else (q * jnp.asarray(scale, dtype=q.dtype)).astype(q.dtype)


def _splash_sdpa(q, k, v, *, causal: bool, scale: float, kv_valid=None, q_valid=None, window=None):
    """Run splash attention with in-executor sequence padding.

    q: (B, H, Tq, D); k: (B, H, Tkv, D); v: (B, H, Tkv, Dv), Dv its own
    (already GQA-expanded). The output is (B, H, Tq, Dv).
    kv_valid/q_valid: optional bool (B, T) — False positions never attend /
    are never attended to (lowered to splash segment-ids). Output positions
    with an invalid query are finite garbage and are expected to be ignored
    by the consumer (their cotangents are zero in the backward, so no
    garbage reaches dq/dk/dv at valid positions).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk

    B, H, Tq, D = q.shape
    Tkv = k.shape[-2]
    off = Tkv - Tq  # bottom-right causal alignment, matching the decomposition
    pq, pkv = _pad_amt(Tq), _pad_amt(Tkv)

    need_seg = kv_valid is not None or q_valid is not None or pq or pkv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pkv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pkv), (0, 0)))

    Tqp, Tkvp = Tq + pq, Tkv + pkv
    kernel = _splash_kernel(
        H, Tqp, Tkvp, causal, off, _interpret(),
        # bf16 data is already narrow; keep f32 inputs at full precision in
        # SMEM (the downcast costs ~1e-3 abs error on f32 workloads).
        q.dtype == jnp.bfloat16,
        window=window,
    )
    qs = _scaled(q, scale)

    # The kernel's index maths is 32-bit; scope out the runtime's x64 mode
    # while it traces.
    with jax.enable_x64(False):
        if need_seg:
            qv = jnp.ones((B, Tq), dtype=jnp.bool_) if q_valid is None else q_valid
            kvv = jnp.ones((B, Tkv), dtype=jnp.bool_) if kv_valid is None else kv_valid
            qv = jnp.pad(qv, ((0, 0), (0, pq))).astype(jnp.int32)
            kvv = jnp.pad(kvv, ((0, 0), (0, pkv))).astype(jnp.int32)
            batched = jax.vmap(kernel, in_axes=(0, 0, 0, sk.SegmentIds(q=0, kv=0)))
            out = per_batch_shard(
                lambda q, k, v, sq, skv: batched(q, k, v, sk.SegmentIds(q=sq, kv=skv)),
                qs, k, v, qv, kvv,
            )
        else:
            out = per_batch_shard(jax.vmap(kernel), qs, k, v)
    return out[..., :Tq, :] if pq else out


# =============================================================================
# Runtime dispatch: mask → flash path (+ verified cond fallback)
# =============================================================================


def _xla_sdpa(q, k, v, attn_mask, causal: bool, scale: float):
    """Exact decomposed SDPA (the lax.cond fallback branch)."""
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    Tq, Tkv = q.shape[-2], k.shape[-2]
    if causal:
        i = jnp.arange(Tq)[:, None]
        j = jnp.arange(Tkv)[None, :]
        s = jnp.where(i + (Tkv - Tq) >= j, s, -jnp.inf)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            s = jnp.where(attn_mask, s, -jnp.inf)
        else:
            s = s + attn_mask.astype(jnp.float32)
    # torch-sdpa safe-softmax: fully-masked rows yield zeros, not NaN
    dead = jnp.max(s, axis=-1, keepdims=True) == -jnp.inf
    p = jnp.where(dead, 0.0, jax.nn.softmax(s, axis=-1)).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _mask_kind_rt(m, q, k) -> str:
    """Runtime twin of _mask_kind (on concrete arrays)."""
    import jax.numpy as jnp

    class _Shim:
        def __init__(self, x):
            self.shape = x.shape
            self.requires_grad = False
            self.dtype = x.dtype

    if m is None:
        return "none"
    B, Tq, Tkv = q.shape[0], q.shape[-2], k.shape[-2]
    shp = tuple(m.shape)
    if shp in {(Tkv,), (B, 1, 1, Tkv), (1, 1, 1, Tkv)}:
        return "keypad" if m.dtype == jnp.bool_ else "keypad_verify"
    return "verify4d"


def _sdpa_runtime(q, k, v, attn_mask, causal: bool, scale: float):
    """Dispatch one SDPA call to splash, with runtime-verified fallbacks."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    B, H, Tq, D = q.shape
    Tkv = k.shape[-2]
    kind = _mask_kind_rt(attn_mask, q, k)

    if kind == "none":
        return _splash_sdpa(q, k, v, causal=causal, scale=scale)

    if kind in ("keypad", "keypad_verify"):
        m = jnp.reshape(attn_mask, (-1, Tkv))
        m = jnp.broadcast_to(m, (B, Tkv))
        if kind == "keypad":
            kv_valid = m
            ok = jnp.ones((), dtype=jnp.bool_)
        else:
            # additive key-padding: entries must be 0 (keep) or <= _NEG_BIG (drop)
            kv_valid = m == 0
            ok = jnp.all(kv_valid | (m <= _NEG_BIG))
        # A row with NO valid key must take the exact branch: torch's
        # safe-softmax yields zeros there, while splash's output for a query
        # with no matching segment is kernel-defined (ADVICE r4). Softmax
        # shift-invariance also means an all-(-1e9) additive row attends
        # normally in the exact path but masks everything in segment-ids.
        ok = ok & jnp.all(jnp.any(kv_valid, axis=-1))
        return lax.cond(
            ok,
            lambda q, k, v: _splash_sdpa(q, k, v, causal=causal, scale=scale, kv_valid=kv_valid),
            lambda q, k, v: _xla_sdpa(q, k, v, attn_mask, causal, scale),
            q, k, v,
        )

    # verify4d: (1|B, 1, Tq, Tkv) — HF's padded causal (or full) mask.
    m4 = jnp.broadcast_to(attn_mask, (B, 1, Tq, Tkv))[:, 0]  # (B, Tq, Tkv)
    if m4.dtype == jnp.bool_:
        visible = m4
    else:
        visible = m4 == 0
        # additive entries must be 0/very-negative for the rebuild to be valid
        additive_ok = jnp.all(visible | (m4 <= _NEG_BIG))
    kv_valid = visible[:, -1, :]  # last query row sees every valid key (causal)
    # q validity: self-attention ⇒ q tokens are the last Tq of the kv axis
    q_valid = kv_valid[:, Tkv - Tq:]
    i = jnp.arange(Tq)[:, None]
    j = jnp.arange(Tkv)[None, :]
    causal_tri = i + (Tkv - Tq) >= j  # (Tq, Tkv)
    rebuild_causal = causal_tri[None] & kv_valid[:, None, :]
    rebuild_full = jnp.broadcast_to(kv_valid[:, None, :], visible.shape)
    rows_ok = q_valid[:, :, None]  # only rows with a valid query must match
    ok_causal = jnp.all((rebuild_causal == visible) | ~rows_ok)
    ok_full = jnp.all((rebuild_full == visible) | ~rows_ok)
    if m4.dtype != jnp.bool_:
        ok_causal = ok_causal & additive_ok
        ok_full = ok_full & additive_ok

    def flash_causal(q, k, v):
        return _splash_sdpa(q, k, v, causal=True, scale=scale, kv_valid=kv_valid, q_valid=q_valid)

    def flash_full(q, k, v):
        return _splash_sdpa(q, k, v, causal=False, scale=scale, kv_valid=kv_valid, q_valid=q_valid)

    def fallback(q, k, v):
        return lax.cond(
            ok_full, flash_full,
            lambda q, k, v: _xla_sdpa(q, k, v, attn_mask, causal, scale),
            q, k, v,
        )

    return lax.cond(ok_causal, flash_causal, fallback, q, k, v)


# =============================================================================
# Claimed implementations
# =============================================================================


def _expand_gqa(k, v, H):
    import jax.numpy as jnp

    G = k.shape[-3]
    if G == H:
        return k, v
    rep = H // G
    return jnp.repeat(k, rep, axis=-3), jnp.repeat(v, rep, axis=-3)


def _sdpa_impl(*args, **kwargs):
    chaos.kernel_seam("flash", "sdpa")
    b = _sdpa_bound(args, kwargs)
    q, k, v = b["query"], b["key"], b["value"]
    H, D = q.shape[-3], q.shape[-1]
    scale = float(b["scale"]) if b["scale"] is not None else 1.0 / math.sqrt(D)
    k, v = _expand_gqa(k, v, H)
    return _sdpa_runtime(q, k, v, b["attn_mask"], bool(b["is_causal"]), scale)


def _sdpa_bwd_impl(g, query, key, value, attn_mask=None, is_causal=False, scale=None, enable_gqa=False):
    chaos.kernel_seam("flash", "sdpa_bwd")
    import jax

    H, D = query.shape[-3], query.shape[-1]
    G = key.shape[-3]
    sm_scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
    k, v = _expand_gqa(key, value, H)

    f = lambda q, k, v: _sdpa_runtime(q, k, v, attn_mask, bool(is_causal), sm_scale)
    with jax.enable_x64(False):
        _, vjp = jax.vjp(f, query, k, v)
        dq, dk, dv = vjp(g)

    if G != H:
        rep = H // G
        bshape = dk.shape[:-3]
        dk = dk.reshape(bshape + (G, rep) + dk.shape[-2:]).sum(axis=len(bshape) + 1)
        dv = dv.reshape(bshape + (G, rep) + dv.shape[-2:]).sum(axis=len(bshape) + 1)
    return dq.astype(query.dtype), dk.astype(key.dtype), dv.astype(value.dtype)


def window_tiles(T: int, window: int) -> int:
    """Score elements of the tiles that the kernel which claims
    ``torch.window_attention`` on bf16 heads of 128 computes for one head of
    ``T`` positions: ``pallasex``'s where its checker takes such a sequence
    (its tiles divide it, the window's span fits its VMEM; reckoned at one
    query head a key-value head), splash's otherwise. The pairs the window
    itself has are fewer: whole tiles are what either can skip."""
    from thunder_tpu.executors import pallasex

    if pallasex.window_attend_fits(int(T), int(window), 1, 128, 2):
        return pallasex.window_attend_tiles(int(T), int(window))
    return splash_window_tiles(T, window)


def splash_window_tiles(T: int, window: int) -> int:
    """That count for splash: the pairs of a query tile and a key tile that its
    table of the local mask keeps (partly or wholly inside the window), times a
    tile's size."""
    Tp = T + _pad_amt(T)
    info = _splash_kernel(1, Tp, Tp, True, 0, _interpret(), True, window=int(window)).fwd_mask_info
    return int((np.asarray(info.block_mask) > 0).sum()) * _fit_block(Tp) ** 2


def _window_checker(q, k, v, *, window, scale=None) -> bool:
    return _on_tpu() and _shapes_ok(q, k, v) and _dtype_ok(q, k, v) and q.shape[-2] == k.shape[-2]


def _window_impl(q, k, v, *, window, scale=None):
    chaos.kernel_seam("flash", "window_attention")
    H, D = q.shape[-3], q.shape[-1]
    k, v = _expand_gqa(k, v, H)
    return _splash_sdpa(q, k, v, causal=True, scale=float(scale) if scale is not None else 1.0 / math.sqrt(D),
                        window=int(window))


# =============================================================================
# Residual-saving pair (transforms/attention_residuals.py; reference:
# cudnnex.py:375 — bwd graph consumes the fwd's saved softmax stats)
# =============================================================================


def residual_eligible(q, k, v) -> bool:
    """The attention-residual pass asks before rewriting: both sides must be
    claimable without padding or masks (the no-recompute path keeps the
    simplest geometry; everything else stays on the recompute composite)."""
    if not (_on_tpu() and _dtype_ok(q, k, v)):
        return False
    if len(q.shape) != 4 or len(k.shape) != 4:
        return False
    S, L, D = q.shape[-2], k.shape[-2], q.shape[-1]
    return S == L and S % _PAD == 0 and D <= 256 and v.shape[-1] <= 256


def _fwd_res_checker(query, key, value, attn_mask=None, is_causal=False, scale=None, enable_gqa=False):
    return attn_mask is None and residual_eligible(query, key, value)


def _bwd_res_checker(g, query, key, value, out, lse, attn_mask=None, is_causal=False,
                     scale=None, enable_gqa=False):
    return attn_mask is None and residual_eligible(query, key, value)


def _splash_fwd_res(q, k, v, *, causal: bool, scale: float):
    import jax
    import jax.numpy as jnp

    B, H, Tq, D = q.shape
    Tkv = k.shape[-2]
    kernel = _splash_kernel(
        H, Tq, Tkv, causal, Tkv - Tq, _interpret(),
        q.dtype == jnp.bfloat16,
        True,
    )
    qs = _scaled(q, scale)
    with jax.enable_x64(False):
        out, (lse,) = per_batch_shard(jax.vmap(kernel), qs, k, v)
    return out, lse[..., :Tq].astype(jnp.float32)


def _sdpa_fwd_res_impl(query, key, value, attn_mask=None, is_causal=False, scale=None, enable_gqa=False):
    H, D = query.shape[-3], query.shape[-1]
    sm_scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
    k, v = _expand_gqa(key, value, H)
    return _splash_fwd_res(query, k, v, causal=bool(is_causal), scale=sm_scale)


def _sdpa_bwd_res_impl(g, query, key, value, out, lse, attn_mask=None, is_causal=False,
                       scale=None, enable_gqa=False):
    """Direct splash backward from saved (out, lse) — no forward recompute
    (the jax.vjp route re-runs the forward kernel to rebuild these exact
    residuals; r4 profile: 24.5 ms/iter on the 3B bench)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk

    B, H, Tq, D = query.shape
    G = key.shape[-3]
    Tkv = key.shape[-2]
    sm_scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
    k, v = _expand_gqa(key, value, H)

    kernel = _splash_kernel(
        H, Tq, Tkv, bool(is_causal), Tkv - Tq, _interpret(),
        query.dtype == jnp.bfloat16,
        False,
    )
    kw = dict(kernel.kwargs)
    qs = (query * jnp.asarray(sm_scale, dtype=query.dtype)).astype(query.dtype)

    def one(qb, kb, vb, ob, lseb, gb):
        res = (qb, kb, vb, None, None, ob, lseb, kernel.dq_mask_info, kernel.dkv_mask_info)
        grads = sk._splash_attention_bwd(
            False,
            kw.get("mask_value", -0.7 * float(np.finfo(np.dtype("float32")).max)),
            kw.get("is_mqa", False),
            kw.get("block_sizes"),
            kw.get("residual_checkpoint_name"),
            kw.get("mask_function"),
            kw.get("attn_logits_soft_cap"),
            kw.get("interpret", False),
            res,
            gb,
        )
        return grads[3], grads[4], grads[5]

    with jax.enable_x64(False):
        dqs, dk, dv = per_batch_shard(jax.vmap(one), qs, k, v, out, lse.astype(jnp.float32), g)
    dq = dqs.astype(jnp.float32) * sm_scale  # fwd consumed q*scale

    if G != H:
        rep = H // G
        bshape = dk.shape[:-3]
        dk = dk.reshape(bshape + (G, rep) + dk.shape[-2:]).sum(axis=len(bshape) + 1)
        dv = dv.reshape(bshape + (G, rep) + dv.shape[-2:]).sum(axis=len(bshape) + 1)
    return dq.astype(query.dtype), dk.astype(key.dtype), dv.astype(value.dtype)


ex.register_implementation("torch.scaled_dot_product_attention", fn=_sdpa_impl, checker=_sdpa_checker)
ex.register_implementation("torch.sdpa_bwd", fn=_sdpa_bwd_impl, checker=_bwd_checker)
ex.register_implementation("torch.window_attention", fn=_window_impl, checker=_window_checker)
ex.register_implementation("torch.sdpa_fwd_res", fn=_sdpa_fwd_res_impl, checker=_fwd_res_checker)
ex.register_implementation("torch.sdpa_bwd_res", fn=_sdpa_bwd_res_impl, checker=_bwd_res_checker)
