"""Microbenchmark: legacy flash vs splash attention on the bench shape.

B=2 H=32 T=2048 D=100 (open_llama_3b), causal, bf16.

Timing method: iterations are dependency-chained (the output feeds the next
input) so the device must serialize them, and we take the slope between a
short and a long run to cancel the fixed host round-trip of the final sync.
"""
from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

B, H, T, D = 2, 32, 2048, 100
SCALE = 1.0 / (100 ** 0.5)


def chain_time(step, state, n_short=5, n_long=45):
    """step: state -> state (jitted). Returns per-iter seconds via slope."""
    s = step(state)
    jax.block_until_ready(s)

    def run(n):
        s = state
        t0 = time.perf_counter()
        for _ in range(n):
            s = step(s)
        jax.block_until_ready(s)
        return time.perf_counter() - t0

    run(2)
    t_s = run(n_short)
    t_l = run(n_long)
    return (t_l - t_s) / (n_long - n_short)


def flops_fwd():
    return 2 * 2 * B * H * T * T * D / 2


def legacy_flash(q, k, v, block=512):
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes, flash_attention

    sizes = BlockSizes(
        block_q=block, block_k_major=block, block_k=block, block_b=1,
        block_q_major_dkv=block, block_k_major_dkv=block, block_k_dkv=block, block_q_dkv=block,
        block_k_major_dq=block, block_k_dq=block, block_q_dq=block,
    )
    return flash_attention(q, k, v, causal=True, sm_scale=SCALE, block_sizes=sizes)


def make_splash(bq=512, bkv=512, bkv_compute=512, use_fused_bwd=True, bq_dkv=512, bkv_dkv=512):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    mask = sm.MultiHeadMask([sm.CausalMask((T, T)) for _ in range(H)])
    block_sizes = sk.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkv_compute,
        block_q_dkv=bq_dkv, block_kv_dkv=bkv_dkv, block_kv_dkv_compute=bkv_dkv,
        block_q_dq=None if use_fused_bwd else bq_dkv,
        block_kv_dq=None if use_fused_bwd else bkv_dkv,
        use_fused_bwd_kernel=use_fused_bwd,
    )
    kernel = sk.make_splash_mha(mask=mask, head_shards=1, q_seq_shards=1, block_sizes=block_sizes)

    def attn(q, k, v):
        return jax.vmap(kernel)(q * SCALE, k, v)

    return attn


def xla_attn(q, k, v):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * SCALE
    mask = jnp.tril(jnp.ones((T, T), dtype=bool))
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def run(name, attn_fn, q, k, v, check_against=None):
    @jax.jit
    def fwd_step(state):
        qq, out = state
        out = attn_fn(qq, k, v)
        # chain: next q depends on out but equals original q numerically-ish
        return qq + 0.0 * out, out

    def loss(qq, kk, vv):
        return jnp.sum(attn_fn(qq, kk, vv).astype(jnp.float32))

    gradf = jax.grad(loss, argnums=(0, 1, 2))

    @jax.jit
    def bwd_step(state):
        qq, _ = state
        dq, dk, dv = gradf(qq, k, v)
        return qq + 0.0 * dq, dq

    state = (q, jnp.zeros_like(q))
    err = ""
    if check_against is not None:
        mine = np.asarray(attn_fn(q, k, v), dtype=np.float32)
        ref = np.asarray(check_against(q, k, v), dtype=np.float32)
        err = f" maxerr={np.abs(mine-ref).max():.3e}"
    try:
        t_fwd = chain_time(fwd_step, state)
    except Exception as e:
        print(f"{name:36s} FWD FAILED: {str(e)[:120]}")
        return
    try:
        t_bwd = chain_time(bwd_step, state)
    except Exception as e:
        print(f"{name:36s} fwd {t_fwd*1e3:7.2f}ms ({flops_fwd()/t_fwd/1e12:5.1f} TF/s)  BWD FAILED: {str(e)[:80]}")
        return
    print(f"{name:36s} fwd {t_fwd*1e3:7.2f}ms ({flops_fwd()/t_fwd/1e12:5.1f} TF/s)   fwd+bwd {t_bwd*1e3:7.2f}ms ({3.5*flops_fwd()/t_bwd/1e12:5.1f} TF/s){err}")


def main():
    global D
    if len(sys.argv) > 1:
        D = int(sys.argv[1])
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, T, D), dtype=jnp.bfloat16)
    k = jax.random.normal(kk, (B, H, T, D), dtype=jnp.bfloat16)
    v = jax.random.normal(kv, (B, H, T, D), dtype=jnp.bfloat16)
    print(f"shape B={B} H={H} T={T} D={D}; ideal causal fwd @197TF/s = {flops_fwd()/197e12*1e3:.2f}ms")

    run("legacy flash b512", legacy_flash, q, k, v, check_against=xla_attn)
    run("splash fused-bwd 512", make_splash(), q, k, v, check_against=xla_attn)
    run("splash fused-bwd bkv1024", make_splash(bq=512, bkv=1024, bkv_compute=512, bq_dkv=512, bkv_dkv=1024), q, k, v)
    run("splash fused-bwd 1024", make_splash(bq=1024, bkv=1024, bkv_compute=1024, bq_dkv=1024, bkv_dkv=1024), q, k, v)
    run("splash fused-bwd 2048", make_splash(bq=2048, bkv=2048, bkv_compute=2048, bq_dkv=2048, bkv_dkv=2048), q, k, v)
    run("splash split-bwd 512", make_splash(use_fused_bwd=False), q, k, v)
    run("xla materialized", xla_attn, q, k, v)


if __name__ == "__main__":
    main()
