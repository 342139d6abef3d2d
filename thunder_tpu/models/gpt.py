"""The GPT family: a functional, trace-friendly transformer.

Reference parity: the litgpt ``GPT`` exercised throughout the reference's
tests and benchmarks (thunder/tests/lit_gpt_model.py,
thunder/benchmarks/benchmark_litgpt.py:41) — GPT-NeoX (pythia) and
Llama/Mistral architectural variants: parallel vs sequential residual,
LayerNorm vs RMSNorm, GptNeoxMLP vs SwiGLU, partial-rotary RoPE, and
grouped-query attention.

TPU-first design: the model is a *pure function* ``forward(params, idx)``
over a params pytree — no module object, no buffers, no in-place state. That
makes it directly traceable by the functional frontend, jittable whole,
shardable by annotating the params pytree with PartitionSpecs, and
differentiable by the trace VJP. Weights live in bf16 (MXU-native); norms
and softmax compute in f32 (handled inside ltorch ops).

Layout notes:
- qkv is one fused projection (q heads, then k, then v) — a single large
  MXU matmul instead of three.
- RoPE uses the rotate-half convention (HF NeoX/Llama compatible) with
  ``rotary_percentage`` of head_size rotated; cos/sin are built from iota
  inside the trace, so XLA constant-folds them into the compiled step.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Optional

import numpy as np

import thunder_tpu.torch as ttorch
from thunder_tpu.core import dtypes


@dataclass(frozen=True)
class GPTConfig:
    name: str = "gpt"
    block_size: int = 2048
    vocab_size: int = 50254
    padded_vocab_size: int = 50304
    n_layer: int = 12
    n_head: int = 12
    n_embd: int = 768
    n_query_groups: Optional[int] = None  # None → MHA (== n_head)
    rotary_percentage: float = 0.25
    parallel_residual: bool = True
    shared_attention_norm: bool = False
    bias: bool = True
    norm_class: str = "LayerNorm"  # or "RMSNorm"
    norm_eps: float = 1e-5
    mlp_class: str = "GptNeoxMLP"  # or "LLaMAMLP" / "MoEMLP"
    intermediate_size: Optional[int] = None
    rope_base: int = 10000
    # MoE (mlp_class="MoEMLP", mixtral-style SwiGLU experts):
    n_expert: int = 0
    n_expert_per_token: int = 2

    @property
    def head_size(self) -> int:
        return self.n_embd // self.n_head

    @property
    def query_groups(self) -> int:
        return self.n_query_groups if self.n_query_groups is not None else self.n_head

    @property
    def rope_n_elem(self) -> int:
        return int(self.rotary_percentage * self.head_size)

    @property
    def mlp_hidden(self) -> int:
        return self.intermediate_size if self.intermediate_size is not None else 4 * self.n_embd

    @property
    def qkv_out(self) -> int:
        return (self.n_head + 2 * self.query_groups) * self.head_size


configs: dict[str, GPTConfig] = {}


def _add(cfg: GPTConfig) -> GPTConfig:
    configs[cfg.name] = cfg
    return cfg


# Tiny configs for tests/dryruns.
_add(GPTConfig(name="gpt-tiny", block_size=64, vocab_size=96, padded_vocab_size=96, n_layer=2,
               n_head=2, n_embd=32, rotary_percentage=1.0, intermediate_size=64))
_add(GPTConfig(name="llama-tiny", block_size=64, vocab_size=96, padded_vocab_size=96, n_layer=2,
               n_head=4, n_embd=32, n_query_groups=2, rotary_percentage=1.0,
               parallel_residual=False, bias=False, norm_class="RMSNorm", mlp_class="LLaMAMLP",
               intermediate_size=88))

# Pythia (GPT-NeoX) family — reference benchmark ladder step 2.
_add(GPTConfig(name="pythia-160m", block_size=2048, vocab_size=50254, padded_vocab_size=50304,
               n_layer=12, n_head=12, n_embd=768, rotary_percentage=0.25, parallel_residual=True,
               bias=True, norm_class="LayerNorm", mlp_class="GptNeoxMLP", intermediate_size=3072))
_add(GPTConfig(name="pythia-410m", block_size=2048, vocab_size=50254, padded_vocab_size=50304,
               n_layer=24, n_head=16, n_embd=1024, rotary_percentage=0.25, parallel_residual=True,
               bias=True, norm_class="LayerNorm", mlp_class="GptNeoxMLP", intermediate_size=4096))
_add(GPTConfig(name="pythia-1b", block_size=2048, vocab_size=50254, padded_vocab_size=50304,
               n_layer=16, n_head=8, n_embd=2048, rotary_percentage=0.25, parallel_residual=True,
               bias=True, norm_class="LayerNorm", mlp_class="GptNeoxMLP", intermediate_size=8192))

# Llama-2 family — reference benchmark ladder steps 3-4 / north star.
_add(GPTConfig(name="llama-2-7b", block_size=4096, vocab_size=32000, padded_vocab_size=32000,
               n_layer=32, n_head=32, n_embd=4096, rotary_percentage=1.0, parallel_residual=False,
               bias=False, norm_class="RMSNorm", norm_eps=1e-5, mlp_class="LLaMAMLP",
               intermediate_size=11008))
_add(GPTConfig(name="llama-2-13b", block_size=4096, vocab_size=32000, padded_vocab_size=32000,
               n_layer=40, n_head=40, n_embd=5120, rotary_percentage=1.0, parallel_residual=False,
               bias=False, norm_class="RMSNorm", norm_eps=1e-5, mlp_class="LLaMAMLP",
               intermediate_size=13824))
_add(GPTConfig(name="open_llama_3b", block_size=2048, vocab_size=32000, padded_vocab_size=32000,
               n_layer=26, n_head=32, n_embd=3200, rotary_percentage=1.0, parallel_residual=False,
               bias=False, norm_class="RMSNorm", norm_eps=1e-6, mlp_class="LLaMAMLP",
               intermediate_size=8640))

# Mixtral-style MoE family (beyond-reference: SURVEY §2.3 has no EP/MoE).
_add(GPTConfig(name="mixtral-tiny", block_size=64, vocab_size=96, padded_vocab_size=96,
               n_layer=2, n_head=4, n_embd=32, n_query_groups=2, rotary_percentage=1.0,
               parallel_residual=False, bias=False, norm_class="RMSNorm",
               mlp_class="MoEMLP", intermediate_size=64, n_expert=4, n_expert_per_token=2))
_add(GPTConfig(name="mixtral-8x7b", block_size=4096, vocab_size=32000, padded_vocab_size=32000,
               n_layer=32, n_head=32, n_embd=4096, n_query_groups=8, rotary_percentage=1.0,
               parallel_residual=False, bias=False, norm_class="RMSNorm", norm_eps=1e-5,
               mlp_class="MoEMLP", intermediate_size=14336, n_expert=8, n_expert_per_token=2))

# Mistral — reference benchmark ladder step 5 (GQA).
_add(GPTConfig(name="mistral-7b", block_size=4096, vocab_size=32000, padded_vocab_size=32000,
               n_layer=32, n_head=32, n_embd=4096, n_query_groups=8, rotary_percentage=1.0,
               parallel_residual=False, bias=False, norm_class="RMSNorm", norm_eps=1e-5,
               mlp_class="LLaMAMLP", intermediate_size=14336))

# Falcon family — MQA (one KV head) + shared-attention-norm parallel residual
# (the litgpt registry's falcon geometry; reference tests run falcon-7b-like
# configs through thunder).
_add(GPTConfig(name="falcon-7b", block_size=2048, vocab_size=65024, padded_vocab_size=65024,
               n_layer=32, n_head=71, n_embd=4544, n_query_groups=1, rotary_percentage=1.0,
               parallel_residual=True, shared_attention_norm=True, bias=False,
               norm_class="LayerNorm", mlp_class="GptNeoxMLP", intermediate_size=18176))
_add(GPTConfig(name="falcon-tiny", block_size=64, vocab_size=96, padded_vocab_size=96,
               n_layer=2, n_head=4, n_embd=32, n_query_groups=1, rotary_percentage=1.0,
               parallel_residual=True, shared_attention_norm=True, bias=False,
               norm_class="LayerNorm", mlp_class="GptNeoxMLP", intermediate_size=128))

# Phi-2 — partial-rotary parallel-residual with biases.
_add(GPTConfig(name="phi-2", block_size=2048, vocab_size=50257, padded_vocab_size=51200,
               n_layer=32, n_head=32, n_embd=2560, rotary_percentage=0.4,
               parallel_residual=True, shared_attention_norm=True, bias=True,
               norm_class="LayerNorm", mlp_class="GptNeoxMLP", intermediate_size=10240))


def name_to_config(name: str) -> GPTConfig:
    return configs[name]


# =============================================================================
# Parameter initialization
# =============================================================================


def init_params(config: GPTConfig, *, dtype=dtypes.bfloat16, seed: int = 0, device_init: bool = False) -> dict:
    """Nested-dict params pytree.

    ``device_init=False`` (default): reproducible numpy init, suitable for
    tests and parity checks. ``device_init=True``: weights are generated
    directly on the accelerator with jax.random — required for multi-GB
    models where a host-side f32 copy would not fit (and is ~100× faster).
    """
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    jdt = dtypes.to_jax_dtype(dtypes.to_dtype(dtype))

    if device_init:
        key_holder = {"k": jax.random.PRNGKey(seed)}

        def w(*shape, std=0.02):
            key_holder["k"], sub = jax.random.split(key_holder["k"])
            # float(): a numpy float64 std is not weakly typed, and with x64 on
            # would promote the whole product to float64, which a TPU emulates.
            return (jax.random.normal(sub, shape, dtype=jnp.float32) * float(std)).astype(jdt)

    else:

        def w(*shape, std=0.02):
            return jnp.asarray(rng.normal(0.0, std, size=shape).astype(np.float32), dtype=jdt)

    def zeros(*shape):
        return jnp.zeros(shape, dtype=jdt)

    def ones(*shape):
        return jnp.ones(shape, dtype=jdt)

    C = config
    def norm_params():
        p = {"weight": ones(C.n_embd)}
        if C.norm_class == "LayerNorm":
            p["bias"] = zeros(C.n_embd)
        return p

    def block_params(i):
        p: dict[str, Any] = {
            "norm_1": norm_params(),
            "attn": {
                "qkv_w": w(C.qkv_out, C.n_embd),
                "proj_w": w(C.n_embd, C.n_head * C.head_size, std=0.02 / np.sqrt(2 * C.n_layer)),
            },
            "mlp": {},
        }
        if not C.shared_attention_norm:
            p["norm_2"] = norm_params()
        if C.bias:
            p["attn"]["qkv_b"] = zeros(C.qkv_out)
            p["attn"]["proj_b"] = zeros(C.n_embd)
        if C.mlp_class == "MoEMLP":
            E, H = C.n_expert, C.mlp_hidden
            p["mlp"]["router_w"] = w(E, C.n_embd)
            p["mlp"]["w1"] = w(E, H, C.n_embd)
            p["mlp"]["w3"] = w(E, H, C.n_embd)
            p["mlp"]["w2"] = w(E, C.n_embd, H, std=0.02 / np.sqrt(2 * C.n_layer))
        elif C.mlp_class == "LLaMAMLP":
            p["mlp"]["fc_1_w"] = w(C.mlp_hidden, C.n_embd)
            p["mlp"]["fc_2_w"] = w(C.mlp_hidden, C.n_embd)
            p["mlp"]["proj_w"] = w(C.n_embd, C.mlp_hidden, std=0.02 / np.sqrt(2 * C.n_layer))
            if C.bias:
                p["mlp"]["fc_1_b"] = zeros(C.mlp_hidden)
                p["mlp"]["fc_2_b"] = zeros(C.mlp_hidden)
                p["mlp"]["proj_b"] = zeros(C.n_embd)
        else:
            p["mlp"]["fc_w"] = w(C.mlp_hidden, C.n_embd)
            p["mlp"]["proj_w"] = w(C.n_embd, C.mlp_hidden, std=0.02 / np.sqrt(2 * C.n_layer))
            if C.bias:
                p["mlp"]["fc_b"] = zeros(C.mlp_hidden)
                p["mlp"]["proj_b"] = zeros(C.n_embd)
        return p

    return {
        "wte": w(C.padded_vocab_size, C.n_embd),
        "blocks": [block_params(i) for i in range(C.n_layer)],
        "ln_f": norm_params(),
        "lm_head_w": w(C.padded_vocab_size, C.n_embd),
    }


# =============================================================================
# Forward
# =============================================================================


def _norm(x, p, config: GPTConfig):
    if config.norm_class == "RMSNorm":
        return ttorch.rms_norm(x, (config.n_embd,), p["weight"], eps=config.norm_eps)
    return ttorch.layer_norm(x, (config.n_embd,), p["weight"], p.get("bias"), eps=config.norm_eps)


def _rope_cache(T: int, config: GPTConfig, device, dtype):
    """cos/sin of shape (T, rope_n_elem) — built from iota, so XLA folds them
    into constants of the compiled executable."""
    n = config.rope_n_elem
    half = n // 2
    import thunder_tpu.clang as clang

    theta = clang.pow(float(config.rope_base), clang.true_divide(
        clang.mul(clang.arange(0, half, 1, device=device, dtype=dtypes.float32), -2.0), float(n)))
    pos = clang.arange(0, T, 1, device=device, dtype=dtypes.float32)
    freqs = clang.mul(clang.unsqueeze(pos, 1), clang.unsqueeze(theta, 0))  # (T, half)
    emb = clang.cat([freqs, freqs], dim=1)  # (T, n) rotate-half convention
    return clang.maybe_convert_to_dtype(clang.cos(emb), dtype), clang.maybe_convert_to_dtype(clang.sin(emb), dtype)


def _apply_rope(x, cos, sin, config: GPTConfig):
    """x: (B, H, T, hs); rotate the first rope_n_elem features. Composite op
    so the Pallas rope kernel claims it (pallasex; the decomposed
    rotate-half is lane-misaligned at hs=100)."""
    return ttorch.apply_rope(x, cos, sin)


def _attention(x, p, cos, sin, config: GPTConfig):
    B, T, C = x.shape
    H, G, hs = config.n_head, config.query_groups, config.head_size

    qkv = ttorch.linear(x, p["qkv_w"], p.get("qkv_b"))  # (B, T, (H+2G)*hs)
    q = qkv[..., : H * hs]
    k = qkv[..., H * hs : (H + G) * hs]
    v = qkv[..., (H + G) * hs :]

    q = ttorch.permute(ttorch.reshape(q, (B, T, H, hs)), (0, 2, 1, 3))  # (B,H,T,hs)
    k = ttorch.permute(ttorch.reshape(k, (B, T, G, hs)), (0, 2, 1, 3))
    v = ttorch.permute(ttorch.reshape(v, (B, T, G, hs)), (0, 2, 1, 3))

    q = _apply_rope(q, cos, sin, config)
    k = _apply_rope(k, cos, sin, config)

    y = ttorch.scaled_dot_product_attention(q, k, v, is_causal=True, enable_gqa=(G != H))
    y = ttorch.reshape(ttorch.permute(y, (0, 2, 1, 3)), (B, T, H * hs))
    return ttorch.linear(y, p["proj_w"], p.get("proj_b"))


def _moe_mlp(x, p, config: GPTConfig):
    """Mixtral-style MoE: top-k softmax routing over SwiGLU experts,
    renormalized gate weights. Dense per-token formulation at the trace
    level (every expert computed, top-k selected) — static shapes the MXU
    tiles; the distributed execution path with real token dispatch over an
    ``ep`` mesh axis is thunder_tpu.parallel.moe.moe_mlp."""
    B, T, C = x.shape
    k = config.n_expert_per_token
    xf = ttorch.reshape(x, (B * T, C))
    gate_logits = ttorch.linear(xf, p["router_w"])            # (N, E)
    top_logits, top_i = ttorch.topk(gate_logits, k, -1)       # (N, k)
    gate = ttorch.softmax(top_logits, -1)                     # renormalized over the k chosen
    h = ttorch.silu(ttorch.einsum("nd,ehd->neh", xf, p["w1"])) * ttorch.einsum(
        "nd,ehd->neh", xf, p["w3"]
    )
    all_out = ttorch.einsum("neh,edh->ned", h, p["w2"])       # (N, E, C)
    idx3 = ttorch.expand(ttorch.unsqueeze(top_i, -1), (B * T, k, C))
    sel = ttorch.take_along_dim(all_out, idx3, 1)             # (N, k, C)
    out = ttorch.sum(sel * ttorch.unsqueeze(gate, -1), 1)
    return ttorch.reshape(out, (B, T, C))


def _mlp(x, p, config: GPTConfig):
    if config.mlp_class == "MoEMLP":
        return _moe_mlp(x, p, config)
    if config.mlp_class == "LLaMAMLP":
        h = ttorch.silu(ttorch.linear(x, p["fc_1_w"], p.get("fc_1_b"))) * ttorch.linear(
            x, p["fc_2_w"], p.get("fc_2_b")
        )
        return ttorch.linear(h, p["proj_w"], p.get("proj_b"))
    h = ttorch.gelu(ttorch.linear(x, p["fc_w"], p.get("fc_b")))
    return ttorch.linear(h, p["proj_w"], p.get("proj_b"))


def _block(x, p, cos, sin, config: GPTConfig):
    n1 = _norm(x, p["norm_1"], config)
    attn_out = _attention(n1, p["attn"], cos, sin, config)
    if config.parallel_residual:
        n2 = n1 if config.shared_attention_norm else _norm(x, p["norm_2"], config)
        return x + attn_out + _mlp(n2, p["mlp"], config)
    x = x + attn_out
    return x + _mlp(_norm(x, p["norm_2"], config), p["mlp"], config)


def forward(params: dict, idx, config: GPTConfig):
    """Token ids (B, T) int → logits (B, T, padded_vocab_size)."""
    B, T = idx.shape
    x = ttorch.embedding(idx, params["wte"])  # (B, T, C)
    cos, sin = _rope_cache(T, config, device=x.device, dtype=x.dtype)
    for p in params["blocks"]:
        x = _block(x, p, cos, sin, config)
    x = _norm(x, params["ln_f"], config)
    return ttorch.linear(x, params["lm_head_w"])


def loss_fn(params: dict, idx, targets, config: GPTConfig):
    """Next-token cross-entropy; logits in f32 for a stable softmax."""
    logits = forward(params, idx, config)
    B, T, V = logits.shape
    logits = ttorch.reshape(logits.float(), (B * T, V))
    return ttorch.cross_entropy(logits, ttorch.reshape(targets, (B * T,)))
