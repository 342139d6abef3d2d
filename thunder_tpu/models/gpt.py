"""The GPT family: a functional, trace-friendly transformer.

Reference parity: the litgpt ``GPT`` exercised throughout the reference's
tests and benchmarks (thunder/tests/lit_gpt_model.py,
thunder/benchmarks/benchmark_litgpt.py:41) — GPT-NeoX (pythia) and
Llama/Mistral architectural variants: parallel vs sequential residual,
LayerNorm vs RMSNorm, GptNeoxMLP vs SwiGLU, partial-rotary RoPE, and
grouped-query attention. Beyond it: the DeepSeek-V3 family's block as A.X-K1
publishes it: latent attention (MLA) under YaRN, a leading dense layer, and
expert layers with a shared expert beside routed ones of which a chip may
hold a share (``experts_held``, ``expert_offset``); and LFM2-8B-A1B's: a mixer
kind per layer (``layer_types``: a gated short convolution or grouped-query
attention with an RMSNorm on every query and key head), a router whose bias
takes part in the choice and not in the weight, a head that is the embedding;
and MiniCPM-SALA's: block-sparse attention whose blocks each query chooses by
scoring pooled keys (``"sparse_attention"``) beside linear attention with a
decay per head (``"linear_attention"``), each with its own head layout and rope
setting, an output gate and an output norm on a mixer, and the MiniCPM
family's scalings of the embedding, the residual and the head's input; and
Trinity-Mini's (``afmoe``): two attention kinds of one head layout that differ
by layer in mask and rope (``"sliding_attention"``: causal within
``sliding_window`` keys, roped; ``"full_attention"``: causal, no rope), and a
norm after each sublayer beside the one before it (``sandwich_norms``); and
LongCat-Flash's (``"ShortcutMoE"``): a double layer of two latent-attention
sublayers and two dense FFNs with one routed layer on a shortcut across them,
whose softmax router scores zero-compute (identity) experts beside the real ones
(``zero_expert_num``) and leaves the chosen weights as they are; and Granite
4.0-H's (``granitemoehybrid``): Mamba-2 mixers (``"mamba"``: a packed projection
to a gate, ``[x | B | C]`` and a step a head, a short causal convolution, a
state-space recurrence whose decay each token sets, a gated norm) beside
rope-less grouped-query attention under its own softmax scale
(``attention_scale``).

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
from thunder_tpu.core.baseutils import check
from thunder_tpu.core.trace import region


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
    head_dim: Optional[int] = None  # a head's width where n_head of them are not n_embd; None -> n_embd // n_head
    rotary_percentage: float = 0.25
    parallel_residual: bool = True
    shared_attention_norm: bool = False
    bias: bool = True
    norm_class: str = "LayerNorm"  # or "RMSNorm"
    norm_eps: float = 1e-5
    # "GptNeoxMLP" (GELU), "LLaMAMLP" (SwiGLU), "MoEMLP" (mixtral-style), "SharedRoutedMoE" (a router over experts of
    # which a share may be held, beside shared ones) or "ShortcutMoE": no MLP kind but a block's, the double layer
    # whose two sublayers have a dense SwiGLU each and share one routed layer (_shortcut_block).
    mlp_class: str = "GptNeoxMLP"
    intermediate_size: Optional[int] = None
    rope_base: int = 10000
    # MoE (mlp_class="MoEMLP", mixtral-style SwiGLU experts: softmax over the
    # top-k logits; "SharedRoutedMoE": sigmoid scores, group-limited top-k,
    # shared experts beside the routed ones):
    n_expert: int = 0
    n_expert_per_token: int = 2
    moe_intermediate_size: Optional[int] = None  # width of one expert; None -> mlp_hidden
    n_shared_experts: int = 0
    n_expert_groups: int = 1  # the router picks from the best n_limited_groups of these
    n_limited_groups: int = 1
    routed_scaling_factor: float = 1.0
    # The router's scores: "sigmoid", or "softmax" over all its outputs; the chosen scores over their sum, or
    # (norm_topk_prob False) as they are. zero_expert_num outputs beyond the n_expert real ones are zero-compute
    # experts: a chosen one adds its weight times the router's input itself (zero_expert_type "identity").
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    zero_expert_num: int = 0
    # Layers [0, first_dense_layers) keep the dense LLaMAMLP at mlp_hidden; the
    # rest have mlp_class. The per-layer setting the registry has.
    first_dense_layers: int = 0
    # This chip's share of each expert layer: experts expert_offset to
    # expert_offset + experts_held are held (and computed) here; the router
    # still scores all n_expert. None holds them all.
    experts_held: Optional[int] = None
    expert_offset: int = 0
    # Latent attention (attention_class="MLA"): low-rank q and kv projections
    # with an RMSNorm on each latent, a rope part shared by all key heads beside
    # a no-rope part, value heads of their own width.
    attention_class: str = "MHA"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleaved: bool = False  # published pairs are (x0,x1),(x2,x3)..: de-interleaved, then rotate-half
    # q after its up-projection times sqrt(n_embd / q_lora_rank); the normed kv latent times
    # sqrt(n_embd / kv_lora_rank) before its up-projection (the shared rope key does not carry it).
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    # YaRN, or None for the plain rope: (factor, original_max_position_embeddings,
    # beta_fast, beta_slow, mscale, mscale_all_dim)
    yarn: Optional[tuple] = None
    # The mixer of each layer, as published: "full_attention", "sliding_attention"
    # (the same layer, causal within sliding_window keys), "conv" (a gated
    # short convolution of conv_kernel taps), "sparse_attention", "linear_attention"
    # or "mamba" (below). Empty is attention everywhere; a model cut in depth runs
    # the first n_layer of them.
    layer_types: tuple = ()
    # "sliding_attention": query i sees the keys j with 0 <= i - j < sliding_window,
    # so a sequence no longer than that attends causally. Such a layer is roped
    # always; attn_rope is "full_attention"'s setting.
    sliding_window: Optional[int] = None
    # Four norms a block: h = x + N(mixer(norm_1(x))), y = h + N(mlp(norm_2(h))).
    sandwich_norms: bool = False
    conv_kernel: int = 3
    qk_norm: bool = False  # an RMSNorm over head_size on every query and key head, before the rope
    tie_embeddings: bool = False  # the head reads wte: no lm_head_w leaf
    # The router's bias (a buffer, float32, one an expert) is added to the scores
    # for the choice and left out of the weights; router_norm_eps is the weights' normaliser's.
    router_bias: bool = False
    router_norm_eps: float = 1e-20
    # The MiniCPM family's scalings: the embedding times embedding_scale, each
    # sublayer's output times residual_scale before it joins the residual, the
    # final norm's output over logit_divisor before the head.
    embedding_scale: float = 1.0
    residual_scale: float = 1.0
    logit_divisor: float = 1.0
    # Softmax attention layers ("full_attention", "sparse_attention"): rope or none,
    # and y * sigmoid(gate(x)) before the output projection.
    attn_rope: bool = True
    attn_output_gate: bool = False
    # "sparse_attention" (InfLLM-V2): keys mean-pooled over sparse_kernel_size at
    # sparse_kernel_stride score blocks of sparse_block_size; a query attends to
    # its sparse_topk best, the first sparse_init_blocks and those of the last
    # sparse_window_size keys always among them; a sequence shorter than
    # sparse_dense_len attends causally to everything.
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_dense_len: int = 8192
    # "linear_attention" (Lightning Attention): its own head counts (None: n_head
    # of each), rope, an RMSNorm over all heads' outputs, an output gate; head h
    # of layer l decays by exp(-g) a step, g = 2**(-8 (h + 1) / H) * (1 - l /
    # (decay_depth - 1) + 1e-5), decay_depth the published depth (None: n_layer).
    linear_n_head: Optional[int] = None
    linear_query_groups: Optional[int] = None
    linear_rope: bool = True
    linear_output_norm: bool = False
    linear_output_gate: bool = False
    decay_depth: Optional[int] = None
    # The softmax scale of "full_attention" and "sliding_attention" layers; None is sdpa's own head_size**-0.5.
    attention_scale: Optional[float] = None
    # "mamba" (Mamba-2): ssm_n_head heads of ssm_head_dim (together the expanded width), a state of ssm_state a
    # head, B and C shared by the heads of each of ssm_groups groups, a causal convolution of ssm_conv_kernel taps
    # with a bias over [x | B | C]. ssm_chunk is the decomposition's chunk (None: ttorch.SSM_SCAN_CHUNK), the
    # program's own choice and no part of the equations.
    ssm_n_head: int = 0
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    ssm_conv_kernel: int = 4
    ssm_chunk: Optional[int] = None

    @property
    def head_size(self) -> int:
        return self.head_dim if self.head_dim is not None else self.n_embd // self.n_head

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

    @property
    def expert_hidden(self) -> int:
        return self.moe_intermediate_size if self.moe_intermediate_size is not None else self.mlp_hidden

    @property
    def held_experts(self) -> int:
        return self.experts_held if self.experts_held is not None else self.n_expert

    def layer_mlp_class(self, i: int) -> str:
        return "LLaMAMLP" if i < self.first_dense_layers else self.mlp_class

    def layer_mixer(self, i: int) -> str:
        return self.layer_types[i] if self.layer_types else "full_attention"

    @property
    def linear_heads(self) -> int:
        return self.linear_n_head if self.linear_n_head is not None else self.n_head

    @property
    def linear_groups(self) -> int:
        return self.linear_query_groups if self.linear_query_groups is not None else self.linear_heads

    def linear_decay(self, layer: int) -> tuple:
        """g of each head of linear-attention layer ``layer`` (0-based, of ``decay_depth``)."""
        H, L = self.linear_heads, self.decay_depth if self.decay_depth is not None else self.n_layer
        return tuple(2.0 ** (-8.0 * (h + 1) / H) * (1.0 - layer / max(L - 1, 1) + 1e-5) for h in range(H))

    @property
    def ssm_inner(self) -> int:
        return self.ssm_n_head * self.ssm_head_dim

    @property
    def ssm_conv_channels(self) -> int:
        """``[x | B | C]``: what the convolution runs over."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def ssm_chunk_size(self) -> int:
        """The chunk ``ssm_scan`` is called with: what the program publishes of its decomposition."""
        return self.ssm_chunk if self.ssm_chunk is not None else ttorch.SSM_SCAN_CHUNK

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> Optional[float]:
        """None is sdpa's own ``D**-0.5``. Latent attention under YaRN
        multiplies it by ``m**2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``.
        ``mla_scale_q_lora``'s factor on q rides here: ``(a q) . k`` is ``a (q . k)``,
        so no activation and no weight is rounded for it."""
        if self.attention_class != "MLA":
            return None
        m = _yarn_mscale(self.yarn[0], self.yarn[5]) if self.yarn else 1.0
        q_lora = (self.n_embd / self.q_lora_rank) ** 0.5 if self.mla_scale_q_lora else 1.0
        return self.qk_head_dim ** -0.5 * m * m * q_lora

    @property
    def router_outputs(self) -> int:
        return self.n_expert + self.zero_expert_num


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

# A.X-K1 (huggingface.co/skt/A.X-K1, model_type axk1) at its published sizes:
# latent attention with 192-wide query-key and 128-wide value heads under YaRN,
# one dense layer, then 192 routed experts (8 a token, from the best 4 of 8
# groups, sigmoid scores, scaled 2.5) beside one shared expert.
_add(GPTConfig(name="A.X-K1", block_size=131072, vocab_size=163840, padded_vocab_size=163840,
               n_layer=61, n_head=64, n_embd=7168, rotary_percentage=1.0, parallel_residual=False,
               bias=False, norm_class="RMSNorm", norm_eps=1e-6, mlp_class="SharedRoutedMoE",
               intermediate_size=18432, rope_base=10000, n_expert=192, n_expert_per_token=8,
               moe_intermediate_size=2048, n_shared_experts=1, n_expert_groups=8, n_limited_groups=4,
               routed_scaling_factor=2.5, first_dense_layers=1, attention_class="MLA",
               q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
               v_head_dim=128, rope_interleaved=True, yarn=(32.0, 4096, 32.0, 1.0, 1.0, 1.0)))
# The same blocks at test size: unequal head widths (24 and 16), groups, a held
# subset of the experts.
_add(GPTConfig(name="axk1-tiny", block_size=64, vocab_size=96, padded_vocab_size=96,
               n_layer=3, n_head=2, n_embd=32, rotary_percentage=1.0, parallel_residual=False,
               bias=False, norm_class="RMSNorm", norm_eps=1e-6, mlp_class="SharedRoutedMoE",
               intermediate_size=64, n_expert=16, n_expert_per_token=4, moe_intermediate_size=16,
               n_shared_experts=1, n_expert_groups=4, n_limited_groups=2, routed_scaling_factor=2.5,
               first_dense_layers=1, experts_held=4, expert_offset=4, attention_class="MLA",
               q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, rope_interleaved=True, yarn=(4.0, 16, 32.0, 1.0, 1.0, 1.0)))

# LFM2-8B-A1B (huggingface.co/LiquidAI/LFM2-8B-A1B, model_type lfm2_moe) at its
# published sizes: 18 gated short convolutions (3 taps) and 6 grouped-query
# attention layers with normed heads of 64, two dense layers of 7168, then 32
# experts of 1792, 4 a token by sigmoid scores plus a bias, no shared expert.
_LFM2_LAYERS = tuple("full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv" for i in range(24))
_add(GPTConfig(name="LFM2-8B-A1B", block_size=128000, vocab_size=65536, padded_vocab_size=65536,
               n_layer=24, n_head=32, n_embd=2048, n_query_groups=8, rotary_percentage=1.0,
               parallel_residual=False, bias=False, norm_class="RMSNorm", norm_eps=1e-5,
               mlp_class="SharedRoutedMoE", intermediate_size=7168, rope_base=1000000, n_expert=32,
               n_expert_per_token=4, moe_intermediate_size=1792, first_dense_layers=2,
               layer_types=_LFM2_LAYERS, conv_kernel=3, qk_norm=True, tie_embeddings=True,
               router_bias=True, router_norm_eps=1e-6))
# The same blocks at test size: both mixers, a dense conv layer first.
_add(GPTConfig(name="lfm2-tiny", block_size=64, vocab_size=96, padded_vocab_size=96,
               n_layer=4, n_head=4, n_embd=32, n_query_groups=1, rotary_percentage=1.0,
               parallel_residual=False, bias=False, norm_class="RMSNorm", norm_eps=1e-5,
               mlp_class="SharedRoutedMoE", intermediate_size=64, rope_base=1000000, n_expert=8,
               n_expert_per_token=2, moe_intermediate_size=16, first_dense_layers=1,
               layer_types=("conv", "full_attention", "conv", "conv"), conv_kernel=3, qk_norm=True,
               tie_embeddings=True, router_bias=True, router_norm_eps=1e-6))

# MiniCPM-SALA (huggingface.co/openbmb/MiniCPM-SALA, model_type minicpm_sala) at
# its published sizes: 8 block-sparse attention layers (32 query heads on 2
# key-value heads of 128, no rope, normed heads, an output gate; MiniCPM4's
# sparse constants are the fields' defaults) among 24 linear-attention layers
# (32 heads of 128, rope, normed heads, a decay per head, an output norm and
# gate), SwiGLU of 16384, the family's scalings, an untied head.
_SALA_LAYERS = tuple("sparse_attention" if i in (0, 9, 16, 17, 22, 29, 30, 31) else "linear_attention"
                     for i in range(32))
_add(GPTConfig(name="MiniCPM-SALA", block_size=524288, vocab_size=73448, padded_vocab_size=73448,
               n_layer=32, n_head=32, n_embd=4096, n_query_groups=2, rotary_percentage=1.0,
               parallel_residual=False, bias=False, norm_class="RMSNorm", norm_eps=1e-6,
               mlp_class="LLaMAMLP", intermediate_size=16384, rope_base=10000, layer_types=_SALA_LAYERS,
               qk_norm=True, embedding_scale=12.0, residual_scale=1.4 / 32 ** 0.5, logit_divisor=4096 / 256,
               attn_rope=False, attn_output_gate=True, linear_n_head=32, linear_query_groups=32,
               linear_rope=True, linear_output_norm=True, linear_output_gate=True, decay_depth=32))
# The same blocks at test size: at T = 256 a query has 16 blocks of 16 keys and
# attends to 6, three of them chosen by score; T < 64 attends densely.
_add(GPTConfig(name="minicpm-sala-tiny", block_size=256, vocab_size=96, padded_vocab_size=96,
               n_layer=4, n_head=4, n_embd=256, n_query_groups=1, rotary_percentage=1.0,
               parallel_residual=False, bias=False, norm_class="RMSNorm", norm_eps=1e-6,
               mlp_class="LLaMAMLP", intermediate_size=512, rope_base=10000,
               layer_types=("sparse_attention", "linear_attention", "linear_attention", "linear_attention"),
               qk_norm=True, embedding_scale=12.0, residual_scale=1.4 / 4 ** 0.5, logit_divisor=256 / 64,
               attn_rope=False, attn_output_gate=True, sparse_kernel_size=8, sparse_kernel_stride=4,
               sparse_block_size=16, sparse_topk=6, sparse_init_blocks=1, sparse_window_size=32,
               sparse_dense_len=64, linear_n_head=4, linear_query_groups=4, linear_rope=True,
               linear_output_norm=True, linear_output_gate=True))

# Mistral — reference benchmark ladder step 5 (GQA). Every layer attends within
# the published window of 4096, which is plain causal attention up to block_size.
_add(GPTConfig(name="mistral-7b", block_size=4096, vocab_size=32000, padded_vocab_size=32000,
               n_layer=32, n_head=32, n_embd=4096, n_query_groups=8, rotary_percentage=1.0,
               parallel_residual=False, bias=False, norm_class="RMSNorm", norm_eps=1e-5,
               mlp_class="LLaMAMLP", intermediate_size=14336,
               layer_types=("sliding_attention",) * 32, sliding_window=4096))
# The same blocks at test size, with room beyond the window.
_add(GPTConfig(name="mistral-tiny", block_size=64, vocab_size=96, padded_vocab_size=96,
               n_layer=2, n_head=4, n_embd=32, n_query_groups=2, rotary_percentage=1.0,
               parallel_residual=False, bias=False, norm_class="RMSNorm", norm_eps=1e-5,
               mlp_class="LLaMAMLP", intermediate_size=88,
               layer_types=("sliding_attention",) * 2, sliding_window=16))

# Trinity-Mini (huggingface.co/arcee-ai/Trinity-Mini, model_type afmoe) at its
# published sizes: 32 query heads on 4 key-value heads of 128, normed and gated;
# three layers in four attend within a window of 2048 keys and are roped, every
# fourth attends to everything and has no rope; four norms a block; two dense
# layers of 6144, then 128 experts of 1024, 8 a token by sigmoid scores plus a
# bias, weighed by the scores over their sum times 2.826, beside one shared
# expert; the embedding times sqrt(2048); an untied head.
_TRINITY_LAYERS = tuple("full_attention" if i % 4 == 3 else "sliding_attention" for i in range(32))
_add(GPTConfig(name="Trinity-Mini", block_size=131072, vocab_size=200192, padded_vocab_size=200192,
               n_layer=32, n_head=32, n_embd=2048, n_query_groups=4, head_dim=128, rotary_percentage=1.0,
               parallel_residual=False, bias=False, norm_class="RMSNorm", norm_eps=1e-5,
               mlp_class="SharedRoutedMoE", intermediate_size=6144, rope_base=10000, n_expert=128,
               n_expert_per_token=8, moe_intermediate_size=1024, n_shared_experts=1,
               routed_scaling_factor=2.826, first_dense_layers=2, layer_types=_TRINITY_LAYERS,
               sliding_window=2048, attn_rope=False, qk_norm=True, attn_output_gate=True,
               sandwich_norms=True, router_bias=True, router_norm_eps=1e-20, embedding_scale=2048 ** 0.5))
# The same blocks at test size: a dense window layer, then window, global, window
# with experts; at T = 64 a query sees 16 keys of its 64 in the window layers.
_add(GPTConfig(name="trinity-tiny", block_size=256, vocab_size=96, padded_vocab_size=96,
               n_layer=4, n_head=4, n_embd=256, n_query_groups=2, head_dim=32, rotary_percentage=1.0,
               parallel_residual=False, bias=False, norm_class="RMSNorm", norm_eps=1e-5,
               mlp_class="SharedRoutedMoE", intermediate_size=512, rope_base=10000, n_expert=8,
               n_expert_per_token=2, moe_intermediate_size=128, n_shared_experts=1,
               routed_scaling_factor=2.826, first_dense_layers=1,
               layer_types=("sliding_attention", "sliding_attention", "full_attention", "sliding_attention"),
               sliding_window=16, attn_rope=False, qk_norm=True, attn_output_gate=True,
               sandwich_norms=True, router_bias=True, router_norm_eps=1e-20, embedding_scale=256 ** 0.5))

# LongCat-Flash-Omni's language model (huggingface.co/meituan-longcat/LongCat-Flash-Omni, Meituan's 560B-A27B) at
# its published sizes: 28 double layers, each two latent-attention sublayers (64 heads of 128 + 64 rope and 128,
# both latents' scales, rope base 1e7, no YaRN) with a dense SwiGLU of 12288 each and one routed layer on a
# shortcut across them: a softmax router over 512 experts of 2048 and 256 zero-compute ones, 12 a token by score
# plus a bias, weighed 6 times the score, not renormalised; no shared expert; an untied head.
_add(GPTConfig(name="LongCat-Flash-Omni", block_size=131072, vocab_size=131072, padded_vocab_size=131072,
               n_layer=28, n_head=64, n_embd=6144, rotary_percentage=1.0, parallel_residual=False,
               bias=False, norm_class="RMSNorm", norm_eps=1e-5, mlp_class="ShortcutMoE",
               intermediate_size=12288, rope_base=10000000, n_expert=512, n_expert_per_token=12,
               moe_intermediate_size=2048, routed_scaling_factor=6.0, scoring_func="softmax",
               norm_topk_prob=False, zero_expert_num=256, router_bias=True, attention_class="MLA",
               q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
               v_head_dim=128, rope_interleaved=True, mla_scale_q_lora=True, mla_scale_kv_lora=True))
# The same blocks at test size: 16 experts and 8 zero-compute ones, 4 a token, a share of 4 held from 4.
_add(GPTConfig(name="longcat-tiny", block_size=64, vocab_size=96, padded_vocab_size=96,
               n_layer=2, n_head=2, n_embd=32, rotary_percentage=1.0, parallel_residual=False,
               bias=False, norm_class="RMSNorm", norm_eps=1e-5, mlp_class="ShortcutMoE",
               intermediate_size=64, rope_base=10000000, n_expert=16, n_expert_per_token=4,
               moe_intermediate_size=16, routed_scaling_factor=6.0, scoring_func="softmax",
               norm_topk_prob=False, zero_expert_num=8, router_bias=True, experts_held=4, expert_offset=4,
               attention_class="MLA", q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, rope_interleaved=True, mla_scale_q_lora=True,
               mla_scale_kv_lora=True))

# Granite 4.0-H Micro (huggingface.co/ibm-granite/granite-4.0-h-micro, model_type granitemoehybrid, no routed
# part: num_local_experts 0) at its published sizes: 36 Mamba-2 mixers (64 heads of 64 on one group's B and C, state
# 128, 4 taps) and, at layers 5, 15, 25 and 35, grouped-query attention (32 on 8 heads of 64) with no rope and a
# softmax scale of 1/64; SwiGLU of 8192; the Granite multipliers (embedding 12, residual 0.22, logits over 8); the
# head is the embedding.
_GRANITE_H_LAYERS = tuple("full_attention" if i % 10 == 5 else "mamba" for i in range(40))
_add(GPTConfig(name="granite-4.0-h-micro", block_size=131072, vocab_size=100352, padded_vocab_size=100352,
               n_layer=40, n_head=32, n_embd=2048, n_query_groups=8, rotary_percentage=1.0,
               parallel_residual=False, bias=False, norm_class="RMSNorm", norm_eps=1e-5, mlp_class="LLaMAMLP",
               intermediate_size=8192, rope_base=10000, layer_types=_GRANITE_H_LAYERS, attn_rope=False,
               attention_scale=0.015625, tie_embeddings=True, embedding_scale=12.0, residual_scale=0.22,
               logit_divisor=8.0, ssm_n_head=64, ssm_head_dim=64, ssm_state=128, ssm_groups=1, ssm_conv_kernel=4))
# The same blocks at test size: heads of 64 in both mixers, and a chunk of 64 so that T = 256 is four chunks.
_add(GPTConfig(name="granite-h-tiny", block_size=256, vocab_size=96, padded_vocab_size=96,
               n_layer=6, n_head=2, n_embd=128, n_query_groups=1, rotary_percentage=1.0,
               parallel_residual=False, bias=False, norm_class="RMSNorm", norm_eps=1e-5, mlp_class="LLaMAMLP",
               intermediate_size=256, rope_base=10000,
               layer_types=("mamba", "full_attention", "mamba", "mamba", "full_attention", "mamba"), attn_rope=False,
               attention_scale=0.015625, tie_embeddings=True, embedding_scale=12.0, residual_scale=0.22,
               logit_divisor=8.0, ssm_n_head=4, ssm_head_dim=64, ssm_state=32, ssm_groups=1, ssm_conv_kernel=4,
               ssm_chunk=64))

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

    def attn_params(heads, groups, gate, out_norm=False):
        if C.attention_class == "MLA":
            H, dqk = C.n_head, C.qk_head_dim
            return {
                "q_a_w": w(C.q_lora_rank, C.n_embd),
                "q_a_norm": {"weight": ones(C.q_lora_rank)},
                "q_b_w": w(H * dqk, C.q_lora_rank),
                "kv_a_w": w(C.kv_lora_rank + C.qk_rope_head_dim, C.n_embd),
                "kv_a_norm": {"weight": ones(C.kv_lora_rank)},
                "kv_b_w": w(H * (C.qk_nope_head_dim + C.v_head_dim), C.kv_lora_rank),
                "proj_w": w(C.n_embd, H * C.v_head_dim, std=0.02 / np.sqrt(2 * C.n_layer)),
            }
        p = {
            "qkv_w": w((heads + 2 * groups) * C.head_size, C.n_embd),
            "proj_w": w(C.n_embd, heads * C.head_size, std=0.02 / np.sqrt(2 * C.n_layer)),
        }
        if C.bias:
            p["qkv_b"] = zeros((heads + 2 * groups) * C.head_size)
            p["proj_b"] = zeros(C.n_embd)
        if C.qk_norm:  # one weight of head_size for all heads
            p["q_norm"] = {"weight": ones(C.head_size)}
            p["k_norm"] = {"weight": ones(C.head_size)}
        if gate:  # y * sigmoid(gate(x)) before the output projection
            p["gate_w"] = w(heads * C.head_size, C.n_embd)
        if out_norm:  # an RMSNorm over all heads' outputs
            p["out_norm"] = {"weight": ones(heads * C.head_size)}
        return p

    def conv_params():
        # conv_w is the depthwise Conv1d's (channels, 1, taps) without the 1, oldest tap first.
        return {"in_proj_w": w(3 * C.n_embd, C.n_embd), "conv_w": w(C.n_embd, C.conv_kernel),
                "out_proj_w": w(C.n_embd, C.n_embd, std=0.02 / np.sqrt(2 * C.n_layer))}

    def mamba_params():
        # in_proj_w's rows are [z | x | B | C | dt]; conv_w as conv_params' (oldest tap first), over [x | B | C].
        # A_log, dt_bias and D are float32 whatever the weights are (the decay is computed in float32), drawn
        # as Mamba-2 draws them: A in [1, 16], a step in [0.001, 0.1] log-uniform through the inverse softplus.
        H = C.ssm_n_head
        uniform = lambda lo, hi: rng.uniform(lo, hi, size=(H,)).astype(np.float32)  # the host's generator on either path
        step = np.exp(uniform(np.log(0.001), np.log(0.1)))
        return {"in_proj_w": w(C.ssm_inner + C.ssm_conv_channels + H, C.n_embd),
                "conv_w": w(C.ssm_conv_channels, C.ssm_conv_kernel), "conv_b": zeros(C.ssm_conv_channels),
                "dt_bias": jnp.asarray(step + np.log(-np.expm1(-step)), dtype=jnp.float32),
                "A_log": jnp.asarray(np.log(uniform(1.0, 16.0)), dtype=jnp.float32),
                "D": jnp.ones((H,), dtype=jnp.float32),
                "norm": {"weight": ones(C.ssm_inner)},
                "out_proj_w": w(C.n_embd, C.ssm_inner, std=0.02 / np.sqrt(2 * C.n_layer))}

    def swiglu_params(hidden):
        p = {
            "fc_1_w": w(hidden, C.n_embd),
            "fc_2_w": w(hidden, C.n_embd),
            "proj_w": w(C.n_embd, hidden, std=0.02 / np.sqrt(2 * C.n_layer)),
        }
        if C.bias:
            p.update(fc_1_b=zeros(hidden), fc_2_b=zeros(hidden), proj_b=zeros(C.n_embd))
        return p

    def mlp_params(kind):
        if kind == "MoEMLP":
            E, H = C.n_expert, C.mlp_hidden
            return {"router_w": w(E, C.n_embd), "w1": w(E, H, C.n_embd), "w3": w(E, H, C.n_embd),
                    "w2": w(E, C.n_embd, H, std=0.02 / np.sqrt(2 * C.n_layer))}
        if kind == "SharedRoutedMoE":
            # Routed experts are stored (expert, in, out), the grouped matmul's
            # layout, and only the held ones: the share is configuration.
            E, H = C.held_experts, C.expert_hidden
            p = {"router_w": w(C.router_outputs, C.n_embd),
                 "experts_gate": w(E, C.n_embd, H), "experts_up": w(E, C.n_embd, H),
                 "experts_down": w(E, H, C.n_embd, std=0.02 / np.sqrt(2 * C.n_layer))}
            if C.router_bias:  # a buffer: float32 whatever the weights are, and no gradient reaches it
                p["router_bias"] = jnp.zeros((C.router_outputs,), dtype=jnp.float32)
            if C.n_shared_experts:
                p["shared"] = swiglu_params(C.n_shared_experts * H)
            return p
        if kind == "LLaMAMLP":
            return swiglu_params(C.mlp_hidden)
        p = {"fc_w": w(C.mlp_hidden, C.n_embd),
             "proj_w": w(C.n_embd, C.mlp_hidden, std=0.02 / np.sqrt(2 * C.n_layer))}
        if C.bias:
            p.update(fc_b=zeros(C.mlp_hidden), proj_b=zeros(C.n_embd))
        return p

    def block_params(i):
        if C.layer_mlp_class(i) == "ShortcutMoE":
            # Two sublayers, each a sequential block's leaves with a dense SwiGLU, and the one routed layer.
            sub = lambda: {"norm_1": norm_params(), "attn": attn_params(C.n_head, C.query_groups, False),
                           "norm_2": norm_params(), "mlp": swiglu_params(C.mlp_hidden)}
            return {"sub_0": sub(), "sub_1": sub(), "moe": mlp_params("SharedRoutedMoE")}
        p: dict[str, Any] = {"norm_1": norm_params(), "mlp": mlp_params(C.layer_mlp_class(i))}
        mixer = C.layer_mixer(i)
        if mixer == "conv":
            p["conv"] = conv_params()
        elif mixer == "mamba":
            p["mamba"] = mamba_params()
        elif mixer == "sparse_attention":
            p["sparse_attn"] = attn_params(C.n_head, C.query_groups, C.attn_output_gate)
        elif mixer == "linear_attention":
            p["linear_attn"] = attn_params(C.linear_heads, C.linear_groups, C.linear_output_gate,
                                           C.linear_output_norm)
        else:
            p["attn"] = attn_params(C.n_head, C.query_groups, C.attn_output_gate)
        if not C.shared_attention_norm:
            p["norm_2"] = norm_params()
        if C.sandwich_norms:
            p["post_attn_norm"], p["post_mlp_norm"] = norm_params(), norm_params()
        return p

    params = {"wte": w(C.padded_vocab_size, C.n_embd)}
    blocks = [block_params(i) for i in range(C.n_layer)]
    if C.first_dense_layers:
        # Two lists, so that each kind of leaf has as many layers as carry it.
        params["dense_blocks"] = blocks[: C.first_dense_layers]
        params["moe_blocks"] = blocks[C.first_dense_layers:]
    else:
        params["blocks"] = blocks
    params["ln_f"] = norm_params()
    if not C.tie_embeddings:
        params["lm_head_w"] = w(C.padded_vocab_size, C.n_embd)
    return params


# =============================================================================
# Forward
# =============================================================================


def _norm(x, p, config: GPTConfig):
    if config.norm_class == "RMSNorm":
        return ttorch.rms_norm(x, (config.n_embd,), p["weight"], eps=config.norm_eps)
    return ttorch.layer_norm(x, (config.n_embd,), p["weight"], p.get("bias"), eps=config.norm_eps)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * float(np.log(factor)) + 1.0


def _yarn_inv_freq(n: int, base: float, yarn: tuple) -> np.ndarray:
    """YaRN's inverse frequencies for a rope of ``n`` features: the plain
    ``base**(-2i/n)`` where a feature turns more than ``beta_fast`` times over
    the original context, that over ``factor`` where it turns fewer than
    ``beta_slow`` times, and a linear blend between the two correction dims."""
    factor, original, beta_fast, beta_slow = yarn[:4]
    extra = base ** (-np.arange(0, n, 2, dtype=np.float64) / n)

    def correction_dim(rotations):
        return n * np.log(original / (rotations * 2 * np.pi)) / (2 * np.log(base))

    low = max(np.floor(correction_dim(beta_fast)), 0)
    high = min(np.ceil(correction_dim(beta_slow)), n - 1)
    ramp = np.clip((np.arange(n // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0, 1)
    return extra / factor * ramp + extra * (1 - ramp)


def _rope_cache(T: int, config: GPTConfig, device, dtype):
    """cos/sin of shape (T, rope_n_elem) — built from iota, so XLA folds them
    into constants of the compiled executable."""
    import thunder_tpu.clang as clang

    if config.attention_class == "MLA":
        n = config.qk_rope_head_dim
        inv = (_yarn_inv_freq(n, float(config.rope_base), config.yarn) if config.yarn
               else float(config.rope_base) ** (-np.arange(0, n, 2, dtype=np.float64) / n))
        theta = clang.tensor_from_sequence([float(v) for v in inv], device=device, dtype=dtypes.float32)
        # The tables' own factor, mscale(factor, mscale) / mscale(factor, mscale_all_dim), is 1.
        check(not config.yarn or config.yarn[4] == config.yarn[5],
              lambda: f"YaRN tables with mscale {config.yarn[4]} != mscale_all_dim {config.yarn[5]} are not built")
    else:
        n = config.rope_n_elem
        theta = clang.pow(float(config.rope_base), clang.true_divide(
            clang.mul(clang.arange(0, n // 2, 1, device=device, dtype=dtypes.float32), -2.0), float(n)))
    pos = clang.arange(0, T, 1, device=device, dtype=dtypes.float32)
    freqs = clang.mul(clang.unsqueeze(pos, 1), clang.unsqueeze(theta, 0))  # (T, half)
    emb = clang.cat([freqs, freqs], dim=1)  # (T, n) rotate-half convention
    return clang.maybe_convert_to_dtype(clang.cos(emb), dtype), clang.maybe_convert_to_dtype(clang.sin(emb), dtype)


def _apply_rope(x, cos, sin, config: GPTConfig):
    """x: (B, H, T, hs); rotate the first rope_n_elem features. Composite op
    so the Pallas rope kernel claims it (pallasex; the decomposed
    rotate-half is lane-misaligned at hs=100)."""
    return ttorch.apply_rope(x, cos, sin)


def _qkv_heads(x, p, H: int, G: int, cos, sin, config: GPTConfig, rope: bool = True):
    """The packed projection's q (B, H, T, hs), k and v (B, G, T, hs), the heads
    normed where the model norms them, q and k roped where the layer ropes."""
    B, T, C = x.shape
    hs = config.head_size

    qkv = ttorch.linear(x, p["qkv_w"], p.get("qkv_b"))  # (B, T, (H+2G)*hs)
    q = qkv[..., : H * hs]
    k = qkv[..., H * hs : (H + G) * hs]
    v = qkv[..., (H + G) * hs :]

    q = ttorch.permute(ttorch.reshape(q, (B, T, H, hs)), (0, 2, 1, 3))  # (B,H,T,hs)
    k = ttorch.permute(ttorch.reshape(k, (B, T, G, hs)), (0, 2, 1, 3))
    v = ttorch.permute(ttorch.reshape(v, (B, T, G, hs)), (0, 2, 1, 3))

    if config.qk_norm:
        with region("attn.qk_norm"):
            q = ttorch.rms_norm(q, (hs,), p["q_norm"]["weight"], eps=config.norm_eps)
            k = ttorch.rms_norm(k, (hs,), p["k_norm"]["weight"], eps=config.norm_eps)
    if rope:
        q = _apply_rope(q, cos, sin, config)
        k = _apply_rope(k, cos, sin, config)
    return q, k, v


def _merge_heads(y):
    """(B, H, T, hs) -> (B, T, H * hs)."""
    B, H, T, hs = y.shape
    return ttorch.reshape(ttorch.permute(y, (0, 2, 1, 3)), (B, T, H * hs))


SPARSE_TILE = 128  # consecutive queries whose chosen blocks ``sparse_selection_counts`` unites


def _attention(x, p, cos, sin, config: GPTConfig, sparse: bool = False, counts=None, window: bool = False):
    """Causal softmax attention between its projections. ``window``: a query sees
    its own key and the ``sliding_window - 1`` before it, and is roped whatever
    ``attn_rope`` says of the other layers; a sequence no longer than the window
    is causal attention and is run as that.
    ``sparse``: InfLLM-V2's
    layer, where every query attends to the ``sparse_topk`` blocks of
    ``sparse_block_size`` keys that its key-value head's queries score highest
    through mean-pooled keys, the first blocks and its own window always among
    them; a sequence shorter than ``sparse_dense_len`` attends to everything.
    ``counts`` collects, a sparse layer, the distinct blocks that each tile of
    ``SPARSE_TILE`` consecutive queries chose (``"tile_union"``)."""
    C, T = config, x.shape[1]
    H, G = C.n_head, C.query_groups
    q, k, v = _qkv_heads(x, p, H, G, cos, sin, C, window or C.attn_rope)
    scale = {} if C.attention_scale is None else {"scale": C.attention_scale}  # a trace without it stays as it was
    if window and T > C.sliding_window:
        with region("attn.window"):
            y = ttorch.window_attention(q, k, v, window=C.sliding_window, **scale)
    elif not sparse or T < C.sparse_dense_len:
        with region("attn.full"):
            y = ttorch.scaled_dot_product_attention(q, k, v, is_causal=True, enable_gqa=(G != H), **scale)
    else:
        how = dict(kernel_size=C.sparse_kernel_size, kernel_stride=C.sparse_kernel_stride,
                   block_size=C.sparse_block_size, topk=C.sparse_topk, init_blocks=C.sparse_init_blocks,
                   local_blocks=C.sparse_window_size // C.sparse_block_size)
        if counts is None:
            y = ttorch.sparse_block_attention(q, k, v, **how)
        else:  # the composite's two halves, the chosen blocks counted between them
            ids = ttorch.sparse_block_select(q, k, **how)
            counts["tile_union"].append(_tile_union(ids, -(-T // C.sparse_block_size)))
            y = ttorch.sparse_block_attend(q, k, v, ids, block_size=C.sparse_block_size)
    y = _merge_heads(y)
    if C.attn_output_gate:
        y = y * ttorch.sigmoid(ttorch.linear(x, p["gate_w"]))
    return ttorch.linear(y, p["proj_w"], p.get("proj_b"))


def _tile_union(ids, n_blocks: int, queries_a_pass: int = 4096):
    """ids (B, G, T, k), a query's chosen blocks (-1: none) -> (B, G, T // SPARSE_TILE)
    int32, the distinct blocks the queries of each whole tile chose between them."""
    B, G, T, k = ids.shape
    blocks = ttorch.arange(0, n_blocks, device=ids.device, dtype=ids.dtype)
    tiles = []
    for t0 in range(0, T - T % SPARSE_TILE, queries_a_pass):  # a pass: (.., 4096 * k, n_blocks) compares, reduced at once
        t1 = min(t0 + queries_a_pass, T - T % SPARSE_TILE)
        of_tile = ttorch.reshape(ids[:, :, t0:t1], (B, G, (t1 - t0) // SPARSE_TILE, SPARSE_TILE * k, 1))
        chosen = ttorch.amax((of_tile == blocks).to(dtypes.int32), 3)           # (B, G, tiles, n_blocks)
        tiles.append(ttorch.sum(chosen, -1))
    return ttorch.cat(tiles, 2)


def _linear_attention(x, p, cos, sin, config: GPTConfig, layer: int):
    """Lightning Attention's layer: ``o_t = sum_{s<=t} exp(-g (t-s)) (q_t . k_s /
    sqrt(d)) v_s`` with one ``g`` a head (``GPTConfig.linear_decay``), no softmax
    and no normaliser; then the norm over all heads' outputs and the gate."""
    import thunder_tpu.clang as clang

    C = config
    H = C.linear_heads
    q, k, v = _qkv_heads(x, p, H, C.linear_groups, cos, sin, C, C.linear_rope)
    decay = clang.tensor_from_sequence(list(C.linear_decay(layer)), device=x.device, dtype=dtypes.float32)
    with region("attn.linear"):
        y = ttorch.linear_attention(q, k, v, decay)
    y = _merge_heads(y)
    if C.linear_output_norm:
        y = ttorch.rms_norm(y, (H * C.head_size,), p["out_norm"]["weight"], eps=C.norm_eps)
    if C.linear_output_gate:
        y = y * ttorch.sigmoid(ttorch.linear(x, p["gate_w"]))
    return ttorch.linear(y, p["proj_w"])


def _short_conv(x, p, config: GPTConfig):
    """The gated short convolution: ``[B | C | u] = in_proj(x)``, a causal
    depthwise convolution of ``conv_kernel`` taps over ``B * u``, times ``C``,
    then ``out_proj``."""
    with region("conv"):
        return ttorch.linear(ttorch.short_conv(ttorch.linear(x, p["in_proj_w"]), p["conv_w"]), p["out_proj_w"])


def _mamba(x, p, config: GPTConfig):
    """The Mamba-2 mixer: ``[z | xBC | dt] = in_proj(x)``; ``[x | B | C] =
    silu(conv(xBC) + b)``, causal and depthwise; ``dt = softplus(dt + dt_bias)``
    and ``A = -exp(A_log)`` in float32; head h's state ``S_t = exp(dt_t A) S_{t-1}
    + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``; ``RMSNorm(y * silu(z))`` over
    all heads' features; ``out_proj``."""
    C = config
    B, T, _ = x.shape
    H, P, G, N, inner = C.ssm_n_head, C.ssm_head_dim, C.ssm_groups, C.ssm_state, C.ssm_inner
    zxbcdt, conv_end = ttorch.linear(x, p["in_proj_w"]), inner + C.ssm_conv_channels
    z, xbc, dt = zxbcdt[..., :inner], zxbcdt[..., inner:conv_end], zxbcdt[..., conv_end:]
    with region("ssm.conv"):
        xbc = ttorch.causal_conv_silu(xbc, p["conv_w"], p["conv_b"])
    xs = ttorch.reshape(xbc[..., :inner], (B, T, H, P))
    Bm = ttorch.reshape(xbc[..., inner:inner + G * N], (B, T, G, N))
    Cm = ttorch.reshape(xbc[..., inner + G * N:], (B, T, G, N))
    with region("ssm.scan"):
        dt = ttorch.softplus(dt.float() + p["dt_bias"].float())
        y = ttorch.ssm_scan(xs, dt, -ttorch.exp(p["A_log"].float()), Bm, Cm, p["D"], chunk=C.ssm_chunk_size)
    with region("ssm.gate_norm"):
        y = ttorch.gated_rms_norm(ttorch.reshape(y, (B, T, inner)), z, p["norm"]["weight"], eps=C.norm_eps)
    return ttorch.linear(y, p["out_proj_w"])


def _deinterleave_rows(w):
    """(G, n, c): each group's rows (x0, x1, x2, x3, ..) to (x0, x2, .., x1, x3, ..).
    The published rope pairs neighbours, rotate-half pairs the two halves."""
    g, n, c = w.shape
    return ttorch.reshape(ttorch.permute(ttorch.reshape(w, (g, n // 2, 2, c)), (0, 2, 1, 3)), (g, n, c))


def _mla_attention(x, p, cos, sin, config: GPTConfig):
    """Latent attention in its expanded (prefill) form. A head's query and key
    are [rope part, no-rope part] here, where the published order is [no-rope,
    rope] with interleaved pairs: q.k is the same under one permutation of both,
    and a permutation of a projection's outputs is a permutation of its
    weight's rows, so it is applied to the rows of ``q_b_w`` and ``kv_a_w``
    and the activations never take a lane shuffle. With the rope part first the
    rope kernel's partial-rotary body rotates q in place."""
    B, T, C = x.shape
    H, dn, dr, dv = config.n_head, config.qk_nope_head_dim, config.qk_rope_head_dim, config.v_head_dim
    L, R = config.kv_lora_rank, config.q_lora_rank
    pairs = _deinterleave_rows if config.rope_interleaved else (lambda w: w)

    q_b = ttorch.reshape(p["q_b_w"], (H, dn + dr, R))
    q_w = ttorch.reshape(ttorch.cat([pairs(q_b[:, dn:, :]), q_b[:, :dn, :]], 1), (H * (dr + dn), R))
    k_pe_w = ttorch.reshape(pairs(ttorch.reshape(p["kv_a_w"][L:, :], (1, dr, C))), (dr, C))
    kv_a_w = ttorch.cat([p["kv_a_w"][:L, :], k_pe_w], 0)

    c_q = ttorch.rms_norm(ttorch.linear(x, p["q_a_w"]), (R,), p["q_a_norm"]["weight"], eps=config.norm_eps)
    q = ttorch.permute(ttorch.reshape(ttorch.linear(c_q, q_w), (B, T, H, dr + dn)), (0, 2, 1, 3))
    kv_a = ttorch.linear(x, kv_a_w)                                          # (B, T, L + dr)
    c_kv = ttorch.rms_norm(kv_a[..., :L], (L,), p["kv_a_norm"]["weight"], eps=config.norm_eps)
    if config.mla_scale_kv_lora:  # on the activation: no power of two, so folded into a bf16 weight it would round otherwise
        c_kv = c_kv * (C / L) ** 0.5
    k_pe = ttorch.reshape(kv_a[..., L:], (B, 1, T, dr))                      # one rope key for all heads
    kv = ttorch.permute(ttorch.reshape(ttorch.linear(c_kv, p["kv_b_w"]), (B, T, H, dn + dv)), (0, 2, 1, 3))

    q = ttorch.apply_rope(q, cos, sin)                                       # the first dr of dr + dn
    k_pe = ttorch.apply_rope(k_pe, cos, sin)
    k = ttorch.cat([ttorch.expand(k_pe, (B, H, T, dr)), kv[..., :dn]], -1)
    y = ttorch.scaled_dot_product_attention(q, k, kv[..., dn:], is_causal=True, scale=config.softmax_scale)
    y = ttorch.reshape(ttorch.permute(y, (0, 2, 1, 3)), (B, T, H * dv))
    return ttorch.linear(y, p["proj_w"])


def _swiglu(x, p):
    h = ttorch.silu(ttorch.linear(x, p["fc_1_w"], p.get("fc_1_b"))) * ttorch.linear(
        x, p["fc_2_w"], p.get("fc_2_b")
    )
    return ttorch.linear(h, p["proj_w"], p.get("proj_b"))


def _moe_mlp(x, p, config: GPTConfig):
    """Mixtral-style MoE: top-k of the router's logits, softmax over the k
    chosen, SwiGLU experts, through the routed-expert operation (tokens
    grouped by expert, one grouped matmul a projection)."""
    B, T, C = x.shape
    xf = ttorch.reshape(x, (B * T, C))
    top_logits, top_i = ttorch.topk(ttorch.linear(xf, p["router_w"]), config.n_expert_per_token, -1)
    gate = ttorch.softmax(top_logits, -1)                     # renormalized over the k chosen
    # w1, w3 (E, H, C) and w2 (E, C, H) keep their checkpoint layout.
    out = ttorch.moe_experts(xf, top_i, gate.float(), ttorch.permute(p["w1"], (0, 2, 1)),
                             ttorch.permute(p["w3"], (0, 2, 1)), ttorch.permute(p["w2"], (0, 2, 1)), 0)
    return ttorch.reshape(out, (B, T, C))


def _shared_routed_moe(x, p, config: GPTConfig, counts=None):
    """``SwiGLU_shared(x) + sum_i w_i SwiGLU_i(x)`` over the chosen experts
    held here (``experts_held`` from ``expert_offset``): the router scores all
    its outputs, weighs all k chosen, and this chip adds its own experts' part.
    A chosen zero-compute expert (an output past ``n_expert``) weighs ``x``
    itself: that term is the token's own chip's to add, whole, as a shared
    expert's is. ``counts`` collects, a layer, the rows each held expert got
    (``"rows"``), where the router has a bias the (token, choice) pairs whose
    expert the same router without its bias does not choose (``"changed"``),
    and where it has zero-compute experts the pairs that chose one (``"zero"``)."""
    B, T, C = x.shape
    xf = ttorch.reshape(x, (B * T, C))

    def route(bias):
        return ttorch.moe_route(xf, p["router_w"], config.n_expert_per_token, config.n_expert_groups,
                                config.n_limited_groups, config.routed_scaling_factor, bias, config.router_norm_eps,
                                config.scoring_func, config.norm_topk_prob)

    with region("moe.route"):
        top_i, top_w = route(p.get("router_bias"))
    zero = top_i >= config.n_expert if config.zero_expert_num else None
    if counts is not None:
        held = ttorch.arange(config.expert_offset, config.expert_offset + config.held_experts,
                             device=x.device, dtype=top_i.dtype)
        counts["rows"].append(ttorch.sum((ttorch.unsqueeze(top_i, -1) == held).to(dtypes.int32), (0, 1)))
        if "router_bias" in p:
            kept = ttorch.unsqueeze(top_i, -1) == ttorch.unsqueeze(route(None)[0], 1)        # (N, k, k)
            counts["changed"].append(top_i.shape[0] * top_i.shape[1] - ttorch.sum(kept.to(dtypes.int32), (0, 1, 2)))
        if zero is not None:
            counts["zero"].append(ttorch.sum(zero.to(dtypes.int32), (0, 1)))
    with region("moe.experts"):
        # The router's output count, zero-compute experts among it, is what an even load is reckoned over.
        out = ttorch.moe_experts(xf, top_i, top_w, p["experts_gate"], p["experts_up"], p["experts_down"],
                                 config.expert_offset, p["router_w"].shape[0])
    if zero is not None:
        with region("moe.zero"):
            weight = ttorch.sum(ttorch.where(zero, top_w, 0.0), -1, True)                      # (N, 1) float32
            out = out + (xf.float() * weight).to(x.dtype)
    if config.n_shared_experts:
        with region("moe.shared"):
            out = out + _swiglu(xf, p["shared"])
    return ttorch.reshape(out, (B, T, C))


def _mlp(x, p, kind: str, config: GPTConfig, counts=None):
    if kind == "MoEMLP":
        return _moe_mlp(x, p, config)
    if kind == "SharedRoutedMoE":
        return _shared_routed_moe(x, p, config, counts)
    if kind == "LLaMAMLP":
        return _swiglu(x, p)
    h = ttorch.gelu(ttorch.linear(x, p["fc_w"], p.get("fc_b")))
    return ttorch.linear(h, p["proj_w"], p.get("proj_b"))


def _mix(x, p, cos, sin, config: GPTConfig, layer: int = 0, counts=None):
    """The layer's mixer, by the parameters it was given: the tree was built from
    ``layer_mixer(i)``. The two attention kinds of one head layout have the same
    parameters and are told apart by ``layer_mixer(layer)``."""
    if "conv" in p:
        return _short_conv(x, p["conv"], config)
    if "mamba" in p:
        return _mamba(x, p["mamba"], config)
    if "sparse_attn" in p:
        return _attention(x, p["sparse_attn"], cos, sin, config, sparse=True, counts=counts)
    if "linear_attn" in p:
        return _linear_attention(x, p["linear_attn"], cos, sin, config, layer)
    if config.attention_class == "MLA":
        with region("mla"):
            return _mla_attention(x, p["attn"], cos, sin, config)
    return _attention(x, p["attn"], cos, sin, config, window=config.layer_mixer(layer) == "sliding_attention")


def _shortcut_block(x, p, cos, sin, config: GPTConfig, counts=None, layer: int = 0):
    """LongCat-Flash's double layer: two sublayers of a mixer and a dense SwiGLU,
    and one routed layer that reads the first sublayer's normed output and joins
    the residual only after the second sublayer's FFN (in a deployment the
    experts' exchange runs behind the dense FFN and the second mixer):

        h1 = x + Mix_0(N(x));  m = N(h1);  s = Routed(m);  h2 = h1 + FFN_0(m)
        h3 = h2 + Mix_1(N(h2));  y = h3 + FFN_1(N(h3)) + s"""
    a, b = p["sub_0"], p["sub_1"]
    h1 = x + _mix(_norm(x, a["norm_1"], config), a, cos, sin, config, layer, counts)
    m = _norm(h1, a["norm_2"], config)
    shortcut = _shared_routed_moe(m, p["moe"], config, counts)
    h2 = h1 + _swiglu(m, a["mlp"])
    h3 = h2 + _mix(_norm(h2, b["norm_1"], config), b, cos, sin, config, layer, counts)
    return h3 + (_swiglu(_norm(h3, b["norm_2"], config), b["mlp"]) + shortcut)


def _block(x, p, cos, sin, kind: str, config: GPTConfig, counts=None, layer: int = 0):
    if kind == "ShortcutMoE":
        return _shortcut_block(x, p, cos, sin, config, counts, layer)
    scaled = (lambda y: y) if config.residual_scale == 1.0 else (lambda y: y * config.residual_scale)
    after = (lambda y, which: _norm(y, p[which], config)) if config.sandwich_norms else (lambda y, which: y)
    n1 = _norm(x, p["norm_1"], config)
    attn_out = scaled(after(_mix(n1, p, cos, sin, config, layer, counts), "post_attn_norm"))
    if config.parallel_residual:
        n2 = n1 if config.shared_attention_norm else _norm(x, p["norm_2"], config)
        return x + attn_out + scaled(after(_mlp(n2, p["mlp"], kind, config, counts), "post_mlp_norm"))
    x = x + attn_out
    return x + scaled(after(_mlp(_norm(x, p["norm_2"], config), p["mlp"], kind, config, counts), "post_mlp_norm"))


def _layers(params: dict, config: GPTConfig):
    """[(block's parameters, its MLP kind)] in layer order."""
    blocks = params["blocks"] if "blocks" in params else params["dense_blocks"] + params["moe_blocks"]
    return [(p, config.layer_mlp_class(i)) for i, p in enumerate(blocks)]


def _hidden(params: dict, idx, config: GPTConfig, counts=None, last: Optional[int] = None):
    B, T = idx.shape
    x = ttorch.embedding(idx, params["wte"])  # (B, T, C)
    if config.embedding_scale != 1.0:
        x = x * config.embedding_scale
    cos, sin = _rope_cache(T, config, device=x.device, dtype=x.dtype)
    for layer, (p, kind) in enumerate(_layers(params, config)):
        x = _block(x, p, cos, sin, kind, config, counts, layer)
    if last is not None:
        x = x[:, T - last:]
    x = _norm(x, params["ln_f"], config)
    return x if config.logit_divisor == 1.0 else x / config.logit_divisor


def forward(params: dict, idx, config: GPTConfig, last: Optional[int] = None):
    """Token ids (B, T) int → logits (B, T, padded_vocab_size); with ``last``
    the final norm and the head run on the last ``last`` positions only, what
    a prefill reads: (B, last, padded_vocab_size)."""
    return ttorch.linear(_hidden(params, idx, config, last=last),
                         params["wte" if config.tie_embeddings else "lm_head_w"])


def router_counts(params: dict, idx, config: GPTConfig):
    """What the routers of the expert layers do with these ids, by the program's
    own count: ``(rows (expert layers, experts held), changed (expert layers,)
    or None)`` and, for a router with zero-compute experts, ``zero (expert
    layers,)`` as a third. ``rows`` are the (token, choice) pairs sent to each
    expert held here, which is what its grouped matmuls compute; ``changed``,
    where the router has a bias, the pairs whose expert the router without it
    leaves out; ``zero`` the pairs that chose a zero-compute expert."""
    counts: dict = {"rows": [], "changed": [], "zero": []}
    _hidden(params, idx, config, counts)
    out = (ttorch.stack(counts["rows"], 0), ttorch.stack(counts["changed"], 0) if counts["changed"] else None)
    return out + (ttorch.stack(counts["zero"], 0),) if counts["zero"] else out


def sparse_selection_counts(params: dict, idx, config: GPTConfig):
    """What the sparse layers' selection does with these ids, by the program's own
    count: (sparse layers, B, key-value heads, T // SPARSE_TILE) int32, the distinct
    blocks the queries of each tile of ``SPARSE_TILE`` consecutive positions
    chose between them (forced blocks included; ``sparse_topk`` if they all agree)."""
    counts: dict = {"rows": [], "changed": [], "zero": [], "tile_union": []}
    _hidden(params, idx, config, counts)
    return ttorch.stack(counts["tile_union"], 0)


def routed_rows(params: dict, idx, config: GPTConfig):
    """``router_counts``'s rows."""
    return router_counts(params, idx, config)[0]


def loss_fn(params: dict, idx, targets, config: GPTConfig):
    """Next-token cross-entropy; logits in f32 for a stable softmax."""
    logits = forward(params, idx, config)
    B, T, V = logits.shape
    logits = ttorch.reshape(logits.float(), (B * T, V))
    return ttorch.cross_entropy(logits, ttorch.reshape(targets, (B * T,)))
