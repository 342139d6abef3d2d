"""Plain reference for LFM2-8B-A1B (``model_type`` ``lfm2_moe``;
huggingface.co/LiquidAI/LFM2-8B-A1B): gated short convolutions beside
grouped-query attention with normed heads, two leading dense SwiGLU layers,
then expert layers whose router chooses by sigmoid scores plus a bias and
weighs by the scores alone. Forward pass and loss in straightforward float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``; gradients by
``jax.grad`` of that. No kernel, no cache, no dispatch: every expert is applied
to every token and masked by the selection, the convolution is three shifted
products. Imports nothing from ``thunder_tpu``.

The equations, from the published ``config.json`` and the family's modelling
code (x is (B, T, hidden); every Linear is without bias; RMSNorm is
``w * x / sqrt(mean(x^2) + norm_eps)``):

* Layer i: ``h = x + mixer_i(RMSNorm_op(x))``; ``y = h + ffn_i(RMSNorm_ffn(h))``;
  after the last layer one RMSNorm, then the head.
* ``layer_types[i] == "conv"``: ``[B | C | u] = in_proj(x)`` (hidden to 3 hidden,
  split in that order); ``z = B * u``; ``c[t] = k[:, 0] z[t-2] + k[:, 1] z[t-1] +
  k[:, 2] z[t]`` with ``z[<0] = 0`` (``conv_L_cache`` 3: a depthwise ``Conv1d``
  of weight (hidden, 1, 3), left padding 2, cut to T); ``out_proj(C * c)``.
* ``"full_attention"``: q, k, v projections to ``num_attention_heads``,
  ``num_key_value_heads`` and again that many heads of ``hidden / heads``;
  ``q = RMSNorm_q(q)``, ``k = RMSNorm_k(k)`` over each head, one weight for all
  heads; rope on the whole head (rotate by halves, ``rope_theta``, no scaling);
  causal softmax attention, scale ``head**-0.5``, one key-value head for each
  ``heads / kv_heads`` query heads; ``out_proj``.
* ``i < num_dense_layers``: ``w2(silu(w1 x) * w3 x)`` at ``intermediate_size``.
* Otherwise ``s = sigmoid(gate(x))`` over ``num_experts``, float32;
  ``I = top_k(s + expert_bias)``; ``w = s[I]``; ``w = w / (sum(w) + 1e-6)``
  (``norm_topk_prob``); times ``routed_scaling_factor``;
  ``sum_{e in I} w_e * w2_e(silu(w1_e x) * w3_e x)`` at ``moe_intermediate_size``.

Departures from the published code, each where it is made:

* Weights arrive under the program's names and layouts, which is the checkpoint
  format and not mathematics: q, k and v as the rows of one ``qkv_w`` (q heads,
  then k, then v; a permutation of the rows of three random matrices); the
  dense MLP as ``fc_1_w`` (w1), ``fc_2_w`` (w3), ``proj_w`` (w2), each (out,
  in); the experts stacked as (expert, in, out); the filter as (hidden, 3)
  without the Conv1d's middle 1, oldest tap first.
* The head is the embedding table (``tie_embedding``: the family ties them; the
  catalog's row omits the key: ``assumed``).
* ``expert_bias`` is drawn, not learned: the published buffer is what training
  left there, and zeros would leave the mechanism idle (``assumed``).
* The model is cut in depth alone: the first ``num_hidden_layers`` of the
  published ``layer_types`` run.
* Layers are a Python loop (their kinds differ), each layer one compiled call
  on that layer's own weights when nothing outside compiles the whole (a
  program of all 14 layers wants every layer's slice of the stacked experts
  alive at once, 8.1 GB beside the 9.3 of the weights: my chip run, PR 31);
  attention runs a head at a time and the experts one at a time, so that one
  (T, T) score matrix and one expert's float32 weights are all that is alive
  beside the bf16 weights; the head is computed for the last ``last`` positions
  where that is asked. The arithmetic is unchanged.
"""

from __future__ import annotations

NORM_TOPK_EPS = 1e-6


def hyper(config: dict, matmul_inputs=None) -> dict:
    """What the equations need. ``matmul_inputs`` (a dtype name, default none)
    rounds both operands of every matmul to that type and back, accumulation
    staying float32: the same mathematics computed in a lower precision, for
    the reading that places the comparison's limits (``perfbench/
    checks_conv_moe.py``). The router and the taps stay float32."""
    depth = config["num_hidden_layers"]
    return {
        "heads": config["num_attention_heads"], "kv_heads": config["num_key_value_heads"],
        "eps": float(config["norm_eps"]), "rope_base": float(config["rope_theta"]), "taps": config["conv_L_cache"],
        "mixers": tuple(config["layer_types"][:depth]), "dense": config["num_dense_layers"],
        "top_k": config["num_experts_per_tok"], "routed_scale": float(config["routed_scaling_factor"]),
        "norm_topk": bool(config["norm_topk_prob"]), "use_bias": bool(config["use_expert_bias"]),
        "matmul_inputs": matmul_inputs,
    }


def _mm(spec: str, a, b, hp: dict):
    import jax.numpy as jnp

    if hp["matmul_inputs"] is not None:
        a, b = (t.astype(hp["matmul_inputs"]).astype(jnp.float32) for t in (a, b))
    return jnp.einsum(spec, a, b)


def _rms(x, scale, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * scale


def _rope(x, base: float):
    """x: (..., T, d), the whole head rotated by halves."""
    import jax.numpy as jnp

    t, d = x.shape[-2], x.shape[-1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * (base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))[None, :]
    cos, sin = (jnp.concatenate([f(ang), f(ang)], -1) for f in (jnp.cos, jnp.sin))
    return x * cos + jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1) * sin


def short_conv(bcu, taps):
    """``[B | C | u]`` (B, T, 3 hidden) and taps (hidden, K), oldest first ->
    ``C * conv(B * u)``: K shifted products, zeros before the sequence."""
    import jax.numpy as jnp

    b, c, u = jnp.split(bcu, 3, axis=-1)
    z, k, t = b * u, taps.shape[1], bcu.shape[1]
    shifted = lambda back: jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :t]  # z[t - back]
    return c * sum(taps[:, j] * shifted(k - 1 - j) for j in range(k))


def _conv_mixer(x, w, hp: dict):
    bcu = _mm("btc,oc->bto", x, w["conv/in_proj_w"], hp)
    return _mm("btc,oc->bto", short_conv(bcu, w["conv/conv_w"]), w["conv/out_proj_w"], hp)


def _attention(x, w, hp: dict):
    import jax
    import jax.numpy as jnp

    b, t, c = x.shape
    h, g = hp["heads"], hp["kv_heads"]
    d = c // h
    qkv = _mm("btc,oc->bto", x, w["attn/qkv_w"], hp)  # the packed layout: q heads, then k, then v
    heads = lambda a, n: a.reshape(b, t, n, d).transpose(2, 0, 1, 3)  # (n, B, T, d)
    q, k, v = heads(qkv[..., : h * d], h), heads(qkv[..., h * d:(h + g) * d], g), heads(qkv[..., (h + g) * d:], g)
    q = _rope(_rms(q, w["attn/q_norm/weight"], hp["eps"]), hp["rope_base"])
    k = _rope(_rms(k, w["attn/k_norm/weight"], hp["eps"]), hp["rope_base"])
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))

    def one_head(args):
        qh, kh, vh = args
        s = _mm("bqd,bkd->bqk", qh, kh, hp) * d ** -0.5
        return _mm("bqk,bkd->bqd", jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1), vh, hp)

    y = jax.lax.map(one_head, (q, jnp.repeat(k, h // g, 0), jnp.repeat(v, h // g, 0)))  # (H, B, T, d)
    return _mm("btv,cv->btc", y.transpose(1, 2, 0, 3).reshape(b, t, h * d), w["attn/proj_w"], hp)


def _swiglu(x, w, hp: dict):
    import jax

    h = jax.nn.silu(_mm("...c,hc->...h", x, w["mlp/fc_1_w"], hp)) * _mm("...c,hc->...h", x, w["mlp/fc_2_w"], hp)
    return _mm("...h,ch->...c", h, w["mlp/proj_w"], hp)


def route(x, router_w, bias, hp: dict):
    """x (N, hidden) -> (chosen (N, k) expert ids, weights (N, k), margin (N,)).
    The choice is by ``s + bias``, the weights are of ``s``. The margin is by
    how much of a biased score the choice was made: the last chosen over the
    best left out. A system that carries hidden states in a lower precision
    chooses otherwise where two scores lie closer than that rounding moves them
    (``perfbench/checks_conv_moe.py``)."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(x @ router_w.T)
    ranked, chosen = jax.lax.top_k(s + bias if hp["use_bias"] else s, hp["top_k"] + 1)
    margin, chosen = ranked[:, -2] - ranked[:, -1], chosen[:, :-1]
    w = jnp.take_along_axis(s, chosen, 1)
    if hp["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + NORM_TOPK_EPS)
    return chosen, w * hp["routed_scale"], margin


def _experts(x, w, hp: dict):
    """Every expert applied to every token and masked by the selection; and the
    router's margins (B, T). The experts' weights are converted to float32 one
    at a time."""
    import jax
    import jax.numpy as jnp

    b, t, c = x.shape
    xf = x.reshape(b * t, c)
    chosen, weight, margin = route(xf, w["mlp/router_w"].astype(jnp.float32), w["mlp/router_bias"], hp)

    def one_expert(out, per_expert):
        e, gate, up, down = per_expert  # (hidden, width), (hidden, width), (width, hidden)
        gate, up, down = (m.astype(jnp.float32) for m in (gate, up, down))
        w_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), -1)  # 0 where e is not chosen
        h = jax.nn.silu(_mm("nc,ch->nh", xf, gate, hp)) * _mm("nc,ch->nh", xf, up, hp)
        return out + w_e[:, None] * _mm("nh,hc->nc", h, down, hp), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(xf),
                          (jnp.arange(w["mlp/experts_gate"].shape[0]), w["mlp/experts_gate"], w["mlp/experts_up"],
                           w["mlp/experts_down"]))
    return out.reshape(b, t, c), margin.reshape(b, t)


def _block(x, w, hp: dict, mixer: str, dense: bool):
    import jax.numpy as jnp

    n1 = _rms(x, w["norm_1/weight"], hp["eps"])
    x = x + (_conv_mixer(n1, w, hp) if mixer == "conv" else _attention(n1, w, hp))
    n2 = _rms(x, w["norm_2/weight"], hp["eps"])
    out, margin = (_swiglu(n2, w, hp), jnp.full(x.shape[:2], jnp.inf)) if dense else _experts(n2, w, hp)
    return x + out, margin


def layer_weights(weights: dict, layer: int, dense_layers: int) -> dict:
    """Layer ``layer``'s leaves out of the stacked kinds (``perfbench/
    weights.py``): ``dense_blocks/*/..`` for the leading dense layers and
    ``moe_blocks/*/..`` for the rest, a kind's leading axis being the layer's
    place in its list. The experts stay as they are stored (``_experts``
    converts them one at a time); everything else is float32 here."""
    import jax.numpy as jnp

    prefix, at = ("dense_blocks/*/", layer) if layer < dense_layers else ("moe_blocks/*/", layer - dense_layers)
    out = {}
    for kind, stacked in weights.items():
        if kind.startswith(prefix) and at < stacked.shape[0]:
            leaf = stacked[at]
            out[kind[len(prefix):]] = leaf if "/experts_" in kind else leaf.astype(jnp.float32)
    return out


def forward_and_margin(weights: dict, idx, config: dict, matmul_inputs=None, last=None):
    """Token ids (B, T) -> (float32 logits (B, T, vocab), the least margin by
    which any expert layer's router made a position's choice (B, T); see
    ``route``). ``last``: the head, and the margins, for the last so many
    positions only. ``weights`` maps a leaf's kind to its array, per-layer
    kinds stacked on a leading layer axis."""
    import jax
    import jax.numpy as jnp

    hp = hyper(config, matmul_inputs)
    blocks: dict = {}  # one compiled function a kind of layer; under a trace of the whole they are inlined
    with jax.default_matmul_precision("highest"):
        table = weights["wte"].astype(jnp.float32)
        x = table[idx]
        margin = jnp.full(idx.shape, jnp.inf)
        for i, mixer in enumerate(hp["mixers"]):
            kind = (mixer, i < hp["dense"])
            if kind not in blocks:
                blocks[kind] = jax.jit(lambda x, w, kind=kind: _block(x, w, hp, *kind))
            x, m = blocks[kind](x, layer_weights(weights, i, hp["dense"]))
            margin = jnp.minimum(margin, m)
        if last is not None:
            x, margin = x[:, -last:], margin[:, -last:]
        x = _rms(x, weights["ln_f/weight"].astype(jnp.float32), hp["eps"])
        return _mm("btc,vc->btv", x, table, hp), margin  # the head is the embedding table


def forward(weights: dict, idx, config: dict, matmul_inputs=None, last=None):
    """Token ids (B, T) -> float32 logits (B, T, vocab)."""
    return forward_and_margin(weights, idx, config, matmul_inputs, last)[0]


def loss(weights: dict, idx, targets, config: dict):
    """Mean next-token cross-entropy over every position, float32."""
    import jax
    import jax.numpy as jnp

    logits = forward(weights, idx, config)
    picked = jnp.take_along_axis(logits, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)
