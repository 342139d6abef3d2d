"""Plain reference for Trinity-Mini (``model_type`` ``afmoe``;
huggingface.co/arcee-ai/Trinity-Mini): grouped-query attention with normed and
gated heads that attends within a window and is roped in three layers of four
and attends to everything without positions in the fourth, four norms a block,
two leading dense SwiGLU layers, then expert layers whose router chooses by
sigmoid scores plus a bias and weighs by the scores alone, beside a shared
expert. The forward pass in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. No kernel, no cache, no dispatch:
the mask is written ``0 <= i - j < W`` over every pair, and each expert is
applied, dense, to the rows that chose it. Imports nothing from ``thunder_tpu``.

The equations, from the published ``config.json`` and the family's modelling
code (``modeling_afmoe.py``; x is (B, T, hidden); every Linear is without bias;
RMSNorm is ``w * x / sqrt(mean(x^2) + rms_norm_eps)``):

* ``x0 = wte[ids] * sqrt(hidden_size)`` (``mup_enabled``).
* Layer l: ``h = x + N2(Attn(N1(x)))``; ``y = h + N4(MLP(N3(h)))``: four RMSNorms
  with weights of their own (``input_layernorm``, ``post_attention_layernorm``,
  ``pre_mlp_layernorm``, ``post_mlp_layernorm``). After the last layer one
  RMSNorm, then the head (``tie_word_embeddings`` false).
* ``Attn(n)``: q to ``num_attention_heads`` heads of ``head_dim``, k and v to
  ``num_key_value_heads``, a gate ``g = Wg n`` as wide as q; every query head
  and every key head through an RMSNorm over the head, one weight for all query
  heads and one for all key heads; scale ``head_dim**-0.5``; one key-value head
  for each ``heads / kv_heads`` query heads. ``layer_types[l] ==
  "sliding_attention"``: rope on q and k (``rope_theta``, the whole head, rotate
  by halves, no scaling) and query ``i`` sees the keys ``j`` with ``0 <= i - j <
  sliding_window``. ``"full_attention"``: no rope at all, query ``i`` sees every
  ``j <= i``. ``Wo (softmax(.) v * sigmoid(g))``, the gate elementwise.
* ``l < num_dense_layers``: ``w2(silu(w1 n) * w3 n)`` at ``intermediate_size``.
* Otherwise ``s = sigmoid(float32(Wr n))`` over ``num_experts``; ``I`` the
  ``num_experts_per_tok`` largest of ``s + b`` (``b`` a float32 buffer, no part
  of the weights; ``n_group`` = ``topk_group`` = 1: no groups); ``w = s[I] /
  (sum(s[I]) + 1e-20) * route_scale`` (``route_norm``); the shared expert's
  SwiGLU of ``num_shared_experts * moe_intermediate_size`` plus
  ``sum_{e in I} w_e SwiGLU_e(n)``, each expert ``moe_intermediate_size`` wide.

Departures from the published code, each where it is made:

* Weights arrive as the program's tree names and lays them out, which is the
  checkpoint format and not mathematics: ``{"wte", "ln_f/weight", "lm_head_w",
  "layers": [{leaf path: array} a layer]}``; q, k and v as the rows of one
  ``attn/qkv_w`` (q heads, then k, then v; a permutation of the rows of three
  random matrices) and the gate as ``attn/gate_w``; the four norms as
  ``norm_1``, ``post_attn_norm``, ``norm_2``, ``post_mlp_norm``; the dense and
  the shared MLP as ``fc_1_w`` (w1), ``fc_2_w`` (w3), ``proj_w`` (w2), each (out,
  in); the experts stacked as (expert, in, out).
* ``expert_bias`` is drawn, not learned (``assumed``): the published buffer is
  what training left there, and zeros would leave the mechanism idle.
* The model is cut in depth alone: the first ``num_hidden_layers`` of the
  published ``layer_types`` run.
* Layers are a Python loop, each layer's two halves compiled calls of their own
  on that layer's weights (the whole does not fit beside them); attention runs
  a head at a time and ``QUERY_BLOCK`` queries at a time against every key, so
  that one (QUERY_BLOCK, T) score matrix is alive; an expert's rows are brought
  first by a stable ``argsort`` of who chose it and taken up to the busiest
  expert's count (read from the router's own choice, a multiple of ``ROW_BLOCK``;
  a row beyond an expert's own has weight 0), the experts one at a time with their
  weights converted to float32 one at a time; the head is computed for the last
  ``last`` positions where that is asked. The arithmetic is unchanged.
"""

from __future__ import annotations

import functools

NORM_TOPK_EPS = 1e-20
QUERY_BLOCK = 1024
ROW_BLOCK = 1024


def hyper(config: dict, matmul_inputs=None) -> tuple:
    """What the equations need, hashable (a compiled half is kept by it).
    ``matmul_inputs`` (a dtype name, default none) rounds both operands of every
    matmul to that type and back, accumulation staying float32: the same
    mathematics computed in a lower precision, for the reading that places the
    comparison's limits (``perfbench/checks_window_moe.py``). The router stays float32."""
    return tuple(sorted({
        "heads": config["num_attention_heads"], "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"], "eps": float(config["rms_norm_eps"]),
        "rope_base": float(config["rope_theta"]), "window": config["sliding_window"],
        "top_k": config["num_experts_per_tok"], "route_scale": float(config["route_scale"]),
        "route_norm": bool(config["route_norm"]), "embed_scale": float(config["hidden_size"]) ** 0.5,
        "matmul_inputs": matmul_inputs,
    }.items()))


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


def _attention(n, w, hp: dict, sliding: bool):
    import jax
    import jax.numpy as jnp

    b, t, _ = n.shape
    h, g, d = hp["heads"], hp["kv_heads"], hp["head_dim"]
    qkv = _mm("btc,oc->bto", n, w["attn/qkv_w"], hp)  # the packed layout: q heads, then k, then v
    heads = lambda a, count: a.reshape(b, t, count, d).transpose(2, 0, 1, 3)  # (count, B, T, d)
    q, k, v = heads(qkv[..., : h * d], h), heads(qkv[..., h * d:(h + g) * d], g), heads(qkv[..., (h + g) * d:], g)
    q, k = _rms(q, w["attn/q_norm/weight"], hp["eps"]), _rms(k, w["attn/k_norm/weight"], hp["eps"])
    if sliding:
        q, k = _rope(q, hp["rope_base"]), _rope(k, hp["rope_base"])
    window = hp["window"] if sliding else t  # a global layer's query sees every key up to its own
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    starts = jnp.arange(0, t + pad, block)

    def one_head(args):
        qh, kh, vh = args  # (B, T, d)
        qh = jnp.pad(qh, ((0, 0), (0, pad), (0, 0)))

        def one_block(start):
            qb = jax.lax.dynamic_slice_in_dim(qh, start, block, 1)
            ahead = (start + jnp.arange(block))[:, None] - jnp.arange(t)[None, :]  # i - j
            s = _mm("bqd,bkd->bqk", qb, kh, hp) * d ** -0.5
            s = jnp.where((ahead >= 0) & (ahead < window), s, -jnp.inf)
            return _mm("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), vh, hp)

        out = jax.lax.map(one_block, starts)  # (blocks, B, block, d); a padded query's row is cut
        return out.transpose(1, 0, 2, 3).reshape(b, t + pad, d)[:, :t]

    y = jax.lax.map(one_head, (q, jnp.repeat(k, h // g, 0), jnp.repeat(v, h // g, 0)))  # (H, B, T, d)
    y = y.transpose(1, 2, 0, 3).reshape(b, t, h * d)
    y = y * jax.nn.sigmoid(_mm("btc,oc->bto", n, w["attn/gate_w"], hp))  # on the heads' output, before o_proj
    return _mm("btv,cv->btc", y, w["attn/proj_w"], hp)


def _swiglu(x, w, hp: dict, at: str):
    import jax

    h = jax.nn.silu(_mm("...c,hc->...h", x, w[at + "fc_1_w"], hp)) * _mm("...c,hc->...h", x, w[at + "fc_2_w"], hp)
    return _mm("...h,ch->...c", h, w[at + "proj_w"], hp)


def route(x, router_w, bias, hp: dict):
    """x (N, hidden) -> (chosen (N, k) expert ids, weights (N, k), margin (N,)).
    The choice is by ``s + bias``, the weights are of ``s``. The margin is by
    how much of a biased score the choice was made: the last chosen over the
    best left out. A system that carries hidden states in a lower precision
    chooses otherwise where two scores lie closer than that rounding moves them
    (``perfbench/checks_window_moe.py``)."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(x @ router_w.T)
    ranked, chosen = jax.lax.top_k(s + bias, hp["top_k"] + 1)
    margin, chosen = ranked[:, -2] - ranked[:, -1], chosen[:, :-1]
    w = jnp.take_along_axis(s, chosen, 1)
    if hp["route_norm"]:
        w = w / (w.sum(-1, keepdims=True) + NORM_TOPK_EPS)
    return chosen, w * hp["route_scale"], margin


@functools.lru_cache(maxsize=None)
def _halves(hp_items: tuple, sliding: bool, dense: bool):
    """The two halves of a layer as compiled calls (kept by what they depend on):
    ``h = x + N2(Attn(N1(x)))`` and, for a dense layer, ``h + N4(MLP(N3(h)))``;
    for an expert layer the router's part and the experts' part, between which
    the busiest expert's count is read."""
    import jax
    import jax.numpy as jnp

    hp = dict(hp_items)

    def mixed(x, w):
        return x + _rms(_attention(_rms(x, w["norm_1/weight"], hp["eps"]), w, hp, sliding), w["post_attn_norm/weight"],
                        hp["eps"])

    def dense_mlp(h, w):
        return h + _rms(_swiglu(_rms(h, w["norm_2/weight"], hp["eps"]), w, hp, "mlp/"), w["post_mlp_norm/weight"], hp["eps"])

    def routed(h, w):
        n = _rms(h, w["norm_2/weight"], hp["eps"]).reshape(-1, h.shape[-1])
        chosen, weight, margin = route(n, w["mlp/router_w"], w["mlp/router_bias"], hp)
        busiest = jnp.max(jnp.sum(chosen[..., None] == jnp.arange(w["mlp/router_w"].shape[0]), (0, 1)))
        return n, chosen, weight, margin.reshape(h.shape[:2]), busiest

    def experts(h, n, chosen, weight, w, rows: int):
        """Each expert, dense, over the rows that chose it: at most ``rows`` of them."""

        def one_expert(out, per_expert):
            e, gate, up, down = per_expert  # (hidden, width), (hidden, width), (width, hidden)
            gate, up, down = (m.astype(jnp.float32) for m in (gate, up, down))
            chose = jnp.any(chosen == e, -1)
            w_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), -1)  # 0 where e is not chosen
            its = jnp.argsort(~chose, stable=True)[:rows]  # the rows that chose e, in order; then rows that did not, at weight 0
            x_e = n[its]
            y_e = _mm("nh,hc->nc", jax.nn.silu(_mm("nc,ch->nh", x_e, gate, hp)) * _mm("nc,ch->nh", x_e, up, hp), down, hp)
            return out.at[its].add(w_e[its][:, None] * y_e), None

        held = w["mlp/experts_gate"].shape[0]
        out, _ = jax.lax.scan(one_expert, _swiglu(n, w, hp, "mlp/shared/"),
                              (jnp.arange(held), w["mlp/experts_gate"], w["mlp/experts_up"], w["mlp/experts_down"]))
        return h + _rms(out.reshape(h.shape), w["post_mlp_norm/weight"], hp["eps"])

    return (jax.jit(mixed), jax.jit(dense_mlp)) if dense else (jax.jit(mixed), jax.jit(routed),
                                                               jax.jit(experts, static_argnames="rows"))


def _float32(layer: dict) -> dict:
    """A layer's leaves as float32, but the experts, which ``experts`` converts one at a time."""
    import jax.numpy as jnp

    return {path: leaf if "/experts_" in path else leaf.astype(jnp.float32) for path, leaf in layer.items()}


def forward_and_margin(weights: dict, idx, config: dict, matmul_inputs=None, last=None):
    """Token ids (B, T) -> (float32 logits (B, T, vocab), the least margin by
    which any expert layer's router made a position's choice (B, T); see
    ``route``). ``last``: the head, and the margins, for the last so many
    positions only."""
    import jax
    import jax.numpy as jnp

    hp_items = hyper(config, matmul_inputs)
    hp = dict(hp_items)
    depth = config["num_hidden_layers"]
    with jax.default_matmul_precision("highest"):
        x = weights["wte"][idx].astype(jnp.float32) * hp["embed_scale"]
        margin = jnp.full(idx.shape, jnp.inf)
        for i, kind in enumerate(config["layer_types"][:depth]):
            w = _float32(weights["layers"][i])
            halves = _halves(hp_items, kind == "sliding_attention", i < config["num_dense_layers"])
            h = halves[0](x, w)
            if len(halves) == 2:
                x = halves[1](h, w)
                continue
            n, chosen, weight, m, busiest = halves[1](h, w)
            rows = min(-(-int(busiest) // ROW_BLOCK) * ROW_BLOCK, n.shape[0])
            x = halves[2](h, n, chosen, weight, w, rows=rows)
            margin = jnp.minimum(margin, m)
        if last is not None:
            x, margin = x[:, -last:], margin[:, -last:]
        x = _rms(x, weights["ln_f/weight"].astype(jnp.float32), hp["eps"])
        return _mm("btc,vc->btv", x, weights["lm_head_w"].astype(jnp.float32), hp), margin


def forward(weights: dict, idx, config: dict, matmul_inputs=None, last=None):
    """Token ids (B, T) -> float32 logits (B, T, vocab)."""
    return forward_and_margin(weights, idx, config, matmul_inputs, last)[0]
