"""Plain reference for A.X-K1 (``model_type`` ``axk1``; huggingface.co/skt/A.X-K1),
the DeepSeek-V3 family's block: latent attention (MLA) under YaRN, one leading
dense SwiGLU layer, then expert layers with a shared expert beside routed ones
chosen by sigmoid scores and a group-limited top-k. Forward pass and loss in
straightforward float32 ``jax.numpy`` under ``jax.default_matmul_precision(
"highest")``; gradients by ``jax.grad`` of that. No kernel, no cache, no
dispatch: every held expert is applied to every token and masked by the
selection. Imports nothing from ``thunder_tpu``.

The equations, from the published ``config.json`` and the family's modelling
code (x is (B, T, hidden); RMSNorm eps ``rms_norm_eps``, pre-norm, sequential
residual, no bias, untied head):

* Attention. ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` as heads of
  ``[q_nope (qk_nope_head_dim), q_pe (qk_rope_head_dim)]``.
  ``[c_kv, k_pe] = x W_kva``; ``c_kv = RMSNorm(c_kv)``; ``c_kv W_kvb`` as heads
  of ``[k_nope, v (v_head_dim)]``. Rope on ``q_pe`` and on the one ``k_pe`` all
  heads share; the published pairs are neighbours ``(x0,x1),(x2,x3)..``, which
  the family's code de-interleaves and then rotates by halves. ``k = [k_nope,
  k_pe]``. Causal softmax of ``q k^T s``, ``s = (d_nope + d_rope)**-0.5 * m**2``
  with ``m = 0.1 * mscale_all_dim * ln(factor) + 1``. YaRN: inverse frequencies
  blended between ``base**(-2i/d)`` and that over ``factor`` by a linear ramp
  between the correction dims of ``beta_fast`` and ``beta_slow`` rotations over
  ``original_max_position_embeddings``; the tables are scaled by ``mscale(
  factor, mscale) / mscale(factor, mscale_all_dim)``, which is 1 as published.
* Dense layer (the first ``first_k_dense_replace``): SwiGLU at
  ``intermediate_size``.
* Expert layer. ``s = sigmoid(x W_g^T)`` over all published experts, float32.
  Experts lie in ``n_group`` groups; a group's score is the sum of its two
  best; the best ``topk_group`` groups stay, the others are masked to 0, the
  top ``num_experts_per_tok`` of what is left are chosen. ``w = s[chosen] /
  (sum s[chosen] + 1e-20) * routed_scaling_factor``. Output ``SwiGLU_shared(x)
  + sum_i w_i SwiGLU_i(x)``.

Departures, each noted where it is made:

* The share. ``n_routed_experts`` of the configuration is what this chip holds,
  experts ``expert_offset`` onwards of ``n_routed_experts_published``: the
  router keeps its published width and its groups, ``w`` is normalised over
  all chosen, and the sum runs over the chosen experts held here. That partial
  result goes on to the next layer, as in the program (``model-configs`` guide,
  section 4). With all experts held it is the uncut layer.
* ``topk_method`` reads ``"none"`` in the published file; the selection is the
  family's group-limited top-k without a correction bias (``assumed``).
* Weights arrive under the program's names and layouts, which is the
  checkpoint format and not mathematics: routed experts stacked as (expert,
  in, out); the shared expert and the dense layer as ``fc_1_w`` (gate),
  ``fc_2_w`` (up), ``proj_w`` (down), each (out, in).
* Expert layers are a ``lax.scan`` over stacked weights, attention runs a head
  at a time and the held experts one at a time, so that one (T, T) score
  matrix and one expert's activations are all that is alive beside the
  weights. The arithmetic is unchanged.
"""

from __future__ import annotations

import math


def hyper(config: dict, matmul_inputs=None) -> dict:
    """What the equations need. ``matmul_inputs`` (a dtype name, default none)
    rounds both operands of every matmul to that type and back, accumulation
    staying float32: the same mathematics computed in a lower precision, for
    the reading that places the comparison's tolerance (``perfbench/
    checks_mla_moe.py``). The router stays float32, as published."""
    rs = config["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0 if rs["factor"] > 1 else 1.0
    table = (0.1 * rs["mscale"] * math.log(rs["factor"]) + 1.0 if rs["factor"] > 1 else 1.0) / m
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    return {
        "heads": config["num_attention_heads"], "dn": dn, "dr": dr, "dv": config["v_head_dim"],
        "kv_rank": config["kv_lora_rank"], "eps": float(config["rms_norm_eps"]),
        "scale": (dn + dr) ** -0.5 * m * m, "table": table, "rope_base": float(config["rope_theta"]),
        "yarn": rs, "top_k": config["num_experts_per_tok"], "groups": config["n_group"],
        "kept_groups": config["topk_group"], "routed_scale": float(config["routed_scaling_factor"]),
        "norm_topk": bool(config["norm_topk_prob"]), "experts": config["n_routed_experts_published"],
        "held": config["n_routed_experts"], "offset": config.get("expert_offset", 0),
        "shared": config["n_shared_experts"], "matmul_inputs": matmul_inputs,
    }


def _mm(spec: str, a, b, hp: dict):
    import jax.numpy as jnp

    if hp["matmul_inputs"] is not None:
        a, b = (t.astype(hp["matmul_inputs"]).astype(jnp.float32) for t in (a, b))
    return jnp.einsum(spec, a, b)


def _rms(x, scale, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * scale


def yarn_inverse_frequencies(hp: dict):
    import jax.numpy as jnp

    rs, d, base = hp["yarn"], hp["dr"], hp["rope_base"]
    extra = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if rs["factor"] <= 1:
        return extra

    def correction_dim(rotations):
        return d * math.log(rs["original_max_position_embeddings"] / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extra / rs["factor"] * ramp + extra * (1.0 - ramp)


def _rope(x, hp: dict):
    """x: (..., T, dr) with the published neighbouring pairs: de-interleave,
    then rotate by halves."""
    import jax.numpy as jnp

    t, d = x.shape[-2], x.shape[-1]
    x = x.reshape(x.shape[:-1] + (d // 2, 2)).swapaxes(-1, -2).reshape(x.shape)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * yarn_inverse_frequencies(hp)[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1) * hp["table"]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1) * hp["table"]
    half = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + half * sin


def _attention(x, w, hp: dict):
    import jax
    import jax.numpy as jnp

    b, t, _ = x.shape
    h, dn, dr, dv, r = hp["heads"], hp["dn"], hp["dr"], hp["dv"], hp["kv_rank"]
    c_q = _rms(_mm("btc,rc->btr", x, w["attn/q_a_w"], hp), w["attn/q_a_norm/weight"], hp["eps"])
    q = _mm("btr,or->bto", c_q, w["attn/q_b_w"], hp).reshape(b, t, h, dn + dr).transpose(0, 2, 1, 3)
    kv_a = _mm("btc,rc->btr", x, w["attn/kv_a_w"], hp)
    c_kv = _rms(kv_a[..., :r], w["attn/kv_a_norm/weight"], hp["eps"])
    k_pe = _rope(kv_a[..., r:], hp)                                        # (B, T, dr), shared by the heads
    kv = _mm("btr,or->bto", c_kv, w["attn/kv_b_w"], hp).reshape(b, t, h, dn + dv).transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], hp)], -1)
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))

    def one_head(args):
        qh, kvh = args  # (B, T, dn + dr), (B, T, dn + dv)
        kh = jnp.concatenate([kvh[..., :dn], k_pe], -1)
        s = _mm("bqd,bkd->bqk", qh, kh, hp) * hp["scale"]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return _mm("bqk,bkd->bqd", p, kvh[..., dn:], hp)

    y = jax.lax.map(one_head, (q.transpose(1, 0, 2, 3), kv.transpose(1, 0, 2, 3)))  # (H, B, T, dv)
    return _mm("btv,cv->btc", y.transpose(1, 2, 0, 3).reshape(b, t, h * dv), w["attn/proj_w"], hp)


def _swiglu(x, w, prefix: str, hp: dict):
    import jax

    h = jax.nn.silu(_mm("...c,hc->...h", x, w[prefix + "fc_1_w"], hp)) * _mm("...c,hc->...h", x, w[prefix + "fc_2_w"], hp)
    return _mm("...h,ch->...c", h, w[prefix + "proj_w"], hp)


def route(x, router_w, hp: dict):
    """x (N, hidden) -> (chosen (N, k) expert ids, weights (N, k), margin (N,)).
    The margin is by how much of a score the choice was made as far as the
    experts held here go: the least distance of a held expert's score from the
    cut it would have to cross to be chosen or to be left out, or of its group's
    score from the cut between the kept groups and the others, whichever is
    smaller. A system that carries hidden states in a lower precision chooses
    otherwise where scores lie closer to a cut than that rounding moves them;
    what it chooses among the experts held elsewhere changes this chip's part
    only through the weights' normaliser (``perfbench/checks_mla_moe.py``)."""
    import jax
    import jax.numpy as jnp

    def to_the_cut(scores, ranked, mine):
        """ranked: the scores in falling order, one more than are taken; mine:
        the columns to measure. A taken one's distance to the best left out, a
        left one's to the last taken."""
        last_taken, best_left = ranked[:, -2, None], ranked[:, -1, None]
        own = scores[:, jnp.asarray(mine)]
        return jnp.where(own >= last_taken, own - best_left, last_taken - own).min(-1)

    n = x.shape[0]
    held = list(range(hp["offset"], hp["offset"] + hp["held"]))
    s = jax.nn.sigmoid(x @ router_w.T)
    choose_from, margin = s, jnp.full((n,), jnp.inf)
    if hp["groups"] > 1:
        grouped = s.reshape(n, hp["groups"], -1)
        group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)
        ranked, kept = jax.lax.top_k(group_score, min(hp["kept_groups"] + 1, hp["groups"]))
        if hp["kept_groups"] < hp["groups"]:
            their_groups = sorted({e // grouped.shape[-1] for e in held})
            margin, kept = to_the_cut(group_score, ranked, their_groups), kept[:, :-1]
        keep = jnp.zeros((n, hp["groups"]), bool).at[jnp.arange(n)[:, None], kept].set(True)
        choose_from = jnp.where(keep[:, :, None], grouped, 0.0).reshape(n, -1)
    ranked, chosen = jax.lax.top_k(choose_from, hp["top_k"] + 1)
    margin, chosen = jnp.minimum(margin, to_the_cut(choose_from, ranked, held)), chosen[:, :-1]
    w = jnp.take_along_axis(s, chosen, 1)
    if hp["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return chosen, w * hp["routed_scale"], margin


def _experts(x, w, hp: dict):
    """The shared expert and the routed experts held here, each applied to
    every token and masked by the selection; and the router's margins (B, T)."""
    import jax
    import jax.numpy as jnp

    b, t, c = x.shape
    xf = x.reshape(b * t, c)
    chosen, weight, margin = route(xf, w["mlp/router_w"], hp)

    def one_expert(out, per_expert):
        e, gate, up, down = per_expert  # (hidden, width), (hidden, width), (width, hidden)
        w_e = jnp.sum(jnp.where(chosen == e + hp["offset"], weight, 0.0), -1)  # 0 where e is not chosen
        h = jax.nn.silu(_mm("nc,ch->nh", xf, gate, hp)) * _mm("nc,ch->nh", xf, up, hp)
        return out + w_e[:, None] * _mm("nh,hc->nc", h, down, hp), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(xf),
                          (jnp.arange(hp["held"]), w["mlp/experts_gate"], w["mlp/experts_up"],
                           w["mlp/experts_down"]))
    if hp["shared"]:
        out = out + _swiglu(xf, w, "mlp/shared/", hp)
    return out.reshape(b, t, c), margin.reshape(b, t)


def _block(x, w, hp: dict, dense: bool):
    import jax.numpy as jnp

    x = x + _attention(_rms(x, w["norm_1/weight"], hp["eps"]), w, hp)
    n2 = _rms(x, w["norm_2/weight"], hp["eps"])
    out, margin = (_swiglu(n2, w, "mlp/", hp), jnp.full(x.shape[:2], jnp.inf)) if dense else _experts(n2, w, hp)
    return x + out, margin


def forward_and_margin(weights: dict, idx, config: dict, matmul_inputs=None):
    """Token ids (B, T) -> (float32 logits (B, T, vocab), the least margin by
    which any expert layer's router made a position's choice (B, T); see
    ``route``). ``weights`` maps a leaf's kind to its array, per-layer kinds
    stacked on a leading layer axis (``perfbench/weights.py``):
    ``dense_blocks/*/..`` and ``moe_blocks/*/..``."""
    import jax
    import jax.numpy as jnp

    hp = hyper(config, matmul_inputs)

    def scan_blocks(x, margin, prefix: str, dense: bool):
        kinds = sorted(k for k in weights if k.startswith(prefix))
        if not kinds:
            return x, margin

        @jax.checkpoint
        def layer(x, ws):
            return _block(x, {k[len(prefix):]: ws[k].astype(jnp.float32) for k in kinds}, hp, dense)

        x, margins = jax.lax.scan(layer, x, {k: weights[k] for k in kinds})
        return x, jnp.minimum(margin, margins.min(0))

    with jax.default_matmul_precision("highest"):
        x = weights["wte"].astype(jnp.float32)[idx]
        x, margin = scan_blocks(x, jnp.full(idx.shape, jnp.inf), "dense_blocks/*/", True)
        x, margin = scan_blocks(x, margin, "moe_blocks/*/", False)
        x = _rms(x, weights["ln_f/weight"].astype(jnp.float32), hp["eps"])
        return _mm("btc,vc->btv", x, weights["lm_head_w"].astype(jnp.float32), hp), margin


def forward(weights: dict, idx, config: dict, matmul_inputs=None):
    """Token ids (B, T) -> float32 logits (B, T, vocab)."""
    return forward_and_margin(weights, idx, config, matmul_inputs)[0]


def loss(weights: dict, idx, targets, config: dict):
    """Mean next-token cross-entropy over every position, float32."""
    import jax
    import jax.numpy as jnp

    logits = forward(weights, idx, config)
    picked = jnp.take_along_axis(logits, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def expert_layer(x, w: dict, config: dict):
    """One expert layer's MLP on x (B, T, hidden) with unstacked float32
    weights ``w`` (``mlp/router_w``, ``mlp/experts_*``, ``mlp/shared/*``):
    what the share test sums over the shares."""
    import jax

    with jax.default_matmul_precision("highest"):
        return _experts(x, w, hyper(config))[0]
