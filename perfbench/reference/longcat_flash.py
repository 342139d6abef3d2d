"""Plain reference for LongCat-Flash-Omni's language model
(huggingface.co/meituan-longcat/LongCat-Flash-Omni, Meituan's 560B-A27B; the
audio and vision encoders and the codec decoder are no language-model layers
and are not here): double layers of two latent-attention sublayers and two
dense FFNs with one routed layer on a shortcut across them, a softmax router
over real and zero-compute experts. The forward pass in straightforward float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``. No kernel, no
cache, no dispatch: every held expert is applied to every token and masked by
the choice. Imports nothing from ``thunder_tpu``.

The equations, from the published ``config.json`` and the family's modelling
code (x is (N, hidden); every Linear is without bias; RMSNorm is ``w * x /
sqrt(mean(x^2) + rms_norm_eps)``; the embedding is not scaled, one RMSNorm
after the last layer, an untied head):

* One of the ``num_layers`` layers, four norms and **one** routed layer::

      h1 = x  + MLA_0(N_in0(x))
      m  = N_post0(h1)
      s  = Routed(m)                     # the shortcut: from the first sublayer's normed output ...
      h2 = h1 + FFN_0(m)                 # dense SwiGLU, ffn_hidden_size wide
      h3 = h2 + MLA_1(N_in1(h2))
      y  = h3 + FFN_1(N_post1(h3)) + s   # ... joined to the residual only here

* ``MLA(n)``: ``c_q = RMSNorm(n W_qa)``; ``q = (c_q W_qb) * sqrt(hidden /
  q_lora_rank)`` (``mla_scale_q_lora``) as heads of ``[q_nope (qk_nope_head_dim),
  q_pe (qk_rope_head_dim)]``. ``[c_kv, k_pe] = n W_kva``; ``c_kv = RMSNorm(c_kv)
  * sqrt(hidden / kv_lora_rank)`` (``mla_scale_kv_lora``: the no-rope keys and
  the values carry it, the shared rope key does not); ``c_kv W_kvb`` as heads of
  ``[k_nope, v (v_head_dim)]``. Rope (``rope_theta``, no scaling) on ``q_pe`` and
  on the one ``k_pe`` all heads share, neighbouring pairs ``(x0,x1),(x2,x3)..``,
  de-interleaved and rotated by halves as the family's code does. Causal softmax
  of ``q [k_nope, k_pe]^T (d_nope + d_rope)**-0.5``; ``W_o`` on the heads' values.
* ``Routed(m)``: logits ``float32(m) float32(W)^T``, W (``n_routed_experts`` +
  ``zero_expert_num``, hidden); ``p = softmax`` over **all** outputs; the choice
  is ``top_k(p + b)`` (``moe_topk``; ``b`` a float32 buffer, no part of the
  weights); the weights ``routed_scaling_factor * p_e`` of the chosen, **not
  renormalised**; ``sum over chosen real e of w_e SwiGLU_e(m)``
  (``expert_ffn_hidden_size`` wide) ``+ (sum over chosen zero-compute e of w_e) m``
  (``zero_expert_type`` identity). No shared expert.

Departures, each where it is made:

* The share. ``n_routed_experts`` of the configuration is what this chip holds,
  experts ``expert_offset`` onwards of ``n_routed_experts_published``: the
  router keeps every output, the sum over real experts runs over the chosen ones
  held here, and the zero-compute term, which needs no weight, is whole here, as
  on every chip that holds the token. That partial result goes on to the next
  layer, as in the program. With all experts held it is the uncut layer.
* ``b`` is drawn, not learned (``assumed``).
* Weights arrive as the program's tree names and lays them out, which is the
  checkpoint format and not mathematics: ``{"wte", "ln_f/weight", "lm_head_w",
  "layers": an iterable of {leaf path: array}, a layer}`` (the job hands out a
  generator that draws a layer when it is asked for, so that the check never
  holds the model beside the float32 copies), a layer's two sublayers under
  ``sub_0/`` and ``sub_1/`` (``norm_1`` the input norm, ``attn/*`` the latent
  attention, ``norm_2`` the post-attention norm, ``mlp/fc_1_w`` gate, ``fc_2_w``
  up, ``proj_w`` down, each (out, in)), the routed layer under ``moe/`` with its
  experts stacked as (expert, in, out). The FFN's leaves here are
  ``mlp/fc_1_w``.. under the sublayer's prefix, which ``_swiglu`` is given without it.
* A layer is five compiled pieces on that layer's weights (the whole does not
  fit beside them): each attention, each FFN, the routed layer; attention runs
  a head at a time and ``QUERY_BLOCK`` queries at a time against every key, the
  held experts one at a time. The arithmetic is unchanged.
"""

from __future__ import annotations

import functools
import itertools

QUERY_BLOCK = 1024


def hyper(config: dict, matmul_inputs=None) -> tuple:
    """What the equations need, hashable (a compiled piece is kept by it).
    ``matmul_inputs`` (a dtype name, default none) rounds both operands of every
    matmul to that type and back, accumulation staying float32: the same
    mathematics computed in a lower precision, for the reading that places the
    comparison's limits (``perfbench/checks_scmoe.py``). The router stays float32."""
    c = config["hidden_size"]
    return tuple(sorted({
        "heads": config["num_attention_heads"], "dn": config["qk_nope_head_dim"], "dr": config["qk_rope_head_dim"],
        "dv": config["v_head_dim"], "kv_rank": config["kv_lora_rank"], "eps": float(config["rms_norm_eps"]),
        "rope_base": float(config["rope_theta"]),
        "q_scale": (c / config["q_lora_rank"]) ** 0.5 if config["mla_scale_q_lora"] else 1.0,
        "kv_scale": (c / config["kv_lora_rank"]) ** 0.5 if config["mla_scale_kv_lora"] else 1.0,
        "top_k": config["moe_topk"], "routed_scale": float(config["routed_scaling_factor"]),
        "experts": config["n_routed_experts_published"], "zero_experts": config["zero_expert_num"],
        "held": config["n_routed_experts"], "offset": config.get("expert_offset", 0),
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
    """x: (..., T, d) with the published neighbouring pairs: de-interleave, then rotate by halves."""
    import jax.numpy as jnp

    t, d = x.shape[-2], x.shape[-1]
    x = x.reshape(x.shape[:-1] + (d // 2, 2)).swapaxes(-1, -2).reshape(x.shape)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * (base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))[None, :]
    cos, sin = (jnp.concatenate([f(ang), f(ang)], -1) for f in (jnp.cos, jnp.sin))
    return x * cos + jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1) * sin


def _attention(n, w, hp: dict):
    """n (B, T, hidden), the sublayer's normed input -> (B, T, hidden)."""
    import jax
    import jax.numpy as jnp

    b, t, _ = n.shape
    h, dn, dr, dv, r = hp["heads"], hp["dn"], hp["dr"], hp["dv"], hp["kv_rank"]
    c_q = _rms(_mm("btc,rc->btr", n, w["attn/q_a_w"], hp), w["attn/q_a_norm/weight"], hp["eps"])
    q = _mm("btr,or->bto", c_q, w["attn/q_b_w"], hp) * hp["q_scale"]          # both parts of every head
    q = q.reshape(b, t, h, dn + dr).transpose(2, 0, 1, 3)                     # (H, B, T, dn + dr)
    kv_a = _mm("btc,rc->btr", n, w["attn/kv_a_w"], hp)
    c_kv = _rms(kv_a[..., :r], w["attn/kv_a_norm/weight"], hp["eps"]) * hp["kv_scale"]
    k_pe = _rope(kv_a[..., r:], hp["rope_base"])                              # (B, T, dr), shared by the heads, unscaled
    kv = _mm("btr,or->bto", c_kv, w["attn/kv_b_w"], hp).reshape(b, t, h, dn + dv).transpose(2, 0, 1, 3)
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    starts = jnp.arange(0, t + pad, block)
    scale = (dn + dr) ** -0.5

    def one_head(args):
        qh, kvh = args  # (B, T, dn + dr), (B, T, dn + dv)
        qh = jnp.pad(jnp.concatenate([qh[..., :dn], _rope(qh[..., dn:], hp["rope_base"])], -1), ((0, 0), (0, pad), (0, 0)))
        kh = jnp.concatenate([kvh[..., :dn], k_pe], -1)

        def one_block(start):
            qb = jax.lax.dynamic_slice_in_dim(qh, start, block, 1)
            ahead = (start + jnp.arange(block))[:, None] - jnp.arange(t)[None, :]  # i - j
            s = jnp.where(ahead >= 0, _mm("bqd,bkd->bqk", qb, kh, hp) * scale, -jnp.inf)
            return _mm("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), kvh[..., dn:], hp)

        out = jax.lax.map(one_block, starts)  # (blocks, B, block, dv); a padded query's row is cut
        return out.transpose(1, 0, 2, 3).reshape(b, t + pad, dv)[:, :t]

    y = jax.lax.map(one_head, (q, kv))  # (H, B, T, dv)
    return _mm("btv,cv->btc", y.transpose(1, 2, 0, 3).reshape(b, t, h * dv), w["attn/proj_w"], hp)


def _swiglu(x, w, hp: dict):
    import jax

    h = jax.nn.silu(_mm("...c,hc->...h", x, w["fc_1_w"], hp)) * _mm("...c,hc->...h", x, w["fc_2_w"], hp)
    return _mm("...h,ch->...c", h, w["proj_w"], hp)


def route(m, router_w, bias, hp: dict):
    """m (N, hidden) -> (chosen (N, k) output ids, weights (N, k), margins (N, 2)).
    The scores are a softmax over every output, the choice is by ``p + bias``,
    the weights are ``routed_scale * p`` of the chosen as they are. The margins
    say by how much of a biased score the choice was made as far as the terms
    this chip computes go: the least distance of such an output's biased score
    from the cut it would have to cross (to the best left out if it is chosen,
    to the last chosen if it is not), first over the experts held here, then over
    the zero-compute outputs. A system that carries hidden states in a lower
    precision chooses otherwise where scores lie closer to the cut than that
    rounding moves them. The weights are not normalised, so a choice that differs
    among the experts held elsewhere moves nothing here; one that takes or leaves
    a held expert moves the row by an expert's term, one that takes or leaves a
    zero-compute output by ``routed_scale * p`` of the router's own input
    (``perfbench/checks_scmoe.py``)."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.softmax(m @ router_w.T, axis=-1)
    biased = p + bias
    ranked, chosen = jax.lax.top_k(biased, hp["top_k"] + 1)
    last_taken, best_left, chosen = ranked[:, -2, None], ranked[:, -1, None], chosen[:, :-1]
    to_the_cut = lambda own: jnp.where(own >= last_taken, own - best_left, last_taken - own).min(-1)
    margins = jnp.stack([to_the_cut(biased[:, hp["offset"]:hp["offset"] + hp["held"]]), to_the_cut(biased[:, hp["experts"]:])], -1)
    return chosen, jnp.take_along_axis(p, chosen, 1) * hp["routed_scale"], margins


def _routed(m, w, hp: dict):
    """m (B, T, hidden) -> (the routed layer's part of this chip (B, T, hidden), margins (B, T, 2))."""
    import jax
    import jax.numpy as jnp

    b, t, c = m.shape
    mf = m.reshape(b * t, c)
    chosen, weight, margin = route(mf, w["router_w"], w["router_bias"], hp)
    # A chosen zero-compute expert returns its input: its weight times m, on the token's own chip.
    out = jnp.sum(jnp.where(chosen >= hp["experts"], weight, 0.0), -1, keepdims=True) * mf

    def one_expert(out, per_expert):
        e, gate, up, down = per_expert  # (hidden, width), (hidden, width), (width, hidden)
        gate, up, down = (a.astype(jnp.float32) for a in (gate, up, down))
        w_e = jnp.sum(jnp.where(chosen == e + hp["offset"], weight, 0.0), -1)  # 0 where e is not chosen
        h = jax.nn.silu(_mm("nc,ch->nh", mf, gate, hp)) * _mm("nc,ch->nh", mf, up, hp)
        return out + w_e[:, None] * _mm("nh,hc->nc", h, down, hp), None

    out, _ = jax.lax.scan(one_expert, out, (jnp.arange(hp["held"]), w["experts_gate"], w["experts_up"], w["experts_down"]))
    return out.reshape(b, t, c), margin.reshape(b, t, 2)


@functools.lru_cache(maxsize=None)
def _pieces(hp_items: tuple):
    """A layer's compiled pieces (kept by what they depend on), each on the
    leaves of its own part of the layer: ``mixed(x, sub) = (h, m)`` with ``h = x +
    MLA(N_in(x))`` and ``m = N_post(h)``; ``ffn(h, m, sub) = h + FFN(m)``;
    ``routed(m, moe) = (Routed(m), margins)``."""
    import jax

    hp = dict(hp_items)

    def mixed(x, w):
        h = x + _attention(_rms(x, w["norm_1/weight"], hp["eps"]), w, hp)
        return h, _rms(h, w["norm_2/weight"], hp["eps"])

    return jax.jit(mixed), jax.jit(lambda h, m, w: h + _swiglu(m, w, hp)), jax.jit(lambda m, w: _routed(m, w, hp))


def _part(layer: dict, prefix: str, skip: str | None = None) -> dict:
    """The leaves under ``prefix`` (but those under ``prefix + skip``) as float32,
    the experts as they are: ``_routed`` converts them one at a time."""
    import jax.numpy as jnp

    return {path[len(prefix):]: leaf if "/experts_" in path else leaf.astype(jnp.float32) for path, leaf in layer.items()
            if path.startswith(prefix) and not (skip and path.startswith(prefix + skip))}


def layer_and_margin(x, layer: dict, hp_items: tuple, outputs: dict | None = None):
    """One double layer on x (B, T, hidden) float32 -> (y, the router's margins
    (B, T, 2)). ``outputs``, where given, collects every sublayer's output by name
    (``h1``, ``m``, ``s``, ``h2``, ``h3``, ``y``) for the tests. Each piece gets
    its own leaves as float32 just before it runs, and no value outlives its use."""
    mixed, ffn, routed = _pieces(hp_items)
    keep = outputs.update if outputs is not None else (lambda **values: None)
    h1, m = mixed(x, _part(layer, "sub_0/", skip="mlp/"))
    del x
    s, margin = routed(m, _part(layer, "moe/"))          # the shortcut reads the first sublayer's normed output
    h2 = ffn(h1, m, _part(layer, "sub_0/mlp/"))
    keep(h1=h1, m=m, s=s, h2=h2)
    del h1, m
    h3, n = mixed(h2, _part(layer, "sub_1/", skip="mlp/"))
    del h2
    y = ffn(h3, n, _part(layer, "sub_1/mlp/")) + s  # and joins after the second sublayer's FFN
    keep(h3=h3, y=y)
    return y, margin


def forward_and_margin(weights: dict, idx, config: dict, matmul_inputs=None, last=None):
    """Token ids (B, T) -> (float32 logits (B, T, vocab), the least margins by
    which any layer's router made a position's choice (B, T, 2): as far as the
    held experts go, and as far as the zero-compute outputs go; see ``route``).
    ``last``: the head, and the margins, for the last so many positions only."""
    import jax
    import jax.numpy as jnp

    hp_items = hyper(config, matmul_inputs)
    hp = dict(hp_items)
    with jax.default_matmul_precision("highest"):
        x = weights["wte"][idx].astype(jnp.float32)
        margin = jnp.full(idx.shape + (2,), jnp.inf)
        for layer in itertools.islice(weights["layers"], config["num_layers"]):  # any iterable: a layer may be made as it is asked for
            x, m = layer_and_margin(x, layer, hp_items)
            margin = jnp.minimum(margin, m)
            del layer
        if last is not None:
            x, margin = x[:, -last:], margin[:, -last:]
        x = _rms(x, weights["ln_f/weight"].astype(jnp.float32), hp["eps"])
        return _mm("btc,vc->btv", x, weights["lm_head_w"].astype(jnp.float32), hp), margin


def forward(weights: dict, idx, config: dict, matmul_inputs=None, last=None):
    """Token ids (B, T) -> float32 logits (B, T, vocab)."""
    return forward_and_margin(weights, idx, config, matmul_inputs, last)[0]


def routed_layer(m, moe: dict, config: dict):
    """One routed layer on m (B, T, hidden) with its float32 leaves (``router_w``,
    ``router_bias``, ``experts_*``): what the share test sums over the shares."""
    import jax

    with jax.default_matmul_precision("highest"):
        return _routed(m, moe, dict(hyper(config)))[0]
