"""Plain reference for MiniCPM-SALA (``model_type`` ``minicpm_sala``;
huggingface.co/openbmb/MiniCPM-SALA): block-sparse attention whose blocks each
query chooses by scoring pooled keys (InfLLM-V2, the ``minicpm4`` layers) beside
linear attention with a decay per head (Lightning Attention, the
``lightning-attn`` layers), SwiGLU MLPs, the MiniCPM family's scalings, an
untied head. Forward pass in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. No kernel, no composite, no chunked
recurrence: the linear layer is the quadratic form ``((Q K^T) * D) V`` with
``D[t, s] = exp(-g (t - s))``, the sparse layer its seven steps as written.
Imports nothing from ``thunder_tpu``.

The equations (x is (B, T, hidden); every Linear is without bias; RMSNorm is
``w * x / sqrt(mean(x^2) + rms_norm_eps)``; ``L`` is the published depth, 32,
also in a model cut in depth; what the published ``config.json`` does not give
is marked *assumed* and listed in ``perfbench/configs/minicpm-sala.json``):

* ``h = scale_emb * E[ids]``; layer l: ``h = h + (scale_depth / sqrt(L)) *
  Mix_l(RMSNorm(h))``; ``h = h + (scale_depth / sqrt(L)) * W_down(silu(W_gate n)
  * W_up n)``, ``n = RMSNorm(h)``; logits ``W_head (RMSNorm(h) / (hidden_size /
  dim_model_base))`` (*assumed*: MiniCPM's earlier models).
* q, k, v projections to ``heads``, ``kv_heads`` and ``kv_heads`` heads of
  ``head_dim``; ``qk_norm``: an RMSNorm over each head of q and of k, one weight
  for all query heads and one for all key heads, before the rope (*assumed*
  placement).
* ``mixer_types[l] == "lightning-attn"`` (``lightning_nh`` heads on
  ``lightning_nkv``): rope by halves on the whole head, ``rope_theta``; head h:
  ``o_t = sum_{s<=t} exp(-g (t - s)) (q_t . k_s / sqrt(d)) v_s``,
  ``g = 2^(-8 (h + 1) / H) * (1 - l / (L - 1) + 1e-5)`` (*assumed*: Lightning
  Attention-2's); ``y = W_o (RMSNorm(concat_h o) * sigmoid(W_g x))``, the norm
  over all heads' features (*assumed* form).
* ``"minicpm4"`` (``num_attention_heads`` on ``num_key_value_heads``, no rope):
  1. ``Kc[g, j] = mean(k[g, stride j : stride j + kernel_size])``;
  2. ``p[h, t, :] = softmax_j(q[h, t] . Kc[g, j] / sqrt(d))`` over the pooled
     keys wholly in the query's past (``stride j + kernel_size <= t + 1``),
     zeros where there is none;
  3. ``P[g, t, j] = sum_{h in g} p[h, t, j]``;
  4. block b (keys ``[block_size b, block_size (b + 1))``):
     ``B[g, t, b] = max_j P[g, t, j]`` over the pooled keys that overlap it;
  5. blocks ``< init_blocks`` and the ``window_size / block_size`` ending at the
     query's own: +inf; ``I[g, t]`` the ``topk`` best of ``b <= t // block_size``,
     the lower b on a tie;
  6. ``o[h, t] = softmax attention over the keys s <= t of the blocks in I[g, t]``;
  7. ``T < dense_len``: step 6 over every block;
  ``y = W_o (concat_h o * sigmoid(W_g x))``. The constants are MiniCPM4's
  published ``sparse_config`` (*assumed*).

Departures from the published code, each where it is made:

* Weights arrive under the program's names and layouts: q, k and v as the rows
  of one ``qkv_w`` (q heads, then k, then v); the MLP as ``fc_1_w`` (gate),
  ``fc_2_w`` (up), ``proj_w`` (down), each (out, in).
* The model is cut in depth alone: the first ``num_hidden_layers`` of the
  published ``mixer_types`` run; the decay and the residual scale keep L = 32.
* Layers are a Python loop, each a compiled call of its own on that layer's
  weights converted to float32; queries go through both mixers in blocks of
  ``QUERY_BLOCK`` against all keys and the MLP in blocks of rows, so that
  32,768 positions fit beside the weights. The blocks are the reference's own
  and have nothing of the program's chunks: no key is left out of a block's
  scores, no state is carried. The head is computed for the last ``last``
  positions where that is asked. The arithmetic is unchanged.
"""

from __future__ import annotations

QUERY_BLOCK = 256
ROW_BLOCK = 2048


def hyper(config: dict, matmul_inputs=None) -> dict:
    """What the equations need. ``matmul_inputs`` (a dtype name, default none)
    rounds both operands of every matmul to that type and back, accumulation
    staying float32: the same mathematics in a lower precision, for the reading
    that places the comparison's limits (``perfbench/checks_sparse_linear.py``)."""
    depth, sparse = config["num_hidden_layers"], config["sparse_config"]
    published = config.get("num_hidden_layers_published", depth)
    return {
        "heads": config["num_attention_heads"], "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"], "linear_heads": config["lightning_nh"], "linear_kv_heads": config["lightning_nkv"],
        "eps": float(config["rms_norm_eps"]), "rope_base": float(config["rope_theta"]),
        "attn_rope": bool(config["attn_use_rope"]), "linear_rope": bool(config["lightning_use_rope"]),
        "qk_norm": bool(config["qk_norm"]), "attn_gate": bool(config["attn_use_output_gate"]),
        "linear_gate": bool(config["use_output_gate"]), "linear_norm": bool(config["use_output_norm"]),
        "mixers": tuple(config["mixer_types"][:depth]), "published_depth": published,
        "scale_emb": float(config["scale_emb"]), "residual": float(config["scale_depth"]) / published ** 0.5,
        "logit_divisor": config["hidden_size"] / config["dim_model_base"],
        "kernel_size": sparse["kernel_size"], "kernel_stride": sparse["kernel_stride"],
        "block_size": sparse["block_size"], "topk": sparse["topk"], "init_blocks": sparse["init_blocks"],
        "local_blocks": sparse["window_size"] // sparse["block_size"], "dense_len": sparse["dense_len"],
        "matmul_inputs": matmul_inputs,
    }


def decay(layer: int, heads: int, published_depth: int):
    """g of each head of layer ``layer`` (0-based, of the published depth)."""
    import jax.numpy as jnp

    h = jnp.arange(1, heads + 1, dtype=jnp.float32)
    return 2.0 ** (-8.0 * h / heads) * (1.0 - layer / max(published_depth - 1, 1) + 1e-5)


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


def _heads(x, w, heads: int, kv_heads: int, rope: bool, hp: dict):
    """x (T, hidden) -> q (heads, T, d), k, v (kv_heads, T, d), normed and roped as the layer says."""
    t, d = x.shape[0], hp["head_dim"]
    qkv = _mm("tc,oc->to", x, w["qkv_w"], hp)
    split = lambda a, n: a.reshape(t, n, d).transpose(1, 0, 2)
    q, k, v = (split(qkv[:, : heads * d], heads), split(qkv[:, heads * d:(heads + kv_heads) * d], kv_heads),
               split(qkv[:, (heads + kv_heads) * d:], kv_heads))
    if hp["qk_norm"]:
        q, k = _rms(q, w["q_norm/weight"], hp["eps"]), _rms(k, w["k_norm/weight"], hp["eps"])
    if rope:
        q, k = _rope(q, hp["rope_base"]), _rope(k, hp["rope_base"])
    return q, k, v


def _by_query_blocks(fn, t: int, block: int = QUERY_BLOCK):
    """``fn(positions (block,)) -> (.., block, ..)`` over every position of
    0..t-1, a block at a time; the last block repeats position t-1 to fill up.
    Each output has the positions on its axis 1 and comes back cut to t."""
    import jax
    import jax.numpy as jnp

    block = min(block, t)
    n = -(-t // block)
    positions = jnp.minimum(jnp.arange(n * block), t - 1).reshape(n, block)
    outs = jax.lax.map(fn, positions)                              # each (n, .., block, ..)
    join = lambda o: jnp.moveaxis(o, 0, 1).reshape(o.shape[1], n * block, *o.shape[3:])[:, :t]
    return jax.tree_util.tree_map(join, outs)


def linear_attention(q, k, v, g, hp: dict):
    """q, k, v (H, T, d), g (H,) -> (H, T, d): ``((Q K^T) * D) V / sqrt(d)``,
    ``D[t, s] = exp(-g (t - s))`` for ``s <= t`` and 0 after."""
    import jax.numpy as jnp

    t, d = q.shape[1], q.shape[2]
    s_pos = jnp.arange(t)

    def block(pos):
        ahead = pos[:, None] - s_pos[None, :]                                          # (n, T)
        weight = jnp.where(ahead >= 0, jnp.exp(-g[:, None, None] * jnp.maximum(ahead, 0)[None]), 0.0)
        scores = _mm("hnd,hsd->hns", q[:, pos], k, hp) * d ** -0.5
        return _mm("hns,hsd->hnd", scores * weight, v, hp)

    return _by_query_blocks(block, t)


def pooled_keys(k, hp: dict):
    """k (G, T, d) -> (G, pooled, d): the mean of each window of ``kernel_size`` keys at ``kernel_stride``."""
    import jax.numpy as jnp

    size, stride = hp["kernel_size"], hp["kernel_stride"]
    n = (k.shape[1] - size) // stride + 1 if k.shape[1] >= size else 0
    window = stride * jnp.arange(n)[:, None] + jnp.arange(size)[None, :]
    return k[:, window].mean(2)


def pooled_scores(q, pooled, pos, hp: dict):
    """Steps 2 and 3 for the queries at ``pos``: q (H, n, d), pooled (G, J, d) -> P (G, n, J)."""
    import jax
    import jax.numpy as jnp

    pools, d = pooled.shape[1], q.shape[-1]
    qg = q.reshape(pooled.shape[0], -1, *q.shape[1:])                                   # (G, R, n, d)
    s = _mm("grnd,gjd->grnj", qg, pooled, hp) * d ** -0.5
    past = (hp["kernel_stride"] * jnp.arange(pools) + hp["kernel_size"])[None, :] <= (pos + 1)[:, None]  # (n, J)
    p = jnp.where(past, jax.nn.softmax(jnp.where(past, s, -jnp.inf), axis=-1), 0.0)     # a row with none: zeros
    return p.sum(1)


def overlapping(n_blocks: int, pools: int, hp: dict):
    """(pooled keys (blocks, per + r - 1) that overlap each block, which of them exist)."""
    import jax.numpy as jnp

    per, r = hp["block_size"] // hp["kernel_stride"], hp["kernel_size"] // hp["kernel_stride"]
    j = per * jnp.arange(n_blocks)[:, None] + jnp.arange(-(r - 1), per)[None, :]
    return jnp.clip(j, 0, max(pools - 1, 0)), (j >= 0) & (j < pools)


def select_blocks(q, pooled, pos, n_blocks: int, hp: dict):
    """Steps 2 to 5 for the queries at ``pos`` -> ids (G, n, topk) int32, best
    first, -1 where a query has fewer blocks."""
    import jax
    import jax.numpy as jnp

    pools = pooled.shape[1]
    if pools:
        j, exists = overlapping(n_blocks, pools, hp)
        score = jnp.where(exists, pooled_scores(q, pooled, pos, hp)[..., j], -jnp.inf).max(-1)  # (G, n, blocks)
        score = jnp.where(exists.any(-1), score, 0.0)  # a block no pooled key overlaps lies in its queries' window
    else:
        score = jnp.zeros((pooled.shape[0], pos.shape[0], n_blocks))
    b, own = jnp.arange(n_blocks)[None, :], (pos // hp["block_size"])[:, None]
    forced = (b < hp["init_blocks"]) | (b > own - hp["local_blocks"])
    score = jnp.where(b > own, -jnp.inf, jnp.where(forced, jnp.inf, score))
    best, ids = jax.lax.top_k(score, min(hp["topk"], n_blocks))                         # the lower block first on a tie
    ids = jnp.where(best > -jnp.inf, ids, -1).astype(jnp.int32)
    return jnp.pad(ids, ((0, 0), (0, 0), (0, hp["topk"] - ids.shape[-1])), constant_values=-1)


def sparse_attention(q, k, v, hp: dict, dense: bool = False):
    """q (H, T, d), k, v (G, T, d) -> (o (H, T, d), ids (G, T, topk)).
    ``dense``: step 7, every block attended."""
    import jax
    import jax.numpy as jnp

    t, d, bs = q.shape[1], q.shape[2], hp["block_size"]
    n_blocks = -(-t // bs)
    pooled = pooled_keys(k, hp)
    block_of_key = jnp.arange(t) // bs

    def block(pos):
        ids = select_blocks(q[:, pos], pooled, pos, n_blocks, hp)
        chosen = (ids[..., None] == jnp.arange(n_blocks)).any(-2)                       # (G, n, blocks)
        seen = (jnp.ones_like(chosen) if dense else chosen)[..., block_of_key] & (jnp.arange(t)[None, :] <= pos[:, None])
        qg = q[:, pos].reshape(k.shape[0], -1, pos.shape[0], d)
        s = _mm("grnd,gsd->grns", qg, k, hp) * d ** -0.5
        w = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), axis=-1)
        return _mm("grns,gsd->grnd", w, v, hp).reshape(q.shape[0], pos.shape[0], d), ids

    return _by_query_blocks(block, t)


def _gated_out(y, x, w, hp: dict, gate: bool):
    import jax

    if gate:
        y = y * jax.nn.sigmoid(_mm("tc,oc->to", x, w["gate_w"], hp))
    return _mm("tv,cv->tc", y, w["proj_w"], hp)


def _sparse_mixer(x, w, hp: dict):
    q, k, v = _heads(x, w, hp["heads"], hp["kv_heads"], hp["attn_rope"], hp)
    o, _ = sparse_attention(q, k, v, hp, dense=x.shape[0] < hp["dense_len"])
    return _gated_out(o.transpose(1, 0, 2).reshape(x.shape[0], -1), x, w, hp, hp["attn_gate"])


def _linear_mixer(x, w, g, hp: dict):
    import jax.numpy as jnp

    heads, kv = hp["linear_heads"], hp["linear_kv_heads"]
    q, k, v = _heads(x, w, heads, kv, hp["linear_rope"], hp)
    k, v = jnp.repeat(k, heads // kv, 0), jnp.repeat(v, heads // kv, 0)
    y = linear_attention(q, k, v, g, hp).transpose(1, 0, 2).reshape(x.shape[0], -1)
    if hp["linear_norm"]:
        y = _rms(y, w["out_norm/weight"], hp["eps"])
    return _gated_out(y, x, w, hp, hp["linear_gate"])


def _swiglu(x, w, hp: dict):
    """(T, hidden) in blocks of rows: the gate and up activations of 32,768 rows do not fit whole."""
    import jax
    import jax.numpy as jnp

    def rows(xb):
        h = jax.nn.silu(_mm("tc,hc->th", xb, w["fc_1_w"], hp)) * _mm("tc,hc->th", xb, w["fc_2_w"], hp)
        return _mm("th,ch->tc", h, w["proj_w"], hp)

    t = x.shape[0]
    block = min(ROW_BLOCK, t)
    n = -(-t // block)
    padded = jnp.pad(x, ((0, n * block - t), (0, 0)))
    return jax.lax.map(rows, padded.reshape(n, block, -1)).reshape(n * block, -1)[:t]


def _block(x, w, g, hp: dict, mixer: str):
    """x (B, T, hidden), g the layer's decays -> x: a sequence at a time."""
    import jax

    of = lambda prefix: {k[len(prefix):]: a for k, a in w.items() if k.startswith(prefix)}

    def one(xs):
        n1 = _rms(xs, w["norm_1/weight"], hp["eps"])
        mixed = (_sparse_mixer(n1, of("sparse_attn/"), hp) if mixer == "minicpm4"
                 else _linear_mixer(n1, of("linear_attn/"), g, hp))
        xs = xs + hp["residual"] * mixed
        return xs + hp["residual"] * _swiglu(_rms(xs, w["norm_2/weight"], hp["eps"]), of("mlp/"), hp)

    return jax.lax.map(one, x)


def layer_weights(weights: dict, layer: int) -> dict:
    """Layer ``layer``'s leaves out of the stacked kinds (``perfbench/weights.py``:
    ``blocks/*/..``, a kind's leading axis being the layer), float32."""
    import jax.numpy as jnp

    prefix = "blocks/*/"
    return {kind[len(prefix):]: stacked[layer].astype(jnp.float32) for kind, stacked in weights.items()
            if kind.startswith(prefix) and layer < stacked.shape[0]}


def forward(weights: dict, idx, config: dict, matmul_inputs=None, last=None):
    """Token ids (B, T) -> float32 logits (B, T, vocab). ``last``: the head for
    the last so many positions only. ``weights`` maps a leaf's kind to its
    array, per-layer kinds stacked on a leading layer axis."""
    import jax
    import jax.numpy as jnp

    hp = hyper(config, matmul_inputs)
    blocks: dict = {}  # one compiled function a kind of layer; under a trace of the whole they are inlined
    with jax.default_matmul_precision("highest"):
        x = hp["scale_emb"] * weights["wte"].astype(jnp.float32)[idx]
        for i, mixer in enumerate(hp["mixers"]):
            if mixer not in blocks:
                blocks[mixer] = jax.jit(lambda x, w, g, mixer=mixer: _block(x, w, g, hp, mixer))
            x = blocks[mixer](x, layer_weights(weights, i), decay(i, hp["linear_heads"], hp["published_depth"]))
        if last is not None:
            x = x[:, -last:]
        x = _rms(x, weights["ln_f/weight"].astype(jnp.float32), hp["eps"]) / hp["logit_divisor"]
        return _mm("btc,vc->btv", x, weights["lm_head_w"].astype(jnp.float32), hp)
