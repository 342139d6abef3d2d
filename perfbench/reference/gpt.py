"""Plain reference for the decoder-only transformer family the repo runs:
GPT-NeoX (Pythia) blocks and Mistral blocks. Forward pass and loss in
straightforward float32 ``jax.numpy``; gradients by ``jax.grad`` of that.
Imports nothing from ``thunder_tpu``.

Written from the published descriptions:

* GPT-NeoX (Black et al. 2022; ``GPTNeoXForCausalLM``): LayerNorm with bias,
  rotary embedding on the first ``rotary_pct`` of each head (rotate-half),
  exact (erf) GELU MLP of two biased matmuls, and with
  ``use_parallel_residual`` ``x + attn(ln1(x)) + mlp(ln2(x))``.
* Mistral 7B (Jiang et al. 2023; ``MistralForCausalLM``): RMSNorm, rotary on
  the whole head, grouped-query attention (query head ``h`` reads key/value
  head ``h // (H / G)``), SwiGLU ``down(silu(gate(x)) * up(x))``, no biases,
  sequential residual.

Departures, each noted where it is made:

* Weights arrive under the program's names and in its layouts, which is the
  checkpoint format and not mathematics: one fused ``qkv_w`` whose rows are
  all query heads, then all key heads, then all value heads (Hugging Face's
  NeoX interleaves them per head); ``fc_1_w`` is the gate and ``fc_2_w`` the
  up projection.
* Mistral's sliding window of 4096 is not applied: at sequence lengths up to
  4096 it equals plain causal attention, and the cells stop there.
* Layers are a ``lax.scan`` over stacked weights with the block rematerialized
  in the backward pass, and attention runs one key/value head at a time, so
  that the float32 (T, T) scores of one head group are all that is alive. The
  arithmetic is unchanged.

A float32 matmul on a TPU runs in lower precision unless
``jax.default_matmul_precision("highest")`` is set; every entry point here
sets it.
"""

from __future__ import annotations


def hyper(config: dict) -> dict:
    """What the equations need. Widths and counts are published keys; what
    ``model_type`` means (the kind of norm and of MLP, where the rotary
    fraction and the key-value heads are written) the configuration file
    spells out under ``reference_hyper``: ``from_keys`` names the published
    key a value is read from, ``fixed`` gives it outright. A model with these
    blocks brings a configuration file and no code."""
    heads = config["num_attention_heads"]
    hs = config["hidden_size"] // heads
    said = config["reference_hyper"]
    hp = {**said["fixed"], **{name: config[key] for name, key in said["from_keys"].items()}}
    if hp["norm"] not in ("layer", "rms") or hp["mlp"] not in ("gelu", "swiglu"):
        raise ValueError(f"this reference has no norm {hp['norm']!r} or MLP {hp['mlp']!r}")
    return {"heads": heads, "kv_heads": hp["kv_heads"], "head_size": hs,
            "rope_n": int(hp["rope_fraction"] * hs), "rope_base": float(hp["rope_base"]),
            "norm": hp["norm"], "eps": float(hp["eps"]), "mlp": hp["mlp"], "parallel": bool(hp["parallel"])}


def _norm(x, w, prefix: str, hp: dict):
    import jax.numpy as jnp

    scale = w[prefix + "/weight"]
    if hp["norm"] == "rms":
        return x * jnp.reciprocal(jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + hp["eps"])) * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + hp["eps"]) * scale + w[prefix + "/bias"]


def _linear(x, w, name: str):
    y = x @ w[name + "_w"].T
    return y + w[name + "_b"] if name + "_b" in w else y


def _rope(x, hp: dict):
    """x: (B, heads, T, hs). Rotate-half on the first ``rope_n`` features."""
    import jax.numpy as jnp

    n = hp["rope_n"]
    t = x.shape[2]
    inv = jnp.float32(hp["rope_base"]) ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    rot, rest = x[..., :n], x[..., n:]
    half = jnp.concatenate([-rot[..., n // 2:], rot[..., : n // 2]], -1)
    return jnp.concatenate([rot * cos + half * sin, rest], -1)


def _attention(x, w, hp: dict):
    import jax
    import jax.numpy as jnp

    b, t, _ = x.shape
    h, g, hs = hp["heads"], hp["kv_heads"], hp["head_size"]
    qkv = _linear(x, w, "attn/qkv")  # rows: q heads, then k heads, then v heads
    q = qkv[..., : h * hs].reshape(b, t, h, hs).transpose(0, 2, 1, 3)
    k = qkv[..., h * hs: (h + g) * hs].reshape(b, t, g, hs).transpose(0, 2, 1, 3)
    v = qkv[..., (h + g) * hs:].reshape(b, t, g, hs).transpose(0, 2, 1, 3)
    q, k = _rope(q, hp), _rope(k, hp)
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))

    def one_group(args):
        qg, kg, vg = args  # (B, H/G, T, hs), (B, T, hs), (B, T, hs)
        s = jnp.einsum("bhqd,bkd->bhqk", qg, kg) / jnp.sqrt(jnp.float32(hs))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkd->bhqd", p, vg)

    qg = q.reshape(b, g, h // g, t, hs).transpose(1, 0, 2, 3, 4)
    y = jax.lax.map(one_group, (qg, k.transpose(1, 0, 2, 3), v.transpose(1, 0, 2, 3)))
    y = y.transpose(1, 0, 2, 3, 4).reshape(b, h, t, hs).transpose(0, 2, 1, 3).reshape(b, t, h * hs)
    return _linear(y, w, "attn/proj")


def _mlp(x, w, hp: dict):
    import jax

    if hp["mlp"] == "swiglu":
        return _linear(jax.nn.silu(_linear(x, w, "mlp/fc_1")) * _linear(x, w, "mlp/fc_2"), w, "mlp/proj")
    return _linear(jax.nn.gelu(_linear(x, w, "mlp/fc"), approximate=False), w, "mlp/proj")


def _block(x, w, hp: dict):
    attn = _attention(_norm(x, w, "norm_1", hp), w, hp)
    if hp["parallel"]:
        return x + attn + _mlp(_norm(x, w, "norm_2", hp), w, hp)
    x = x + attn
    return x + _mlp(_norm(x, w, "norm_2", hp), w, hp)


def _identity(kind, weight, layer):
    return weight


def forward(weights: dict, idx, config: dict, adjust=_identity):
    """Token ids (B, T) -> float32 logits (B, T, vocab).

    ``weights`` maps a leaf's kind to its array, per-layer kinds stacked on a
    leading layer axis (``perfbench/weights.py``). ``adjust(kind, w, layer)``
    sees every weight in float32 just before it is used (``layer`` is the scan
    index, ``None`` outside the blocks); the gradient check adds its probes
    there. The default changes nothing."""
    import jax
    import jax.numpy as jnp

    hp = hyper(config)
    f32 = lambda kind, w, layer=None: adjust(kind, w.astype(jnp.float32), layer)  # noqa: E731
    block_kinds = sorted(k for k in weights if k.startswith("blocks/*/"))
    depth = weights[block_kinds[0]].shape[0]

    @jax.checkpoint
    def layer(x, per_layer):
        i, ws = per_layer
        w = {k[len("blocks/*/"):]: f32(k, ws[k], i) for k in block_kinds}
        return _block(x, w, hp), None

    with jax.default_matmul_precision("highest"):
        x = f32("wte", weights["wte"])[idx]
        x, _ = jax.lax.scan(layer, x, (jnp.arange(depth, dtype=jnp.int32),
                                       {k: weights[k] for k in block_kinds}))
        final = {k[len("ln_f/"):]: f32(k, weights[k]) for k in weights if k.startswith("ln_f/")}
        x = _norm(x, {"ln_f/" + k: v for k, v in final.items()}, "ln_f", hp)
        return x @ f32("lm_head_w", weights["lm_head_w"]).T


def loss(weights: dict, idx, targets, config: dict, adjust=_identity):
    """Mean next-token cross-entropy over every position, float32."""
    import jax
    import jax.numpy as jnp

    logits = forward(weights, idx, config, adjust)
    picked = jnp.take_along_axis(logits, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)
