"""Plain reference for Ouro (ByteDance's LoopLM, ``model_type`` ouro; Zhu et al.
2025, *Scaling Latent Reasoning via Looped Language Models*): the forward pass
of every loop step and the training objective with its exit gate, in
straightforward float32 ``jax.numpy``; gradients by ``jax.grad`` of that.
Imports nothing from ``thunder_tpu``.

The equations, as the configuration file's keys and its ``assumed`` give them:

* ``x = E[ids]``; then ``total_ut_steps`` times: every layer ``l`` in turn,
  ``h = x + N2_l(Attn_l(N1_l x))`` and ``x = h + N4_l(MLP_l(N3_l h))``, and
  after the last layer ``x = N_f(x)``: this normed state ``s_t`` feeds the next
  step, the head ``logits_t = W_head s_t`` and the exit gate
  ``λ_t = σ(w_g · s_t + b_g)``. The same positions feed the rope every step.
* RMSNorm everywhere; attention of 16 query and 16 key-value heads, rotate-half
  rope on the whole head at ``rope_theta``, causal, scaled by ``head_dim**-0.5``;
  SwiGLU ``down(silu(gate(x)) * up(x))``; no biases but the gate's.
* The exit distribution ``p_t = λ_t Π_{j<t}(1 − λ_j)`` for ``t < T_ut`` and
  ``p_{T_ut} = Π_{j<T_ut}(1 − λ_j)``; the loss is the mean over tokens of
  ``Σ_t p_t ℓ_t − β H(p)``, ``ℓ_t`` the token's cross-entropy at step t,
  ``H(p) = −Σ_t p_t log p_t``, β the file's ``exit_entropy``.

Departures, each noted where it is made:

* Weights arrive under the program's names and in its layouts, which is the
  checkpoint format and not mathematics: one fused ``qkv_w`` whose rows are
  all query heads, then all key heads, then all value heads; ``fc_1_w`` is the
  gate and ``fc_2_w`` the up projection; the four norms of a block are
  ``norm_1`` (Ouro's ``input_layernorm``), ``post_attn_norm``
  (``input_layernorm_2``), ``norm_2`` (``post_attention_layernorm``) and
  ``post_mlp_norm`` (``post_attention_layernorm_2``); ``ln_f`` is ``norm``,
  ``exit_gate_w`` and ``exit_gate_b`` the ``early_exit_gate``'s.
* A loop step is a ``lax.scan`` over the stacked layers, each layer
  rematerialized in the backward pass, and attention runs one head at a time;
  each step's head and cross-entropy are rematerialized too, so that one
  float32 (T, vocab) array is alive at a time. The arithmetic is unchanged.

A float32 matmul on a TPU runs in lower precision unless
``jax.default_matmul_precision("highest")`` is set; every entry point here
sets it.
"""

from __future__ import annotations


def hyper(config: dict, matmul_inputs=None) -> dict:
    """What the equations need, from the published keys and the file's
    ``exit_entropy``. ``matmul_inputs`` (a dtype name, default none) rounds both
    operands of every matmul to that type and back, accumulation staying
    float32: the same mathematics in a lower precision, for the readings that
    say what the comparison hears."""
    return {"heads": config["num_attention_heads"], "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"], "rope_base": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]), "loops": int(config["total_ut_steps"]),
            "beta": float(config["exit_entropy"]), "matmul_inputs": matmul_inputs}


def _mm(spec: str, a, b, hp: dict):
    import jax.numpy as jnp

    if hp["matmul_inputs"] is not None:
        a, b = (x.astype(hp["matmul_inputs"]).astype(jnp.float32) for x in (a, b))
    return jnp.einsum(spec, a, b)


def _rms(x, scale, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * scale


def _rope(x, hp: dict):
    """x: (B, heads, T, d). Rotate-half on the whole head."""
    import jax.numpy as jnp

    d, t = x.shape[-1], x.shape[2]
    inv = jnp.float32(hp["rope_base"]) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = (jnp.concatenate([f(ang), f(ang)], -1) for f in (jnp.cos, jnp.sin))
    half = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + half * sin


def _attention(x, w, hp: dict):
    import jax
    import jax.numpy as jnp

    b, t, _ = x.shape
    h, g, d = hp["heads"], hp["kv_heads"], hp["head_dim"]
    qkv = _mm("btc,oc->bto", x, w["attn/qkv_w"], hp)  # rows: q heads, then k heads, then v heads
    split = lambda a, n: a.reshape(b, t, n, d).transpose(0, 2, 1, 3)  # noqa: E731
    q = _rope(split(qkv[..., : h * d], h), hp)
    k = _rope(split(qkv[..., h * d: (h + g) * d], g), hp)
    v = split(qkv[..., (h + g) * d:], g)
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))

    def one_head(args):
        qh, kh, vh = args  # (B, T, d) each
        s = _mm("bqd,bkd->bqk", qh, kh, hp) / jnp.sqrt(jnp.float32(d))
        return _mm("bqk,bkd->bqd", jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1), vh, hp)

    kv_of = jnp.arange(h) // (h // g)  # query head -> its key-value head
    y = jax.lax.map(one_head, (q.transpose(1, 0, 2, 3), k[:, kv_of].transpose(1, 0, 2, 3),
                               v[:, kv_of].transpose(1, 0, 2, 3)))
    return _mm("bto,co->btc", y.transpose(1, 2, 0, 3).reshape(b, t, h * d), w["attn/proj_w"], hp)


def _mlp(x, w, hp: dict):
    import jax

    up = jax.nn.silu(_mm("btc,hc->bth", x, w["mlp/fc_1_w"], hp)) * _mm("btc,hc->bth", x, w["mlp/fc_2_w"], hp)
    return _mm("bth,ch->btc", up, w["mlp/proj_w"], hp)


def _block(x, w, hp: dict):
    """Four norms a block: ``h = x + N2(Attn(N1 x))``, ``x = h + N4(MLP(N3 h))``."""
    eps = hp["eps"]
    h = x + _rms(_attention(_rms(x, w["norm_1/weight"], eps), w, hp), w["post_attn_norm/weight"], eps)
    return h + _rms(_mlp(_rms(h, w["norm_2/weight"], eps), w, hp), w["post_mlp_norm/weight"], eps)


def _identity(kind, weight, layer):
    return weight


def states(weights: dict, idx, config: dict, adjust=_identity, matmul_inputs=None) -> list:
    """Token ids (B, T) -> [s_1 .. s_T_ut], each loop step's normed state
    (B, T, hidden), float32. ``weights`` maps a leaf's kind to its array,
    per-layer kinds stacked on a leading layer axis (``perfbench/weights.py``).
    ``adjust(kind, w, layer)`` sees every weight in float32 just before each of
    its uses (``layer`` is the scan index, the same at every loop step, ``None``
    outside the blocks); the gradient check adds its probes there, so a probe's
    gradient is the sum of its weight's uses. The default changes nothing."""
    import jax
    import jax.numpy as jnp

    hp = hyper(config, matmul_inputs)
    f32 = lambda kind, w, layer=None: adjust(kind, w.astype(jnp.float32), layer)  # noqa: E731
    block_kinds = sorted(k for k in weights if k.startswith("blocks/*/"))
    depth = weights[block_kinds[0]].shape[0]

    @jax.checkpoint
    def layer(x, per_layer):
        i, ws = per_layer
        return _block(x, {k[len("blocks/*/"):]: f32(k, ws[k], i) for k in block_kinds}, hp), None

    out = []
    with jax.default_matmul_precision("highest"):
        x = f32("wte", weights["wte"])[idx]
        for _ in range(hp["loops"]):
            x, _ = jax.lax.scan(layer, x, (jnp.arange(depth, dtype=jnp.int32), {k: weights[k] for k in block_kinds}))
            x = _rms(x, f32("ln_f/weight", weights["ln_f/weight"]), hp["eps"])
            out.append(x)
    return out


def forward(weights: dict, idx, config: dict, adjust=_identity, matmul_inputs=None) -> list:
    """[logits_1 .. logits_T_ut], each (B, T, vocab) float32: every loop step's
    head. An exit threshold of 1 serves the last."""
    import jax

    hp = hyper(config, matmul_inputs)
    with jax.default_matmul_precision("highest"):
        head = adjust("lm_head_w", weights["lm_head_w"].astype("float32"), None)
        return [_mm("btc,vc->btv", s, head, hp) for s in states(weights, idx, config, adjust, matmul_inputs)]


def loss(weights: dict, idx, targets, config: dict, adjust=_identity, matmul_inputs=None):
    """The exit objective, float32: the mean over tokens of
    ``Σ_t p_t ℓ_t − β H(p)``."""
    import jax
    import jax.numpy as jnp

    hp = hyper(config, matmul_inputs)
    f32 = lambda kind: adjust(kind, weights[kind].astype(jnp.float32), None)  # noqa: E731
    tgt = targets[..., None].astype(jnp.int32)

    @jax.checkpoint
    def token_losses(s, head):  # (B, T): the step's cross-entropy, the float32 logits not kept
        logits = _mm("btc,vc->btv", s, head, hp)
        return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, tgt, axis=-1)[..., 0]

    with jax.default_matmul_precision("highest"):
        each = states(weights, idx, config, adjust, matmul_inputs)
        head, gate_w, gate_b = f32("lm_head_w"), f32("exit_gate_w"), f32("exit_gate_b")
        ell = [token_losses(s, head) for s in each]
        lam = [jax.nn.sigmoid(_mm("btc,oc->bto", s, gate_w, hp) + gate_b)[..., 0] for s in each[:-1]]
        p, stay = [], jnp.ones_like(ell[0])
        for l in lam:
            p.append(stay * l)
            stay = stay * (1.0 - l)
        p.append(stay)  # the last step takes what is left
        expected = sum(pt * lt for pt, lt in zip(p, ell))
        entropy = -sum(pt * jnp.log(pt) for pt in p)
        return jnp.mean(expected - hp["beta"] * entropy)
