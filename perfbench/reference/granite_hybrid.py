"""Plain reference for Granite 4.0-H (``model_type`` ``granitemoehybrid`` with no
routed part; huggingface.co/ibm-granite/granite-4.0-h-micro): Mamba-2 mixers,
whose decay each token sets, beside rope-less grouped-query attention, SwiGLU
MLPs, Granite's four multipliers, the head tied to the embedding. Forward pass in
straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. No kernel, no composite, no chunk:
the recurrence is a ``lax.scan`` over positions that carries the state, the
convolution a sum over its shifted copies, attention the masked softmax. Imports
nothing from ``thunder_tpu``.

The equations (x is (B, T, hidden); every Linear is without bias; RMSNorm is
``w * x / sqrt(mean(x^2) + rms_norm_eps)``; what the catalogue's ``config`` does
not give is marked *assumed* and listed in
``perfbench/configs/granite-4.0-h-micro.json``):

* ``h = embedding_multiplier * E[ids]``; layer l: ``h = h + residual_multiplier *
  Mix_l(RMSNorm(h))``; ``h = h + residual_multiplier * W_out(silu(a) * b)``,
  ``[a | b] = W_in RMSNorm(h)`` (*assumed* packing: a the first
  ``shared_intermediate_size`` rows); logits ``E RMSNorm(h) / logits_scaling``.
* ``layer_types[l] == "attention"``: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` key-value heads of ``hidden_size / num_attention_heads``,
  no rope (``position_embedding_type`` ``nope``), causal softmax of
  ``attention_multiplier * q . k``, ``W_o``.
* ``"mamba"``: ``[z | xBC | dt] = W_in_proj n`` (*assumed* order), widths
  ``d_inner | d_inner + 2 G N | H`` with ``d_inner = mamba_expand * hidden_size = H
  P``; ``xBC = silu(conv(xBC) + b_conv)``, depthwise, causal, ``mamba_d_conv`` taps,
  zeros before the sequence; ``[x | B | C] = xBC`` (*assumed* order), x as H heads
  of P, B and C as G groups of N shared by the ``H / G`` heads of a group; ``dt =
  softplus(dt + dt_bias)`` (no clamp: *assumed* ``time_step_limit`` (0, inf)), ``A =
  -exp(A_log)``; head h, state ``S`` (P, N), ``S_{-1} = 0``:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``;
  ``y = RMSNorm(y * silu(z))`` over all ``d_inner`` features (*assumed*: the gate
  first, one group); ``W_out_proj``. ``mamba_chunk_size`` is the published kernel's
  constant and appears in no equation: it is not read here.

Departures from the published code, each where it is made:

* Weights arrive under the program's names and layouts: q, k and v as the rows
  of one ``qkv_w`` (q heads, then k, then v); the MLP's ``input_linear`` as
  ``fc_1_w`` (a) and ``fc_2_w`` (b), ``proj_w`` the output; the convolution's
  weight (channels, taps), oldest tap first, without Conv1d's middle 1;
  ``weights`` is ``{"wte", "ln_f/weight", "layers": [a layer's leaves by their
  paths]}`` (``perfbench/jobs/forward_window_moe.py::for_reference``).
* Layers are a Python loop, each a compiled call of its own on that layer's
  weights converted to float32; attention's queries go in blocks of
  ``QUERY_BLOCK`` against all keys and the MLP in blocks of rows, so that 16,384
  positions fit beside the weights. The blocks are the reference's own: no key is
  left out of a block's scores and no state is summarised. The head is computed
  for the last ``last`` positions where that is asked. The arithmetic is unchanged.
"""

from __future__ import annotations

QUERY_BLOCK = 256
ROW_BLOCK = 2048
MIXERS = ("mamba", "attention")


def hyper(config: dict, matmul_inputs=None) -> dict:
    """What the equations need. ``matmul_inputs`` (a dtype name, default none)
    rounds both operands of every matmul, and of the recurrence's two products, to
    that type and back, accumulation and the state staying float32: the same
    mathematics in a lower precision, for the reading that places the comparison's
    limit (``perfbench/checks_ssm.py``)."""
    depth = config["num_hidden_layers"]
    return {
        "heads": config["num_attention_heads"], "kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "attention_scale": float(config["attention_multiplier"]), "eps": float(config["rms_norm_eps"]),
        "ssm_heads": config["mamba_n_heads"], "ssm_head_dim": config["mamba_d_head"], "ssm_state": config["mamba_d_state"],
        "ssm_groups": config["mamba_n_groups"], "ssm_taps": config["mamba_d_conv"],
        "mixers": tuple(config["layer_types"][:depth]),
        "embedding": float(config["embedding_multiplier"]), "residual": float(config["residual_multiplier"]),
        "logit_divisor": float(config["logits_scaling"]), "matmul_inputs": matmul_inputs,
    }


def _lowered(a, hp: dict):
    import jax.numpy as jnp

    return a if hp["matmul_inputs"] is None else a.astype(hp["matmul_inputs"]).astype(jnp.float32)


def _mm(spec: str, a, b, hp: dict):
    import jax.numpy as jnp

    return jnp.einsum(spec, _lowered(a, hp), _lowered(b, hp))


def _rms(x, scale, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * scale


def _by_blocks(fn, rows, block: int):
    """``fn((block, ..)) -> (block, ..)`` over the leading axis of ``rows``, a block at a time."""
    import jax
    import jax.numpy as jnp

    t = rows.shape[0]
    block = min(block, t)
    n = -(-t // block)
    padded = jnp.pad(rows, ((0, n * block - t),) + ((0, 0),) * (rows.ndim - 1))
    out = jax.lax.map(fn, padded.reshape(n, block, *rows.shape[1:]))
    return out.reshape(n * block, *out.shape[2:])[:t]


def attention(q, k, v, hp: dict):
    """q (H, T, d), k, v (G, T, d) -> (H, T, d): causal softmax of ``attention_scale * q . k``."""
    import jax
    import jax.numpy as jnp

    t, d = q.shape[1], q.shape[2]
    qg = q.reshape(k.shape[0], -1, t, d)                                                # (G, R, T, d)
    keys = jnp.arange(t)

    def block(pos):
        s = _mm("grnd,gsd->grns", qg[:, :, pos], k, hp) * hp["attention_scale"]
        w = jax.nn.softmax(jnp.where(keys[None, :] <= pos[:, None], s, -jnp.inf), axis=-1)
        return _mm("grns,gsd->ngrd", w, v, hp)                                          # (n, G, R, d)

    out = _by_blocks(block, jnp.arange(t), QUERY_BLOCK)                                 # (T, G, R, d)
    return out.reshape(t, q.shape[0], d).transpose(1, 0, 2)


def causal_conv(x, w, b):
    """x (T, C), w (C, K) oldest tap first, b (C,) -> (T, C): ``sum_j w[:, j] x[t - (K - 1 - j)] + b``, zeros before 0."""
    import jax.numpy as jnp

    t, taps = x.shape[0], w.shape[1]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(padded[j:j + t] * w[:, j] for j in range(taps)) + b


def recurrence(x, dt, A, B, C, D, hp: dict):
    """x (T, H, P), dt (T, H), A (H,), B and C (T, G, N), D (H,) -> (T, H, P):
    the state (H, P, N) carried over the positions one at a time, as written."""
    import jax
    import jax.numpy as jnp

    heads, width = x.shape[1], x.shape[2]
    rep = heads // B.shape[1]
    xd, Bl, Cl = _lowered(dt[..., None] * x, hp), _lowered(B, hp), _lowered(C, hp)

    def step(state, at):
        xd_t, da_t, b_t, c_t = at
        b_h, c_h = jnp.repeat(b_t, rep, 0), jnp.repeat(c_t, rep, 0)                     # (H, N)
        state = jnp.exp(da_t)[:, None, None] * state + xd_t[:, :, None] * b_h[:, None, :]
        return state, (_lowered(state, hp) * c_h[:, None, :]).sum(-1)

    _, y = jax.lax.scan(step, jnp.zeros((heads, width, B.shape[2]), jnp.float32), (xd, dt * A, Bl, Cl))
    return y + D[:, None] * x


def _mamba_mixer(x, w, hp: dict):
    """x (T, hidden) -> (T, hidden)."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    H, P, N, G = hp["ssm_heads"], hp["ssm_head_dim"], hp["ssm_state"], hp["ssm_groups"]
    inner, bc = H * P, G * N
    zxbcdt = _mm("tc,oc->to", x, w["in_proj_w"], hp)
    z, xbc, dt = zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * bc], zxbcdt[:, 2 * inner + 2 * bc:]
    xbc = jax.nn.silu(causal_conv(xbc, w["conv_w"], w["conv_b"]))
    xs, B, C = xbc[:, :inner], xbc[:, inner:inner + bc], xbc[:, inner + bc:]
    y = recurrence(xs.reshape(t, H, P), jax.nn.softplus(dt + w["dt_bias"]), -jnp.exp(w["A_log"]),
                   B.reshape(t, G, N), C.reshape(t, G, N), w["D"], hp)
    y = _rms(y.reshape(t, inner) * jax.nn.silu(z), w["norm/weight"], hp["eps"])
    return _mm("tv,cv->tc", y, w["out_proj_w"], hp)


def _attention_mixer(x, w, hp: dict):
    t, d, heads, kv = x.shape[0], hp["head_dim"], hp["heads"], hp["kv_heads"]
    qkv = _mm("tc,oc->to", x, w["qkv_w"], hp)
    split = lambda a, n: a.reshape(t, n, d).transpose(1, 0, 2)
    o = attention(split(qkv[:, : heads * d], heads), split(qkv[:, heads * d:(heads + kv) * d], kv),
                  split(qkv[:, (heads + kv) * d:], kv), hp)
    return _mm("tv,cv->tc", o.transpose(1, 0, 2).reshape(t, heads * d), w["proj_w"], hp)


def _swiglu(x, w, hp: dict):
    """(T, hidden) in blocks of rows: ``W_out(silu(a) * b)``."""
    import jax

    def rows(xb):
        h = jax.nn.silu(_mm("tc,hc->th", xb, w["fc_1_w"], hp)) * _mm("tc,hc->th", xb, w["fc_2_w"], hp)
        return _mm("th,ch->tc", h, w["proj_w"], hp)

    return _by_blocks(rows, x, ROW_BLOCK)


def _block(x, w, hp: dict, mixer: str):
    """x (B, T, hidden) -> x: a sequence at a time."""
    import jax

    of = lambda prefix: {k[len(prefix):]: a for k, a in w.items() if k.startswith(prefix)}

    def one(xs):
        n1 = _rms(xs, w["norm_1/weight"], hp["eps"])
        mixed = _mamba_mixer(n1, of("mamba/"), hp) if mixer == "mamba" else _attention_mixer(n1, of("attn/"), hp)
        xs = xs + hp["residual"] * mixed
        return xs + hp["residual"] * _swiglu(_rms(xs, w["norm_2/weight"], hp["eps"]), of("mlp/"), hp)

    return jax.lax.map(one, x)


def forward(weights: dict, idx, config: dict, matmul_inputs=None, last=None):
    """Token ids (B, T) -> float32 logits (B, T, vocab). ``last``: the head for
    the last so many positions only."""
    import jax
    import jax.numpy as jnp

    hp = hyper(config, matmul_inputs)
    unknown = set(hp["mixers"]) - set(MIXERS)
    if unknown:
        raise ValueError(f"no such mixers here: {sorted(unknown)}")
    blocks: dict = {}  # one compiled function a kind of layer; under a trace of the whole they are inlined
    with jax.default_matmul_precision("highest"):
        table = weights["wte"].astype(jnp.float32)
        x = hp["embedding"] * table[idx]
        for i, mixer in enumerate(hp["mixers"]):
            if mixer not in blocks:
                blocks[mixer] = jax.jit(lambda x, w, mixer=mixer: _block(x, w, hp, mixer))
            x = blocks[mixer](x, {path: leaf.astype(jnp.float32) for path, leaf in weights["layers"][i].items()})
        if last is not None:
            x = x[:, -last:]
        x = _rms(x, weights["ln_f/weight"].astype(jnp.float32), hp["eps"]) / hp["logit_divisor"]
        return _mm("btc,vc->btv", x, table, hp)
