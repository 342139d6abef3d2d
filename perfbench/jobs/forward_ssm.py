"""Job ``forward_ssm``: job ``forward`` (the forward pass through
``thunder_tpu.jit``, a closed loop whose caller reads the argmax of the last
position) for a model whose layers mix by a state-space recurrence whose decay
each token sets (Mamba-2) or by causal attention, as a long prefill. What it
takes from ``forward_sparse_linear.Job``: the head on the last ``last``
positions, the Zipf ids with one assignment of ranks to ids a run, the ids of the
last units kept, the executable compiled once. What differs: the weights are
drawn a leaf of a layer at a time (``forward_window_moe.draw``: 6.4 GB are never
held twice), **the state-space parameters as Mamba-2 draws them** and two
projections larger, so that the comparison hears the recurrence and the
attention layers (``with_ssm_draw``); the required operations are ``perfbench/flops_ssm.py``'s; the
comparison has its own limit (``perfbench/checks_ssm.py``); the regions are this
model's; and the chunk the program published says what its chunked form performs
beyond the recurrence."""

from __future__ import annotations

import collections
import gc
import math
import os
import time

import numpy as np

from perfbench import checks_ssm, flops_ssm, weights
from perfbench.jobs import forward, forward_sparse_linear, forward_window_moe, gpt_model
from perfbench.layer_metrics import _regions

MIXERS = {"mamba": "mamba", "attention": "full_attention"}  # published name -> the program's
REGIONS = ("ssm.conv", "ssm.scan", "ssm.gate_norm", "attn.full")
# The configuration file's ``assumed``. With every leaf at N(0, 0.02) ``dt`` is softplus(0) = 0.69 and ``A`` is -1 in
# every head, a state halves each step; and the convolution's output is a fiftieth of its input, so that B and C are
# 0.02 and the state's term 5e-4 of the ``D x`` beside it (one layer at the published widths, float32): a program that
# dropped the recurrence whole would read as rounding. So the state-space leaves are drawn as Mamba-2 draws them, and
# B and C four times larger, which puts the state's term beside ``D x`` and neither under the other. And under
# ``attention_multiplier`` = 1/64 a draw at 0.02 gives scores that spread by 0.1: attention is a mean over every earlier
# value and a layer's output 1/200 of a Mamba-2 layer's; with q and k eight times larger a query attends to a few keys.
A_RANGE = (1.0, 16.0)      # A = -U(1, 16)
DT_RANGE = (0.001, 0.1)    # a step log-uniform in [0.001, 0.1], through the inverse of its softplus
CONV_BOUND = 0.5           # the taps U(-1/sqrt(K), 1/sqrt(K)), K = 4: torch's Conv1d on a depthwise filter
BC_SCALE = 4.0             # in_proj's rows for B and C times this (a power of two: exact in bf16)
QK_SCALE = 8.0             # qkv_w's rows for q and k times this


def with_ssm_draw(tree, keys: dict):
    """The weights' draw with each Mamba-2 layer's state-space leaves at their
    own distributions, from the same seed: a leaf drawn N(0, ``weights.STD``)
    goes through its own distribution function to a uniform u, and ``A_log =
    log(1 + 15 u)``, ``dt_bias = softplus^-1(0.001 * 100^u)``, ``conv_w = u - 0.5``;
    ``D = 1``; ``in_proj_w``'s rows that make B and C times ``BC_SCALE``; and an
    attention layer's rows of ``qkv_w`` that make q and k times ``QK_SCALE``.
    Each element by itself, so any layout of the same draw gets the same numbers.
    No other leaf changes."""
    import jax
    import jax.numpy as jnp

    inner = keys["mamba_n_heads"] * keys["mamba_d_head"]
    bc = slice(2 * inner, 2 * inner + 2 * keys["mamba_n_groups"] * keys["mamba_d_state"])
    heads, kv = keys["num_attention_heads"], keys["num_key_value_heads"]
    qk = slice(0, (heads + kv) * (keys["hidden_size"] // heads))

    def uniform(a):
        return 0.5 * (1.0 + jax.lax.erf(a.astype(jnp.float32) / (weights.STD * math.sqrt(2.0))))

    def leaf(path, a):
        names = [getattr(k, "key", None) for k in path]
        if names[-2:] == ["attn", "qkv_w"]:
            return a.at[qk].multiply(QK_SCALE)
        if "mamba" not in names:
            return a
        if names[-1] == "A_log":
            return jnp.log(A_RANGE[0] + (A_RANGE[1] - A_RANGE[0]) * uniform(a)).astype(a.dtype)
        if names[-1] == "dt_bias":
            step = jnp.exp(math.log(DT_RANGE[0]) + math.log(DT_RANGE[1] / DT_RANGE[0]) * uniform(a))
            return (step + jnp.log(-jnp.expm1(-step))).astype(a.dtype)
        if names[-1] == "conv_w":
            return (2.0 * CONV_BOUND * (uniform(a) - 0.5)).astype(a.dtype)
        if names[-1] == "in_proj_w":
            return a.at[bc].multiply(BC_SCALE)
        return jnp.ones_like(a) if names[-1] == "D" else a

    return jax.tree_util.tree_map_with_path(leaf, tree)


class Job(forward_sparse_linear.Job):
    """That job's batches, ``issue`` and ``compiled``; this model's weights,
    counters, required operations and comparison."""

    def __init__(self, cell, **how):
        forward.Job.__init__(self, cell, **how)  # not the parent's: it reads the sparse model's keys
        k, depth = self.keys, self.keys["num_hidden_layers"]
        said, runs = tuple(MIXERS[m] for m in k["layer_types"][:depth]), tuple(self.cfg.layer_mixer(i) for i in range(depth))
        if said != runs:
            raise ValueError(f"the registry's mixers {runs} are not the configuration file's {said}")
        if k["mamba_expand"] * k["hidden_size"] != k["mamba_n_heads"] * k["mamba_d_head"]:
            raise ValueError("mamba_expand * hidden_size is not mamba_n_heads * mamba_d_head")
        self.last = self.traffic["last"]
        # Zipf over the vocabulary: the id of rank r has weight r**-a, and which
        # id has which rank is one permutation for the whole run.
        weight = np.arange(1, k["vocab_size"] + 1, dtype=np.float64) ** -float(self.traffic["zipf_exponent"])
        self.cdf = np.cumsum(weight / weight.sum())
        self.id_of_rank = np.random.RandomState((self.seed, 1)).permutation(len(self.cdf)).astype(np.int32)
        self.issued = collections.deque(maxlen=cell.traffic["trace_units"])  # as many as run.py traces
        self._compiled = None
        kinds = k["layer_types"][:depth]
        ssm = (k["mamba_n_heads"], k["mamba_d_head"], k["mamba_d_state"], k["mamba_n_groups"])
        channels = k["mamba_n_heads"] * k["mamba_d_head"] + 2 * k["mamba_n_groups"] * k["mamba_d_state"]
        layers = self.batch * kinds.count("mamba")
        self.counters["tokens_per_unit"] = self.tokens_per_unit
        # (operations, bytes) a call of each part of the Mamba-2 layers, as the equations require them.
        self.counters["mixer_work"] = {
            "ssm_scan": [layers * x for x in flops_ssm.ssm_scan(self.seq, *ssm)],
            "ssm_conv": [layers * x for x in flops_ssm.ssm_conv(self.seq, channels, k["mamba_d_conv"])]}
        self.counters["region_of_instruction"] = lambda: _regions.of_instructions(
            forward_window_moe.an_instruction_a_line(self.compiled().as_text()), REGIONS)
        # (what the chunked form performs at the program's own chunk, what the recurrence requires), a call
        self.counters["ssm_chunk_ops"] = lambda: [layers * flops_ssm.chunked_ops(self.seq, self.cfg.ssm_chunk_size, *ssm),
                                                  self.counters["mixer_work"]["ssm_scan"][0]]

    def draw(self):
        """The program's tree from the seed, with the state-space parameters at their own draw."""
        return with_ssm_draw(forward_window_moe.draw(self.shapes, self.seed), self.keys)

    def setup(self) -> None:
        """``forward.Job.setup`` with the weights drawn a layer at a time and the
        head on the last ``last`` positions."""
        import jax
        import jax.numpy as jnp

        import thunder_tpu
        from thunder_tpu.models import gpt

        cfg, last = self.cfg, self.last
        t0 = time.perf_counter()
        self.params = self.draw()
        jax.block_until_ready(self.params)
        self.spans["weights_s"] = time.perf_counter() - t0

        self.jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg, last=last))
        self.read_back = jax.jit(lambda logits: (jnp.argmax(logits[:, -1, :], axis=-1),
                                                 jnp.isfinite(logits[:, -1, :]).all()))
        self.rng = np.random.RandomState(self.seed)
        self.first_batch = self.make_batch()
        t0 = time.perf_counter()
        self.wait(self.issue(self.first_batch))
        self.spans["compile_first_call_s"] = time.perf_counter() - t0
        self.entry = thunder_tpu.compile_stats(self.jfn).cache_entries[-1]
        phases = self.entry.stats.phases
        self.spans["trace_claim_s"] = sum(phases[p] for p in forward.TRACE_CLAIM_PHASES if p in phases)
        self.counters["kernels_claimed"] = gpt_model.kernels_claimed(thunder_tpu.last_traces(self.jfn)[-1])
        for _ in range(self.traffic["warmup_units"]):
            self.wait(self.issue(self.make_batch()))
        self.non_finite = 0

    def flops_per_token(self) -> float:
        return flops_ssm.forward_flops_per_token(self.keys, self.seq, self.last)

    def check(self, reference) -> dict:
        """Logits of the last ``checks_ssm.LOGIT_POSITIONS`` positions of the one
        seeded sequence of the first batch, what the timed program gives for it,
        against the reference's forward of that sequence, under this model's
        limit. ``PERFBENCH_CHECK_PRECISIONS`` (dtype names, comma-separated; unset
        in the driver's runs) is the builder's control of that limit: the
        reference itself with its matmul inputs rounded to each goes through the
        same comparison in the system's place, and its verdict is printed under
        ``reference_at``. It never changes ``ok``."""
        import jax.numpy as jnp

        idx = self.first_batch
        picks = np.sort(np.random.RandomState(self.seed).choice(
            self.batch, size=min(self.traffic["check_sequences"], self.batch), replace=False))
        last = min(checks_ssm.LOGIT_POSITIONS, self.last)
        logits = self.jfn(self.params, idx)
        system = np.asarray(logits[jnp.asarray(picks), -last:, :].astype(jnp.float32))
        self.params = logits = None
        gc.collect()
        tree = forward_window_moe.for_reference(self.draw(), 0)
        sequences = jnp.asarray(idx[picks])

        def last_positions(*args):  # the reference compiles a layer at a time: the whole does not fit in float32
            return np.asarray(reference.forward(tree, sequences, self.keys, *args, last=last))

        ref = last_positions()
        verdict = checks_ssm.compare_logits(system, ref)
        for dtype in filter(None, os.environ.get("PERFBENCH_CHECK_PRECISIONS", "").split(",")):
            verdict.setdefault("reference_at", {})[dtype] = checks_ssm.compare_logits(last_positions(dtype), ref)
        return verdict


lower_for = forward_sparse_linear.lower_for  # the forward with the head on the traffic's last positions
