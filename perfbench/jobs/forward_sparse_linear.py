"""Job ``forward_sparse_linear``: job ``forward`` (the forward pass through
``thunder_tpu.jit``, a closed loop whose caller reads the argmax of the last
position) for a model whose layers mix by block-sparse attention whose blocks
each query chooses, or by linear attention with a decay a head, as a long
prefill. What differs from ``forward.Job``: the head runs on the last ``last``
positions only (``gpt.forward(..., last=)``: at 32,768 positions the logits of
every one are 4.8 GB, which no prefill writes); token ids are drawn from a Zipf
distribution over the whole vocabulary, one assignment of ranks to ids a run,
from the seed (repeated ids give the rope-less sparse layers equal keys to
find, as text does); the required operations are
``perfbench/flops_sparse_linear.py``'s; the comparison has its own limits
(``perfbench/checks_sparse_linear.py``), and so that they can tell a sparse
layer that attends to the wrong keys from rounding, the sparse layers' output
projection is drawn at ``SPARSE_OUT_SCALE`` times the other matrices' size; the
ids of the last units are kept, so
that after the windows the program's own selection can count, for the traced
units' batches, the blocks each tile of queries chose between them; and the
compiled program's text says which instruction lies in which region of the
model's code, for the readers of the device trace."""

from __future__ import annotations

import collections
import gc
import os
import time

import numpy as np

from perfbench import checks_sparse_linear, flops_sparse_linear, weights
from perfbench.jobs import forward, gpt_model
from perfbench.layer_metrics import _regions

MIXERS = {"minicpm4": "sparse_attention", "lightning-attn": "linear_attention"}  # published name -> the program's
# The configuration file's ``assumed``: attention with random weights is diffuse, a sparse layer's output a mean of
# some 4,096 values near zero, and at N(0, 0.02) its output projection adds a hundredth of what a linear layer
# (whose output is normed) or an MLP adds: no comparison of logits hears it. Drawn four times larger it is heard.
SPARSE_OUT_SCALE = 4.0
SPARSE_OUT_LEAF = ("sparse_attn", "proj_w")


def with_mixers_heard(tree):
    """The weights' draw with every sparse layer's output projection times
    ``SPARSE_OUT_SCALE`` (a power of two: exact in bf16): the program's tree or
    the reference's stacked kinds."""
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf * SPARSE_OUT_SCALE if all(part in str(path) for part in SPARSE_OUT_LEAF) else leaf, tree)


SPARSE_FIELDS = {"kernel_size": "sparse_kernel_size", "kernel_stride": "sparse_kernel_stride",
                 "block_size": "sparse_block_size", "topk": "sparse_topk", "init_blocks": "sparse_init_blocks",
                 "window_size": "sparse_window_size", "dense_len": "sparse_dense_len"}


class Job(forward.Job):
    def __init__(self, cell, **how):
        super().__init__(cell, **how)
        depth = self.keys["num_hidden_layers"]
        said = tuple(MIXERS[m] for m in self.keys["mixer_types"][:depth])
        runs = tuple(self.cfg.layer_mixer(i) for i in range(depth))
        if said != runs:
            raise ValueError(f"the registry's mixers {runs} are not the configuration file's {said}")
        differ = {k: (getattr(self.cfg, f), self.keys["sparse_config"][k]) for k, f in SPARSE_FIELDS.items()
                  if getattr(self.cfg, f) != self.keys["sparse_config"][k]}
        if differ:
            raise ValueError(f"the registry's sparse constants and the configuration file's disagree: {differ}")
        self.last = self.traffic["last"]
        # Zipf over the vocabulary: the id of rank r has weight r**-a, and which
        # id has which rank is one permutation for the whole run.
        weight = np.arange(1, self.keys["vocab_size"] + 1, dtype=np.float64) ** -float(self.traffic["zipf_exponent"])
        self.cdf = np.cumsum(weight / weight.sum())
        self.id_of_rank = np.random.RandomState((self.seed, 1)).permutation(len(self.cdf)).astype(np.int32)
        self.issued = collections.deque(maxlen=cell.traffic["trace_units"])  # as many as run.py traces
        self._compiled = self._count = self._counted = None
        k = self.keys
        self.counters["tokens_per_unit"] = self.tokens_per_unit
        # (operations, bytes) a call of each mixer's layers, as the equations require them.
        mixers = k["mixer_types"][:depth]
        self.counters["mixer_work"] = {
            "sparse_attention": [self.batch * mixers.count("minicpm4") * x for x in flops_sparse_linear.sparse_attention(
                self.seq, k["num_attention_heads"], k["num_key_value_heads"], k["head_dim"], k["sparse_config"])],
            "linear_attention": [self.batch * mixers.count("lightning-attn") * x for x in
                                 flops_sparse_linear.linear_attention(self.seq, k["lightning_nh"], k["lightning_head_dim"])]}
        self.counters["region_of_instruction"] = lambda: _regions.of_instructions(self.compiled().as_text())
        self.counters["sparse_tile_union"] = self.tile_union_of_last_units

    def setup(self) -> None:
        """``forward.Job.setup`` with the head on the last ``last`` positions."""
        import jax
        import jax.numpy as jnp

        import thunder_tpu
        from thunder_tpu.models import gpt

        cfg, last = self.cfg, self.last
        t0 = time.perf_counter()
        self.params = with_mixers_heard(weights.make_system_weights(self.shapes, self.seed))
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

    def make_batch(self):
        ranks = np.searchsorted(self.cdf, self.rng.random_sample((self.batch, self.seq)))
        return self.id_of_rank[np.minimum(ranks, len(self.cdf) - 1)]

    def issue(self, idx):
        self.issued.append(idx)
        return super().issue(idx)

    def flops_per_token(self) -> float:
        return flops_sparse_linear.forward_flops_per_token(self.keys, self.seq, self.last)

    def compiled(self):
        """Once: the memory report and the regions' reader ask for the same executable."""
        if self._compiled is None:
            self._compiled = super().compiled()
        return self._compiled

    def tile_union_of_last_units(self):
        """The mean, over the last ``trace_units`` units issued (the traced ones,
        when a per-layer reader asks after a ``--trace 1`` run), their sparse
        layers, key-value heads and tiles of ``gpt.SPARSE_TILE`` consecutive
        queries, of the distinct blocks a tile's queries chose between them, over
        ``topk``: ``gpt.sparse_selection_counts`` through ``thunder_tpu.jit``,
        the program's own selection on these ids. Counted once, on weights made
        anew from the seed: the check let the first ones go. ``None`` where no
        layer selects (a sequence under ``dense_len``)."""
        if self._counted is None:
            import thunder_tpu
            from thunder_tpu.models import gpt

            cfg = self.cfg
            if self.seq < cfg.sparse_dense_len or not self.issued:
                return None
            self._count = thunder_tpu.jit(lambda p, i: gpt.sparse_selection_counts(p, i, cfg))
            params = with_mixers_heard(weights.make_system_weights(self.shapes, self.seed))
            means = [float(np.asarray(self._count(params, idx)).mean()) for idx in self.issued]
            del params
            gc.collect()
            self._counted = float(np.mean(means)) / cfg.sparse_topk
        return self._counted

    def check(self, reference) -> dict:
        """Logits of the last ``checks_sparse_linear.LOGIT_POSITIONS`` positions
        of one seeded sequence of the first batch, what the timed program gives
        for it, against the reference's forward of that sequence, under this
        model's limits. ``PERFBENCH_CHECK_PRECISIONS`` (dtype names,
        comma-separated; unset in the driver's runs) is the builder's control of
        those limits: the reference itself with its matmul inputs rounded to
        each goes through the same comparison in the system's place, and its
        verdict is printed under ``reference_at``. It never changes ``ok``."""
        import jax.numpy as jnp

        idx = self.first_batch
        picks = np.sort(np.random.RandomState(self.seed).choice(
            self.batch, size=min(self.traffic["check_sequences"], self.batch), replace=False))
        last = min(checks_sparse_linear.LOGIT_POSITIONS, self.last)
        logits = self.jfn(self.params, idx)
        system = np.asarray(logits[jnp.asarray(picks), -last:, :].astype(jnp.float32))
        self.params = logits = None
        gc.collect()
        stacked = with_mixers_heard(weights.make_reference_weights(self.shapes, self.seed))
        sequences = jnp.asarray(idx[picks])

        def last_positions(*args):  # the reference compiles a layer at a time: the whole does not fit
            return np.asarray(reference.forward(stacked, sequences, self.keys, *args, last=last))

        ref = last_positions()
        verdict = checks_sparse_linear.compare_logits(system, ref)
        for dtype in filter(None, os.environ.get("PERFBENCH_CHECK_PRECISIONS", "").split(",")):
            verdict.setdefault("reference_at", {})[dtype] = checks_sparse_linear.compare_logits(last_positions(dtype), ref)
        return verdict


def lower_for(cell, keys: dict, batch: int, seq: int, topo):
    """``forward.lower_for`` with the head on the traffic's last positions."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from perfbench.rehearse import with_sharding
    from thunder_tpu.api import trace_program
    from thunder_tpu.executors.passes import transform_for_execution
    from thunder_tpu.extend import resolve_executors
    from thunder_tpu.models import gpt
    from thunder_tpu.transforms.common import dce

    cfg, last = gpt_model.gpt_config(keys), cell.traffic["last"]
    shapes = gpt_model.param_shapes(cfg)
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    _, comp = trace_program(lambda p, i: gpt.forward(p, i, cfg, last=last), (shapes, tokens), {})
    run = transform_for_execution(dce(comp), resolve_executors(None)).python_callable()
    one = SingleDeviceSharding(topo.devices[0])
    flat = jax.tree_util.tree_leaves((shapes, tokens))
    return jax.jit(run).lower(*(with_sharding(a, one) for a in flat))
