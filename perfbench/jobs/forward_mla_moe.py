"""Job ``forward_mla_moe``: job ``forward`` (the forward pass through
``thunder_tpu.jit``, a closed loop with one caller that waits for each reply
and reads the argmax of the last position) for a model with latent attention
and routed experts of which this chip holds a share. What differs from
``forward.Job``: token ids are drawn from a Zipf distribution over the
vocabulary slice, one assignment of ranks to ids a run (text is skewed, the
commonest tokens stay the commonest from call to call, and skewed ids are what
routes unevenly without touching a weight), and that assignment is the one of
``assignments_tried`` from the seed under which this chip gets its even share
of the rows (``even_assignment``: every seed then asks the same work of the
chip); the required operations count the routed experts by the rows an even
router sends here, not by the held experts' sum; the comparison has its own
limits (``perfbench/checks_mla_moe.py``); and the ids of the last units are
kept, so that after the windows the rows routed to each held expert can be
counted for the traced units' own batches."""

from __future__ import annotations

import collections
import gc
import os
import time

import numpy as np

from perfbench import checks, checks_mla_moe, flops_mla_moe, weights
from perfbench.jobs import forward

YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "mscale", "mscale_all_dim")


class Job(forward.Job):
    def __init__(self, cell, **how):
        super().__init__(cell, **how)
        said = tuple(float(self.keys["rope_scaling"][k]) for k in YARN_KEYS)
        if self.keys["rope_scaling"]["type"] != "yarn" or tuple(float(v) for v in self.cfg.yarn) != said:
            raise ValueError(f"the registry's rope scaling {self.cfg.yarn} is not the configuration file's {said}")
        # Zipf over the slice: the id of rank r has weight r**-a, and which id
        # has which rank is one permutation for the whole run, chosen at the
        # run's first batch (``even_assignment`` needs the weights).
        weight = np.arange(1, self.keys["vocab_size"] + 1, dtype=np.float64) ** -float(self.traffic["zipf_exponent"])
        self.cdf = np.cumsum(weight / weight.sum())
        self.id_of_rank = self._count = None
        self.issued = collections.deque(maxlen=cell.traffic["trace_units"])  # as many as run.py traces
        self.counters["tokens_per_unit"] = self.tokens_per_unit
        self.counters["routed_rows"] = self.routed_rows_of_last_units

    def setup(self) -> None:
        """Set-up builds two programs here, the forward and the router's count:
        ``trace_claim_s`` is both's, as the ``phase_*`` spans it is made of are."""
        import thunder_tpu

        super().setup()
        phases = thunder_tpu.compile_stats(self._count).cache_entries[-1].stats.phases
        self.spans["trace_claim_s"] += sum(phases[p] for p in forward.TRACE_CLAIM_PHASES if p in phases)

    def make_batch(self):
        if self.id_of_rank is None:
            self.id_of_rank = self.even_assignment()
        return self.zipf_ids(self.rng, self.id_of_rank)

    def zipf_ids(self, rng, id_of_rank):
        ranks = np.searchsorted(self.cdf, rng.random_sample((self.batch, self.seq)))
        return id_of_rank[np.minimum(ranks, len(self.cdf) - 1)]

    def assignments(self):
        """``assignments_tried`` permutations of the ids from the seed, each
        with one batch drawn under it (a stream of its own: the run's batches
        are drawn as if nothing had been tried)."""
        rng = np.random.RandomState((self.seed, 1))
        for _ in range(self.traffic["assignments_tried"]):
            id_of_rank = rng.permutation(len(self.cdf)).astype(np.int32)
            yield id_of_rank, self.zipf_ids(rng, id_of_rank)

    def even_assignment(self):
        """Which id has which rank. With the weights random from the seed, the
        experts that the few commonest ids choose are as likely held here as
        anywhere, so under one permutation a seed routes 0.37 and another 0.58
        experts a token to this chip where the deployment's mean is 0.5 (the
        commonest id alone is 9.5% of the tokens), and ``tokens_per_s``
        followed that by 1.7%: the luck of one chip of the 16, not the
        system's rate. So the seed makes ``assignments_tried`` permutations,
        the program's own router counts the rows each sends here on one batch,
        and the run takes the one nearest the even share, which is what
        ``flops_per_token``, and so ``mfu``, count. The commonest ids still
        stay the commonest, and the layers and the held experts still get
        uneven rows (``expert_load_max_over_mean``)."""
        t0 = time.perf_counter()
        even = flops_mla_moe.routed_here_per_token(self.keys)
        tried = list(self.assignments())
        loads = np.stack([self.count_rows(self.params, ids).sum(-1) for _, ids in tried]) / self.tokens_per_unit
        best = int(np.argmin(np.abs(loads.mean(1) - even)))
        self.spans["assign_ids_s"] = time.perf_counter() - t0
        print(f"ids: of {len(tried)} assignments of ranks to ids, routed here a token "
              f"{np.sort(loads.mean(1)).round(4).tolist()}; taken {loads[best].mean():.4f} (even {even}), "
              f"by layer {loads[best].round(4).tolist()}", flush=True)
        return tried[best][0]

    def count_rows(self, params, idx) -> np.ndarray:
        """(expert layers, experts held): the rows the program's own routers
        send to each held expert for these ids (``gpt.routed_rows`` through
        ``thunder_tpu.jit``)."""
        if self._count is None:
            import thunder_tpu
            from thunder_tpu.models import gpt

            cfg = self.cfg
            self._count = thunder_tpu.jit(lambda p, i: gpt.routed_rows(p, i, cfg))
        return np.asarray(self._count(params, idx))

    def issue(self, idx):
        self.issued.append(idx)
        return super().issue(idx)

    def flops_per_token(self) -> float:
        return flops_mla_moe.forward_flops_per_token(self.keys, self.seq)

    def routed_rows_of_last_units(self) -> list:
        """[[[rows of a held expert] an expert layer] a unit] for the last
        ``trace_units`` units issued: the traced ones, when a per-layer reader
        asks after a ``--trace 1`` run. Counted on weights made anew from the
        seed: the check let the first ones go."""
        params = weights.make_system_weights(self.shapes, self.seed)
        rows = [self.count_rows(params, idx).tolist() for idx in self.issued]
        del params
        gc.collect()
        self.counters["routed_rows"] = lambda: rows  # counted once, read by three metrics
        return rows

    def check(self, reference) -> dict:
        """As ``forward.Job.check``, under this model's limits.
        ``PERFBENCH_CHECK_PRECISIONS`` (dtype names, comma-separated; unset in
        the driver's runs) is the builder's control of those limits: the
        reference itself with its matmul inputs rounded to each goes through
        the same comparison in the system's place, and its verdict is printed
        under ``reference_at``. It never changes ``ok``."""
        import jax
        import jax.numpy as jnp

        idx = self.first_batch
        picks = np.sort(np.random.RandomState(self.seed).choice(
            self.batch, size=min(self.traffic["check_sequences"], self.batch), replace=False))
        last = min(checks.LOGIT_POSITIONS, self.seq)
        logits = self.jfn(self.params, idx)
        system = np.asarray(logits[jnp.asarray(picks), -last:, :].astype(jnp.float32))
        self.params = logits = None
        gc.collect()
        stacked = weights.make_reference_weights(self.shapes, self.seed)
        sequences = jnp.asarray(idx[picks])

        def last_positions(of, *args):
            return jax.jit(lambda w, i: jax.tree_util.tree_map(lambda out: out[:, -last:], of(w, i, self.keys, *args)))(
                stacked, sequences)

        ref, margin = (np.asarray(out) for out in last_positions(reference.forward_and_margin))
        verdict = checks_mla_moe.compare_logits(system, ref, margin)
        for dtype in filter(None, os.environ.get("PERFBENCH_CHECK_PRECISIONS", "").split(",")):
            verdict.setdefault("reference_at", {})[dtype] = checks_mla_moe.compare_logits(
                np.asarray(last_positions(reference.forward, dtype)), ref, margin)
        return verdict


lower_for = forward.lower_for
