"""Job ``forward_conv_moe``: job ``forward`` (the forward pass through
``thunder_tpu.jit``, a closed loop with one caller that waits for each reply
and reads the argmax of the last position) for a model whose layers mix by a
gated short convolution or by attention and whose router has a bias, with
every expert held here. What differs from ``forward.Job``: token ids are drawn
from a Zipf distribution over the whole vocabulary, one assignment of ranks to
ids a run, from the seed (text is skewed, the commonest tokens stay the
commonest from call to call, and skewed ids are what routes unevenly without
touching a weight); no assignment is searched for, since every layer computes
``num_experts_per_tok`` rows a token whatever the ids; the router's bias is
drawn at its own size (``BIAS_STD``); the required operations are
``perfbench/flops_conv_moe.py``'s; the comparison has its own limits
(``perfbench/checks_conv_moe.py``); and the ids of the last units are kept, so
that after the windows the program's own routers can count, for the traced
units' batches, the rows each expert got and the choices the bias changed."""

from __future__ import annotations

import collections
import gc
import os

import numpy as np

from perfbench import checks_conv_moe, flops_conv_moe, weights
from perfbench.jobs import forward

# The configuration file's ``assumed``: the published bias is learned; drawn
# here N(0, 0.1), the weights' draw times BIAS_STD / weights.STD.
BIAS_STD = 0.1
BIAS_LEAF = "router_bias"


def with_bias_drawn(tree):
    """The weights' draw with every router bias at ``BIAS_STD``: the program's
    tree or the reference's stacked kinds."""
    import jax

    scale = BIAS_STD / weights.STD
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf * scale if BIAS_LEAF in str(path[-1]) else leaf, tree)


class Job(forward.Job):
    def __init__(self, cell, **how):
        super().__init__(cell, **how)
        depth = self.keys["num_hidden_layers"]
        said, runs = tuple(self.keys["layer_types"][:depth]), tuple(self.cfg.layer_mixer(i) for i in range(depth))
        if said != runs:
            raise ValueError(f"the registry's mixers {runs} are not the configuration file's {said}")
        # Zipf over the vocabulary: the id of rank r has weight r**-a, and which
        # id has which rank is one permutation for the whole run.
        weight = np.arange(1, self.keys["vocab_size"] + 1, dtype=np.float64) ** -float(self.traffic["zipf_exponent"])
        self.cdf = np.cumsum(weight / weight.sum())
        self.id_of_rank = None
        self.issued = collections.deque(maxlen=cell.traffic["trace_units"])  # as many as run.py traces
        self._count = self._counted = None
        self.counters["tokens_per_unit"] = self.tokens_per_unit
        self.counters["routed_rows"] = lambda: self.router_counts_of_last_units()[0]
        self.counters["bias_changed_choices"] = lambda: self.router_counts_of_last_units()[1]

    def make_batch(self):
        if self.id_of_rank is None:
            # The run's first batch: set-up has just drawn the weights and has called nothing yet.
            self.params = with_bias_drawn(self.params)
            self.id_of_rank = np.random.RandomState((self.seed, 1)).permutation(len(self.cdf)).astype(np.int32)
        ranks = np.searchsorted(self.cdf, self.rng.random_sample((self.batch, self.seq)))
        return self.id_of_rank[np.minimum(ranks, len(self.cdf) - 1)]

    def issue(self, idx):
        self.issued.append(idx)
        return super().issue(idx)

    def flops_per_token(self) -> float:
        return flops_conv_moe.forward_flops_per_token(self.keys, self.seq)

    def router_counts(self, params, idx):
        """(rows (expert layers, experts), (token, choice) pairs the bias
        changed (expert layers,)): ``gpt.router_counts`` through
        ``thunder_tpu.jit``, the program's own routers on these ids."""
        if self._count is None:
            import thunder_tpu
            from thunder_tpu.models import gpt

            cfg = self.cfg
            self._count = thunder_tpu.jit(lambda p, i: gpt.router_counts(p, i, cfg))
        rows, changed = self._count(params, idx)
        return np.asarray(rows), None if changed is None else np.asarray(changed)  # no bias, no such count

    def router_counts_of_last_units(self):
        """([[[rows of an expert] an expert layer] a unit], the share of the
        (token, choice) pairs the bias changed) for the last ``trace_units``
        units issued: the traced ones, when a per-layer reader asks after a
        ``--trace 1`` run. Counted once, on weights made anew from the seed:
        the check let the first ones go."""
        if self._counted is None:
            params = with_bias_drawn(weights.make_system_weights(self.shapes, self.seed))
            counts = [self.router_counts(params, idx) for idx in self.issued]
            del params
            gc.collect()
            pairs = sum(int(rows.sum()) for rows, _ in counts)
            biased = pairs and all(changed is not None for _, changed in counts)
            self._counted = ([rows.tolist() for rows, _ in counts],
                             sum(int(changed.sum()) for _, changed in counts) / pairs if biased else None)
        return self._counted

    def check(self, reference) -> dict:
        """Logits of the last ``checks_conv_moe.LOGIT_POSITIONS`` positions of
        one seeded sequence of the first batch, what the timed program gives for
        it, against the reference's forward of that sequence, under this
        model's limits. ``PERFBENCH_CHECK_PRECISIONS`` (dtype names,
        comma-separated; unset in the driver's runs) is the builder's control of
        those limits: the reference itself with its matmul inputs rounded to
        each goes through the same comparison in the system's place, and its
        verdict is printed under ``reference_at``. It never changes ``ok``."""
        import jax.numpy as jnp

        idx = self.first_batch
        picks = np.sort(np.random.RandomState(self.seed).choice(
            self.batch, size=min(self.traffic["check_sequences"], self.batch), replace=False))
        last = min(checks_conv_moe.LOGIT_POSITIONS, self.seq)
        logits = self.jfn(self.params, idx)
        system = np.asarray(logits[jnp.asarray(picks), -last:, :].astype(jnp.float32))
        self.params = logits = None
        gc.collect()
        stacked = with_bias_drawn(weights.make_reference_weights(self.shapes, self.seed))
        sequences = jnp.asarray(idx[picks])

        def last_positions(of, *args):  # the reference compiles a layer at a time: the whole does not fit
            return of(stacked, sequences, self.keys, *args, last=last)

        ref, margin = (np.asarray(out) for out in last_positions(reference.forward_and_margin))
        verdict = checks_conv_moe.compare_logits(system, ref, margin)
        for dtype in filter(None, os.environ.get("PERFBENCH_CHECK_PRECISIONS", "").split(",")):
            verdict.setdefault("reference_at", {})[dtype] = checks_conv_moe.compare_logits(
                np.asarray(last_positions(reference.forward, dtype)), ref, margin)
        return verdict


lower_for = forward.lower_for
