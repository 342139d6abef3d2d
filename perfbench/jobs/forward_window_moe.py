"""Job ``forward_window_moe``: job ``forward`` (the forward pass through
``thunder_tpu.jit``, a closed loop whose caller reads the argmax of the last
position) for a model whose attention layers attend within a window or to
everything, by layer, and whose later layers route to experts that are all held
here, as a long prefill. What differs from ``forward.Job``: the head runs on the
last ``last`` positions only (``gpt.forward(..., last=)``: at 32,768 positions
the logits of every one are 13 GB, which no prefill writes); token ids are drawn
from a Zipf distribution over the whole vocabulary, one assignment of ranks to
ids a run, from the seed, and none is searched for (every expert is held: each
expert layer computes ``num_experts_per_tok`` rows a token whatever the ids);
the weights are drawn a leaf of a layer at a time (``draw``), so that set-up
never holds them twice, and the router's bias at its own size (``BIAS_STD``);
the required operations are ``perfbench/flops_window_moe.py``'s; the comparison
has its own limits (``perfbench/checks_window_moe.py``); the ids of the last
units are kept, so that after the windows the program's own routers can count,
for the traced units' batches, the rows each expert got and the choices the bias
changed; the program's own mask says which tiles a window layer's kernel visits;
and the compiled program's text says which instruction lies in which region of
the model's code, for the readers of the device trace."""

from __future__ import annotations

import collections
import functools
import gc
import os
import time
import zlib

import numpy as np

from perfbench import checks_window_moe, flops_window_moe, weights
from perfbench.jobs import forward, forward_sparse_linear, gpt_model
from perfbench.layer_metrics import _regions

# The configuration file's ``assumed``: the published bias is learned; drawn here N(0, 0.1), float32.
BIAS_STD = 0.1
BIAS_LEAF = "router_bias"
REGIONS = ("attn.window", "attn.full")
_STARTS_A_LINE = ("  ", "%", "ENTRY", "ROOT", "HloModule")
BLOCK_LISTS = ("dense_blocks/*/", "moe_blocks/*/", "blocks/*/")


@functools.lru_cache(maxsize=None)
def _leaf_drawn(shape: tuple, dtype, mean: float, std: float):
    """One compiled draw a shape: the seed, the leaf's kind and its layer are arguments."""
    import jax
    import jax.numpy as jnp

    def one(seed, kind, layer):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), kind), layer)
        return (jnp.float32(mean) + jnp.float32(std) * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    return jax.jit(one)


def draw(shape_tree, seed: int):
    """The program's parameter tree from the seed, a leaf of a layer at a time:
    matrices N(0, ``weights.STD``), norm scales 1 + N(0, ``weights.STD``), the
    routers' biases N(0, ``BIAS_STD``), as ``perfbench/weights.py`` draws them but
    never stacked: at 10.29 GB a stacked draw unstacked holds the model twice.
    The system and the reference are handed the same tree."""
    import jax

    leaves = []
    for kind, layer, leaf in weights.leaf_kinds(shape_tree):
        mean, std = (1.0, weights.STD) if kind.endswith("/weight") else (0.0, BIAS_STD if BIAS_LEAF in kind else weights.STD)
        leaves.append(_leaf_drawn(tuple(leaf.shape), leaf.dtype, mean, std)(
            np.uint32(seed), np.uint32(zlib.crc32(kind.encode()) & 0x7FFFFFFF), np.uint32(0 if layer is None else layer + 1)))
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(shape_tree), leaves)


def an_instruction_a_line(hlo_text: str) -> str:
    """The compiled program's text with every instruction on one line. A splash
    call's ``frontend_attributes`` hold a JSON string with line breaks, so its
    ``metadata={op_name=...}`` stands two lines below its name, where
    ``_regions.of_instructions``, which reads a line at a time, does not find it
    (my chip run, PR 38: the window region read 9 ms, the fusions in front of
    the calls, and its roofline 353%)."""
    out: list[str] = []
    for line in hlo_text.splitlines():
        if out and line and line != "}" and not line.startswith(_STARTS_A_LINE):
            out[-1] += " " + line
        else:
            out.append(line)
    return "\n".join(out)


def for_reference(tree, dense_layers: int) -> dict:
    """The tree as the plain reference takes it: the leaves outside the layers by
    their paths, and ``"layers"``, a layer's leaves by their paths within it."""
    import jax

    out: dict = {"layers": collections.defaultdict(dict)}
    for (kind, layer, _), leaf in zip(weights.leaf_kinds(tree), jax.tree_util.tree_leaves(tree)):
        if layer is None:
            out[kind] = leaf
            continue
        prefix = next(p for p in BLOCK_LISTS if kind.startswith(p))
        out["layers"][layer + (dense_layers if prefix == "moe_blocks/*/" else 0)][kind[len(prefix):]] = leaf
    out["layers"] = [out["layers"][i] for i in range(len(out["layers"]))]
    return out


class Job(forward_sparse_linear.Job):
    """That job's batches, ``issue`` and ``compiled``; this model's weights,
    counters, required operations and comparison."""

    def __init__(self, cell, **how):
        forward.Job.__init__(self, cell, **how)  # not the parent's: it reads the sparse model's keys
        k, depth = self.keys, self.keys["num_hidden_layers"]
        said, runs = tuple(k["layer_types"][:depth]), tuple(self.cfg.layer_mixer(i) for i in range(depth))
        if said != runs:
            raise ValueError(f"the registry's mixers {runs} are not the configuration file's {said}")
        self.last = self.traffic["last"]
        # Zipf over the vocabulary: the id of rank r has weight r**-a, and which
        # id has which rank is one permutation for the whole run.
        weight = np.arange(1, k["vocab_size"] + 1, dtype=np.float64) ** -float(self.traffic["zipf_exponent"])
        self.cdf = np.cumsum(weight / weight.sum())
        self.id_of_rank = np.random.RandomState((self.seed, 1)).permutation(len(self.cdf)).astype(np.int32)
        self.issued = collections.deque(maxlen=cell.traffic["trace_units"])  # as many as run.py traces
        self._compiled = self._count = self._counted = None
        kinds = k["layer_types"][:depth]
        shape = (self.seq, k["num_attention_heads"], k["num_key_value_heads"], k["head_dim"])
        self.counters["tokens_per_unit"] = self.tokens_per_unit
        # (operations, bytes) a call of each attention kind's layers, as the equations require them.
        self.counters["mixer_work"] = {
            "window_attention": [self.batch * kinds.count("sliding_attention") * x
                                 for x in flops_window_moe.attention(*shape, k["sliding_window"])],
            "full_attention": [self.batch * kinds.count("full_attention") * x for x in flops_window_moe.attention(*shape)]}
        self.counters["region_of_instruction"] = lambda: _regions.of_instructions(
            an_instruction_a_line(self.compiled().as_text()), REGIONS)
        self.counters["routed_rows"] = lambda: self.router_counts_of_last_units()[0]
        self.counters["bias_changed_choices"] = lambda: self.router_counts_of_last_units()[1]
        self.counters["window_tiles"] = self.window_tiles

    def setup(self) -> None:
        """``forward.Job.setup`` with the weights drawn a layer at a time and the
        head on the last ``last`` positions."""
        import jax
        import jax.numpy as jnp

        import thunder_tpu
        from thunder_tpu.models import gpt

        cfg, last = self.cfg, self.last
        t0 = time.perf_counter()
        self.params = draw(self.shapes, self.seed)
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
        return flops_window_moe.forward_flops_per_token(self.keys, self.seq, self.last)

    def window_tiles(self):
        """(score elements of the tiles the claimed kernel's mask visits, pairs
        the equations require) of one head of one window layer at this sequence,
        by the program's own mask (``flashex.window_tiles``); ``None`` where no
        layer has a window longer than the sequence."""
        from thunder_tpu.executors import flashex

        window = self.keys["sliding_window"]
        if self.seq <= window:
            return None
        return [flashex.window_tiles(self.seq, window), flops_window_moe.window_pairs(self.seq, window)]

    def router_counts_of_last_units(self):
        """([[[rows of an expert] an expert layer] a unit], the share of the
        (token, choice) pairs the bias changed) for the last ``trace_units``
        units issued: the traced ones, when a per-layer reader asks after a
        ``--trace 1`` run; ``gpt.router_counts`` through ``thunder_tpu.jit``, the
        program's own routers on these ids. Counted once, on weights made anew
        from the seed: the check let the first ones go."""
        if self._counted is None:
            import thunder_tpu
            from thunder_tpu.models import gpt

            cfg = self.cfg
            self._count = thunder_tpu.jit(lambda p, i: gpt.router_counts(p, i, cfg))
            params = draw(self.shapes, self.seed)
            counts = [tuple(np.asarray(c) for c in self._count(params, idx)) for idx in self.issued]
            del params
            gc.collect()
            pairs = sum(int(rows.sum()) for rows, _ in counts)
            self._counted = ([rows.tolist() for rows, _ in counts],
                             sum(int(changed.sum()) for _, changed in counts) / pairs if pairs else None)
        return self._counted

    def check(self, reference) -> dict:
        """Logits of the last ``checks_window_moe.LOGIT_POSITIONS`` positions of
        the one seeded sequence of the first batch, what the timed program gives
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
        last = min(checks_window_moe.LOGIT_POSITIONS, self.last)
        logits = self.jfn(self.params, idx)
        system = np.asarray(logits[jnp.asarray(picks), -last:, :].astype(jnp.float32))
        self.params = logits = None
        gc.collect()
        tree = for_reference(draw(self.shapes, self.seed), self.keys["num_dense_layers"])
        sequences = jnp.asarray(idx[picks])

        def last_positions(of, *args):  # the reference compiles half a layer at a time: the whole does not fit
            return of(tree, sequences, self.keys, *args, last=last)

        ref, margin = (np.asarray(out) for out in last_positions(reference.forward_and_margin))
        verdict = checks_window_moe.compare_logits(system, ref, margin)
        for dtype in filter(None, os.environ.get("PERFBENCH_CHECK_PRECISIONS", "").split(",")):
            verdict.setdefault("reference_at", {})[dtype] = checks_window_moe.compare_logits(
                np.asarray(last_positions(reference.forward, dtype)), ref, margin)
        return verdict


lower_for = forward_sparse_linear.lower_for  # the forward with the head on the traffic's last positions
