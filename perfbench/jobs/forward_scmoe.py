"""Job ``forward_scmoe``: job ``forward`` (the forward pass through
``thunder_tpu.jit``, a closed loop whose caller reads the argmax of the last
position) for a model of double layers, two latent-attention sublayers and two
dense FFNs with one routed layer on a shortcut across them, whose softmax router
has zero-compute experts beside real ones of which this chip holds a share, as
a long prefill. What differs from ``forward.Job``: the head runs on the last
``last`` positions only (``gpt.forward(..., last=)``); token ids are drawn from
a Zipf distribution over the vocabulary slice, one assignment of ranks to ids a
run, and that assignment is the one of ``assignments_tried`` from the seed under
which this chip gets its even share of the rows (``forward_mla_moe``'s
``even_assignment`` and its reasons); the weights are drawn a leaf of a layer at
a time (``draw``), the router's bias at the size the configuration file gives
(``router_bias_std``) and the held experts' down projection ``experts_down_scale``
times the other matrices' size, so that the comparison hears them; the required
operations are ``perfbench/flops_scmoe.py``'s; the comparison has its own limits
(``perfbench/checks_scmoe.py``); the ids of the last units are kept, so that the
program's own routers can count, for the traced units' batches, the rows each
held expert got, the choices the bias changed and those that fell to a
zero-compute expert, and from the rows how often the routed layer went over its
buffer; and the compiled program's text says which instruction lies in which
region of the model's code, for the readers of the device trace."""

from __future__ import annotations

import collections
import gc
import os
import time
import zlib

import numpy as np

from perfbench import checks_scmoe, flops_scmoe, weights
from perfbench.jobs import forward, forward_mla_moe, forward_window_moe, gpt_model
from perfbench.layer_metrics import _regions

BIAS_LEAF = "router_bias"
DOWN_LEAF = "experts_down"
REGIONS = ("moe.route", "moe.experts", "moe.zero")
LAYERS = "blocks/*/"


def _drawn(kind: str, layer, leaf, seed: int, bias_std: float, down_scale: float):
    mean, std = (1.0, weights.STD) if kind.endswith("/weight") else (0.0, weights.STD)
    std = bias_std if BIAS_LEAF in kind else std * down_scale if DOWN_LEAF in kind else std
    return forward_window_moe._leaf_drawn(tuple(leaf.shape), leaf.dtype, mean, std)(
        np.uint32(seed), np.uint32(zlib.crc32(kind.encode()) & 0x7FFFFFFF), np.uint32(0 if layer is None else layer + 1))


def draw(shape_tree, seed: int, bias_std: float, down_scale: float):
    """The program's parameter tree from the seed, a leaf of a layer at a time
    (``forward_window_moe.draw``'s compiled draws and its reason: at 10.34 GB a
    stacked draw unstacked holds the model twice): matrices N(0, ``weights.STD``),
    norm scales 1 + N(0, ``weights.STD``), the routers' biases N(0, ``bias_std``),
    the held experts' down projections N(0, ``down_scale * weights.STD``). The
    system and the reference are handed the same numbers."""
    import jax

    leaves = [_drawn(kind, layer, leaf, seed, bias_std, down_scale) for kind, layer, leaf in weights.leaf_kinds(shape_tree)]
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(shape_tree), leaves)


def drawn_for_reference(shape_tree, seed: int, bias_std: float, down_scale: float) -> dict:
    """``draw``'s numbers as the plain reference takes them: the leaves outside the
    layers by their paths, and ``"layers"``, a layer's leaves by their paths within
    it, a generator that draws a layer when the reference asks for it: at the
    cell's size the check cannot hold the 10.34 GB beside a layer's float32
    copies and the reference's own values."""
    kinds = weights.leaf_kinds(shape_tree)
    out = {kind: _drawn(kind, None, leaf, seed, bias_std, down_scale) for kind, layer, leaf in kinds if layer is None}
    depth = 1 + max(layer for _, layer, _ in kinds if layer is not None)
    out["layers"] = ({kind[len(LAYERS):]: _drawn(kind, layer, leaf, seed, bias_std, down_scale)
                      for kind, layer, leaf in kinds if layer == i} for i in range(depth))
    return out


class Job(forward.Job):
    zipf_ids = forward_mla_moe.Job.zipf_ids
    assignments = forward_mla_moe.Job.assignments

    def __init__(self, cell, **how):
        super().__init__(cell, **how)
        k = self.keys
        self.last = self.traffic["last"]
        self.bias_std, self.down_scale = float(k["router_bias_std"]), float(k["experts_down_scale"])
        # Zipf over the slice: the id of rank r has weight r**-a, and which id has which rank is one permutation
        # for the whole run, chosen at the run's first batch (the choice needs the weights).
        weight = np.arange(1, k["vocab_size"] + 1, dtype=np.float64) ** -float(self.traffic["zipf_exponent"])
        self.cdf = np.cumsum(weight / weight.sum())
        self.id_of_rank = self._compiled = self._count = self._counted = None
        self.issued = collections.deque(maxlen=cell.traffic["trace_units"])  # as many as run.py traces
        self.counters["tokens_per_unit"] = self.tokens_per_unit
        self.counters["region_of_instruction"] = lambda: _regions.of_instructions(
            forward_window_moe.an_instruction_a_line(self.compiled().as_text()), REGIONS)
        for name in ("routed_rows", "bias_changed_choices", "zero_expert_choices", "expert_buffer_passes"):
            self.counters[name] = lambda name=name: self.router_counts_of_last_units()[name]

    def weights(self):
        return draw(self.shapes, self.seed, self.bias_std, self.down_scale)

    def setup(self) -> None:
        """``forward.Job.setup`` with the weights drawn a leaf at a time and the
        head on the last ``last`` positions. Set-up builds two programs, the
        forward and the routers' count: ``trace_claim_s`` is both's."""
        import jax
        import jax.numpy as jnp

        import thunder_tpu
        from thunder_tpu.models import gpt

        cfg, last = self.cfg, self.last
        t0 = time.perf_counter()
        self.params = self.weights()
        jax.block_until_ready(self.params)
        self.spans["weights_s"] = time.perf_counter() - t0

        self.jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg, last=last))
        self._count = thunder_tpu.jit(lambda p, i: gpt.router_counts(p, i, cfg))
        self.read_back = jax.jit(lambda logits: (jnp.argmax(logits[:, -1, :], axis=-1),
                                                 jnp.isfinite(logits[:, -1, :]).all()))
        self.rng = np.random.RandomState(self.seed)
        self.first_batch = self.make_batch()
        t0 = time.perf_counter()
        self.wait(self.issue(self.first_batch))
        self.spans["compile_first_call_s"] = time.perf_counter() - t0
        self.entry = thunder_tpu.compile_stats(self.jfn).cache_entries[-1]
        self.spans["trace_claim_s"] = sum(
            phases[p] for phases in (self.entry.stats.phases, thunder_tpu.compile_stats(self._count).cache_entries[-1].stats.phases)
            for p in forward.TRACE_CLAIM_PHASES if p in phases)
        self.counters["kernels_claimed"] = gpt_model.kernels_claimed(thunder_tpu.last_traces(self.jfn)[-1])
        for _ in range(self.traffic["warmup_units"]):
            self.wait(self.issue(self.make_batch()))
        self.non_finite = 0

    def make_batch(self):
        if self.id_of_rank is None:
            self.id_of_rank = self.even_assignment()
        return self.zipf_ids(self.rng, self.id_of_rank)

    def even_assignment(self):
        """``forward_mla_moe.Job.even_assignment``, by this model's even share:
        of the seed's permutations the one under which the program's own routers
        send this chip the rows nearest ``routed_here_per_token`` a token."""
        t0 = time.perf_counter()
        even = flops_scmoe.routed_here_per_token(self.keys)
        tried = list(self.assignments())
        loads = np.stack([np.asarray(self._count(self.params, ids)[0]).sum(-1) for _, ids in tried]) / self.tokens_per_unit
        best = int(np.argmin(np.abs(loads.mean(1) - even)))
        self.spans["assign_ids_s"] = time.perf_counter() - t0
        print(f"ids: of {len(tried)} assignments of ranks to ids, routed here a token "
              f"{np.sort(loads.mean(1)).round(4).tolist()}; taken {loads[best].mean():.4f} (even {even}), "
              f"by layer {loads[best].round(4).tolist()}", flush=True)
        return tried[best][0]

    def issue(self, idx):
        self.issued.append(idx)
        return super().issue(idx)

    def flops_per_token(self) -> float:
        return flops_scmoe.forward_flops_per_token(self.keys, self.seq, self.last)

    def compiled(self):
        """Once: the memory report and the regions' reader ask for the same executable."""
        if self._compiled is None:
            self._compiled = super().compiled()
        return self._compiled

    def router_counts_of_last_units(self) -> dict:
        """For the last ``trace_units`` units issued (the traced ones, after a
        ``--trace 1`` run), by ``gpt.router_counts`` through ``thunder_tpu.jit``,
        the program's own routers on these ids: ``routed_rows`` [[[rows of a held
        expert] a layer] a unit]; ``bias_changed_choices`` and
        ``zero_expert_choices``, shares of the (token, choice) pairs; and
        ``expert_buffer_passes`` [[passes a layer] a unit], how often the claimed
        ``moe_experts`` went over its buffer for those rows, by the program's own
        reckoning of the buffer. Counted once, before the check lets the weights go."""
        if self._counted is None:
            from thunder_tpu.executors import pallasex

            cfg = self.cfg
            counts = [tuple(np.asarray(c) for c in self._count(self.params, idx)) for idx in self.issued]
            pairs = len(counts) * cfg.n_layer * self.tokens_per_unit * cfg.n_expert_per_token
            self._counted = {
                "routed_rows": [rows.tolist() for rows, _, _ in counts],
                "bias_changed_choices": sum(int(changed.sum()) for _, changed, _ in counts) / pairs if pairs else None,
                "zero_expert_choices": sum(int(zero.sum()) for _, _, zero in counts) / pairs if pairs else None,
                "expert_buffer_passes": [[pallasex.expert_buffer_passes(
                    layer.sum(), self.tokens_per_unit, cfg.n_expert_per_token, cfg.held_experts, cfg.router_outputs)
                    for layer in rows] for rows, _, _ in counts]}
            print(f"routing of the last {len(counts)} units: expert_buffer_passes {self._counted['expert_buffer_passes']} "
                  f"rows here a layer {[rows.sum(-1).tolist() for rows, _, _ in counts]} "
                  f"busiest held expert over the mean {[(rows.max(-1) / np.maximum(rows.mean(-1), 1e-9)).round(2).tolist() for rows, _, _ in counts]} "
                  f"bias_changed_choices {self._counted['bias_changed_choices']} "
                  f"zero_expert_choices {self._counted['zero_expert_choices']}", flush=True)
        return self._counted

    def check(self, reference) -> dict:
        """Logits of the last ``checks_scmoe.LOGIT_POSITIONS`` positions of the one
        seeded sequence of the first batch, what the timed program gives for it,
        against the reference's forward of that sequence, under this model's
        limits. ``PERFBENCH_CHECK_PRECISIONS`` (dtype names, comma-separated; unset
        in the driver's runs) is the builder's control of those limits: the
        reference itself with its matmul inputs rounded to each goes through the
        same comparison in the system's place, and its verdict is printed under
        ``reference_at``; ``PERFBENCH_CHECK_DUMP`` (a directory; unset likewise)
        keeps every compared row's error and margins. Neither changes ``ok``."""
        import jax.numpy as jnp

        self.router_counts_of_last_units()  # while the weights are here
        idx = self.first_batch
        picks = np.sort(np.random.RandomState(self.seed).choice(
            self.batch, size=min(self.traffic["check_sequences"], self.batch), replace=False))
        last = min(checks_scmoe.LOGIT_POSITIONS, self.last)
        logits = self.jfn(self.params, idx)
        system = np.asarray(logits[jnp.asarray(picks), -last:, :].astype(jnp.float32))
        self.params = logits = None
        gc.collect()
        sequences = jnp.asarray(idx[picks])

        def last_positions(of, *args):  # the reference compiles a piece of a layer at a time: the whole does not fit
            return of(drawn_for_reference(self.shapes, self.seed, self.bias_std, self.down_scale), sequences, self.keys,
                      *args, last=last)

        ref, margin = (np.asarray(out) for out in last_positions(reference.forward_and_margin))
        verdict = checks_scmoe.compare_logits(system, ref, margin)
        if os.environ.get("PERFBENCH_CHECK_DUMP"):  # the builder's, as PERFBENCH_CHECK_PRECISIONS is: every row's error and margins
            np.savez(os.path.join(os.environ["PERFBENCH_CHECK_DUMP"], f"check_{self.seed}.npz"), margin=margin,
                     rows=checks_scmoe.row_errors(system, ref))
        for dtype in filter(None, os.environ.get("PERFBENCH_CHECK_PRECISIONS", "").split(",")):
            verdict.setdefault("reference_at", {})[dtype] = checks_scmoe.compare_logits(
                np.asarray(last_positions(reference.forward, dtype)), ref, margin)
        verdict["expert_buffer_passes"] = self._counted["expert_buffer_passes"]
        return verdict


def lower_for(cell, keys: dict, batch: int, seq: int, topo):
    """``forward_sparse_linear.lower_for`` (the forward with the head on the
    traffic's last positions, lowered for a described device) with the
    dispatcher's own pass over the trace, ``fold_attention_layouts``, between the
    trace and the claim: the program the cell runs, not the one as written."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from perfbench.rehearse import with_sharding
    from thunder_tpu.api import trace_program
    from thunder_tpu.executors.passes import transform_for_execution
    from thunder_tpu.extend import resolve_executors
    from thunder_tpu.models import gpt
    from thunder_tpu.transforms.attention_layout import fold_attention_layouts
    from thunder_tpu.transforms.common import dce

    keys = {**keys, "num_layers": keys["num_hidden_layers"]}  # rehearse.py's name for the depth, and what its --depth sets
    cfg, last = gpt_model.gpt_config(keys), cell.traffic["last"]
    shapes = gpt_model.param_shapes(cfg)
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    _, comp = trace_program(lambda p, i: gpt.forward(p, i, cfg, last=last), (shapes, tokens), {})
    executors = resolve_executors(None)
    run = transform_for_execution(fold_attention_layouts(dce(comp), executors), executors).python_callable()
    one = SingleDeviceSharding(topo.devices[0])
    flat = jax.tree_util.tree_leaves((shapes, tokens))
    return jax.jit(run).lower(*(with_sharding(a, one) for a in flat))
