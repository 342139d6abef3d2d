"""Job ``forward``: the forward pass through ``thunder_tpu.jit``, a closed loop
with one caller that waits for each reply and reads back the argmax of the
last position (what a greedy decoder's first step reads). One unit of work is
one call on a fresh seeded batch."""

from __future__ import annotations

import gc
import time

import numpy as np

from perfbench import checks, weights
from perfbench.jobs import gpt_model

TRACE_CLAIM_PHASES = ("trace", "transforms", "claim", "static_analysis", "codegen")


class Job(gpt_model.JobBase):
    def __init__(self, cell, **how):
        super().__init__(cell, **how)
        self.non_finite = 0

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        import thunder_tpu
        from thunder_tpu.models import gpt

        cfg = self.cfg
        t0 = time.perf_counter()
        self.params = weights.make_system_weights(self.shapes, self.seed)
        jax.block_until_ready(self.params)
        self.spans["weights_s"] = time.perf_counter() - t0

        self.jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
        # What the caller reads back: the greedy token of the last position, and
        # whether that row of logits is finite.
        self.read_back = jax.jit(lambda logits: (jnp.argmax(logits[:, -1, :], axis=-1),
                                                 jnp.isfinite(logits[:, -1, :]).all()))
        self.rng = np.random.RandomState(self.seed)
        self.first_batch = self.make_batch()
        t0 = time.perf_counter()
        self.wait(self.issue(self.first_batch))
        self.spans["compile_first_call_s"] = time.perf_counter() - t0
        self.entry = thunder_tpu.compile_stats(self.jfn).cache_entries[-1]
        phases = self.entry.stats.phases
        self.spans["trace_claim_s"] = sum(phases[p] for p in TRACE_CLAIM_PHASES if p in phases)
        self.counters["kernels_claimed"] = gpt_model.kernels_claimed(thunder_tpu.last_traces(self.jfn)[-1])
        for _ in range(self.traffic["warmup_units"]):
            self.wait(self.issue(self.make_batch()))
        self.non_finite = 0

    def make_batch(self):
        return gpt_model.token_batch(self.rng, self.keys["vocab_size"], self.batch, self.seq)[0]

    def issue(self, idx):
        self.logits = self.jfn(self.params, idx)
        return self.read_back(self.logits)

    def wait(self, handle) -> None:
        token, finite = handle
        np.asarray(token)
        self.non_finite += 0 if bool(finite) else 1

    def failed_units(self) -> int:
        return self.non_finite

    def flops_per_token(self) -> float:
        return self.forward_flops_per_token()

    def compiled(self):
        """The cache entry's executable, reached as ``chip_smoke.py``'s Stage C does."""
        return self.entry.computation_fn.lower(*self.entry.hlo_audit_avals).compile()

    def validity(self) -> list[str]:
        import thunder_tpu

        problems = gpt_model.hidden_recovery()
        misses = thunder_tpu.cache_misses(self.jfn)
        if misses != 1:
            problems.append(f"the forward was compiled {misses} times, not once")
        info = thunder_tpu.cache_info(self.jfn)
        if info["degradation_level"] != 0 or any(e["degradation_level"] for e in info["entries"]):
            problems.append(f"the forward was de-optimized: level {info['degradation_level']}")
        return problems + gpt_model.off_device(self.platform, self.logits)

    def release(self) -> None:
        """Keeps the weights: the check calls the system once more."""
        self.logits = None
        gc.collect()

    def check(self, reference) -> dict:
        """Logits of the last positions of a seeded sample of sequences of the
        first batch, against the reference's forward of those sequences."""
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
        ref = jax.jit(lambda w, i: reference.forward(w, i, self.keys)[:, -last:, :])(
            stacked, jnp.asarray(idx[picks]))
        return checks.compare_logits(system, np.asarray(ref))


def lower_for(cell, keys: dict, batch: int, seq: int, topo):
    """The forward lowered at ``(batch, seq)`` for a described device of
    ``topo``, for ``perfbench/rehearse.py``. The dispatcher builds its
    executable at the first call, which cannot run there, so the trace goes
    through the same claiming pass by hand."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from perfbench.rehearse import with_sharding
    from thunder_tpu.api import trace_program
    from thunder_tpu.executors.passes import transform_for_execution
    from thunder_tpu.extend import resolve_executors
    from thunder_tpu.models import gpt
    from thunder_tpu.transforms.common import dce

    cfg = gpt_model.gpt_config(keys)
    shapes = gpt_model.param_shapes(cfg)
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    _, comp = trace_program(lambda p, i: gpt.forward(p, i, cfg), (shapes, tokens), {})
    run = transform_for_execution(dce(comp), resolve_executors(None)).python_callable()
    one = SingleDeviceSharding(topo.devices[0])
    flat = jax.tree_util.tree_leaves((shapes, tokens))
    return jax.jit(run).lower(*(with_sharding(a, one) for a in flat))
