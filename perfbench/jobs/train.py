"""Job ``train``: training steps through ``parallel.build_train_step``, the
entry points ``examples/train.py`` and ``examples/train_fsdp.py`` call, from a
loop of the benchmark's own. One unit of work is one optimizer step on a fresh
seeded batch made on the host; no loss is read inside the window."""

from __future__ import annotations

import gc
import time

import numpy as np

from perfbench import checks, weights
from perfbench.jobs import gpt_model


class Job(gpt_model.JobBase):
    def __init__(self, cell, **how):
        super().__init__(cell, **how)
        self.losses: list = []

    # -- set-up ---------------------------------------------------------------

    def _weights(self):
        return weights.make_system_weights(self.shapes, self.seed, self.param_shardings)

    def setup(self) -> None:
        import jax

        from thunder_tpu.parallel import build_train_step, gpt_param_specs, make_mesh, named_shardings

        t = self.traffic
        self.mesh = self.specs = self.param_shardings = None
        if t.get("mesh"):
            self.mesh = make_mesh(devices=jax.devices()[: self.cell.chips], **t["mesh"])
            self.specs = gpt_param_specs(self.cfg, self.mesh)
            self.param_shardings = named_shardings(self.mesh, self.specs)

        t0 = time.perf_counter()
        self.params = self._weights()
        jax.block_until_ready(self.params)
        self.spans["weights_s"] = time.perf_counter() - t0

        self.rng = np.random.RandomState(self.seed)
        self.first_batch = self.make_batch()
        t0 = time.perf_counter()
        self.step, self.opt, self.extrace = build_train_step(
            self.cfg, self.params, *self.first_batch, mesh=self.mesh, param_specs=self.specs,
            lr=t["lr"], weight_decay=t["weight_decay"], b1=t["b1"], b2=t["b2"],
            optimizer=t["optimizer"], return_extrace=True)
        self.spans["trace_claim_s"] = time.perf_counter() - t0
        self.counters["kernels_claimed"] = gpt_model.kernels_claimed(self.extrace)

        t0 = time.perf_counter()
        self.wait(self.issue(self.first_batch))
        self.spans["compile_first_call_s"] = time.perf_counter() - t0
        for _ in range(t["warmup_units"]):
            handle = self.issue(self.make_batch())
        self.wait(handle)
        self.warmup_losses, self.losses = self.losses, []

    # -- one unit of work -----------------------------------------------------

    def make_batch(self):
        return gpt_model.token_batch(self.rng, self.keys["vocab_size"], self.batch, self.seq)

    def issue(self, batch):
        self.params, self.opt, loss = self.step(self.params, self.opt, *batch)
        self.losses.append(loss)
        return loss

    def wait(self, loss) -> None:
        loss.block_until_ready()

    def failed_units(self) -> int:
        """Read after the window: steps whose loss is not finite."""
        self.loss_values = np.asarray([float(np.asarray(l)) for l in self.losses])
        return int((~np.isfinite(self.loss_values)).sum())

    # -- after the window -----------------------------------------------------

    def flops_per_token(self) -> float:
        """Forward plus backward, the backward at twice the forward (a gradient
        for each operand of each matmul); no recomputation is counted."""
        return 3.0 * self.forward_flops_per_token()

    def compiled(self):
        """The executable the window ran; in this process the lowering and the
        compile are cached, so this costs milliseconds."""
        return self.step.lower(self.params, self.opt, *self.first_batch).compile()

    def validity(self) -> list[str]:
        problems = gpt_model.hidden_recovery()
        if self.step._cache_size() != 1:
            problems.append(f"the step was traced {self.step._cache_size()} times, not once")
        return problems + gpt_model.off_device(self.platform, self.opt["step"],
                                               *self.losses[-1:])

    def release(self) -> None:
        self.params = self.opt = None
        self.losses = []
        gc.collect()

    def check(self, reference) -> dict:
        """One step from the seeded weights and zero moments on the first batch,
        against the reference's loss and sampled gradient (``checks.py``)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        from thunder_tpu.parallel import adamw_init, named_shardings
        from thunder_tpu.parallel.train import opt_state_specs

        clock = [time.perf_counter()]

        def lap() -> float:
            clock.append(time.perf_counter())
            return round(clock[-1] - clock[-2], 2)

        idx, tgt = self.first_batch
        params = self._weights()
        opt = adamw_init(params)
        if self.mesh is not None:  # laid out as build_train_step laid its own out
            opt = jax.device_put(opt, named_shardings(
                self.mesh, opt_state_specs(self.specs, self.traffic["optimizer"])))
        params, opt, loss = self.step(params, opt, idx, tgt)
        system_loss = float(np.asarray(loss))
        plan = checks.sample_plan(weights.leaf_kinds(self.shapes), self.cfg.n_layer, idx, self.seed)
        system_sample = checks.system_gradient_sample(opt["m"], plan, self.traffic["b1"])
        del params, opt, loss
        gc.collect()
        seconds = {"system_step_and_sample": lap()}

        replicated = None
        if self.cell.chips > 1:  # the reference's own mesh: weights whole on every chip, batch split
            ref_mesh = Mesh(np.array(jax.devices()[: self.cell.chips]), ("batch",))
            replicated = NamedSharding(ref_mesh, PartitionSpec())
            idx, tgt = (jax.device_put(a, NamedSharding(ref_mesh, PartitionSpec("batch"))) for a in (idx, tgt))
        stacked = weights.make_reference_weights(self.shapes, self.seed, replicated)
        jax.block_until_ready(stacked)
        seconds["reference_weights"] = lap()
        ref_loss, ref_sample = checks.reference_loss_and_gradient_sample(
            reference, stacked, plan, jnp.asarray(idx), jnp.asarray(tgt), self.keys)
        seconds["reference_loss_and_gradient"] = lap()
        verdict = checks.compare_training(system_loss, system_sample, ref_loss, ref_sample)
        verdict["seconds"] = seconds
        if self.step._cache_size() != 1:  # the check's own step must have hit the compiled one
            verdict.update(ok=False, traced=self.step._cache_size())
        return verdict


def lower_for(cell, keys: dict, batch: int, seq: int, topo):
    """The step lowered at ``(batch, seq)`` for the described devices of
    ``topo``, for ``perfbench/rehearse.py``: shapes only, nothing runs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, SingleDeviceSharding

    from perfbench.rehearse import device_put_as_shapes, with_sharding
    from thunder_tpu.parallel import build_train_step, data_spec, gpt_param_specs, make_mesh, named_shardings
    from thunder_tpu.parallel.train import opt_state_specs

    t = cell.traffic
    cfg = gpt_model.gpt_config(keys)
    shapes = gpt_model.param_shapes(cfg)
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    kwargs = dict(lr=t["lr"], weight_decay=t["weight_decay"], b1=t["b1"], b2=t["b2"], optimizer=t["optimizer"])
    if t.get("mesh"):
        mesh = make_mesh(devices=topo.devices[: cell.chips], **t["mesh"])
        specs = gpt_param_specs(cfg, mesh)
        param_sh = named_shardings(mesh, specs)
        opt_sh = named_shardings(mesh, opt_state_specs(specs, t["optimizer"]))
        data_sh = NamedSharding(mesh, data_spec(mesh))
        with device_put_as_shapes():
            step, opt = build_train_step(cfg, shapes, tokens, tokens, mesh=mesh, param_specs=specs, **kwargs)
        args = (with_sharding(shapes, param_sh), with_sharding(opt, opt_sh),
                with_sharding(tokens, data_sh), with_sharding(tokens, data_sh))
    else:
        one = SingleDeviceSharding(topo.devices[0])
        step, opt = build_train_step(cfg, shapes, tokens, tokens, **kwargs)
        args = tuple(with_sharding(a, one) for a in (shapes, opt, tokens, tokens))
    return step.lower(*args)
