"""What the schedule certificate predicts of a trace's collectives
(analysis/schedule.py): predict_overlap window/budget goldens, the
sched.exposed-collective advisory rule, and ICI calibration
(analysis/cost.py)."""

import numpy as np
import pytest

import thunder_tpu.clang as clang
import thunder_tpu.core.prims as prims
from thunder_tpu.analysis import Severity, verify
from thunder_tpu.analysis import schedule as sched_mod
from thunder_tpu.analysis.cost import (
    DEVICE_SPECS,
    calibrate_ici,
    resolve_device_spec,
    trace_cost,
)
from thunder_tpu.api import trace_program
from thunder_tpu.core import devices, dtypes
from thunder_tpu.core.proxies import TensorProxy
from thunder_tpu.core.trace import TraceCtx, tracectx
from thunder_tpu.distributed import prims as dist_prims
from thunder_tpu.executors.passes import transform_for_execution
from thunder_tpu.extend import resolve_executors
from thunder_tpu.transforms.autodiff import grad_transform
from thunder_tpu.transforms.common import dce


def _cpu():
    return devices.Device("cpu")


def _t(shape=(64, 64), name=None):
    return TensorProxy(name=name, shape=shape, dtype=dtypes.float32, device=_cpu())


def _mlp_extrace(layers=3, d=64, B=16, fsdp=4, tp=2, grad=True):
    """The fsdp×tp explicit-collective MLP fw(+bw) claimed trace — the
    bench/smoke workload shape."""
    rng = np.random.RandomState(0)
    ws = [rng.randn(d // fsdp, d).astype(np.float32) for _ in range(layers)]
    x = rng.randn(B, d).astype(np.float32)

    def loss(*flat_in):
        *w_shards, xv = flat_in
        h = xv
        for w_shard in w_shards:
            w_full = dist_prims.synchronize(w_shard, "fsdp", fsdp, "fsdp")
            h = clang.matmul(h, clang.transpose(w_full, 0, 1))
            h = dist_prims.all_reduce(h, "tp", tp, op="avg")
            h = clang.tanh(h)
        return clang.mean(clang.mul(h, h))

    _, comp = trace_program(loss, (*ws, x), {})
    comp = dce(comp)
    if grad:
        comp = grad_transform(comp, return_value=True)
    return transform_for_execution(comp, resolve_executors(["jax"]))


class TestPredictOverlap:
    def _gather_then_compute(self):
        """gather (wire) -> independent matmul -> consumer of the gather."""
        trc = TraceCtx()
        with tracectx(trc):
            a = _t((16, 64))
            b = _t((64, 64))
            trc.args = (a, b)
            g = dist_prims.all_gather(a, "dp", 4, dim=0)
            c = clang.matmul(b, b)          # independent of g: in g's window
            out = clang.matmul(c, clang.transpose(g, 0, 1))
            prims.python_return(out)
            trc.output = out
        return trc

    def test_window_is_independent_compute(self):
        pred = sched_mod.predict_overlap(self._gather_then_compute(), device="v5e")
        site = pred.sites[0]
        assert site.sym == "all_gather"
        assert site.first_consumer == 2  # the consuming matmul
        assert site.window_us > 0
        assert site.hidden_us == pytest.approx(min(site.wire_us, site.window_us))

    def test_hidden_capped_by_wire(self):
        pred = sched_mod.predict_overlap(self._gather_then_compute(), device="v5e")
        for s in pred.sites:
            assert s.hidden_us <= s.wire_us + 1e-9
            assert s.exposed_us == pytest.approx(s.wire_us - s.hidden_us)

    def test_budget_not_double_counted(self):
        """Two collectives sharing one window line cannot both claim it."""
        trc = TraceCtx()
        with tracectx(trc):
            a = _t((16, 64))
            b = _t((64, 64))
            trc.args = (a, b)
            g1 = dist_prims.all_gather(a, "dp", 4, dim=0)
            g2 = dist_prims.all_gather(a, "tp", 4, dim=0)
            c = clang.matmul(b, b)  # the one shared window line
            o1 = clang.matmul(c, clang.transpose(g1, 0, 1))
            o2 = clang.matmul(o1, clang.transpose(g2, 0, 1))
            out = clang.add(o2, o2)
            prims.python_return(out)
            trc.output = out
        pred = sched_mod.predict_overlap(trc, device="v5e")
        s1, s2 = pred.sites[0], pred.sites[1]
        # The two windows overlap on the shared compute line: whatever the
        # split, total hidden cannot exceed the compute in the UNION of the
        # two windows (lines between site 0/1 and their first consumers).
        union = range(2, max(s1.first_consumer, s2.first_consumer))
        union_budget = sum(
            r.roofline_s * 1e6
            for r in trace_cost(trc, "v5e").rows
            if r.index in union and r.kind != "collective"
        )
        assert s1.hidden_us + s2.hidden_us <= union_budget + 1e-6
        # The first site drains the shared line entirely (its window is only
        # that line and smaller than its wire), so the second site's hidden
        # comes from the rest of its window alone.
        shared_us = next(
            r.roofline_s * 1e6 for r in trace_cost(trc, "v5e").rows
            if r.index == 2
        )
        assert s1.hidden_us == pytest.approx(shared_us)
        assert s2.hidden_us <= s2.window_us - shared_us + 1e-6

    def test_exposed_pct_totals(self):
        pred = sched_mod.predict_overlap(_mlp_extrace(), device="cpu")
        assert 0.0 <= pred.exposed_pct <= 100.0
        assert pred.exposed_us == pytest.approx(pred.wire_us - pred.hidden_us)


class TestExposedCollectiveRule:
    def test_fires_info_on_exposed_site(self):
        trc = TraceCtx()
        with tracectx(trc):
            a = _t((256, 256))
            trc.args = (a,)
            g = dist_prims.all_gather(a, "dp", 8, dim=0)
            out = clang.mul(g, g)  # immediate consumer: fully exposed
            prims.python_return(out)
            trc.output = out
        diags = [d for d in verify(trc) if d.rule == "sched.exposed-collective"]
        assert diags and all(d.severity == Severity.INFO for d in diags)
        assert "exposed" in diags[0].message

    def test_silent_without_collectives(self):
        trc = TraceCtx()
        with tracectx(trc):
            a = _t()
            trc.args = (a,)
            out = clang.mul(a, a)
            prims.python_return(out)
            trc.output = out
        assert [d for d in verify(trc)
                if d.rule == "sched.exposed-collective"] == []

    def test_advisory_never_gates(self):
        """INFO diagnostics must not fail verify_or_raise at ERROR."""
        from thunder_tpu.analysis import verify_or_raise

        trc = TraceCtx()
        with tracectx(trc):
            a = _t((256, 256))
            trc.args = (a,)
            g = dist_prims.all_gather(a, "dp", 8, dim=0)
            out = clang.mul(g, g)
            prims.python_return(out)
            trc.output = out
        verify_or_raise(trc)  # must not raise


class TestCalibration:
    def test_fit_and_pricing(self):
        spec = DEVICE_SPECS["cpu"]
        # 1 MB all-gather measured at 1 s -> 1 MB/s effective.
        cal = calibrate_ici(spec, [("all-gather", 1e6, 1.0)])
        assert cal.ici_bw_for("all-gather") == pytest.approx(1e6)
        # Unfitted classes fall back to the datasheet rate.
        assert cal.ici_bw_for("all-reduce") == spec.ici_bw
        assert cal.ici_bw_for(None) == spec.ici_bw
        # The base spec is untouched (frozen + replace).
        assert spec.ici_class_bw is None

    def test_fit_clamped_to_datasheet(self):
        spec = DEVICE_SPECS["cpu"]
        cal = calibrate_ici(spec, [("all-reduce", 1e12, 1.0)])  # "faster than wire"
        assert cal.ici_bw_for("all-reduce") == spec.ici_bw

    def test_empty_or_garbage_samples_are_identity(self):
        spec = DEVICE_SPECS["cpu"]
        assert calibrate_ici(spec, []) is spec
        assert calibrate_ici(spec, [(None, 0, 0), ("x", 1e3, 0.0)]) is spec

    def test_trace_cost_prices_calibrated_wire(self):
        extrace = _mlp_extrace(grad=False)
        spec = resolve_device_spec("cpu")
        slow = calibrate_ici(spec, [("all-gather", 1e6, 1.0)])  # 1 MB/s
        base_rows = [r for r in trace_cost(extrace, spec).rows
                     if r.sym == "synchronize"]
        slow_rows = [r for r in trace_cost(extrace, slow).rows
                     if r.sym == "synchronize"]
        assert slow_rows[0].roofline_s > base_rows[0].roofline_s * 100
