"""Observability subsystem tests (ISSUE 4): metrics registry semantics,
JSONL event-log schema (golden field sets per kind), the per-op
instrumentation transform (NaN watch with BoundSymbol/provenance
attribution on a seeded-NaN GPT block, OpTimer, no-op when disabled),
profiler bracketing, and the event-replay analyzer's recompile-storm
detection.
"""

import json
import os

import numpy as np
import pytest

import thunder_tpu as ttpu
import thunder_tpu.clang as clang
import thunder_tpu.monitor as monitor
from thunder_tpu.observability import events as obs_events
from thunder_tpu.observability import metrics as obsm
from thunder_tpu.observability.metrics import MetricsRegistry


@pytest.fixture(autouse=True)
def _metrics_isolation():
    """Each test starts with metrics off and zeroed, and never leaks an
    ambient event log into the next test."""
    was = monitor.enabled()
    monitor.disable()
    monitor.reset()
    yield
    monitor.reset()
    (monitor.enable if was else monitor.disable)()


# =============================================================================
# Metrics registry
# =============================================================================


class TestMetricsRegistry:
    def test_counter_disabled_is_noop(self):
        r = MetricsRegistry()
        c = r.counter("c_total", "help")
        c.inc()
        assert c.value() == 0  # monitor disabled by the fixture

    def test_counter_labels(self):
        monitor.enable()
        r = MetricsRegistry()
        c = r.counter("claims_total")
        c.inc(3, executor="jax")
        c.inc(1, executor="flash")
        c.inc(2, executor="jax")
        assert c.value(executor="jax") == 5
        assert c.value(executor="flash") == 1
        assert c.value(executor="none") == 0

    def test_gauge_set_max(self):
        monitor.enable()
        r = MetricsRegistry()
        g = r.gauge("hw_bytes")
        g.set_max(100)
        g.set_max(50)
        assert g.value() == 100
        g.set(10)
        assert g.value() == 10

    def test_histogram_summary(self):
        monitor.enable()
        r = MetricsRegistry()
        h = r.histogram("lat_us")
        for v in (5.0, 50.0, 500.0):
            h.observe(v)
        s = h.summary()
        assert s["count"] == 3
        assert s["min"] == 5.0 and s["max"] == 500.0
        assert abs(s["mean"] - 185.0) < 1e-9
        # cumulative buckets: le=10 holds 1, le=100 holds 2, le=1000 holds 3
        by_le = dict(zip(h.buckets, s["bucket_counts"]))
        assert by_le[10.0] == 1 and by_le[100.0] == 2 and by_le[1e3] == 3

    def test_kind_collision_raises(self):
        r = MetricsRegistry()
        r.counter("x")
        with pytest.raises(TypeError):
            r.gauge("x")

    def test_report_and_prometheus(self):
        monitor.enable()
        r = MetricsRegistry()
        r.counter("a_total", "ha").inc(2)
        r.histogram("h_us").observe(7.0)
        rep = r.report()
        assert rep["a_total"]["kind"] == "counter"
        assert rep["a_total"]["values"][""] == 2
        text = r.prometheus_text()
        assert "# TYPE a_total counter" in text
        assert "a_total 2" in text
        assert 'h_us_bucket{le="10.0"} 1' in text
        assert "h_us_count 1" in text

    def test_reset_keeps_definitions(self):
        monitor.enable()
        r = MetricsRegistry()
        c = r.counter("n_total")
        c.inc(4)
        r.reset()
        assert c.value() == 0
        assert "n_total" in r.report()

    def test_dump_json(self, tmp_path):
        monitor.enable()
        r = MetricsRegistry()
        r.counter("j_total").inc()
        p = tmp_path / "m.json"
        r.dump_json(str(p))
        data = json.loads(p.read_text())
        assert data["metrics"]["j_total"]["values"][""] == 1

    def test_jit_populates_framework_metrics(self):
        monitor.enable()

        def f(x):
            return clang.sum(clang.tanh(x))

        jf = ttpu.jit(f, executors=["jax"])
        x = np.ones((4, 4), np.float32)
        jf(x)
        jf(x)
        assert obsm.CACHE_MISSES.value() == 1
        assert obsm.CACHE_HITS.value(kind="fast") == 1
        assert obsm.COMPILES.value() >= 1
        assert obsm.CLAIMED_BSYMS.value(executor="jax") >= 2
        assert obsm.PASS_MS.summary(**{"pass": "Dead Code Elimination"})["count"] >= 1


# =============================================================================
# Event log: schema golden test + wiring
# =============================================================================


def _read_events(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


class TestEventLog:
    def test_compile_event_schema_golden(self, tmp_path):
        """Golden field sets: every emitted kind carries exactly the common
        envelope plus its schema fields (a superset breaks replay consumers,
        a subset breaks the writer)."""
        log = str(tmp_path / "ev.jsonl")

        def f(x):
            return clang.sum(clang.mul(x, x))

        jf = ttpu.jit(f, executors=["jax"], events=log)
        jf(np.ones((2, 2), np.float32))

        recs = _read_events(log)
        kinds = [r["kind"] for r in recs]
        assert kinds[0] == "cache_miss"
        assert kinds[1] == "compile_start"
        assert "compile_end" in kinds
        assert "pass" in kinds
        # Build-side compile_phase spans (trace/claim/...) precede
        # compile_end; the first-run span (ISSUE 8: xla_compile + the
        # persistent-cache sub-spans) lands AFTER it — XLA compiles at the
        # entry's first run, which happens after the build bracket.
        assert kinds[-1] == "compile_phase"
        assert kinds.index("compile_phase") < kinds.index("compile_end")

        # pid/host joined the envelope in PR 5 (multi-host log merging).
        envelope = {"v", "ts", "seq", "kind", "pid", "host"}
        golden = {
            "cache_miss": envelope | {"fn", "call"},
            "compile_start": envelope | {"compile_id", "fn", "cache_option", "call"},
            "pass": envelope | {"compile_id", "name", "ms", "n_bsyms", "trace"},
            "compile_end": envelope | {
                "compile_id", "fn", "ms", "n_bsyms", "claims",
                "collective_bytes", "symbolic", "recompile", "staged",
            },
            # Optional fields: cache (hit|miss verdict on xla_compile), the
            # static_analysis span's planner summary (ISSUE 10:
            # predicted_peak_bytes + collective_sites), and the hlo_audit
            # span's auditor summary (ISSUE 16 — present by-presence: an
            # absent field means the audit had nothing to say there);
            # sub-spans carry the bare triple.
            "compile_phase": envelope | {"compile_id", "phase", "s"},
        }
        phase_optional = {
            "cache", "predicted_peak_bytes", "collective_sites",
            "cross_entropy_upcasts_folded",  # on transforms (ISSUE 28)
            "attention_layouts_folded",  # on transforms (ISSUE 30)
            # hlo_audit (ISSUE 16)
            "hlo_ops", "hlo_acquire_s", "hlo_analyze_s", "hlo_collectives",
            "hlo_inserted_collectives", "hlo_exposed_pct", "hlo_host_transfers",
        }
        for r in recs:
            want = golden[r["kind"]]
            got = set(r) - (phase_optional if r["kind"] == "compile_phase" else set())
            assert got == want, (r["kind"], sorted(got ^ want))
        assert all(r["v"] == 1 for r in recs)
        # seq is the per-log line counter
        assert [r["seq"] for r in recs] == list(range(len(recs)))
        end = next(r for r in recs if r["kind"] == "compile_end")
        assert end["claims"].get("jax", 0) >= 1
        assert end["staged"] is True and end["symbolic"] is False
        # One span per pipeline phase, all correlated to this compile.
        phases = [r for r in recs if r["kind"] == "compile_phase"]
        assert {"trace", "transforms", "claim", "codegen", "staging",
                "xla_compile"} <= {r["phase"] for r in phases}
        assert {r["compile_id"] for r in phases} == {end["compile_id"]}

    def test_bucket_select_and_recompile_events(self, tmp_path):
        log = str(tmp_path / "ev.jsonl")

        def f(x):
            return clang.sum(clang.tanh(x))

        jf = ttpu.jit(f, executors=["jax"], cache="symbolic values",
                      symbolic_dims={0: (0,)}, events=log)
        jf(np.ones((2, 8), np.float32))
        jf(np.ones((3, 8), np.float32))  # next pow2 bucket -> second compile
        recs = _read_events(log)
        buckets = [r for r in recs if r["kind"] == "bucket_select"]
        assert len(buckets) == 2
        assert "leaf0.dim0" in buckets[0]["buckets"]
        ends = [r for r in recs if r["kind"] == "compile_end"]
        assert [e["recompile"] for e in ends] == [False, True]
        assert all(e["symbolic"] for e in ends)

    def test_global_env_log(self, tmp_path):
        log = str(tmp_path / "glob.jsonl")
        obs_events.set_global_path(log)
        try:
            jf = ttpu.jit(lambda x: clang.abs(x), executors=["jax"])
            jf(np.ones((2,), np.float32))
        finally:
            obs_events.set_global_path(None)
        kinds = {r["kind"] for r in _read_events(log)}
        assert {"compile_start", "pass", "compile_end"} <= kinds

    def test_sharp_edge_event(self, tmp_path):
        log = str(tmp_path / "se.jsonl")
        obs_events.set_global_path(log)
        try:
            # an opaque (unguardable) input leaf is the canonical sharp edge
            jf = ttpu.jit(lambda x, o: clang.tanh(x), executors=["jax"])
            jf(np.ones((2, 2), np.float32), object())
        finally:
            obs_events.set_global_path(None)
        edges = [r for r in _read_events(log) if r["kind"] == "sharp_edge"]
        assert edges and "cannot be guarded" in edges[0]["message"]
        assert edges[0]["policy"] == "allow"

    def test_no_log_is_silent(self, tmp_path):
        # no env, no events= : nothing is written anywhere
        assert obs_events.active_log() is None or os.environ.get("THUNDER_TPU_EVENTS")


# =============================================================================
# Instrumentation transform
# =============================================================================


def _tiny_gpt():
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt as m

    cfg = m.name_to_config("gpt-tiny")
    params = m.init_params(cfg, dtype=dtypes.float32, seed=0)
    idx = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 16)).astype(np.int32)
    return m, cfg, params, idx


class TestInstrumentation:
    def test_nan_watch_gpt_block_attribution(self):
        """Acceptance: jit(fn, debug_watch="nan") on a seeded-NaN GPT block
        raises with the offending BoundSymbol name, trace line, and pass
        provenance."""
        from thunder_tpu.observability.instrument import NaNWatchError

        m, cfg, params, idx = _tiny_gpt()
        # Seed a NaN into the first block's QKV projection weight: the first
        # matmul touching it goes NaN mid-block.
        w = np.array(params["blocks"][0]["attn"]["qkv_w"], np.float32, copy=True)
        w[0, 0] = np.nan
        params["blocks"][0]["attn"]["qkv_w"] = w

        jf = ttpu.jit(lambda p, i: m.forward(p, i, cfg), executors=["jax"],
                      debug_watch="nan")
        with pytest.raises(NaNWatchError) as ei:
            jf(params, idx)
        err = ei.value
        assert err.sym_name  # the BoundSymbol name
        assert err.trace_line and "=" in err.trace_line  # the generated line
        assert err.provenance  # the pass that produced the executed trace
        assert err.sym_name in err.trace_line or err.sym_name in str(err)
        assert "NaN" in str(err)

    def test_nan_watch_clean_run_no_trip(self):
        m, cfg, params, idx = _tiny_gpt()
        jf = ttpu.jit(lambda p, i: m.forward(p, i, cfg), executors=["jax"],
                      debug_watch="nan")
        out = jf(params, idx)
        assert np.isfinite(np.asarray(out)).all()

    def test_inf_watch(self):
        from thunder_tpu.observability.instrument import NaNWatchError

        def f(x):
            return clang.true_divide(clang.abs(x), clang.sub(x, x))  # |x|/0 = inf

        jf = ttpu.jit(f, executors=["jax"], debug_watch="inf")
        with pytest.raises(NaNWatchError) as ei:
            jf(np.full((2, 2), 3.0, np.float32))
        assert ei.value.kind == "Inf"

    def test_noop_when_disabled(self):
        """With no debug_watch/instrument option, no instrumentation symbols
        exist in the final trace and the entry stages under jax.jit."""

        def f(x):
            return clang.sum(clang.tanh(x))

        jf = ttpu.jit(f, executors=["jax"])
        jf(np.ones((2, 2), np.float32))
        final = ttpu.last_traces(jf)[-1]
        names = [b.sym.name for b in final.bound_symbols]
        assert not any("instrument" in n for n in names)
        entry = ttpu.compile_stats(jf).cache_entries[0]
        # staged: the computation_fn is a jax.jit wrapper (has .lower), not
        # the raw trace callable
        assert hasattr(entry.computation_fn, "lower")

    def test_instrumented_matches_staged_result(self):
        from thunder_tpu.observability.instrument import OpTimer

        def f(x):
            return clang.sum(clang.mul(clang.tanh(x), x))

        x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
        plain = ttpu.jit(f, executors=["jax"])
        timed = ttpu.jit(f, executors=["jax"], instrument=OpTimer())
        np.testing.assert_allclose(np.asarray(plain(x)), np.asarray(timed(x)), rtol=1e-6)

    def test_op_timer_report(self):
        from thunder_tpu.observability.instrument import OpTimer, instrument_reports

        t = OpTimer()

        def f(x):
            return clang.sum(clang.tanh(x))

        jf = ttpu.jit(f, executors=["jax"], instrument=t)
        jf(np.ones((16, 16), np.float32))
        jf(np.ones((16, 16), np.float32))
        rep = instrument_reports(jf)
        assert rep and rep[0]["hook"] == "OpTimer"
        ops = {o["symbol"]: o for o in rep[0]["ops"]}
        assert ops["tanh"]["calls"] == 2 and ops["sum"]["calls"] == 2
        assert rep[0]["total_s"] > 0

    def test_instrument_shorthand_persists_across_entries(self):
        """Hook instances are resolved once per compiled function, not per
        cache entry: a second shape specialization keeps feeding the same
        OpTimer, so instrument_reports sees the whole history."""
        from thunder_tpu.observability.instrument import instrument_reports

        def f(x):
            return clang.sum(clang.tanh(x))

        jf = ttpu.jit(f, executors=["jax"], instrument="time")
        jf(np.ones((4, 4), np.float32))
        jf(np.ones((8, 8), np.float32))  # new shape -> second entry
        assert ttpu.cache_misses(jf) == 2
        rep = instrument_reports(jf)
        assert len(rep) == 1  # ONE OpTimer across both entries
        ops = {o["symbol"]: o for o in rep[0]["ops"]}
        assert ops["tanh"]["calls"] == 2

    def test_custom_callback_hook(self):
        seen = []

        def cb(rec, outs):
            seen.append((rec.sym_name, len(outs)))

        jf = ttpu.jit(lambda x: clang.tanh(x), executors=["jax"], instrument=cb)
        jf(np.ones((2, 2), np.float32))
        assert ("tanh", 1) in seen

    def test_memory_high_water_hook(self):
        from thunder_tpu.observability.instrument import MemoryHighWater, instrument_reports

        h = MemoryHighWater()
        jf = ttpu.jit(lambda x: clang.sum(clang.mul(x, x)), executors=["jax"],
                      instrument=h)
        jf(np.ones((32, 32), np.float32))
        rep = instrument_reports(jf)[0]
        assert rep["peak_bytes"] > 0 and rep["peak_op"]

    def test_watch_events_logged_with_warn_action(self, tmp_path):
        from thunder_tpu.observability.instrument import NaNWatcher

        log = str(tmp_path / "w.jsonl")
        obs_events.set_global_path(log)
        try:
            watcher = NaNWatcher(mode="nan", action="warn")
            jf = ttpu.jit(lambda x: clang.true_divide(x, x), executors=["jax"],
                          instrument=watcher)
            with pytest.warns(RuntimeWarning):
                jf(np.zeros((2, 2), np.float32))  # 0/0
        finally:
            obs_events.set_global_path(None)
        assert watcher.trips and watcher.trips[0]["kind"] == "NaN"
        trips = [r for r in _read_events(log) if r["kind"] == "nan_watch"]
        assert trips and trips[0]["symbol"] == watcher.trips[0]["symbol"]

    def test_module_frontend_rejects_debug_watch(self):
        torch = pytest.importorskip("torch")
        mod = torch.nn.Linear(4, 4)
        with pytest.raises(NotImplementedError):
            ttpu.jit(mod, debug_watch="nan")


# =============================================================================
# Dispatch metrics: padding waste
# =============================================================================


class TestPaddingWasteMetric:
    def test_waste_counted(self):
        monitor.enable()

        def f(x):
            return clang.sum(clang.tanh(x))

        jf = ttpu.jit(f, executors=["jax"], cache="symbolic values",
                      symbolic_dims={0: (0,)}, buckets={"batch": "pow2"})
        jf(np.ones((4, 8), np.float32))  # at the bucket ceiling: no waste
        before = obsm.PADDING_WASTE_ELEMENTS.value()
        jf(np.ones((3, 8), np.float32))  # padded 3 -> 4: one row of 8 wasted
        assert obsm.PADDING_WASTE_ELEMENTS.value() - before == 8
        assert obsm.BUCKET_COMPILES.value() >= 1


# =============================================================================
# Profiler bracketing
# =============================================================================


class TestProfile:
    def test_profile_smoke(self, tmp_path):
        def f(x):
            return clang.sum(clang.mul(x, x))

        jf = ttpu.jit(f, executors=["jax"])
        x = np.ones((8, 8), np.float32)
        res = ttpu.profile(jf, x, trace_dir=str(tmp_path / "prof"), steps=2, warmup=1)
        assert res["steps"] == 2 and res["avg_s"] > 0
        if res["profiler"]:
            assert os.path.isdir(res["trace_dir"])
            assert any(os.scandir(res["trace_dir"]))

    def test_profile_emits_events(self, tmp_path):
        log = str(tmp_path / "p.jsonl")
        obs_events.set_global_path(log)
        try:
            jf = ttpu.jit(lambda x: clang.abs(x), executors=["jax"])
            ttpu.profile(jf, np.ones((2,), np.float32),
                         trace_dir=str(tmp_path / "prof"), steps=1, warmup=0)
        finally:
            obs_events.set_global_path(None)
        kinds = [r["kind"] for r in _read_events(log)]
        assert "profile_start" in kinds and "profile_stop" in kinds


# =============================================================================
# Annotated codegen
# =============================================================================


class TestAnnotatedCodegen:
    def test_annotate_carries_line_and_pass(self):
        def f(x):
            return clang.sum(clang.tanh(x))

        jf = ttpu.jit(f, executors=["jax"])
        jf(np.ones((2, 2), np.float32))
        final = ttpu.last_traces(jf)[-1]
        src = final.python(annotate=True)
        # '#' separator: JAX's name stack truncates scope names at '@', which
        # would strip the pass provenance from HLO metadata (PR 5 fix).
        assert "__annotate_scope('L0.tanh#Delete_Last_Used')" in src
        assert "L2.sum#Delete_Last_Used" in src

    def test_regions_of_the_traced_function_nest_and_reach_the_hlo(self):
        """``core.trace.region`` around lines of the traced function: their
        generated lines, and the ``del`` after their last use, stand under one
        ``with`` a region, nested regions as ``a/b``, under annotation too; the
        result does not change; outside a trace the context does nothing."""
        import jax

        from thunder_tpu.core.trace import region

        def f(x):
            with region("outer"):
                y = clang.tanh(x)
                with region("inner"):
                    z = clang.mul(y, y)
            return clang.sum(z)

        with region("nowhere"):
            pass
        x = np.ones((2, 2), np.float32)
        jf = ttpu.jit(f, executors=["jax"])
        np.testing.assert_allclose(np.asarray(jf(x)), 4 * np.tanh(1.0) ** 2, rtol=1e-6)
        final = ttpu.last_traces(jf)[-1]
        assert [b.region for b in final.bound_symbols if b.sym.name in ("tanh", "mul", "sum")] == [
            "outer", "outer/inner", None]
        lines = [line for line in final.python().splitlines() if "__region" in line]
        assert lines == ["  with __region('outer'):", "  with __region('outer/inner'):"]
        assert final.python(annotate=True).count("with __region(") == 2
        hlo = jax.jit(final.python_callable()).lower(x).as_text(debug_info=True)
        assert "outer/inner/" in hlo

    @pytest.mark.parametrize("name,layers", [("conv", 3), ("attn.qk_norm", 1)])
    def test_a_layers_mixer_has_a_region_of_its_own_in_the_program_and_the_hlo(self, name, layers):
        """The model opens ``conv`` around each gated short convolution with its
        two projections and ``attn.qk_norm`` around the norms of the query and
        key heads (``models/gpt.py``, ISSUE 31): one ``with`` a layer that has
        the mixer, the lines inside carry the region, and jax's HLO names it."""
        import jax

        from thunder_tpu.core import dtypes
        from thunder_tpu.models import gpt

        cfg = gpt.name_to_config("lfm2-tiny")
        params = gpt.init_params(cfg, dtype=dtypes.float32, seed=0)
        idx = np.arange(32, dtype=np.int32).reshape(2, 16) % cfg.padded_vocab_size
        jf = ttpu.jit(lambda p, i: gpt.forward(p, i, cfg), executors=["jax"])
        jf(params, idx)
        final = ttpu.last_traces(jf)[-1]
        assert final.python().count(f"with __region('{name}'):") == layers
        inside = [b.sym.name for b in final.bound_symbols if b.region == name]
        assert inside and ("linear" in inside) == (name == "conv") and ("rsqrt" in inside) == (name == "attn.qk_norm")
        hlo = jax.jit(final.python_callable()).lower(*jax.tree_util.tree_leaves((params, idx))).as_text(debug_info=True)
        assert f"/{name}/" in hlo


# =============================================================================
# Event replay / recompile-storm analysis
# =============================================================================


class TestEventReplay:
    def test_roundtrip_clean(self, tmp_path):
        from thunder_tpu.analysis.events import replay_events

        log = str(tmp_path / "ev.jsonl")

        def f(x):
            return clang.sum(clang.tanh(x))

        jf = ttpu.jit(f, executors=["jax"], events=log)
        jf(np.ones((2, 4), np.float32))
        summary, diags = replay_events(log)
        assert not diags
        assert summary["kinds"]["compile_start"] == 1
        assert summary["compiles_by_fn"] == {"f": 1}
        assert summary["pass_ms_total"].get("Transform for execution", 0) > 0

    def test_recompile_storm_flagged(self, tmp_path):
        from thunder_tpu.analysis import Severity
        from thunder_tpu.analysis.events import replay_events

        log = str(tmp_path / "storm.jsonl")

        def f(x):
            return clang.sum(clang.tanh(x))

        jf = ttpu.jit(f, executors=["jax"], events=log)
        for n in range(2, 9):  # 7 distinct exact shapes -> 7 compiles
            jf(np.ones((n, 4), np.float32))
        summary, diags = replay_events(log, storm_threshold=4)
        storms = [d for d in diags if d.rule == "events.recompile-storm"]
        assert storms and storms[0].severity >= Severity.ERROR
        assert "7 times" in storms[0].message

    def test_healthy_bucket_sweep_not_flagged_as_storm(self, tmp_path):
        """One compile per shape bucket is the documented steady state for
        cache="symbolic values" — a sweep over many batch sizes must NOT
        trip the recompile-storm rule even when bucket count exceeds the
        exact-shape threshold."""
        from thunder_tpu.analysis.events import replay_events

        log = str(tmp_path / "buckets.jsonl")

        def f(x):
            return clang.sum(clang.tanh(x))

        jf = ttpu.jit(f, executors=["jax"], cache="symbolic values",
                      symbolic_dims={0: (0,)}, buckets={"batch": "pow2"},
                      events=log)
        for b in (1, 2, 3, 5, 9, 17, 33):  # 7 distinct pow2 buckets
            jf(np.ones((b, 4), np.float32))
        summary, diags = replay_events(log, storm_threshold=4)
        assert summary["kinds"]["compile_end"] == 7
        assert not [d for d in diags if d.rule == "events.recompile-storm"], [
            d.message for d in diags
        ]

    def test_schema_violations_flagged(self, tmp_path):
        from thunder_tpu.analysis import Severity
        from thunder_tpu.analysis.events import replay_events

        p = tmp_path / "bad.jsonl"
        p.write_text(
            "not json at all\n"
            '{"v": 1, "ts": 0, "seq": 0, "kind": "pass"}\n'  # missing fields
            '{"v": 99, "ts": 0, "seq": 1, "kind": "compile_start"}\n'  # bad version
            '{"v": 1, "ts": 0, "seq": 2, "kind": "mystery"}\n'  # unknown kind
        )
        _, diags = replay_events(str(p))
        rules = sorted(d.rule for d in diags)
        assert rules == [
            "events.malformed-line", "events.missing-fields",
            "events.schema-version", "events.unknown-kind",
        ]
        by_rule = {d.rule: d for d in diags}
        assert by_rule["events.unknown-kind"].severity == Severity.WARNING
        assert by_rule["events.missing-fields"].severity == Severity.ERROR

    def test_lint_traces_cli(self, tmp_path):
        import subprocess
        import sys

        log = str(tmp_path / "cli.jsonl")
        jf = ttpu.jit(lambda x: clang.abs(x), executors=["jax"], events=log)
        jf(np.ones((2,), np.float32))
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, os.path.join(repo, "scripts", "lint_traces.py"),
             "--events", log],
            capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "0 error(s)" in proc.stdout


# =============================================================================
# monitor facade
# =============================================================================


class TestMonitor:
    def test_enable_report_reset(self):
        monitor.enable()
        obsm.CACHE_MISSES.inc()
        assert monitor.report()["thunder_tpu_cache_misses_total"]["values"][""] == 1
        assert "thunder_tpu_cache_misses_total 1" in monitor.prometheus_text()
        monitor.reset()
        assert monitor.report()["thunder_tpu_cache_misses_total"]["values"] == {}

    def test_dump_json(self, tmp_path):
        monitor.enable()
        obsm.COMPILES.inc(2)
        p = tmp_path / "snap.json"
        monitor.dump_json(str(p))
        data = json.loads(p.read_text())
        assert data["metrics"]["thunder_tpu_compiles_total"]["values"][""] == 2
