"""Resilience subsystem tests (ISSUE 6): the chaos matrix.

Every fault class × its recovery path: executor kernel raise → demotion
(quarantine + re-claim, bitwise-equal rerun), compile failure / OOM → the
de-opt ladder (bitwise-equal rerun, per-entry degradation_level), NaN
poisoning → the post-step isfinite guard with instrumented attribution,
checkpoint I/O errors → retry/backoff, corrupted checkpoints → fallback
restore, preemption → step-boundary save + resume reproducing the
uninterrupted loss trajectory. Plus the chaos spec grammar, the
fault_injected → degradation event correlation in the replay, and the
satellites (event-log drop counter, compile-cache sweep, narrowed jaxex
donation probe).
"""

import json
import os
import signal
import time

import numpy as np
import pytest

import thunder_tpu as ttpu
import thunder_tpu.monitor as monitor
from thunder_tpu.core.prims import PrimIDs
from thunder_tpu.extend import OperatorExecutor, get_executor, register_executor
from thunder_tpu.resilience import chaos, demotion
from thunder_tpu.resilience.chaos import (
    InjectedCompileError,
    InjectedCompileTimeout,
    InjectedKernelError,
    InjectedOOMError,
)
from thunder_tpu.resilience.deopt import NonFiniteOutputError
from thunder_tpu.resilience.preemption import (
    CheckpointManager,
    CheckpointRestoreError,
    CheckpointWriteError,
    Preempted,
    PreemptionGuard,
    run_training,
)


@pytest.fixture(autouse=True)
def _resilience_isolation(monkeypatch):
    """Zero backoff, no ambient chaos, empty quarantine, metrics reset."""
    monkeypatch.setenv("THUNDER_TPU_RETRY_BACKOFF_S", "0")
    monkeypatch.delenv("THUNDER_TPU_CHAOS", raising=False)
    chaos.reset_env_config()
    demotion.clear_quarantine()
    was = monitor.enabled()
    monitor.disable()
    monitor.reset()
    yield
    monitor.reset()
    (monitor.enable if was else monitor.disable)()
    demotion.clear_quarantine()
    chaos.reset_env_config()


def _events(path):
    return [json.loads(line) for line in open(path)]


def _kinds(path):
    return [r["kind"] for r in _events(path)]


def _toy_executor():
    """A chaos-armed executor claiming the tanh prim, registered once. Its
    impl delegates to the jax executor's, so an un-demoted claim stays
    bitwise-identical to the jax baseline."""
    ex = get_executor("toyex")
    if ex is not None:
        return ex
    ex = OperatorExecutor("toyex")
    register_executor(ex)
    jax_tanh = get_executor("jax").get_impl(PrimIDs.TANH)

    def _toy_tanh(a, _jax_tanh=jax_tanh):
        chaos.kernel_seam("toyex", "tanh")
        return _jax_tanh(a)

    ex.register_implementation(PrimIDs.TANH, fn=_toy_tanh)
    return ex


def _fn(a):
    return (a.tanh() * 2.0 + 1.0).sum()


X = np.random.RandomState(0).randn(4, 4).astype(np.float32)


def _baseline():
    return np.asarray(ttpu.jit(_fn, executors=["jax"])(X))


# =============================================================================
# Chaos spec grammar
# =============================================================================


class TestChaosSpec:
    def test_parse_components(self):
        cfg = chaos.parse_spec("kernel_raise@flash*2;oom%0.5;seed=7")
        assert cfg.seed == 7
        kr, oom = cfg.rules
        assert (kr.seam, kr.target, kr.count) == ("kernel_raise", "flash", 2)
        assert (oom.seam, oom.target, oom.prob) == ("oom", None, 0.5)

    def test_suffix_order_insensitive(self):
        a = chaos.parse_spec("straggler@any*2~0.05").rules[0]
        b = chaos.parse_spec("straggler@any~0.05*2").rules[0]
        assert (a.count, a.delay_s) == (b.count, b.delay_s) == (2, 0.05)

    def test_unknown_seam_raises(self):
        with pytest.raises(ValueError, match="unknown seam"):
            chaos.parse_spec("explode*1")

    def test_bad_prob_raises(self):
        with pytest.raises(ValueError, match="prob"):
            chaos.parse_spec("oom%1.5")

    def test_count_inf(self):
        assert chaos.parse_spec("oom*inf").rules[0].count == float("inf")

    def test_count_exhausts(self):
        with chaos.chaos_scope("oom*2"):
            fired = [chaos._should_fire("oom") is not None for _ in range(4)]
        assert fired == [True, True, False, False]

    def test_seeded_probability_is_deterministic(self):
        def draw(spec):
            with chaos.chaos_scope(spec):
                return [chaos._should_fire("oom") is not None for _ in range(12)]

        a = draw("oom*inf%0.5;seed=42")
        b = draw("oom*inf%0.5;seed=42")
        c = draw("oom*inf%0.5;seed=9")
        assert a == b
        assert a != c

    def test_env_activation(self, monkeypatch):
        monkeypatch.setenv("THUNDER_TPU_CHAOS", "oom*1")
        chaos.reset_env_config()
        assert chaos.enabled()
        assert chaos.active().rules[0].seam == "oom"

    def test_injected_errors_name_their_seam(self):
        assert InjectedKernelError("flash", "sdpa").seam == "kernel_raise"
        assert InjectedOOMError().seam == "oom"
        assert "RESOURCE_EXHAUSTED" in str(InjectedOOMError())
        assert InjectedCompileTimeout("f").seam == "compile_timeout"


# =============================================================================
# Executor demotion (kernel_raise → quarantine → re-claim)
# =============================================================================


class TestExecutorDemotion:
    def test_kernel_raise_recovers_bitwise_equal(self, tmp_path):
        _toy_executor()
        baseline = _baseline()
        log = str(tmp_path / "ev.jsonl")
        jf = ttpu.jit(_fn, executors=["toyex", "jax"],
                      chaos="kernel_raise@toyex*1", events=log)
        out = jf(X)
        assert np.array_equal(np.asarray(out), baseline)
        # quarantined pair + jax-only claims in the recompiled trace
        assert any(k == (PrimIDs.TANH, "toyex")
                   for k in demotion.quarantine_snapshot())
        claims = ttpu.last_traces(jf)[-1].tags.get("claim_breakdown") or {}
        assert "toyex" not in claims
        kinds = _kinds(log)
        assert "fault_injected" in kinds and "executor_demoted" in kinds
        assert kinds.index("fault_injected") < kinds.index("executor_demoted")
        # warm path serves the demoted entry
        assert np.array_equal(np.asarray(jf(X)), baseline)

    def test_recovery_is_a_warning_that_names_the_exception(self, caplog):
        """A caller who did not ask for recovery can see that it happened:
        every demotion/escalation warns on the ``thunder_tpu`` logger."""
        _toy_executor()
        jf = ttpu.jit(_fn, executors=["toyex", "jax"], chaos="kernel_raise@toyex*1")
        with caplog.at_level("WARNING", logger="thunder_tpu"):
            jf(X)
        msgs = [r.getMessage() for r in caplog.records]
        assert any("recovered from a kernel failure" in m and "InjectedKernelError" in m
                   and "demoting" in m for m in msgs), msgs

    def test_warm_entry_failure_demotes(self, tmp_path):
        """Unstaged (op-by-op) entries re-enter kernel impls every call, so
        a kernel fault on a WARM entry must evict + demote + recompile —
        the staged path only reaches impls during its first-run trace."""
        _toy_executor()
        baseline = _baseline()
        jf = ttpu.jit(_fn, executors=["toyex", "jax"], disable_jit_staging=True)
        assert np.array_equal(np.asarray(jf(X)), baseline)  # healthy warm entry
        with chaos.chaos_scope("kernel_raise@toyex*1"):
            out = jf(X)  # warm run raises → evict, demote, recompile, rerun
        assert np.array_equal(np.asarray(out), baseline)
        assert demotion.quarantine_snapshot()
        # the recovered call re-accounts as a miss: hits + misses == calls
        cs = ttpu.compile_stats(jf)
        assert cs.cache_hits + cs.cache_misses == cs.calls

    def test_quarantine_ttl_expires(self):
        demotion.quarantine("some.sym", "toyex", ttl=0.05)
        assert demotion.is_quarantined("some.sym", "toyex")
        time.sleep(0.06)
        assert not demotion.is_quarantined("some.sym", "toyex")

    def test_terminal_executors_never_quarantined(self):
        assert not demotion.quarantine("s", "jax")
        assert not demotion.quarantine("s", "python")
        assert not demotion.is_quarantined("s", "jax")

    def test_wildcard_quarantine(self):
        demotion.quarantine("*", "toyex", ttl=10)
        assert demotion.is_quarantined("anything.at.all", "toyex")

    def test_unrecognized_error_propagates(self):
        class Boom(RuntimeError):
            pass

        ex = get_executor("boomex")
        if ex is None:
            ex = OperatorExecutor("boomex")
            register_executor(ex)

            def _boom(a):
                raise Boom("user bug, not a fault class")

            ex.register_implementation(PrimIDs.TANH, fn=_boom)
        jf = ttpu.jit(_fn, executors=["boomex", "jax"])
        with pytest.raises(Boom):
            jf(X)
        assert not demotion.quarantine_snapshot()


# =============================================================================
# Compile de-opt ladder
# =============================================================================


class TestDeoptLadder:
    def test_compile_fail_recovers_at_level_1(self, tmp_path):
        baseline = _baseline()
        log = str(tmp_path / "ev.jsonl")
        jf = ttpu.jit(_fn, executors=["jax"], chaos="compile_fail*1", events=log)
        assert np.array_equal(np.asarray(jf(X)), baseline)
        info = ttpu.cache_info(jf)
        assert info["degradation_level"] == 1
        assert [e["degradation_level"] for e in info["entries"]] == [1]
        kinds = _kinds(log)
        assert kinds.index("fault_injected") < kinds.index("compile_deopt")

    def test_compile_timeout_recovers(self):
        baseline = _baseline()
        jf = ttpu.jit(_fn, executors=["jax"], chaos="compile_timeout*1")
        assert np.array_equal(np.asarray(jf(X)), baseline)
        assert ttpu.cache_info(jf)["degradation_level"] == 1

    def test_oom_at_first_run_recovers(self, tmp_path):
        baseline = _baseline()
        log = str(tmp_path / "ev.jsonl")
        jf = ttpu.jit(_fn, executors=["jax"], chaos="oom*1", events=log)
        assert np.array_equal(np.asarray(jf(X)), baseline)
        info = ttpu.cache_info(jf)
        # the failed entry was evicted; only the recovered one remains
        assert len(info["entries"]) == 1
        assert info["entries"][0]["degradation_level"] == 1
        kinds = _kinds(log)
        assert kinds.index("fault_injected") < kinds.index("compile_deopt")

    def test_repeated_oom_climbs_to_exact_shapes(self):
        """Three OOMs walk L1→L2→L3; at L3 a symbolic-values function
        compiles an exact (no bucket padding) entry."""
        jf = ttpu.jit(_fn, executors=["jax"], cache="symbolic values",
                      symbolic_dims={0: (0,)}, chaos="oom*3")
        out = jf(X)
        baseline = _baseline()
        assert np.array_equal(np.asarray(out), baseline)
        info = ttpu.cache_info(jf)
        assert info["degradation_level"] == 3
        assert info["entries"][-1]["buckets"] == "exact"

    def test_ladder_exhausted_raises_typed_error(self):
        jf = ttpu.jit(_fn, executors=["jax"], chaos="oom*inf")
        with pytest.raises(InjectedOOMError):
            jf(X)

    def test_compile_failures_exhaust_loudly(self):
        jf = ttpu.jit(_fn, executors=["jax"], chaos="compile_fail*inf")
        with pytest.raises(InjectedCompileError):
            jf(X)

    def test_aggressive_remat_scope(self):
        from thunder_tpu.transforms import rematerialization as remat

        assert remat.aggressiveness() == "normal"
        with remat.aggressive_remat():
            assert remat.aggressiveness() == "aggressive"
        assert remat.aggressiveness() == "normal"


# =============================================================================
# NaN poisoning + post-step isfinite guard
# =============================================================================


class TestNaNGuard:
    def test_poison_plus_raise(self):
        jf = ttpu.jit(_fn, executors=["jax"], chaos="nan@tanh*1", on_nan="raise")
        with pytest.raises(NonFiniteOutputError):
            jf(X)

    def test_rerun_instrumented_attributes_producer(self, tmp_path):
        log = str(tmp_path / "ev.jsonl")
        jf = ttpu.jit(_fn, executors=["jax"], chaos="nan@tanh*1",
                      on_nan="rerun-instrumented", events=log)
        with pytest.raises(NonFiniteOutputError) as exc_info:
            jf(X)
        assert exc_info.value.symbol == "chaos_nan_poison"
        assert exc_info.value.line is not None
        kinds = _kinds(log)
        assert kinds.index("fault_injected") < kinds.index("nan_guard")
        assert "nan_watch" in kinds  # the instrumented re-run's attribution

    def test_on_nan_warn_returns_result(self):
        jf = ttpu.jit(_fn, executors=["jax"], chaos="nan@tanh*1", on_nan="warn")
        with pytest.warns(RuntimeWarning, match="non-finite"):
            out = jf(X)
        assert not np.isfinite(np.asarray(out)).all()

    def test_guard_passes_clean_runs(self):
        jf = ttpu.jit(_fn, executors=["jax"], on_nan="raise")
        out = jf(X)
        assert np.array_equal(np.asarray(out), _baseline())
        assert np.array_equal(np.asarray(jf(X)), _baseline())  # warm path too

    def test_invalid_on_nan_rejected(self):
        with pytest.raises(ValueError, match="on_nan"):
            ttpu.jit(_fn, on_nan="explode")

    def test_real_nan_input_trips_guard(self):
        """The guard is not chaos-specific: a genuinely non-finite output
        trips it too."""
        jf = ttpu.jit(lambda a: (a / a).sum(), executors=["jax"], on_nan="raise")
        with pytest.raises(NonFiniteOutputError):
            jf(np.zeros(4, np.float32))

    def test_guard_ignores_nonfinite_padding_lanes(self):
        """Bucketed entries zero-pad inputs, so 1/0 = inf appears in the
        PADDING lanes of the uncropped output — the guard must check the
        cropped (user-visible) output only."""
        jf = ttpu.jit(lambda a: 1.0 / a, executors=["jax"],
                      cache="symbolic values", symbolic_dims={0: (0,)},
                      on_nan="raise")
        x = np.arange(1, 7, dtype=np.float32).reshape(6, 1)  # pads dim0 6→8
        out = jf(x)  # must not raise: only padding rows are inf
        assert out.shape == (6, 1)
        assert np.isfinite(np.asarray(out)).all()


# =============================================================================
# Collective straggler
# =============================================================================


class TestStraggler:
    def test_straggler_delays_but_completes(self, tmp_path):
        log = str(tmp_path / "ev.jsonl")
        jf = ttpu.jit(_fn, executors=["jax"],
                      chaos="straggler@any~0.05*2", events=log)
        jf(X)  # first run consumes one fire
        t0 = time.perf_counter()
        out = jf(X)  # warm run consumes the second
        dt = time.perf_counter() - t0
        assert dt >= 0.05
        assert np.array_equal(np.asarray(out), _baseline())
        assert "fault_injected" in _kinds(log)
        t0 = time.perf_counter()
        jf(X)  # rule exhausted: no delay
        assert time.perf_counter() - t0 < 0.05


# =============================================================================
# Checkpoint manager (retry, corruption fallback)
# =============================================================================


def _state():
    import jax.numpy as jnp

    return {"p": jnp.arange(6, dtype=jnp.float32), "step": 3}


class TestCheckpointManager:
    def test_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / "ck"), backoff_s=0)
        mgr.save(_state(), 7, rng_seed=11)
        state, meta = mgr.restore()
        assert meta["step"] == 7 and meta["rng_seed"] == 11
        assert np.array_equal(np.asarray(state["p"]), np.arange(6, dtype=np.float32))

    def test_transient_io_error_retries(self, tmp_path):
        log = str(tmp_path / "ev.jsonl")
        from thunder_tpu.observability import events as obs_events

        mgr = CheckpointManager(str(tmp_path / "ck"), retries=3, backoff_s=0)
        with obs_events.event_scope(obs_events.log_for_path(log)):
            with chaos.chaos_scope("ckpt_io*2"):
                mgr.save(_state(), 1)
        assert mgr.latest_complete_step() == 1
        saves = [r for r in _events(log) if r["kind"] == "checkpoint_save"]
        assert [s["ok"] for s in saves] == [False, False, True]
        assert [r["kind"] for r in _events(log)].count("fault_injected") == 2

    def test_exhausted_retries_raise_typed_error(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / "ck"), retries=1, backoff_s=0)
        with chaos.chaos_scope("ckpt_io*inf"):
            with pytest.raises(CheckpointWriteError, match="ckpt_io"):
                mgr.save(_state(), 1)
        assert mgr.latest_complete_step() is None

    def test_corrupted_newest_falls_back(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / "ck"), backoff_s=0)
        mgr.save(_state(), 1)
        mgr.save(_state(), 2)
        # Torn write: newest step lost its commit marker
        os.remove(os.path.join(mgr._step_dir(2), mgr.META))
        _, meta = mgr.restore()
        assert meta["step"] == 1

    def test_corrupted_payload_quarantined(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / "ck"), backoff_s=0)
        mgr.save(_state(), 1)
        mgr.save(_state(), 2)
        # Corrupt the newest payload wholesale but keep the marker
        import shutil

        step2 = mgr._step_dir(2)

        def corrupt(step_dir):
            for name in os.listdir(step_dir):
                if name != mgr.META:
                    p = os.path.join(step_dir, name)
                    shutil.rmtree(p) if os.path.isdir(p) else os.remove(p)

        corrupt(step2)
        _, meta = mgr.restore()
        assert meta["step"] == 1
        assert os.path.isdir(step2 + ".corrupt")
        # the same step corrupting AGAIN (after a resume re-saved it) must
        # still quarantine + fall back, not collide with the old .corrupt
        mgr.save(_state(), 2)
        corrupt(mgr._step_dir(2))
        _, meta = mgr.restore()
        assert meta["step"] == 1
        assert os.path.isdir(step2 + ".corrupt.1")

    def test_no_complete_checkpoint_raises(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / "ck"), backoff_s=0)
        with pytest.raises(CheckpointRestoreError):
            mgr.restore()

    def test_retention(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / "ck"), backoff_s=0, keep=2)
        for s in (1, 2, 3, 4):
            mgr.save(_state(), s)
        assert mgr.steps_on_disk() == [3, 4]


# =============================================================================
# Preemption-safe training
# =============================================================================


def _make_step():
    import jax.numpy as jnp

    def step(state):
        p = state["p"]
        p = p - 0.1 * (2.0 * p)
        return {"p": p}, float(jnp.sum(p * p))

    return step


def _init_state():
    import jax.numpy as jnp

    return {"p": jnp.arange(8, dtype=jnp.float32)}


class TestPreemption:
    def test_sigterm_sets_flag_and_restores_handler(self):
        before = signal.getsignal(signal.SIGTERM)
        with PreemptionGuard() as guard:
            assert not guard.requested_local()
            os.kill(os.getpid(), signal.SIGTERM)
            assert guard.requested_local()
            assert guard.should_checkpoint()
        assert signal.getsignal(signal.SIGTERM) is before

    def test_sigterm_event_emitted_at_poll_not_in_handler(self, tmp_path):
        """The signal handler must only set flags (emitting under EventLog's
        non-reentrant lock from a handler can deadlock); the preemption
        event lands at the next step-boundary poll, exactly once."""
        from thunder_tpu.observability import events as obs_events

        log = str(tmp_path / "ev.jsonl")
        with obs_events.event_scope(obs_events.log_for_path(log)):
            with PreemptionGuard() as guard:
                os.kill(os.getpid(), signal.SIGTERM)
                while not guard._flag:  # handler runs at a bytecode boundary
                    time.sleep(0.001)
                assert not os.path.exists(log) or "preemption" not in _kinds(log)
                assert guard.requested_local(step=5)
                assert _kinds(log).count("preemption") == 1
                guard.requested_local(step=6)  # repeated polls don't re-emit
                assert _kinds(log).count("preemption") == 1

    def test_preempt_save_resume_matches_uninterrupted(self, tmp_path):
        uninterrupted_mgr = CheckpointManager(str(tmp_path / "a"), backoff_s=0)
        _, losses_all = run_training(
            _make_step(), _init_state(), 8, manager=uninterrupted_mgr
        )
        assert len(losses_all) == 8

        mgr = CheckpointManager(str(tmp_path / "b"), backoff_s=0)
        with chaos.chaos_scope("preempt@3"):
            with pytest.raises(Preempted) as exc_info:
                run_training(_make_step(), _init_state(), 8, manager=mgr)
        assert exc_info.value.step == 3
        assert mgr.latest_complete_step() == 3

        # fresh "process": resume and finish — the trajectory must match the
        # uninterrupted run exactly
        _, losses_resumed = run_training(
            _make_step(), _init_state(), 8, manager=mgr
        )
        assert losses_resumed == losses_all[3:]

    def test_preemption_events_logged(self, tmp_path):
        from thunder_tpu.observability import events as obs_events

        log = str(tmp_path / "ev.jsonl")
        mgr = CheckpointManager(str(tmp_path / "ck"), backoff_s=0)
        with obs_events.event_scope(obs_events.log_for_path(log)):
            with chaos.chaos_scope("preempt@2"):
                with pytest.raises(Preempted):
                    run_training(_make_step(), _init_state(), 5, manager=mgr)
        kinds = _kinds(log)
        assert "fault_injected" in kinds and "preemption" in kinds
        assert "checkpoint_save" in kinds

    def test_save_every_cadence_supports_resume(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / "ck"), backoff_s=0)
        _, losses_all = run_training(
            _make_step(), _init_state(), 6,
            manager=CheckpointManager(str(tmp_path / "ref"), backoff_s=0),
        )
        # crash (simulated) right after the step-4 cadence checkpoint
        run_training(_make_step(), _init_state(), 4, manager=mgr, save_every=2)
        assert mgr.latest_complete_step() == 2  # saved mid-run, not at the end
        _, tail = run_training(_make_step(), _init_state(), 6, manager=mgr)
        assert tail == losses_all[2:]


# =============================================================================
# Event-log replay: fault → recovery correlation
# =============================================================================


def _write_log(path, records):
    with open(path, "w") as f:
        for i, rec in enumerate(records):
            rec = dict({"v": 1, "ts": float(i), "seq": i, "pid": 1, "host": 0}, **rec)
            f.write(json.dumps(rec) + "\n")


class TestReplayCorrelation:
    def test_unrecovered_fault_flagged(self, tmp_path):
        from thunder_tpu.analysis import Severity
        from thunder_tpu.analysis.events import replay_events

        log = str(tmp_path / "ev.jsonl")
        _write_log(log, [
            {"kind": "fault_injected", "seam": "kernel_raise", "target": "flash", "n": 1},
        ])
        summary, diags = replay_events(log)
        assert summary["unrecovered_faults"] == ["kernel_raise@flash"]
        assert any(d.rule == "events.unrecovered-fault"
                   and d.severity >= Severity.ERROR for d in diags)

    def test_recovered_fault_clean(self, tmp_path):
        from thunder_tpu.analysis.events import replay_events

        log = str(tmp_path / "ev.jsonl")
        _write_log(log, [
            {"kind": "fault_injected", "seam": "kernel_raise", "target": "flash", "n": 1},
            {"kind": "executor_demoted", "sym": "torch.sdpa", "executor": "flash",
             "ttl_s": 300.0, "reason": "InjectedKernelError"},
            {"kind": "fault_injected", "seam": "ckpt_io", "target": None, "n": 1},
            {"kind": "checkpoint_save", "path": "/x", "step": 1, "ok": True, "attempt": 1},
        ])
        summary, diags = replay_events(log)
        assert summary["unrecovered_faults"] == []
        assert not [d for d in diags if d.rule == "events.unrecovered-fault"]

    def test_failed_save_does_not_count_as_recovery(self, tmp_path):
        from thunder_tpu.analysis.events import replay_events

        log = str(tmp_path / "ev.jsonl")
        _write_log(log, [
            {"kind": "fault_injected", "seam": "ckpt_io", "target": None, "n": 1},
            {"kind": "checkpoint_save", "path": "/x", "step": 1, "ok": False, "attempt": 0},
        ])
        summary, _ = replay_events(log)
        assert summary["unrecovered_faults"] == ["ckpt_io@None"]


# =============================================================================
# Satellites
# =============================================================================


class TestEventLogDropSatellite:
    def test_sink_failure_increments_counter_without_metrics(self, tmp_path):
        from thunder_tpu.observability.events import EventLog
        from thunder_tpu.observability.metrics import EVENT_LOG_DROPPED

        assert not monitor.enabled()
        before = EVENT_LOG_DROPPED.value()
        log = EventLog(str(tmp_path / "nope" / "deep"))
        # make the directory path unwritable by shadowing it with a file
        (tmp_path / "nope").write_text("a file, not a dir")
        with pytest.warns(UserWarning, match="disabled after I/O failure"):
            log.emit("cache_miss", fn="f", call=1)
        assert EVENT_LOG_DROPPED.value() == before + 1
        # visible in the monitor report despite metrics being disabled
        rep = monitor.report()["thunder_tpu_event_log_dropped_total"]
        assert sum(rep["values"].values()) >= 1


class TestCompileCacheSatellite:
    def test_sweep_removes_torn_entries_only(self, tmp_path, caplog):
        from thunder_tpu.resilience.compile_cache import sweep_corrupt_entries

        good = tmp_path / "entry_good"
        good.write_bytes(b"x" * 64)
        torn = tmp_path / "entry_torn"
        torn.write_bytes(b"")
        with caplog.at_level("WARNING", logger="thunder_tpu"):
            removed = sweep_corrupt_entries(str(tmp_path))
        assert removed == [str(torn)]
        assert good.exists() and not torn.exists()
        assert any("corrupt entry" in r.message for r in caplog.records)

    def test_chaos_corrupt_then_sweep(self, tmp_path):
        from thunder_tpu.resilience.compile_cache import sweep_corrupt_entries

        (tmp_path / "entry").write_bytes(b"y" * 32)
        with chaos.chaos_scope("cache_corrupt*1"):
            victim = chaos.corrupt_cache_seam(str(tmp_path))
        assert victim is not None and os.path.getsize(victim) == 0
        assert sweep_corrupt_entries(str(tmp_path)) == [victim]

    def test_corrupt_seam_not_consumed_on_empty_dir(self, tmp_path):
        """An empty cache dir must not consume the rule (or record a
        fault_injected with no possible recovery event) — the injection
        stays armed for a dir that has something to corrupt."""
        empty = tmp_path / "empty"
        empty.mkdir()
        (tmp_path / "entry").write_bytes(b"y" * 32)
        with chaos.chaos_scope("cache_corrupt*1"):
            assert chaos.corrupt_cache_seam(str(empty)) is None
            victim = chaos.corrupt_cache_seam(str(tmp_path))  # still armed
        assert victim is not None

    def test_cache_corrupt_seam_wired_into_runtime_config(self, tmp_path, monkeypatch):
        """The seam fires (and the sweep repairs) when the persistent cache
        dir is first configured — the end-to-end recovery, not just the
        helpers in isolation."""
        import jax

        from thunder_tpu import api

        entry = tmp_path / "entry"
        entry.write_bytes(b"z" * 32)
        monkeypatch.setattr(api, "_cache_dir_logged", {"dir": None})
        old = jax.config.jax_compilation_cache_dir
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        try:
            with chaos.chaos_scope("cache_corrupt*1"):
                ttpu.jit(_fn, executors=["jax"])  # jit() → _ensure_runtime
        finally:
            jax.config.update("jax_compilation_cache_dir", old)
        assert not entry.exists()  # corrupted by the seam, removed by the sweep


class TestJaxexDonationSatellite:
    def test_backend_runtime_error_reports_sharp_edge(self, monkeypatch, tmp_path):
        import jax

        from thunder_tpu.executors.jaxex import _donation_active
        from thunder_tpu.observability import events as obs_events

        def boom():
            raise RuntimeError("no backend")

        monkeypatch.setattr(jax, "default_backend", boom)
        log_path = str(tmp_path / "ev.jsonl")
        with obs_events.event_scope(obs_events.log_for_path(log_path)):
            assert _donation_active() is False
        recs = _events(log_path)
        assert any(r["kind"] == "sharp_edge" and "donation" in r["message"]
                   for r in recs)

    def test_unexpected_error_propagates(self, monkeypatch):
        import jax

        from thunder_tpu.executors.jaxex import _donation_active

        def boom():
            raise TypeError("API change")

        monkeypatch.setattr(jax, "default_backend", boom)
        with pytest.raises(TypeError):
            _donation_active()


class TestQuarantineMetricsAndInfo:
    def test_demotion_metric(self):
        monitor.enable()
        demotion.quarantine("a.b", "flash", ttl=1)
        from thunder_tpu.observability.metrics import EXECUTOR_DEMOTIONS

        assert EXECUTOR_DEMOTIONS.value(executor="flash") == 1

    def test_cache_info_default_degradation(self):
        jf = ttpu.jit(_fn, executors=["jax"])
        jf(X)
        info = ttpu.cache_info(jf)
        assert info["degradation_level"] == 0
        assert info["entries"][0]["degradation_level"] == 0
