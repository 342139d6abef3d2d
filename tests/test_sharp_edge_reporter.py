"""The sharp-edge reporter (``common.sharp_edge`` behind the interceptors of
``frontend/sharp.py``) reports the traced function's own reads, once each, and
nothing else: it does not report its own reads (ROADMAP D9: the unresolved
global event log read the patched ``os.environ`` and recursed to the limit),
it does not answer for another thread, and a ``gc`` callback that reads a clock
during ``build_train_step`` costs no traceback (ISSUE 35).

A report is counted where the reporter writes it: ``sharp_edge`` records of
the process-wide event log, which each test points at a file of its own."""

import gc
import json
import os
import random
import sys
import threading
import time
import warnings

import numpy as np
import pytest

import thunder_tpu
from thunder_tpu import clang
from thunder_tpu.api import trace_program
from thunder_tpu.common import resolve_sharp_edges_option, sharp_edges_policy
from thunder_tpu.observability import events

POLICIES = pytest.mark.parametrize("policy", ["allow", "warn", "error"])
# Whether the process-wide log was asked for before the trace: unresolved, the
# reporter's first report resolves it from inside the interceptors (D9).
RESOLVED = pytest.mark.parametrize("resolved", [False, True], ids=["log-unresolved", "log-resolved"])


@pytest.fixture
def global_log(monkeypatch, tmp_path):
    """The process-wide log as a fresh process has it: unresolved, with
    THUNDER_TPU_EVENTS naming a file. Yields the file's path."""
    path = tmp_path / "events.jsonl"
    monkeypatch.setenv("THUNDER_TPU_EVENTS", str(path))
    saved = dict(events._global)
    events._global.clear()
    events._global.update(path=None, log=None)
    yield path
    log = events._global.get("log")
    if log is not None:
        log.close()
    events._global.clear()
    events._global.update(saved)


def reports(path) -> list:
    if not path.exists():
        return []
    with open(path) as f:
        return [r for r in map(json.loads, f) if r["kind"] == "sharp_edge"]


def trace_under(policy: str, fn):
    """``fn`` through ``trace_program`` under ``policy``; (caught warnings of
    the reporter's, the error it raised or None)."""
    x = np.ones(3, dtype=np.float32)
    raised = None
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with sharp_edges_policy(resolve_sharp_edges_option(policy)):
            try:
                trace_program(fn, (x,), {})
            except thunder_tpu.ThunderSharpEdgeError as e:
                raised = e
    return [w for w in caught if issubclass(w.category, thunder_tpu.ThunderSharpEdgeWarning)], raised


def reads_environ(x):
    return clang.mul(x, float(os.environ.get("X", "2.0")))


def reads_clock(x):
    return clang.add(x, time.perf_counter())


def draws(x):
    return clang.mul(x, random.random())


@RESOLVED
@POLICIES
@pytest.mark.parametrize("fn, names", [(reads_environ, "os.environ['X']"), (reads_clock, "time.perf_counter"),
                                       (draws, "random.random")], ids=["environ", "perf_counter", "random"])
def test_one_read_is_one_report(global_log, policy, resolved, fn, names):
    if resolved:
        assert events.active_log() is not None
    caught, raised = trace_under(policy, fn)
    assert events._global["resolved"] and events._global["path"] == str(global_log)
    seen = reports(global_log)
    assert [r["policy"] for r in seen] == [policy], seen
    assert names in seen[0]["message"]
    assert len(caught) == (policy == "warn")
    assert (raised is not None) == (policy == "error")
    if policy != "allow":
        assert names in str(raised or caught[0].message)
    # the interceptors are gone, and the real functions are back
    assert type(os.environ) is os._Environ and type(time.perf_counter).__name__ == "builtin_function_or_method"


@POLICIES
def test_each_of_several_reads_is_reported_once(global_log, policy):
    def fn(x):
        scale = float(os.environ.get("X", "2.0")) if "X" in os.environ else 3.0
        return clang.mul(x, scale + time.time() * 0.0)

    caught, raised = trace_under(policy, fn)
    wanted = 1 if policy == "error" else 2  # the first raises
    assert len(reports(global_log)) == wanted
    assert len(caught) == (2 if policy == "warn" else 0)
    assert (raised is not None) == (policy == "error")


def test_jit_bakes_the_environment_it_read(global_log, monkeypatch):
    monkeypatch.setenv("X", "4.0")
    out = thunder_tpu.jit(reads_environ)(np.ones(3, dtype=np.float32))
    np.testing.assert_array_equal(np.asarray(out), np.full(3, 4.0, dtype=np.float32))
    assert len(reports(global_log)) == 1


class ClockReadingCollections:
    """What ``perfbench/run.py`` installs: a ``gc`` callback that reads the
    clock at the start and at the end of every collection."""

    events = 0

    def __call__(self, phase, info):
        self.events += 1
        time.perf_counter()


@pytest.fixture
def unraisable(monkeypatch):
    seen = []
    monkeypatch.setattr(sys, "unraisablehook", seen.append)
    return seen


@pytest.fixture
def collections():
    callback = ClockReadingCollections()
    gc.callbacks.append(callback)
    yield callback
    gc.callbacks.remove(callback)


def test_a_gc_callback_that_reads_the_clock_costs_build_train_step_no_traceback(
        global_log, unraisable, collections, monkeypatch):
    """The train path asks for no log before its trace (the ``jit`` path's
    ``cache_miss`` does), so the first report of the process is the callback's,
    from inside the interceptors."""
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt
    from thunder_tpu.parallel import build_train_step, train

    def collecting_loss_fn(*args, real=train.loss_fn):
        gc.collect()  # when a collection falls due is the allocator's business: one here, for certain
        return real(*args)

    monkeypatch.setattr(train, "loss_fn", collecting_loss_fn)
    monkeypatch.delenv("THUNDER_TPU_EVENTS")  # no log at all, as in the benchmark's runs
    cfg = gpt.name_to_config("llama-tiny")
    params = gpt.init_params(cfg, dtype=dtypes.bfloat16, seed=0)
    idx = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 32)).astype(np.int32)
    mark = time.perf_counter()
    before = collections.events
    build_train_step(cfg, params, idx, np.roll(idx, -1, axis=1).astype(np.int32))
    assert collections.events - before >= 2
    assert unraisable == [], [(type(u.exc_value).__name__, u.object) for u in unraisable[:3]]
    assert events._global["resolved"] and events._global["log"] is None
    phases = [r["phase"] for r in thunder_tpu.compile_phases() if r["at"] >= mark]
    assert phases[:2] == ["trace", "transforms"]


def test_a_collection_inside_the_traced_function_is_silent(global_log, unraisable, collections):
    def fn(x):
        gc.collect()
        return clang.mul(x, 2.0)

    before = collections.events
    caught, raised = trace_under("warn", fn)
    assert collections.events - before >= 2
    assert unraisable == [] and raised is None


def trace_beside_a_thread(policy: str, foreign_reads, traced_reads=lambda: 0.0):
    """``trace_under(policy, ...)`` of a function that, inside the interceptors,
    waits for another thread to run ``foreign_reads`` and then adds
    ``traced_reads()``; (what the thread saw, caught, raised)."""
    patched, done = threading.Event(), threading.Event()
    seen = {"error": None, "stand_in": None}

    def foreign():
        try:
            patched.wait(10)
            seen["stand_in"] = type(os.environ).__name__
            foreign_reads()
        except BaseException as e:  # noqa: BLE001 — the test reports it
            seen["error"] = e
        finally:
            done.set()

    def fn(x):
        patched.set()
        assert done.wait(10)
        return clang.add(x, traced_reads())

    thread = threading.Thread(target=foreign)
    thread.start()
    caught, raised = trace_under(policy, fn)
    thread.join(10)
    return seen, caught, raised


def test_another_thread_is_not_the_traced_function(global_log):
    """The interceptors are process-wide while the trace is acquired; a thread
    that is not tracing gets the real clock and the real mapping: no report,
    and under ``error`` nothing raised into it."""
    reads = []

    def foreign_reads():
        for _ in range(50):
            assert time.time() > 0 and time.perf_counter() > 0
            os.environ.get("X")
            "X" in os.environ
            reads.append(os.environ["PATH"])

    seen, caught, raised = trace_beside_a_thread("error", foreign_reads)
    assert seen == {"error": None, "stand_in": "_ReportingEnviron"} and len(reads) == 50
    assert raised is None and caught == [] and reports(global_log) == []


def test_the_tracing_thread_is_still_reported_beside_a_foreign_one(global_log):
    seen, caught, raised = trace_beside_a_thread("error", lambda: time.time(), traced_reads=lambda: time.time())
    assert seen["error"] is None and raised is not None and len(reports(global_log)) == 1


def test_the_global_log_resolves_once_even_when_the_read_raises(global_log, monkeypatch):
    class Unreadable(dict):
        reads = 0

        def get(self, key, default=None):
            Unreadable.reads += 1
            raise OSError("no environment here")

    monkeypatch.setattr(os, "environ", Unreadable())
    with pytest.raises(OSError):
        events.active_log()
    assert events._global["resolved"] and events._global["log"] is None
    assert events.active_log() is None  # the next caller neither reads nor raises
    assert Unreadable.reads == 1


def test_the_global_log_reads_the_mapping_the_interceptors_wrap(global_log):
    """D9 without the reporter's own suppression: the resolution, asked from
    inside the interceptors, does not go through the stand-in."""
    from thunder_tpu.frontend.sharp import sharp_edge_interceptors

    with sharp_edges_policy(resolve_sharp_edges_option("error")), sharp_edge_interceptors():
        log = events.active_log()
    assert log is not None and log.path == str(global_log)
    assert reports(global_log) == []
