"""The dispatcher's spans: one record a call in ``thunder_tpu.dispatch_phases()``
and the ``thunder_tpu.call*`` annotations on the profiler's trace, at the same
marks ``cache_info`` and the dispatch histograms read."""

import collections
import glob
import os
import time

import jax
import numpy as np
import pytest

import thunder_tpu
import thunder_tpu.clang as clang
from thunder_tpu import api

PARTS = ("lookup_s", "prepare_s", "launch_s")


@pytest.fixture
def records(monkeypatch):
    """The record is the process's: this test gets one of its own, as long."""
    mine = collections.deque(maxlen=api._dispatch_records.maxlen)
    monkeypatch.setattr(api, "_dispatch_records", mine)
    return mine


def _fn(a, b):
    return clang.tanh(clang.add(clang.mul(a, b), a))


def _args():
    rng = np.random.RandomState(0)
    return rng.randn(8, 8).astype(np.float32), rng.randn(8, 8).astype(np.float32)


@pytest.mark.parametrize("cache, warm_hit", [(None, "fast"), ("same input", "same_input")])
def test_one_record_a_call_with_the_hit_it_was(records, cache, warm_hit):
    jfn = thunder_tpu.jit(_fn) if cache is None else thunder_tpu.jit(_fn, cache=cache)
    a, b = _args()
    for _ in range(3):
        jax.block_until_ready(jfn(a, b))
    got = thunder_tpu.dispatch_phases()
    assert [r["hit"] for r in got] == ["miss", warm_hit, warm_hit]
    assert all(r["fn"] == "_fn" for r in got)
    assert "compile_s" in got[0] and not any("compile_s" in r for r in got[1:])
    # The compile holds its first run: that run's prepare and launch are inside it.
    assert got[0]["prepare_s"] + got[0]["launch_s"] <= got[0]["compile_s"] <= got[0]["s"]
    info = thunder_tpu.cache_info(jfn)
    assert (info["calls"], info["misses"]) == (3, 1)
    # cache_info's total is the records' lookups: one set of marks.
    assert info["cache_lookup_us_total"] == pytest.approx(1e6 * sum(r["lookup_s"] for r in got), rel=1e-9)


def test_the_parts_lie_inside_the_call_and_at_is_the_host_clock(records):
    jfn = thunder_tpu.jit(_fn)
    a, b = _args()
    before = time.perf_counter()
    for _ in range(5):
        jfn(a, b)
    after = time.perf_counter()
    got = thunder_tpu.dispatch_phases()
    assert len(got) == 5
    for r in got:
        assert all(r[p] >= 0 for p in PARTS), r
        assert sum(r[p] for p in PARTS) <= r["s"], r
    ats = [r["at"] for r in got]
    assert before <= ats[0] and ats == sorted(ats) and ats[-1] <= after
    # The reader hands out copies.
    got[0]["s"] = -1.0
    assert thunder_tpu.dispatch_phases()[0]["s"] >= 0


def test_the_record_keeps_the_newest_4096(records):
    jfn = thunder_tpu.jit(_fn)
    a, b = _args()
    for _ in range(5):
        jfn(a, b)
    mark = time.perf_counter()
    for _ in range(4096):
        jfn(a, b)
    got = thunder_tpu.dispatch_phases()
    assert len(got) == 4096
    assert all(r["at"] > mark and r["hit"] == "fast" for r in got)


def _host_line_with(plane, name):
    for line in plane.lines:
        events = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events),
                        key=lambda e: (e[1], -e[2]))
        if any(e[0] == name for e in events):
            return events
    raise AssertionError(f"no line of {plane.name} holds {name}")


def test_a_warm_call_under_the_profiler_nests_its_three_parts_in_order(tmp_path):
    from jax.profiler import ProfileData

    jfn = thunder_tpu.jit(_fn)
    a, b = _args()
    jax.block_until_ready(jfn(a, b))  # the miss, outside the trace
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("outer"):
            out = jfn(a, b)
        jax.block_until_ready(out)
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    (host,) = [p for p in ProfileData.from_file(path).planes if p.name == "/host:CPU"]
    events = _host_line_with(host, "outer")  # every span on the caller's thread's line
    spans = {name: (start, end) for name, start, end in events
             if name == "outer" or name.startswith("thunder_tpu.call")}
    assert set(spans) == {"outer", "thunder_tpu.call", "thunder_tpu.call.lookup",
                          "thunder_tpu.call.prepare", "thunder_tpu.call.launch"}, spans
    outer, call = spans["outer"], spans["thunder_tpu.call"]
    assert outer[0] <= call[0] and call[1] <= outer[1]
    parts = [spans[f"thunder_tpu.call.{p}"] for p in ("lookup", "prepare", "launch")]
    assert call[0] <= parts[0][0] and parts[-1][1] <= call[1]
    for (_, end), (start, _) in zip(parts, parts[1:]):
        assert end <= start  # in that order, not overlapping
