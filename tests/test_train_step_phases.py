"""Compile-phase spans on the train path: ``build_train_step`` records its
stages through the recorder the ``jit`` path has (``api._record_compile_phase``)
and ``thunder_tpu.compile_phases()`` hands them out, for both paths.

The list is the process's, and a worker runs many files: every test reads the
records that ended after a mark on ``time.perf_counter()``, the records' clock,
never the whole list."""

import time
import types

import jax
import numpy as np
import pytest

import thunder_tpu
from thunder_tpu import api

BUILD_PHASES = ["trace", "transforms", "claim", "codegen", "optimizer_state"]
AXES = pytest.mark.parametrize("axes", [None, {"fsdp": 4}], ids=["one-chip", "fsdp4"], indirect=True)


def since(mark: float) -> list:
    return [r for r in thunder_tpu.compile_phases() if r["at"] >= mark]


def build(axes):
    """``build_train_step`` at stand-in widths; (wall seconds of the call, what
    it recorded, the step and its arguments)."""
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt
    from thunder_tpu.parallel import build_train_step, gpt_param_specs, make_mesh, shard_pytree

    cfg = gpt.name_to_config("llama-tiny")
    params = gpt.init_params(cfg, dtype=dtypes.bfloat16, seed=0)
    idx = np.random.RandomState(0).randint(0, cfg.vocab_size, (8, 64)).astype(np.int32)
    tgt = np.roll(idx, -1, axis=1).astype(np.int32)
    kwargs = {}
    if axes is not None:
        mesh = make_mesh(**axes)
        specs = gpt_param_specs(cfg, mesh)
        params = shard_pytree(params, mesh, specs)
        kwargs = dict(mesh=mesh, param_specs=specs)
    t0 = time.perf_counter()
    step, opt = build_train_step(cfg, params, idx, tgt, **kwargs)
    t1 = time.perf_counter()
    return types.SimpleNamespace(wall=t1 - t0, started=t0, ended=t1, records=since(t0),
                                 step=step, args=(params, opt, idx, tgt))


@pytest.fixture(scope="module")
def axes(request):
    return request.param


@pytest.fixture(scope="module")
def built(axes):
    """Two builds (the first pays the lazy imports), then two calls of the
    second's step with what each recorded, then its lowered text."""
    first = build(axes)
    b = build(axes)
    b.first_build = first
    params, opt, idx, tgt = b.args
    b.calls = []
    for _ in range(2):  # donated: each call takes the state the one before returned
        mark = time.perf_counter()
        params, opt, loss = b.step(params, opt, idx, tgt)
        b.calls.append(since(mark))
    assert np.isfinite(float(loss))
    b.text = b.step.lower(params, opt, idx, tgt).as_text()
    return b


@AXES
def test_a_build_records_its_five_phases_once_under_one_program(built):
    records = built.records
    assert [r["phase"] for r in records] == BUILD_PHASES
    assert len({r["program"] for r in records}) == 1
    assert records[0]["program"] != built.first_build.records[0]["program"]
    assert all(r["s"] >= 0 for r in records)
    # oldest first, on the clock that was around the call
    ends = [r["at"] for r in records]
    assert ends == sorted(ends) and built.started <= ends[0] and ends[-1] <= built.ended
    # the spans do not overlap, and with the lazy imports done little else is in the call
    assert built.wall / 2 <= sum(r["s"] for r in records) <= built.wall
    params = jax.tree_util.tree_leaves(built.args[0])
    assert records[-1]["leaves"] == 2 * len(params) + 1  # two moments a parameter, and the counter


@AXES
def test_jax_trace_is_recorded_at_the_first_call_and_not_at_the_second(built):
    (traced,), second = built.calls
    assert traced["phase"] == "jax_trace" and traced["s"] > 0
    assert traced["program"] == built.records[0]["program"]
    assert second == [] and built.step._cache_size() == 1


@AXES
def test_the_lowered_step_does_not_depend_on_what_the_list_holds(built, axes):
    for _ in range(api._compile_phase_records.maxlen + 1):  # the list full, the build's own records gone
        api._record_compile_phase(None, "filler", 0.0)
    again = build(axes)
    assert again.step.lower(*again.args).as_text() == built.text


def test_a_jit_compile_is_another_program_with_the_phases_cache_info_reports():
    import thunder_tpu.clang as clang

    mark = time.perf_counter()
    train = build(None).records[0]["program"]
    jfn = thunder_tpu.jit(lambda a, b: clang.tanh(clang.add(clang.mul(a, b), a)))
    a = np.ones((8, 8), np.float32)
    jfn(a, a)
    records = [r for r in since(mark) if r["program"] != train]
    entry = thunder_tpu.compile_stats(jfn).cache_entries[-1]
    assert {r["program"] for r in records} == {entry.compile_id}
    by_phase = {r["phase"]: r["s"] for r in records}
    assert len(by_phase) == len(records)
    assert by_phase == pytest.approx(thunder_tpu.cache_info(jfn)["compile_phase_seconds"], abs=1e-6)
    assert {"trace", "transforms", "claim", "static_analysis", "codegen", "staging", "xla_compile"} <= set(by_phase)


def test_the_list_is_bounded_and_the_reader_returns_a_copy():
    bound = api._compile_phase_records.maxlen
    for i in range(bound + 10):
        api._record_compile_phase(-1, "filler", float(i))
    got = thunder_tpu.compile_phases()
    assert len(got) == bound
    assert [r["s"] for r in got] == [float(i) for i in range(10, bound + 10)]  # oldest first, the oldest dropped
    assert set(got[-1]) == {"program", "phase", "s", "at"}
    got[-1]["s"] = None
    got.clear()
    assert thunder_tpu.compile_phases()[-1]["s"] == float(bound + 9)
