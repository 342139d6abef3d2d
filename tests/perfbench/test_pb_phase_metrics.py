"""The ``phase_*_s`` readers and ``trace_claim_unattributed_s`` on a reading
made by hand around a stand-in ``build_train_step``, as ``jobs/train.py`` takes
its ``trace_claim_s``: the host clock around the call, the window started after
it. In process; no run of ``run.py``."""

import collections
import time

import numpy as np
import pytest

from perfbench import reading, window

IN_TRACE_CLAIM = ["phase_trace_s", "phase_transforms_s", "phase_claim_s", "phase_codegen_s",
                  "phase_optimizer_state_s"]
NEW_METRICS = IN_TRACE_CLAIM + ["phase_jax_trace_s", "trace_claim_unattributed_s"]


def hand_made(trace_claim_s: float, started_at: float) -> reading.Reading:
    return reading.Reading(cell=None, spans={"trace_claim_s": trace_claim_s}, counters={},
                           window=window.WindowResult(started_at=started_at),
                           tokens_per_s=None, flops_per_token=0.0, peaks=None)


@pytest.fixture
def only_this_tests_records(monkeypatch):
    """The list is the process's and the readers sum all of it, as they do in a
    run of one cell: this test gets a list of its own."""
    from thunder_tpu import api

    monkeypatch.setattr(api, "_compile_phase_records", collections.deque(maxlen=64))


def test_the_phases_and_the_remainder_add_up_to_trace_claim_s(only_this_tests_records):
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt
    from thunder_tpu.parallel import build_train_step

    cfg = gpt.name_to_config("llama-tiny")
    params = gpt.init_params(cfg, dtype=dtypes.bfloat16, seed=0)
    idx = np.random.RandomState(0).randint(0, cfg.vocab_size, (8, 64)).astype(np.int32)
    tgt = np.roll(idx, -1, axis=1).astype(np.int32)
    t0 = time.perf_counter()
    step, opt = build_train_step(cfg, params, idx, tgt)
    trace_claim_s = time.perf_counter() - t0
    step.lower(params, opt, idx, tgt)  # jax traces the step: the first call's share of set-up
    r = hand_made(trace_claim_s, started_at=time.perf_counter())

    got = {name: reading.read_metric(name, r) for name in NEW_METRICS}
    assert all(isinstance(v, float) for v in got.values()), got
    assert sum(got[n] for n in IN_TRACE_CLAIM) + got["trace_claim_unattributed_s"] == pytest.approx(
        trace_claim_s, abs=1e-6)
    assert 0 <= got["trace_claim_unattributed_s"] < trace_claim_s
    assert got["phase_jax_trace_s"] > 0  # beside trace_claim_s, not inside it

    # What the program records once the window has started is not set-up's:
    # another build, and another trace of the step, change no reading.
    build_train_step(cfg, params, idx, tgt)[0].lower(params, opt, idx, tgt)
    assert {name: reading.read_metric(name, r) for name in NEW_METRICS} == got


def test_a_program_without_the_reader_gives_no_metric_and_does_not_raise(monkeypatch):
    """The driver lays these files over the parent's checkout, whose program
    has no ``compile_phases``: the line then leaves the metrics out."""
    import thunder_tpu

    monkeypatch.delattr(thunder_tpu, "compile_phases")
    r = hand_made(1.0, started_at=time.perf_counter())
    assert [reading.read_metric(name, r) for name in NEW_METRICS] == [None] * len(NEW_METRICS)
