"""Job ``forward_mla_moe`` and the readers this configuration brings, without a
run: the Zipf batches, the counters the readers are handed, and what a reader
makes of them (or of their absence, on a program that has no routed experts)."""

import types

import numpy as np
import pytest
from test_pb_flops import job_of

from perfbench import flops_mla_moe, manifest, peaks, reading


def rehearsal_job(seed=7):
    import importlib

    cell = manifest.load_cell("a.x-k1.fwd")
    job = importlib.import_module(f"perfbench.jobs.{cell.traffic['job']}").Job(
        cell, seed=seed, platform="cpu", rehearse=True)
    job.rng = np.random.RandomState(seed)
    return job


def test_batches_are_zipf_over_the_slice_and_follow_the_seed():
    job = job_of("a.x-k1.fwd")
    job.rng, job.id_of_rank = np.random.RandomState(job.seed), next(job.assignments())[0]
    batches = [job.make_batch() for _ in range(4)]
    harmonic = (1.0 / np.arange(1, 20481)).sum()
    for ids in batches:
        assert ids.shape == (2, 4096) and ids.min() >= 0 and ids.max() < 20480
        # exponent 1.0 over 20480 ids: the commonest has 1 / H(20480) = 9.5% of the tokens, the ten commonest 28%
        counts = np.sort(np.bincount(ids.ravel(), minlength=20480))[::-1] / ids.size
        assert counts[0] == pytest.approx(1 / harmonic, rel=0.15)
        assert counts[:10].sum() == pytest.approx((1.0 / np.arange(1, 11)).sum() / harmonic, rel=0.1)
    # what is frequent stays frequent from call to call (one assignment of ranks to ids a run), the
    # batches differ, and another seed makes other ids the frequent ones
    commonest = [np.bincount(ids.ravel()).argmax() for ids in batches]
    assert len(set(commonest)) == 1 and commonest[0] == job.id_of_rank[0]
    assert not np.array_equal(batches[0], batches[1])
    again = job_of("a.x-k1.fwd")
    again.rng, again.id_of_rank = np.random.RandomState(again.seed), next(again.assignments())[0]
    assert np.array_equal(again.make_batch(), batches[0])
    assert next(rehearsal_job(seed=8).assignments())[0][0] != next(rehearsal_job(seed=7).assignments())[0][0]
    other = rehearsal_job(seed=2**31 + 5)  # a seed beyond 32 signed bits
    other.id_of_rank = next(other.assignments())[0]
    assert other.make_batch().shape == (2, 128) and other.make_batch().max() < 512


def test_the_run_takes_the_assignment_that_routes_the_even_share_here(capsys):
    """Of the seed's assignments of ranks to ids, the run's first batch takes
    the one under which the program's router sends this chip its even share of
    the rows, as a mean over the expert layers; the run's batches are drawn as
    if nothing had been tried."""
    job = rehearsal_job()
    tried = list(job.assignments())
    assert len(tried) == job.traffic["assignments_tried"] == 3
    assert all(sorted(perm) == list(range(512)) and ids.shape == (2, 128) for perm, ids in tried)
    assert flops_mla_moe.routed_here_per_token(job.keys) == 1.0  # 4 a token, 4 of 16 held
    # routed here a token in the two expert layers: over, nearly even, short
    by_layer = [(1.7, 0.7), (1.15, 1.0), (0.7, 0.8)]

    def count_rows(params, ids):
        which = next(k for k, (_, batch) in enumerate(tried) if np.array_equal(batch, ids))
        return np.array([[share * job.tokens_per_unit / 4] * 4 for share in by_layer[which]])

    job.count_rows, job.params = count_rows, None
    first = job.make_batch()
    assert np.array_equal(job.id_of_rank, tried[1][0]) and "taken 1.0750 (even 1.0)" in capsys.readouterr().out
    assert np.array_equal(first, job.zipf_ids(np.random.RandomState(job.seed), tried[1][0]))
    assert job.spans["assign_ids_s"] >= 0
    by_layer[0] = (1.7, 0.3)  # uneven between its layers, even as a mean
    job.id_of_rank = None
    job.make_batch()
    assert np.array_equal(job.id_of_rank, tried[0][0])


def test_the_job_keeps_the_last_units_ids_and_refuses_another_rope_scaling():
    job = rehearsal_job()
    job.jfn, job.params, job.read_back = (lambda p, i: i), None, (lambda x: x)
    job.id_of_rank = next(job.assignments())[0]
    batches = [job.make_batch() for _ in range(11)]
    for b in batches:
        job.issue(b)
    assert len(job.issued) == job.cell.traffic["trace_units"] == 8
    assert all(np.array_equal(a, b) for a, b in zip(job.issued, batches[-8:]))
    assert job.counters["tokens_per_unit"] == 2 * 128 and callable(job.counters["routed_rows"])
    cell = manifest.load_cell("a.x-k1.fwd")
    changed = {**cell.config, "rope_scaling": {**cell.config["rope_scaling"], "factor": 40}}
    import dataclasses
    import importlib

    module = importlib.import_module(f"perfbench.jobs.{cell.traffic['job']}")
    with pytest.raises(ValueError, match="rope scaling"):
        module.Job(dataclasses.replace(cell, config=changed), seed=1, platform="cpu", rehearse=True)


def fake_reading(rows, ms_of_experts=20.0):
    """A reading whose trace holds one device with one grouped-matmul event of
    ``ms_of_experts`` a unit and whose job counted ``rows``."""
    from perfbench import xplane

    units = len(rows) if rows else 1
    cell = manifest.load_cell("a.x-k1.fwd")
    event = xplane.Event("%ragged-dot-none.1 = bf16[8,8]{1,0} custom-call(bf16[8,8]{1,0} %x), "
                         'custom_call_target="tpu_custom_call"', 0.0, units * ms_of_experts * 1e-3)
    trace = xplane.Trace([xplane.DeviceTrace(0, [event], [], [])], [])
    counters = {"tokens_per_unit": 8192}
    if rows is not None:
        counters["routed_rows"] = lambda: rows
    return reading.Reading(cell=cell, spans={}, counters=counters, window=types.SimpleNamespace(), tokens_per_s=1.0,
                           flops_per_token=1.0, peaks=peaks.peaks_for("TPU v5 lite"), trace=trace, traced_units=units)


def test_the_expert_readers_count_routed_rows_not_buffer_rows():
    even = [341] * 12
    skewed = [1400, 0, 200, 341, 341, 341, 341, 341, 341, 150, 200, 100]  # 4096 rows
    r = fake_reading([[even] * 6, [skewed] * 6])
    assert reading.read_metric("experts_ms", r) == pytest.approx(20.0)
    assert reading.read_metric("routed_here_per_token", r) == pytest.approx((4092 + 4096) / 2 / 8192)
    assert reading.read_metric("expert_load_max_over_mean", r) == pytest.approx((1.0 + 1400 / (4096 / 12)) / 2)
    from perfbench import flops

    least = sum(flops.least_seconds(*flops_mla_moe.experts(layer, 7168, 2048), r.peaks)[0]
                for layer in [even] * 6 + [skewed] * 6) / 2
    share = reading.read_metric("experts_roofline", r)
    assert share == pytest.approx(100.0 * 1e3 * least / 20.0) and 0 < share < 100
    # the static worst case (8 rows a token in the buffer) would read sixteen times that
    worst = flops.least_seconds(*flops_mla_moe.experts([65536 // 12] * 12, 7168, 2048), r.peaks)[0]
    assert 6 * worst / least > 15


@pytest.mark.parametrize("metric", ["experts_roofline", "expert_load_max_over_mean", "routed_here_per_token",
                                    "mla_attention_ms", "mla_attention_roofline"])
def test_on_a_program_without_the_counters_or_the_kernels_a_reader_reads_nothing(metric):
    """The parent commit has no routed experts and no latent attention: the
    reader returns ``None``, does not raise, and the line leaves the metric out."""
    assert reading.read_metric(metric, fake_reading(None)) is None
    assert reading.read_metric(metric, fake_reading([])) is None


def test_the_check_passes_the_system_and_fails_the_reference_at_float8(monkeypatch):
    """The cell's check at the stand-in sizes, in process: the system passes;
    and the builder's control (``PERFBENCH_CHECK_PRECISIONS``, unset in the
    driver's runs) puts the reference itself with float8 and with bf16 matmul
    inputs through the same comparison in the system's place: float8, the
    precision below the one the configuration states, comes out as not correct,
    bf16 as correct."""
    import importlib

    from perfbench import checks_mla_moe

    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    monkeypatch.setenv("PERFBENCH_CHECK_PRECISIONS", "float8_e4m3fn,bfloat16")
    job = rehearsal_job(seed=2**31 + 11)
    job.setup()
    # set-up took one of the seed's assignments by the program's own count, on the weights it holds
    assert any(np.array_equal(job.id_of_rank, perm) for perm, _ in job.assignments()) and "assign_ids_s" in job.spans
    job.release()
    verdict = job.check(importlib.import_module("perfbench.reference.axk1"))
    assert verdict["ok"] and verdict["logits_rtol"] == checks_mla_moe.MLA_MOE_LOGITS_RTOL
    assert verdict["settled_rows_over"] <= verdict["settled_rows_over_limit"] == checks_mla_moe.MLA_MOE_ROWS_OVER
    assert 0 < verdict["settled_rows"] <= 128
    lower, same = verdict["reference_at"]["float8_e4m3fn"], verdict["reference_at"]["bfloat16"]
    assert same["ok"] and not lower["ok"]
    assert same["logits_rel_l2"] < verdict["logits_rtol"] < lower["logits_rel_l2"]
    assert job.params is None  # the system's weights were let go before the reference's were drawn
