"""Job ``forward_conv_moe`` and the readers this configuration brings, without
a run: the Zipf batches over the whole vocabulary, the counters the readers are
handed, what a reader makes of them (or of their absence, on a program whose
router has no bias), and the check's two limits at the stand-in sizes."""

import dataclasses
import importlib
import types

import numpy as np
import pytest
from test_pb_flops import job_of

from perfbench import flops, flops_mla_moe, manifest, peaks, reading

CELL = "lfm2-8b-a1b.fwd"


def rehearsal_job(seed=7):
    cell = manifest.load_cell(CELL)
    job = importlib.import_module(f"perfbench.jobs.{cell.traffic['job']}").Job(
        cell, seed=seed, platform="cpu", rehearse=True)
    job.rng = np.random.RandomState(seed)
    return job


def test_batches_are_zipf_over_the_whole_vocabulary_and_follow_the_seed():
    job = job_of(CELL)
    job.rng, job.params = np.random.RandomState(job.seed), {}
    batches = [job.make_batch() for _ in range(4)]
    harmonic = (1.0 / np.arange(1, 65537)).sum()
    for ids in batches:
        assert ids.shape == (2, 4096) and ids.dtype == np.int32 and ids.min() >= 0 and ids.max() < 65536
        # exponent 1.0 over 65536 ids: the commonest has 1 / H(65536) = 8.6% of the tokens, the ten commonest 25%
        counts = np.sort(np.bincount(ids.ravel(), minlength=65536))[::-1] / ids.size
        assert counts[0] == pytest.approx(1 / harmonic, rel=0.15)
        assert counts[:10].sum() == pytest.approx((1.0 / np.arange(1, 11)).sum() / harmonic, rel=0.1)
    assert max(ids.max() for ids in batches) > 60000  # the tail reaches the end of the vocabulary: no slice
    # what is frequent stays frequent from call to call (one assignment of ranks to ids a run, none
    # searched for), the batches differ, and another seed makes other ids the frequent ones
    commonest = [np.bincount(ids.ravel()).argmax() for ids in batches]
    assert len(set(commonest)) == 1 and commonest[0] == job.id_of_rank[0]
    assert sorted(job.id_of_rank) == list(range(65536))
    assert not np.array_equal(batches[0], batches[1])
    again = job_of(CELL)
    again.rng, again.params = np.random.RandomState(again.seed), {}
    assert np.array_equal(again.make_batch(), batches[0])
    assert "assignments_tried" not in job.traffic and "assign_ids_s" not in job.spans
    seven, eight, large = rehearsal_job(7), rehearsal_job(8), rehearsal_job(2**31 + 5)  # a seed beyond 32 signed bits
    for j in (seven, eight, large):
        j.params = {}
    assert seven.make_batch().shape == large.make_batch().shape == (2, 128) and large.make_batch().max() < 512
    eight.make_batch()
    assert not np.array_equal(seven.id_of_rank, eight.id_of_rank)


def test_the_first_batch_draws_the_routers_bias_at_its_own_size_and_no_other_leaf():
    """The weights' draw is N(0, 0.02); the bias is that draw times 5, N(0, 0.1),
    in the program's tree and in the reference's stacked kinds alike."""
    import jax

    from perfbench import weights
    from perfbench.jobs import forward_conv_moe

    job = rehearsal_job()
    drawn = weights.make_system_weights(job.shapes, job.seed)
    job.params = drawn
    job.make_batch()
    for (path, before), after in zip(jax.tree_util.tree_flatten_with_path(drawn)[0], jax.tree_util.tree_leaves(job.params)):
        if "router_bias" in str(path[-1]):
            np.testing.assert_allclose(np.asarray(after), 5.0 * np.asarray(before), rtol=1e-6)
            assert after.dtype == np.float32 and 0.02 < float(np.std(np.asarray(after))) < 0.3
        else:
            assert after is before
    stacked = forward_conv_moe.with_bias_drawn(weights.make_reference_weights(job.shapes, job.seed))
    np.testing.assert_array_equal(np.asarray(stacked["moe_blocks/*/mlp/router_bias"][0]),
                                  np.asarray(job.params["moe_blocks"][0]["mlp"]["router_bias"]))
    job.make_batch()  # and once only
    np.testing.assert_array_equal(np.asarray(stacked["moe_blocks/*/mlp/router_bias"][0]),
                                  np.asarray(job.params["moe_blocks"][0]["mlp"]["router_bias"]))


def test_the_job_keeps_the_last_units_ids_and_refuses_another_layer_pattern():
    job = rehearsal_job()
    job.jfn, job.params, job.read_back = (lambda p, i: i), {}, (lambda x: x)
    batches = [job.make_batch() for _ in range(11)]
    for b in batches:
        job.issue(b)
    assert len(job.issued) == job.cell.traffic["trace_units"] == 8
    assert all(np.array_equal(a, b) for a, b in zip(job.issued, batches[-8:]))
    assert job.counters["tokens_per_unit"] == 2 * 128
    assert callable(job.counters["routed_rows"]) and callable(job.counters["bias_changed_choices"])
    cell = manifest.load_cell(CELL)
    changed = {**cell.config, "layer_types": ["full_attention"] + cell.config["layer_types"][1:]}
    module = importlib.import_module(f"perfbench.jobs.{cell.traffic['job']}")
    with pytest.raises(ValueError, match="mixers"):
        module.Job(dataclasses.replace(cell, config=changed), seed=1, platform="cpu", rehearse=False)


def test_the_counters_are_the_programs_own_routers_count_of_the_last_units(monkeypatch):
    """After the windows the job counts, for the ids of the last units, the rows
    each expert got and the share of the (token, choice) pairs the bias changed:
    every pair lands here (2 a token at the stand-in's sizes), and the count is
    made once however many readers ask."""
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    job = rehearsal_job(seed=11)
    job.jfn, job.params, job.read_back = (lambda p, i: i), {}, (lambda x: x)
    for _ in range(3):
        job.issue(job.make_batch())
    rows, share = job.counters["routed_rows"](), job.counters["bias_changed_choices"]()
    rows = np.asarray(rows)
    assert rows.shape == (3, 3, 8)  # units, expert layers, experts
    assert (rows.sum(-1) == 2 * 2 * 128).all()
    assert 0.0 < share < 1.0
    counted = job._count
    assert job.counters["routed_rows"]() == rows.tolist() and job._count is counted
    import thunder_tpu

    assert thunder_tpu.cache_misses(counted) == 1  # one program for every unit's count


def fake_reading(rows, changed=None, ms_of_experts=100.0):
    """A reading whose trace holds one device with one grouped-matmul event of
    ``ms_of_experts`` a unit and whose job counted ``rows`` and ``changed``."""
    from perfbench import xplane

    units = len(rows) if rows else 1
    cell = manifest.load_cell(CELL)
    event = xplane.Event("%gmm.1 = bf16[32768,1792]{1,0} custom-call(bf16[32768,2048]{1,0} %x), "
                         'custom_call_target="tpu_custom_call"', 0.0, units * ms_of_experts * 1e-3)
    trace = xplane.Trace([xplane.DeviceTrace(0, [event], [], [])], [])
    counters = {"tokens_per_unit": 8192}
    if rows is not None:
        counters["routed_rows"] = lambda: rows
    if changed is not None:
        counters["bias_changed_choices"] = lambda: changed
    return reading.Reading(cell=cell, spans={}, counters=counters, window=types.SimpleNamespace(), tokens_per_s=1.0,
                           flops_per_token=1.0, peaks=peaks.peaks_for("TPU v5 lite"), trace=trace, traced_units=units)


def test_the_new_readers_read_the_rows_each_expert_got_and_the_share_the_bias_changed():
    even = [1024] * 32
    skewed = [4096, 0] + [28672 // 30] * 29 + [28672 - 29 * (28672 // 30)]
    r = fake_reading([[even] * 12, [skewed] * 12], changed=0.2184)
    assert reading.read_metric("routed_experts_ms", r) == pytest.approx(100.0)
    assert reading.read_metric("routed_load_max_over_mean", r) == pytest.approx((1.0 + 4.0) / 2)
    assert reading.read_metric("bias_changed_choices", r) == pytest.approx(21.84)
    least = sum(flops.least_seconds(*flops_mla_moe.experts(layer, 2048, 1792), r.peaks)[0]
                for layer in [even] * 12 + [skewed] * 12) / 2
    share = reading.read_metric("routed_experts_roofline", r)
    assert share == pytest.approx(100.0 * 1e3 * least / 100.0) and 40 < share < 50  # 12 layers of 3.66 ms in 100
    # the cell's own entries, and none of a.x-k1.fwd's four
    names = {m["name"] for m in r.cell.per_layer}
    assert {"routed_experts_ms", "routed_experts_roofline", "routed_load_max_over_mean", "bias_changed_choices"} <= names
    assert not {"experts_ms", "experts_roofline", "expert_load_max_over_mean", "routed_here_per_token",
                "mla_attention_ms"} & names


@pytest.mark.parametrize("metric", ["routed_experts_roofline", "routed_load_max_over_mean", "bias_changed_choices"])
def test_on_a_program_without_the_counters_a_new_reader_reads_nothing(metric):
    """The parent commit's router has no bias and its jobs hand out no such
    counter: the reader returns ``None``, does not raise, and the line leaves
    the metric out."""
    assert reading.read_metric(metric, fake_reading(None)) is None
    assert reading.read_metric(metric, fake_reading([])) is None


def test_a_job_whose_router_has_no_bias_hands_out_no_share():
    r = fake_reading([[[1024] * 32]], changed=None)
    r.counters["bias_changed_choices"] = lambda: None
    assert reading.read_metric("bias_changed_choices", r) is None


def test_the_check_passes_the_system_and_fails_the_reference_at_float8(monkeypatch):
    """The cell's check at the stand-in sizes, in process: the system passes
    both limits; and the builder's control (``PERFBENCH_CHECK_PRECISIONS``,
    unset in the driver's runs) puts the reference itself with float8 and with
    bf16 matmul inputs through the same comparison in the system's place:
    float8, the precision below the one the configuration states, comes out as
    not correct, bf16 as correct."""
    from perfbench import checks_conv_moe

    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    monkeypatch.setenv("PERFBENCH_CHECK_PRECISIONS", "float8_e4m3fn,bfloat16")
    job = rehearsal_job(seed=2**31 + 11)
    job.setup()
    assert job.counters["kernels_claimed"] == 6  # one attention call, its two rope calls, three claimed moe_experts
    job.release()
    verdict = job.check(importlib.import_module("perfbench.reference.lfm2_moe"))
    assert verdict["ok"] and verdict["logits_rtol"] == checks_conv_moe.CONV_MOE_LOGITS_RTOL
    assert verdict["settled_rows_over"] <= verdict["settled_rows_over_limit"] == checks_conv_moe.CONV_MOE_ROWS_OVER
    assert verdict["settled_margin"] == checks_conv_moe.CONV_MOE_SETTLED_MARGIN and 0 < verdict["settled_rows"] <= 128
    assert verdict["compared"] == [1, 128, 512]
    lower, same = verdict["reference_at"]["float8_e4m3fn"], verdict["reference_at"]["bfloat16"]
    assert same["ok"] and not lower["ok"]
    # by one of the two limits, not by each: at four layers of these widths float8 moves the block by less
    # than the limit set at 14 layers on the chip, and every row whose routing is settled by more than a row's
    assert same["logits_rel_l2"] < lower["logits_rel_l2"] and same["settled_rows_over"] == 0.0
    assert lower["settled_rows_over"] > 5 * lower["settled_rows_over_limit"]
    assert verdict["settled_row_max"] < verdict["row_rtol"] < lower["settled_row_median"]
    assert job.params is None  # the system's weights were let go before the reference's were drawn
