"""Job ``forward_sparse_linear``, the yardstick's arithmetic for its two mixers
and the five readers this configuration brings, without a chip: the Zipf
prompts, the counters the readers are handed, the regions read out of a
compiled program's text and matched to a device trace's events, and the
check at the stand-in sizes."""

import importlib
import types

import numpy as np
import pytest
from test_pb_flops import job_of

from perfbench import flops, flops_sparse_linear, manifest, peaks, reading, xplane
from perfbench.layer_metrics import _regions

CELL = "minicpm-sala.fwd-t32k"
SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64, "init_blocks": 1,
          "window_size": 2048, "dense_len": 8192}


def rehearsal_job(seed=7):
    cell = manifest.load_cell(CELL)
    job = importlib.import_module(f"perfbench.jobs.{cell.traffic['job']}").Job(
        cell, seed=seed, platform="cpu", rehearse=True)
    job.rng = np.random.RandomState(seed)
    return job


# -----------------------------------------------------------------------------
# The arithmetic, by hand
# -----------------------------------------------------------------------------


def test_a_querys_pooled_keys_and_attended_keys_by_hand():
    before, attended = flops_sparse_linear.pooled_keys_before, flops_sparse_linear.keys_attended
    # windows [16 j, 16 j + 32): the first is wholly past at t = 31, the second at t = 47
    assert [before(t, 32, 16) for t in (0, 30, 31, 46, 47, 63, 32767)] == [0, 0, 1, 1, 2, 3, 2047]
    # 64 blocks of 64 are everything up to t = 4095; then 63 whole blocks and the query's own up to itself
    assert [attended(t, 64, 64) for t in (0, 4095, 4096, 4097, 4159, 4160, 32767)] == \
        [1, 4096, 63 * 64 + 1, 63 * 64 + 2, 4096, 63 * 64 + 1, 4096]


def test_the_mixers_work_by_hand_at_a_small_size():
    """T = 200, blocks of 16, 6 a query, pooling 8 at 4, 4 query heads of 8 on 1 key-value head, counted by loops."""
    sparse = {"kernel_size": 8, "kernel_stride": 4, "block_size": 16, "topk": 6, "init_blocks": 1, "window_size": 32,
              "dense_len": 64}
    scored = sum(len([j for j in range(49) if 4 * j + 8 <= t + 1]) for t in range(200))
    attended = sum(t + 1 if t < 96 else 5 * 16 + t % 16 + 1 for t in range(200))
    ops, nbytes = flops_sparse_linear.sparse_attention(200, 4, 1, 8, sparse)
    assert ops == 2 * 4 * 8 * scored + 4 * 4 * 8 * attended
    assert nbytes == 2 * (4 + 4 + 1 + 1) * 200 * 8  # q and the output at 4 heads, k and v at 1, bf16
    # under dense_len: plain causal attention, nothing scored
    assert flops_sparse_linear.sparse_attention(48, 4, 1, 8, sparse)[0] == 4 * 4 * 8 * (48 * 49 // 2)
    # the recurrence: k^T v into the state and q S out of it, 2 * d * d each a head and position
    assert flops_sparse_linear.linear_attention(200, 4, 8) == (4.0 * 4 * 8 * 8 * 200, 2.0 * 4 * 4 * 200 * 8)


def test_the_cells_operations_a_token_by_hand():
    job = job_of(CELL)
    # sparse layer: q, o, gate 3 * 4096 * 4096, k and v 2 * 4096 * 256, SwiGLU 3 * 4096 * 16384
    sparse_layer = 3 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 16384
    linear_layer = 5 * 4096 * 4096 + 3 * 4096 * 16384
    assert flops_sparse_linear.layer_matmul_params(job.keys, "minicpm4") == sparse_layer == 253_755_392
    assert flops_sparse_linear.layer_matmul_params(job.keys, "lightning-attn") == linear_layer == 285_212_672
    sparse_ops = flops_sparse_linear.sparse_attention(32768, 32, 2, 128, SPARSE)[0]
    assert sparse_ops == pytest.approx(2.32e12, rel=5e-3)  # ISSUE 33: 2.3 TFLOP required, 8.8 if run dense
    want = (2 * (2 * sparse_layer + 8 * linear_layer) + 2 * 73448 * 4096 * 1024 / 32768
            + (2 * sparse_ops + 8 * 4 * 32 * 128 * 128 * 32768) / 32768)
    assert job.flops_per_token() == pytest.approx(want, rel=1e-12)
    assert 32768 * job.flops_per_token() == pytest.approx(188.6e12, rel=1e-3)  # a call
    # the same counts are what the job hands the roofline readers, a call
    work = job.counters["mixer_work"]
    assert work["sparse_attention"][0] == 2 * sparse_ops and work["linear_attention"][1] == 8 * 2.0 * 4 * 32 * 32768 * 128
    assert job.tokens_per_unit == 32768 and job.last == 1024 and job.traffic["in_flight"] == 2


# -----------------------------------------------------------------------------
# The job
# -----------------------------------------------------------------------------


def test_prompts_are_zipf_over_the_whole_vocabulary_and_follow_the_seed():
    job = job_of(CELL)
    job.rng = np.random.RandomState(job.seed)
    batches = [job.make_batch() for _ in range(3)]
    harmonic = (1.0 / np.arange(1, 73449)).sum()
    for ids in batches:
        assert ids.shape == (1, 32768) and ids.dtype == np.int32 and 0 <= ids.min() and ids.max() < 73448
        counts = np.sort(np.bincount(ids.ravel(), minlength=73448))[::-1] / ids.size
        assert counts[0] == pytest.approx(1 / harmonic, rel=0.15)  # the commonest id: 8.5% of a prompt
    assert len({np.bincount(ids.ravel()).argmax() for ids in batches}) == 1  # and it stays the commonest
    assert not np.array_equal(batches[0], batches[1])
    again = job_of(CELL)
    again.rng = np.random.RandomState(again.seed)
    assert np.array_equal(again.make_batch(), batches[0])
    large = rehearsal_job(2**31 + 5)  # a seed beyond 32 signed bits
    assert large.make_batch().shape == (1, 256) and large.make_batch().max() < 512


def test_the_job_keeps_the_last_units_ids_and_refuses_another_model():
    import dataclasses

    job = rehearsal_job()
    job.jfn, job.params, job.read_back = (lambda p, i: i), {}, (lambda x: x)
    batches = [job.make_batch() for _ in range(5)]
    for b in batches:
        job.issue(b)
    assert len(job.issued) == job.cell.traffic["trace_units"] == 3
    assert all(np.array_equal(a, b) for a, b in zip(job.issued, batches[-3:]))
    cell = manifest.load_cell(CELL)
    module = importlib.import_module(f"perfbench.jobs.{cell.traffic['job']}")
    mixers = ["lightning-attn"] + cell.config["mixer_types"][1:]
    with pytest.raises(ValueError, match="mixers"):
        module.Job(dataclasses.replace(cell, config={**cell.config, "mixer_types": mixers}), seed=1, platform="cpu",
                   rehearse=False)
    topk = {**cell.config["sparse_config"], "topk": 32}
    with pytest.raises(ValueError, match="sparse constants"):
        module.Job(dataclasses.replace(cell, config={**cell.config, "sparse_config": topk}), seed=1, platform="cpu",
                   rehearse=False)


def test_the_sparse_layers_output_projection_is_drawn_four_times_larger_and_no_other_leaf():
    import jax

    from perfbench import weights
    from perfbench.jobs import forward_sparse_linear

    job = rehearsal_job()
    drawn = weights.make_system_weights(job.shapes, job.seed)
    heard = forward_sparse_linear.with_mixers_heard(drawn)
    changed = []
    for (path, before), after in zip(jax.tree_util.tree_flatten_with_path(drawn)[0], jax.tree_util.tree_leaves(heard)):
        if after is not before:
            changed.append(jax.tree_util.keystr(path))
            np.testing.assert_array_equal(np.asarray(after, np.float32), 4.0 * np.asarray(before, np.float32))
    assert changed == ["['blocks'][0]['sparse_attn']['proj_w']"]
    stacked = forward_sparse_linear.with_mixers_heard(weights.make_reference_weights(job.shapes, job.seed))
    np.testing.assert_array_equal(np.asarray(stacked["blocks/*/sparse_attn/proj_w"][0], np.float32),
                                  np.asarray(heard["blocks"][0]["sparse_attn"]["proj_w"], np.float32))


def test_the_tile_union_counter_is_the_programs_own_selection_on_the_last_units():
    job = rehearsal_job(seed=11)
    job.jfn, job.params, job.read_back = (lambda p, i: i), {}, (lambda x: x)
    for _ in range(2):
        job.issue(job.make_batch())
    ratio = job.counters["sparse_tile_union"]()
    assert 8 / 6 < ratio <= 16 / 6  # a tile of 128 queries sees 8 to 16 blocks of 16 and a query chooses 6
    counted = job._count
    assert job.counters["sparse_tile_union"]() == ratio and job._count is counted
    import thunder_tpu

    assert thunder_tpu.cache_misses(counted) == 1  # one program for every unit's count


def test_the_check_passes_the_system_and_fails_the_reference_at_float8(monkeypatch):
    """The cell's check at the stand-in sizes, in process: the system passes;
    the builder's control (``PERFBENCH_CHECK_PRECISIONS``, unset in the
    driver's runs) puts the reference itself with float8 and with bf16 matmul
    inputs through the same comparison in the system's place: float8, the
    precision below the one the configuration states, comes out as not
    correct, bf16 as correct."""
    from perfbench import checks_sparse_linear

    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    monkeypatch.setenv("PERFBENCH_CHECK_PRECISIONS", "float8_e4m3fn,bfloat16")
    job = rehearsal_job(seed=2**31 + 11)
    job.setup()
    assert job.counters["kernels_claimed"] == 6  # the three linear layers' rope of q and of k
    job.release()
    verdict = job.check(importlib.import_module("perfbench.reference.minicpm_sala"))
    assert verdict["ok"] and verdict["logits_rtol"] == checks_sparse_linear.SPARSE_LINEAR_LOGITS_RTOL
    assert verdict["compared"] == [1, 64, 512]
    lower, same = verdict["reference_at"]["float8_e4m3fn"], verdict["reference_at"]["bfloat16"]
    assert same["ok"] and not lower["ok"] and same["logits_rel_l2"] < verdict["logits_rtol"] < lower["logits_rel_l2"]
    assert job.params is None  # the system's weights were let go before the reference's were drawn


# -----------------------------------------------------------------------------
# The readers
# -----------------------------------------------------------------------------

HLO = """HloModule jit_run

%fused_computation.7 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %exp.1 = f32[8,8]{1,0} exponential(%p0), metadata={op_name="jit(run)/attn.sparse.attend/exp"}
  ROOT %sub.2 = f32[8,8]{1,0} subtract(%exp.1, %p0), metadata={op_name="jit(run)/attn.sparse.attend/sub"}
}

%body.3 (arg: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %arg = (s32[], f32[8,8]{1,0}) parameter(0)
  %sort.4 = f32[8,8]{1,0} sort(%gte.1), dimensions={1}, metadata={op_name="jit(run)/attn.sparse.select/while/body/top_k"}
  ROOT %tuple.9 = (s32[], f32[8,8]{1,0}) tuple(%add.1, %sort.4)
}

ENTRY %main.20 (Arg_0.1: f32[8,8]) -> f32[8,8] {
  %Arg_0.1 = f32[8,8]{1,0} parameter(0)
  %fusion.12 = f32[8,8]{1,0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.7
  %fusion.13 = f32[8,8]{1,0} fusion(%fusion.12), kind=kOutput, calls=%fused_computation.8, metadata={op_name="jit(run)/attn.linear/dot_general"}
  %while.5 = (s32[], f32[8,8]{1,0}) while(%tuple.1), condition=%cond.2, body=%body.3
  %fusion.14 = f32[8,8]{1,0} fusion(%fusion.13), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(run)/mul"}
  ROOT %copy.15 = f32[8,8]{1,0} copy(%fusion.14)
}
"""


def test_regions_are_read_out_of_a_compiled_programs_text():
    """By an instruction's own ``op_name``; for a fusion that carries none, by its fused computation's instructions."""
    found = _regions.of_instructions(HLO)
    assert found == {"exp.1": "attn.sparse.attend", "sub.2": "attn.sparse.attend", "sort.4": "attn.sparse.select",
                     "fusion.12": "attn.sparse.attend", "fusion.13": "attn.linear"}
    assert _regions.of_instructions("") == {}


def fake_reading(region_of=None, union=None, work=None):
    """A reading whose trace holds one device and two traced units: 30 ms in
    ``fusion.12`` (attend), 6 in ``sort.4`` (select, inside a ``while`` of 8),
    10 in ``fusion.13`` (linear), 50 in an instruction of no region."""
    cell = manifest.load_cell(CELL)
    ms = 1e-3
    events = [xplane.Event("%fusion.12 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %Arg_0.1), kind=kLoop", 0.0, 30 * ms),
              xplane.Event("%while.5 = (s32[], f32[8,8]{1,0}) while(%tuple.1), condition=%cond.2", 30 * ms, 38 * ms),
              xplane.Event("%sort.4 = f32[8,8]{1,0} sort(f32[8,8]{1,0} %gte.1), dimensions={1}", 31 * ms, 37 * ms),
              xplane.Event("%fusion.13 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %fusion.12), kind=kOutput", 40 * ms, 50 * ms),
              xplane.Event("%fusion.14 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %fusion.13), kind=kLoop", 50 * ms, 100 * ms)]
    trace = xplane.Trace([xplane.DeviceTrace(0, events, [], [])], [])
    counters = {"tokens_per_unit": 32768}
    if region_of is not None:
        counters["region_of_instruction"] = lambda: region_of
    if union is not None:
        counters["sparse_tile_union"] = lambda: union
    if work is not None:
        counters["mixer_work"] = work
    return reading.Reading(cell=cell, spans={}, counters=counters, window=types.SimpleNamespace(), tokens_per_s=1.0,
                           flops_per_token=1.0, peaks=peaks.peaks_for("TPU v5 lite"), trace=trace, traced_units=2)


def test_the_five_readers_on_a_synthetic_trace():
    work = {"sparse_attention": [197e12 * 1e-3, 1.0], "linear_attention": [1.0, 819e9 * 0.5e-3]}  # least 1 ms and 0.5 ms a call
    r = fake_reading(_regions.of_instructions(HLO), union=3.75, work=work)
    assert reading.read_metric("sparse_attention_ms", r) == pytest.approx((30 + 6) / 2)  # the while's own 2 ms are no region's
    assert reading.read_metric("linear_attention_ms", r) == pytest.approx(10 / 2)
    assert reading.read_metric("sparse_attention_roofline", r) == pytest.approx(100 * 1.0 / 18)
    assert reading.read_metric("linear_attention_roofline", r) == pytest.approx(100 * 0.5 / 5)
    assert reading.read_metric("sparse_tile_union_over_topk", r) == 3.75
    names = {m["name"] for m in r.cell.per_layer}
    assert {"sparse_attention_ms", "sparse_attention_roofline", "linear_attention_ms", "linear_attention_roofline",
            "sparse_tile_union_over_topk", "kernels_ms", "mfu", "device_idle_share"} <= names
    assert not {"experts_ms", "routed_experts_ms", "mla_attention_ms", "collective_ms"} & names
    # the least time is the roofline's: the larger of operations over the peak and bytes over the bandwidth
    assert flops.least_seconds(*work["linear_attention"], r.peaks) == (pytest.approx(0.5e-3), "memory")


@pytest.mark.parametrize("metric", ["sparse_attention_ms", "sparse_attention_roofline", "linear_attention_ms",
                                    "linear_attention_roofline", "sparse_tile_union_over_topk"])
def test_on_a_program_without_the_regions_or_the_counter_a_new_reader_reads_nothing(metric):
    """The parent commit's program names no such region and its jobs hand out no
    such counter: the reader returns ``None``, does not raise, and the line
    leaves the metric out."""
    assert reading.read_metric(metric, fake_reading()) is None
    assert reading.read_metric(metric, fake_reading(region_of={})) is None
    r = fake_reading(region_of={}, union=None)
    r.counters["sparse_tile_union"] = lambda: None  # a sequence under dense_len: no layer selects
    assert reading.read_metric(metric, r) is None
