"""Job ``forward_window_moe`` and the readers this configuration brings,
without a chip: the Zipf prompts, the weights drawn a leaf of a layer at a
time, the counters the readers are handed, the regions read out of a compiled
program's text and matched to a device trace's events, the check at the
stand-in sizes, and the cell through ``run.py --rehearse --trace 1``."""

import dataclasses
import importlib
import types

import numpy as np
import pytest
from pb_helpers import metrics_for, result_of, run_cell
from test_pb_flops import job_of

from perfbench import flops, manifest, peaks, reading, xplane
from perfbench.jobs import forward_window_moe
from perfbench.layer_metrics import _regions

CELL = "trinity-mini.fwd-t32k"
NEW = ["window_attention_ms", "window_attention_roofline", "full_attention_ms", "window_visited_over_required",
       "trinity_experts_ms", "trinity_experts_roofline", "trinity_load_max_over_mean", "trinity_bias_changed_choices"]


def rehearsal_job(seed=7):
    cell = manifest.load_cell(CELL)
    job = importlib.import_module(f"perfbench.jobs.{cell.traffic['job']}").Job(
        cell, seed=seed, platform="cpu", rehearse=True)
    job.rng = np.random.RandomState(seed)
    return job


def test_the_cell_is_the_issues_letter_for_letter():
    cell = manifest.load_cell(CELL)
    t = cell.traffic
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "trinity-mini", "fwd_b1_t32768_zipf_v200192_last1024")
    assert (t["job"], t["batch"], t["seq"], t["last"], t["in_flight"]) == ("forward_window_moe", 1, 32768, 1024, 2)
    assert (t["zipf_exponent"], t["warmup_units"], t["trace_units"], t["check_sequences"]) == (1.0, 1, 3, 1)
    assert cell.config["reduced"] == ["num_hidden_layers"] and cell.config["num_hidden_layers"] == 7
    assert cell.config["max_position_embeddings"] == 131072 and cell.config["reference"] == "afmoe"
    assert {m["name"] for m in cell.per_layer} >= set(NEW) | {"kernels_ms", "kernels_roofline", "mfu", "device_idle_share"}
    assert not {"experts_ms", "routed_experts_ms", "sparse_attention_ms", "collective_ms"} & {m["name"] for m in cell.per_layer}


def test_prompts_are_zipf_over_the_whole_vocabulary_and_follow_the_seed():
    job = job_of(CELL)
    job.rng = np.random.RandomState(job.seed)
    batches = [job.make_batch() for _ in range(3)]
    harmonic = (1.0 / np.arange(1, 200193)).sum()
    for ids in batches:
        assert ids.shape == (1, 32768) and ids.dtype == np.int32 and 0 <= ids.min() and ids.max() < 200192
        counts = np.sort(np.bincount(ids.ravel(), minlength=200192))[::-1] / ids.size
        assert counts[0] == pytest.approx(1 / harmonic, rel=0.15)  # the commonest id: 7.8% of a prompt
    assert max(ids.max() for ids in batches) > 190000  # the tail reaches the end of the vocabulary: no slice
    assert len({np.bincount(ids.ravel()).argmax() for ids in batches}) == 1  # and it stays the commonest
    assert not np.array_equal(batches[0], batches[1])
    again = job_of(CELL)
    again.rng = np.random.RandomState(again.seed)
    assert np.array_equal(again.make_batch(), batches[0])
    assert "assignments_tried" not in job.traffic  # none is searched for
    large = rehearsal_job(2**31 + 5)  # a seed beyond 32 signed bits
    assert large.make_batch().shape == (1, 256) and large.make_batch().max() < 512


def test_the_job_keeps_the_last_units_ids_and_refuses_another_model():
    job = rehearsal_job()
    job.jfn, job.params, job.read_back = (lambda p, i: i), {}, (lambda x: x)
    batches = [job.make_batch() for _ in range(5)]
    for b in batches:
        job.issue(b)
    assert len(job.issued) == job.cell.traffic["trace_units"] == 3
    assert all(np.array_equal(a, b) for a, b in zip(job.issued, batches[-3:]))
    cell = manifest.load_cell(CELL)
    module = importlib.import_module(f"perfbench.jobs.{cell.traffic['job']}")
    kinds = ["full_attention"] + cell.config["layer_types"][1:]
    with pytest.raises(ValueError, match="mixers"):
        module.Job(dataclasses.replace(cell, config={**cell.config, "layer_types": kinds}), seed=1, platform="cpu",
                   rehearse=False)
    with pytest.raises(ValueError, match="disagree"):  # a width that differs is an error, never a private variant
        module.Job(dataclasses.replace(cell, config={**cell.config, "sliding_window": 1024}), seed=1, platform="cpu",
                   rehearse=False)


def test_the_weights_are_drawn_a_leaf_of_a_layer_at_a_time_and_the_bias_at_its_own_size():
    import jax

    from perfbench import weights

    job = rehearsal_job()
    tree = forward_window_moe.draw(job.shapes, 11)
    again = forward_window_moe.draw(job.shapes, 11)
    other = forward_window_moe.draw(job.shapes, 12)
    flat = lambda t: {kind + str(layer): np.asarray(leaf, np.float32) for (kind, layer, _), leaf in
                      zip(weights.leaf_kinds(t), jax.tree_util.tree_leaves(t))}
    a, b, c = flat(tree), flat(again), flat(other)
    assert all(np.array_equal(a[k], b[k]) for k in a) and not any(np.array_equal(a[k], c[k]) for k in a)
    for name, leaf in a.items():
        if "router_bias" in name:
            assert leaf.dtype == np.float32 and 0.05 < leaf.std() < 0.2 and abs(leaf.mean()) < 0.12
        elif "/weight" in name:
            assert abs(leaf.mean() - 1) < 0.01 and 0.01 < leaf.std() < 0.03
        else:
            assert abs(leaf.mean()) < 0.005 and leaf.std() == pytest.approx(0.02, rel=0.1)
    # two layers' leaves of one kind are two draws, and a layer's draw does not depend on the depth
    assert not np.array_equal(a["moe_blocks/*/attn/qkv_w0"], a["moe_blocks/*/attn/qkv_w1"])
    shallow = dataclasses.replace(job.cell, config={**job.cell.config, "stand_in": {**job.cell.config["stand_in"],
                                                                                   "num_hidden_layers": 3}})
    fewer = flat(forward_window_moe.draw(type(job)(shallow, seed=7, platform="cpu", rehearse=True).shapes, 11))
    assert set(fewer) < set(a) and all(np.array_equal(fewer[k], a[k]) for k in fewer)
    # no leaf is ever stacked: every array the draw makes is a leaf's own shape
    assert [tuple(l.shape) for l in jax.tree_util.tree_leaves(tree)] == [tuple(l.shape) for l in jax.tree_util.tree_leaves(job.shapes)]
    # as the reference takes them: the layers in the model's order, dense first
    ref = forward_window_moe.for_reference(tree, job.keys["num_dense_layers"])
    assert sorted(k for k in ref if k != "layers") == ["lm_head_w", "ln_f/weight", "wte"] and len(ref["layers"]) == 4
    assert "mlp/fc_1_w" in ref["layers"][0] and all("mlp/experts_gate" in layer for layer in ref["layers"][1:])
    assert ref["layers"][1]["attn/qkv_w"] is tree["moe_blocks"][0]["attn"]["qkv_w"]
    assert ref["layers"][3]["post_mlp_norm/weight"] is tree["moe_blocks"][2]["post_mlp_norm"]["weight"]


def test_the_counters_are_the_programs_own_routers_and_mask_on_the_last_units():
    from thunder_tpu.executors import flashex

    job = rehearsal_job(seed=11)
    job.jfn, job.params, job.read_back = (lambda p, i: i), {}, (lambda x: x)
    for _ in range(2):
        job.issue(job.make_batch())
    rows = job.counters["routed_rows"]()
    assert np.shape(rows) == (2, 3, 8) and (np.sum(rows, -1) == 256 * 2).all()  # 2 rows a token, every expert held
    share = job.counters["bias_changed_choices"]()
    assert 0.05 < share < 0.9  # a bias of N(0, 0.1) changes choices and not all of them
    counted = job._count
    assert job.counters["routed_rows"]() == rows and job._count is counted
    visited, required = job.counters["window_tiles"]()
    assert (visited, required) == (flashex.window_tiles(256, 128), 256 * 128 - 128 * 127 // 2) == (256 * 256, 24640)
    short = rehearsal_job()
    short.seq = 128  # no layer has a window longer than the sequence
    assert short.counters["window_tiles"]() is None
    work = job.counters["mixer_work"]
    assert work["window_attention"][0] == 3 * 4 * 4 * 128 * 24640 and work["full_attention"][0] == 4 * 4 * 128 * (256 * 257 // 2)


def test_the_check_passes_the_system_and_fails_the_reference_at_float8(monkeypatch):
    """The cell's check at the stand-in sizes, in process: the system passes;
    the builder's control (``PERFBENCH_CHECK_PRECISIONS``, unset in the
    driver's runs) puts the reference itself with float8 and with bf16 matmul
    inputs through the same comparison in the system's place: float8, the
    precision below the one the configuration states, comes out as not
    correct, bf16 as correct."""
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    monkeypatch.setenv("PERFBENCH_CHECK_PRECISIONS", "float8_e4m3fn,bfloat16")
    job = rehearsal_job(seed=2**31 + 11)
    job.setup()
    # three window layers' rope of q and of k and their attention, the global layer's attention, three dispatches
    assert job.counters["kernels_claimed"] == 6 + 3 + 1 + 3
    job.release()
    verdict = job.check(importlib.import_module("perfbench.reference.afmoe"))
    assert verdict["ok"] and verdict["compared"] == [1, 64, 512]
    lower, same = verdict["reference_at"]["float8_e4m3fn"], verdict["reference_at"]["bfloat16"]
    assert same["ok"] and not lower["ok"] and same["logits_rel_l2"] < verdict["logits_rtol"] < lower["logits_rel_l2"]
    assert job.params is None  # the system's weights were let go before the reference's were drawn


@pytest.mark.parametrize("met, heard", [(3, False), (4, True)], ids=["three-of-80-pass", "four-of-80-fail"])
def test_the_second_limit_hears_a_term_that_more_than_its_share_of_the_settled_rows_lost(met, heard):
    """The smallest fault ``checks_window_moe.py`` says it sees: of 80 settled
    rows ``WINDOW_MOE_ROWS_OVER`` allows 3 to be off. A quarter of the other
    rows flip an expert, as in a sound run, and the block limit does not mind."""
    from perfbench import checks_window_moe as c

    rng = np.random.RandomState(met)
    want = rng.randn(1, 400, 64).astype(np.float32)
    margin = np.full((1, 400), 0.5 * c.WINDOW_MOE_SETTLED_MARGIN)
    margin[0, :80] = 1.5 * c.WINDOW_MOE_SETTLED_MARGIN
    assert int(c.WINDOW_MOE_ROWS_OVER * 80) == 3
    off = np.zeros(400, bool)
    off[:met] = True  # settled rows that met the lost term
    off[80:] = rng.random_sample(320) < 0.25  # flips where the margin was small
    got = want * (1 + 0.009 * rng.randn(1, 400, 1).astype(np.float32))
    got[0, off] += 0.1 * rng.randn(int(off.sum()), 64).astype(np.float32)
    verdict = c.compare_logits(got, want, margin)
    assert verdict["settled_rows"] == 80 and verdict["settled_rows_over"] == pytest.approx(met / 80)
    assert verdict["logits_rel_l2"] < verdict["logits_rtol"] and verdict["ok"] is (not heard), verdict


# -----------------------------------------------------------------------------
# The readers
# -----------------------------------------------------------------------------

HLO = """HloModule jit_run

%fused_computation.7 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %mul.1 = f32[8,8]{1,0} multiply(%p0, %p0), metadata={op_name="jit(run)/attn.window/mul"}
  ROOT %sub.2 = f32[8,8]{1,0} subtract(%mul.1, %p0), metadata={op_name="jit(run)/attn.window/sub"}
}

ENTRY %main.20 (Arg_0.1: f32[8,8]) -> f32[8,8] {
  %Arg_0.1 = f32[8,8]{1,0} parameter(0)
  %fusion.12 = f32[8,8]{1,0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.7
  %splash_mha_fwd_no_residuals.7 = f32[8,8]{1,0} custom-call(%fusion.12), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/attn.window/pallas_call"}
  %splash_mha_fwd_no_residuals.10 = f32[8,8]{1,0} custom-call(%fusion.12), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/attn.full/pallas_call"}
  %fusion.13 = f32[8,8]{1,0} fusion(%fusion.12), kind=kOutput, calls=%fused_computation.8, metadata={op_name="jit(run)/dot_general"}
  %gmm.3 = f32[8,8]{1,0} custom-call(%fusion.13), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/moe.experts/pallas_call"}
  %fusion.14 = f32[8,8]{1,0} fusion(%fusion.13), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(run)/mul"}
  ROOT %copy.15 = f32[8,8]{1,0} copy(%fusion.14)
}
"""


def test_regions_are_read_out_of_a_compiled_programs_text():
    found = _regions.of_instructions(HLO, forward_window_moe.REGIONS)
    assert found == {"mul.1": "attn.window", "sub.2": "attn.window", "fusion.12": "attn.window",
                     "splash_mha_fwd_no_residuals.7": "attn.window", "splash_mha_fwd_no_residuals.10": "attn.full"}
    assert _regions.of_instructions(HLO) == {}  # the older cells' regions are not this program's


def fake_reading(region_of=None, tiles=None, work=None, rows=None, changed=None):
    """A reading whose trace holds one device and two traced units: 2 ms in
    ``fusion.12`` and 12 in a Mosaic call (the window region), 20 in the global
    layer's call, 4 in the gate's fusion, 30 in ``gmm``, 50 in no region."""
    cell = manifest.load_cell(CELL)
    ms = 1e-3
    call = '%{} = f32[8,8]{{1,0}} custom-call(f32[8,8]{{1,0}} %x), custom_call_target="tpu_custom_call"'
    events = [xplane.Event("%fusion.12 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %Arg_0.1), kind=kLoop", 0.0, 2 * ms),
              xplane.Event(call.format("splash_mha_fwd_no_residuals.7"), 2 * ms, 14 * ms),
              xplane.Event(call.format("splash_mha_fwd_no_residuals.10"), 14 * ms, 34 * ms),
              xplane.Event("%fusion.13 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %fusion.12), kind=kOutput", 34 * ms, 38 * ms),
              xplane.Event(call.format("gmm.3"), 38 * ms, 68 * ms),
              xplane.Event("%fusion.14 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %fusion.13), kind=kLoop", 68 * ms, 118 * ms)]
    trace = xplane.Trace([xplane.DeviceTrace(0, events, [], [])], [])
    counters = {"tokens_per_unit": 32768}
    if region_of is not None:
        counters["region_of_instruction"] = lambda: region_of
    if tiles is not None:
        counters["window_tiles"] = lambda: tiles
    if work is not None:
        counters["mixer_work"] = work
    if rows is not None:
        counters["routed_rows"] = lambda: rows
    if changed is not None:
        counters["bias_changed_choices"] = lambda: changed
    return reading.Reading(cell=cell, spans={}, counters=counters, window=types.SimpleNamespace(), tokens_per_s=1.0,
                           flops_per_token=1.0, peaks=peaks.peaks_for("TPU v5 lite"), trace=trace, traced_units=2)


def test_the_eight_readers_on_a_synthetic_trace():
    from perfbench import flops_mla_moe

    work = {"window_attention": [197e12 * 2e-3, 1.0], "full_attention": [197e12 * 5e-3, 1.0]}  # least 2 ms and 5 ms a call
    rows = [[[300, 100, 0, 0]], [[100, 100, 100, 100]]]  # two units, one expert layer, four experts
    r = fake_reading(_regions.of_instructions(HLO, forward_window_moe.REGIONS), tiles=[93 * 1024 * 1024, 65_012_736],
                     work=work, rows=rows, changed=0.25)
    assert reading.read_metric("window_attention_ms", r) == pytest.approx((2 + 12) / 2)  # the fusion and the call
    assert reading.read_metric("full_attention_ms", r) == pytest.approx(20 / 2)
    assert reading.read_metric("window_attention_roofline", r) == pytest.approx(100 * 2.0 / 7)
    assert reading.read_metric("window_visited_over_required", r) == pytest.approx(1.5, abs=5e-4)  # ISSUE 38: about 1.5
    assert reading.read_metric("trinity_experts_ms", r) == pytest.approx(30 / 2)
    least = sum(flops.least_seconds(*flops_mla_moe.experts(unit[0], 2048, 1024), r.peaks)[0] for unit in rows) / 2
    assert reading.read_metric("trinity_experts_roofline", r) == pytest.approx(100 * 1e3 * least / 15)
    assert reading.read_metric("trinity_load_max_over_mean", r) == pytest.approx((3.0 + 1.0) / 2)
    assert reading.read_metric("trinity_bias_changed_choices", r) == 25.0
    units = {m["name"]: m["unit"] for m in r.cell.per_layer}
    assert [units[n] for n in NEW] == ["ms", "%", "ms", "ratio", "ms", "%", "ratio", "%"]


@pytest.mark.parametrize("metric", NEW)
def test_on_a_program_without_the_regions_or_the_counters_a_new_reader_reads_nothing(metric):
    """The parent commit's program names no such region and its jobs hand out no
    such counter: the reader returns ``None``, does not raise, and the line
    leaves the metric out."""
    bare = fake_reading()
    bare.trace = xplane.Trace([xplane.DeviceTrace(0, [e for e in bare.trace.devices[0].ops if "gmm" not in e.name], [], [])], [])
    assert reading.read_metric(metric, bare) is None
    empty = fake_reading(region_of={})
    empty.trace = bare.trace
    empty.counters.update(window_tiles=lambda: None, routed_rows=lambda: None, bias_changed_choices=lambda: None)
    assert reading.read_metric(metric, empty) is None


def test_the_cell_through_run_py_rehearse_trace_1_reads_every_new_metric_a_cpu_can():
    """Every per-layer metric of the cell that is no device's (a CPU's trace has
    no device plane) is on the line of a ``--rehearse --trace 1`` run, the four
    new counters' among them, and the run is correct."""
    from pb_helpers import DEVICE_ONLY

    result = result_of(run_cell(CELL, "--rehearse", trace=1))
    assert result["correct"] is True and result["failed"] == 0 and "breakdown" in result
    wanted = {m["name"] for m in metrics_for(CELL, "per_layer")} - DEVICE_ONLY
    assert set(result["metrics"]) == wanted
    assert {"window_visited_over_required", "trinity_load_max_over_mean", "trinity_bias_changed_choices"} <= wanted
    assert result["metrics"]["window_visited_over_required"]["value"] == pytest.approx(256 * 256 / 24640)
    assert result["metrics"]["trinity_bias_changed_choices"]["value"] > 0  # the drawn bias is not idle
    assert result["metrics"]["kernels_claimed"]["value"] == 13 and result["metrics"]["compiles_in_window"]["value"] == 0


def test_a_splash_calls_three_lines_are_read_as_one_instruction():
    """As the cell's own compiled program writes the call (a described v5e, PR
    38): the JSON of its ``frontend_attributes`` breaks the line twice, and the
    region stands after the second break."""
    text = """ENTRY %main.1 (Arg_0.1: f32[8,8]) -> f32[8,8] {
  %splash_mha_fwd_no_residuals.7 = (f32[1024,128]{1,0}, bf16[32,32768,128]{2,1,0}) custom-call(%copy-done.240, %iota.20), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 1024, \\"block_kv\\": 1024}"
}}, metadata={op_name="jit(computation)/attn.window/vmap(jit(_splash_attention))/splash_mha_fwd_no_residuals/pallas_call" stack_frame_id=145}, backend_config={}
  %pallas_call.1 = bf16[32,32768,128]{2,1,0} get-tuple-element(%splash_mha_fwd_no_residuals.7), index=3, metadata={op_name="jit(computation)/attn.full/pallas_call"}
  ROOT %copy.15 = f32[8,8]{1,0} copy(%Arg_0.1)
}
"""
    assert _regions.of_instructions(text, forward_window_moe.REGIONS) == {"pallas_call.1": "attn.full"}  # the call is missed
    joined = forward_window_moe.an_instruction_a_line(text)
    assert len(joined.splitlines()) == len(text.splitlines()) - 2 and joined.splitlines()[-1] == "}"
    assert _regions.of_instructions(joined, forward_window_moe.REGIONS) == {
        "splash_mha_fwd_no_residuals.7": "attn.window", "pallas_call.1": "attn.full"}
    assert forward_window_moe.an_instruction_a_line(HLO) == HLO.rstrip("\n")  # a text without such breaks is itself
