"""Job ``forward_ssm`` and the readers this configuration brings, without a
chip: the cell's files, the Zipf prompts, the weights drawn a leaf of a layer at
a time with the state-space leaves at their own distributions, the counters the
readers are handed, the check at the stand-in sizes, and the cell through
``run.py --rehearse --trace 1``."""

import dataclasses
import importlib
import json
import types

import numpy as np
import pytest
from pb_helpers import MANIFEST, metrics_for, result_of, run_cell
from test_pb_flops import job_of

from perfbench import flops, flops_ssm, manifest, peaks, reading, xplane
from perfbench.jobs import forward_ssm
from perfbench.layer_metrics import _regions

CELL = "granite-4.0-h-micro.fwd-t16k"
NEW = ["ssm_scan_ms", "ssm_scan_roofline", "ssm_conv_ms", "ssm_conv_roofline", "ssm_gate_norm_ms", "granite_full_attention_ms",
       "ssm_chunk_ops_over_required"]
NINE = ["pythia-410m.train", "pythia-410m.fwd", "mistral-7b.train", "mistral-7b.fsdp4", "a.x-k1.fwd", "lfm2-8b-a1b.fwd",
        "minicpm-sala.fwd-t32k", "trinity-mini.fwd-t32k", "longcat-flash-omni.fwd-t16k"]


def rehearsal_job(seed=7):
    cell = manifest.load_cell(CELL)
    job = importlib.import_module(f"perfbench.jobs.{cell.traffic['job']}").Job(cell, seed=seed, platform="cpu", rehearse=True)
    job.rng = np.random.RandomState(seed)
    return job


def claimed_lines(trace) -> int:
    """The symbols a kernel executor owns, counted on the execution trace's own lines."""
    return sum(1 for b in trace.bound_symbols if b.sym.executor is not None and b.sym.executor.name in ("flash", "pallas"))


def test_the_cell_is_the_issues_letter_for_letter():
    cell = manifest.load_cell(CELL)
    t, c = cell.traffic, cell.config
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "granite-4.0-h-micro", "fwd_b1_t16384_zipf_v100352_last1024")
    assert (t["job"], t["batch"], t["seq"], t["last"], t["in_flight"]) == ("forward_ssm", 1, 16384, 1024, 2)
    assert (t["zipf_exponent"], t["warmup_units"], t["trace_units"], t["check_sequences"]) == (1.0, 1, 3, 1)
    assert c["reduced"] == [] and c["reference"] == "granite_hybrid" and c["deployment_chips_per_layer"] == 1
    # every key of the catalogue's row under its own name, as published
    with open("/opt/skills/guides/model-configs/architectures.jsonl", encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-micro")
    assert {k: c[k] for k in row["config"]} == row["config"] and c["source"] == row["source_url"]
    assert {"in_proj_order", "gated_norm", "time_step_limit", "mlp_packing", "chunk", "weights", "heard"} <= set(c["assumed"])
    assert {m["name"] for m in cell.per_layer} >= set(NEW) | {"kernels_ms", "mfu", "device_idle_share", "xla_ms", "dispatch_ms"}
    assert not {"kernels_roofline", "linear_attention_ms", "full_attention_ms", "collective_ms"} & {m["name"] for m in cell.per_layer}
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and len(MANIFEST["configs"][-1]["why"]) <= 200 and MANIFEST["configs"][-1]["reduced"] == []


def test_kernels_roofline_lists_the_nine_accepted_cells_and_not_the_new_one():
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == "kernels_roofline")
    assert entry["workloads"] == NINE == [w["name"] for w in MANIFEST["workloads"]][:9]
    # every other metric without a list is read in the new cell too
    unlisted = [m["name"] for m in MANIFEST["per_layer"] if "workloads" not in m]
    assert set(unlisted) <= {m["name"] for m in manifest.load_cell(CELL).per_layer} and "mfu" in unlisted


def test_prompts_are_zipf_over_the_whole_vocabulary_and_follow_the_seed():
    job = job_of(CELL)
    job.rng = np.random.RandomState(job.seed)
    batches = [job.make_batch() for _ in range(2)]
    harmonic = (1.0 / np.arange(1, 100353)).sum()
    for ids in batches:
        assert ids.shape == (1, 16384) and ids.dtype == np.int32 and 0 <= ids.min() and ids.max() < 100352
        counts = np.sort(np.bincount(ids.ravel(), minlength=100352))[::-1] / ids.size
        assert counts[0] == pytest.approx(1 / harmonic, rel=0.15)  # the commonest id: 8.3% of a prompt
    assert np.bincount(batches[0].ravel()).argmax() == np.bincount(batches[1].ravel()).argmax()
    assert not np.array_equal(batches[0], batches[1])
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
    mixers = ["attention"] + cell.config["layer_types"][1:]
    with pytest.raises(ValueError, match="mixers"):
        module.Job(dataclasses.replace(cell, config={**cell.config, "layer_types": mixers}), seed=1, platform="cpu", rehearse=False)
    for key, value in (("mamba_d_state", 64), ("mamba_n_heads", 32), ("attention_multiplier", 0.125), ("residual_multiplier", 1.0)):
        with pytest.raises(ValueError, match="disagree|mamba_expand"):  # a width that differs is an error, never a private variant
            module.Job(dataclasses.replace(cell, config={**cell.config, key: value}), seed=1, platform="cpu", rehearse=False)


def test_the_state_space_leaves_are_drawn_as_mamba_2_draws_them_and_two_projections_larger():
    import jax

    from perfbench import weights
    from perfbench.jobs import forward_window_moe

    job = rehearsal_job()
    plain = forward_window_moe.draw(job.shapes, job.seed)
    tree, again = job.draw(), job.draw()
    changed = set()
    for (kind, layer, _), before, after, same in zip(weights.leaf_kinds(tree), jax.tree_util.tree_leaves(plain),
                                                      jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(again)):
        a, b = np.asarray(after, np.float64), np.asarray(before, np.float64)
        assert np.array_equal(a, np.asarray(same, np.float64)) and after.dtype == before.dtype and after.shape == before.shape
        if not np.array_equal(a, b):
            changed.add(kind)
        if kind.endswith("A_log"):
            assert after.dtype == np.float32 and (0 <= a).all() and (a <= np.log(16)).all() and np.exp(a).std() > 2
        elif kind.endswith("dt_bias"):
            step = np.log1p(np.exp(a))  # the step a token's zero gives
            assert after.dtype == np.float32 and (0.000999 <= step).all() and (step <= 0.1001).all()
            assert np.log(step).std() > 0.8  # log-uniform over two decades
        elif kind.endswith("/D"):
            assert (a == 1).all()
        elif kind.endswith("conv_w"):
            assert (np.abs(a) <= 0.5).all() and a.std() == pytest.approx(0.5 / 3 ** 0.5, rel=0.1)  # U(-0.5, 0.5)
        elif kind.endswith("mamba/in_proj_w"):
            rows = slice(2 * 2048, 2 * 2048 + 2 * 128)  # the stand-in's B and C: after z and x of 32 heads of 64
            assert np.array_equal(a[rows], 4.0 * b[rows]) and np.array_equal(np.delete(a, np.r_[rows], 0), np.delete(b, np.r_[rows], 0))
        elif kind.endswith("attn/qkv_w"):
            rows = slice(0, (16 + 4) * 64)  # q and k; v as drawn
            assert np.array_equal(a[rows], 8.0 * b[rows]) and np.array_equal(a[rows.stop:], b[rows.stop:])
        elif kind.endswith("/weight"):
            assert abs(a.mean() - 1) < 0.01
    assert changed == {"blocks/*/mamba/" + leaf for leaf in ("A_log", "dt_bias", "D", "conv_w", "in_proj_w")} | {"blocks/*/attn/qkv_w"}
    # two layers' leaves of one kind are draws of their own
    assert not np.array_equal(np.asarray(tree["blocks"][0]["mamba"]["A_log"]), np.asarray(tree["blocks"][2]["mamba"]["A_log"]))
    assert (forward_ssm.A_RANGE, forward_ssm.DT_RANGE, forward_ssm.BC_SCALE, forward_ssm.QK_SCALE) == ((1.0, 16.0), (0.001, 0.1), 4.0, 8.0)


def test_the_counters_are_the_equations_work_and_the_programs_own_chunk():
    job, small = job_of(CELL), rehearsal_job()
    assert job.cfg.ssm_chunk_size == 256 and small.cfg.ssm_chunk_size == 64  # what the program publishes
    performed, required = job.counters["ssm_chunk_ops"]()
    assert required == job.counters["mixer_work"]["ssm_scan"][0] == 36 * 4.0 * 64 * 64 * 128 * 16384
    assert performed == 36 * flops_ssm.chunked_ops(16384, 256, 64, 64, 128, 1) and performed / required == pytest.approx(2.15625)
    performed, required = small.counters["ssm_chunk_ops"]()
    assert performed / required == pytest.approx(flops_ssm.chunked_ops(256, 64, 32, 64, 128, 1) / (4.0 * 32 * 64 * 128 * 256))


def test_the_check_passes_the_system_and_fails_the_reference_at_float8(monkeypatch):
    """The cell's check at the stand-in sizes, in process: the system passes;
    the builder's control (``PERFBENCH_CHECK_PRECISIONS``, unset in the driver's
    runs) puts the reference itself with float8 and with bf16 matmul inputs
    through the same comparison in the system's place: float8, the precision
    below the one the configuration states, comes out as not correct, bf16 as
    correct. The count of claimed symbols is derived from the rehearsal's own
    execution trace, not stated."""
    import thunder_tpu
    from perfbench import checks_ssm

    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    monkeypatch.setenv("PERFBENCH_CHECK_PRECISIONS", "float8_e4m3fn,bfloat16")
    job = rehearsal_job(seed=2**31 + 11)
    job.setup()
    claimed = claimed_lines(thunder_tpu.last_traces(job.jfn)[-1])
    assert job.counters["kernels_claimed"] == claimed > 0
    job.release()
    verdict = job.check(importlib.import_module("perfbench.reference.granite_hybrid"))
    assert verdict["ok"] and verdict["logits_rtol"] == checks_ssm.SSM_LOGITS_RTOL and verdict["compared"] == [1, 64, 512]
    lower, same = verdict["reference_at"]["float8_e4m3fn"], verdict["reference_at"]["bfloat16"]
    assert same["ok"] and not lower["ok"] and same["logits_rel_l2"] < verdict["logits_rtol"] < lower["logits_rel_l2"]
    assert job.params is None  # the system's weights were let go before the reference's were drawn


# -----------------------------------------------------------------------------
# The readers
# -----------------------------------------------------------------------------

HLO = """HloModule jit_run

%fused_computation.7 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %exp.1 = f32[8,8]{1,0} exponential(%p0), metadata={op_name="jit(run)/ssm.scan/exp"}
  ROOT %mul.2 = f32[8,8]{1,0} multiply(%exp.1, %p0), metadata={op_name="jit(run)/ssm.scan/mul"}
}

ENTRY %main.20 (Arg_0.1: f32[8,8]) -> f32[8,8] {
  %Arg_0.1 = f32[8,8]{1,0} parameter(0)
  %fusion.11 = f32[8,8]{1,0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.6, metadata={op_name="jit(run)/ssm.conv/logistic"}
  %fusion.12 = f32[8,8]{1,0} fusion(%fusion.11), kind=kLoop, calls=%fused_computation.7
  %fusion.13 = f32[8,8]{1,0} fusion(%fusion.12), kind=kOutput, calls=%fused_computation.8, metadata={op_name="jit(run)/ssm.scan/dot_general"}
  %fusion.14 = f32[8,8]{1,0} fusion(%fusion.13), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(run)/ssm.gate_norm/rsqrt"}
  %splash_mha_fwd_no_residuals.7 = f32[8,8]{1,0} custom-call(%fusion.14), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/attn.full/pallas_call"}
  %fusion.15 = f32[8,8]{1,0} fusion(%splash_mha_fwd_no_residuals.7), kind=kOutput, calls=%fused_computation.10, metadata={op_name="jit(run)/dot_general"}
  ROOT %copy.16 = f32[8,8]{1,0} copy(%fusion.15)
}
"""


def fake_reading(region_of=None, work=None, chunk_ops=None):
    """A reading whose trace holds one device and two traced units: 8 ms in the
    convolution's fusion, 20 + 10 in the scan's two, 6 in the gated norm's, 16 in
    the attention call, 40 in no region."""
    cell = manifest.load_cell(CELL)
    ms = 1e-3
    fusion = "%{} = f32[8,8]{{1,0}} fusion(f32[8,8]{{1,0}} %x), kind=kLoop"
    call = '%splash_mha_fwd_no_residuals.7 = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %x), custom_call_target="tpu_custom_call"'
    events = [xplane.Event(fusion.format("fusion.11"), 0.0, 8 * ms), xplane.Event(fusion.format("fusion.12"), 8 * ms, 28 * ms),
              xplane.Event(fusion.format("fusion.13"), 28 * ms, 38 * ms), xplane.Event(fusion.format("fusion.14"), 38 * ms, 44 * ms),
              xplane.Event(call, 44 * ms, 60 * ms), xplane.Event(fusion.format("fusion.15"), 60 * ms, 100 * ms)]
    trace = xplane.Trace([xplane.DeviceTrace(0, events, [], [])], [])
    counters = {"tokens_per_unit": 16384}
    if region_of is not None:
        counters["region_of_instruction"] = lambda: region_of
    if work is not None:
        counters["mixer_work"] = work
    if chunk_ops is not None:
        counters["ssm_chunk_ops"] = lambda: chunk_ops
    return reading.Reading(cell=cell, spans={}, counters=counters, window=types.SimpleNamespace(), tokens_per_s=1.0,
                           flops_per_token=1.0, peaks=peaks.peaks_for("TPU v5 lite"), trace=trace, traced_units=2)


def test_regions_are_read_out_of_a_compiled_programs_text():
    found = _regions.of_instructions(HLO, forward_ssm.REGIONS)
    assert found == {"exp.1": "ssm.scan", "mul.2": "ssm.scan", "fusion.11": "ssm.conv", "fusion.12": "ssm.scan",
                     "fusion.13": "ssm.scan", "fusion.14": "ssm.gate_norm", "splash_mha_fwd_no_residuals.7": "attn.full"}
    assert _regions.of_instructions(HLO) == {}  # the older cells' regions are not this program's


def test_the_seven_readers_on_a_synthetic_trace():
    work = {"ssm_scan": [1.0, 819e9 * 1.5e-3], "ssm_conv": [1.0, 819e9 * 1e-3]}  # least 1.5 ms and 1 ms a call, by memory
    r = fake_reading(_regions.of_instructions(HLO, forward_ssm.REGIONS), work=work, chunk_ops=[69.0, 32.0])
    assert reading.read_metric("ssm_conv_ms", r) == pytest.approx(8 / 2)
    assert reading.read_metric("ssm_scan_ms", r) == pytest.approx((20 + 10) / 2)
    assert reading.read_metric("ssm_gate_norm_ms", r) == pytest.approx(6 / 2)
    assert reading.read_metric("granite_full_attention_ms", r) == pytest.approx(16 / 2)
    assert reading.read_metric("ssm_scan_roofline", r) == pytest.approx(100 * 1.5 / 15)
    assert reading.read_metric("ssm_conv_roofline", r) == pytest.approx(100 * 1.0 / 4)
    assert reading.read_metric("ssm_chunk_ops_over_required", r) == pytest.approx(69 / 32)
    units = {m["name"]: (m["unit"], m["layer"], m["source"]) for m in r.cell.per_layer}
    assert [units[n][0] for n in NEW] == ["ms", "%", "ms", "%", "ms", "ms", "ratio"]
    assert {units[n][1] for n in NEW[:6]} == {"kernels"} and units[NEW[6]][1:] == ("attention", "program_counter")
    assert flops.least_seconds(*work["ssm_scan"], r.peaks) == (pytest.approx(1.5e-3), "memory")


@pytest.mark.parametrize("metric", NEW)
def test_on_a_program_without_the_regions_or_the_counters_a_new_reader_reads_nothing(metric):
    """The parent commit's program names no such region and its jobs hand out no
    such counter: the reader returns ``None``, does not raise, and the line
    leaves the metric out."""
    assert reading.read_metric(metric, fake_reading()) is None
    assert reading.read_metric(metric, fake_reading(region_of={})) is None
    r = fake_reading(region_of={})
    r.counters["ssm_chunk_ops"] = lambda: None
    assert reading.read_metric(metric, r) is None


def test_the_cell_through_run_py_rehearse_trace_1_reads_every_new_metric_a_cpu_can():
    """Every per-layer metric of the cell that is no device's (a CPU's trace has
    no device plane) is on the line of a ``--rehearse --trace 1`` run, the new
    counter's among them, and the run is correct."""
    from pb_helpers import DEVICE_ONLY

    result = result_of(run_cell(CELL, "--rehearse", trace=1))
    assert result["correct"] is True and result["failed"] == 0 and "breakdown" in result
    wanted = {m["name"] for m in metrics_for(CELL, "per_layer")} - DEVICE_ONLY
    assert set(result["metrics"]) == wanted and "ssm_chunk_ops_over_required" in wanted and "kernels_roofline" not in wanted
    small = rehearsal_job()
    assert result["metrics"]["ssm_chunk_ops_over_required"]["value"] == pytest.approx(
        small.counters["ssm_chunk_ops"]()[0] / small.counters["ssm_chunk_ops"]()[1])
    assert result["metrics"]["kernels_claimed"]["value"] > 0 and result["metrics"]["compiles_in_window"]["value"] == 0
    untraced = result_of(run_cell(CELL, "--rehearse", trace=0))
    assert untraced["correct"] is True and set(untraced["metrics"]) == {"tokens_per_s", "peak_hbm_gb", "setup_s"}
