"""Job ``train_loop`` and the readers this configuration brings, without a
chip: the cell's files, the required operations of a looped model, the exit
heads' least work, the counters the readers are handed, the per-row
cross-entropy claimed at every loop step, the check at the stand-in sizes, and
the cell through ``run.py --rehearse --trace 1``."""

import importlib
import json
import types

import numpy as np
import pytest
from pb_helpers import MANIFEST, metrics_for, result_of, run_cell
from test_pb_flops import job_of

from perfbench import flops, flops_loop, manifest, peaks, reading, weights, xplane
from perfbench.jobs import train_loop
from perfbench.layer_metrics import _regions

CELL = "ouro-2.6b.train"
NEW = ["exit_heads_ms", "exit_heads_roofline"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def rehearsal_job(seed=7):
    cell = manifest.load_cell(CELL)
    return importlib.import_module(f"perfbench.jobs.{cell.traffic['job']}").Job(cell, seed=seed, platform="cpu", rehearse=True)


def test_the_cell_is_the_issues_letter_for_letter():
    cell = manifest.load_cell(CELL)
    t, c = cell.traffic, cell.config
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "ouro-2.6b", "train_b1_t4096_exit4")
    assert (t["job"], t["batch"], t["seq"], t["in_flight"], t["warmup_units"], t["trace_units"]) == ("train_loop", 1, 4096, 2, 2, 6)
    assert (t["optimizer"], t["lr"], t["weight_decay"], t["b1"], t["b2"], t["mesh"]) == ("adamw", 3e-4, 0.1, 0.9, 0.95, None)
    assert c["reduced"] == ["num_hidden_layers", "max_position_embeddings"] and c["reference"] == "ouro"
    assert (c["num_hidden_layers"], c["max_position_embeddings"], c["exit_entropy"]) == (7, 4096, 0.1)
    # every key of the catalogue's row under its own name, as published, but the two reduced
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    kept = {k: v for k, v in row["config"].items() if k not in c["reduced"]}
    assert {k: c[k] for k in kept} == kept and c["source"] == row["source_url"]
    assert (row["config"]["num_hidden_layers"], row["config"]["max_position_embeddings"]) == (48, 65536)
    assert set(c["assumed"]) == {"norms", "final_norm", "gate", "exit_distribution", "objective", "no_biases", "weights"}
    # the depth is the deepest that leaves 1.5 GB of the 16 free, by the compiles in the traffic file
    sized = t["sized_by"]["memory_analysis_gb"]
    assert sized["depth 7"]["needs"] <= 16 - 1.5 < sized["depth 8"]["needs"]
    names = {m["name"] for m in cell.per_layer}
    assert names >= set(NEW) | {"kernels_ms", "mfu", "device_idle_share", "xla_ms", "dispatch_ms", "stall_share",
                                "kernels_claimed", "phase_jax_trace_s", "phase_optimizer_state_s"}
    assert not {"kernels_roofline", "ssm_scan_ms", "collective_ms"} & names
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    config = next(e for e in MANIFEST["configs"] if e["name"] == "ouro-2.6b")
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200 and config["reduced"] == c["reduced"]


def test_the_required_operations_count_every_loop_step():
    job = job_of(CELL)
    # a layer: qkv (16 + 2*16)*128*2048 + proj 2048*2048 + SwiGLU 3*2048*5632 = 51,380,224
    assert flops_loop.layer_matmul_params(job.keys) == 51_380_224
    # forward a token: 4 steps of 7 layers and the head, the gate on 3, causal attention over 28 layers
    counted = 4 * (7 * 51_380_224 + 49152 * 2048) + 3 * 2048
    assert flops_loop.forward_flops_per_token(job.keys, 4096) == 2 * counted + 28 * 2 * 4096 * 16 * 128
    assert job.flops_per_token() == 3 * flops_loop.forward_flops_per_token(job.keys, 4096)
    # the issue's reckoning at depth 6: 3.67 GFLOP forward a token, 45 TFLOP a step; four times what one pass counts
    six = dict(job.keys, num_hidden_layers=6)
    assert flops_loop.forward_flops_per_token(six, 4096) == pytest.approx(3.674e9, rel=1e-3)
    assert 3 * 4096 * flops_loop.forward_flops_per_token(six, 4096) == pytest.approx(45.15e12, rel=1e-3)
    assert job.flops_per_token() / (3 * job.forward_flops_per_token()) == pytest.approx(4.0, rel=1e-3)
    # the heads are a fifth of the work
    assert 2 * 4 * 49152 * 2048 / flops_loop.forward_flops_per_token(job.keys, 4096) == pytest.approx(0.194, abs=1e-3)


def test_the_exit_heads_least_work_is_the_issues():
    job = job_of(CELL)
    ops, nbytes = job.counters["mixer_work"]["exit_heads"]
    T, C, V = 4096, 2048, 49152
    assert ops == 4 * 6 * T * C * V
    assert nbytes == 4 * 2 * (3 * T * V + 3 * T * V + V * C)
    least, bound = flops.least_seconds(ops, nbytes, peaks.peaks_for("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(ops / 197e12)


def test_every_steps_cross_entropy_is_claimed_forward_and_backward(monkeypatch):
    """At the stand-in sizes: the claimed trace holds a per-row ``pallas``
    cross-entropy and its backward for each of the four loop steps, 8 symbols
    more than the same trace with the claim declined."""
    from perfbench.jobs import gpt_model
    from thunder_tpu.executors import pallasex
    from thunder_tpu.parallel import build_train_step

    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    job = rehearsal_job()
    params = weights.make_system_weights(job.shapes, job.seed)
    batch = gpt_model.token_batch(np.random.RandomState(0), job.keys["vocab_size"], job.batch, job.seq)

    def claimed():
        _, _, extrace = build_train_step(job.cfg, params, *batch, return_extrace=True)
        ce = [b for b in extrace.bound_symbols if "cross_entropy" in b.sym.name and b.sym.executor.name == "pallas"]
        return gpt_model.kernels_claimed(extrace), ce

    count, ce = claimed()
    assert sorted(b.sym.name for b in ce) == ["cross_entropy"] * 4 + ["cross_entropy_bwd"] * 4
    assert {b.region for b in ce} == {"exit.heads", "exit.heads/bwd"}
    monkeypatch.setattr(pallasex, "_CE_REDUCTIONS", ("mean", "sum"))  # the parent's claim
    declined, none = claimed()
    assert count - declined == 8 and not none


def test_the_check_passes_and_reads_the_reference_at_lower_precisions(monkeypatch):
    """The cell's check at the stand-in sizes, in process: the system passes, and
    the builder's control (``PERFBENCH_CHECK_PRECISIONS``, unset in the driver's
    runs) puts the reference with float8 and with bf16 matmul inputs through the
    same comparison against the float32 reference."""
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    monkeypatch.setenv("PERFBENCH_CHECK_PRECISIONS", "float8_e4m3fn,bfloat16")
    job = rehearsal_job(seed=2**31 + 11)
    job.setup()
    assert job.counters["kernels_claimed"] > 8
    job.compiled()
    job.release()
    verdict = job.check(importlib.import_module("perfbench.reference.ouro"))
    assert verdict["ok"], verdict
    assert "exit_gate_w@None" in verdict["grad_err"] and "exit_gate_b@None" in verdict["grad_err"]
    lower, same = verdict["reference_at"]["float8_e4m3fn"], verdict["reference_at"]["bfloat16"]
    assert same["ok"] and not lower["ok"], verdict["reference_at"]
    assert set(verdict["gate_bias"]) == {"system", "reference", "floor"}
    # the executable was kept: the regions can be read after the state is gone
    assert set(job.counters["region_of_instruction"]().values()) == {"exit.heads", "exit.gate"}


@pytest.mark.parametrize("case", ["rounding", "cancelled_rounding", "dropped_bias", "dropped_bias_cancelled", "other_leaf"])
def test_the_gate_bias_is_judged_as_one_more_column_of_the_gates_row(case):
    """``checks_loop.compare_training``: the bias's gradient counts as at least
    the gate row's root-mean-square entry, so a rounding of a sum that fell
    among its row's small entries passes and a dropped bias fails wherever its
    gradient is a typical entry or more; every other leaf is
    ``checks.compare_training``'s."""
    from perfbench import checks, checks_loop

    rng = np.random.RandomState(0)
    w = rng.randn(1, 2048)  # entries ~1: the row's rms entry, the floor, ~1
    b = {"rounding": 3.0, "cancelled_rounding": 0.01, "dropped_bias": 3.0, "dropped_bias_cancelled": 0.01,
         "other_leaf": 3.0}[case]
    ref = {"exit_gate_w@None": w, "exit_gate_b@None": np.array([b]), "ln_f/weight@None": rng.randn(64)}
    got = {k: v.copy() for k, v in ref.items()}
    if case.startswith("dropped"):
        got["exit_gate_b@None"] = np.array([0.0])
    else:
        got["exit_gate_b@None"] = np.array([b + 0.012])  # an entry's error where its row reads about 1%
    if case == "other_leaf":
        got["ln_f/weight@None"] = 1.5 * ref["ln_f/weight@None"]
    verdict = checks_loop.compare_training(11.0, got, 11.0, ref)
    plain = checks.compare_training(11.0, got, 11.0, ref)
    err = verdict["grad_err"]["exit_gate_b@None"]
    floor = np.sqrt(np.mean(np.append(w, b) ** 2))
    assert verdict["gate_bias"]["floor"] == pytest.approx(floor) and 0.95 < floor < 1.05
    if case == "rounding":
        assert verdict["ok"] and plain["ok"] and err == pytest.approx(0.004)
    elif case == "cancelled_rounding":  # the plain rule reads 1.2: the error over a gradient a hundredth of its row's
        assert verdict["ok"] and not plain["ok"] and err == pytest.approx(0.012 / floor, rel=1e-3)
    elif case == "dropped_bias":
        assert not verdict["ok"] and err == 1.0
    elif case == "dropped_bias_cancelled":  # among its row's small entries a dropped bias is not heard
        assert verdict["ok"] and err == pytest.approx(0.01 / floor, rel=1e-3)
    else:
        assert not verdict["ok"] and not plain["ok"] and verdict["grad_worst"] == plain["grad_worst"]
    assert {k: v for k, v in verdict["grad_err"].items() if k != "exit_gate_b@None"} == \
        {k: v for k, v in plain["grad_err"].items() if k != "exit_gate_b@None"}


# -----------------------------------------------------------------------------
# The readers
# -----------------------------------------------------------------------------

HLO = """HloModule jit_step

%fused_computation.7 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  ROOT %dot.2 = f32[8,8]{1,0} dot(%p0, %p0), metadata={op_name="jit(step)/exit.heads/bwd/dot_general"}
}

ENTRY %main.20 (Arg_0.1: f32[8,8]) -> f32[8,8] {
  %Arg_0.1 = f32[8,8]{1,0} parameter(0)
  %fusion.11 = f32[8,8]{1,0} fusion(%Arg_0.1), kind=kOutput, calls=%fused_computation.6, metadata={op_name="jit(step)/exit.heads/dot_general"}
  %pallas_ce.3 = f32[8,8]{1,0} custom-call(%fusion.11), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/exit.heads/pallas_call"}
  %fusion.12 = f32[8,8]{1,0} fusion(%pallas_ce.3), kind=kLoop, calls=%fused_computation.7
  %fusion.13 = f32[8,8]{1,0} fusion(%fusion.12), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(step)/exit.gate/logistic"}
  ROOT %fusion.15 = f32[8,8]{1,0} fusion(%fusion.13), kind=kOutput, calls=%fused_computation.10, metadata={op_name="jit(step)/dot_general"}
}
"""


def fake_reading(region_of=None, work=None):
    """A reading whose trace holds one device and two traced units: 30 ms in the
    heads' forward fusion, 10 in their cross-entropy call, 40 in their backward
    fusion, 4 in the gate, 100 in no region."""
    cell = manifest.load_cell(CELL)
    ms = 1e-3
    fusion = "%{} = f32[8,8]{{1,0}} fusion(f32[8,8]{{1,0}} %x), kind=kLoop"
    call = '%pallas_ce.3 = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %x), custom_call_target="tpu_custom_call"'
    events = [xplane.Event(fusion.format("fusion.11"), 0.0, 30 * ms), xplane.Event(call, 30 * ms, 40 * ms),
              xplane.Event(fusion.format("fusion.12"), 40 * ms, 80 * ms), xplane.Event(fusion.format("fusion.13"), 80 * ms, 84 * ms),
              xplane.Event(fusion.format("fusion.15"), 84 * ms, 184 * ms)]
    trace = xplane.Trace([xplane.DeviceTrace(0, events, [], [])], [])
    counters = {}
    if region_of is not None:
        counters["region_of_instruction"] = lambda: region_of
    if work is not None:
        counters["mixer_work"] = work
    return reading.Reading(cell=cell, spans={}, counters=counters, window=types.SimpleNamespace(), tokens_per_s=1.0,
                           flops_per_token=1.0, peaks=peaks.peaks_for("TPU v5 lite"), trace=trace, traced_units=2)


def test_the_regions_of_both_passes_are_read_out_of_a_compiled_programs_text():
    found = _regions.of_instructions(HLO, train_loop.REGIONS)
    assert found == {"dot.2": "exit.heads", "fusion.11": "exit.heads", "pallas_ce.3": "exit.heads",
                     "fusion.12": "exit.heads", "fusion.13": "exit.gate"}
    assert _regions.of_instructions(HLO) == {}  # the older cells' regions are not this program's


def test_the_two_readers_on_a_synthetic_trace():
    work = {"exit_heads": [197e12 * 20e-3, 1.0]}  # least 20 ms a step, by compute
    r = fake_reading(_regions.of_instructions(HLO, train_loop.REGIONS), work=work)
    assert reading.read_metric("exit_heads_ms", r) == pytest.approx((30 + 10 + 40) / 2)
    assert reading.read_metric("exit_heads_roofline", r) == pytest.approx(100 * 20 / 40)
    units = {m["name"]: (m["unit"], m["layer"], m["source"], m["moves"], m["workloads"]) for m in r.cell.per_layer if m["name"] in NEW}
    assert units == {"exit_heads_ms": ("ms", "kernels", "device_trace", "tokens_per_s", [CELL]),
                     "exit_heads_roofline": ("%", "kernels", "device_trace", "tokens_per_s", [CELL])}


@pytest.mark.parametrize("metric", NEW)
def test_on_a_program_without_the_regions_a_new_reader_reads_nothing(metric):
    """The parent commit's program names no such region: the reader returns
    ``None``, does not raise, and the line leaves the metric out."""
    work = {"exit_heads": [1.0, 1.0]}
    assert reading.read_metric(metric, fake_reading(work=work)) is None
    assert reading.read_metric(metric, fake_reading(region_of={}, work=work)) is None


def test_the_cell_through_run_py_rehearse_trace_1_reads_every_metric_a_cpu_can():
    from pb_helpers import DEVICE_ONLY

    result = result_of(run_cell(CELL, "--rehearse", trace=1))
    assert result["correct"] is True and result["failed"] == 0 and "breakdown" in result
    wanted = {m["name"] for m in metrics_for(CELL, "per_layer")} - DEVICE_ONLY
    assert set(result["metrics"]) == wanted and "phase_optimizer_state_s" in wanted and not set(NEW) & wanted
    assert result["metrics"]["kernels_claimed"]["value"] > 8 and result["metrics"]["compiles_in_window"]["value"] == 0
