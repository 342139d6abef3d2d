"""Job ``forward_scmoe`` and the readers this configuration brings, without a
chip: the Zipf prompts over the slice and the assignment that routes the even
share here, the weights drawn a leaf of a layer at a time (and a layer at a time
for the reference), the counters the readers are handed, the check at the
stand-in sizes, and the cell through ``run.py --rehearse --trace 1``."""

import dataclasses
import importlib
import types

import numpy as np
import pytest
from pb_helpers import metrics_for, result_of, run_cell
from test_pb_flops import job_of

from perfbench import flops, manifest, peaks, reading, xplane
from perfbench.jobs import forward_scmoe
from perfbench.layer_metrics import _regions

CELL = "longcat-flash-omni.fwd-t16k"
NEW = ["longcat_mla_attention_ms", "longcat_mla_attention_roofline", "longcat_experts_ms", "longcat_experts_roofline",
       "longcat_load_max_over_mean", "longcat_routed_here_per_token", "longcat_bias_changed_choices", "zero_expert_choices",
       "shortcut_moe_ms"]


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
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "longcat-flash-omni", "fwd_b1_t16384_zipf_v16384_last1024")
    assert (t["job"], t["batch"], t["seq"], t["last"], t["in_flight"]) == ("forward_scmoe", 1, 16384, 1024, 2)
    assert (t["zipf_exponent"], t["assignments_tried"], t["warmup_units"], t["trace_units"], t["check_sequences"]) == (1.0, 32, 1, 3, 1)
    assert c["reduced"] == ["num_layers", "n_routed_experts", "vocab_size", "max_position_embeddings"]
    assert (c["num_layers"], c["n_routed_experts"], c["vocab_size"], c["max_position_embeddings"]) == (4, 16, 16384, 16384)
    assert (c["n_routed_experts_published"], c["expert_offset"], c["deployment_chips_per_layer"], c["reference"]) == (512, 0, 32, "longcat_flash")
    # every number of the catalog's config under its own key, the four cut ones apart
    published = {"attention_bias": False, "hidden_size": 6144, "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
                 "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
                 "qk_nope_head_dim": 128, "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
                 "rms_norm_eps": 1e-05, "rope_theta": 10000000, "attention_method": "MLA", "zero_expert_num": 256,
                 "zero_expert_type": "identity", "moe_topk": 12}
    assert {k: c[k] for k in published} == published
    assert sorted(c["assumed"]) == sorted(["mla_scales", "norm_topk_prob", "router_bias", "rope_pairing", "untied_head", "weights",
                                           "experts_down_scale", "block", "zero_expert_weight"])
    assert {m["name"] for m in cell.per_layer} >= set(NEW) | {"kernels_ms", "kernels_roofline", "mfu", "device_idle_share"}
    assert not {"experts_ms", "mla_attention_ms", "trinity_experts_ms", "collective_ms"} & {m["name"] for m in cell.per_layer}
    assert "32" in manifest.load_manifest()["workloads"][-1]["why"]  # attention and the dense FFNs see 32 times their share


def test_prompts_are_zipf_over_the_slice_and_the_assignment_is_the_even_one_of_the_seeds():
    job = rehearsal_job()
    counted = []

    def count(params, ids):  # the program's routers, stood in for: the second permutation routes the even share
        counted.append(ids)
        here = [0.40, 2 / 3, 0.90][len(counted) - 1]
        return (np.full((2, 4), here * job.tokens_per_unit / 4),)

    job._count, job.params = count, {}
    first = job.make_batch()
    assert len(counted) == job.traffic["assignments_tried"] == 3 and job.spans["assign_ids_s"] >= 0
    assert first.shape == (1, 256) and first.dtype == np.int32 and 0 <= first.min() and first.max() < 512
    tried = list(job.assignments())
    assert np.array_equal(job.id_of_rank, tried[1][0]) and not np.array_equal(tried[0][0], tried[1][0])
    more = [job.make_batch() for _ in range(3)]
    assert len(counted) == 3 and not np.array_equal(more[0], more[1])  # chosen once, at the run's first batch
    real = job_of(CELL)
    harmonic = (1.0 / np.arange(1, 16385)).sum()
    ids = real.zipf_ids(np.random.RandomState(3), np.arange(16384, dtype=np.int32))
    assert ids.shape == (1, 16384) and ids.max() < 16384
    assert (ids == 0).mean() == pytest.approx(1 / harmonic, rel=0.15)  # the commonest id: 9.7% of a prompt
    large = rehearsal_job(2**31 + 5)  # a seed beyond 32 signed bits
    large._count, large.params = count, {}
    counted.clear()
    assert large.make_batch().shape == (1, 256)


def test_the_job_keeps_the_last_units_ids_and_refuses_a_width_that_differs():
    job = rehearsal_job()
    job.jfn, job.params, job.read_back = (lambda p, i: i), {}, (lambda x: x)
    job.id_of_rank = np.arange(512, dtype=np.int32)
    batches = [job.make_batch() for _ in range(5)]
    for b in batches:
        job.issue(b)
    assert len(job.issued) == job.cell.traffic["trace_units"] == 3
    assert all(np.array_equal(a, b) for a, b in zip(job.issued, batches[-3:]))
    cell = manifest.load_cell(CELL)
    module = importlib.import_module(f"perfbench.jobs.{cell.traffic['job']}")
    for key, value in (("expert_ffn_hidden_size", 1024), ("zero_expert_num", 128), ("mla_scale_kv_lora", False), ("moe_topk", 8)):
        with pytest.raises(ValueError, match="disagree"):  # a width that differs is an error, never a private variant
            module.Job(dataclasses.replace(cell, config={**cell.config, key: value}), seed=1, platform="cpu", rehearse=False)


def test_the_weights_are_drawn_a_leaf_of_a_layer_at_a_time_and_a_layer_at_a_time_for_the_reference():
    import jax

    from perfbench import weights

    job = rehearsal_job()
    tree, again, other = job.weights(), forward_scmoe.draw(job.shapes, 7, job.bias_std, job.down_scale), forward_scmoe.draw(
        job.shapes, 8, job.bias_std, job.down_scale)
    flat = lambda t: {kind + str(layer): np.asarray(leaf, np.float32) for (kind, layer, _), leaf in
                      zip(weights.leaf_kinds(t), jax.tree_util.tree_leaves(t))}
    a, b, c = flat(tree), flat(again), flat(other)
    assert all(np.array_equal(a[k], b[k]) for k in a) and not any(np.array_equal(a[k], c[k]) for k in a)
    for name, leaf in a.items():
        if "router_bias" in name:
            assert leaf.dtype == np.float32 and leaf.std() == pytest.approx(job.bias_std, rel=0.5) and job.bias_std == 0.005
        elif "/weight" in name:
            assert abs(leaf.mean() - 1) < 0.01 and 0.01 < leaf.std() < 0.03
        elif "experts_down" in name:
            assert leaf.std() == pytest.approx(job.down_scale * 0.02, rel=0.1)  # drawn larger, so that the comparison hears them
        else:
            assert abs(leaf.mean()) < 0.005 and leaf.std() == pytest.approx(0.02, rel=0.1)
    # two layers' and two sublayers' leaves of one kind are draws of their own; no leaf is ever stacked
    assert not np.array_equal(a["blocks/*/sub_0/attn/q_a_w0"], a["blocks/*/sub_0/attn/q_a_w1"])
    assert not np.array_equal(a["blocks/*/sub_0/attn/q_a_w0"], a["blocks/*/sub_1/attn/q_a_w0"])
    assert [tuple(l.shape) for l in jax.tree_util.tree_leaves(tree)] == [tuple(l.shape) for l in jax.tree_util.tree_leaves(job.shapes)]
    # as the reference takes them: a layer at a time from the seed, the same numbers
    lazy = forward_scmoe.drawn_for_reference(job.shapes, 7, job.bias_std, job.down_scale)
    assert sorted(k for k in lazy if k != "layers") == ["lm_head_w", "ln_f/weight", "wte"]
    assert not isinstance(lazy["layers"], (list, tuple)) and np.array_equal(np.asarray(lazy["wte"], np.float32), a["wteNone"])
    layers = list(lazy["layers"])
    assert len(layers) == 2 and "sub_1/mlp/fc_1_w" in layers[1] and "moe/router_bias" in layers[0]
    for i, layer in enumerate(layers):
        for path, leaf in layer.items():
            assert np.array_equal(np.asarray(leaf, np.float32), a[f"blocks/*/{path}{i}"])


def test_the_counters_are_the_programs_own_routers_on_the_last_units(monkeypatch):
    from thunder_tpu.executors import pallasex

    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")  # as ``run.py --rehearse`` sets it: flash claims on the CPU too
    job = rehearsal_job(seed=11)
    job.setup()
    for _ in range(2):
        job.wait(job.issue(job.make_batch()))
    rows = job.counters["routed_rows"]()
    assert np.shape(rows) == (3, 2, 4) and (np.sum(rows, -1) < 256 * 4).all()  # the last 3 units, 2 routed layers, 4 held experts
    assert 0.02 < job.counters["bias_changed_choices"]() < 0.9  # the drawn bias changes choices and not all of them
    assert 0.15 < job.counters["zero_expert_choices"]() < 0.6   # 8 of 24 outputs: a third when even
    passes = job.counters["expert_buffer_passes"]()
    assert passes == [[pallasex.expert_buffer_passes(sum(layer), 256, 4, 4, 24) for layer in unit] for unit in rows]
    assert all(p in (1, 2) for unit in passes for p in unit) and pallasex.expert_buffer_rows(256, 4, 4, 24) == 512
    counted = job._counted
    assert job.counters["routed_rows"]() == rows and job._counted is counted  # counted once, read by six metrics
    # the count of claimed symbols is the execution trace's own: a rope of q and of k, an attention call a sublayer
    # and a dispatch a layer, whatever their number comes to
    import thunder_tpu

    run = thunder_tpu.last_traces(job.jfn)[-1]
    assert job.counters["kernels_claimed"] == claimed_lines(run) > 0
    owned = [b.sym.name for b in run.bound_symbols if b.sym.executor is not None and b.sym.executor.name in ("flash", "pallas")]
    assert owned.count("moe_experts") == 2 and owned.count("scaled_dot_product_attention") == 4
    assert len(owned) == 2 * (2 * 3 + 1)  # a layer: two sublayers' rope of q, of k and attention, and one dispatch
    found = job.counters["region_of_instruction"]()
    assert set(found.values()) == set(forward_scmoe.REGIONS)


def test_the_check_passes_the_system_and_fails_the_reference_at_float8(monkeypatch):
    """The cell's check at the stand-in sizes, in process: the system passes;
    the builder's control (``PERFBENCH_CHECK_PRECISIONS``, unset in the driver's
    runs) puts the reference itself with float8 and with bf16 matmul inputs
    through the same comparison in the system's place: float8, the precision
    below the one the configuration states, comes out as not correct, bf16 as
    correct."""
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    monkeypatch.setenv("PERFBENCH_CHECK_PRECISIONS", "float8_e4m3fn,bfloat16")
    job = rehearsal_job(seed=2**31 + 11)
    job.setup()
    job.release()
    verdict = job.check(importlib.import_module("perfbench.reference.longcat_flash"))
    assert verdict["ok"] and verdict["compared"] == [1, 64, 512]
    lower, same = verdict["reference_at"]["float8_e4m3fn"], verdict["reference_at"]["bfloat16"]
    assert same["ok"] and not lower["ok"] and same["logits_rel_l2"] < verdict["logits_rtol"] < lower["logits_rel_l2"]
    assert job.params is None  # the system's weights were let go before the reference's were drawn
    assert np.shape(verdict["expert_buffer_passes"]) == (2, 2)  # set-up's two units, two routed layers


# -----------------------------------------------------------------------------
# The readers
# -----------------------------------------------------------------------------

HLO = """HloModule jit_run

%fused_computation.7 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %mul.1 = f32[8,8]{1,0} multiply(%p0, %p0), metadata={op_name="jit(run)/moe.route/mul"}
  ROOT %sub.2 = f32[8,8]{1,0} subtract(%mul.1, %p0), metadata={op_name="jit(run)/moe.route/sub"}
}

%region_1.5 (p0: f32[8,8]) -> f32[8,8] {
  %p0.1 = f32[8,8]{1,0} parameter(0)
  ROOT %gmm.3 = f32[8,8]{1,0} custom-call(%p0.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/moe.experts/cond/branch_1_fun/pallas_call"}
}

ENTRY %main.20 (Arg_0.1: f32[8,8]) -> f32[8,8] {
  %Arg_0.1 = f32[8,8]{1,0} parameter(0)
  %fusion.12 = f32[8,8]{1,0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.7
  %splash_mha_fwd_no_residuals.7 = f32[8,8]{1,0} custom-call(%fusion.12), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/mla/pallas_call"}
  %cond.4 = f32[8,8]{1,0} conditional(%fusion.12), branch_computations={%region_1.5}, metadata={op_name="jit(run)/moe.experts/cond"}
  %fusion.13 = f32[8,8]{1,0} fusion(%cond.4), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(run)/moe.zero/mul"}
  %fusion.14 = f32[8,8]{1,0} fusion(%fusion.13), kind=kOutput, calls=%fused_computation.9, metadata={op_name="jit(run)/dot_general"}
  ROOT %copy.15 = f32[8,8]{1,0} copy(%fusion.14)
}
"""


def fake_reading(region_of=None, rows=None, changed=None, zero=None):
    """A reading whose trace holds one device and two traced units: 2 ms in the
    router's fusion, 40 in the latent-attention call, 30 in ``gmm``, 6 in the
    zero-compute term's fusion, 50 in no region."""
    cell = manifest.load_cell(CELL)
    ms = 1e-3
    call = '%{} = f32[8,8]{{1,0}} custom-call(f32[8,8]{{1,0}} %x), custom_call_target="tpu_custom_call"'
    mla = ('%splash_mha_fwd_no_residuals.7 = (f32[1024,128]{1,0}, bf16[64,16384,128]{2,1,0}) custom-call(s8[1,16,16]{2,1,0} %a, '
           's8[1,16,16]{2,1,0} %b, bf16[64,16384,192]{2,1,0} %q, bf16[64,16384,192]{2,1,0} %k, bf16[64,16384,128]{2,1,0} %v, '
           's32[16384,128]{1,0} %i), custom_call_target="tpu_custom_call"')
    events = [xplane.Event("%fusion.12 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %Arg_0.1), kind=kLoop", 0.0, 2 * ms),
              xplane.Event(mla, 2 * ms, 42 * ms),
              xplane.Event(call.format("gmm.3"), 42 * ms, 72 * ms),
              xplane.Event("%fusion.13 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %cond.4), kind=kLoop", 72 * ms, 78 * ms),
              xplane.Event("%fusion.14 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %fusion.13), kind=kOutput", 78 * ms, 128 * ms)]
    trace = xplane.Trace([xplane.DeviceTrace(0, events, [], [])], [])
    counters = {"tokens_per_unit": 16384}
    for name, value in (("region_of_instruction", region_of), ("routed_rows", rows), ("bias_changed_choices", changed),
                        ("zero_expert_choices", zero)):
        if value is not None:
            counters[name] = lambda value=value: value
    return reading.Reading(cell=cell, spans={}, counters=counters, window=types.SimpleNamespace(), tokens_per_s=1.0,
                           flops_per_token=1.0, peaks=peaks.peaks_for("TPU v5 lite"), trace=trace, traced_units=2)


def test_regions_are_read_out_of_a_compiled_programs_text_branches_and_all():
    found = _regions.of_instructions(HLO, forward_scmoe.REGIONS)
    assert found == {"mul.1": "moe.route", "sub.2": "moe.route", "fusion.12": "moe.route", "gmm.3": "moe.experts",
                     "cond.4": "moe.experts", "fusion.13": "moe.zero"}
    assert _regions.of_instructions(HLO) == {}  # the older cells' regions are not this program's


def test_the_nine_readers_on_a_synthetic_trace():
    from perfbench import flops_mla_moe

    rows = [[[300, 100, 0, 0]], [[100, 100, 100, 100]]]  # two units, one routed layer, four held experts
    r = fake_reading(_regions.of_instructions(HLO, forward_scmoe.REGIONS), rows=rows, changed=0.1, zero=1 / 3)
    assert reading.read_metric("longcat_mla_attention_ms", r) == pytest.approx(40 / 2)
    least = flops.least_seconds(*flops_mla_moe.attn_mla_fwd([64, 16384, 192], [64, 16384, 128]), r.peaks)[0]
    assert reading.read_metric("longcat_mla_attention_roofline", r) == pytest.approx(100 * 1e3 * least / 40)
    assert reading.read_metric("longcat_experts_ms", r) == pytest.approx(30 / 2)
    least = sum(flops.least_seconds(*flops_mla_moe.experts(unit[0], 6144, 2048), r.peaks)[0] for unit in rows) / 2
    assert reading.read_metric("longcat_experts_roofline", r) == pytest.approx(100 * 1e3 * least / 15)
    assert reading.read_metric("longcat_load_max_over_mean", r) == pytest.approx((3.0 + 1.0) / 2)
    assert reading.read_metric("longcat_routed_here_per_token", r) == pytest.approx(400 / 16384)
    assert reading.read_metric("longcat_bias_changed_choices", r) == pytest.approx(10.0)
    assert reading.read_metric("zero_expert_choices", r) == pytest.approx(100 / 3)
    assert reading.read_metric("shortcut_moe_ms", r) == pytest.approx((2 + 30 + 6) / 2)  # the router, the experts, the zero term
    units = {m["name"]: m["unit"] for m in r.cell.per_layer}
    assert [units[n] for n in NEW] == ["ms", "%", "ms", "%", "ratio", "experts/token", "%", "%", "ms"]
    assert r.cell.config["moe_intermediate_size"] == r.cell.config["expert_ffn_hidden_size"]  # the older reader's name for it


@pytest.mark.parametrize("metric", NEW)
def test_on_a_program_without_the_regions_or_the_counters_a_new_reader_reads_nothing(metric):
    """The parent commit's program names no such cell, region or counter: the
    reader returns ``None``, does not raise, and the line leaves the metric out."""
    bare = fake_reading()
    bare.trace = xplane.Trace([xplane.DeviceTrace(0, [e for e in bare.trace.devices[0].ops
                                                      if "gmm" not in e.name and "splash" not in e.name], [], [])], [])
    assert reading.read_metric(metric, bare) is None
    empty = fake_reading(region_of={})
    empty.trace = bare.trace
    empty.counters.update(routed_rows=lambda: None, bias_changed_choices=lambda: None, zero_expert_choices=lambda: None)
    assert reading.read_metric(metric, empty) is None


def test_the_cell_through_run_py_rehearse_trace_1_reads_every_new_metric_a_cpu_can():
    """Every per-layer metric of the cell that is no device's (a CPU's trace has
    no device plane) is on the line of a ``--rehearse --trace 1`` run, the new
    counters' among them, and the run is correct. The count of claimed symbols
    is no number stated here: ``test_the_counters_are_..`` derives it from the
    rehearsal's own execution trace."""
    from pb_helpers import DEVICE_ONLY

    result = result_of(run_cell(CELL, "--rehearse", trace=1))
    assert result["correct"] is True and result["failed"] == 0 and "breakdown" in result
    wanted = {m["name"] for m in metrics_for(CELL, "per_layer")} - DEVICE_ONLY
    assert set(result["metrics"]) == wanted
    assert {"longcat_load_max_over_mean", "longcat_routed_here_per_token", "longcat_bias_changed_choices",
            "zero_expert_choices"} <= wanted
    assert 15 < result["metrics"]["zero_expert_choices"]["value"] < 60
    assert result["metrics"]["longcat_bias_changed_choices"]["value"] > 0  # the drawn bias is not idle
    assert result["metrics"]["kernels_claimed"]["value"] > 0 and result["metrics"]["compiles_in_window"]["value"] == 0
    untraced = result_of(run_cell(CELL, "--rehearse", trace=0))
    assert untraced["correct"] is True and set(untraced["metrics"]) == {"tokens_per_s", "peak_hbm_gb", "setup_s"}


def test_the_lowering_for_a_described_chip_applies_the_dispatchers_own_pass(monkeypatch):
    """``lower_for`` runs ``fold_attention_layouts`` between the trace and the
    claim, which the older forward jobs' leave out (PERF.md section 7): what
    ``perfbench/rehearse.py`` compiles for this cell is the program the cell runs."""
    import inspect

    from thunder_tpu.transforms import attention_layout

    source = inspect.getsource(forward_scmoe.lower_for)
    assert "fold_attention_layouts(dce(comp), executors)" in source and "transform_for_execution" in source
    assert attention_layout.fold_attention_layouts.__name__ in source
