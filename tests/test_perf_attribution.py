"""Performance-attribution observatory tests (ISSUE 5): cost-model golden
values (matmul 2·m·n·k, SDPA, collective wire bytes, dtype awareness),
roofline classification against device specs, the trace-events attribution
parser round-tripped on the checked-in fixture (≥90% of non-idle device time
attributed with pass provenance), the cost×measured join, the bench
regression gate on synthetic and committed histories, bench.py's
prev-round delta helper, and the new observability satellites (event host
identity + merged replay, the XLA-compile-seconds histogram).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import thunder_tpu as ttpu
import thunder_tpu.clang as clang
import thunder_tpu.monitor as monitor
from thunder_tpu.analysis.cost import (
    DEVICE_SPECS,
    DeviceSpec,
    cost_report,
    resolve_device_spec,
    trace_cost,
)
from thunder_tpu.core import dtypes
from thunder_tpu.observability import metrics as obsm
from thunder_tpu.observability.attribution import (
    Attribution,
    ScopeRef,
    attribute,
    hlo_scope_map,
    join_cost_attribution,
    parse_scope,
    parse_scopes,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO_ROOT, "tests", "fixtures", "gpt_step.trace.json")


def _write_synthetic_history(tmp_path):
    """Five BENCH rounds in the driver's ``{"n", "cmd", "rc", "tail",
    "parsed"}`` wrapper plus an ack file, written under ``tmp_path``: a
    steady series whose only regression is train_xla_compile_s 20.7 -> 43.3
    over r04->r05, acknowledged. Returns (round paths, ack path)."""
    compile_s = [50.0, 48.0, 35.6, 20.7, 43.3]
    step_s = [1.10, 0.95, 0.8045, 0.6678, 0.6681]
    paths = []
    for i, (c, t) in enumerate(zip(compile_s, step_s), start=1):
        parsed = {"metric": "train_iter", "value": t, "unit": "s",
                  "train_mfu": round(0.43 / t, 3), "train_xla_compile_s": c,
                  "fwd_xla_compile_s": 6.2}
        path = tmp_path / f"BENCH_r{i:02d}.json"
        path.write_text(json.dumps({"n": i, "cmd": "python bench.py", "rc": 0,
                                    "tail": json.dumps(parsed), "parsed": parsed}))
        paths.append(str(path))
    ack = tmp_path / "BENCH_ACK.json"
    ack.write_text(json.dumps({"acknowledged": [
        {"transition": "r04->r05", "metric": "train_xla_compile_s",
         "reason": "backend-compile variance of the round host"}]}))
    return paths, str(ack)
SCRIPTS = os.path.join(REPO_ROOT, "scripts")
sys.path.insert(0, SCRIPTS)

from perf_report import (  # noqa: E402
    Regression,
    analyze_history,
    compare_rounds,
    load_ack,
    load_round,
    metric_direction,
    noise_floor,
    run_history_gate,
)


@pytest.fixture(autouse=True)
def _metrics_isolation():
    was = monitor.enabled()
    monitor.disable()
    monitor.reset()
    yield
    monitor.reset()
    (monitor.enable if was else monitor.disable)()


def _extrace(fn, *args):
    from thunder_tpu.api import trace_program
    from thunder_tpu.executors.passes import transform_for_execution
    from thunder_tpu.extend import resolve_executors
    from thunder_tpu.transforms.common import cse, dce

    _, comp = trace_program(fn, args, {})
    return transform_for_execution(cse(dce(comp)), resolve_executors(["jax"]))


# =============================================================================
# Cost model: golden values
# =============================================================================


class TestCostGoldens:
    def test_matmul_2mnk(self):
        m, k, n = 64, 96, 32
        a = np.ones((m, k), np.float32)
        b = np.ones((k, n), np.float32)
        tc = trace_cost(_extrace(lambda a, b: clang.matmul(a, b), a, b), "v5e")
        mm = [r for r in tc.rows if r.kind == "matmul"]
        assert len(mm) == 1
        assert mm[0].flops == 2.0 * m * n * k
        # HBM bytes: both inputs + the output, dtype-aware (f32 = 4B).
        assert mm[0].bytes_moved == 4 * (m * k + k * n + m * n)

    def test_linear_counts_bias(self):
        import thunder_tpu.torch as ttorch

        a = np.ones((8, 16), np.float32)
        w = np.ones((4, 16), np.float32)
        bias = np.ones((4,), np.float32)
        tc = trace_cost(_extrace(lambda a, w, b: ttorch.linear(a, w, b), a, w, bias), "v5e")
        mm = [r for r in tc.rows if r.kind == "matmul"]
        assert len(mm) == 1
        assert mm[0].flops == 2.0 * 8 * 4 * 16 + 8 * 4  # 2·m·n·k + bias adds

    def test_dtype_aware_bytes(self):
        a32 = np.ones((32, 32), np.float32)
        tc32 = trace_cost(_extrace(lambda a: clang.tanh(a), a32), "v5e")
        a16 = a32.astype("bfloat16") if hasattr(np, "bfloat16") else None
        row32 = [r for r in tc32.rows if r.sym == "tanh"][0]
        assert row32.bytes_moved == 2 * 32 * 32 * 4  # in + out, 4B each
        import jax.numpy as jnp

        tc16 = trace_cost(
            _extrace(lambda a: clang.tanh(a), jnp.ones((32, 32), jnp.bfloat16)), "v5e")
        row16 = [r for r in tc16.rows if r.sym == "tanh"][0]
        assert row16.bytes_moved == 2 * 32 * 32 * 2  # bf16 halves the traffic

    def test_sdpa_flops_formula(self):
        import thunder_tpu.torch as ttorch

        B, H, T, D = 2, 4, 128, 64
        q = np.ones((B, H, T, D), np.float32)
        # Cost the acquisition-level composite bsym directly, regardless of
        # which executor would claim the decomposition.
        from thunder_tpu.analysis.cost import bsym_cost
        from thunder_tpu.api import trace_program

        _, comp = trace_program(
            lambda q, k, v: ttorch.scaled_dot_product_attention(q, k, v), (q, q, q), {})
        sdpa = [b for b in comp.bound_symbols
                if str(b.sym.id) == "torch.nn.functional.scaled_dot_product_attention"
                or b.sym.name == "scaled_dot_product_attention"]
        if sdpa:
            c = bsym_cost(sdpa[0])
            if c is not None and c.kind == "sdpa":
                expected = 4.0 * B * H * T * T * D + 5.0 * B * H * T * T
                assert c.flops == expected

    def test_sdpa_claimed_symbol_golden(self):
        # Golden check on the claimed-op rule without tracing: bind the
        # symbol shape-only.
        from thunder_tpu.analysis.cost import bsym_cost
        from thunder_tpu.core.proxies import TensorProxy
        from thunder_tpu.core.symbol import BoundSymbol, Symbol

        B, H, T, D = 2, 8, 256, 64
        mk = lambda nm: TensorProxy(  # noqa: E731
            nm, shape=(B, H, T, D), dtype=dtypes.bfloat16)
        sym = Symbol("scaled_dot_product_attention",
                     id="torch.scaled_dot_product_attention")
        out = TensorProxy("o", shape=(B, H, T, D), dtype=dtypes.bfloat16)
        bsym = BoundSymbol(sym, args=(mk("q"), mk("k"), mk("v")), kwargs={}, output=out)
        c = bsym_cost(bsym)
        assert c.kind == "sdpa"
        assert c.flops == 4.0 * B * H * T * T * D + 5.0 * B * H * T * T
        # flash HBM traffic: q,k,v,out only — never the T×T score matrix.
        assert c.bytes_moved == 4 * B * H * T * D * 2
        causal = BoundSymbol(sym, args=(mk("q2"), mk("k2"), mk("v2")),
                             kwargs={"is_causal": True},
                             output=TensorProxy("o2", shape=(B, H, T, D),
                                                dtype=dtypes.bfloat16))
        c2 = bsym_cost(causal)
        assert c2.flops == pytest.approx(c.flops / 2.0)  # causal halves the scores

    def test_collective_wire_bytes(self):
        from thunder_tpu.analysis.cost import bsym_cost
        from thunder_tpu.core.proxies import TensorProxy
        from thunder_tpu.distributed import prims as dist_prims

        g = 8
        a = TensorProxy("a", shape=(1024,), dtype=dtypes.float32)
        out = TensorProxy("o", shape=(1024,), dtype=dtypes.float32)
        c = bsym_cost(dist_prims.all_reduce.bind(a, "data", g, output=out))
        assert c.kind == "collective"
        nbytes = 1024 * 4
        assert c.comm_bytes == pytest.approx(2.0 * (g - 1) / g * nbytes)  # ring all-reduce
        c_ag = bsym_cost(dist_prims.all_gather.bind(a, "data", g, output=out))
        assert c_ag.comm_bytes == pytest.approx((g - 1) / g * nbytes)

    def test_layout_ops_are_free(self):
        a = np.ones((16, 16), np.float32)
        tc = trace_cost(_extrace(lambda a: clang.reshape(a, (256,)), a), "v5e")
        layout = [r for r in tc.rows if r.kind == "layout"]
        assert all(r.flops == 0 and r.bytes_moved == 0 for r in layout)


# =============================================================================
# Cost model: roofline classification + GPT forward total
# =============================================================================


class TestRoofline:
    def test_big_bf16_matmul_compute_bound_on_v5e(self):
        import jax.numpy as jnp

        n = 2048
        a = jnp.ones((n, n), jnp.bfloat16)
        tc = trace_cost(_extrace(lambda a, b: clang.matmul(a, b), a, a), "v5e")
        mm = [r for r in tc.rows if r.kind == "matmul"][0]
        # AI = 2n³/(3n²·2B) = n/3 ≈ 683 FLOP/B > v5e ridge (197e12/819e9 ≈ 240).
        assert mm.bound == "compute"
        assert mm.intensity > DEVICE_SPECS["v5e"].ridge(None)

    def test_elementwise_memory_bound_everywhere(self):
        a = np.ones((512, 512), np.float32)
        for dev in ("v5e", "v5p", "a100"):
            tc = trace_cost(_extrace(lambda a: clang.tanh(a), a), dev)
            row = [r for r in tc.rows if r.sym == "tanh"][0]
            assert row.bound == "memory"

    def test_gpt_forward_flops_within_5pct_of_analytic(self):
        """Acceptance: total forward FLOPs within 5% of the analytic matmul
        estimate, and the matmuls compute-bound at bench-like shapes."""
        from thunder_tpu.models import gpt as m

        cfg = m.GPTConfig(
            name="cost-test", block_size=512, vocab_size=512, padded_vocab_size=512,
            n_layer=2, n_head=6, n_embd=768, rotary_percentage=1.0,
            intermediate_size=3072)
        params = m.init_params(cfg, dtype=dtypes.bfloat16, seed=0)
        B, T = 4, 512
        idx = np.random.RandomState(0).randint(0, cfg.vocab_size, (B, T)).astype(np.int32)
        tc = cost_report(lambda p, i: m.forward(p, i, cfg), params, idx,
                         executors=["jax"], device="v5e")

        E, I, V, L, H = (cfg.n_embd, cfg.intermediate_size, cfg.padded_vocab_size,
                         cfg.n_layer, cfg.n_head)
        hd = E // H
        qkv_out = cfg.qkv_out  # fused qkv projection width
        analytic = L * (
            2 * B * T * E * qkv_out        # qkv projection
            + 2 * B * T * E * E            # attention output projection
            + 2 * B * T * E * I            # mlp up
            + 2 * B * T * I * E            # mlp down
            + 2 * 2 * B * H * T * T * hd   # QK^T and AV
        ) + 2 * B * T * E * V              # lm head
        assert tc.total_flops == pytest.approx(analytic, rel=0.05)

        # The projection GEMMs clear the v5e bf16 ridge (compute-bound); the
        # decomposed attention-score matmuls materialize T×T and are
        # memory-bound — which is exactly the flash-executor motivation.
        proj = [r for r in tc.rows if r.sym == "linear" and r.flops > 1e8]
        assert proj, "no projection matmuls costed"
        assert all(r.bound == "compute" for r in proj)
        scores = [r for r in tc.rows if r.sym == "matmul" and r.flops > 1e8]
        assert scores and all(r.bound == "memory" for r in scores)

    def test_device_spec_override_and_unknown(self):
        spec = DeviceSpec("lab-chip", {"bf16": 1e15, "f32": 5e14, "int8": 2e15},
                          hbm_bw=4e12, ici_bw=1e12)
        assert resolve_device_spec(spec) is spec
        assert resolve_device_spec("v5p").name == "v5p"
        assert resolve_device_spec("v6e").name == "v6e"
        with pytest.raises(ValueError):
            resolve_device_spec("not-a-chip")

    def test_compute_bound_uses_row_dtype_peak(self):
        import jax.numpy as jnp

        n = 512
        a = jnp.ones((n, n), jnp.bfloat16)
        tc = trace_cost(_extrace(lambda a, b: clang.matmul(a, b), a, a), "v5e")
        # compute_s must be scored at the bf16 peak (197 TF), not f32 —
        # and must never exceed the roofline total it lower-bounds.
        assert tc.compute_s == pytest.approx(
            tc.total_flops / DEVICE_SPECS["v5e"].peak_flops["bf16"], rel=1e-6)
        assert tc.compute_s <= tc.roofline_s + 1e-12


# =============================================================================
# Scope parsing + attribution round-trip on the committed fixture
# =============================================================================


class TestScopeParsing:
    def test_hash_separator(self):
        ref = parse_scope("jit_f/L17.matmul#Transform_for_execution/dot.3")
        assert ref == ScopeRef(17, "matmul", "Transform_for_execution")

    def test_legacy_at_separator(self):
        ref = parse_scope("L3.tanh@Delete_Last_Used")
        assert ref == ScopeRef(3, "tanh", "Delete_Last_Used")

    def test_truncated_scope_keeps_line_drops_pass(self):
        # JAX ate '@<pass>' in PR 3 profiles: line + sym survive.
        ref = parse_scope("jit_f/jit_main/L5.linear/dot.1")
        assert ref == ScopeRef(5, "linear", None)

    def test_dotted_symbol_names(self):
        ref = parse_scope("L9.torch.sdpa_fwd_res#Transform_for_execution/custom-call")
        assert ref == ScopeRef(9, "torch.sdpa_fwd_res", "Transform_for_execution")

    def test_multiple_scopes_in_fused_name(self):
        refs = parse_scopes(
            "fusion jit/L1.mul#P/multiply jit/L2.add#P/add")
        assert {(r.line, r.sym) for r in refs} == {(1, "mul"), (2, "add")}

    def test_no_scope(self):
        assert parse_scope("fusion.123") is None
        assert parse_scope("") is None

    def test_truncated_scope_survives_event_args(self, tmp_path):
        # A PR 3-era truncated name ends the event NAME; the args dict must
        # not break the end-of-string anchor of the bare-scope regex.
        doc = {"traceEvents": [
            {"ph": "M", "pid": 1, "name": "process_name", "args": {"name": "/device:TPU:0"}},
            {"ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 50.0,
             "name": "jit_f/L3.tanh", "args": {"hlo_op": "tanh.2"}},
        ]}
        p = tmp_path / "t.trace.json"
        p.write_text(json.dumps(doc))
        attr = attribute(str(p))
        assert attr.by_line[ScopeRef(3, "tanh", None)] == pytest.approx(50.0)


class TestAttributionFixture:
    def test_roundtrip_coverage_and_provenance(self):
        attr = attribute(FIXTURE)
        # Non-idle device time: 1000us; idle excluded; host python excluded.
        assert attr.device_busy_us == pytest.approx(1000.0)
        assert attr.idle_us == pytest.approx(500.0)
        # Acceptance: ≥90% of non-idle device time attributed to named lines.
        assert attr.coverage >= 0.90
        # Pass provenance rides along for everything but the truncated L30.
        assert attr.with_provenance_us == pytest.approx(910.0)

    def test_per_line_aggregation(self):
        attr = attribute(FIXTURE)
        by_label = {ref.label: us for ref, us in attr.by_line.items()}
        assert by_label["L12.linear#Transform_for_execution"] == pytest.approx(400.0)
        assert by_label[
            "L17.torch.scaled_dot_product_attention#Transform_for_execution"
        ] == pytest.approx(250.0)
        assert by_label["L23.add#Delete_Last_Used"] == pytest.approx(80.0)
        assert by_label["L30.sum"] == pytest.approx(40.0)
        # The fused row splits evenly across its two member scopes.
        assert by_label["L40.mul#Transform_for_execution"] == pytest.approx(90.0)
        assert by_label["L41.tanh#Transform_for_execution"] == pytest.approx(90.0)
        assert "fusion.9" in attr.fusions
        us, members = attr.fusions["fusion.9"]
        assert us == pytest.approx(180.0) and len(members) == 2

    def test_unattributed_named(self):
        attr = attribute(FIXTURE)
        assert attr.unattributed["custom-call.7"] == pytest.approx(30.0)
        assert attr.unattributed["copy.3"] == pytest.approx(20.0)

    def test_by_pass_rollup(self):
        attr = attribute(FIXTURE)
        assert attr.by_pass["Transform_for_execution"] == pytest.approx(400 + 250 + 180)
        assert attr.by_pass["Delete_Last_Used"] == pytest.approx(80.0)

    def test_top_ordering_and_format(self):
        attr = attribute(FIXTURE)
        top = attr.top(3)
        assert top[0][0].sym == "linear" and top[0][1] == pytest.approx(400.0)
        text = attr.format()
        assert "L12.linear" in text and "%" in text


class TestSelfTimeNesting:
    def test_wrapper_events_charged_self_time_only(self, tmp_path):
        # A 'call' wrapper (CPU plugin) containing a 90us child must
        # contribute 10us self, not 100us — no double counting.
        doc = {"traceEvents": [
            {"ph": "M", "pid": 1, "name": "process_name", "args": {"name": "/device:TPU:0"}},
            {"ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 100.0, "name": "call",
             "args": {"hlo_op": "call"}},
            {"ph": "X", "pid": 1, "tid": 1, "ts": 5.0, "dur": 90.0,
             "name": "jit_f/L0.matmul#P/dot.1", "args": {"hlo_op": "dot.1"}},
        ]}
        p = tmp_path / "t.trace.json"
        p.write_text(json.dumps(doc))
        attr = attribute(str(p))
        assert attr.device_busy_us == pytest.approx(100.0)
        assert attr.by_line[ScopeRef(0, "matmul", "P")] == pytest.approx(90.0)
        assert attr.unattributed["call"] == pytest.approx(10.0)


class TestHloScopeMap:
    def test_maps_hlo_ops_to_scopes(self):
        hlo = '''
HloModule jit_f
%dot.3 = f32[256,256]{1,0} dot(f32[256,256]{1,0} %a, f32[256,256]{1,0} %b), metadata={op_name="jit(f)/jit(main)/L0.matmul#Transform_for_execution/dot_general" source_file="<string>"}
%tanh.4 = f32[256,256]{1,0} tanh(f32[256,256]{1,0} %dot.3), metadata={op_name="jit(f)/jit(main)/L2.tanh#Transform_for_execution/tanh"}
%add.9 = f32[] add(f32[] %x, f32[] %y), metadata={op_name="jit(f)/unrelated"}
'''
        mapping = hlo_scope_map(hlo)
        assert parse_scope(mapping["dot.3"]) == ScopeRef(0, "matmul", "Transform_for_execution")
        assert parse_scope(mapping["tanh.4"]) == ScopeRef(2, "tanh", "Transform_for_execution")
        assert "add.9" not in mapping  # no scope in its metadata

    def test_attribute_joins_via_hlo_map(self, tmp_path):
        doc = {"traceEvents": [
            {"ph": "M", "pid": 1, "name": "process_name", "args": {"name": "/device:TPU:0"}},
            {"ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 70.0, "name": "dot.3",
             "args": {"hlo_op": "dot.3"}},
        ]}
        p = tmp_path / "t.trace.json"
        p.write_text(json.dumps(doc))
        attr = attribute(str(p), extra_scope_map={"dot.3": "jit(f)/L0.matmul#P/dot"})
        assert attr.by_line[ScopeRef(0, "matmul", "P")] == pytest.approx(70.0)
        assert attr.coverage == pytest.approx(1.0)


# =============================================================================
# Cost × measured join
# =============================================================================


class TestJoin:
    def test_join_matches_lines_and_scales_steps(self):
        a = np.ones((64, 64), np.float32)
        extrace = _extrace(lambda a, b: clang.sum(clang.tanh(clang.matmul(a, b))), a, a)
        cost = trace_cost(extrace, "v5e")
        mm_row = [r for r in cost.rows if r.kind == "matmul"][0]
        attr = Attribution(
            by_line={ScopeRef(mm_row.index, mm_row.sym, "Transform_for_execution"): 300.0},
            device_busy_us=300.0,
        )
        join = join_cost_attribution(attr, cost, steps=3)
        assert join.measured_step_us == pytest.approx(100.0)
        row = join.rows[0]
        assert row.measured_us == pytest.approx(100.0)
        assert row.bound == mm_row.bound
        assert row.roofline_us == pytest.approx(mm_row.roofline_s * 1e6)
        assert 0 < row.efficiency <= 1.0
        assert join.mfu == pytest.approx(cost.mfu_at(100e-6))
        assert "perf attribution" in join.format()

    def test_monitor_attribution_report_on_fixture(self):
        rep = monitor.attribution_report(FIXTURE, steps=1)
        assert rep.attribution.coverage >= 0.90
        assert "L12.linear" in rep.format()


# =============================================================================
# Regression gate
# =============================================================================


class TestRegressionGate:
    def test_direction_inference(self):
        assert metric_direction("train_xla_compile_s") == -1
        assert metric_direction("train_mfu") == 1
        assert metric_direction("train_synced_mfu_vs_ref_mfu") == 1  # not a time
        assert metric_direction("fwd_vs_baseline") == 1
        assert metric_direction("tokens_per_sec") == 1
        assert metric_direction("value") == -1
        assert metric_direction("recompile_count") == -1
        assert metric_direction("timing_protocol") is None

    def test_flags_lower_better_regression(self):
        rounds = [("r01", {"step_s": 1.0}), ("r02", {"step_s": 1.5})]
        regs = analyze_history(rounds)
        assert len(regs) == 1 and regs[0].metric == "step_s" and not regs[0].acked

    def test_flags_higher_better_drop(self):
        rounds = [("r01", {"train_mfu": 0.60}), ("r02", {"train_mfu": 0.50})]
        regs = analyze_history(rounds)
        assert len(regs) == 1 and regs[0].pct < 0

    def test_improvement_not_flagged(self):
        rounds = [("r01", {"step_s": 1.5, "train_mfu": 0.5}),
                  ("r02", {"step_s": 1.0, "train_mfu": 0.6})]
        assert analyze_history(rounds) == []

    def test_noise_floor_suppresses_small_absolute_jitter(self):
        # +50% on a 0.2s trace timing is jitter, not a regression.
        rounds = [("r01", {"fwd_trace_claim_s": 0.2}), ("r02", {"fwd_trace_claim_s": 0.3})]
        assert analyze_history(rounds) == []
        assert noise_floor("fwd_trace_claim_s") == 1.0

    def test_ack_downgrades(self):
        rounds = [("r04", {"train_xla_compile_s": 20.7}),
                  ("r05", {"train_xla_compile_s": 43.3})]
        regs = analyze_history(
            rounds, ack={"r04->r05:train_xla_compile_s": "known"})
        assert len(regs) == 1 and regs[0].acked and regs[0].reason == "known"

    def test_headline_skipped_when_workload_changed(self):
        rounds = [
            ("r01", {"value": 1.27, "vs_baseline": 1.0, "_metric_name": "fwd"}),
            ("r02", {"value": 0.98, "vs_baseline": 0.5, "_metric_name": "train"}),
        ]
        assert analyze_history(rounds) == []

    def test_history_flags_r4_r5_compile_jump(self, tmp_path):
        """Acceptance: an r4→r5 train_xla_compile_s 20.7→43.3 regression is
        flagged on a five-round BENCH history (synthetic rounds in the
        driver's wrapper format; the committed rounds it was first seen on
        were taken through a backend that no longer exists and are gone)."""
        paths, ack_path = _write_synthetic_history(tmp_path)
        assert len(paths) == 5
        rounds = [load_round(p) for p in paths]
        regs = analyze_history(rounds)  # no ack: the raw flag must fire
        hits = [r for r in regs
                if r.metric == "train_xla_compile_s" and (r.frm, r.to) == ("r04", "r05")]
        assert len(hits) == 1
        assert hits[0].prev == pytest.approx(20.7) and hits[0].cur == pytest.approx(43.3)
        # ... and the ack file covers exactly it, so the CI gate stays green
        # on history while failing on anything new.
        acked = analyze_history(rounds, ack=load_ack(ack_path))
        assert acked and all(r.acked for r in acked)

    def test_gate_exit_codes(self, tmp_path, capsys):
        r1 = tmp_path / "BENCH_r01.json"
        r2 = tmp_path / "BENCH_r02.json"
        r1.write_text(json.dumps({"parsed": {"metric": "m", "step_s": 1.0}}))
        r2.write_text(json.dumps({"parsed": {"metric": "m", "step_s": 2.0}}))
        ack = tmp_path / "BENCH_ACK.json"
        assert run_history_gate([str(r1), str(r2)], gate=True,
                                ack_path=str(ack)) == 1
        ack.write_text(json.dumps({"acknowledged": [
            {"transition": "r01->r02", "metric": "step_s", "reason": "deliberate"}]}))
        assert run_history_gate([str(r1), str(r2)], gate=True,
                                ack_path=str(ack)) == 0
        capsys.readouterr()

    def test_compare_rounds_for_bench(self):
        prev = {"train_xla_compile_s": 20.0, "train_mfu": 0.6, "_metric_name": "m"}
        cur = {"train_xla_compile_s": 45.0, "train_mfu": 0.61, "_metric_name": "m"}
        deltas, regs = compare_rounds(prev, cur)
        assert deltas["train_xla_compile_s"] == pytest.approx(1.25)
        assert len(regs) == 1 and "train_xla_compile_s" in regs[0]


# =============================================================================
# Satellites: event host identity + merged replay; XLA compile histogram
# =============================================================================


class TestEventHostIdentity:
    def test_every_event_carries_pid_and_host(self, tmp_path):
        from thunder_tpu.observability import events as obs_events

        log = str(tmp_path / "ev.jsonl")
        jf = ttpu.jit(lambda x: clang.sum(clang.tanh(x)), executors=["jax"], events=log)
        jf(np.ones((2, 4), np.float32))
        recs = [json.loads(l) for l in open(log) if l.strip()]
        assert recs
        for r in recs:
            assert r["pid"] == os.getpid()
            assert isinstance(r["host"], int)

    def test_merged_replay_stable_order_and_scoped_cids(self, tmp_path):
        from thunder_tpu.analysis.events import merge_event_logs, replay_events

        log0 = str(tmp_path / "h0.jsonl")
        jf = ttpu.jit(lambda x: clang.sum(clang.tanh(x)), executors=["jax"], events=log0)
        jf(np.ones((2, 4), np.float32))
        recs = [json.loads(l) for l in open(log0) if l.strip()]
        log1 = str(tmp_path / "h1.jsonl")
        with open(log1, "w") as f:
            for r in recs:
                r2 = dict(r)
                r2["host"] = 1
                f.write(json.dumps(r2) + "\n")

        merged, diags = merge_event_logs([log1, log0])  # input order irrelevant
        assert not diags and len(merged) == 2 * len(recs)
        keys = [(r["ts"], r["host"], r["pid"], r["seq"]) for r in merged]
        assert keys == sorted(keys)
        # Same merge from the other input order: identical stream.
        merged2, _ = merge_event_logs([log0, log1])
        assert merged == merged2

        # A malformed (non-numeric ts) record must become a diagnostic in the
        # merge path, not a ValueError from the sort key.
        log_bad = str(tmp_path / "bad.jsonl")
        with open(log_bad, "w") as f:
            f.write(json.dumps({"v": 1, "ts": "bogus", "seq": 0, "kind": "sharp_edge",
                                "message": "m", "policy": "warn"}) + "\n")
        merged_bad, bad_diags = merge_event_logs([log0, log_bad])
        assert len(merged_bad) == len(recs) + 1 and not bad_diags

        summary, rdiags = replay_events([log0, log1])
        # compile_ids are per-process: the two hosts' compiles must not be
        # conflated (no unclosed-compile/storm false positives).
        assert not [d for d in rdiags if d.rule != "events.unknown-kind"]
        assert summary["lines"] == 2 * len(recs)
        assert any(k.startswith("h0:") for k in summary["compiles_by_fn"])
        assert any(k.startswith("h1:") for k in summary["compiles_by_fn"])

    def test_lint_traces_cli_merges_multiple_logs(self, tmp_path):
        log0 = str(tmp_path / "h0.jsonl")
        jf = ttpu.jit(lambda x: clang.tanh(x), executors=["jax"], events=log0)
        jf(np.ones((2,), np.float32))
        log1 = str(tmp_path / "h1.jsonl")
        recs = [json.loads(l) for l in open(log0) if l.strip()]
        with open(log1, "w") as f:
            for r in recs:
                r["host"] = 1
                f.write(json.dumps(r) + "\n")
        out = subprocess.run(
            [sys.executable, os.path.join(SCRIPTS, "lint_traces.py"),
             "--events", log0, log1],
            capture_output=True, text=True, timeout=300,
        )
        assert out.returncode == 0, out.stdout + out.stderr
        assert f"{len(recs) * 2} records" in out.stdout


class TestXlaCompileHistogram:
    def test_first_run_observed_per_class(self):
        monitor.enable()
        jf = ttpu.jit(lambda x: clang.tanh(x), executors=["jax"])
        jf(np.ones((4,), np.float32))
        s = obsm.XLA_COMPILE_S.summary(cls="exact")
        assert s is not None and s["count"] == 1 and s["sum"] > 0

    def test_bucketed_class(self):
        monitor.enable()
        jf = ttpu.jit(lambda x: clang.sum(clang.tanh(x)), cache="symbolic values",
                      executors=["jax"], symbolic_dims={0: (0,)})
        jf(np.ones((3, 8), np.float32))
        s = obsm.XLA_COMPILE_S.summary(cls="bucketed")
        assert s is not None and s["count"] >= 1

    def test_disabled_records_nothing(self):
        jf = ttpu.jit(lambda x: clang.tanh(x), executors=["jax"])
        jf(np.ones((4,), np.float32))
        assert obsm.XLA_COMPILE_S.summary(cls="exact") is None


# =============================================================================
# Live profile round-trip (profiler plugin permitting)
# =============================================================================


class TestLiveProfileAttribution:
    def test_live_cpu_profile_attributes_with_hlo_join(self, tmp_path, monkeypatch):
        monkeypatch.setenv("THUNDER_TPU_ANNOTATE_TRACES", "1")
        import jax

        def f(x, w):
            return clang.sum(clang.tanh(clang.matmul(x, w)))

        jf = ttpu.jit(f, executors=["jax"])
        x = np.ones((128, 128), np.float32)
        jf(x, x)
        res = ttpu.profile(jf, x, x, trace_dir=str(tmp_path / "prof"),
                           steps=2, warmup=1)
        if not res["profiler"]:
            pytest.skip("no profiler plugin on this backend")
        extrace = jf._lc_cs.last_traces[-1]
        hlo = jax.jit(extrace.python_callable()).lower(x, x).compile().as_text()
        assert hlo_scope_map(hlo), "annotated codegen left no scopes in HLO metadata"
        attr = attribute(str(tmp_path / "prof"), hlo_text=hlo)
        assert attr.by_line, "no device time attributed on live profile"
        assert any(ref.sym == "matmul" for ref in attr.by_line)
        assert all(ref.pass_name for ref in attr.by_line)


# =============================================================================
# perf_report CLI
# =============================================================================


class TestPerfReportCli:
    def test_history_cli_on_synthetic_rounds(self, tmp_path):
        paths, ack_path = _write_synthetic_history(tmp_path)
        out = subprocess.run(
            [sys.executable, os.path.join(SCRIPTS, "perf_report.py"),
             "--history", *paths, "--gate", "--ack", ack_path],
            capture_output=True, text=True, timeout=120, cwd=REPO_ROOT,
        )
        assert out.returncode == 0, out.stdout + out.stderr
        assert "train_xla_compile_s" in out.stdout
        assert "acked: train_xla_compile_s 20.7 -> 43.3" in out.stdout

    def test_trace_dir_cli_on_fixture(self):
        out = subprocess.run(
            [sys.executable, os.path.join(SCRIPTS, "perf_report.py"),
             "--trace-dir", FIXTURE, "--steps", "1"],
            capture_output=True, text=True, timeout=300, cwd=REPO_ROOT,
        )
        assert out.returncode == 0, out.stdout + out.stderr
        assert "L12.linear" in out.stdout
