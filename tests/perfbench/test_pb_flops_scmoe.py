"""The yardstick's arithmetic for ``longcat-flash-omni.fwd-t16k``, by hand: a
double layer's required operations a token by part as ISSUE 40 reckons them,
the cell's operations a call, the even shares of the router's 768 outputs, and
the families that take the cell's Mosaic calls."""

import pytest
from test_pb_flops import job_of

from perfbench import flops, flops_mla_moe, flops_scmoe, kernel_families, peaks

CELL = "longcat-flash-omni.fwd-t16k"
T = 16384
_TAIL = ', custom_call_target="tpu_custom_call", operand_layout_constraints={}'
# The latent-attention call of the cell's own compiled program (a described v5e, PR 40), its operands typed as the
# device trace names an event: q and k 192 wide, v 128.
MLA_CALL = ("%splash_mha_fwd_no_residuals.8 = (f32[1024,128]{1,0:T(8,128)}, f32[1024,128]{1,0:T(8,128)}, f32[1024,128]{1,0:T(8,128)}, "
            "bf16[64,16384,128]{2,1,0:T(8,128)(2,1)}) custom-call(s8[1,16,16]{2,1,0:T(8,128)(4,1)S(1)} %copy-done.475, "
            "s8[1,16,16]{2,1,0} %copy-done.483, bf16[64,16384,192]{2,1,0:T(8,128)(2,1)} %bitcast.1, "
            "bf16[64,16384,192]{2,1,0} %maximum_bitcast_fusion, bf16[64,16384,128]{2,1,0} %bitcast.3, s32[16384,128]{1,0} %iota.15)" + _TAIL)


def test_a_double_layers_operations_a_token_by_hand():
    """ISSUE 40: MLA projections 362 M, causal scores at T = 16,384 671 M, dense
    FFNs 906 M, router 9 M, experts here 19 M: 1.97 G a token and layer."""
    k = job_of(CELL).keys
    mla = 6144 * 1536 + 1536 * 64 * 192 + 6144 * (512 + 64) + 512 * 64 * 256 + 64 * 128 * 6144
    assert flops_scmoe.attention_matmul_params(k) == mla == 90_570_752  # q_a 9.44, q_b 18.87, kv_a 3.54, kv_b 8.39, o 50.33
    assert flops_scmoe.expert_params(k) == 3 * 6144 * 2048 == 37_748_736 and flops_scmoe.router_outputs(k) == 768
    assert flops_scmoe.routed_here_per_token(k) == 12 * 16 / 768 == 0.25
    parts = flops_scmoe.layer_flops_per_token(k, T)
    assert parts == {"projections": 4.0 * mla, "scores": 2.0 * T * 64 * (192 + 128), "dense_ffns": 4.0 * 3 * 6144 * 12288,
                     "router": 2.0 * 768 * 6144, "experts": 2.0 * 0.25 * 37_748_736, "zero": 2.0 * 6144}
    assert {n: round(v / 1e6) for n, v in parts.items()} == {"projections": 362, "scores": 671, "dense_ffns": 906, "router": 9,
                                                              "experts": 19, "zero": 0}
    assert round(sum(parts.values()) / 1e9, 2) == 1.97


def test_the_cells_operations_a_call_by_hand():
    job = job_of(CELL)
    layer = sum(flops_scmoe.layer_flops_per_token(job.keys, T).values())
    want = 4 * layer + 2.0 * 16384 * 6144 * 1024 / T  # the head on the last 1,024 positions
    assert job.flops_per_token() == flops_scmoe.forward_flops_per_token(job.keys, T, 1024) == pytest.approx(want)
    assert round(want * T / 1e12) == 129  # ISSUE 40: 129 TFLOP a call
    assert round(1.0 * T * T * 64 * 320 / 1e12, 1) == 5.5  # two calls of 5.5 TFLOP a layer
    # a sequence a quarter as long pays a quarter of the scores and all of the rest
    short = flops_scmoe.forward_flops_per_token(job.keys, T // 4, 1024)
    assert short == pytest.approx(want - 4 * 0.75 * 2.0 * T * 64 * 320 + 3 * 2.0 * 16384 * 6144 * 1024 / T)
    # the experts count by the even share, whatever is held: all 512 held is 8 of 12 a token
    whole = {**job.keys, "n_routed_experts": 512}
    assert flops_scmoe.routed_here_per_token(whole) == 8.0


def test_the_families_of_the_cells_mosaic_calls():
    hit = kernel_families.match(MLA_CALL)
    assert hit[0] == "attn_mla_fwd" and hit[1:] == flops_mla_moe.attn_mla_fwd([64, T, 192], [64, T, 128])
    assert hit[1] == 1.0 * 64 * T * T * 320  # 5.5 TFLOP: the causal half of both matmuls
    least, bound = flops.least_seconds(hit[1], hit[2], peaks.peaks_for("TPU v5 lite"))
    assert bound == "compute" and 8 * least == pytest.approx(0.2233, rel=2e-3)  # ISSUE 40: 223 ms least in 8 calls
    # the grouped matmuls at the even 256 rows an expert: memory and compute bound alike, 1.5 ms a layer
    ops, nbytes = flops_mla_moe.experts([256] * 16, 6144, 2048)
    assert ops == 2.0 * 4096 * 3 * 6144 * 2048 and nbytes == 2.0 * (16 * 37_748_736 + 4096 * (3 * 6144 + 3 * 2048))
    least, bound = flops.least_seconds(ops, nbytes, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and round(1e3 * least, 2) == 1.72 and round(1e3 * ops / 197e12, 2) == 1.57
