"""The yardstick's arithmetic for latent attention and routed experts, against
numbers worked by hand: A.X-K1's operations a token on one chip's share, the
attention call's and the grouped matmuls' operations and bytes, and which
family the order of the family files sends an attention call to."""

import pytest
from test_pb_flops import KERNELS, _TAIL, job_of

from perfbench import flops, flops_mla_moe, kernel_families, peaks


def test_axk1_by_hand():
    job = job_of("a.x-k1.fwd")
    keys = job.keys
    # attention: q_a 7168*1536 + q_b 1536*12288 + kv_a 7168*576 + kv_b 512*16384 + o 8192*7168
    assert flops_mla_moe.attention_matmul_params(keys) == 101_122_048
    assert flops_mla_moe.expert_params(keys) == 3 * 7168 * 2048 == 44_040_192
    # 8 a token over 192 experts, 12 held: half an expert a token here
    assert flops_mla_moe.routed_here_per_token(keys) == 0.5
    # outside the routed experts: 7 attentions, the dense MLP 3*7168*18432, per expert layer the
    # router 7168*192 and the shared expert, the head 20480*7168; the embedding is a gather
    outside = 7 * 101_122_048 + 396_361_728 + 6 * (1_376_256 + 44_040_192) + 146_800_640
    assert outside == 1_523_515_392
    # attention proper: 7 layers * T=4096 * 64 heads * (192 + 128), causal
    assert job.flops_per_token() == 2 * outside + 2 * 6 * 0.5 * 44_040_192 + 7 * 4096 * 64 * 320 == 3_898_474_496
    # a call of 8192 tokens: 31.9 TFLOP
    assert job.tokens_per_unit * job.flops_per_token() == pytest.approx(31.936e12, rel=1e-4)
    # the inherited count would take all 12 held experts for every token
    assert 2 * job.matmul_params() - 2 * outside == 2 * 6 * 12 * 44_040_192


def test_kernel_costs_by_hand():
    # q, k (2, 64, 4096, 192), v (2, 64, 4096, 128): 128 head-sequences, QK^T at 192 and PV at 128
    # over half of 4096^2; q, k read at 192, v read and o written at 128, one float32 a row
    ops, nbytes = flops_mla_moe.attn_mla_fwd([2, 64, 4096, 192], [2, 64, 4096, 128])
    assert ops == 128 * 4096 * 4096 * (192 + 128) == 687_194_767_360
    assert nbytes == 128 * 4096 * (2 * 192 + 2 * 128) * 2 + 128 * 4096 * 4
    # what flash_fwd's count of the same call would be: the value matmul at the query's width
    assert flops.flash_fwd([2, 64, 4096, 192])[0] == pytest.approx(1.2 * ops)
    # one expert layer, rows as counted: 2 * rows * 3 * 7168 * 2048, never the buffer's 65536 rows
    rows = [700, 0, 341, 341, 100, 900, 341, 341, 341, 341, 200, 150]
    ops, nbytes = flops_mla_moe.experts(rows, 7168, 2048)
    assert ops == 2.0 * 4096 * 44_040_192
    assert nbytes == 2.0 * (11 * 44_040_192 + 4096 * (3 * 7168 + 3 * 2048))  # the expert with no row is not read
    seconds, bound = flops.least_seconds(ops, nbytes, peaks.peaks_for("TPU v5 lite"))
    assert bound == "compute" and seconds == pytest.approx(1.8313e-3, rel=1e-3)


MLA_FWD = ("%splash_mha_fwd_residuals.5 = (f32[2,64,4096,128]{3,2,1,0:T(8,128)}, bf16[2,64,4096,128]{3,2,1,0:T(8,128)(2,1)}) "
           "custom-call(s8[1,4,4]{2,1,0:T(4,128)(4,1)S(1)} %copy-done.9, s8[1,4,4]{2,1,0} %copy-done.8, "
           "bf16[2,64,4096,192]{3,2,1,0:T(8,128)(2,1)} %multiply_fusion.3, bf16[2,64,4096,192]{3,2,1,0} %concatenate.2, "
           "bf16[2,64,4096,128]{3,2,1,0:T(8,128)(2,1)} %slice.9)" + _TAIL)


def test_the_family_order_sends_a_call_with_narrower_values_to_attn_mla_fwd_and_the_rest_to_flash_fwd():
    hit = kernel_families.match(MLA_FWD)
    assert hit[0] == "attn_mla_fwd"
    assert hit[1:] == flops_mla_moe.attn_mla_fwd([2, 64, 4096, 192], [2, 64, 4096, 128])
    # q, k and v of one shape (128/128, 64/64): nothing of this family, on to flash_fwd as before
    same = MLA_FWD.replace("4096,192]", "4096,128]")
    assert kernel_families.match(same)[0] == "flash_fwd"
    assert kernel_families.match(KERNELS["flash_fwd"])[0] == "flash_fwd"
    assert kernel_families.match(KERNELS["flash_bwd"])[0] == "flash_bwd"
    names = [f[0] for f in kernel_families._families()]
    assert names.index("attn_mla_fwd") < names.index("flash_fwd")


def test_the_grouped_matmuls_are_found_by_name_and_belong_to_no_shape_only_family():
    from perfbench.layer_metrics import _experts

    ragged = ("%ragged-dot-none.4 = bf16[65536,2048]{1,0:T(8,128)(2,1)} custom-call(s32[1]{0} %gte, s32[13]{0} %gte.1, "
              "bf16[65536,7168]{1,0} %x, bf16[12,7168,2048]{2,1,0} %w)" + _TAIL)
    assert kernel_families.match(ragged) is None  # its required work follows counted rows, not its shapes
    for name, grouped in (("ragged-dot-none.4", True), ("ragged-dot-metadata.1", True), ("gmm.3", True),
                          ("splash_mha_fwd_residuals.5", False), ("fusion.12", False)):
        assert bool(_experts._GROUPED.match(name)) is grouped
