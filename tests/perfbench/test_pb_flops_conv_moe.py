"""The yardstick's arithmetic for a model that mixes by gated short
convolutions or grouped-query attention and routes to experts that are all
held: LFM2-8B-A1B's operations a token at the depth the cell runs, against
numbers worked by hand, and the grouped matmuls' least time at its widths."""

import pytest
from test_pb_flops import job_of

from perfbench import flops, flops_conv_moe, flops_mla_moe, peaks


def test_lfm2_by_hand():
    job = job_of("lfm2-8b-a1b.fwd")
    keys = job.keys
    # a conv mixer: in_proj 2048*6144 + out_proj 2048*2048; an attention mixer: qkv 2048*(32+8+8)*64 + out 2048*2048
    assert flops_conv_moe.conv_mixer_params(keys) == 16_777_216
    assert flops_conv_moe.attention_params(keys) == 6_291_456 + 4_194_304 == 10_485_760
    assert flops_mla_moe.expert_params(keys) == 3 * 2048 * 1792 == 11_010_048
    # depth 14: 11 conv and 3 attention mixers, 2 dense MLPs of 3*2048*7168, 12 expert layers each the
    # router 32*2048 and 4 experts (every expert is held: 4 a token, exactly), the head 65536*2048 (the
    # embedding table once more); the embedding itself is a gather
    weights = 11 * 16_777_216 + 3 * 10_485_760 + 2 * 44_040_192 + 12 * (65_536 + 4 * 11_010_048) + 134_217_728
    assert weights == 967_573_504
    # attention proper, causal: 3 layers * T=4096 * 32 heads * 2 * 64; the taps: 11 layers * 2048 channels * 3 * 2
    assert job.flops_per_token() == 2 * weights + 3 * 4096 * 32 * 128 + 11 * 2048 * 6 == 1_985_613_824
    assert job.flops_per_token() == pytest.approx(1.98e9, rel=5e-3)  # ISSUE 31's 1.98 GFLOP a token
    # a call of 8192 tokens: 16.3 TFLOP, 82.6 ms at the v5e's 197 TFLOP/s
    assert job.tokens_per_unit * job.flops_per_token() == pytest.approx(16.266e12, rel=1e-4)
    assert job.tokens_per_unit * job.flops_per_token() / peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == pytest.approx(
        82.57e-3, rel=1e-3)
    # the routed experts are 53% of it and the head 13.5%, where the whole model's 24 layers give the head 8%
    assert 2 * 12 * 4 * 11_010_048 / job.flops_per_token() == pytest.approx(0.532, abs=1e-3)
    assert 2 * 134_217_728 / job.flops_per_token() == pytest.approx(0.135, abs=1e-3)
    whole = flops_conv_moe.forward_flops_per_token({**keys, "num_hidden_layers": 24}, 4096)
    assert 2 * 134_217_728 / whole == pytest.approx(0.083, abs=2e-3)
    # the count the dense cells inherit would take all 32 experts for every token and the 11 filters
    # (2048, 3) for matrices, and would miss the head: a tied head has no leaf beside the gathered table
    assert job.matmul_params() - (weights - 134_217_728) == 12 * 28 * 11_010_048 + 11 * 2048 * 3


def test_the_count_follows_the_layer_pattern_and_the_depth():
    keys = job_of("lfm2-8b-a1b.fwd").keys
    at = lambda depth, seq=4096: flops_conv_moe.forward_flops_per_token({**keys, "num_hidden_layers": depth}, seq)
    # layer 2 is attention with experts, layer 3 a convolution with experts
    experts = 2 * (65_536 + 4 * 11_010_048)
    assert at(3) - at(2) == 2 * 10_485_760 + 4096 * 32 * 128 + experts
    assert at(4) - at(3) == 2 * 16_777_216 + 2048 * 6 + experts
    assert at(14, 2048) == at(14) - 3 * 2048 * 32 * 128  # only attention proper sees the length
    with pytest.raises(AssertionError):
        flops_conv_moe.forward_flops_per_token({**keys, "layer_types": ["conv", "windowed"] * 7}, 4096)


def test_the_grouped_matmuls_least_time_at_1792_wide_experts():
    # one expert layer of the cell, even: 32 experts of 1,024 rows, 2 * 32768 * 3 * 2048 * 1792
    ops, nbytes = flops_mla_moe.experts([1024] * 32, 2048, 1792)
    assert ops == 2.0 * 32768 * 11_010_048 == 721_554_505_728
    assert nbytes == 2.0 * (32 * 11_010_048 + 32768 * (3 * 2048 + 3 * 1792))
    seconds, bound = flops.least_seconds(ops, nbytes, peaks.peaks_for("TPU v5 lite"))
    assert bound == "compute" and seconds == pytest.approx(3.6627e-3, rel=1e-3)
    # an expert without a row is not read; the operations follow the rows, however they are shared
    skewed = [8192, 0] + [24576 // 30] * 29 + [24576 - 29 * (24576 // 30)]
    assert sum(skewed) == 32768
    assert flops_mla_moe.experts(skewed, 2048, 1792)[0] == ops
    assert flops_mla_moe.experts(skewed, 2048, 1792)[1] == nbytes - 2.0 * 11_010_048
