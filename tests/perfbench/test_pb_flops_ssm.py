"""The yardstick's arithmetic for ``granite-4.0-h-micro.fwd-t16k``, by hand: a
layer's matmul weights by kind, the recurrence's, the convolution's and causal
attention's required work, the cell's operations a token and a call as ISSUE 44
reckons them, and what the chunked form performs at a chunk."""

import pytest
from test_pb_flops import job_of

from perfbench import flops, flops_ssm, peaks

CELL = "granite-4.0-h-micro.fwd-t16k"
T = 16384


def test_a_layers_matmul_weights_by_hand():
    k = job_of(CELL).keys
    in_proj, out_proj, mlp = 2048 * (4096 + 4352 + 64), 4096 * 2048, 3 * 2048 * 8192
    assert (in_proj, out_proj, mlp) == (17_432_576, 8_388_608, 50_331_648)
    assert flops_ssm.layer_matmul_params(k, "mamba") == in_proj + out_proj + mlp == 76_152_832       # ISSUE 44: 76.15 M
    qkv, o = 2048 * (32 + 2 * 8) * 64, 2048 * 2048
    assert flops_ssm.layer_matmul_params(k, "attention") == qkv + o + mlp == 60_817_408            # 60.82 M
    # the convolution's 17,408 taps multiply no matrix: the tree's leaves of two dimensions hold them, this count does not
    job = job_of(CELL)
    assert job.matmul_params() - (36 * 76_152_832 + 4 * 60_817_408) == 36 * 4352 * 4


def test_the_mixers_required_work_by_hand():
    ops, nbytes = flops_ssm.ssm_scan(T, 64, 64, 128, 1)
    assert ops == 4.0 * 64 * 128 * 64 * T and round(ops / T / 1e6, 3) == 2.097      # 4 P N a head and position
    assert nbytes == 2.0 * T * (2 * 4096 + 2 * 128 + 64) and round(nbytes / 1e6) == 279  # x and y, B and C, dt: bf16
    least, bound = flops.least_seconds(ops, nbytes, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and round(1e3 * least, 2) == 0.34 and round(36e3 * least, 1) == 12.3  # ISSUE 44: 12.3 ms for 36
    ops, nbytes = flops_ssm.ssm_conv(T, 4352, 4)
    assert ops == 2.0 * 4 * 4352 * T and nbytes == 4.0 * 4352 * T and round(nbytes / 1e6) == 285
    assert flops.least_seconds(ops, nbytes, peaks.peaks_for("TPU v5 lite"))[1] == "memory"
    ops, nbytes = flops_ssm.attention(T, 32, 8, 64)
    assert ops == 4.0 * 32 * 64 * (T * (T + 1) // 2) and round(ops / 1e12, 2) == 1.10  # a layer and sequence
    assert nbytes == 2.0 * (64 + 16) * T * 64
    # two groups move two groups' B and C; a shorter sequence pays in proportion
    assert flops_ssm.ssm_scan(100, 4, 8, 16, 2) == (4.0 * 4 * 8 * 16 * 100, 2.0 * 100 * (2 * 32 + 2 * 32 + 4))


def test_the_cells_operations_a_token_and_a_call_by_hand():
    job = job_of(CELL)
    weights = 36 * 76_152_832 + 4 * 60_817_408
    assert round(weights / 1e9, 3) == 2.985
    head = 2.0 * 100352 * 2048 * 1024 / T
    attention = 4 * 4.0 * 32 * 64 * (T * (T + 1) // 2) / T
    scan = 36 * 4.0 * 64 * 64 * 128
    assert [round(x / 1e6, 1) for x in (head, attention, scan)] == [25.7, 268.5, 75.5]
    want = 2.0 * weights + head + attention + scan
    assert job.flops_per_token() == flops_ssm.forward_flops_per_token(job.keys, T, 1024) == pytest.approx(want, rel=1e-12)
    assert round(want / 1e9, 2) == 6.34 and round(want * T / 1e12) == 104  # ISSUE 44: 6.34 GFLOP a token, 104 TFLOP a call
    assert round(want * T / 197e12, 2) == 0.53  # seconds at the bf16 peak
    # the same counts are what the job hands the roofline readers, a call
    work = job.counters["mixer_work"]
    assert work["ssm_scan"] == [36 * x for x in flops_ssm.ssm_scan(T, 64, 64, 128, 1)]
    assert work["ssm_conv"] == [36 * x for x in flops_ssm.ssm_conv(T, 4352, 4)]
    assert job.tokens_per_unit == T and job.last == 1024 and job.traffic["in_flight"] == 2


@pytest.mark.parametrize("chunk,ratio", [(256, 2.15625), (128, 2.015625), (64, 3.2578125), (16384, 66.0)])
def test_what_the_chunked_form_performs_over_what_the_recurrence_requires(chunk, ratio):
    """A head and position: ``2 L P`` in the masked product, ``2 L N / heads`` of the
    group's ``C B^T``, ``4 N P`` into the summaries and out of the entering states,
    ``2 n N P / L`` in the product over the chunks' summaries; the recurrence needs
    ``4 N P``. One chunk of the whole sequence is the quadratic form."""
    n = -(-T // chunk)
    by_hand = 2 * chunk * 64 + 2 * chunk * 128 / 64 + (4 * 128 * 64 + 2 * n * 128 * 64 / chunk if n > 1 else 0)
    got = flops_ssm.chunked_ops(T, chunk, 64, 64, 128, 1) / flops_ssm.ssm_scan(T, 64, 64, 128, 1)[0]
    assert got == pytest.approx(by_hand / (4 * 128 * 64)) == pytest.approx(ratio)


def test_a_padded_last_chunk_is_paid_for_whole():
    assert flops_ssm.chunked_ops(300, 256, 2, 8, 4, 1) == flops_ssm.chunked_ops(512, 256, 2, 8, 4, 1)
    assert flops_ssm.chunked_ops(200, 256, 2, 8, 4, 1) == 2.0 * 200 * 200 * 4 + 2.0 * 200 * 200 * 8 * 2  # one chunk, no carry
