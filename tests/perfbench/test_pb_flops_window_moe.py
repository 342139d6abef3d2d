"""The yardstick's arithmetic for ``trinity-mini.fwd-t32k``, by hand: the pairs
a window keeps, each attention kind's operations and bytes, the cell's
operations a token as ISSUE 38 reckons them, and the kernel family that takes a
window call and leaves a causal call to ``flash_fwd``."""

import pytest
from test_pb_flops import job_of

from perfbench import flops, flops_window_moe, kernel_families, peaks

CELL = "trinity-mini.fwd-t32k"
_TAIL = ', custom_call_target="tpu_custom_call", operand_layout_constraints={}'
# The two calls of the cell's own compiled program (a described v5e, PR 38; `/root/scratch/aot.py`), their operands
# typed as the device trace names an event: the first is the mask's table, s8[1, query tiles, key tiles visited].
WINDOW_CALL = ("%splash_mha_fwd_no_residuals.7 = (f32[1024,128]{1,0:T(8,128)}, f32[1024,128]{1,0:T(8,128)}, f32[1024,128]{1,0:T(8,128)}, "
               "bf16[32,32768,128]{2,1,0:T(8,128)(2,1)}) custom-call(s8[1,32,3]{2,1,0:T(4,128)(4,1)S(1)} %copy-done.240, "
               "s8[1,32,3]{2,1,0} %copy-done.246, bf16[32,32768,128]{2,1,0:T(8,128)(2,1)} %broadcast_multiply_fusion.5, "
               "bf16[32,32768,128]{2,1,0} %bitcast.9, bf16[32,32768,128]{2,1,0} %bitcast.11, s32[32768,128]{1,0} %iota.20)" + _TAIL)
CAUSAL_CALL = WINDOW_CALL.replace("s8[1,32,3]", "s8[1,32,32]").replace("no_residuals.7", "no_residuals.10")


def test_the_pairs_a_window_keeps_by_hand():
    assert flops_window_moe.window_pairs(5, 2) == 1 + 2 * 4  # the first query sees one key, the rest two
    assert flops_window_moe.window_pairs(5, 5) == flops_window_moe.window_pairs(5, 9) == 15  # the causal triangle
    assert flops_window_moe.window_pairs(4, 1) == 4
    t, w = 32768, 2048
    pairs = flops_window_moe.window_pairs(t, w)
    assert pairs == t * w - w * (w - 1) // 2 == 65_012_736  # ISSUE 38: 6.50e7 a head
    assert pairs / flops_window_moe.window_pairs(t, t) == pytest.approx(0.121, abs=5e-4)  # 12.1% of a global layer's


def test_each_attention_kinds_work_by_hand():
    ops, nbytes = flops_window_moe.attention(32768, 32, 4, 128, 2048)
    assert ops == 4 * 32 * 128 * 65_012_736 and nbytes == 2 * (2 * 32 + 2 * 4) * 32768 * 128
    full = flops_window_moe.attention(32768, 32, 4, 128)
    assert full[0] == 4 * 32 * 128 * (32768 * 32769 // 2) and full[1] == nbytes
    assert round(full[0] / 1e12, 1) == 8.8 and round(6 * ops / 1e12, 1) == 6.4  # ISSUE 38's TFLOP a call
    least, bound = flops.least_seconds(ops, nbytes, peaks.peaks_for("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(1.065e12 / 197e12, rel=2e-3)


def test_the_cells_operations_a_token_by_hand():
    job = job_of(CELL)
    k = job.keys
    assert flops_window_moe.attention_params(k) == 27_262_976  # q, k, v 10.49 M, the gate and o 8.39 M each
    expert = 3 * 2048 * 1024
    weights = 7 * 27_262_976 + 2 * 3 * 2048 * 6144 + 5 * (128 * 2048 + 9 * expert)
    mixing = 6 * 4 * 32 * 128 * 65_012_736 + 4 * 32 * 128 * (32768 * 32769 // 2)
    want = 2.0 * weights + 2.0 * 200192 * 2048 * 1024 / 32768 + mixing / 32768
    assert job.flops_per_token() == flops_window_moe.forward_flops_per_token(k, 32768, 1024) == pytest.approx(want)
    assert round(want * 32768 / 1e12, 1) == 52.1  # ISSUE 38: 52.1 TFLOP a call
    shares = {"experts": 2 * 5 * 9 * expert, "projections": 2 * 7 * 27_262_976, "global": 4 * 32 * 128 * (32768 * 32769 // 2) / 32768,
              "windows": 6 * 4 * 32 * 128 * 65_012_736 / 32768}
    assert {n: round(100 * v / want, 1) for n, v in shares.items()} == {"experts": 35.6, "projections": 24.0, "global": 16.9,
                                                                        "windows": 12.3}
    # under the window every layer is causal, and the count follows
    assert flops_window_moe.forward_flops_per_token(k, 1024, 1024) == pytest.approx(
        2.0 * weights + 2.0 * 200192 * 2048 + 7 * 4 * 32 * 128 * (1024 * 1025 // 2) / 1024)


def test_the_family_takes_a_window_call_and_leaves_a_causal_call_to_flash_fwd():
    hit = kernel_families.match(WINDOW_CALL)
    assert hit[0] == "attn_window_fwd"
    assert hit[1:] == flops_window_moe.attn_window_fwd([32, 32768, 128], [1, 32, 3])
    assert hit[1] == 4.0 * 32 * 128 * 65_012_736 and hit[2] == 4.0 * 32 * 32768 * 128 * 2
    causal = kernel_families.match(CAUSAL_CALL)
    assert causal[0] == "flash_fwd" and causal[1:] == flops.flash_fwd([32, 32768, 128])
    assert hit[1] / causal[1] == pytest.approx(0.121, abs=5e-4)
    # required, never more than the tiles the kernel visits: 93 of 1024 x 1024 a head
    assert hit[1] <= 4.0 * 32 * 128 * 93 * 1024 * 1024
    # the calls of the cells the benchmark had keep their families: a square table is no window's
    for text in (CAUSAL_CALL.replace("s8[1,32,32]", "s8[1,4,4]"), CAUSAL_CALL.replace("s8[1,32,32]", "s8[1,2,2]")):
        assert kernel_families.match(text)[0] == "flash_fwd"


@pytest.mark.parametrize("steps,tile,window", [([1, 32, 3], 1024, 2048), ([1, 32, 2], 1024, 1024), ([1, 8, 5], 4096, 16384),
                                               ([32, 32, 1], 1024, 1024)], ids=lambda x: str(x))
def test_the_familys_window_is_the_upper_end_of_what_its_table_allows(steps, tile, window):
    t = steps[1] * tile
    ops, nbytes = flops_window_moe.attn_window_fwd([2, 4, t, 128], steps)
    assert ops == 4.0 * 8 * 128 * flops_window_moe.window_pairs(t, window) and nbytes == 4.0 * 8 * t * 128 * 2
