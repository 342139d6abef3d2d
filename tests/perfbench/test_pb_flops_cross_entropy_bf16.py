"""The families of the cross-entropy calls on bfloat16 logits (PR 28): half
the float32 families' bytes, told from them and from the rope family by
operand types alone, and adding them moves no call that had a family."""

import pytest
from test_pb_flops import KERNELS, _TAIL

from perfbench import flops, flops_cross_entropy_bf16, kernel_families, peaks

# Instruction texts as the v5e's trace names them (PR 28's traced pythia-410m.train and mistral-7b.train).
FWD = ("%step.19 = f32[8192,128]{1,0:T(8,128)S(1)} custom-call(bf16[8192,50304]{1,0:T(8,128)(2,1)} %bitcast.34, "
       "s32[8192,128]{1,0:T(8,128)S(1)} %copy-done.15)" + _TAIL)
BWD = ("%step.14 = bf16[8192,50304]{1,0:T(8,128)(2,1)} custom-call(bf16[8192,50304]{1,0:T(8,128)(2,1)} %bitcast.34, "
       "s32[8192,128]{1,0:T(8,128)S(1)} %copy-done.15, f32[8192,128]{1,0:T(8,128)S(1)} %copy-done.16)" + _TAIL)
FWD_MISTRAL = FWD.replace("8192", "4096").replace("50304", "32000")


def test_costs_by_hand():
    # (8192, 50304) bfloat16 logits read once, a target and a loss a row; backward also writes them once
    assert flops_cross_entropy_bf16.cross_entropy_fwd_bf16([8192], [50304]) == (
        4.0 * 8192 * 50304, 8192 * 50304 * 2 + 8192 * 8)
    assert flops_cross_entropy_bf16.cross_entropy_bwd_bf16([8192], [50304]) == (
        4.0 * 8192 * 50304, 2 * 8192 * 50304 * 2 + 8192 * 8)
    v5e = peaks.peaks_for("TPU v5 lite")
    seconds, bound = flops.least_seconds(*flops_cross_entropy_bf16.cross_entropy_fwd_bf16([8192], [50304]), v5e)
    assert bound == "memory" and seconds == pytest.approx(1.0064e-3, rel=1e-4)
    seconds, bound = flops.least_seconds(*flops_cross_entropy_bf16.cross_entropy_bwd_bf16([8192], [50304]), v5e)
    assert bound == "memory" and seconds == pytest.approx(2.0128e-3, rel=1e-4)


@pytest.mark.parametrize("text,family,dims", [
    (FWD, "cross_entropy_fwd_bf16", ([8192], [50304])),
    (BWD, "cross_entropy_bwd_bf16", ([8192], [50304])),
    (FWD_MISTRAL, "cross_entropy_fwd_bf16", ([4096], [32000])),
], ids=["forward", "backward", "forward-mistral"])
def test_the_bfloat16_calls_have_a_family(text, family, dims):
    hit = kernel_families.match(text)
    assert hit is not None and hit[0] == family
    assert hit[1:] == getattr(flops_cross_entropy_bf16, family)(*dims)
    # half the bytes of the float32 family, less the per-row part that stays
    wide = getattr(flops, family.removesuffix("_bf16"))(*dims)
    assert hit[1] == wide[0] and hit[2] - dims[0][0] * 8 == (wide[1] - dims[0][0] * 8) / 2


@pytest.mark.parametrize("family", sorted(KERNELS))
def test_no_call_that_had_a_family_changes_it(family):
    """The float32 calls, which the parent of PR 28 runs, and flash and rope read as before."""
    assert kernel_families.match(KERNELS[family])[0] == family
    for new in ("cross_entropy_fwd_bf16", "cross_entropy_bwd_bf16"):
        pattern = next(p for name, p, _ in kernel_families._families() if name == new)
        assert pattern.search(KERNELS[family]) is None


def test_a_float16_or_mixed_call_matches_neither():
    assert kernel_families.match(FWD.replace("bf16[", "f16[")) is None
    # a float32 gradient of bfloat16 logits is no call the program makes
    assert kernel_families.match(BWD.replace("%step.14 = bf16[", "%step.14 = f32[")) is None
