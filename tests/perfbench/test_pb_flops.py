"""The yardstick's arithmetic, against numbers worked by hand: the model's
operations per token for both configurations, each kernel family's operations
and bytes, the roofline, and the table of peaks."""

import importlib

import pytest

from perfbench import flops, kernel_families, manifest, peaks


def job_of(cell_name):
    """The cell's job as a run makes it, at the real sizes: shapes only, no array."""
    cell = manifest.load_cell(cell_name)
    module = importlib.import_module(f"perfbench.jobs.{cell.traffic['job']}")
    return module.Job(cell, seed=0, platform="tpu", rehearse=False)


def test_pythia_410m_by_hand():
    train, fwd = job_of("pythia-410m.train"), job_of("pythia-410m.fwd")
    # a layer: qkv 3*1024*1024 + proj 1024*1024 + GELU MLP 2*1024*4096 = 12,582,912
    # 24 of them 301,989,888; the head 50304*1024 = 51,511,296; wte is a gather, biases and norms no matmul
    assert train.matmul_params() == fwd.matmul_params() == 353_501_184
    # causal attention, forward, a token: 24 layers * 2 * T=2048 * 16 heads * 64
    assert flops.attention_flops_per_token(16, 64, 24, 2048) == 100_663_296
    assert fwd.flops_per_token() == 2 * 353_501_184 + 100_663_296
    assert train.flops_per_token() == 2_422_996_992
    # the train cell's step: 4 * 2048 tokens -> 19.85 TFLOP
    assert train.tokens_per_unit * train.flops_per_token() == pytest.approx(19.849e12, rel=1e-4)


def test_mistral_7b_by_hand():
    one, four = job_of("mistral-7b.train"), job_of("mistral-7b.fsdp4")
    # a layer: qkv (32 + 2*8)*128*4096 = 25,165,824; proj 4096*4096 = 16,777,216;
    # SwiGLU 3*4096*14336 = 176,160,768; together 218,103,808. The head 32000*4096.
    assert one.matmul_params() == 6 * 218_103_808 + 131_072_000 == 1_439_694_848
    assert four.matmul_params() == 1_875_902_464
    assert one.flops_per_token() == 3 * (2 * 1_439_694_848 + 6 * 2 * 4096 * 4096)
    # one-chip cell: 4096 tokens a step -> 37.86 TFLOP; four-chip cell: 49.4 TFLOP a chip
    assert 4096 * one.flops_per_token() == pytest.approx(37.856e12, rel=1e-4)
    assert 4096 * four.flops_per_token() == pytest.approx(49.401e12, rel=1e-4)


def test_a_forward_pass_is_two_operations_a_weight_and_the_attention():
    assert flops.forward_flops_per_token(1000, heads=2, head_size=8, depth=3, seq=16) == 2000 + 3 * 2 * 16 * 2 * 8


def test_kernel_costs_by_hand():
    # flash forward, q (4, 16, 2048, 64): 64 head-sequences, two matmuls over half of 2048^2
    assert flops.flash_fwd([4, 16, 2048, 64]) == (2.0 * 64 * 2048 * 2048 * 64, 4 * 64 * 2048 * 64 * 2 + 64 * 2048 * 4)
    assert flops.flash_fwd([32, 4096, 128])[0] == 2.0 * 32 * 4096 * 4096 * 128  # B=1: no batch axis
    assert flops.flash_bwd([4, 16, 2048, 64])[0] == 2.5 * flops.flash_fwd([4, 16, 2048, 64])[0]
    # cross-entropy over (8192, 50304) float32 logits: read once; backward also writes once
    assert flops.cross_entropy_fwd([8192], [50304])[1] == 8192 * 50304 * 4 + 8192 * 8 == 1_648_427_008
    assert flops.cross_entropy_bwd([8192], [50304])[1] == 2 * 8192 * 50304 * 4 + 8192 * 8
    # rope on (32, 4096, 128) bf16: x read and written, cos and sin (4096, 128) read
    assert flops.rope([32, 4096, 128], [4096, 128]) == (3.0 * 16_777_216, 2 * 16_777_216 * 2 + 2 * 524_288 * 2)


def test_roofline_takes_the_larger_bound():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert (v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"]) == (197e12, 819e9)
    ops, nbytes = flops.cross_entropy_fwd([8192], [50304])
    seconds, bound = flops.least_seconds(ops, nbytes, v5e)
    assert bound == "memory" and seconds == pytest.approx(2.0127e-3, rel=1e-4)
    seconds, bound = flops.least_seconds(*flops.flash_fwd([4, 16, 2048, 64]), v5e)
    assert bound == "compute" and seconds == pytest.approx(34_359_738_368 / 197e12)


@pytest.mark.parametrize("kind", ["cpu", "TPU v9", "NVIDIA A100", "", "_source"])
def test_an_unknown_device_has_no_peak(kind):
    with pytest.raises(ValueError, match="no peak is recorded"):
        peaks.peaks_for(kind)


# Instruction texts as the v5e's trace names them (PR 22's chip runs), cut to what the patterns read.
_TAIL = ', custom_call_target="tpu_custom_call", operand_layout_constraints={}'
KERNELS = {
    "flash_fwd": "%splash_mha_fwd_residuals.3 = (f32[4,1024,128]{2,1,0:T(8,128)}, bf16[4,16,2048,64]{3,2,1,0:T(8,128)(2,1)S(1)}) "
                 "custom-call(s8[1,2,2]{2,1,0:T(4,128)(4,1)S(1)} %copy-done.199, s8[1,2,2]{2,1,0} %copy-done.193, "
                 "bf16[4,16,2048,64]{3,2,1,0:T(8,128)(2,1)} %maximum_multiply_fusion.1, bf16[4,16,2048,64]{3,2,1,0} %b)" + _TAIL,
    "flash_bwd": "%splash_mha_dkv_no_residuals.1 = (f32[1024,128]{1,0:T(8,128)}, bf16[4,32,4096,128]{3,2,1,0}) "
                 "custom-call(s8[1,4,4]{2,1,0} %copy-done.65, s8[1,4,4]{2,1,0} %copy-done.64, "
                 "bf16[32,4096,128]{2,1,0:T(8,128)(2,1)S(1)} %custom-call.10, bf16[32,4096,128]{2,1,0} %bitcast.107)" + _TAIL,
    "cross_entropy_fwd": "%step.3 = f32[8192,128]{1,0:T(8,128)S(1)} custom-call(f32[8192,50304]{1,0:T(8,128)} %bitcast.26, "
                         "s32[8192,128]{1,0:T(8,128)S(1)} %copy-done.21)" + _TAIL,
    "cross_entropy_bwd": "%step.2 = f32[8192,50304]{1,0:T(8,128)} custom-call(f32[8192,50304]{1,0:T(8,128)} %bitcast.26, "
                         "s32[8192,128]{1,0:T(8,128)S(1)} %copy-done.21, f32[8192,128]{1,0:T(8,128)S(1)} %copy-done.22)" + _TAIL,
    "rope": "%step.6 = bf16[32,4096,128]{2,1,0:T(8,128)(2,1)S(1)} custom-call(bf16[32,4096,128]{2,1,0:T(8,128)(2,1)S(1)} "
            "%bitcast.117, bf16[4096,128]{1,0:T(8,128)(2,1)S(1)} %cosine_convert_fusion, bf16[4096,128]{1,0} %gte.36)" + _TAIL,
}


@pytest.mark.parametrize("family", sorted(KERNELS))
def test_kernel_families_are_told_apart(family):
    hit = kernel_families.match(KERNELS[family])
    assert hit is not None and hit[0] == family
    want = {"flash_fwd": flops.flash_fwd([4, 16, 2048, 64]), "flash_bwd": flops.flash_bwd([32, 4096, 128]),
            "cross_entropy_fwd": flops.cross_entropy_fwd([8192], [50304]),
            "cross_entropy_bwd": flops.cross_entropy_bwd([8192], [50304]),
            "rope": flops.rope([32, 4096, 128], [4096, 128])}[family]
    assert hit[1:] == want


def test_a_kernel_of_no_known_family_matches_nothing():
    assert kernel_families.match("%step.9 = s32[8,128]{1,0} custom-call(s32[8,128]{1,0} %x)" + _TAIL) is None
