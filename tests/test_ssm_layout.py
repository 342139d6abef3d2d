"""transforms/ssm_layout.py: the state-space mixer reads its packed arrays where
they lie. The pass rewrites the idiom ``models/gpt.py::_mamba`` writes (the
convolution on a slice of ``in_proj``'s result, ``ssm_scan`` on three slices of
the convolution's), declines whatever it cannot prove is that idiom or that
``pallas`` would not take, and the rewritten program computes what was written,
to the bit: the same kernel reads the same bytes. Where ``pallas`` would also
convolve in the scan's call, the ranged convolution and the packed scan become
one ``ssm_conv_scan`` line, whose kernel computes what the two computed."""

import numpy as np
import pytest

import thunder_tpu
import thunder_tpu.torch as ttorch
from thunder_tpu import pipeline
from thunder_tpu.api import trace_program
from thunder_tpu.core import dtypes
from thunder_tpu.core.trace import region
from thunder_tpu.extend import resolve_executors
from thunder_tpu.models import gpt
from thunder_tpu.transforms import ssm_layout
from thunder_tpu.transforms.common import dce

from test_granite_hybrid import _bf16_sibling, batch
from test_ssm_scan_kernel import like, proxy

B, T, C, K = 2, 256, 64, 4
FOLDED, FUSED = ssm_layout.FOLDED_TAG, ssm_layout.FUSED_TAG


@pytest.fixture(autouse=True)
def _kernels_claim_on_the_cpu(monkeypatch):
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")


def _draw(*shape, seed=0, scale=0.5, dtype="bfloat16"):
    import jax.numpy as jnp

    return jnp.asarray(np.random.RandomState(seed + sum(shape)).randn(*shape) * scale, dtype=dtype)


def _mixer(H=4, P=64, G=1, N=128, layers=1, dtype="bfloat16", skip=True, chunk=128, conv_reads="a_slice", order="xBC",
           spare=0, xbc_read_twice=False, xbc_returned=False, holds_a_backward=False, taps=K, gap=0):
    """``_mamba`` of ``models/gpt.py``, ``layers`` times over, with a plain gate
    for its gated norm: (program, arguments). ``conv_reads`` "an_activation": the
    convolution's input is no slice. ``order`` "xCB": C's columns before B's.
    ``spare`` widens the convolution by columns that no slice reads. ``taps`` is
    the convolution's K; ``gap`` columns of the projection that nothing reads lie
    between z and the convolution's."""
    inner, GN = H * P, G * N
    conv = inner + 2 * GN + spare
    args = [_draw(B, T, C, dtype=dtype)]
    for i in range(layers):
        args += [_draw(inner + gap + conv + H, C, seed=7 * i + 1, scale=0.1, dtype=dtype),
                 _draw(conv, taps, seed=7 * i + 2, dtype=dtype),
                 _draw(conv, seed=7 * i + 3, scale=0.1, dtype=dtype), _draw(H, seed=7 * i + 4, dtype="float32") - 3.0,
                 np.log(np.linspace(1.0, 8.0, H)).astype(np.float32), _draw(H, seed=7 * i + 5, dtype="float32"),
                 _draw(C, inner, seed=7 * i + 6, scale=0.1, dtype=dtype)]
    b_at, c_at = (inner, inner + GN) if order == "xBC" else (inner + GN, inner)

    def program(x, *weights):
        kept = []
        for in_w, conv_w, conv_b, dt_bias, A_log, D, out_w in zip(*(weights[j::7] for j in range(7))):
            lin = ttorch.linear(x, in_w)
            z, xbc, dt = lin[..., :inner], lin[..., inner + gap:inner + gap + conv], lin[..., inner + gap + conv:]
            if conv_reads == "an_activation":
                xbc = ttorch.silu(xbc)
            with region("ssm.conv"):
                xbc = ttorch.causal_conv_silu(xbc, conv_w, conv_b)
            xs = ttorch.reshape(xbc[..., :inner], (B, T, H, P))
            Bm = ttorch.reshape(xbc[..., b_at:b_at + GN], (B, T, G, N))
            Cm = ttorch.reshape(xbc[..., c_at:c_at + GN], (B, T, G, N))
            with region("ssm.scan"):
                dt = ttorch.softplus(dt.float() + dt_bias)
                y = ttorch.ssm_scan(xs, dt, -ttorch.exp(A_log), Bm, Cm, D if skip else None, chunk=chunk)
            if holds_a_backward:  # any line of a backward: the pass reads a trace's kind off its symbols' names
                y = y + ttorch.sum(ttorch.layer_norm_bwd(z, z, None, None, 1e-5)[0])
            x = ttorch.linear(ttorch.reshape(y, (B, T, inner)) * ttorch.silu(z), out_w)
            if xbc_read_twice:
                x = x + ttorch.sum(xbc)
            kept.append(xbc)
        return (x, *kept) if xbc_returned else x

    return program, args


def _folded_trace(program, args, executors=None):
    """The pass on the program's trace, as ``pipeline.compile_trace`` places it."""
    _, trc = trace_program(program, tuple(args), {})
    return ssm_layout.fold_ssm_layouts(dce(trc), resolve_executors(executors))


def _transforms_record(fn):
    program = thunder_tpu.compile_stats(fn).cache_entries[-1].compile_id
    (record,) = [r for r in thunder_tpu.compile_phases() if r["program"] == program and r["phase"] == "transforms"]
    return record


def _without_the_pass(monkeypatch):
    monkeypatch.setattr(pipeline, "REWRITES", tuple(r for r in pipeline.REWRITES if r is not ssm_layout.fold_ssm_layouts))


def _bits(a):
    return [np.asarray(x, np.float32) for x in (a if isinstance(a, (tuple, list)) else (a,))]


IDIOMS = {
    # name: (program and arguments, sites, the convolution takes its projection's columns)
    # granite-4.0-h-micro's shape of it: one group on a state of 128, heads of 64; x's columns 2 blocks of B's
    "one_group_on_a_state_of_128": (lambda: _mixer(), 1, True),
    "two_groups_on_a_state_of_64": (lambda: _mixer(G=2, N=64), 1, True),
    "sixteen_heads_in_two_turns_of_the_kernels_loop": (lambda: _mixer(H=16), 1, True),
    "without_D": (lambda: _mixer(skip=False), 1, True),
    "the_published_chunk": (lambda: _mixer(chunk=None), 1, True),
    "two_layers": (lambda: _mixer(layers=2), 2, True),
    # the scan's half alone: the convolution read no slice, and stays the symbol it was
    "a_convolution_that_reads_no_slice": (lambda: _mixer(conv_reads="an_activation"), 1, False),
}


@pytest.mark.parametrize("idiom", IDIOMS)
def test_rewrites_the_idiom_and_computes_what_was_written(monkeypatch, idiom):
    make, sites, ranged = IDIOMS[idiom]
    program, args = make()
    trc = _folded_trace(program, args)
    assert trc.tags[FOLDED] == sites and trc.tags[FUSED] == (sites if ranged else 0)
    lines = trc.bound_symbols
    ids = [b.sym.id for b in lines]
    assert "torch.ssm_scan" not in ids
    if ranged:  # the ranged convolution and the packed scan, one line in the scan's region that stands for both
        fused = [b for b in lines if b.sym.id == "torch.ssm_conv_scan"]
        assert len(fused) == sites and "torch.causal_conv_silu" not in ids and "torch.ssm_scan_packed" not in ids
        pairs = [b.subsymbols for b in fused]
        for line, (conv, scan) in zip(fused, pairs):
            assert line.region == "ssm.scan" and line.output is scan.output and line.args[0] is conv.args[0]
            assert line.kwargs["columns"] == conv.kwargs["columns"] and line.args[3] is scan.args[1]
    else:
        assert ids.count("torch.ssm_scan_packed") == ids.count("torch.causal_conv_silu") == sites
        pairs = list(zip((b for b in lines if b.sym.id == "torch.causal_conv_silu"),
                         (b for b in lines if b.sym.id == "torch.ssm_scan_packed")))
    for conv, scan in pairs:
        xbc, lin = conv.output, conv.args[0]
        assert scan.args[0] is xbc and conv.region == "ssm.conv" and scan.region == "ssm.scan"
        # nothing between them: the packed call is the array's one reader, and no line cuts it
        assert [b.sym.id for b in lines if any(p is xbc for p in b.flat_proxy_args)] == ([] if ranged else ["torch.ssm_scan_packed"])
        width = xbc.shape[-1]
        if ranged:  # the projection whole, and where in it
            inner = lin.shape[-1] - width - scan.kwargs["heads"]
            assert conv.kwargs["columns"] == (inner, inner + width)
            assert [b.sym.id for b in lines if any(p is lin for p in b.flat_proxy_args)] == ["torch.getitem"] * 2 + ["torch.ssm_conv_scan"]
        else:
            assert "columns" not in conv.kwargs and tuple(lin.shape) == tuple(xbc.shape)
        assert [s.sym.id for s in scan.subsymbols] == ["torch.getitem", "torch.reshape"] * 3 + ["torch.ssm_scan"]

    folded = thunder_tpu.jit(program)
    got = folded(*args)
    src = thunder_tpu.last_traces(folded)[-1].python()
    assert _transforms_record(folded)[FOLDED] == sites and _transforms_record(folded)[FUSED] == (sites if ranged else 0)
    assert src.count("pallas_ssm_conv_scan(" if ranged else "pallas_ssm_scan_packed(") == sites and "pallas_ssm_scan(" not in src
    with monkeypatch.context() as m:
        _without_the_pass(m)
        written = thunder_tpu.jit(program)
        want = written(*args)
    assert FOLDED not in _transforms_record(written) and FUSED not in _transforms_record(written)
    assert thunder_tpu.last_traces(written)[-1].python().count("pallas_ssm_scan(") == sites
    # the same kernel on the same bytes; convolving in its step, the convolution's own arithmetic in its order
    assert got.dtype == want.dtype and np.array_equal(*_bits(got), *_bits(want))


def test_a_claim_that_fails_later_runs_the_program_as_written():
    """Each new line's decomposition is what it replaced: on the ``jax``
    executor the folded trace computes the written program's bits."""
    program, args = _mixer(G=2, N=64)
    trc = _folded_trace(program, args)
    assert trc.tags[FOLDED] == 1
    want = thunder_tpu.jit(program, executors=["jax"])(*args)
    got = thunder_tpu.jit(trc.python_callable(), executors=["jax"])(*args)
    assert np.array_equal(*_bits(got), *_bits(want))


DECLINES = {
    # why: (program and arguments, the executors, who owns the scan afterwards)
    "a_second_reader_of_xbc": (lambda: _mixer(xbc_read_twice=True), None, "pallas"),
    "xbc_returned": (lambda: _mixer(xbc_returned=True), None, "pallas"),
    "ranges_that_do_not_tile": (lambda: _mixer(spare=128), None, "pallas"),
    "ranges_out_of_order": (lambda: _mixer(order="xCB"), None, "pallas"),
    # two heads of 64 beside two groups of 128: x's 128 columns are no multiple of B's 256 (nor a turn of the kernel's loop)
    "x_no_multiple_of_B": (lambda: _mixer(H=2, G=2, N=128), None, None),
    # B's 64 columns are half a lane group: no block of the packed array; the three slices are the kernel's as before
    "B_narrower_than_a_lane_group": (lambda: _mixer(G=1, N=64), None, "pallas"),
    "float32": (lambda: _mixer(dtype="float32"), None, None),
    "an_executor_list_without_pallas": (lambda: _mixer(), ["flash", "jax"], None),
    "a_trace_that_holds_a_backward": (lambda: _mixer(holds_a_backward=True), None, "pallas"),
}


@pytest.mark.parametrize("why", DECLINES)
def test_declines_and_leaves_the_program_as_written(why):
    make, executors, owner = DECLINES[why]
    program, args = make()
    _, trc = trace_program(program, tuple(args), {})
    trc = dce(trc)
    before = list(trc.bound_symbols)
    after = ssm_layout.fold_ssm_layouts(trc, resolve_executors(executors))
    assert after is trc and after.tags[FOLDED] == after.tags[FUSED] == 0
    assert len(after.bound_symbols) == len(before) and all(a is b for a, b in zip(after.bound_symbols, before))
    claimed = pipeline.compile_trace(trc, resolve_executors(executors)).claimed
    scans = [b.sym.executor.name for b in claimed.bound_symbols if b.sym.name == "ssm_scan"]
    assert scans == ([owner] if owner else []) and "ssm_scan_packed" not in claimed.python()


UNFUSED = {
    # why: (program and arguments, the packed scan's folds); where they are 0 the program stays as written
    # x's columns 128 after a multiple of H P: no block of the projection (B's are still whole lane groups)
    "a_column_start_no_multiple_of_H_P": (lambda: _mixer(gap=128), 1),
    "taps_of_9": (lambda: _mixer(taps=9), 1),  # 8 rows to look back: more than a step keeps of the chunk before
    "float32": (lambda: _mixer(dtype="float32"), 0),
    "a_trace_that_holds_a_backward": (lambda: _mixer(holds_a_backward=True), 0),
}


@pytest.mark.parametrize("why", UNFUSED)
def test_where_the_fused_call_is_declined_the_ranged_convolution_and_the_packed_scan_stand(monkeypatch, why):
    """Where ``pallas`` would not convolve in the scan's call, the fold writes
    what it wrote before the fused line existed: the convolution on its
    projection's columns in ``ssm.conv`` and the packed scan in ``ssm.scan``, or
    nothing at all where the packed scan is declined too; and the program
    computes what was written, to the bit."""
    make, folds = UNFUSED[why]
    program, args = make()
    trc = _folded_trace(program, args)
    assert trc.tags[FOLDED] == folds and trc.tags[FUSED] == 0
    ids = [str(b.sym.id) for b in trc.bound_symbols]
    assert "torch.ssm_conv_scan" not in ids and ids.count("torch.ssm_scan_packed") == folds
    convs = [b for b in trc.bound_symbols if b.sym.id == "torch.causal_conv_silu"]
    assert len(convs) == 1 and convs[0].region == "ssm.conv"
    if folds:
        (scan,) = [b for b in trc.bound_symbols if b.sym.id == "torch.ssm_scan_packed"]
        inner = convs[0].args[0].shape[-1] - convs[0].output.shape[-1] - scan.kwargs["heads"]
        assert convs[0].kwargs["columns"] == (inner, inner + convs[0].output.shape[-1]) and scan.args[0] is convs[0].output
    folded = thunder_tpu.jit(program)
    got = folded(*args)
    assert thunder_tpu.last_traces(folded)[-1].python().count("pallas_ssm_conv_scan(") == 0
    with monkeypatch.context() as m:
        _without_the_pass(m)
        want = thunder_tpu.jit(program)(*args)
    assert np.array_equal(*_bits(got), *_bits(want))


def test_a_trace_without_a_scan_comes_back_the_same_object_and_says_nothing():
    _, trc = trace_program(lambda x, w: ttorch.causal_conv_silu(ttorch.linear(x, w)[..., 64:], w[:64, :K]),
                           (_draw(B, T, C), _draw(128, C)), {})
    trc = dce(trc)
    assert ssm_layout.fold_ssm_layouts(trc, resolve_executors(None)) is trc and FOLDED not in trc.tags and FUSED not in trc.tags


CHECKS = {
    # (the packed array's width, heads, groups, state, dtype), taken
    "granite_4_0_h_micro_fwd_t16k": ((16384, 4352, 64, 1, 128, dtypes.bfloat16), True),
    "eight_groups": ((16384, 4096 + 2 * 1024, 64, 8, 128, dtypes.bfloat16), True),
    "x_no_multiple_of_B": ((16384, 384 + 2 * 256, 6, 2, 128, dtypes.bfloat16), False),
    "B_narrower_than_a_lane_group": ((16384, 4096 + 2 * 64, 64, 1, 64, dtypes.bfloat16), False),
    "float32": ((16384, 4352, 64, 1, 128, dtypes.float32), False),
    "heads_of_32": ((16384, 2048 + 256, 64, 1, 128, dtypes.bfloat16), False),
    "a_width_that_is_no_x_B_C": ((16384, 4352 + 7, 64, 1, 128, dtypes.bfloat16), False),
    "a_ragged_length": ((16384 + 64, 4352, 64, 1, 128, dtypes.bfloat16), False),
}


@pytest.mark.parametrize("case", CHECKS)
def test_the_packed_checker_is_the_scans_on_the_parts_and_whole_blocks(monkeypatch, case):
    from thunder_tpu.executors import pallasex

    monkeypatch.setattr(pallasex, "_device_kind", lambda: "TPU v5 lite")  # the cell's chip: 64 MiB may be asked for
    (t, width, heads, groups, state, dtype), taken = CHECKS[case]
    f32 = dtypes.float32
    call = [proxy((1, t, width), dtype), proxy((1, t, heads), f32), proxy((heads,), f32), proxy((heads,), f32)]
    assert pallasex._ssm_scan_packed_checker(*call, heads=heads, groups=groups, state=state, chunk=256) is taken
    inner = width - 2 * groups * state
    if taken:  # and then the three slices would be taken too
        parts = [proxy((1, t, heads, inner // heads), dtype), *call[1:3], *[proxy((1, t, groups, state), dtype)] * 2, call[3]]
        assert pallasex._ssm_scan_checker(*parts, chunk=256)


KERNEL = {
    # heads, groups, state, batch, D: x's block 0 and B's and C's at H P / (G N) and one more
    "one_group_on_a_state_of_128": (4, 1, 128, 1, True),
    "two_groups_on_a_state_of_64_a_batch_of_2": (4, 2, 64, 2, True),
    "sixteen_heads_without_D": (16, 1, 128, 1, False),
}


@pytest.mark.parametrize("heads,groups,state,b,skip", KERNEL.values(), ids=KERNEL)
def test_the_kernel_on_the_packed_array_is_the_kernel_on_its_three_slices(heads, groups, state, b, skip):
    from thunder_tpu.executors import pallasex

    inner, GN = heads * 64, groups * state
    xbc = _draw(b, T, inner + 2 * GN, seed=1)
    dt = np.exp(np.random.RandomState(2).uniform(np.log(1e-3), np.log(0.1), (b, T, heads))).astype(np.float32)
    A, D = -np.linspace(1.0, 16.0, heads).astype(np.float32), np.linspace(-1.0, 1.0, heads).astype(np.float32) if skip else None
    how = dict(heads=heads, groups=groups, state=state, chunk=128)
    assert pallasex._ssm_scan_packed_checker(like(xbc), like(dt), like(A), None if D is None else like(D), **how)
    got = pallasex._ssm_scan_packed_impl(xbc, dt, A, D, **how)
    x, Bm, Cm = xbc[..., :inner], xbc[..., inner:inner + GN], xbc[..., inner + GN:]
    want = pallasex._ssm_scan_impl(x.reshape(b, T, heads, 64), dt, A, Bm.reshape(b, T, groups, state),
                                   Cm.reshape(b, T, groups, state), D, chunk=128)
    assert got.shape == want.shape == (b, T, heads, 64) and np.array_equal(*_bits(got), *_bits(want))
    # and the symbol's decomposition is ``ssm_scan`` on the slices, whoever runs it
    packed = thunder_tpu.jit(lambda xbc, dt, A, D: ttorch.ssm_scan_packed(xbc, dt, A, D, **how), executors=["jax"])
    sliced = thunder_tpu.jit(lambda x, dt, A, Bm, Cm, D: ttorch.ssm_scan(x, dt, A, Bm, Cm, D, chunk=128), executors=["jax"])
    old = sliced(x.reshape(b, T, heads, 64), dt, A, Bm.reshape(b, T, groups, state), Cm.reshape(b, T, groups, state), D)
    assert np.array_equal(*_bits(packed(xbc, dt, A, D)), *_bits(old))


CONV_KERNEL = {
    # batch, positions (chunks of 128), heads, groups, state, taps, bias, D, x's block of H P columns in the projection
    "granite_s_form_x_at_block_1": (1, 384, 4, 1, 128, 4, True, True, 1),
    "a_batch_of_2_two_groups_on_a_state_of_64": (2, 384, 4, 2, 64, 4, True, True, 1),
    "taps_of_2_without_a_bias": (1, 384, 4, 1, 128, 2, False, True, 1),
    "without_D_x_at_block_0": (1, 384, 4, 1, 128, 4, True, False, 0),
    "sixteen_heads_x_at_block_2_four_chunks": (1, 512, 16, 1, 128, 4, False, False, 2),
}


@pytest.mark.parametrize("b,t,heads,groups,state,taps,bias,skip,block", CONV_KERNEL.values(), ids=CONV_KERNEL)
def test_the_kernel_that_convolves_in_its_step_is_the_convolution_then_the_packed_kernel(b, t, heads, groups, state, taps,
                                                                                       bias, skip, block):
    """``pallas``'s ``ssm_conv_scan`` on the projection's columns against
    ``causal_conv_silu`` on the same columns followed by the packed kernel: every
    chunk after the first reads the rows the chunk before kept, and the sum, the
    bias, the SiLU and the rounding are the convolution's, so the two agree to the
    bit. The symbol's decomposition is those two lines, whoever runs it."""
    from thunder_tpu.executors import pallasex

    inner, GN = heads * 64, groups * state
    width = inner + 2 * GN
    lo = block * inner
    lin = _draw(b, t, lo + width + 3 * 64 + heads, seed=1)
    w, bb = _draw(width, taps, seed=2), _draw(width, seed=3, scale=0.1) if bias else None
    dt = np.exp(np.random.RandomState(2).uniform(np.log(1e-3), np.log(0.1), (b, t, heads))).astype(np.float32)
    A, D = -np.linspace(1.0, 16.0, heads).astype(np.float32), np.linspace(-1.0, 1.0, heads).astype(np.float32) if skip else None
    how = dict(heads=heads, groups=groups, state=state, chunk=128)
    columns = (lo, lo + width)
    assert pallasex._ssm_conv_scan_checker(like(lin), like(w), None if bb is None else like(bb), like(dt), like(A),
                                           None if D is None else like(D), columns=columns, **how)
    got = pallasex._ssm_conv_scan_impl(lin, w, bb, dt, A, D, columns=columns, **how)
    conv = thunder_tpu.jit(lambda lin, w, bb: ttorch.causal_conv_silu(lin, w, bb, columns=columns), executors=["jax"])
    want = pallasex._ssm_scan_packed_impl(conv(lin, w, bb), dt, A, D, **how)
    assert got.shape == want.shape == (b, t, heads, 64) and np.array_equal(*_bits(got), *_bits(want))
    fused = thunder_tpu.jit(lambda *a: ttorch.ssm_conv_scan(*a, columns=columns, **how), executors=["jax"])
    packed = thunder_tpu.jit(lambda xbc, dt, A, D: ttorch.ssm_scan_packed(xbc, dt, A, D, **how), executors=["jax"])
    assert np.array_equal(*_bits(fused(lin, w, bb, dt, A, D)), *_bits(packed(conv(lin, w, bb), dt, A, D)))


CONV_CHECKS = {
    # (the projection's width, x's first column, taps, dtype), taken: granite-4.0-h-micro.fwd-t16k's mixer
    "granite_4_0_h_micro_fwd_t16k": ((8512, 4096, 4, dtypes.bfloat16), True),
    "x_at_block_0": ((4352, 0, 4, dtypes.bfloat16), True),
    "taps_of_8": ((8512, 4096, 8, dtypes.bfloat16), True),
    "taps_of_9": ((8512, 4096, 9, dtypes.bfloat16), False),
    "x_off_its_block": ((8512 + 128, 4096 + 128, 4, dtypes.bfloat16), False),
    "float32": ((8512, 4096, 4, dtypes.float32), False),
}


@pytest.mark.parametrize("case", CONV_CHECKS)
def test_the_fused_checker_is_the_packed_one_and_whole_blocks_of_the_projection(monkeypatch, case):
    from thunder_tpu.executors import pallasex

    monkeypatch.setattr(pallasex, "_device_kind", lambda: "TPU v5 lite")
    (total, lo, taps, dtype), taken = CONV_CHECKS[case]
    f32, width = dtypes.float32, 4352
    call = [proxy((1, 16384, total), dtype), proxy((width, taps), dtype), proxy((width,), dtype), proxy((1, 16384, 64), f32),
            proxy((64,), f32), proxy((64,), f32)]
    how = dict(heads=64, groups=1, state=128, chunk=256)
    assert pallasex._ssm_conv_scan_checker(*call, columns=(lo, lo + width), **how) is taken
    if taken:  # the step, convolving, fits the scope the packed step asks for
        needed = pallasex._ssm_scan_vmem(256, 64, 128, 1, 2, convolves=True)
        assert pallasex._ssm_scan_scope(needed) == pallasex._ssm_scan_scope(pallasex._ssm_scan_vmem(256, 64, 128, 1, 2))


@pytest.mark.parametrize("columns", [(0, 96), (32, 128), (16, 112)], ids=["the_first", "the_last", "the_middle"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
def test_the_convolution_on_columns_is_the_convolution_on_the_slice(columns, bias):
    lo, hi = columns
    x, w, b = _draw(B, 40, 128), _draw(hi - lo, K, seed=1), _draw(hi - lo, seed=2) if bias else None
    ranged = thunder_tpu.jit(lambda x, w, b: ttorch.causal_conv_silu(x, w, b, columns=columns))
    sliced = thunder_tpu.jit(lambda x, w, b: ttorch.causal_conv_silu(x[..., lo:hi], w, b))
    got, want = ranged(x, w, b), sliced(x, w, b)
    assert got.shape == want.shape == (B, 40, hi - lo) and np.array_equal(*_bits(got), *_bits(want))
    with pytest.raises(Exception, match="columns"):
        thunder_tpu.jit(lambda x, w: ttorch.causal_conv_silu(x, w, columns=(lo, hi + 8)))(x, w)


def test_the_model_folds_a_site_a_mamba_layer_and_computes_what_was_written(monkeypatch):
    """``models/gpt.py::forward`` on the Granite stand-in at the kernel's shapes: a site a Mamba layer, where the
    convolution and the scan are one call ``pallas``'s in region ``ssm.scan`` beside ``flash``'s attention, the
    logits the unfolded program's bits."""
    cfg, params = _bf16_sibling()
    idx = batch(256)
    mamba = [cfg.layer_mixer(i) for i in range(cfg.n_layer)].count("mamba")
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg, last=32))
    got = jfn(params, idx)
    assert mamba == 4 and _transforms_record(jfn)[FOLDED] == _transforms_record(jfn)[FUSED] == mamba
    (made,) = [t for t in thunder_tpu.last_traces(jfn) if t.pass_name() == "State-space layout folding"]
    assert not [b for b in made.bound_symbols if b.sym.id in ("torch.causal_conv_silu", "torch.ssm_scan_packed")]
    fused = [b for b in made.bound_symbols if b.sym.id == "torch.ssm_conv_scan"]
    assert len(fused) == mamba and all(b.kwargs["columns"] == (cfg.ssm_inner, cfg.ssm_inner + cfg.ssm_conv_channels)
                                       and b.args[0].shape[-1] > cfg.ssm_conv_channels for b in fused)
    claimed = thunder_tpu.last_traces(jfn)[-1]
    owners = [(b.sym.name, b.sym.executor.name, b.region) for b in claimed.bound_symbols
              if b.sym.executor is not None and b.sym.executor.name in ("flash", "pallas")]
    scan, attention = ("ssm_conv_scan", "pallas", "ssm.scan"), ("scaled_dot_product_attention", "flash", "attn.full")
    assert owners == [scan, attention, scan, scan, attention, scan]
    with monkeypatch.context() as m:
        _without_the_pass(m)
        want = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg, last=32))(params, idx)
    assert np.array_equal(*_bits(got), *_bits(want))


def test_the_grad_of_a_foldable_forward_is_todays(monkeypatch):
    """``value_and_grad`` differentiates ``ssm_scan``'s decomposition before the
    rewrites run, so its trace holds no scan for the pass to fold: the same
    object back, no tag, and the loss and gradients with the pass in the list
    are those with it out, to the bit."""
    program, args = _mixer()
    loss = lambda *a: ttorch.sum(program(*a).float())

    def step():
        vg = thunder_tpu.value_and_grad(loss)
        return vg, vg(*args)

    vg, (got_loss, got) = step()
    assert FOLDED not in _transforms_record(vg) and "ssm_scan" not in thunder_tpu.last_traces(vg)[-1].python()
    _without_the_pass(monkeypatch)
    _, (want_loss, want) = step()
    assert float(got_loss) == float(want_loss) and len(got) == len(want) == len(args)
    for g, w in zip(got, want):
        assert np.array_equal(*_bits(g), *_bits(w))
