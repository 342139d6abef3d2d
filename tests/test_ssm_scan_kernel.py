"""``pallasex``'s kernel for ``ttorch.ssm_scan`` (``ssm_scan_fwd``), interpreted
on the CPU under ``THUNDER_FLASH_FORCE=1``: against the decomposition it stands
in for and against the recurrence a position at a time in float64, the state
carried from chunk to chunk in its scratch, the fastest head, and every call
the checker leaves to the decomposition with the decomposition's own numbers."""

import numpy as np
import pytest

import thunder_tpu
import thunder_tpu.torch as ttorch
from thunder_tpu.core import dtypes

from test_granite_hybrid import recurrence, rel, scan_inputs


@pytest.fixture
def pallasex(monkeypatch):
    from thunder_tpu.executors import pallasex

    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    return pallasex


def low(a):
    import jax.numpy as jnp

    return jnp.asarray(a, jnp.bfloat16)


def f32(a):
    import jax.numpy as jnp

    return np.asarray(a.astype(jnp.float32))


def operands(t, heads, groups=1, state=128, width=64, b=1, seed=0, skip=True):
    """(what the call takes: x, B and C bf16, dt, A and D float32; the same numbers in float32 for the recurrence)."""
    x, dt, A, B, C, D = scan_inputs(t, heads=heads, width=width, groups=groups, state=state, b=b, seed=seed)
    call = [low(x), dt, A, low(B), low(C)] + ([D] if skip else [])
    return call, (f32(call[0]), dt, A, f32(call[3]), f32(call[4]), D if skip else np.zeros_like(D))


def scan(chunk, executors=None):
    return thunder_tpu.jit(lambda *a: ttorch.ssm_scan(*a, chunk=chunk), **({"executors": executors} if executors else {}))


def owner(jfn):
    return [b.sym.executor.name if b.sym.executor is not None else None
            for b in thunder_tpu.last_traces(jfn)[-1].bound_symbols if b.sym.name == "ssm_scan"]


def proxy(shape, dtype=dtypes.bfloat16):
    """What a checker sees of a tensor."""
    return type("P", (), {"shape": tuple(shape), "dtype": dtypes.to_dtype(dtype)})()


def like(a):
    return proxy(a.shape, a.dtype)


def cell(t=16384, heads=64, groups=1, state=128, width=64, dtype=dtypes.bfloat16):
    """granite-4.0-h-micro.fwd-t16k's call as the checker sees it, or a neighbour of it."""
    f32 = dtypes.float32
    return [proxy((1, t, heads, width), dtype), proxy((1, t, heads), f32), proxy((heads,), f32),
            proxy((1, t, groups, state), dtype), proxy((1, t, groups, state), dtype), proxy((heads,), f32)]


CASES = {
    # t, chunk, heads, groups, state, batch, D
    "one-chunk": (128, 128, 2, 1, 128, 1, True),
    "three-chunks": (384, 128, 4, 1, 128, 1, True),
    "two-groups-a-batch-of-2": (256, 128, 4, 2, 64, 2, True),
    "no-skip": (256, 128, 2, 1, 128, 1, False),
    "a-chunk-of-256-in-two-turns-of-the-loop": (512, 256, 16, 1, 128, 1, True),
    "the-published-chunk": (512, None, 2, 1, 64, 1, True),
}


@pytest.mark.parametrize("t,chunk,heads,groups,state,b,skip", CASES.values(), ids=CASES)
def test_the_kernel_is_the_decomposition_and_the_recurrence(pallasex, t, chunk, heads, groups, state, b, skip):
    """``pallas`` owns the call; its numbers are the decomposition's to bf16's
    rounding, and no farther from the float64 recurrence on the same rounded
    operands than the decomposition's are: nothing is held in a lower precision."""
    call, exact = operands(t, heads, groups, state, b=b, skip=skip)
    assert pallasex._ssm_scan_checker(*(like(a) for a in call), chunk=chunk)
    jfn = scan(chunk)
    got = jfn(*call)
    assert owner(jfn) == ["pallas"] and got.shape == call[0].shape and got.dtype == call[0].dtype
    plain = scan(chunk, ["jax"])
    old = f32(plain(*call))
    assert owner(plain) == []
    want = recurrence(*exact)
    assert rel(f32(got), old) < 5e-3 and rel(f32(got), want) < 4e-3
    assert rel(f32(got), want) < 1.1 * rel(old, want)


def test_the_state_is_carried_past_a_chunk_with_the_decays_product(pallasex):
    """An impulse at position 5 and nothing else: two chunks on, position t hears
    ``exp(sum of dt A over 6..t) dt_5 (C_t . B_5) x_5``, which only the state in
    the scratch can have brought there."""
    (x, dt, A, B, C), _ = operands(384, 4, skip=False)
    dt = np.exp(np.random.RandomState(1).uniform(np.log(1e-3), np.log(4e-3), dt.shape)).astype(np.float32)
    A = -np.linspace(1.0, 4.0, 4).astype(np.float32)
    impulse = np.zeros(x.shape, np.float32)
    impulse[0, 5] = np.linspace(-1.0, 1.0, 4 * 64).reshape(4, 64)
    got = f32(scan(128)(low(impulse), dt, A, B, C, None))
    Bf, Cf, xf = f32(B)[0, :, 0], f32(C)[0, :, 0], f32(low(impulse))[0, 5]
    np.testing.assert_array_equal(got[0, :5], 0.0)
    for t in (256, 300, 383):  # all in the third chunk
        decay = np.exp(np.float64(dt[0, 6:t + 1]).sum(0) * A)                          # (heads,)
        want = (decay * dt[0, 5] * float(Cf[t].astype(np.float64) @ Bf[5]))[:, None] * xf
        assert np.abs(want).max() > 1e-3 and rel(got[0, t], want) < 1e-2


def test_the_fastest_head_gives_no_inf_or_nan(pallasex):
    """``A = -16`` under ``dt = 0.1``: exp(-1.6) a step and exp(-410) a chunk; any exponent taken the wrong way is inf."""
    call, exact = operands(256, 2)
    call[1], call[2] = np.full_like(call[1], 0.1), np.full_like(call[2], -16.0)
    got = f32(scan(128)(*call))
    assert np.isfinite(got).all()
    assert rel(got, recurrence(exact[0], call[1], call[2], *exact[3:])) < 4e-3


def test_under_a_mesh_the_kernel_runs_a_batch_shard_a_device(pallasex):
    import jax
    from jax.sharding import Mesh

    from thunder_tpu.executors.kernel_mesh import kernel_mesh

    call, _ = operands(256, 2, b=2)
    want = f32(jax.jit(lambda *a: pallasex._ssm_scan_impl(*a, chunk=128))(*call))
    with kernel_mesh(Mesh(np.asarray(jax.devices()[:2]), ("dp",)), "dp"):
        assert pallasex._ssm_scan_checker(*(like(a) for a in call), chunk=128)
        assert not pallasex._ssm_scan_checker(*(like(a[:1]) if a.ndim > 1 else like(a) for a in call), chunk=128)
        got = jax.jit(lambda *a: pallasex._ssm_scan_impl(*a, chunk=128))(*call)
    assert len(got.sharding.device_set) == 2
    np.testing.assert_array_equal(f32(got), want)


DECLINES = {
    # what the checker sees at the cell's size -> the same refusal on a call small enough to run here
    "float32": (dict(dtype="float32"), dict(dtype="float32")),
    "a-length-the-chunk-does-not-divide": (dict(t=16384 + 128), dict(t=320)),
    "a-chunk-of-64": (dict(chunk=64), dict(chunk=64)),
    "heads-of-32": (dict(width=32), dict(width=32)),
    "a-state-of-32": (dict(state=32), dict(state=32)),
    "three-heads-a-group": (dict(heads=96, groups=32), dict(heads=6, groups=2)),
    "a-step-past-the-vmem": (dict(scope=2 ** 20), dict(scope=2 ** 20)),
}


@pytest.mark.parametrize("case", DECLINES)
def test_what_the_checker_declines_is_the_decompositions_with_the_same_numbers(pallasex, monkeypatch, case):
    big, small = DECLINES[case]

    def shapes(dtype="bfloat16", scope=None, chunk=None, **sizes):
        return cell(dtype=dtypes.float32 if dtype == "float32" else dtypes.bfloat16, **sizes)

    monkeypatch.setattr(pallasex, "_device_kind", lambda: "TPU v5 lite")  # the cell's chip: 64 MiB may be asked for
    assert pallasex._ssm_scan_checker(*shapes(), chunk=256)
    if "scope" in big:  # a chip whose VMEM holds less than the step
        monkeypatch.setattr(pallasex, "_SCOPED_VMEM_DEFAULT", big["scope"])
        monkeypatch.setattr(pallasex, "_device_kind", lambda: "cpu")
    assert not pallasex._ssm_scan_checker(*shapes(**big), chunk=big.get("chunk", 256))

    how = {"t": 256, "heads": 2, "groups": 1, "state": 128, "width": 64, **{k: v for k, v in small.items() if k not in ("scope", "chunk", "dtype")}}
    x, dt, A, B, C, D = scan_inputs(how["t"], heads=how["heads"], width=how["width"], groups=how["groups"], state=how["state"], b=1)
    call = [x, dt, A, B, C, D] if small.get("dtype") == "float32" else [low(x), dt, A, low(B), low(C), D]
    chunk = small.get("chunk", 128)
    jfn, plain = scan(chunk), scan(chunk, ["jax"])
    got, want = jfn(*call), plain(*call)
    assert owner(jfn) == [] and "ssm_scan" not in [b.sym.name for b in thunder_tpu.last_traces(jfn)[-1].bound_symbols]
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_no_chip_and_nothing_forced_is_nobodys(monkeypatch):
    from thunder_tpu.executors import pallasex

    monkeypatch.delenv("THUNDER_FLASH_FORCE", raising=False)
    call, _ = operands(256, 2)
    assert not pallasex._ssm_scan_checker(*(like(a) for a in call), chunk=128)
    jfn = scan(128)
    jfn(*call)
    assert owner(jfn) == []


def test_the_vmem_the_cells_step_is_reckoned_to_hold_and_what_is_asked_for_it(pallasex, monkeypatch):
    """The cell: 64 heads of 64 on a state of 128, a chunk of 256. x's and y's
    blocks twice (8 MiB), dt's padded to the lanes and B's and C's twice; the
    state 2 MiB, the eight turns' columns and three rows a head, ``C B^T`` and B
    turned in float32, C; what stands before the loop and inside it: over three quarters of the default
    scope, so the call asks for the generation's half (64 MiB on a v5e), and a
    chip without that room (a v4's 16 MiB) leaves the call to the decomposition."""
    MiB = 2 ** 20
    blocks = 4 * 256 * 4096 * 2 + 2 * 256 * 128 * 4 + 4 * 256 * 128 * 2
    scratch = 128 * 4096 * 4 + 8 * 256 * (128 + 24) * 4 + (256 * 256 + 128 * 256) * 4 + 256 * 128 * 2
    inside = 2 * 256 * 256 * 4 + 6 * 256 * 128 * 4 + 12 * 128 * 128 * 4
    needed = pallasex._ssm_scan_vmem(256, 64, 128, 1, 2)
    assert needed == blocks + scratch + inside and 12 * MiB < needed < 15 * MiB
    assert pallasex._ssm_scan_turn(64, 1) == 8 and pallasex._ssm_scan_turn(4, 2) == 2 and pallasex._ssm_scan_turn(6, 2) is None
    monkeypatch.setattr(pallasex, "_device_kind", lambda: "TPU v5 lite")
    assert pallasex._ssm_scan_scope(needed) == 64 * MiB and pallasex._ssm_scan_scope(12 * MiB) == 16 * MiB
    assert pallasex._ssm_scan_checker(*cell(), chunk=256)
    monkeypatch.setattr(pallasex, "_device_kind", lambda: "TPU v4")
    assert pallasex._ssm_scan_scope(needed) == 16 * MiB and not pallasex._ssm_scan_checker(*cell(), chunk=256)
