"""The claimed routed experts' dispatch (``pallasex._moe_experts_impl``, ISSUE 32).

Rows go into the buffer by one gather and come back by k gathers of (N, C)
that one fusion selects, weighs and sums: the pairs lie choice-major, no
gather asks for a fill, and where some experts are held elsewhere the mask is a
select. Held against ``torch.moe_experts``'s own decomposition through
``thunder_tpu.jit``, which is the definition: in bf16 as the pallas executor
claims it (megablox gmm, interpreted here) and, the function called on float32
copies, to the limits ``tests/test_axk1.py`` holds the decomposition to."""

import numpy as np
import pytest

import thunder_tpu
import thunder_tpu.torch as ttorch

C, H = 128, 128
HELD_ELSEWHERE = (0, 1, 2, 6, 7, 8, 9, 10, 11)  # of 12, where experts 3 to 5 are held


def _distinct(rng, n, k, among, p=None):
    among = np.asarray(among)
    return np.stack([among[rng.choice(len(among), size=k, replace=False, p=p)] for _ in range(n)]).astype(np.int64)


def _three_of_twelve(rng, rows_here):
    """512 tokens, 4 choices among 12, experts 3 to 5 held: exactly
    ``rows_here`` pairs land here (the short buffer holds 1024 of the worst
    case's 1536), three a token until one token takes what is left."""
    top_i = _distinct(rng, 512, 4, HELD_ELSEWHERE)
    whole, rest = divmod(rows_here, 3)
    top_i[:whole, :3] = [3, 4, 5]
    top_i[whole, :rest] = [3, 4][:rest]
    return top_i[:, rng.permutation(4)]  # the held choices are not always the first


def _an_eighth_lands_here(rng):
    top_i = _distinct(rng, 512, 4, range(12))
    top_i[7] = [0, 9, 1, 11]  # every choice held elsewhere
    top_i[8] = [10, 5, 0, 3]  # two choices held here
    return top_i


def _every_expert_held(rng):
    return _distinct(rng, 128, 4, range(8), p=np.arange(8, 0, -1) / 36)  # uneven: most tokens prefer the low experts


def _one_expert_idle(rng):
    return _distinct(rng, 128, 4, [e for e in range(8) if e != 5])


def _every_choice_held_here(n, k, held, offset=0):
    """The router's worst case: every token's k choices among the ``held`` experts here."""
    return lambda rng: _distinct(rng, n, k, range(offset, offset + held))


CASES = {
    # name: (held, expert_offset, n_expert, top_i from an rng, lax.cond in the program, gmm poisoned)
    "every-expert-held-k-4-of-8": (8, 0, 8, _every_expert_held, False, False),
    "3-of-12-held-at-an-offset-fits-the-short-buffer": (3, 3, 12, lambda rng: _three_of_twelve(rng, 1024), True, False),
    "3-of-12-held-one-row-over-the-short-buffer": (3, 3, 12, lambda rng: _three_of_twelve(rng, 1025), True, False),
    "an-expert-with-no-rows": (8, 0, 8, _one_expert_idle, False, False),
    "a-token-whose-every-choice-is-held-elsewhere": (3, 3, 12, _an_eighth_lands_here, True, False),
    "a-token-with-two-choices-held-here": (3, 3, 12, _an_eighth_lands_here, True, False),
    "rows-beyond-the-groups-poisoned-with-nan": (3, 3, 12, _an_eighth_lands_here, True, True),
    # PR 40: the worst case goes over the short buffer in passes, and no buffer of min(k, held) * N rows exists:
    # 16 of 768 outputs held and 12 a token (longcat-flash-omni.fwd-t16k's routed layer), every choice held here,
    "worst-case-12-choices-all-among-16-held-of-768-in-3-passes": (16, 0, 768, _every_choice_held_here(128, 12, 16), True, False),
    "worst-case-in-passes-with-nan-beyond-the-groups": (16, 0, 768, _every_choice_held_here(128, 12, 16), True, True),
    # and a.x-k1's stand-in shapes (4 of 16 held from 4, 4 a token, 256 tokens): 1,024 rows through a buffer of 512.
    "worst-case-axk1-stand-in-4-held-of-16-in-2-passes": (4, 4, 16, _every_choice_held_here(256, 4, 4, 4), True, False),
}
PASSES = {"3-of-12-held-at-an-offset-fits-the-short-buffer": 1, "3-of-12-held-one-row-over-the-short-buffer": 2,
          "worst-case-12-choices-all-among-16-held-of-768-in-3-passes": 3, "worst-case-in-passes-with-nan-beyond-the-groups": 3,
          "worst-case-axk1-stand-in-4-held-of-16-in-2-passes": 2}


def _poison_rows_beyond_the_groups(monkeypatch):
    """megablox's gmm leaves the rows beyond the groups as they were; here it
    leaves them NaN, which is what they may be on the chip."""
    import importlib

    import jax.numpy as jnp

    megablox = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")  # the package has a gmm too
    real = megablox.gmm

    def poisoned(lhs, rhs, group_sizes, **kwargs):
        out = real(lhs, rhs, group_sizes, **kwargs)
        computed = jnp.arange(out.shape[0])[:, None] < jnp.sum(group_sizes)
        return jnp.where(computed, out, jnp.nan)

    monkeypatch.setattr(megablox, "gmm", poisoned)


@pytest.mark.parametrize("case", list(CASES))
def test_the_claimed_dispatch_is_the_composites_decomposition(case, monkeypatch):
    import jax
    import jax.numpy as jnp

    from thunder_tpu.executors import pallasex

    held, offset, total, routing, branches, poison = CASES[case]
    rng = np.random.RandomState(32)
    top_i = routing(rng)
    n, k = top_i.shape
    x = rng.randn(n, C).astype(np.float32)
    gate, up = (rng.randn(held, C, H).astype(np.float32) * 0.1 for _ in range(2))
    down = rng.randn(held, H, C).astype(np.float32) * 0.1
    top_w = rng.rand(n, k).astype(np.float32)
    here = (top_i >= offset) & (top_i < offset + held)
    if case.startswith("3-of-12"):
        short = 2 * k * n * held // total
        assert short == 1024 and here.sum() - short == (1 if "over" in case else 0)
    if case == "an-expert-with-no-rows":
        assert not (top_i == 5).any()
    if case in PASSES:
        assert pallasex.expert_buffer_passes(here.sum(), n, k, held, total) == PASSES[case]
        assert pallasex.expert_buffer_rows(n, k, held, total) == 512 * (2 if case.startswith("3-of-12") else 1) < min(k, held) * n
    if case.startswith("worst-case"):
        assert here.all() and here.sum() == min(k, held) * n
    if poison:
        _poison_rows_beyond_the_groups(monkeypatch)

    def decomposition(*operands):
        fn = thunder_tpu.jit(lambda *a: ttorch.moe_experts(*a, offset, total), executors=["jax"])
        return fn(*operands)

    # Float32, the function itself (the checker claims bf16 only): the limits of the decomposition's own tests.
    got = np.asarray(pallasex._moe_experts_impl(*map(jnp.asarray, (x, top_i, top_w, gate, up, down)), offset, total))
    want = np.asarray(decomposition(x, top_i, top_w, gate, up, down))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert not got[~here.any(-1)].any() and np.abs(got[here.any(-1)]).max(-1).min() > 0
    if case == "a-token-whose-every-choice-is-held-elsewhere":
        assert not here[7].any() and not got[7].any()
    if case == "a-token-with-two-choices-held-here":
        assert here[8].sum() == 2
        silu = lambda a: a / (1 + np.exp(-a))
        both = sum(top_w[8, j] * (silu(x[8] @ gate[e]) * (x[8] @ up[e])) @ down[e]
                   for j, e in enumerate(top_i[8] - offset) if here[8, j])
        np.testing.assert_allclose(got[8], both, rtol=2e-4, atol=2e-5)

    # bf16 through thunder_tpu.jit, where the pallas executor claims it.
    bf16 = lambda a: jnp.asarray(a, jnp.bfloat16)
    operands = (bf16(x), top_i, top_w, bf16(gate), bf16(up), bf16(down))
    fn = thunder_tpu.jit(lambda *a: ttorch.moe_experts(*a, offset, total))
    claimed = np.asarray(fn(*operands).astype(jnp.float32))
    run = thunder_tpu.last_traces(fn)[-1]
    owners = {b.sym.name: b.sym.executor.name for b in run.bound_symbols if b.sym.executor is not None}
    assert owners.get("moe_experts") == "pallas"
    steps = [eqn.primitive.name for eqn in jax.make_jaxpr(run.python_callable())(*operands).eqns]
    assert steps.count("cond") == int(branches)
    defined = np.asarray(decomposition(*operands).astype(jnp.float32))
    assert np.isfinite(claimed).all() and not claimed[~here.any(-1)].any()
    # The decomposition rounds silu and its product to bf16, the claimed form their product once.
    assert np.linalg.norm(claimed - defined) / np.linalg.norm(defined) < 2e-2
    rows = np.linalg.norm(claimed - defined, axis=-1) / (np.linalg.norm(defined, axis=-1) + 1e-2)
    assert rows.max() < 5e-2


def test_the_dispatchs_program_for_the_tpu_moves_each_row_once_each_way(monkeypatch):
    """``_moe_experts_impl`` with every expert held, lowered for the TPU as
    ``test_chip_smoke.py::test_train_step_lowers_for_tpu_with_mosaic_kernels``
    lowers: no array of the buffer's (rows, C) in float32, no gather in fill
    mode (a select between the rows and a NaN constant), no reshape to
    (N, k, C) and no stack of (k, N, C), no mask; the way back is k gathers of
    (N, C) summed in float32 behind a barrier. What the compiler makes of it
    is ``tests/test_mosaic_compiles.py``'s to hold."""
    import jax
    import jax.numpy as jnp

    from thunder_tpu.executors import pallasex

    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    n, k, held, h = 256, 4, 8, 256  # experts of another width than the rows, so a shape names one array
    rows = k * n
    shapes = [((n, C), jnp.bfloat16), ((n, k), jnp.int32), ((n, k), jnp.float32),
              ((held, C, h), jnp.bfloat16), ((held, C, h), jnp.bfloat16), ((held, h, C), jnp.bfloat16)]
    operands = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]

    def lines_of(n_expert):
        fn = jax.jit(lambda *a: pallasex._moe_experts_impl(*a, 0, n_expert))
        return fn.trace(*operands).lower(lowering_platforms=("tpu",)).as_text().splitlines()

    def gathers_of_rows(lines):
        made = [line.rsplit("-> ", 1)[1] for line in lines if "stablehlo.gather" in line and f"x{C}xbf16>" in line]
        return sorted(made)

    lines = lines_of(held)
    assert any("tpu_custom_call" in line for line in lines) and any("optimization_barrier" in line for line in lines)
    wide = [line for line in lines if f"{rows}x{C}x" in line]
    assert wide and not [line for line in wide if "xf32>" in line]
    assert not [line for line in lines if f"{n}x{k}x{C}x" in line or f"{k}x{n}x{C}x" in line]
    assert not [line for line in lines if "stablehlo.select" in line and f"x{C}xbf16>" in line]
    assert gathers_of_rows(lines) == [f"tensor<{rows}x{C}xbf16>"] + [f"tensor<{n}x{C}xbf16>"] * k

    # Some experts held elsewhere: the only select on rows is the mask's, on bf16, a gather of (N, C) at a time
    # (the one pass and the loop of passes both call it); still no float32 of the buffer's shape, and since PR 40
    # nothing at all of the worst case's rows: both branches work on the short buffer.
    lines = lines_of(4 * held)
    selects = [line for line in lines if "stablehlo.select" in line and f"x{C}xbf16>" in line]
    assert selects and all(f"tensor<{n}x{C}xbf16>" in line for line in selects)
    short = pallasex.expert_buffer_rows(n, k, held, 4 * held)
    assert short == 2 * k * n * held // (4 * held) < rows
    # the one pass brings its rows back by k gathers; a pass of the loop by one gather in a loop over the choices
    assert gathers_of_rows(lines) == sorted([f"tensor<{short}x{C}xbf16>"] * 2 + [f"tensor<{n}x{C}xbf16>"] * (k + 1))
    assert sum("stablehlo.while" in line for line in lines) >= 2
    assert not [line for line in lines if f"<{rows}x{C}x" in line or f"<{rows}x{h}x" in line]
    assert not [line for line in lines if f"tensor<{short}x{C}xf32>" in line]
