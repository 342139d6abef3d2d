"""Flash-attention and Pallas cross-entropy executors.

Reference parity: thunder/tests/test_cudnn_executor.py /
test_sdpaex_executor.py / test_triton_ce.py — each executor is exercised
through the full jit pipeline, the claim is asserted in the trace text, and
the result is compared against the decomposed fallback / torch oracle.
"""

import numpy as np
import pytest

import thunder_tpu
import thunder_tpu.torch as ttorch
from thunder_tpu.extend import get_executor, resolve_executors


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() != "cpu"


def _t(*shape, seed=0, scale=0.5):
    rng = np.random.RandomState(seed + sum(shape))
    return (rng.randn(*shape) * scale).astype(np.float32)


def _bt(*shape, seed=0, scale=0.5):
    """bf16 input — the flash executor, like the reference's cudnn/sdpa
    executors, claims half precision only."""
    import jax.numpy as jnp

    return jnp.asarray(_t(*shape, seed=seed, scale=scale), dtype=jnp.bfloat16)


def _f32(x):
    return np.asarray(x, dtype=np.float32)


jax_only = resolve_executors(["jax"])


@pytest.fixture(autouse=True)
def _force_flash_on_cpu(monkeypatch):
    """Exercise the splash kernels via Pallas interpret mode on the CPU mesh."""
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")


class TestFlashAttention:
    def test_fwd_claims_and_matches(self):
        q, k, v = _bt(2, 4, 256, 64), _bt(2, 4, 256, 64, seed=1), _bt(2, 4, 256, 64, seed=2)

        def f(q, k, v):
            return ttorch.scaled_dot_product_attention(q, k, v, is_causal=True)

        fast = thunder_tpu.jit(f)
        slow = thunder_tpu.jit(f, executors=jax_only)
        got = _f32(fast(q, k, v))
        want = _f32(slow(q, k, v))

        src = thunder_tpu.last_traces(fast)[-1].python()
        assert "flash_scaled_dot_product_attention" in src
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=8e-3)

    def test_gqa_fwd(self):
        q = _bt(1, 8, 128, 64)
        k, v = _bt(1, 2, 128, 64, seed=1), _bt(1, 2, 128, 64, seed=2)

        def f(q, k, v):
            return ttorch.scaled_dot_product_attention(q, k, v, is_causal=True, enable_gqa=True)

        fast = thunder_tpu.jit(f)
        slow = thunder_tpu.jit(f, executors=jax_only)
        np.testing.assert_allclose(_f32(fast(q, k, v)), _f32(slow(q, k, v)), rtol=2e-2, atol=8e-3)

    def test_bwd_claims_and_matches(self):
        q, k, v = _bt(1, 2, 128, 64), _bt(1, 2, 128, 64, seed=1), _bt(1, 2, 128, 64, seed=2)

        def loss(q, k, v):
            o = ttorch.scaled_dot_product_attention(q, k, v, is_causal=True)
            return ttorch.sum(o * o)

        fast = thunder_tpu.value_and_grad(loss)
        slow = thunder_tpu.value_and_grad(loss, executors=jax_only)
        lf, gf = fast(q, k, v)
        ls, gs = slow(q, k, v)

        src = thunder_tpu.last_traces(fast)[-1].python()
        assert "flash_sdpa_bwd" in src
        np.testing.assert_allclose(float(lf), float(ls), rtol=2e-2)
        for a, b in zip(gf, gs):
            np.testing.assert_allclose(_f32(a), _f32(b), rtol=5e-2, atol=2e-2)

    def test_unaligned_seq_claims_via_padding(self):
        # 96 not divisible by 128 → in-executor padding keeps the fast path
        # (reference bar: sdpaex.py:49 pads head dims to stay on it).
        q, k, v = _bt(1, 2, 96, 32), _bt(1, 2, 96, 32, seed=1), _bt(1, 2, 96, 32, seed=2)

        def f(q, k, v):
            return ttorch.scaled_dot_product_attention(q, k, v, is_causal=True)

        jf = thunder_tpu.jit(f)
        got = _f32(jf(q, k, v))
        src = thunder_tpu.last_traces(jf)[-1].python()
        assert "flash_scaled_dot_product_attention" in src
        want = _f32(thunder_tpu.jit(f, executors=jax_only)(q, k, v))
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=8e-3)

    def test_unequal_q_kv_lengths(self):
        # Cross/kv-cache shape: Tq < Tkv, bottom-right causal alignment.
        q = _bt(1, 2, 128, 32)
        k, v = _bt(1, 2, 256, 32, seed=1), _bt(1, 2, 256, 32, seed=2)

        def f(q, k, v):
            return ttorch.scaled_dot_product_attention(q, k, v, is_causal=True)

        jf = thunder_tpu.jit(f)
        got = _f32(jf(q, k, v))
        assert "flash_scaled_dot_product_attention" in thunder_tpu.last_traces(jf)[-1].python()
        want = _f32(thunder_tpu.jit(f, executors=jax_only)(q, k, v))
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=8e-3)

    def test_unclaimed_on_large_head_dim(self):
        q, k, v = _bt(1, 2, 128, 288), _bt(1, 2, 128, 288, seed=1), _bt(1, 2, 128, 288, seed=2)

        def f(q, k, v):
            return ttorch.scaled_dot_product_attention(q, k, v, is_causal=True)

        jf = thunder_tpu.jit(f)
        jf(q, k, v)
        src = thunder_tpu.last_traces(jf)[-1].python()
        assert "flash_scaled_dot_product_attention" not in src


class TestFlashMasks:
    """Mask-capable flash claims (reference bar: cudnnex.py:81-92 builds its
    SDPA graph with an attn-mask bias input)."""

    B, H, T, D = 2, 2, 128, 32

    def _qkv(self):
        return (_bt(self.B, self.H, self.T, self.D),
                _bt(self.B, self.H, self.T, self.D, seed=1),
                _bt(self.B, self.H, self.T, self.D, seed=2))

    @staticmethod
    def _f(q, k, v, m):
        return ttorch.scaled_dot_product_attention(q, k, v, attn_mask=m)

    def test_bool_keypad_mask(self):
        q, k, v = self._qkv()
        m = np.ones((self.B, 1, 1, self.T), dtype=bool)
        m[0, :, :, :40] = False  # left padding
        jf = thunder_tpu.jit(self._f)
        got = _f32(jf(q, k, v, m))
        assert "flash_scaled_dot_product_attention" in thunder_tpu.last_traces(jf)[-1].python()
        want = _f32(thunder_tpu.jit(self._f, executors=jax_only)(q, k, v, m))
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=8e-3)

    def test_additive_keypad_mask_runtime_verified(self):
        q, k, v = self._qkv()
        m = np.zeros((self.B, 1, 1, self.T), dtype=np.float32)
        m[0, :, :, :40] = np.finfo(np.float32).min
        jf = thunder_tpu.jit(self._f)
        got = _f32(jf(q, k, v, m))
        assert "flash_scaled_dot_product_attention" in thunder_tpu.last_traces(jf)[-1].python()
        want = _f32(thunder_tpu.jit(self._f, executors=jax_only)(q, k, v, m))
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=8e-3)

    def test_bool_keypad_all_masked_row_safe_softmax(self):
        # ADVICE r4: a batch row whose every key is masked must produce
        # torch's safe-softmax zeros, not splash's kernel-defined output —
        # the runtime guard routes it to the exact decomposition.
        q, k, v = self._qkv()
        m = np.ones((self.B, 1, 1, self.T), dtype=bool)
        m[0] = False  # batch 0: no valid key at all
        jf = thunder_tpu.jit(self._f)
        got = _f32(jf(q, k, v, m))
        want = _f32(thunder_tpu.jit(self._f, executors=jax_only)(q, k, v, m))
        np.testing.assert_allclose(got[0], 0.0, atol=1e-6)
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=8e-3)

    def test_additive_keypad_all_masked_row_shift_invariance(self):
        # ADVICE r4: an additive row that is uniformly <= -1e9 passes the
        # 0-or-very-negative check, but softmax shift-invariance means the
        # exact path attends UNIFORMLY while segment-ids would mask every
        # key. The non-empty-row guard must force the exact branch.
        q, k, v = self._qkv()
        m = np.zeros((self.B, 1, 1, self.T), dtype=np.float32)
        m[0] = np.finfo(np.float32).min  # whole row "masked"
        jf = thunder_tpu.jit(self._f)
        got = _f32(jf(q, k, v, m))
        want = _f32(thunder_tpu.jit(self._f, executors=jax_only)(q, k, v, m))
        # batch 0 attends uniformly (mean over values), NOT zeros
        np.testing.assert_allclose(got[0], _f32(v).mean(axis=-2, keepdims=True)[0]
                                   * np.ones_like(got[0]), rtol=2e-2, atol=8e-3)
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=8e-3)

    def test_additive_bias_falls_back_exactly(self):
        # A real bias (ALiBi-style) fails runtime verification: the cond's
        # decomposed branch must produce the exact decomposition result.
        q, k, v = self._qkv()
        m = (np.random.RandomState(3).randn(self.B, 1, 1, self.T) * 0.1).astype(np.float32)
        jf = thunder_tpu.jit(self._f)
        got = _f32(jf(q, k, v, m))
        want = _f32(thunder_tpu.jit(self._f, executors=jax_only)(q, k, v, m))
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=8e-3)

    def _hf_mask(self, pad):
        """HF-style 4D additive causal+padding mask incl. _unmask_unattended."""
        B, T = self.B, self.T
        MIN = np.finfo(np.float32).min
        m4 = np.zeros((B, 1, T, T), dtype=np.float32)
        tri = np.triu(np.ones((T, T), dtype=bool), k=1)
        for b in range(B):
            mb = np.zeros((T, T), dtype=np.float32)
            mb[tri] = MIN
            mb[:, pad[b]] = MIN
            fully = (mb == MIN).all(axis=1)
            mb[fully, :] = 0.0
            m4[b, 0] = mb
        return m4

    def test_hf_4d_causal_padding_mask(self):
        q, k, v = self._qkv()
        pad = np.zeros((self.B, self.T), dtype=bool)
        pad[0, :40] = True
        m4 = self._hf_mask(pad)
        jf = thunder_tpu.jit(self._f)
        got = _f32(jf(q, k, v, m4))
        assert "flash_scaled_dot_product_attention" in thunder_tpu.last_traces(jf)[-1].python()
        want = _f32(thunder_tpu.jit(self._f, executors=jax_only)(q, k, v, m4))
        # flash leaves pad-query rows as finite garbage; compare valid rows
        for b in range(self.B):
            rows = ~pad[b]
            np.testing.assert_allclose(got[b][:, rows], want[b][:, rows], rtol=2e-2, atol=8e-3)

    def test_hf_4d_mask_grads(self):
        q, k, v = self._qkv()
        pad = np.zeros((self.B, self.T), dtype=bool)
        pad[0, :40] = True
        m4 = self._hf_mask(pad)
        w = np.ones((self.B, 1, self.T, 1), dtype=np.float32)
        w[0, :, pad[0], :] = 0.0  # zero cotangents at garbage rows

        def loss(q, k, v, m, w):
            o = ttorch.scaled_dot_product_attention(q, k, v, attn_mask=m)
            return ttorch.sum(o * o * w)

        vg_f = thunder_tpu.value_and_grad(loss)
        vg_s = thunder_tpu.value_and_grad(loss, executors=jax_only)
        lf, gf = vg_f(q, k, v, m4, w)
        ls, gs = vg_s(q, k, v, m4, w)
        assert "flash_sdpa_bwd" in thunder_tpu.last_traces(vg_f)[-1].python()
        np.testing.assert_allclose(float(lf), float(ls), rtol=2e-2)
        for name, a, b in zip("qkv", gf[:3], gs[:3]):
            np.testing.assert_allclose(_f32(a), _f32(b), rtol=5e-2, atol=2e-2,
                                       err_msg=f"d{name}")


class TestPallasCrossEntropy:
    def test_fwd_claims_and_matches_torch(self):
        torch = pytest.importorskip("torch")
        import torch.nn.functional as F

        logits = _t(32, 256, scale=2.0)
        target = np.random.RandomState(0).randint(0, 256, (32,)).astype(np.int64)
        target[3] = -100

        jf = thunder_tpu.jit(lambda l, t: ttorch.cross_entropy(l, t))
        got = float(np.asarray(jf(logits, target)))
        src = thunder_tpu.last_traces(jf)[-1].python()
        assert "pallas_cross_entropy" in src

        want = float(F.cross_entropy(torch.from_numpy(logits), torch.from_numpy(target)))
        np.testing.assert_allclose(got, want, rtol=1e-3)

    def test_bwd_claims_and_matches_torch(self):
        torch = pytest.importorskip("torch")
        import torch.nn.functional as F

        logits = _t(32, 256, scale=2.0)
        target = np.random.RandomState(1).randint(0, 256, (32,)).astype(np.int64)

        vg = thunder_tpu.value_and_grad(lambda l, t: ttorch.cross_entropy(l, t))
        loss, (dl,) = vg(logits, target)
        src = thunder_tpu.last_traces(vg)[-1].python()
        assert "pallas_cross_entropy_bwd" in src

        tl = torch.from_numpy(logits).requires_grad_(True)
        F.cross_entropy(tl, torch.from_numpy(target)).backward()
        np.testing.assert_allclose(np.asarray(dl), tl.grad.numpy(), rtol=1e-3, atol=1e-5)

    def test_sum_reduction(self):
        torch = pytest.importorskip("torch")
        import torch.nn.functional as F

        logits = _t(16, 128, scale=2.0)
        target = np.random.RandomState(2).randint(0, 128, (16,)).astype(np.int64)
        jf = thunder_tpu.jit(lambda l, t: ttorch.cross_entropy(l, t, reduction="sum"))
        got = float(np.asarray(jf(logits, target)))
        want = float(F.cross_entropy(torch.from_numpy(logits), torch.from_numpy(target), reduction="sum"))
        np.testing.assert_allclose(got, want, rtol=1e-3)

    def test_per_row_losses_are_claimed_and_ignored_rows_read_zero(self):
        torch = pytest.importorskip("torch")
        import torch.nn.functional as F

        logits = _t(32, 256, scale=2.0)
        target = np.random.RandomState(4).randint(0, 256, (32,)).astype(np.int64)
        target[[3, 17]] = -100
        jf = thunder_tpu.jit(lambda l, t: ttorch.cross_entropy(l, t, reduction="none"))
        got = np.asarray(jf(logits, target))
        assert "pallas_cross_entropy(" in thunder_tpu.last_traces(jf)[-1].python()
        want = F.cross_entropy(torch.from_numpy(logits), torch.from_numpy(target), reduction="none").numpy()
        assert got.shape == (32,) and got[3] == got[17] == 0.0
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        decomposed = thunder_tpu.jit(lambda l, t: ttorch.cross_entropy(l, t, reduction="none"), executors=jax_only)
        np.testing.assert_allclose(got, np.asarray(decomposed(logits, target)), rtol=1e-5, atol=1e-6)

    def test_a_per_row_cotangent_is_claimed_by_the_backward_kernel(self):
        """The trace VJP of a per-row loss is one ``torch.cross_entropy_bwd`` with
        the row's own cotangent, the kernel claims it, and the gradient is the
        decomposition's (and torch's), ignored rows zero."""
        torch = pytest.importorskip("torch")
        import torch.nn.functional as F

        logits = _t(32, 256, scale=2.0)
        target = np.random.RandomState(5).randint(0, 256, (32,)).astype(np.int64)
        target[9] = -100
        scale = np.random.RandomState(6).rand(32).astype(np.float32)

        def loss(l, t, w):
            return ttorch.sum(ttorch.cross_entropy(l, t, reduction="none") * w)

        vg = thunder_tpu.value_and_grad(loss)
        value, (dl, _) = vg(logits, target, scale)
        trc = thunder_tpu.last_traces(vg)[-1]
        bwd = [b for b in trc.bound_symbols if b.sym.id == "torch.cross_entropy_bwd"]
        assert len(bwd) == 1 and bwd[0].sym.executor.name == "pallas" and bwd[0].args[0].shape == (32,)
        assert not any("log_softmax" in b.sym.name for b in trc.bound_symbols)
        tl = torch.from_numpy(logits).requires_grad_(True)
        (F.cross_entropy(tl, torch.from_numpy(target), reduction="none") * torch.from_numpy(scale)).sum().backward()
        np.testing.assert_allclose(np.asarray(dl), tl.grad.numpy(), rtol=1e-4, atol=1e-6)
        assert not np.asarray(dl)[9].any()
        decomposed = thunder_tpu.value_and_grad(loss, executors=jax_only)
        dv, (dd, _) = decomposed(logits, target, scale)
        np.testing.assert_allclose(np.asarray(dl), np.asarray(dd), rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(float(value), float(dv), rtol=1e-6)

    @pytest.mark.parametrize("reduction,claimed", [("mean", True), ("sum", True), ("none", True), ("batchmean", False)])
    def test_the_checkers_take_each_reduction_the_kernels_compute(self, reduction, claimed):
        from thunder_tpu.executors import pallasex

        logits, target = _t(32, 256), np.zeros((32,), dtype=np.int64)
        assert bool(pallasex._ce_checker(logits, target, reduction=reduction)) == claimed
        assert bool(pallasex._ce_bwd_checker(np.ones(32, np.float32), logits, target, reduction=reduction)) == claimed

    def test_unclaimed_on_bad_vocab(self):
        logits = _t(16, 96)  # 96 % 128 != 0
        target = np.zeros((16,), dtype=np.int64)
        jf = thunder_tpu.jit(lambda l, t: ttorch.cross_entropy(l, t))
        jf(logits, target)
        src = thunder_tpu.last_traces(jf)[-1].python()
        assert "pallas_cross_entropy" not in src

    @pytest.mark.parametrize("kind,mib,rows_bf16", [
        ("TPU v5 lite", 64, 64), ("TPU v6 lite", 64, 64), ("TPU v5", 32, 32), ("TPU v4", 16, 16),
        ("cpu", 16, 16), ("TPU v9", 16, 16),  # no generation of the table: the default scope
    ])
    def test_the_block_follows_the_vmem_of_the_devices_generation(self, monkeypatch, kind, mib, rows_bf16):
        from thunder_tpu.executors import pallasex

        monkeypatch.setattr(pallasex, "_device_kind", lambda: kind)
        assert pallasex._ce_vmem_limit() == mib * 1024 * 1024
        assert pallasex._ce_block_n(8192, 50304, 2) == rows_bf16  # pythia-410m.train's logits
        # a vocabulary of 163840 in 16 rows of bfloat16: claimed where there is room, declined where not
        assert (pallasex._ce_block_n(4096, 163840, 2) is None) == (mib == 16)


class TestCrossEntropyUpcastFold:
    """transforms/cross_entropy_upcast.py: where a program upcasts bf16 logits
    for cross-entropy alone, the claimed pair reads the bf16 logits and writes a
    bf16 gradient; loss and gradients are the ones of the program as written."""

    N = 32
    ROW_WEIGHTS = np.linspace(0.25, 2.0, N, dtype=np.float32)

    def _program(self, V, as_written_logits=lambda l: l.float(), **ce):
        x, w = _bt(4, 8, 64), _bt(V, 64, seed=1)
        target = np.random.RandomState(V).randint(0, V, (self.N,)).astype(np.int64)

        def loss(x, w, t):
            logits = as_written_logits(ttorch.linear(x, w))
            out = ttorch.cross_entropy(ttorch.reshape(logits, (self.N, V)), t, **ce)
            return out if ce.get("reduction") != "none" else ttorch.sum(out * self.ROW_WEIGHTS)  # a cotangent a row

        return loss, (x, w, target)

    @staticmethod
    def _transforms_extra(fn):
        program = thunder_tpu.compile_stats(fn).cache_entries[-1].compile_id
        (record,) = [r for r in thunder_tpu.compile_phases()
                     if r["program"] == program and r["phase"] == "transforms"]
        return record

    @staticmethod
    def _as_written(monkeypatch):
        from thunder_tpu import pipeline
        from thunder_tpu.transforms.cross_entropy_upcast import fold_cross_entropy_upcasts

        monkeypatch.setattr(pipeline, "REWRITES", tuple(r for r in pipeline.REWRITES if r is not fold_cross_entropy_upcasts))

    @pytest.mark.parametrize("case", ["several_chunks", "one_chunk", "ignored_rows", "sum", "none", "none_ignored_rows"])
    def test_folded_pair_equals_the_program_as_written_bit_for_bit(self, monkeypatch, case):
        from thunder_tpu.executors import pallasex

        V = 256
        if case == "several_chunks":  # two whole chunks and a ragged one
            V = 640
            monkeypatch.setattr(pallasex, "_CE_CHUNK", 256)
        loss, args = self._program(V, **({"reduction": case.split("_")[0]} if case.startswith(("sum", "none")) else {}))
        if case.endswith("ignored_rows"):
            args[2][[0, 5, 31]] = -100

        folded = thunder_tpu.value_and_grad(loss, argnums=(0, 1))
        lf, gf = folded(*args)
        src = thunder_tpu.last_traces(folded)[-1].python()
        assert "pallas_cross_entropy(" in src and "pallas_cross_entropy_bwd(" in src
        assert self._transforms_extra(folded)["cross_entropy_upcasts_folded"] == 1

        with monkeypatch.context() as m:
            self._as_written(m)
            written = thunder_tpu.value_and_grad(loss, argnums=(0, 1))
            lw, gw = written(*args)
        assert "cross_entropy_upcasts_folded" not in self._transforms_extra(written)
        assert lf.dtype == lw.dtype == np.float32
        assert np.asarray(lf).tobytes() == np.asarray(lw).tobytes()
        for a, b in zip(gf, gw):
            assert a.dtype == b.dtype and np.array_equal(_f32(a), _f32(b))

    def test_folded_joint_trace_keeps_bf16_logits_and_no_float32_copy(self):
        from thunder_tpu.core import dtypes

        V = 256
        loss, args = self._program(V)
        vg = thunder_tpu.value_and_grad(loss, argnums=(0, 1))
        vg(*args)
        bsyms = thunder_tpu.last_traces(vg)[-1].bound_symbols
        at = {b.sym.name: i for i, b in enumerate(bsyms) if "cross_entropy" in b.sym.name}
        fwd, bwd = at["cross_entropy"], at["cross_entropy_bwd"]
        made = {p.name: p for b in bsyms[:fwd + 1] for p in b.flat_proxy_outs}
        saved = {p.name: p for b in bsyms[bwd:] for p in b.flat_proxy_args if p.name in made}
        wide = [p for p in saved.values() if p.dtype == dtypes.float32 and V in p.shape]
        assert not wide
        logits = bsyms[bwd].args[1]
        assert logits.name in saved and logits.dtype == dtypes.bfloat16 and logits.shape == (self.N, V)
        assert bsyms[bwd].output.dtype == dtypes.bfloat16
        assert not any(b.sym.name == "convert_element_type" and V in getattr(b.output, "shape", ())
                       for b in bsyms)

    @pytest.mark.parametrize("case", ["second_reader", "float32_model", "label_smoothing", "vocabulary_off_the_lanes"])
    def test_the_pass_leaves_alone(self, case):
        V = 96 if case == "vocabulary_off_the_lanes" else 256
        if case == "second_reader":
            def loss(x, w, t):
                logits = ttorch.linear(x, w).float()
                return ttorch.cross_entropy(ttorch.reshape(logits, (self.N, V)), t) + 1e-4 * ttorch.sum(logits * logits)
            args = self._program(V)[1]
        elif case == "float32_model":
            loss, (x, w, t) = self._program(V)
            args = (_f32(x), _f32(w), t)
        elif case == "label_smoothing":
            loss, args = self._program(V, label_smoothing=0.1)
        else:
            loss, args = self._program(V)
        vg = thunder_tpu.value_and_grad(loss, argnums=(0, 1))
        lf, gf = vg(*args)
        assert self._transforms_extra(vg)["cross_entropy_upcasts_folded"] == 0
        src = thunder_tpu.last_traces(vg)[-1].python()
        claimed = case in ("second_reader", "float32_model")  # on float32 logits, as before
        assert ("pallas_cross_entropy(" in src) == claimed
        slow = thunder_tpu.value_and_grad(loss, argnums=(0, 1), executors=jax_only)
        ls, gs = slow(*args)
        np.testing.assert_allclose(float(lf), float(ls), rtol=1e-5)
        for a, b in zip(gf, gs):
            assert a.dtype == b.dtype
            np.testing.assert_allclose(_f32(a), _f32(b), rtol=5e-2, atol=1e-3)

    def test_counter_is_one_for_loss_fn_and_zero_for_forward(self):
        import time

        from thunder_tpu.core import dtypes
        from thunder_tpu.models import gpt as m
        from thunder_tpu.parallel import build_train_step

        cfg = m.GPTConfig(
            name="fold-test", block_size=16, vocab_size=128, padded_vocab_size=128,
            n_layer=1, n_head=2, n_embd=32, rotary_percentage=1.0, parallel_residual=False,
            bias=False, norm_class="RMSNorm", mlp_class="LLaMAMLP", intermediate_size=64,
        )
        params = m.init_params(cfg, dtype=dtypes.bfloat16, seed=0)
        idx = np.random.RandomState(0).randint(0, 128, (2, 16)).astype(np.int32)
        tgt = np.roll(idx, -1, 1).astype(np.int32)

        mark = time.perf_counter()
        step, opt, extrace = build_train_step(cfg, params, idx, tgt, return_extrace=True)
        (record,) = [r for r in thunder_tpu.compile_phases() if r["at"] >= mark and r["phase"] == "transforms"]
        assert record["cross_entropy_upcasts_folded"] == 1
        bwd = next(b for b in extrace.bound_symbols if b.sym.name == "cross_entropy_bwd")
        assert bwd.sym.executor.name == "pallas" and bwd.args[1].dtype == dtypes.bfloat16
        _, _, loss = step(params, opt, idx, tgt)
        assert np.isfinite(float(loss))

        jfn = thunder_tpu.jit(lambda p, i: m.forward(p, i, cfg))
        jfn(m.init_params(cfg, dtype=dtypes.bfloat16, seed=0), idx)
        assert self._transforms_extra(jfn)["cross_entropy_upcasts_folded"] == 0

    def test_a_claim_that_fails_after_the_fold_computes_the_program_as_written(self, monkeypatch):
        """The folded symbols decompose into the convert and the pair as written."""
        V = 256
        loss, args = self._program(V)
        want_l, want_g = thunder_tpu.value_and_grad(loss, argnums=(0, 1), executors=jax_only)(*args)

        pallas = get_executor("pallas")
        vg = thunder_tpu.value_and_grad(loss, argnums=(0, 1))
        asked = {"n": 0}
        real = pallas.can_execute

        def out_of_fuel(bsym):  # the pass asks the checkers; the claiming pass asks here
            asked["n"] += "cross_entropy" in bsym.sym.name
            return False if "cross_entropy" in bsym.sym.name else real(bsym)

        monkeypatch.setattr(pallas, "can_execute", out_of_fuel)
        got_l, got_g = vg(*args)
        assert asked["n"] >= 2 and self._transforms_extra(vg)["cross_entropy_upcasts_folded"] == 1
        assert "pallas_cross_entropy" not in thunder_tpu.last_traces(vg)[-1].python()
        np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-6)
        for a, b in zip(got_g, want_g):
            assert np.array_equal(_f32(a), _f32(b))


class TestEndToEndModel:
    def test_model_training_uses_kernels(self):
        """A flash-eligible model config trains with both kernels claimed."""
        from thunder_tpu.core import dtypes
        from thunder_tpu.models import gpt as m

        cfg = m.GPTConfig(
            name="kernel-test", block_size=128, vocab_size=128, padded_vocab_size=128,
            n_layer=2, n_head=2, n_embd=64, rotary_percentage=1.0, parallel_residual=False,
            bias=False, norm_class="RMSNorm", mlp_class="LLaMAMLP", intermediate_size=128,
        )
        params = m.init_params(cfg, dtype=dtypes.bfloat16, seed=0)
        idx = np.random.RandomState(0).randint(0, 128, (2, 128)).astype(np.int32)
        tgt = np.roll(idx, -1, 1).astype(np.int32)

        vg = thunder_tpu.value_and_grad(lambda p, i, t: m.loss_fn(p, i, t, cfg))
        loss, grads = vg(params, idx, tgt)
        src = thunder_tpu.last_traces(vg)[-1].python()
        # the attention-residual pass upgrades eligible pairs to the
        # no-recompute composites
        assert "flash_sdpa_fwd_res" in src or "flash_scaled_dot_product_attention" in src
        assert "flash_sdpa_bwd" in src  # matches both sdpa_bwd and sdpa_bwd_res
        assert "pallas_cross_entropy" in src
        assert np.isfinite(float(np.asarray(loss)))

        slow = thunder_tpu.value_and_grad(
            lambda p, i, t: m.loss_fn(p, i, t, cfg), executors=jax_only
        )
        loss_s, grads_s = slow(params, idx, tgt)
        np.testing.assert_allclose(float(np.asarray(loss)), float(np.asarray(loss_s)), rtol=1e-2)


class TestAttentionResiduals:
    """The attention-residual pass (transforms/attention_residuals.py,
    reference: cudnnex.py:375 saved softmax stats): sdpa pairs rewrite to
    fwd_res/bwd_res so the flash backward runs WITHOUT forward recompute."""

    def _qkv(self):
        return (_bt(2, 2, 128, 32), _bt(2, 2, 128, 32, seed=1), _bt(2, 2, 128, 32, seed=2))

    def test_joint_pipeline_claims_and_matches(self):
        q, k, v = self._qkv()

        def loss(q, k, v):
            o = ttorch.scaled_dot_product_attention(q, k, v, is_causal=True)
            return ttorch.sum(o.float() * o.float())

        fast = thunder_tpu.value_and_grad(loss)
        slow = thunder_tpu.value_and_grad(loss, executors=jax_only)
        lf, gf = fast(q, k, v)
        ls, gs = slow(q, k, v)
        src = thunder_tpu.last_traces(fast)[-1].python()
        assert "flash_sdpa_fwd_res" in src and "flash_sdpa_bwd_res" in src
        assert "flash_sdpa_bwd(" not in src  # recompute composite gone
        np.testing.assert_allclose(float(lf), float(ls), rtol=2e-2)
        for n, a, b in zip("qkv", gf, gs):
            np.testing.assert_allclose(_f32(a), _f32(b), rtol=5e-2, atol=2e-2, err_msg=n)

    def test_split_pipeline_matches(self):
        import jax.numpy as jnp

        from thunder_tpu.api import trace_program
        from thunder_tpu.core import dtypes
        from thunder_tpu.core.pytree import tree_flatten
        from thunder_tpu.executors.passes import transform_for_execution
        from thunder_tpu.models import gpt as m
        from thunder_tpu.transforms.attention_residuals import save_sdpa_residuals
        from thunder_tpu.transforms.autodiff import forward_and_backward_from_trace
        from thunder_tpu.transforms.common import cse, dce
        from thunder_tpu.transforms.rematerialization import rematerialize_forward_and_backward

        cfg = m.GPTConfig(
            name="res-test", block_size=128, vocab_size=128, padded_vocab_size=128,
            n_layer=2, n_head=2, n_embd=64, rotary_percentage=1.0, parallel_residual=False,
            bias=False, norm_class="RMSNorm", mlp_class="LLaMAMLP", intermediate_size=128,
        )
        params = m.init_params(cfg, dtype=dtypes.bfloat16, seed=0)
        idx = np.random.RandomState(0).randint(0, 128, (2, 128)).astype(np.int32)
        tgt = np.roll(idx, -1, 1).astype(np.int32)
        flat_p, _ = tree_flatten((params,))

        def build(executors, use_pass):
            _, comp = trace_program(lambda p, i, t: m.loss_fn(p, i, t, cfg), (params, idx, tgt), {})
            comp = cse(dce(comp))
            fw, bw = forward_and_backward_from_trace(comp)
            if use_pass:
                fw, bw = save_sdpa_residuals(fw, bw, executors)
            fw, bw = rematerialize_forward_and_backward(fw, bw)
            bw_ex = transform_for_execution(bw, executors)
            return (transform_for_execution(fw, executors).python_callable(),
                    bw_ex.python_callable(), bw_ex.python())

        fast = resolve_executors(None)
        fwf, bwf, bw_src = build(fast, True)
        assert "flash_sdpa_bwd_res" in bw_src and "flash_sdpa_bwd(" not in bw_src
        loss_f, saved_f = fwf(*flat_p, idx, tgt)
        grads_f = bwf(*saved_f, jnp.ones((), dtype=jnp.float32))

        fws, bws, _ = build(jax_only, False)
        loss_s, saved_s = fws(*flat_p, idx, tgt)
        grads_s = bws(*saved_s, jnp.ones((), dtype=jnp.float32))

        np.testing.assert_allclose(float(np.asarray(loss_f)), float(np.asarray(loss_s)), rtol=1e-2)
        for a, b in zip(grads_f, grads_s):
            np.testing.assert_allclose(_f32(a), _f32(b), rtol=5e-2, atol=2e-2)


class TestPallasRope:
    """Fused rotate-half ROPE kernel (pallasex): the decomposed form is
    lane-misaligned at odd head sizes (e.g. 100) and with partial rotary
    (pythia's 16 of 64); bwd is the same kernel with -sin via the
    torch.apply_rope VJP rule."""

    # (hs, n): pythia, a half share, 50-lane halves beside 28 lanes that pass, phi-2, full rotary twice
    SHARES = [(64, 16), (64, 32), (128, 100), (80, 32), (128, 128), (100, 100)]

    def _inputs(self, D, n, B=2, H=3, T=64):
        import jax.numpy as jnp

        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32), dtype=jnp.bfloat16)
        theta = (10000.0 ** (np.arange(0, n // 2) * -2.0 / n)).astype(np.float32)
        freqs = np.arange(T, dtype=np.float32)[:, None] * theta[None, :]
        emb = np.concatenate([freqs, freqs], 1)
        cos = jnp.asarray(np.cos(emb), dtype=jnp.bfloat16)
        sin = jnp.asarray(np.sin(emb), dtype=jnp.bfloat16)
        return x, cos, sin

    @pytest.mark.parametrize("hs,n", SHARES)
    def test_fwd_claims_and_matches(self, hs, n):
        x, cos, sin = self._inputs(D=hs, n=n)
        f = lambda x, c, s: ttorch.apply_rope(x, c, s)
        fast = thunder_tpu.jit(f)
        got = _f32(fast(x, cos, sin))
        assert "pallas_apply_rope" in thunder_tpu.last_traces(fast)[-1].python()
        want = _f32(thunder_tpu.jit(f, executors=jax_only)(x, cos, sin))
        np.testing.assert_allclose(got, want, rtol=3e-2, atol=4e-2)
        # the features beyond n pass through untouched
        np.testing.assert_array_equal(got[..., n:], _f32(x)[..., n:])

    @pytest.mark.parametrize("hs,n", SHARES)
    def test_bwd_same_kernel(self, hs, n):
        x, cos, sin = self._inputs(D=hs, n=n)

        def loss(x, c, s):
            o = ttorch.apply_rope(x, c, s)
            return ttorch.sum(o.float() * o.float())

        vgf = thunder_tpu.value_and_grad(loss)
        vgs = thunder_tpu.value_and_grad(loss, executors=jax_only)
        lf, gf = vgf(x, cos, sin)
        ls, gs = vgs(x, cos, sin)
        # forward and backward, one kernel each
        assert thunder_tpu.last_traces(vgf)[-1].python().count("= pallas_apply_rope(") == 2
        np.testing.assert_allclose(float(lf), float(ls), rtol=2e-2)
        np.testing.assert_allclose(_f32(gf[0]), _f32(gs[0]), rtol=5e-2, atol=8e-2)
        np.testing.assert_array_equal(_f32(gf[0])[..., n:], _f32(gs[0])[..., n:])

    @pytest.mark.parametrize("case", ["odd_n", "mixed_dtypes", "float16"])
    def test_declined_decomposes(self, case):
        """What Mosaic does not lower for partial rotary is declined at claim
        time: there is no fallback at run time."""
        import jax.numpy as jnp

        x, cos, sin = self._inputs(D=64, n=16)
        if case == "odd_n":
            cos, sin = cos[:, :15], sin[:, :15]
        elif case == "mixed_dtypes":
            cos, sin = cos.astype(jnp.float32), sin.astype(jnp.float32)
        else:  # no float16 matmul on the v5e
            x, cos, sin = (a.astype(jnp.float16) for a in (x, cos, sin))
        f = lambda x, c, s: ttorch.apply_rope(x, c, s)
        jf = thunder_tpu.jit(f)
        got = jf(x, cos, sin)
        assert "pallas_apply_rope" not in thunder_tpu.last_traces(jf)[-1].python()
        want = thunder_tpu.jit(f, executors=jax_only)(x, cos, sin)
        assert got.dtype == want.dtype
        np.testing.assert_allclose(_f32(got), _f32(want), rtol=3e-2, atol=4e-2)
