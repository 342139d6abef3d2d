"""The CPU guards around ``chip_smoke.py`` (ISSUE 21).

The smoke itself proves the chip; these prove, where there is no chip, that
it refuses to run, that its rehearsal passes every stage on the 8-device CPU
mesh, that the compile cache is where it was placed, that an unknown device
has no peak, and that the sharded train step lowers for the TPU with its
kernels as Mosaic calls (the partitioner cannot split one, so each runs
inside ``jax.shard_map``)."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(*args, timeout=600, **env_extra):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, SMOKE, *args], capture_output=True, text=True,
                          timeout=timeout, env=env, cwd=REPO)


def test_refuses_a_cpu():
    proc = _run_smoke()
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    # It named the device and printed no result.
    assert "'platform': 'cpu'" in proc.stdout
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_alone_it_prints_no_result(tmp_path):
    """In a directory that holds the script and nothing else of the repo the
    program cannot be imported: a non-zero exit and no result line."""
    import shutil

    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py", "--rehearse"], capture_output=True,
                          text=True, timeout=120, env=env, cwd=tmp_path)
    assert proc.returncode != 0
    assert "No module named 'thunder_tpu'" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_rehearsal_passes_every_stage():
    proc = _run_smoke("--rehearse")
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    # The result line holds "ok" and "device" and no other key: the chip check
    # refuses anything else (PR 21's first submission carried the stages here).
    device = {"platform": "cpu", "kind": "cpu", "count": 8}
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert lines[-2].startswith("summary: ")
    summary = json.loads(lines[-2][len("summary: "):])
    assert summary["ok"] is True and summary["chip"] is False
    assert summary["device"] == device
    assert sorted(summary["stages"]) == [
        "A_trainer", "B_gqa_rope", "C_dispatcher", "D_compile_cache", "E_four_chips"]
    assert all(stage["ok"] for stage in summary["stages"].values())
    assert "multichip" not in summary  # Stage E ran
    assert summary["stages"]["E_four_chips"]["collectives"]["all-gather"] > 0
    assert summary["stages"]["D_compile_cache"]["dir"] == os.path.join(REPO, ".jax_cache")


class TestCompileCachePlacement:
    """api._ensure_runtime: placed from outside, nothing is set; unset, the
    cache is ``<checkout>/.jax_cache``. A fresh process each, since jax reads
    JAX_COMPILATION_CACHE_DIR once, at import."""

    PROBE = (
        "import jax, thunder_tpu.api as api; api._ensure_runtime(); "
        "print(jax.config.jax_compilation_cache_dir)"
    )

    def _probe(self, **env_extra):
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu", **env_extra)
        if "JAX_COMPILATION_CACHE_DIR" not in env_extra:
            env.pop("JAX_COMPILATION_CACHE_DIR", None)
        proc = subprocess.run([sys.executable, "-c", self.PROBE], capture_output=True,
                              text=True, timeout=120, env=env, cwd=REPO)
        assert proc.returncode == 0, proc.stderr
        return proc.stdout.strip().splitlines()[-1]

    def test_unset_goes_to_the_checkout(self):
        assert self._probe() == os.path.join(REPO, ".jax_cache")

    def test_set_from_outside_is_untouched(self, tmp_path):
        placed = str(tmp_path / "placed")  # not created: the owner's to make
        assert self._probe(JAX_COMPILATION_CACHE_DIR=placed) == placed
        assert not os.path.exists(os.path.join(placed, "native"))

    def test_a_directory_from_outside_is_never_purged(self, tmp_path, monkeypatch):
        import jax

        from thunder_tpu.resilience import compile_cache

        entry = tmp_path / "entry"
        entry.write_bytes(b"x" * 32)
        old = jax.config.jax_compilation_cache_dir
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        try:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert compile_cache.purge_on_error(RuntimeError("deserialize")) is False
            assert entry.exists()
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
            assert compile_cache.purge_on_error(RuntimeError("deserialize")) is True
            assert not entry.exists()
        finally:
            jax.config.update("jax_compilation_cache_dir", old)


class TestPeakLookup:
    def test_known_kinds(self):
        from thunder_tpu.core.devices import peak_tflops, tpu_generation

        assert tpu_generation("TPU v5 lite") == "v5e"
        assert tpu_generation("TPU v5e") == "v5e"
        assert tpu_generation("TPU v5p") == "v5p"
        assert tpu_generation("TPU v4") == "v4"
        assert tpu_generation("TPU v6 lite") == "v6e"
        assert peak_tflops("TPU v5 lite") == 197.0

    @pytest.mark.parametrize("kind", ["cpu", "TPU v9", "NVIDIA A100", ""])
    def test_unknown_kind_raises(self, kind):
        from thunder_tpu.core.devices import peak_tflops, tpu_generation

        with pytest.raises(ValueError, match="no peak is recorded"):
            tpu_generation(kind)
        with pytest.raises(ValueError, match="no peak is recorded"):
            peak_tflops(kind)

    def test_cost_model_keeps_the_cpu_spec_by_name(self):
        from thunder_tpu.analysis.cost import resolve_device_spec

        assert resolve_device_spec(None).name == "cpu"
        assert resolve_device_spec("cpu").name == "cpu"

    @pytest.mark.parametrize("kind,gen,vmem_mib", [("TPU v4", "v4", 16), ("TPU v5 lite", "v5e", 128),
                                                    ("TPU v5p", "v5p", 64), ("TPU v6 lite", "v6e", 128)])
    def test_one_row_a_generation(self, monkeypatch, kind, gen, vmem_mib):
        """core/devices.py is the program's one table: the executor sizes its
        blocks by the row's VMEM, and the cost model prices by its peak."""
        from thunder_tpu.analysis.cost import DEVICE_SPECS
        from thunder_tpu.core.devices import TPU_SPECS, peak_tflops
        from thunder_tpu.executors import pallasex

        assert TPU_SPECS[gen].vmem_bytes == vmem_mib * 1024 * 1024
        monkeypatch.setattr(pallasex, "_device_kind", lambda: kind)
        assert pallasex._ce_vmem_limit() == max(16, vmem_mib // 2) * 1024 * 1024
        assert peak_tflops(kind) == DEVICE_SPECS[gen].peak_flops["bf16"] / 1e12

    def test_the_table_covers_the_cost_models_tpus(self):
        from thunder_tpu.analysis.cost import DEVICE_SPECS
        from thunder_tpu.core.devices import TPU_SPECS

        assert set(TPU_SPECS) == set(DEVICE_SPECS) - {"a100", "cpu"}

    def test_the_benchmarks_table_agrees(self):
        """perfbench/peaks.json is the benchmark's own table (the ledger's
        ``mfu``); every device_kind it has reads the same peak here."""
        from thunder_tpu.core.devices import peak_tflops

        with open(os.path.join(REPO, "perfbench", "peaks.json")) as f:
            peaks = {k: v for k, v in json.load(f).items() if not k.startswith("_")}
        assert peaks
        for kind, row in peaks.items():
            assert peak_tflops(kind) == row["bf16_flops_per_s"] / 1e12

    @pytest.mark.parametrize("kind", ["cpu", "TPU v9"])
    def test_an_unknown_kind_gets_the_default_scope_and_no_benchmark_module(self, monkeypatch, kind):
        from thunder_tpu.executors import pallasex

        monkeypatch.setattr(pallasex, "_device_kind", lambda: kind)
        assert pallasex._ce_vmem_limit() == 16 * 1024 * 1024
        # the executors sit below anything that measures them
        assert not [m for m in sys.modules if "benchmarks" in m and m.startswith("thunder_tpu")]


@pytest.mark.parametrize("axes,rotary", [(None, 1.0), ({"fsdp": 4}, 1.0), ({"dp": 4}, 1.0),
                                         ({"dp": 2, "fsdp": 2, "tp": 2}, 1.0), ({"fsdp": 4}, 0.25)],
                         ids=["one-chip", "fsdp4", "dp4", "dp2-fsdp2-tp2", "fsdp4-partial-rotary"])
def test_train_step_lowers_for_tpu_with_mosaic_kernels(axes, rotary, monkeypatch):
    """``build_train_step`` cross-lowered for the TPU from the CPU host, the
    kernels as Mosaic calls rather than interpreted. Under a mesh this fails
    with "Mosaic kernels cannot be automatically partitioned" unless every
    claimed kernel runs inside ``jax.shard_map``."""
    from thunder_tpu.core import dtypes
    from thunder_tpu.executors import flashex, pallasex
    from thunder_tpu.models import gpt
    from thunder_tpu.parallel import build_train_step, gpt_param_specs, make_mesh, shard_pytree

    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    monkeypatch.setattr(flashex, "_interpret", lambda: False)
    monkeypatch.setattr(pallasex, "_interpret", lambda: False)

    # pythia's shape at a tenth of the width; the rope kernel's full body, and its partial one in place
    cfg = dataclasses.replace(gpt.name_to_config("pythia-410m"), n_layer=2, n_embd=128,
                              n_head=2, intermediate_size=512, rotary_percentage=rotary,
                              vocab_size=512, padded_vocab_size=512)
    B, T = 8, 256
    params = gpt.init_params(cfg, dtype=dtypes.bfloat16, seed=0)
    idx = np.random.RandomState(0).randint(0, cfg.vocab_size, (B, T)).astype(np.int32)
    tgt = np.roll(idx, -1, axis=1).astype(np.int32)
    kwargs = {}
    if axes is not None:
        mesh = make_mesh(**axes)
        specs = gpt_param_specs(cfg, mesh)
        params = shard_pytree(params, mesh, specs)
        kwargs = dict(mesh=mesh, param_specs=specs)
    step, opt, extrace = build_train_step(cfg, params, idx, tgt, return_extrace=True, **kwargs)

    from chip_smoke import kernel_claims

    assert kernel_claims(extrace) == {
        "apply_rope": "pallas", "sdpa_fwd_res": "flash", "sdpa_bwd_res": "flash",
        "cross_entropy": "pallas", "cross_entropy_bwd": "pallas"}
    text = step.trace(params, opt, idx, tgt).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name,cut,sites", [("trinity-tiny", dict(head_dim=128), 4), ("lfm2-tiny", dict(n_embd=256, n_query_groups=2), 1)],
                         ids=["trinity-heads-of-128", "lfm2-two-heads-of-64-a-lane-group"])
def test_forward_with_normed_heads_lowers_for_tpu_with_mosaic_kernels(name, cut, sites, monkeypatch):
    """A forward program whose heads are normed, as the dispatcher rewrites it
    (transforms/attention_layout.py): the projection head-major, one call that
    norms, ropes (or not: Trinity's global layer) and scales q and k, the
    attention call causal or within a window; cross-lowered for the TPU with
    the kernels as Mosaic calls, so that what Mosaic's lowering refuses of the
    normed call shows here."""
    import jax

    from thunder_tpu.api import trace_program
    from thunder_tpu.core import dtypes
    from thunder_tpu.executors import flashex, pallasex
    from thunder_tpu.executors.passes import transform_for_execution
    from thunder_tpu.extend import resolve_executors
    from thunder_tpu.models import gpt
    from thunder_tpu.transforms.attention_layout import FOLDED_TAG, fold_attention_layouts
    from thunder_tpu.transforms.common import dce

    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    monkeypatch.setattr(flashex, "_interpret", lambda: False)
    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    cfg = dataclasses.replace(gpt.name_to_config(name), **cut)
    params = gpt.init_params(cfg, dtype=dtypes.bfloat16, seed=0)
    idx = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 64)).astype(np.int32)
    _, trc = trace_program(lambda p, i: gpt.forward(p, i, cfg), (params, idx), {})
    trc = fold_attention_layouts(dce(trc), resolve_executors(None))
    assert trc.tags[FOLDED_TAG] == sites
    extrace = transform_for_execution(trc, resolve_executors(None))
    src = extrace.python()
    assert src.count("pallas_apply_rope_heads(") == src.count("norm_weight=") == 2 * sites
    flat = jax.tree_util.tree_leaves((params, idx))
    text = jax.jit(extrace.python_callable()).trace(*flat).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


HEAD_SHAPES = {  # registry entry, what is cut, (head size, rotary features)
    # pythia-410m's: 64 wide, 25% rotary, one key-value head a query head
    "pythia-head": ("pythia-410m", dict(n_embd=128, n_head=2), (64, 16)),
    # mistral-7b's: 128 wide, full rotary, grouped key-value heads
    "mistral-head": ("mistral-7b", dict(n_embd=512, n_head=4, n_query_groups=1), (128, 128)),
}


@pytest.mark.parametrize("path", ["jit", "train"])
@pytest.mark.parametrize("head", list(HEAD_SHAPES))
def test_kernels_claimed_at_a_cell_head_shape(head, path, monkeypatch):
    """Two layers at a benchmark configuration's head shape: the counter the
    benchmark reports (``kernels_claimed``) reads a flash call and two rope
    calls a layer and direction (and in a forward program of 64-wide heads the
    call that splits v's), plus the two cross-entropy calls of a train
    step, whether the rotary share is 25% or all of the head; and the step
    lowers for the TPU with its kernels as Mosaic calls."""
    import thunder_tpu
    from perfbench.jobs.gpt_model import kernels_claimed
    from thunder_tpu.core import dtypes
    from thunder_tpu.executors import flashex, pallasex
    from thunder_tpu.models import gpt
    from thunder_tpu.parallel import build_train_step

    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    base, cut, head_shape = HEAD_SHAPES[head]
    cfg = dataclasses.replace(gpt.name_to_config(base), n_layer=2, intermediate_size=512,
                              vocab_size=512, padded_vocab_size=512, **cut)
    assert (cfg.head_size, cfg.rope_n_elem) == head_shape
    B, T = 2, 256
    params = gpt.init_params(cfg, dtype=dtypes.bfloat16, seed=0)
    idx = np.random.RandomState(0).randint(0, cfg.vocab_size, (B, T)).astype(np.int32)
    if path == "jit":
        jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
        assert np.isfinite(np.asarray(jfn(params, idx), dtype=np.float32)).all()
        # 2 flash, 4 rope; and where two heads of 64 share a lane group, the call that gives v's heads
        # a (T, hs) each, one a layer (transforms/attention_layout.py)
        assert kernels_claimed(thunder_tpu.last_traces(jfn)[-1]) == (8 if cfg.head_size == 64 else 6)
        return
    tgt = np.roll(idx, -1, axis=1).astype(np.int32)
    step, opt, extrace = build_train_step(cfg, params, idx, tgt, return_extrace=True)
    assert kernels_claimed(extrace) == 14  # 4 flash, 8 rope, 2 cross-entropy
    monkeypatch.setattr(flashex, "_interpret", lambda: False)
    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    text = step.trace(params, opt, idx, tgt).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("optimizer,axes", [("adamw", {"fsdp": 4}), ("sgd", {"dp": 4}), ("sgd", None)],
                         ids=["adamw-fsdp4", "sgd-dp4", "sgd-one-chip"])
def test_train_step_is_traced_once(optimizer, axes):
    """The optimizer state goes in as the step hands it back. At the parent a
    mesh step traced and compiled twice (the step counter came back with the
    mesh in its type), a minute of XLA compile hidden in the second call."""
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt
    from thunder_tpu.parallel import build_train_step, gpt_param_specs, make_mesh, shard_pytree

    cfg = gpt.name_to_config("llama-tiny")
    params = gpt.init_params(cfg, dtype=dtypes.bfloat16, seed=0)
    idx = np.random.RandomState(0).randint(0, cfg.vocab_size, (8, 64)).astype(np.int32)
    tgt = np.roll(idx, -1, axis=1).astype(np.int32)
    kwargs = {}
    if axes is not None:
        mesh = make_mesh(**axes)
        specs = gpt_param_specs(cfg, mesh)
        params = shard_pytree(params, mesh, specs)
        kwargs = dict(mesh=mesh, param_specs=specs)
    step, opt = build_train_step(cfg, params, idx, tgt, optimizer=optimizer, **kwargs)
    for _ in range(3):
        params, opt, loss = step(params, opt, idx, tgt)
    assert np.isfinite(float(loss))
    assert step._cache_size() == 1
