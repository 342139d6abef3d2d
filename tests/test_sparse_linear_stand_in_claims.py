"""The test of ``tests/perfbench/test_pb_sparse_linear_job.py`` that pins the
``minicpm-sala.fwd-t32k`` stand-in's ``kernels_claimed``, whole, at the count
the program gives since PR 41: 9, where the parent's gave 6. That file is the
benchmark's and not a program PR's to edit, so ``tests/conftest.py`` expects
its test to fail and this one stands for it until a ``benchmark`` PR moves the
assert there (or derives the count from the trace) and takes this file out."""

import importlib
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # the checkout, for ``perfbench``

from perfbench import checks_sparse_linear, manifest  # noqa: E402

CELL = "minicpm-sala.fwd-t32k"
LINEAR_LAYERS = 3  # of the stand-in's four; the first is sparse
# a linear layer's q and k out of the head-major projection, normed and roped (the two rope calls before PR 41), and
# v's split: the stand-in's heads are 64 wide and lie two a lane group, where the cell's 128 make v a plain slice
CLAIMED = 3 * LINEAR_LAYERS


def rehearsal_job(seed):
    cell = manifest.load_cell(CELL)
    job = importlib.import_module(f"perfbench.jobs.{cell.traffic['job']}").Job(
        cell, seed=seed, platform="cpu", rehearse=True)
    job.rng = np.random.RandomState(seed)
    return job


def test_the_check_passes_the_system_and_fails_the_reference_at_float8(monkeypatch):
    import thunder_tpu

    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    monkeypatch.setenv("PERFBENCH_CHECK_PRECISIONS", "float8_e4m3fn,bfloat16")
    job = rehearsal_job(seed=2**31 + 11)
    job.setup()
    assert job.counters["kernels_claimed"] == CLAIMED
    # the linear layers' sites and not the sparse layer's: its consumer is none of the pass's
    (record,) = [r for r in thunder_tpu.compile_phases()
                 if r["program"] == job.entry.compile_id and r["phase"] == "transforms"]
    assert record["attention_layouts_folded"] == LINEAR_LAYERS
    src = thunder_tpu.last_traces(job.jfn)[-1].python()
    assert src.count("pallas_apply_rope_heads(") == 2 * LINEAR_LAYERS and src.count("pallas_split_heads(") == LINEAR_LAYERS
    assert src.count("jax_linear_heads(") == LINEAR_LAYERS and "pallas_apply_rope(" not in src
    job.release()
    verdict = job.check(importlib.import_module("perfbench.reference.minicpm_sala"))
    assert verdict["ok"] and verdict["logits_rtol"] == checks_sparse_linear.SPARSE_LINEAR_LOGITS_RTOL
    assert verdict["compared"] == [1, 64, 512]
    lower, same = verdict["reference_at"]["float8_e4m3fn"], verdict["reference_at"]["bfloat16"]
    assert same["ok"] and not lower["ok"] and same["logits_rel_l2"] < verdict["logits_rtol"] < lower["logits_rel_l2"]
    assert job.params is None  # the system's weights were let go before the reference's were drawn
