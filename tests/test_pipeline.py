"""thunder_tpu/pipeline.py: one list of passes between an acquired trace and a
claimed one, and every front end runs it. What the fold of nine hand-made
assemblies into it leans on is pinned here: each entry point runs the list in
its order, the ``jit`` path and the train path claim one program, each rewrite
declines the kind of trace it does not apply to, and ``cse`` removes nothing
from the training cells' forward."""

import functools

import numpy as np
import pytest

import thunder_tpu
import thunder_tpu.clang as clang
import thunder_tpu.torch as ttorch
from perfbench import manifest
from perfbench.jobs import gpt_model
from thunder_tpu import pipeline
from thunder_tpu.api import trace_program
from thunder_tpu.core import dtypes
from thunder_tpu.extend import resolve_executors
from thunder_tpu.models import gpt
from thunder_tpu.parallel.train import _compile_loss_and_grads
from thunder_tpu.transforms import attention_layout, cross_entropy_upcast, ssm_layout
from thunder_tpu.transforms.attention_residuals import save_sdpa_residuals_joint
from thunder_tpu.transforms.autodiff import grad_transform
from thunder_tpu.transforms.common import cse, dce


@pytest.fixture(autouse=True)
def _flash_claims_on_the_cpu(monkeypatch):
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")


CLEAN = [step.__name__ for step in pipeline.CLEAN]
COMPILE = [step.__name__ for step in (*pipeline.REWRITES, *pipeline.LOWER, pipeline.CLAIM)]


@pytest.fixture
def stages_run(monkeypatch):
    """The owner's stages by name, in the order a front end ran them."""
    ran = []

    def logged(step):
        @functools.wraps(step)
        def run(*args, **kwargs):
            ran.append(step.__name__)
            return step(*args, **kwargs)
        return run

    for name in ("CLEAN", "REWRITES", "LOWER"):
        monkeypatch.setattr(pipeline, name, tuple(logged(step) for step in getattr(pipeline, name)))
    monkeypatch.setattr(pipeline, "CLAIM", logged(pipeline.CLAIM))
    return ran


def _two(seed=0):
    rng = np.random.RandomState(seed)
    return rng.randn(8, 8).astype(np.float32), rng.randn(8, 8).astype(np.float32)


def _loss(a, b):
    return clang.sum(clang.tanh(clang.add(clang.mul(a, b), a)))


def _through_jit():
    thunder_tpu.jit(_loss)(*_two())


def _through_value_and_grad():
    thunder_tpu.value_and_grad(_loss)(*_two())


def _through_vmap_of_a_compiled_function():
    thunder_tpu.vmap(thunder_tpu.jit(lambda a, b: clang.tanh(clang.mul(a, b))))(*_two())


def _through_build_train_step():
    from thunder_tpu.parallel import build_train_step

    cfg = gpt.name_to_config("llama-tiny")
    idx = np.zeros((2, 16), np.int32)
    build_train_step(cfg, gpt.init_params(cfg, dtype=dtypes.float32, seed=0), idx, idx)


def _through_a_module(grad: bool):
    import torch

    linear, x = torch.nn.Linear(8, 8).requires_grad_(grad), torch.randn(4, 8)
    out = thunder_tpu.jit(linear)(x)
    if grad:
        out.sum().backward()


def _through_compile_with_collectives():
    import jax
    from jax.sharding import Mesh, PartitionSpec

    from thunder_tpu.distributed import prims as dist_prims
    from thunder_tpu.distributed.runtime import compile_with_collectives

    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    compile_with_collectives(lambda x: dist_prims.all_reduce(clang.mul(x, x), "dp", 2, op="sum"),
                             (_two()[0],), mesh, (PartitionSpec(),), PartitionSpec())


def _through_a_pipeline_stage():
    from thunder_tpu.parallel import gpt_pp

    gpt_pp._staged(lambda a, b: ttorch.mul(a, b), _two(), None)


def _through_lint():
    from thunder_tpu.examine import lint

    lint(_loss, *_two(), verbose=False)


ENTRY_POINTS = {
    "jit": (_through_jit, 1),
    "value_and_grad": (_through_value_and_grad, 1),
    "vmap_of_a_compiled_function": (_through_vmap_of_a_compiled_function, 1),
    "build_train_step": (_through_build_train_step, 1),
    "module_without_grad": (functools.partial(_through_a_module, False), 1),
    "module_with_grad": (functools.partial(_through_a_module, True), 2),  # the forward half, then the backward half
    "compile_with_collectives": (_through_compile_with_collectives, 1),
    "gpt_pp_staged": (_through_a_pipeline_stage, 1),
    "lint": (_through_lint, 1),
}


def test_the_rewrites_stand_in_this_order_and_these_counts_go_into_the_transforms_record():
    """A layout fold reads the trace the folds before it left: the state-space
    fold (PR 46) is the last, after the attention fold."""
    assert [step.__name__ for step in pipeline.REWRITES] == [
        "save_sdpa_residuals_joint", "fold_cross_entropy_upcasts", "fold_attention_layouts", "fold_ssm_layouts"]
    assert pipeline._COUNTED == (cross_entropy_upcast.FOLDED_TAG, attention_layout.FOLDED_TAG, ssm_layout.FOLDED_TAG)
    assert pipeline._COUNTED == ("cross_entropy_upcasts_folded", "attention_layouts_folded", "ssm_layouts_folded")


@pytest.mark.parametrize("entry", ENTRY_POINTS)
def test_every_entry_point_runs_the_owners_list_in_the_owners_order(stages_run, entry):
    drive, programs = ENTRY_POINTS[entry]
    drive()
    assert stages_run == CLEAN + COMPILE * programs


# -- the jit path and the train path compile one program -------------------------------------------------------------


def _stand_in(cell_name):
    """A cell's configuration at its stand-in sizes: (config, parameters, ids)."""
    cell = manifest.load_cell(cell_name)
    cfg = gpt_model.gpt_config(manifest.published(cell, rehearse=True), rehearse=True)
    traffic = {**cell.traffic, **cell.traffic["stand_in"]}
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (traffic["batch"], traffic["seq"])).astype(np.int32)
    return cfg, gpt.init_params(cfg, dtype=dtypes.bfloat16, seed=0), ids


def _claims(trace):
    return [(str(b.sym.id), b.sym.executor.name if b.sym.executor is not None else None) for b in trace.bound_symbols]


@pytest.mark.parametrize("cell", ["pythia-410m.train", "lfm2-8b-a1b.fwd", "trinity-mini.fwd-t32k"],
                         ids=["dense", "routed", "window"])
def test_jit_of_value_and_grad_and_the_train_path_claim_the_same_symbols_by_the_same_executors(cell):
    cfg, params, ids = _stand_in(cell)
    _, trained = _compile_loss_and_grads(cfg, params, ids, ids)
    vg = thunder_tpu.value_and_grad(lambda p, i, t: gpt.loss_fn(p, i, t, cfg))
    vg(params, ids, ids)
    (jitted,) = [t for t in thunder_tpu.last_traces(vg) if t.pass_name() == "Transform for execution"]
    assert _claims(trained) == _claims(jitted)
    assert {"flash", "pallas"} <= {executor for _, executor in _claims(trained)}


# -- each rewrite declines the kind of trace it does not apply to ----------------------------------------------------


def _traces_of(cell_name):
    """(forward of ``forward``, joint of ``loss_fn``) of a cell's stand-in, clean."""
    cfg, params, ids = _stand_in(cell_name)
    _, fwd = trace_program(lambda p, i: gpt.forward(p, i, cfg), (params, ids), {})
    _, loss = trace_program(lambda p, i, t: gpt.loss_fn(p, i, t, cfg), (params, ids, ids), {})
    return pipeline.clean(fwd)[-1], grad_transform(pipeline.clean(loss)[-1], return_value=True)


DECLINES = {  # the rewrite, the kind of trace it declines (0: the forward, 1: the joint), what it says it did
    "fold_attention_layouts_a_joint_trace": (attention_layout.fold_attention_layouts, 1, (attention_layout.FOLDED_TAG, 0)),
    "fold_cross_entropy_upcasts_a_trace_with_no_cross_entropy": (
        cross_entropy_upcast.fold_cross_entropy_upcasts, 0, (cross_entropy_upcast.FOLDED_TAG, 0)),
    "save_sdpa_residuals_joint_a_forward": (save_sdpa_residuals_joint, 0, None),
    # a trace with no ``ssm_scan`` in it: the same object, and no count of sites that were never looked for
    "fold_ssm_layouts_a_forward_without_a_scan": (ssm_layout.fold_ssm_layouts, 0, None),
    "fold_ssm_layouts_a_joint_trace_without_a_scan": (ssm_layout.fold_ssm_layouts, 1, None),
}


@pytest.mark.parametrize("cell", ["pythia-410m.train", "mistral-7b.train"])
@pytest.mark.parametrize("case", DECLINES)
def test_a_rewrite_leaves_the_trace_it_declines_as_it_was(case, cell):
    rewrite, kind, said = DECLINES[case]
    trace = _traces_of(cell)[kind]
    before = list(trace.bound_symbols)
    after = rewrite(trace, resolve_executors(None))
    assert after is trace  # not a copy: nothing is added to a front end's list of traces
    assert len(after.bound_symbols) == len(before) and all(a is b for a, b in zip(after.bound_symbols, before))
    if said is not None:
        assert after.tags[said[0]] == said[1]
    assert ssm_layout.FOLDED_TAG not in after.tags


@pytest.mark.parametrize("cell", ["pythia-410m.train", "mistral-7b.train"])
def test_cse_removes_nothing_from_the_forward_the_training_cells_differentiate(cell):
    """The train path ran ``dce`` alone before this module owned the order. The
    day ``cse`` bites here the three training cells compile another program: a
    ``perf_opt`` with a prediction, not a silent change."""
    cfg, params, ids = _stand_in(cell)
    assert cfg.n_layer == 2
    _, loss = trace_program(lambda p, i, t: gpt.loss_fn(p, i, t, cfg), (params, ids, ids), {})
    swept = dce(loss)
    assert len(cse(swept).bound_symbols) == len(swept.bound_symbols)


# -- the module front end runs the folds; the de-opt ladder's lever leaves them out ----------------------------------


def test_the_module_front_ends_training_forward_carries_the_cross_entropy_folds_tag():
    import torch

    class Scored(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.head = torch.nn.Linear(16, 32)

        def forward(self, x, target):
            return ttorch.cross_entropy(self.head(x), target)  # the torch function's own arguments reach no lookaside

    module = thunder_tpu.jit(Scored())
    module(torch.randn(8, 16), torch.randint(0, 32, (8,))).backward()
    forward = thunder_tpu.last_traces(module)[-1]
    assert forward.pass_name() == "Transform for execution"
    assert forward.tags[cross_entropy_upcast.FOLDED_TAG] == 0  # a half holds no pair: the fold ran, and declined it


def test_rewrites_false_is_the_program_as_written_and_computes_the_same():
    import jax

    cfg, params, ids = _stand_in("pythia-410m.train")
    executors = resolve_executors(None)

    def compiled(rewrites):
        _, loss = trace_program(lambda p, i, t: gpt.loss_fn(p, i, t, cfg), (params, ids, ids), {})
        return pipeline.compile_trace(pipeline.clean(loss)[-1], executors, rewrites=rewrites,
                                      transforms=(functools.partial(grad_transform, return_value=True),))

    folded, written = compiled(True), compiled(False)
    assert folded.extras["transforms"] == {cross_entropy_upcast.FOLDED_TAG: 1, attention_layout.FOLDED_TAG: 0}
    assert written.extras["transforms"] == {}
    ids_of = lambda c: {str(b.sym.id) for b in c.claimed.bound_symbols}  # noqa: E731
    assert "torch.sdpa_fwd_res" in ids_of(folded) and "torch.sdpa_fwd_res" not in ids_of(written)
    flat = jax.tree_util.tree_leaves((params, ids, ids))
    (lf, gf), (lw, gw) = folded.claimed.python_callable()(*flat), written.claimed.python_callable()(*flat)
    np.testing.assert_allclose(float(lf), float(lw), rtol=2e-2)
    for a, b in zip(gf, gw):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=5e-2, atol=2e-2)


def test_importing_the_owner_leaves_the_default_executors_in_their_order():
    """A transform the owner imports imports ``pallasex``, and the default
    order is the order of import: ``api.py`` imports the owner after the
    executors, or ``flash`` is asked before ``pallas`` and the window layers
    lose their kernel."""
    from thunder_tpu.extend import get_default_executors

    assert [e.name for e in get_default_executors()] == ["pallas", "flash", "jax"]


def test_a_front_end_that_threads_no_key_still_runs_a_program_that_draws_random_numbers():
    """``LOWER`` gives such a trace a key as its last argument on every front
    end; ``api.keyed_callable`` hands it over where the front end has none."""
    import torch

    module = thunder_tpu.jit(torch.nn.Sequential(torch.nn.Linear(8, 64), torch.nn.Dropout(0.5)))
    out = module(torch.randn(16, 8))
    out.sum().backward()
    dropped = float((out == 0).float().mean())
    assert 0.25 < dropped < 0.75
    assert thunder_tpu.last_traces(module)[-1].args[-1].name.startswith("rng_key")
