"""The input gradient of a vocabulary head behind ``prims.optimization_barrier``.

``transforms/autodiff.py::_linear_vjp`` writes ``ga = g @ w`` of a linear whose
outputs are a vocabulary (16,384 or more, and four times its inputs or more)
as a product nothing fuses into: for a TPU v5e XLA otherwise fuses the final
norm's backward into it, and the compiled product keeps the MXU busy in 59 to
75% of its bundles where alone it does in 92%. Here, on the CPU: where the rule places the barrier and where it does not, that
the barrier changes no number, that ``jaxex`` lowers it to StableHLO's
``optimization_barrier``, how ``dce`` and ``cse`` treat it, and the count in the
``transforms`` record of ``thunder_tpu.compile_phases()``."""

import dataclasses
import time

import jax
import numpy as np
import pytest

import thunder_tpu
from thunder_tpu.core import dtypes, prims
from thunder_tpu.core.prims import PrimIDs
from thunder_tpu.core.pytree import tree_flatten
from thunder_tpu.models import gpt
from thunder_tpu.transforms import autodiff

from test_ouro_loop import B, CFG as OURO, T, built

TAG = autodiff.KEPT_WHOLE_TAG
# The looped stand-in with a vocabulary the rule takes: four heads of (16384, 256).
OURO_VOCAB = dataclasses.replace(OURO, vocab_size=16384, padded_vocab_size=16384)
# One head of (16384, 32), the model of the three other train cells.
LLAMA_VOCAB = dataclasses.replace(gpt.name_to_config("llama-tiny"), vocab_size=16384, padded_vocab_size=16384)
STAND_INS = {"ouro": (OURO_VOCAB, 4), "llama": (LLAMA_VOCAB, 1), "below_the_rule": (OURO, 0)}
# The train cells of BENCHMARK.json: each has one head the rule takes, and no other linear.
TRAIN_CELLS = ("pythia-410m.train", "mistral-7b.train", "mistral-7b.fsdp4", "ouro-2.6b.train")


def is_barrier(bsym) -> bool:
    return bsym.sym.id is PrimIDs.OPTIMIZATION_BARRIER


def batch(cfg, seed=0):
    idx = np.random.RandomState(seed).randint(0, cfg.padded_vocab_size, (B, T)).astype(np.int32)
    return idx, np.roll(idx, -1, axis=1).astype(np.int32)


def params_of(cfg):
    if cfg.loop_steps > 1:
        return built(cfg)[0]
    return gpt.init_params(cfg, dtype=dtypes.float32, seed=0)


def train_program(cfg, params, idx, tgt):
    """``build_train_step``'s loss and gradients: (the claimed joint trace, (loss, grads), the transforms record)."""
    from thunder_tpu.parallel.train import _compile_loss_and_grads

    mark = time.perf_counter()
    run, claimed = _compile_loss_and_grads(cfg, params, idx, tgt)
    (record,) = [r for r in thunder_tpu.compile_phases() if r["at"] >= mark and r["phase"] == "transforms"]
    loss, grads = run(*tree_flatten(((params, idx, tgt), {}))[0])
    return claimed, (np.asarray(loss), [np.asarray(g) for g in grads]), record


@pytest.fixture(scope="module", params=sorted(STAND_INS))
def stand_in(request):
    """A stand-in's train program with the rule and without it (its threshold out of reach), and the count it expects."""
    cfg, expected = STAND_INS[request.param]
    params, (idx, tgt) = params_of(cfg), batch(cfg)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(autodiff, "VOCAB_MIN_OUT", 1 << 40)
        without = train_program(cfg, params, idx, tgt)
    return cfg, expected, train_program(cfg, params, idx, tgt), without


def test_the_rule_takes_each_train_cells_head_and_no_other_linear_weight():
    """By the shapes the cells run: the head's (V, C), and none of the attention's, the MLP's
    (``mistral-7b``'s widest, 14336 over 4096, is 3.5 times its input) or the exit gate's."""
    from perfbench import manifest
    from perfbench.jobs import gpt_model

    for name in TRAIN_CELLS:
        cfg = gpt_model.gpt_config(manifest.published(manifest.load_cell(name)))
        head = "wte" if cfg.tie_embeddings else "lm_head_w"
        taken = []
        for path, leaf in jax.tree_util.tree_flatten_with_path(gpt_model.param_shapes(cfg))[0]:
            key = jax.tree_util.keystr(path)
            if len(leaf.shape) == 2 and (head in key or "wte" not in key) and autodiff._is_vocab_head(*leaf.shape):
                taken.append(key)
        assert taken == [f"['{head}']"], (name, taken)


def test_the_barrier_stands_once_a_head_on_the_input_gradient_alone(stand_in):
    cfg, expected, (claimed, _, record), (plain, _, plain_record) = stand_in
    bsyms = claimed.bound_symbols
    barriers = [b for b in bsyms if is_barrier(b)]
    assert len(barriers) == expected
    assert not any(is_barrier(b) for b in plain.bound_symbols)
    writer = {o.name: b for b in bsyms for o in b.flat_proxy_outs}
    for b in barriers:  # each fences (..., V) @ (V, C) -> (..., C): the head's input gradient, not its weight's
        product = writer[b.args[0].name]
        g, w = product.args
        assert product.sym.id is PrimIDs.MATMUL and tuple(w.shape) == (cfg.padded_vocab_size, cfg.n_embd)
        assert b.output.shape[-1] == cfg.n_embd and g.shape[-1] == cfg.padded_vocab_size
    # the count reaches the transforms record through the passes between the grad transform and the claim
    assert claimed.tags.get(TAG, 0) == record.get(TAG, 0) == expected
    assert (TAG in record) == (expected > 0) and TAG not in plain_record


def test_the_barrier_changes_no_number(stand_in):
    _, _, (_, (loss, grads), _), (_, (plain_loss, plain_grads), _) = stand_in
    assert loss.tobytes() == plain_loss.tobytes()
    assert len(grads) == len(plain_grads)
    for a, b in zip(grads, plain_grads):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


def test_the_lowered_train_step_holds_one_barrier_a_head():
    from thunder_tpu.parallel import build_train_step

    cfg = LLAMA_VOCAB
    params, (idx, tgt) = gpt.init_params(cfg, dtype=dtypes.float32, seed=0), batch(cfg)
    step, opt = build_train_step(cfg, params, idx, tgt)
    assert step.lower(params, opt, idx, tgt).as_text().count("optimization_barrier") == 1


def transforms_record(jfn) -> dict:
    program = thunder_tpu.compile_stats(jfn).cache_entries[-1].compile_id
    (record,) = [r for r in thunder_tpu.compile_phases() if r["program"] == program and r["phase"] == "transforms"]
    return record


@pytest.mark.parametrize("grad", [True, False], ids=["value_and_grad", "forward"])
def test_a_jit_program_has_a_barrier_and_its_count_where_it_has_a_backward(grad):
    cfg = LLAMA_VOCAB
    params, (idx, tgt) = gpt.init_params(cfg, dtype=dtypes.float32, seed=0), batch(cfg)
    if grad:
        jfn = thunder_tpu.value_and_grad(lambda p, i, t: gpt.loss_fn(p, i, t, cfg))
        jfn(params, idx, tgt)
    else:
        jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
        jfn(params, idx)
    n = sum(is_barrier(b) for b in thunder_tpu.last_traces(jfn)[-1].bound_symbols)
    assert n == int(grad) and transforms_record(jfn).get(TAG, 0) == n
    assert (TAG in transforms_record(jfn)) == grad


@pytest.mark.parametrize("case", ["read", "unread", "twice_on_one_input"])
def test_dce_keeps_a_barrier_that_is_read_and_cse_merges_none(case):
    import thunder_tpu.clang as clang

    def fn(a):
        if case == "read":
            return clang.add(prims.optimization_barrier(a), a)
        if case == "unread":
            prims.optimization_barrier(a)
            return clang.add(a, a)
        return clang.add(prims.optimization_barrier(a), prims.optimization_barrier(a))

    jfn = thunder_tpu.jit(fn)
    a = np.arange(12, dtype=np.float32).reshape(3, 4)
    np.testing.assert_array_equal(np.asarray(jfn(a)), 2 * a)
    n = sum(is_barrier(b) for b in thunder_tpu.last_traces(jfn)[-1].bound_symbols)
    assert n == {"read": 1, "unread": 0, "twice_on_one_input": 2}[case]
