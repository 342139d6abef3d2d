"""Ouro's looped blocks at test size on the CPU, float32, seeded weights: two
layers of heads of 128 with four norms a block, run four times with the same
weights, the final norm between the steps, a head and an exit gate on every
step's normed state, and the exit objective. Against the plain reference
(``perfbench/reference/ouro.py``), which knows nothing of the program, and
against mutations of the program that each leave out a part of the mechanism."""

import dataclasses
import os

import numpy as np
import pytest

import thunder_tpu
import thunder_tpu.torch as ttorch
from thunder_tpu.core import dtypes
from thunder_tpu.core.trace import region
from thunder_tpu.models import gpt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = gpt.name_to_config("ouro-tiny")
B, T = 2, 64
# The CPU multiplies float32 exactly enough that the system and the reference agree to rounding; a missing term
# moves the loss or a gradient by far more (each mutation below reads 1e-2 and more).
TOL = 1e-4


def keys_of(cfg) -> dict:
    """What the reference reads, under the published names, for a registry entry."""
    return {"num_attention_heads": cfg.n_head, "num_key_value_heads": cfg.query_groups, "head_dim": cfg.head_size,
            "rope_theta": cfg.rope_base, "rms_norm_eps": cfg.norm_eps, "total_ut_steps": cfg.loop_steps,
            "exit_entropy": cfg.exit_entropy}


def built(cfg=CFG, seed=3):
    """(the program's parameters, float32, as the benchmark draws them; the same numbers stacked for the reference)."""
    import jax

    from perfbench import weights

    shapes = jax.eval_shape(lambda: gpt.init_params(cfg, dtype=dtypes.float32, device_init=True))
    return weights.make_system_weights(shapes, seed), weights.make_reference_weights(shapes, seed)


def batch(seed=0):
    idx = np.random.RandomState(seed).randint(0, CFG.padded_vocab_size, (B, T)).astype(np.int32)
    return idx, np.roll(idx, -1, axis=1).astype(np.int32)


def rel(got, want) -> float:
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(np.asarray(got, np.float64) - want) / max(np.linalg.norm(want), 1e-30))


def system_loss_and_grads(cfg, params, idx, tgt):
    """``build_train_step``'s traced loss and gradients, as the step calls them."""
    from thunder_tpu.core.pytree import tree_flatten
    from thunder_tpu.parallel.train import _compile_loss_and_grads

    run, _ = _compile_loss_and_grads(cfg, params, idx, tgt)
    loss, grads = run(*tree_flatten(((params, idx, tgt), {}))[0])
    return float(loss), [np.asarray(g) for g in grads]


def reference_loss_and_grads(stacked, idx, tgt, cfg=CFG):
    import jax

    from perfbench.reference import ouro

    loss, grads = jax.jit(jax.value_and_grad(lambda w: ouro.loss(w, idx, tgt, keys_of(cfg))))(stacked)
    return float(loss), grads


def worst_error(params, system, reference) -> tuple[str, float]:
    """The largest relative error over the loss and the gradient of every leaf, by ``kind@layer``."""
    from perfbench import weights

    (s_loss, s_grads), (r_loss, r_grads) = system, reference
    errs = {"loss": abs(s_loss - r_loss) / abs(r_loss)}
    for (kind, layer, _), g in zip(weights.leaf_kinds(params), s_grads):
        errs[f"{kind}@{layer}"] = rel(g, r_grads[kind] if layer is None else r_grads[kind][layer])
    worst = max(errs, key=errs.get)
    return worst, errs[worst]


# -----------------------------------------------------------------------------
# The model
# -----------------------------------------------------------------------------


def test_the_registry_lists_the_model_at_its_published_sizes():
    import jax

    from perfbench import manifest
    from perfbench.jobs import gpt_model

    cell = manifest.load_cell("ouro-2.6b.train")
    listed, file = gpt.name_to_config("ouro-2.6b"), manifest.published(cell)
    assert gpt_model.gpt_config(file) == dataclasses.replace(listed, n_layer=file["num_hidden_layers"], block_size=4096)
    assert (listed.n_layer, listed.n_embd, listed.n_head, listed.query_groups, listed.head_size) == (48, 2048, 16, 16, 128)
    assert (listed.mlp_hidden, listed.padded_vocab_size, listed.block_size, listed.tie_embeddings) == (5632, 49152, 65536, False)
    assert (listed.loop_steps, listed.exit_entropy, listed.rope_base, listed.rope_n_elem) == (4, 0.1, 1000000, 128)
    assert (listed.norm_class, listed.norm_eps, listed.bias, listed.mlp_class, listed.sandwich_norms) == ("RMSNorm", 1e-6, False, "LLaMAMLP", True)
    assert listed.loop_steps == file["total_ut_steps"] and listed.layer_mixer(47) == "full_attention"
    # the issue's reckoning: 51.38 M a layer, 201.3 M in the embedding and the head, 2.668 B in all
    shapes = jax.eval_shape(lambda: gpt.init_params(listed, device_init=True))
    sizes = {k: sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(v)) for k, v in shapes.items()}
    assert sizes["blocks"] / 48 == 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048 == 51_380_224 + 8192  # and four norms
    assert sizes["wte"] + sizes["lm_head_w"] == 2 * 49152 * 2048 and sizes["exit_gate_w"] + sizes["exit_gate_b"] == 2049
    assert sum(sizes.values()) == pytest.approx(2.668e9, rel=1e-3)
    # every default is yesterday's program: one step, no gate leaf
    plain = gpt.name_to_config("mistral-tiny")
    assert (plain.loop_steps, plain.exit_entropy) == (1, 0.0)
    assert "exit_gate_w" not in jax.eval_shape(lambda: gpt.init_params(plain, device_init=True))


def test_each_loop_steps_logits_through_jit_are_the_references():
    import jax

    from perfbench.reference import ouro

    params, stacked = built()
    idx, _ = batch()

    def each_step(p, i):
        states = []
        last = gpt._hidden(p, i, CFG, states=states)
        return [gpt._head(s, p, CFG) for s in states + [last]]

    system = thunder_tpu.jit(each_step)(params, idx)
    reference = jax.jit(lambda w: ouro.forward(w, idx, keys_of(CFG)))(stacked)
    assert len(system) == len(reference) == 4
    errs = [rel(s, r) for s, r in zip(system, reference)]
    assert max(errs) < TOL, errs
    # the forward serves the last step, and slices after the loop
    assert rel(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, CFG))(params, idx), reference[-1]) < TOL
    tail = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, CFG, last=8))(params, idx)
    assert rel(tail, np.asarray(reference[-1])[:, -8:]) < TOL
    # four steps are not one: the first step's logits are not the last's
    assert rel(reference[0], reference[-1]) > 0.1


def test_the_loss_and_every_kind_of_leafs_gradient_are_the_references():
    """``build_train_step``'s traced loss and gradients against ``jax.grad`` of the
    reference: the shared layers' (the sum of their four uses), the head's, the
    final norm's, the embedding's and the gate's."""
    params, stacked = built()
    idx, tgt = batch()
    system = system_loss_and_grads(CFG, params, idx, tgt)
    reference = reference_loss_and_grads(stacked, idx, tgt)
    assert worst_error(params, system, reference)[1] < TOL
    kinds = {k.split("@")[0] for k in _kinds(params)}
    assert {"exit_gate_w", "exit_gate_b", "lm_head_w", "ln_f/weight", "wte", "blocks/*/post_mlp_norm/weight"} <= kinds
    assert np.abs(system[1][_kinds(params).index("exit_gate_b@None")]).max() > 0


def _kinds(params) -> list:
    from perfbench import weights

    return [f"{kind}@{layer}" for kind, layer, _ in weights.leaf_kinds(params)]


def test_one_build_train_step_step_on_the_cpu():
    from thunder_tpu.parallel import build_train_step

    params = gpt.init_params(CFG, seed=1)
    idx, tgt = batch(1)
    step, opt, extrace = build_train_step(CFG, params, idx, tgt, return_extrace=True)
    before = {k: np.asarray(params[k], np.float32).copy() for k in ("exit_gate_w", "exit_gate_b", "lm_head_w")}
    new, opt, loss = step(params, opt, idx, tgt)
    assert np.isfinite(float(loss)) and abs(float(loss) - (np.log(CFG.padded_vocab_size) - 0.1 * np.log(4))) < 0.5
    for k, was in before.items():
        assert not np.array_equal(np.asarray(new[k], np.float32), was), k
    # each step's cross-entropy, forward and backward, is one per-row symbol in region exit.heads
    ce = [b for b in extrace.bound_symbols if b.sym.id in ("torch.cross_entropy", "torch.cross_entropy_bwd")]
    assert [b.region for b in ce] == ["exit.heads"] * 4 + ["exit.heads/bwd"] * 4
    assert all(b.kwargs.get("reduction", b.args[4] if len(b.args) > 4 else None) == "none" for b in ce)


# -----------------------------------------------------------------------------
# Mutations: each leaves out a part of the mechanism and must fail the comparison
# -----------------------------------------------------------------------------


def _hidden_without_feedback(params, idx, config, counts=None, last=None, states=None):
    """``gpt._hidden`` whose steps hand the next one the state before the final norm."""
    x = ttorch.embedding(idx, params["wte"])
    cos, sin = gpt._rope_cache(idx.shape[1], config, device=x.device, dtype=x.dtype)
    for step in range(config.loop_steps):
        if step and states is not None:
            states.append(gpt._norm(x, params["ln_f"], config))
        for layer, (p, kind) in enumerate(gpt._layers(params, config)):
            x = gpt._block(x, p, cos, sin, kind, config, counts, layer)
    return gpt._norm(x, params["ln_f"], config)


def _log_probs_without_remainder(real):
    def mutated(gate_logits):
        log_p = real(gate_logits)
        return log_p[:-1] + [log_p[-1] + ttorch.logsigmoid(gate_logits[-1])]  # p_L = λ_L Π(1 - λ_j)
    return mutated


# name -> (monkeypatch, config) -> the config to run, the program patched where the mutation is in its code
MUTATIONS = {
    "one_loop_step": lambda mp, cfg: dataclasses.replace(cfg, loop_steps=1),
    "final_norm_not_fed_back": lambda mp, cfg: mp.setattr(gpt, "_hidden", _hidden_without_feedback) or cfg,
    "last_step_without_the_remainder": lambda mp, cfg: mp.setattr(
        gpt, "_exit_log_probs", _log_probs_without_remainder(gpt._exit_log_probs)) or cfg,
    "entropy_dropped": lambda mp, cfg: dataclasses.replace(cfg, exit_entropy=0.0),
}


@pytest.mark.parametrize("mutation", sorted(MUTATIONS))
def test_a_mutation_fails_the_comparison(mutation, monkeypatch):
    """Against the unmutated reference, each mutation of the program fails this
    file's comparison and the chip's (``checks.compare_training`` on the loss
    and a gradient sample, at the benchmark's tolerances) alike."""
    from perfbench import checks, weights

    params, stacked = built()
    idx, tgt = batch()
    cfg = MUTATIONS[mutation](monkeypatch, CFG)
    system = system_loss_and_grads(cfg, params, idx, tgt)
    reference = reference_loss_and_grads(stacked, idx, tgt)
    worst, err = worst_error(params, system, reference)
    assert err > 100 * TOL, (worst, err)
    plan = checks.sample_plan(weights.leaf_kinds(params), CFG.n_layer, idx, seed=3)
    sampled = {}
    for e in plan:
        for layer in e["layers"] or [None]:
            at = _kinds(params).index(f"{e['kind']}@{layer}")
            want = reference[1][e["kind"]] if layer is None else reference[1][e["kind"]][layer]
            pick = (lambda a: a) if e["rows"] is None else (lambda a: np.asarray(a)[e["rows"]])
            sampled[f"{e['kind']}@{layer}"] = (pick(system[1][at]), pick(want))
    verdict = checks.compare_training(system[0], {k: v[0] for k, v in sampled.items()},
                                      reference[0], {k: v[1] for k, v in sampled.items()})
    assert not verdict["ok"], verdict


# -----------------------------------------------------------------------------
# One loop step is yesterday's program
# -----------------------------------------------------------------------------


def _parents_loss_fn(params, idx, targets, config):
    """``models/gpt.py``'s ``_hidden``, ``forward`` and ``loss_fn`` as the parent commit wrote them."""
    B, T = idx.shape
    x = ttorch.embedding(idx, params["wte"])
    if config.embedding_scale != 1.0:
        x = x * config.embedding_scale
    cos, sin = gpt._rope_cache(T, config, device=x.device, dtype=x.dtype)
    for layer, (p, kind) in enumerate(gpt._layers(params, config)):
        x = gpt._block(x, p, cos, sin, kind, config, None, layer)
    x = gpt._norm(x, params["ln_f"], config)
    x = x if config.logit_divisor == 1.0 else x / config.logit_divisor
    logits = ttorch.linear(x, params["wte" if config.tie_embeddings else "lm_head_w"])
    B, T, V = logits.shape
    logits = ttorch.reshape(logits.float(), (B * T, V))
    return ttorch.cross_entropy(logits, ttorch.reshape(targets, (B * T,)))


@pytest.mark.parametrize("name", ["mistral-tiny", "trinity-tiny", "granite-h-tiny"])
def test_with_one_loop_step_loss_fns_trace_is_the_parents_symbol_for_symbol(name):
    from thunder_tpu.api import trace_program

    cfg = gpt.name_to_config(name)
    params = gpt.init_params(cfg, seed=0)
    idx, tgt = (np.random.RandomState(0).randint(0, cfg.padded_vocab_size, (2, 64)).astype(np.int32) for _ in range(2))
    traces = [trace_program(lambda p, i, t, f=f: f(p, i, t, cfg), (params, idx, tgt), {})[1]
              for f in (gpt.loss_fn, _parents_loss_fn)]
    now, parent = (t.python(include_header=False) for t in traces)
    assert now == parent and "exit" not in now


def test_regions_of_the_backward_are_the_forwards_with_bwd():
    """A symbol the reverse walk writes for a forward symbol of region ``r`` is in
    ``r/bwd``; the rest of the backward, and the forward outside any region, in none."""
    from thunder_tpu.api import trace_program
    from thunder_tpu.transforms.autodiff import grad_transform

    def fn(a, b):
        with region("exit.heads"):
            y = ttorch.exp(a * b)
        return ttorch.sum(ttorch.tanh(y))

    a = np.random.RandomState(0).randn(4, 4).astype(np.float32)
    _, trc = trace_program(fn, (a, a), {})
    joint = grad_transform(trc)
    fwd = {b.output.name for b in trc.bound_symbols if getattr(b.output, "name", None)}
    regions = {b.region for b in joint.bound_symbols if getattr(b.output, "name", None) not in fwd}
    assert regions == {None, "exit.heads/bwd"}
    assert {b.region for b in trc.bound_symbols} == {None, "exit.heads"}
