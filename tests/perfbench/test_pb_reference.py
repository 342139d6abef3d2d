"""The plain float32 reference against the program (``gpt.loss_fn`` and its
gradient through ``thunder_tpu.value_and_grad``) at the configurations'
stand-in widths on the CPU, for both block kinds: parallel residual with
partial rotary and LayerNorm (GPT-NeoX), and sequential with grouped-query
attention, full rotary, RMSNorm and SwiGLU (Mistral). And a mutated system,
the rope or the causal mask dropped, which the benchmark's tolerance must
refuse."""

import dataclasses
import json
import os
import types

import numpy as np
import pytest
from pb_helpers import REPO

from perfbench import checks, manifest, weights
from perfbench.jobs import gpt_model
from perfbench.reference import gpt as reference

BATCH, SEQ, SEED = 2, 128, 3


def stand_in(name):
    with open(os.path.join(REPO, "perfbench", "configs", name + ".json"), encoding="utf-8") as f:
        body = json.load(f)
    keys = manifest.published(types.SimpleNamespace(config=body, chips=1), rehearse=True)
    return keys, gpt_model.gpt_config(keys, rehearse=True)


def system_loss_and_grads(cfg, params, idx, tgt):
    import thunder_tpu
    from thunder_tpu.models import gpt

    loss, grads = thunder_tpu.value_and_grad(lambda p, i, t: gpt.loss_fn(p, i, t, cfg))(params, idx, tgt)
    return float(np.asarray(loss)), [np.asarray(g, np.float32) for g in grads]


def reference_loss_and_grads(keys, shapes, stacked, idx, tgt):
    import jax

    loss, grads = jax.value_and_grad(lambda w: reference.loss(w, idx, tgt, keys))(stacked)
    assert loss.dtype == np.float32  # the program turns x64 on; the reference stays float32
    return float(loss), [np.asarray(g, np.float32) for g in jax.tree_util.tree_leaves(weights.unstack(grads, shapes))]


@pytest.mark.parametrize("name", ["pythia-410m", "mistral-7b"])
def test_float32_system_and_reference_agree_to_rounding(name):
    """Same float32 weights on both sides: what is left is the order of sums."""
    import jax
    import jax.numpy as jnp

    keys, cfg = stand_in(name)
    shapes = jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                                    gpt_model.param_shapes(cfg))
    idx, tgt = gpt_model.token_batch(np.random.RandomState(0), keys["vocab_size"], BATCH, SEQ)
    loss, grads = system_loss_and_grads(cfg, weights.make_system_weights(shapes, SEED), idx, tgt)
    ref_loss, ref_grads = reference_loss_and_grads(keys, shapes, weights.make_reference_weights(shapes, SEED), idx, tgt)
    assert abs(loss - ref_loss) / ref_loss < 1e-6
    worst = max(checks.relative_l2(g, r) for g, r in zip(grads, ref_grads))
    assert worst < 1e-5, worst


def _mutate(monkeypatch, what):
    from thunder_tpu.models import gpt

    if what == "rope dropped":
        monkeypatch.setattr(gpt, "_apply_rope", lambda x, cos, sin, config: x)
    elif what == "causal mask dropped":
        real = gpt.ttorch.scaled_dot_product_attention
        monkeypatch.setattr(gpt.ttorch, "scaled_dot_product_attention",
                            lambda q, k, v, is_causal=True, **kw: real(q, k, v, is_causal=False, **kw))


@pytest.mark.parametrize("what", ["as it is", "rope dropped", "causal mask dropped"])
@pytest.mark.parametrize("name", ["pythia-410m", "mistral-7b"])
def test_the_tolerance_passes_bf16_and_fails_a_missing_term(name, what, monkeypatch):
    """The system in bf16, as the cells run it, against the float32 reference,
    judged by ``checks.compare_training`` on the rows ``checks.sample_plan``
    draws: within the tolerance as it is, outside it with a term missing."""
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")  # the kernels claim, interpreted, as in a rehearsal
    keys, cfg = stand_in(name)
    shapes = gpt_model.param_shapes(cfg)  # bf16
    idx, tgt = gpt_model.token_batch(np.random.RandomState(0), keys["vocab_size"], BATCH, SEQ)
    _mutate(monkeypatch, what)
    loss, grads = system_loss_and_grads(cfg, weights.make_system_weights(shapes, SEED), idx, tgt)
    monkeypatch.undo()

    kinds = weights.leaf_kinds(shapes)
    plan = checks.sample_plan(kinds, cfg.n_layer, idx, SEED)
    rows = {e["kind"]: e["rows"] for e in plan}
    system_sample = {f"{kind}@{layer}": g if rows[kind] is None else g[rows[kind]]
                     for (kind, layer, _), g in zip(kinds, grads)}
    ref_loss, ref_sample = checks.reference_loss_and_gradient_sample(
        reference, weights.make_reference_weights(shapes, SEED), plan, idx, tgt, keys)
    assert set(ref_sample) == set(system_sample)
    verdict = checks.compare_training(loss, system_sample, ref_loss, ref_sample)
    if what == "as it is":
        assert verdict["ok"], verdict
        assert verdict["grad_worst"][1] < checks.GRAD_RTOL / 2, verdict["grad_worst"]
    else:
        assert not verdict["ok"], verdict
        assert verdict["grad_worst"][1] > 2 * checks.GRAD_RTOL, verdict["grad_worst"]


def test_the_probes_give_the_rows_of_the_whole_gradient():
    """Differentiating through the probes equals taking rows of the full gradient."""
    keys, cfg = stand_in("mistral-7b")
    shapes = gpt_model.param_shapes(cfg)
    idx, tgt = gpt_model.token_batch(np.random.RandomState(1), keys["vocab_size"], BATCH, SEQ)
    stacked = weights.make_reference_weights(shapes, SEED)
    kinds = weights.leaf_kinds(shapes)
    plan = checks.sample_plan(kinds, cfg.n_layer, idx, SEED)
    loss, sample = checks.reference_loss_and_gradient_sample(reference, stacked, plan, idx, tgt, keys)

    import jax
    import jax.numpy as jnp

    as_f32 = {k: v.astype(jnp.float32) for k, v in stacked.items()}
    full_loss, full = jax.value_and_grad(lambda w: reference.loss(w, idx, tgt, keys))(as_f32)
    assert loss == pytest.approx(float(full_loss), rel=1e-6)
    rows = {e["kind"]: e["rows"] for e in plan}
    for (kind, layer, _), g in zip(kinds, jax.tree_util.tree_leaves(weights.unstack(full, shapes))):
        want = np.asarray(g) if rows[kind] is None else np.asarray(g)[rows[kind]]
        assert checks.relative_l2(sample[f"{kind}@{layer}"], want) < 1e-5, (kind, layer)


def test_weights_do_not_depend_on_how_they_are_laid_out():
    """The system's tree under a mesh and the reference's stacked arrays hold
    the same numbers from the same seed, and another seed gives others."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    _, cfg = stand_in("mistral-7b")
    shapes = gpt_model.param_shapes(cfg)
    mesh = Mesh(np.array(jax.devices()[:4]), ("fsdp",))
    split = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, PartitionSpec("fsdp") if len(s.shape) == 2 else PartitionSpec()), shapes)
    sharded = jax.tree_util.tree_leaves(weights.make_system_weights(shapes, SEED, split))
    whole = jax.tree_util.tree_leaves(weights.unstack(weights.make_reference_weights(shapes, SEED), shapes))
    other = jax.tree_util.tree_leaves(weights.make_system_weights(shapes, SEED + 1))
    assert all(np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32)) for a, b in zip(sharded, whole))
    assert not np.array_equal(np.asarray(whole[0], np.float32), np.asarray(other[0], np.float32))


def test_the_configuration_file_and_the_programs_registry_agree():
    from thunder_tpu.models import gpt

    for name in ("pythia-410m", "mistral-7b"):
        with open(os.path.join(REPO, "perfbench", "configs", name + ".json"), encoding="utf-8") as f:
            body = json.load(f)
        for chips in (1, 4):
            cell = types.SimpleNamespace(config=body, chips=chips)
            cfg = gpt_model.gpt_config(manifest.published(cell))
            depth = body.get("num_hidden_layers_at_chips", {}).get(str(chips), body["num_hidden_layers"])
            # the registry's entry, but for what the file lists under ``reduced``
            assert cfg == dataclasses.replace(gpt.name_to_config(name), n_layer=depth)
    assert cfg.n_layer == 8  # mistral-7b on four chips
    keys = dict(manifest.published(cell), hidden_size=2048)
    with pytest.raises(ValueError, match=r"disagree.*n_embd.*\(4096, 2048\)"):
        gpt_model.gpt_config(keys)
    # the stand-in lays every size over the registry's entry, and keeps its kind of block
    keys, small = stand_in("mistral-7b")
    assert (small.n_embd, small.n_head, small.n_query_groups, small.n_layer, small.padded_vocab_size) == (256, 2, 1, 2, 512)
    assert (small.norm_class, small.mlp_class, small.bias, small.rotary_percentage) == ("RMSNorm", "LLaMAMLP", False, 1.0)


def test_a_model_of_the_same_blocks_is_a_configuration_file_and_no_code():
    """What ``model_type`` means is spelled out in the file (``reference_hyper``,
    ``program_fields``), so a Llama, whose blocks are Mistral's with as many
    key-value heads as query heads, needs no case anywhere."""
    with open(os.path.join(REPO, "perfbench", "configs", "mistral-7b.json"), encoding="utf-8") as f:
        body = json.load(f)
    body.update(registry_name="llama-2-7b", model_type="llama", intermediate_size=11008, num_key_value_heads=32,
                num_hidden_layers=32, reduced=[])
    del body["num_hidden_layers_at_chips"], body["sliding_window"]
    del body["program_fields"]["n_query_groups"]  # the registry's Llama says None: a key-value head a query head
    keys = manifest.published(types.SimpleNamespace(config=body, chips=1))
    cfg = gpt_model.gpt_config(keys)
    assert (cfg.name, cfg.n_layer, cfg.intermediate_size, cfg.query_groups) == ("llama-2-7b", 32, 11008, 32)
    hp = reference.hyper(keys)
    assert (hp["kv_heads"], hp["rope_n"], hp["norm"], hp["mlp"], hp["parallel"]) == (32, 128, "rms", "swiglu", False)
    with pytest.raises(ValueError, match="no norm 'batch'"):
        reference.hyper({**keys, "reference_hyper": {"from_keys": {}, "fixed": {"norm": "batch", "mlp": "gelu"}}})
