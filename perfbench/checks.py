"""The comparison that decides ``correct``: the system against the plain
float32 reference, at the cell's widths, on the device, outside the window.

A training cell compares the loss of one step and a seeded sample of its
gradient. The gradient is what AdamW's first moment holds after one step from
zero moments, ``m = (1 - b1) * g``; the sample is a few rows of every kind of
leaf in a few layers (whole vectors for norms and biases), so that nothing
large is kept. On the reference side the same rows are differentiated through
probes added to the weights where they are used, so no full-size gradient is
ever formed. A forward cell compares the logits of the last positions of a
seeded sample of sequences.

Tolerances, with their reasons (the numbers are beside each constant below):

* The system multiplies in bf16 (8 bits of mantissa, 2**-8 = 3.9e-3 a
  rounding) with float32 accumulation; the reference in float32 "highest".
  Rounding errors of independent elements add like a random walk, so a sum
  over some thousand terms carries a relative error of a few 1e-3 of the
  result's norm, and a gradient that went through every layer twice a few
  hundredths. A sampled vector's error is the L2 norm of the difference over
  the L2 norm of the reference. A sampled matrix is judged by its worst row,
  each row's difference over that row's own norm (and at least a tenth of
  the sample's typical row norm, so that a row of nearly no gradient is not
  all noise): a term that is missing touches some rows and not others (the
  rope turns the first features of the query and key heads only), and in a
  norm over the whole leaf the untouched rows would hide it.
* A missing term is not a rounding: dropping the rope or the causal mask
  moves the rows it touches by more than half their norm (the CPU tests
  mutate the system so and require a failure).
"""

from __future__ import annotations

import numpy as np

# What the chip showed (my chip runs, PR 22) over 24 runs of the two one-chip
# training cells, each with another seed, 9 of the forward cell and one of the
# four-chip cell:
#
# Loss: a float32 mean over thousands of rows of a float32 log-softmax over
# bf16-computed logits, near ln(vocab) ~ 11 at random init. pythia-410m (8192
# rows) at most 2.1e-5, mistral-7b on one chip (4096 rows) at most 1.2e-4, on
# four (16384 rows) 1.6e-5. The tolerance is four times the largest. The loss
# is the weak half of the check (at init a dropped mask moves it by 6e-4 and a
# dropped rope by nothing): the gradient sample is what catches a missing term.
LOSS_RTOL = 5e-4
# Sampled gradient, worst row of a matrix or whole vector, relative L2: see
# above. A run's worst sample was 0.04 to 0.10 in both models (most leaves
# 0.01 to 0.03 for pythia-410m, 0.04 for mistral-7b, whose K=14336 and T=4096
# make longer sums). It is a maximum over a thousand rows, so the tolerance is
# three times the largest seen; a dropped rope or mask gives 0.9 and more in
# the rows it touches (the CPU tests).
GRAD_RTOL = 0.3
# Logits of the forward cell, relative L2 over the compared block: one pass
# through 24 layers in bf16 gave 0.0095 to 0.0100 in every run.
LOGITS_RTOL = 0.03

ROWS_PER_LEAF = 32
LOGIT_POSITIONS = 256


def relative_l2(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def worst_row(got, want) -> float:
    """Largest relative L2 error of a row of a sampled matrix; a row's norm
    counts as at least a tenth of the sample's root-mean-square row norm."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    norms = np.linalg.norm(want, axis=1)
    floor = 0.1 * np.sqrt(np.mean(norms ** 2))
    return float(np.max(np.linalg.norm(got - want, axis=1) / np.maximum(np.maximum(norms, floor), 1e-30)))


def sample_error(got, want) -> float:
    return worst_row(got, want) if np.ndim(want) == 2 else relative_l2(got, want)


# -----------------------------------------------------------------------------
# The gradient sample
# -----------------------------------------------------------------------------


def sample_plan(kinds, depth: int, first_tokens: np.ndarray, seed: int) -> list[dict]:
    """Which rows of which leaves are compared. ``kinds`` is
    ``weights.leaf_kinds(shape_tree)``. Layers: the first, the last and up to
    two between. Rows are drawn from the seed; ``wte`` takes rows of tokens
    that occur in the batch (any other row's gradient is zero)."""
    rng = np.random.RandomState(seed)
    layers = sorted({0, depth - 1, depth // 3, (2 * depth) // 3})
    plan, seen = [], set()
    for kind, layer, leaf in kinds:
        if kind in seen:
            continue
        seen.add(kind)
        rows = None
        if len(leaf.shape) == 2:
            pool = np.unique(first_tokens) if kind == "wte" else np.arange(leaf.shape[0])
            rows = np.sort(rng.choice(pool, size=min(ROWS_PER_LEAF, len(pool)), replace=False))
        plan.append({"kind": kind, "layers": layers if layer is not None else None, "rows": rows})
    return plan


def _leaf(tree, kind: str, layer):
    node = tree
    for part in kind.split("/"):
        node = node[layer] if part == "*" else node[part]
    return node


def _row_arguments(plan: list[dict]) -> dict:
    """The sampled row numbers as arrays. They are arguments of the jitted
    calls below, not constants in them: the rows follow the seed, and a
    program that held them would be compiled anew for every seed."""
    return {e["kind"]: np.asarray(e["rows"], np.int32) for e in plan if e["rows"] is not None}


def system_gradient_sample(first_moment_tree, plan: list[dict], b1: float) -> dict:
    """{"kind@layer": float32 rows} of ``m / (1 - b1)``, gathered on the device
    in one jitted call and brought to the host."""
    import jax
    import jax.numpy as jnp

    def gather(m, rows):
        out = {}
        for entry in plan:
            for layer in entry["layers"] or [None]:
                leaf = _leaf(m, entry["kind"], layer)
                picked = leaf if entry["rows"] is None else leaf[rows[entry["kind"]]]
                out[f"{entry['kind']}@{layer}"] = picked.astype(jnp.float32) / jnp.float32(1.0 - b1)
        return out

    return {k: np.asarray(v) for k, v in jax.jit(gather)(first_moment_tree, _row_arguments(plan)).items()}


def reference_loss_and_gradient_sample(reference, stacked_weights: dict, plan: list[dict],
                                       idx, targets, config: dict):
    """The reference's loss and, for the rows in ``plan``, its gradient: each
    sampled row gets a zero float32 probe added where the weight is used, and
    the loss is differentiated with respect to the probes."""
    import jax
    import jax.numpy as jnp

    by_kind = {e["kind"]: e for e in plan}
    probes = {}
    for e in plan:
        shape = stacked_weights[e["kind"]].shape
        lead = (len(e["layers"]),) if e["layers"] is not None else ()
        tail = shape[len(lead):] if e["rows"] is None else (len(e["rows"]),) + shape[len(lead) + 1:]
        probes[e["kind"]] = jnp.zeros(lead + tuple(tail), jnp.float32)

    def loss_of(probes, rows, weights, idx, targets):
        def adjust(kind, w, layer):
            e = by_kind.get(kind)
            if e is None:
                return w
            probe = probes[kind]
            if e["layers"] is not None:  # this layer's probe, or zeros if it is not sampled
                hit = (jnp.asarray(e["layers"], jnp.int32) == layer).astype(jnp.float32)
                probe = jnp.tensordot(hit, probe, axes=1)
            return w + probe if e["rows"] is None else w.at[rows[kind]].add(probe)

        return reference.loss(weights, idx, targets, config, adjust)

    # The weights go in as arguments too: closed over, they would be constants
    # of the compiled program, gigabytes of them.
    loss, grads = jax.jit(jax.value_and_grad(loss_of))(
        probes, _row_arguments(plan), stacked_weights, idx, targets)
    out = {}
    for e in plan:
        g = np.asarray(grads[e["kind"]])
        for i, layer in enumerate(e["layers"] or [None]):
            out[f"{e['kind']}@{layer}"] = g[i] if e["layers"] is not None else g
    return float(loss), out


def compare_training(system_loss: float, system_sample: dict, reference_loss: float,
                     reference_sample: dict) -> dict:
    loss_err = abs(system_loss - reference_loss) / abs(reference_loss)
    errs = {k: sample_error(system_sample[k], reference_sample[k]) for k in reference_sample}
    worst = max(errs, key=errs.get)
    return {
        "ok": bool(loss_err <= LOSS_RTOL and errs[worst] <= GRAD_RTOL
                   and all(np.isfinite(v).all() for v in system_sample.values())),
        "loss": [system_loss, reference_loss], "loss_rel_err": loss_err, "loss_rtol": LOSS_RTOL,
        "grad_worst": [worst, errs[worst]], "grad_rtol": GRAD_RTOL,
        "grad_err": {k: float(f"{v:.3e}") for k, v in sorted(errs.items())},
    }


def compare_logits(system_logits, reference_logits) -> dict:
    err = relative_l2(system_logits, reference_logits)
    return {"ok": bool(err <= LOGITS_RTOL and np.isfinite(np.asarray(system_logits, np.float32)).all()),
            "logits_rel_l2": err, "logits_rtol": LOGITS_RTOL,
            "compared": list(np.shape(reference_logits))}
