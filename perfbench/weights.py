"""Seeded random weights, made on the device in one jitted call.

The program says what a parameter tree looks like (``jax.eval_shape`` of its
own initializer: names, shapes, dtypes); the values are the benchmark's, so
the system under test and the plain reference are handed the same numbers
from the same ``--seed``. The seed is an argument of the jitted call, not a
constant in it: one compiled program serves every seed.

Leaves that repeat per layer (any path through a list) are generated stacked,
one random draw of shape (layers, ...) per kind of leaf. The reference scans
over the stacked arrays as they are; the system gets them unstacked into the
tree it expects. A leaf's kind is its path with the layer index replaced by
``*``, e.g. ``blocks/*/attn/qkv_w``.
"""

from __future__ import annotations

import zlib

import numpy as np

STD = 0.02  # the GPT-2 / NeoX / Mistral initializer_range


def leaf_kinds(shape_tree) -> list[tuple[str, int | None, object]]:
    """[(kind, layer or None, ShapeDtypeStruct)] in the tree's own leaf order."""
    import jax

    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(shape_tree)[0]:
        parts, layer = [], None
        for key in path:
            if isinstance(key, jax.tree_util.SequenceKey):
                parts.append("*")
                layer = key.idx
            else:
                parts.append(str(getattr(key, "key", getattr(key, "name", key))))
        out.append(("/".join(parts), layer, leaf))
    return out


def _draw(key, kind: str, shape, dtype):
    """Matrices N(0, STD); norm scales 1 + N(0, STD); biases N(0, STD). Norm
    scales and biases are random rather than 1 and 0 so that a gradient check
    sees a dropped bias or scale."""
    import jax
    import jax.numpy as jnp

    sub = jax.random.fold_in(key, zlib.crc32(kind.encode()) & 0x7FFFFFFF)
    noise = jax.random.normal(sub, shape, dtype=jnp.float32) * jnp.float32(STD)
    if kind.endswith("/weight"):
        noise = noise + jnp.float32(1.0)
    return noise.astype(dtype)


def stacked_weights(shape_tree, seed):
    """{kind: array}: per-layer kinds stacked on a leading layer axis."""
    import jax

    key = jax.random.PRNGKey(seed)
    layers: dict[str, int] = {}
    leaf_of: dict[str, object] = {}
    for kind, layer, leaf in leaf_kinds(shape_tree):
        leaf_of[kind] = leaf
        if layer is not None:
            layers[kind] = max(layers.get(kind, 0), layer + 1)
    return {kind: _draw(key, kind, ((layers[kind],) if kind in layers else ()) + tuple(leaf.shape),
                        leaf.dtype)
            for kind, leaf in leaf_of.items()}


def unstack(stacked: dict, shape_tree):
    """The stacked weights laid out as the program's tree."""
    import jax

    treedef = jax.tree_util.tree_structure(shape_tree)
    leaves = [stacked[kind] if layer is None else stacked[kind][layer]
              for kind, layer, _ in leaf_kinds(shape_tree)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def make_system_weights(shape_tree, seed: int, out_shardings=None):
    """The program's parameter tree, one jitted call from the seed. Under a
    mesh ``out_shardings`` lays every leaf out as the step wants it, and each
    device draws its own shards."""
    import jax

    make = jax.jit(lambda s: unstack(stacked_weights(shape_tree, s), shape_tree),
                   out_shardings=out_shardings)
    return make(np.uint32(seed))


def make_reference_weights(shape_tree, seed: int, out_shardings=None):
    """The same numbers, stacked, for the plain reference."""
    import jax

    make = jax.jit(lambda s: stacked_weights(shape_tree, s), out_shardings=out_shardings)
    return make(np.uint32(seed))
