"""Kernels of the main path compiled at real widths for a described v5e, from
a host with no chip: what Mosaic's compiler refuses (a slice off the tiling,
too much VMEM) it refuses here, at no chip time. Interpret mode, which every
other test of the kernels runs in, cannot show that.

One process at a time may load the TPU's library, so the topology is described
inside a fixture (never at import) and every such test lives in this file."""

from types import SimpleNamespace

import pytest


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu from describing a chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent cache and never read back.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


ROPE_SHAPES = [
    # dtype, (B, H, T, hs), n
    ("bfloat16", (8, 16, 2048, 64), 16),    # pythia-410m.fwd's q and k
    ("bfloat16", (4, 32, 2048, 80), 32),    # phi-2
    ("bfloat16", (1, 8, 4096, 128), 64),
    ("bfloat16", (2, 4, 2048, 256), 64),    # the widest row the checker takes
    ("float32", (2, 4, 2048, 128), 32),
    ("bfloat16", (2, 4, 8, 64), 16),        # the shortest block
    ("bfloat16", (1, 32, 4096, 128), 128),  # mistral-7b.train's q: full rotary
]


def _rope_claim_and_lowering(monkeypatch, one_chip, dtype, shape, n):
    """(what the checker says of the shapes, the rope call lowered for the described chip)."""
    import jax
    import jax.numpy as jnp

    from thunder_tpu.core import dtypes
    from thunder_tpu.executors import pallasex

    tables = (shape[-2], n)
    proxy = lambda s: SimpleNamespace(shape=s, dtype=getattr(dtypes, dtype))
    sds = lambda s: jax.ShapeDtypeStruct(s, getattr(jnp, dtype), sharding=one_chip)
    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    return (pallasex._rope_checker(proxy(shape), proxy(tables), proxy(tables)),
            jax.jit(pallasex._rope_impl, donate_argnums=0).lower(sds(shape), sds(tables), sds(tables)))


@pytest.mark.parametrize("dtype,shape,n", ROPE_SHAPES, ids=[f"{d}-{s[-1]}-{n}-T{s[-2]}" for d, s, n in ROPE_SHAPES])
def test_rope_kernel_compiles_for_v5e(one_chip, monkeypatch, dtype, shape, n):
    """Every shape the rope checker takes compiles, in place where the rotary is partial."""
    claimed, lowered = _rope_claim_and_lowering(monkeypatch, one_chip, dtype, shape, n)
    assert claimed
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert ("output_to_operand_aliasing" in text) == (n != shape[-1])


@pytest.mark.parametrize("dtype,hs", [("float32", 256), ("float16", 64)])
def test_rope_checker_declines_what_does_not_compile(one_chip, monkeypatch, dtype, hs):
    """Partial rotary in float32 at 256 lanes runs out of VMEM and float16 has no
    matmul on the v5e: the checker says no, because nothing falls back at run time."""
    claimed, lowered = _rope_claim_and_lowering(monkeypatch, one_chip, dtype, (8, 16, 2048, hs), 16)
    assert not claimed
    with pytest.raises(Exception):  # noqa: B017, PT011 - Mosaic's own error, whatever its type
        lowered.compile()
