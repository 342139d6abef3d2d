#!/usr/bin/env python3
"""Compile a cell's step at its real size for a described ``v5e:2x2`` topology,
from a host with no chip, and print what the TPU's compiler says of it:
``memory_analysis()``, the count of Mosaic calls and of collectives, the
seconds the compile took. Nothing runs, so it says nothing of results or
times, and a compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse.py                       # every cell as BENCHMARK.json has it
    JAX_PLATFORMS=cpu python3 perfbench/rehearse.py --workload mistral-7b.train --depth 5 --depth 6
    JAX_PLATFORMS=cpu python3 perfbench/rehearse.py --workload pythia-410m.fwd --batch 8 --batch 16

This is how PR 22 fixed the micro-batch and the two depths before any chip
call, and how a later PR sizes a cell of its own: the rule is in the traffic
file's ``sized_by``, the numbers come from here. Run by hand, not by the tests
(a whole pythia-410m step takes a minute).

The program picks interpret mode for its kernels from ``jax.default_backend()``,
which is the CPU here; the script steers it as
``tests/test_chip_smoke.py::test_train_step_lowers_for_tpu_with_mosaic_kernels``
does (``THUNDER_FLASH_FORCE=1`` and the two ``_interpret`` functions replaced),
not through an option of the program. ``build_train_step`` lays the optimizer
state out with ``jax.device_put``, which a described device cannot take, so
that one call is replaced by its shapes while the step is built. The forward
job's executable is built by the dispatcher at its first call, which cannot
run here; its trace goes through the same claiming pass by hand instead.

How a job's step is lowered is the job's: ``perfbench/jobs/<job>.py`` has a
function ``lower_for(cell, keys, batch, seq, topo)`` beside its ``Job``, and
uses ``with_sharding`` and ``device_put_as_shapes`` from here.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import sys
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)

GB = 1e9


def describe_topology():
    """The described chips. Called from ``main`` only, never at import: one
    process at a time may load the TPU's library."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@contextlib.contextmanager
def kernels_compiled_not_interpreted():
    from thunder_tpu.executors import flashex, pallasex

    saved = (os.environ.get("THUNDER_FLASH_FORCE"), flashex._interpret, pallasex._interpret)
    os.environ["THUNDER_FLASH_FORCE"] = "1"
    flashex._interpret = pallasex._interpret = lambda: False
    try:
        yield
    finally:
        if saved[0] is None:
            del os.environ["THUNDER_FLASH_FORCE"]
        else:
            os.environ["THUNDER_FLASH_FORCE"] = saved[0]
        flashex._interpret, pallasex._interpret = saved[1], saved[2]


@contextlib.contextmanager
def persistent_cache_off():
    """A compile for a described chip is written to the cache and can never be
    read back without one; keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def with_sharding(tree, sharding_tree_or_one):
    import jax

    if isinstance(sharding_tree_or_one, jax.sharding.Sharding):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding_tree_or_one), tree)
    return jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree, sharding_tree_or_one)


@contextlib.contextmanager
def device_put_as_shapes():
    """``jax.device_put(tree, shardings)`` returning the tree's shapes with
    those shardings, for the time ``build_train_step`` runs."""
    import jax

    real, jax.device_put = jax.device_put, with_sharding
    try:
        yield
    finally:
        jax.device_put = real


def rehearse(cell, topo, *, batch: int | None, depth: int | None) -> dict:
    from perfbench import manifest, xplane
    from perfbench.run import executable_needs

    keys = manifest.published(cell)
    if depth is not None:  # a cut that is being tried out
        keys.update(num_hidden_layers=depth, reduced=[*keys["reduced"], "num_hidden_layers"])
    batch = batch if batch is not None else cell.traffic["batch"]
    seq = cell.traffic["seq"]
    lower = importlib.import_module(f"perfbench.jobs.{cell.traffic['job']}").lower_for
    t0 = time.perf_counter()
    with kernels_compiled_not_interpreted(), persistent_cache_off():
        lowered = lower(cell, keys, batch, seq, topo)
        t1 = time.perf_counter()
        compiled = lowered.compile()
    t2 = time.perf_counter()
    needs, sizes = executable_needs(compiled)
    text = compiled.as_text()
    return {
        "workload": cell.name, "batch": batch, "seq": seq, "depth": keys["num_hidden_layers"],
        "chips": cell.chips, "needs_gb_per_chip": round(needs / GB, 3),
        **{k.replace("_size_in_bytes", "_gb"): round(sizes[k] / GB, 3)
           for k in ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes", "alias_size_in_bytes")},
        "tpu_custom_calls": text.count('custom_call_target="tpu_custom_call"'),
        "collectives": {op: text.count(f" {op}(") + text.count(f" {op}-start(") for op in xplane.COLLECTIVES
                        if f" {op}(" in text or f" {op}-start(" in text},
        "trace_claim_lower_s": round(t1 - t0, 1), "compile_s": round(t2 - t1, 1),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", action="append", help="a cell of BENCHMARK.json; default: every cell")
    p.add_argument("--batch", type=int, action="append", help="try this batch instead of the traffic file's")
    p.add_argument("--depth", type=int, action="append", help="try this depth instead of the configuration's")
    args = p.parse_args(argv)

    from perfbench import manifest

    names = args.workload or [w["name"] for w in manifest.load_manifest()["workloads"]]
    topo = describe_topology()
    for name in names:
        cell = manifest.load_cell(name)
        for batch in args.batch or [None]:
            for depth in args.depth or [None]:
                print(json.dumps(rehearse(cell, topo, batch=batch, depth=depth)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
