#!/usr/bin/env python3
"""One sha256 a cell of ``BENCHMARK.json``, of the step as it lowers for a
described ``v5e:2x2``: to show that two commits compile one program, run this
from both and compare. Nothing runs and no chip is needed; a minute a cell.

    JAX_PLATFORMS=cpu python3 scripts/hash_cells.py [--workload CELL ...] [--text DIR]

A train cell (job ``train`` or ``train_loop``) goes through ``build_train_step``;
a forward cell through ``trace_program``, ``pipeline.clean`` and
``pipeline.compile_trace``, as the dispatcher takes it. What is hashed is
``lowered.as_text()`` with every Mosaic payload decoded (a
``tpu_custom_call``'s ``backend_config`` holds base64 of MLIR bytecode) and
printed without locations: that strikes source paths and line numbers, which
differ between two checkouts, and nothing else. ``--text DIR`` keeps the
hashed text a cell, for ``diff``. Beside each hash: the compiler's
``memory_analysis()``. Run by hand, not by the tests.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_BACKEND_CONFIG = re.compile(r'backend_config = "((?:[^"\\]|\\.)*)"')


def without_locations(text: str) -> str:
    """``text`` with each Mosaic payload as MLIR assembly, no debug info."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def decoded(match):
        raw = re.sub(r"\\([0-9A-Fa-f]{2})", lambda m: chr(int(m.group(1), 16)), match.group(1))
        try:
            config = json.loads(raw)
            body = base64.b64decode(config["custom_call_config"]["body"])
        except (ValueError, KeyError, TypeError):
            return match.group(0)  # not a Mosaic call's
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            config["custom_call_config"]["body"] = ir.Module.parse(body).operation.get_asm(enable_debug_info=False)
        return "backend_config = " + json.dumps(config, sort_keys=True)

    return _BACKEND_CONFIG.sub(decoded, text)


def lower_forward(cell, keys, topo):
    """A forward cell's program as ``thunder_tpu.jit`` compiles it, lowered for
    one described chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from perfbench.jobs import gpt_model
    from perfbench.rehearse import with_sharding
    from thunder_tpu import pipeline
    from thunder_tpu.api import trace_program
    from thunder_tpu.extend import resolve_executors
    from thunder_tpu.models import gpt

    cfg, last = gpt_model.gpt_config(keys), cell.traffic.get("last")
    shapes = gpt_model.param_shapes(cfg)
    tokens = jax.ShapeDtypeStruct((cell.traffic["batch"], cell.traffic["seq"]), jnp.int32)
    _, comp = trace_program(lambda p, i: gpt.forward(p, i, cfg, last=last), (shapes, tokens), {})
    run = pipeline.compile_trace(pipeline.clean(comp)[-1], resolve_executors(None)).claimed.python_callable()
    one = SingleDeviceSharding(topo.devices[0])
    return jax.jit(run).lower(*(with_sharding(a, one) for a in jax.tree_util.tree_leaves((shapes, tokens))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", action="append", help="a cell of BENCHMARK.json; default: every cell")
    p.add_argument("--text", help="a directory that gets each cell's hashed text")
    args = p.parse_args(argv)

    from perfbench import manifest
    from perfbench.jobs import train
    from perfbench.rehearse import describe_topology, kernels_compiled_not_interpreted, persistent_cache_off
    from perfbench.run import executable_needs
    from thunder_tpu.api import _ensure_runtime
    from thunder_tpu.executors import pallasex

    _ensure_runtime()  # x64 dtype semantics, as the dispatcher and build_train_step set them before they trace
    topo = describe_topology()
    # the checkers that size themselves on the generation's VMEM ask this: the described chip's, not the CPU's
    pallasex._device_kind = lambda: topo.devices[0].device_kind
    for name in args.workload or [w["name"] for w in manifest.load_manifest()["workloads"]]:
        cell = manifest.load_cell(name)
        keys, job = manifest.published(cell), cell.traffic["job"]
        with kernels_compiled_not_interpreted(), persistent_cache_off():
            if job.startswith("train"):  # train, train_loop: build_train_step
                lowered = train.lower_for(cell, keys, cell.traffic["batch"], cell.traffic["seq"], topo)
            else:
                lowered = lower_forward(cell, keys, topo)
            text = without_locations(lowered.as_text())
            _, sizes = executable_needs(lowered.compile())
        if args.text:
            os.makedirs(args.text, exist_ok=True)
            with open(os.path.join(args.text, f"{name}.mlir"), "w") as f:
                f.write(text)
        print(json.dumps({"workload": name, "sha256": hashlib.sha256(text.encode()).hexdigest(),
                          "tpu_custom_calls": text.count("@tpu_custom_call"), **sizes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
