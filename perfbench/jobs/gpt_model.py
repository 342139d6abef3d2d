"""What the ``train`` and ``forward`` jobs share: the cell's configuration as
the program's ``GPTConfig``, the shapes of its parameter tree, the operations
a forward pass needs, the seeded token batches, and the run-validity checks
copied from ``chip_smoke.py``."""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from perfbench import flops, weights
from perfbench.manifest import published

KERNEL_EXECUTORS = ("flash", "pallas")


def gpt_config(keys: dict, rehearse: bool = False):
    """The program's registry entry ``registry_name`` with the run's published
    keys laid over it through the file's ``program_fields`` (``GPTConfig``
    field <- published key). Whatever the file does not name (the kind of
    norm and of MLP, biases) is the registry's: the benchmark runs the model
    the program lists, and the plain reference, which knows nothing of the
    registry, says whether that is the published one. On a key the file does
    not list under ``reduced`` the two have to agree: a width that differs is
    an error, never a private variant. ``--rehearse`` lays the stand-in sizes
    over unchecked."""
    from thunder_tpu.models import gpt

    listed = gpt.name_to_config(keys["registry_name"])
    laid = {field: keys[key] for field, key in keys["program_fields"].items()
            if getattr(listed, field) != keys[key]}
    differ = {field: (getattr(listed, field), value) for field, value in laid.items()
              if keys["program_fields"][field] not in keys["reduced"]}
    if differ and not rehearse:
        raise ValueError(f"the program's registry entry {keys['registry_name']!r} and the configuration "
                         f"file disagree (registry, file): {differ}")
    return dataclasses.replace(listed, **laid)


class JobBase:
    """What both jobs are given and derive before anything is built: no array
    is made here."""

    def __init__(self, cell, *, seed: int, platform: str, rehearse: bool):
        self.cell, self.seed, self.platform, self.rehearse = cell, seed, platform, rehearse
        self.traffic = {**cell.traffic, **(cell.traffic["stand_in"] if rehearse else {})}
        self.keys = published(cell, rehearse)
        self.cfg = gpt_config(self.keys, rehearse)
        self.shapes = param_shapes(self.cfg)
        self.batch, self.seq = self.traffic["batch"], self.traffic["seq"]
        self.tokens_per_unit = self.batch * self.seq
        self.spans: dict = {}  # host-clock seconds around the program's layers
        self.counters: dict = {}

    def matmul_params(self) -> int:
        """Weights that take part in a matmul, counted on the parameter tree
        itself so that no kind of block needs a case here: every leaf of two
        or more dimensions (the blocks' projections, the output head) but the
        embedding table, which is gathered."""
        return sum(math.prod(leaf.shape) for kind, _, leaf in weights.leaf_kinds(self.shapes)
                   if len(leaf.shape) >= 2 and kind != "wte")

    def forward_flops_per_token(self) -> float:
        return flops.forward_flops_per_token(self.matmul_params(), self.cfg.n_head, self.cfg.head_size,
                                             self.cfg.n_layer, self.seq)


def param_shapes(cfg):
    """Names, shapes and dtypes of the program's parameter tree; no array is made."""
    import jax

    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt

    return jax.eval_shape(lambda: gpt.init_params(cfg, dtype=dtypes.bfloat16, device_init=True))


def token_batch(rng: np.random.RandomState, vocab: int, batch: int, seq: int):
    """Random token ids and their next-token targets, made on the host."""
    idx = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
    return idx, np.roll(idx, -1, axis=1).astype(np.int32)


def kernels_claimed(trace) -> int:
    """Symbols of the execution trace owned by a kernel executor."""
    return sum(1 for b in trace.bound_symbols
               if b.sym.executor is not None and b.sym.executor.name in KERNEL_EXECUTORS)


def hidden_recovery() -> list[str]:
    """Nothing demoted, de-optimized or quarantined anywhere in the process."""
    from thunder_tpu.resilience import demotion, deopt

    problems = []
    if demotion.quarantine_snapshot():
        problems.append(f"executors were demoted: {demotion.quarantine_snapshot()}")
    if deopt.process_max_level() != 0:
        problems.append(f"a function was de-optimized to level {deopt.process_max_level()}")
    return problems


def off_device(platform: str, *arrays) -> list[str]:
    return [f"an output lives on {sorted({d.platform for d in a.devices()})}, not on {platform}"
            for a in arrays if {d.platform for d in a.devices()} != {platform}]
