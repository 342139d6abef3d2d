"""Kernel families: which Mosaic call is which, and what each call needs.

One file per family, ``perfbench/layer_metrics/kernel_families/<family>.json``: a regular
expression over the text of the HLO instruction as the device trace names it,
whose named groups capture dimension lists, and the dotted name of the cost
function those lists are handed to (``perfbench/flops.py`` has the first
five). A later kernel brings a file here and a cost function in a module of
its own. Files are tried in name order; a call that matches none still counts
in ``kernels_ms``.
"""

from __future__ import annotations

import functools
import glob
import importlib
import json
import os
import re

_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "layer_metrics", "kernel_families")


def _resolve(dotted: str):
    module, _, name = dotted.rpartition(".")
    return getattr(importlib.import_module(module), name)


@functools.lru_cache(maxsize=None)
def _families() -> tuple:
    rows = []
    for path in sorted(glob.glob(os.path.join(_DIR, "*.json"))):
        with open(path, encoding="utf-8") as f:
            row = json.load(f)
        rows.append((row["family"], re.compile(row["pattern"], re.S), _resolve(row["cost"])))
    return tuple(rows)


@functools.lru_cache(maxsize=4096)
def match(instruction_text: str):
    """``(family, operations, bytes)`` of a Mosaic call, or ``None``."""
    for family, pattern, cost in _families():
        m = pattern.search(instruction_text)
        if m:
            dims = {k: [int(d) for d in v.split(",") if d] for k, v in m.groupdict().items()}
            return (family, *cost(**dims))
    return None
