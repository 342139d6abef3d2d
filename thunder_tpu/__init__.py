"""thunder_tpu: a TPU-native source-to-source JIT compiler for PyTorch programs.

Built from scratch with the capabilities of Lightning Thunder
(reference: carmocca/lightning-thunder): programs are acquired into a
readable trace IR over a reduced primitive set, transformed (autodiff,
autocast, DCE/CSE, rematerialization, distributed rewrites), and executed by
priority-ordered pluggable executors — here JAX/XLA and Pallas kernels over
TPU, with `jax.lax` collectives on an ICI/DCN device mesh for distribution.

Public surface mirrors the reference's thunder/__init__.py: `jit`,
`last_traces`, `compile_data`, `grad`, ThunderModule, etc.

Which passes run between an acquired trace and a claimed one, and in which
order, is said once, in `thunder_tpu/pipeline.py`; every front end calls it.
"""

__version__ = "0.1.0"

from thunder_tpu.core import dtypes, devices  # noqa: F401
from thunder_tpu import torch as _ltorch  # register the torch-mirror language  # noqa: F401
from thunder_tpu.api import (  # noqa: F401
    jit,
    grad,
    value_and_grad,
    vmap,
    jvp,
    seed,
    compile_data,
    compile_stats,
    last_traces,
    last_prologue_traces,
    last_backward_traces,
    last_compile_options,
    cache_hits,
    cache_misses,
    cache_info,
    compile_phases,
    set_execution_callback_file,
)
from thunder_tpu.common import (  # noqa: F401
    CACHE_OPTIONS,
    SHARP_EDGES_OPTIONS,
    ThunderSharpEdgeError,
    ThunderSharpEdgeWarning,
)
from thunder_tpu import monitor  # noqa: F401  # metrics facade (docs/observability.md)
from thunder_tpu import resilience  # noqa: F401  # fault injection + recovery (docs/robustness.md)
from thunder_tpu.observability.profile import profile  # noqa: F401

# Legacy entry point (reference parity: thunder.compile, thunder/__init__.py:655
# — deprecated there in favor of jit; same here). Excluded from __all__ so
# `from thunder_tpu import *` cannot shadow the Python builtin.
compile = jit

__all__ = [
    "jit", "grad", "value_and_grad", "vmap", "jvp", "seed",
    "compile_data", "compile_stats", "last_traces", "last_prologue_traces",
    "last_backward_traces", "last_compile_options", "cache_hits",
    "cache_misses", "cache_info", "compile_phases",
    "set_execution_callback_file",
    "CACHE_OPTIONS", "SHARP_EDGES_OPTIONS",
    "ThunderSharpEdgeError", "ThunderSharpEdgeWarning",
    "dtypes", "devices", "monitor", "profile", "resilience",
]

