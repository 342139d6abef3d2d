"""Layer ``xla-compile``: seconds jax spent running the Python body of the train
step to trace it, part of ``compile_first_call_s``."""

from perfbench.layer_metrics import _phases


def read(reading):
    return _phases.seconds(reading, "jax_trace")
