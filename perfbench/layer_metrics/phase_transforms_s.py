"""Layer ``trace-claim``: seconds of set-up in the program's ``transforms`` phase:
on the train path ``grad_transform``, the saved attention residuals and the
argument divisors; on the ``jit`` path its transform passes."""

from perfbench.layer_metrics import _phases


def read(reading):
    return _phases.seconds(reading, "transforms")
