"""Layer ``trace-claim``: seconds of set-up in the program's ``trace`` phase:
acquiring the trace (and on the train path the ``dce`` after it)."""

from perfbench.layer_metrics import _phases


def read(reading):
    return _phases.seconds(reading, "trace")
