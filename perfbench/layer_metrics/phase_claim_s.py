"""Layer ``trace-claim``: seconds of set-up in executor claiming and what rides
on it: ``claim`` (on the train path it holds the comm scheduler) and the
``jit`` path's ``static_analysis``."""

from perfbench.layer_metrics import _phases


def read(reading):
    return _phases.seconds(reading, "claim", "static_analysis")
