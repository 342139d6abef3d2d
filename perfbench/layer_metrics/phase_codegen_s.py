"""Layer ``trace-claim``: seconds of set-up in the program's ``codegen`` phase,
printing and executing the generated Python. Not ``staging``:
``trace_claim_s`` leaves it out on the ``jit`` path too."""

from perfbench.layer_metrics import _phases


def read(reading):
    return _phases.seconds(reading, "codegen")
