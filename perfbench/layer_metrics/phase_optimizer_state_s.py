"""Layer ``trace-claim``: seconds of set-up ``build_train_step`` spent making the
optimizer state (``adamw_init``, eager, and under a mesh its ``device_put``),
which the host clock around the call counts into ``trace_claim_s``."""

from perfbench.layer_metrics import _phases


def read(reading):
    return _phases.seconds(reading, "optimizer_state")
