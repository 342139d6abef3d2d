"""Layer ``entry``: median ms a call, over the untraced window, of the
generated program into jax, up to its enqueue (span
``thunder_tpu.call.launch``)."""

from perfbench.layer_metrics import _dispatch


def read(reading):
    return _dispatch.median_ms(reading, "launch_s")
