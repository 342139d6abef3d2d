"""Layer ``entry``: median ms a call, over the untraced window, of the
arguments converted to jax and padded, up to the program's call (span
``thunder_tpu.call.prepare``)."""

from perfbench.layer_metrics import _dispatch


def read(reading):
    return _dispatch.median_ms(reading, "prepare_s")
