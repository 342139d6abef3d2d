"""Layer ``entry``: median ms a call, over the untraced window, of the
dispatcher's cache lookup: the arguments flattened, the cache key and its
guards (span ``thunder_tpu.call.lookup``)."""

from perfbench.layer_metrics import _dispatch


def read(reading):
    return _dispatch.median_ms(reading, "lookup_s")
