"""Layer ``entry``: median ms a call, over the untraced window, of the whole
call inside the program, from ``thunder_tpu.jit``'s entry to its return (span
``thunder_tpu.call``). ``dispatch_ms`` less this is the harness's own part of a
call: its read-back."""

from perfbench.layer_metrics import _dispatch


def read(reading):
    return _dispatch.median_ms(reading, "s")
