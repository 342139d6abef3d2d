"""Shared by the routed-expert readers. The grouped matmuls in the device
trace: XLA's own ragged dot (Mosaic calls it names ``ragged-dot-*``, the
metadata call that lays the groups out among them) or jax's megablox kernel
(``gmm``). Their required work follows the rows that were *routed* here, which
only the program can count: the job keeps the traced units' ids and hands out
``counters["routed_rows"]``, a function that counts them after the windows
(units x expert layers x held experts). A program without routed experts, or a
job that keeps no such counter, reads as nothing."""

import re

from perfbench import xplane

_GROUPED = re.compile(r"^(ragged-dot|gmm|tgmm)\b")


def device_seconds(device) -> float:
    return sum(own for ev, own in xplane.self_seconds(device.ops)
               if _GROUPED.match(xplane.instruction(ev.name)[0]))


def routed_rows(reading):
    """[[[rows of an expert] a layer] a traced unit], or ``None``."""
    count = reading.counters.get("routed_rows")
    rows = count() if callable(count) else None
    return rows if rows else None
