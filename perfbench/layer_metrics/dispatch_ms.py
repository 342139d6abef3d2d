"""Layer ``entry``: median host time for one step or call to return, without
blocking, over the untraced window."""

import statistics


def read(reading):
    d = reading.window.dispatch_s
    return 1e3 * statistics.median(d) if d else None
