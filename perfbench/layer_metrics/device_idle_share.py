"""Layer ``device``: 100 * (1 - busy / window) over the traced window, busy
being the union of the intervals in which an instruction ran, averaged over
the devices."""

from perfbench import xplane


def read(reading):
    if reading.trace is None or not reading.trace.devices:
        return None
    busy, window = xplane.busy_and_window(reading.trace)
    return 100.0 * (1.0 - busy / window)
