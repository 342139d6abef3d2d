"""Layer ``collectives``: the part of ``collective_ms`` during which no compute
ran on that device."""

from perfbench import xplane


def read(reading):
    if reading.trace is None or not reading.trace.devices or not reading.traced_units:
        return None
    return 1e3 * xplane.collective_and_exposed(xplane.busiest(reading.trace))[1] / reading.traced_units
