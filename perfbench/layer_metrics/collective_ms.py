"""Layer ``collectives``: ms a unit of work during which a collective was
running or in flight on the busiest device."""

from perfbench import xplane


def read(reading):
    if reading.trace is None or not reading.trace.devices or not reading.traced_units:
        return None
    return 1e3 * xplane.collective_and_exposed(xplane.busiest(reading.trace))[0] / reading.traced_units
