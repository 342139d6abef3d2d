"""Layer ``trace-claim``: symbols of the execution trace that a kernel executor
(``flash``, ``pallas``) owns. A count, reported and not enforced."""


def read(reading):
    return reading.counters.get("kernels_claimed")
