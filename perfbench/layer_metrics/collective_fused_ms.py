"""Layer ``collectives``: device ms a unit of work in the compute fusions that
carry a step of a collective themselves (``calls=%async_collective_fusion``):
the matmuls XLA overlapped with the ring that gathers their weights. They
count under ``xla_ms``, and a wait on a neighbour inside one looks like compute
to ``collective_exposed_ms``. What the overlap costs is this less the same
matmuls' time where nothing is gathered (the one-chip cell of the same
configuration, layer for layer); better overlap lowers it towards that."""

from perfbench import xplane


def read(reading):
    return reading.per_unit_ms(xplane.fused_with_collective_seconds)
