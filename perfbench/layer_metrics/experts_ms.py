"""Layer ``kernels``: device ms a call in the routed experts' grouped matmuls."""

from perfbench.layer_metrics import _experts


def read(reading):
    ms = reading.per_unit_ms(_experts.device_seconds)
    return ms if ms else None
