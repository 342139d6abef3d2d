"""Layer ``kernels``: device ms a call of every instruction in the region
``ssm.gate_norm`` (``RMSNorm(y * silu(z))`` over all heads' features behind the
recurrence, all the Mamba-2 layers together)."""

from perfbench.layer_metrics import _regions


def read(reading):
    return _regions.region_ms(reading, "ssm.gate_norm")
