"""Layer ``kernels``: least time over measured time, in %, of the routed
experts' grouped matmuls. The least time is for the rows the router sent to
each held expert in the traced units' own batches (``2 * rows * 3 * hidden *
width`` operations; ``perfbench/flops_mla_moe.py``), never for the buffer's
worst-case rows."""

from perfbench import flops, flops_mla_moe
from perfbench.layer_metrics import _experts


def read(reading):
    ms = reading.per_unit_ms(_experts.device_seconds)
    rows = _experts.routed_rows(reading)
    if not ms or rows is None or reading.peaks is None:
        return None
    config = reading.cell.config
    least = sum(flops.least_seconds(*flops_mla_moe.experts(layer, config["hidden_size"],
                                                           config["moe_intermediate_size"]), reading.peaks)[0]
                for unit in rows for layer in unit)
    return 100.0 * (1e3 * least / len(rows)) / ms
