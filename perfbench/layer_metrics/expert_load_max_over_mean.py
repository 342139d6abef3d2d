"""Layer ``experts``: rows of the busiest held expert over the mean of the held
experts, mean over the expert layers and the traced units: what uneven routing
costs a grouped matmul whose time follows its busiest group's tiles, and a
deployment its slowest chip."""

import statistics

from perfbench.layer_metrics import _experts


def read(reading):
    rows = _experts.routed_rows(reading)
    if rows is None:
        return None
    ratios = [max(layer) / statistics.fmean(layer) for unit in rows for layer in unit if sum(layer)]
    return statistics.fmean(ratios) if ratios else None
