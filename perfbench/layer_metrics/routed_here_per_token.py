"""Layer ``experts``: routed experts a token computes here, mean over the
expert layers and the traced units: ``num_experts_per_tok`` times the held
share of the experts (0.5 for 12 of 192 at 8 a token) when the router is
even."""

import statistics

from perfbench.layer_metrics import _experts


def read(reading):
    rows = _experts.routed_rows(reading)
    if rows is None:
        return None
    tokens = reading.counters["tokens_per_unit"]
    return statistics.fmean(sum(layer) / tokens for unit in rows for layer in unit)
