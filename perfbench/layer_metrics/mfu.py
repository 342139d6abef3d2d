"""Layer ``device``: model FLOP/s utilization in %: the operations the passes
require per token (``perfbench/flops.py``) times the untraced window's tokens
per second, over chips times the bf16 peak of ``perfbench/peaks.json``."""


def read(reading):
    if reading.peaks is None or reading.tokens_per_s is None:
        return None
    return (100.0 * reading.flops_per_token * reading.tokens_per_s
            / (reading.cell.chips * reading.peaks["bf16_flops_per_s"]))
