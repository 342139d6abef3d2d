"""Layer ``xla-fusions``: device ms a unit of work in every instruction that is
neither a Mosaic call nor a collective: matmul fusions, norms, AdamW, converts."""

from perfbench import xplane


def read(reading):
    return reading.per_unit_ms(lambda d: xplane.class_seconds(d)["xla"])
