"""Layer ``kernels``: device ms a unit of work in Mosaic custom calls, all of
them, whatever their names."""

from perfbench import xplane


def read(reading):
    return reading.per_unit_ms(lambda d: xplane.class_seconds(d)["kernel"])
