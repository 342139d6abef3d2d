"""Layer ``kernels``: least time over measured time, in %, summed over the
Mosaic calls whose family ``kernel_families/`` knows. Each family's own
share and binding bound are printed on an earlier line of the run."""

from perfbench.reading import kernel_family_table


def read(reading):
    rows = kernel_family_table(reading).values()
    measured = sum(r["ms"] for r in rows)
    return 100.0 * sum(r["least_ms"] for r in rows) / measured if measured else None
