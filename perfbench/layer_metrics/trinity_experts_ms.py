"""Layer ``kernels``: device ms a call in the routed experts' grouped matmuls,
for the cell that holds all 128 experts of 1024. ``experts_ms``'s reader under
the name the manifest lists for this cell (the older entry's ``workloads`` is
not this PR's to lengthen)."""

from perfbench.layer_metrics.experts_ms import read  # noqa: F401
