"""Layer ``kernels``: device ms a call in the routed experts' grouped matmuls,
for the cell that holds 16 of 512 experts of 2048 beside 256 zero-compute ones.
``experts_ms``'s reader under the name the manifest lists for this cell."""

from perfbench.layer_metrics.experts_ms import read  # noqa: F401
