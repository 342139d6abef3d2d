"""Layer ``experts``: routed experts a token computes here, mean over the routed
layers and the traced units: 0.25 when the router is even over its 768 outputs
(12 a token, 16 held). ``routed_here_per_token``'s reader under the name the
manifest lists for this cell."""

from perfbench.layer_metrics.routed_here_per_token import read  # noqa: F401
