"""Layer ``experts``: rows of the busiest held expert over the mean of the held
experts, mean over the routed layers and the traced units.
``expert_load_max_over_mean``'s reader under the name the manifest lists for
this cell."""

from perfbench.layer_metrics.expert_load_max_over_mean import read  # noqa: F401
