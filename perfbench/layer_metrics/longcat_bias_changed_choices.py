"""Layer ``experts``: the share, in %, of the (token, choice) pairs of the traced
units whose output the router would not have chosen without its bias.
``bias_changed_choices``'s reader under the name the manifest lists for this
cell. 0 means the drawn bias is idle."""

from perfbench.layer_metrics.bias_changed_choices import read  # noqa: F401
