"""Every cell through ``perfbench/run.py --rehearse --trace 1``: the per-layer
metrics a host can give are there, and nothing a CPU measured stands under the
name of a device metric."""

import pytest
from pb_helpers import CELLS, CONTRACT_KEYS, DEVICE_ONLY, metrics_for, result_of, run_cell


@pytest.mark.parametrize("cell", CELLS)
def test_per_layer_metrics(cell):
    result = result_of(run_cell(cell, "--rehearse", trace=1))
    assert set(result) == CONTRACT_KEYS | {"breakdown"}
    assert result["correct"] is True and result["failed"] == 0
    wanted = {m["name"]: m["unit"] for m in metrics_for(cell, "per_layer")}
    assert set(result["metrics"]) == set(wanted) - DEVICE_ONLY
    assert all(result["metrics"][n]["unit"] == wanted[n] for n in result["metrics"])
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert result["metrics"]["kernels_claimed"]["value"] > 0
    # The trace of a CPU has no device plane: not measured, and said so.
    assert result["device"]["busy_s"] is None and result["device"]["window_s"] is None
    assert result["breakdown"] == {"device_ops": [], "idle_gaps": []}
