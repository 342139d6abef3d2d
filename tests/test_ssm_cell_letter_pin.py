"""The test of ``tests/perfbench/test_pb_ssm_job.py`` that holds the
``granite-4.0-h-micro.fwd-t16k`` cell to its issue letter for letter, whole,
with Granite's configuration entry found by its name. The file's own test reads
it as ``configs[-1]``, which it was until PR 47 appended ``ouro-2.6b`` after it
(a new entry goes at the end of its list, and a depth-cut configuration has
``reduced`` keys). That file is the benchmark's and not a program PR's to edit,
so ``tests/conftest.py`` expects its test to fail and this one stands for it
until a ``benchmark`` PR finds the entry by name there and takes this file out."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # the checkout, for ``perfbench``

from perfbench import manifest  # noqa: E402

CELL = "granite-4.0-h-micro.fwd-t16k"
NEW = ["ssm_scan_ms", "ssm_scan_roofline", "ssm_conv_ms", "ssm_conv_roofline", "ssm_gate_norm_ms", "granite_full_attention_ms",
       "ssm_chunk_ops_over_required"]


def test_the_cell_is_the_issues_letter_for_letter():
    MANIFEST = manifest.load_manifest()
    cell = manifest.load_cell(CELL)
    t, c = cell.traffic, cell.config
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "granite-4.0-h-micro", "fwd_b1_t16384_zipf_v100352_last1024")
    assert (t["job"], t["batch"], t["seq"], t["last"], t["in_flight"]) == ("forward_ssm", 1, 16384, 1024, 2)
    assert (t["zipf_exponent"], t["warmup_units"], t["trace_units"], t["check_sequences"]) == (1.0, 1, 3, 1)
    assert c["reduced"] == [] and c["reference"] == "granite_hybrid" and c["deployment_chips_per_layer"] == 1
    # every key of the catalogue's row under its own name, as published
    with open("/opt/skills/guides/model-configs/architectures.jsonl", encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-micro")
    assert {k: c[k] for k in row["config"]} == row["config"] and c["source"] == row["source_url"]
    assert {"in_proj_order", "gated_norm", "time_step_limit", "mlp_packing", "chunk", "weights", "heard"} <= set(c["assumed"])
    assert {m["name"] for m in cell.per_layer} >= set(NEW) | {"kernels_ms", "mfu", "device_idle_share", "xla_ms", "dispatch_ms"}
    assert not {"kernels_roofline", "linear_attention_ms", "full_attention_ms", "collective_ms"} & {m["name"] for m in cell.per_layer}
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    granite = next(e for e in MANIFEST["configs"] if e["name"] == "granite-4.0-h-micro")
    assert len(entry["why"]) <= 200 and len(granite["why"]) <= 200 and granite["reduced"] == []
