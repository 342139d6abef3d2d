"""Shared by the perfbench tests: where things are, and a rehearsal run."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(REPO, "perfbench", "run.py")
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}

with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
# What only the chip can give: read from the device trace, or scaled by the chip's peak.
DEVICE_ONLY = {m["name"] for m in MANIFEST["per_layer"] if m["source"] == "device_trace"} | {"mfu"}


def metrics_for(cell: str, group: str) -> list:
    return [m for m in MANIFEST[group] if "workloads" not in m or cell in m["workloads"]]


def run_cell(cell: str, *extra, trace: int, seconds: float = 1.0, timeout: int = 600):
    """``perfbench/run.py`` as the driver starts it, in a process of its own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--seed", "7", "--seconds", str(seconds),
         "--trace", str(trace), *extra],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)


def result_of(proc) -> dict:
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
