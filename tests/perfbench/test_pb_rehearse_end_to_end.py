"""Every cell of ``BENCHMARK.json`` through ``perfbench/run.py --rehearse
--trace 0`` (stand-in sizes, virtual CPU devices, the kernels interpreted):
the result line is the contract's, and without ``--rehearse`` a CPU is refused."""

import pytest
from pb_helpers import CELLS, CONTRACT_KEYS, metrics_for, result_of, run_cell


@pytest.mark.parametrize("cell", CELLS)
def test_result_line_is_the_contracts(cell):
    result = result_of(run_cell(cell, "--rehearse", trace=0))
    assert set(result) == CONTRACT_KEYS
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    # A rehearsal names the CPU it ran on: never a chip's result.
    peak = result["device"].pop("memory_peak_bytes")
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 8} and peak > 0
    wanted = {m["name"]: m["unit"] for m in metrics_for(cell, "end_to_end")}
    assert set(result["metrics"]) == set(wanted)
    for name, reading in result["metrics"].items():
        assert set(reading) == {"value", "unit"} and reading["unit"] == wanted[name]
        assert isinstance(reading["value"], float) and reading["value"] > 0


def test_refuses_a_cpu_and_prints_no_result():
    proc = run_cell(CELLS[0], trace=0, timeout=120)
    assert proc.returncode != 0
    assert "platform 'tpu'" in proc.stderr and "Nothing was run" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_an_unknown_cell_is_an_error_not_a_default():
    proc = run_cell("no-such.cell", "--rehearse", trace=0, timeout=120)
    assert proc.returncode != 0
    assert "no workload 'no-such.cell'" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_with_only_the_benchmarks_own_files_it_prints_no_result(tmp_path):
    """In a directory that holds ``BENCHMARK.json`` and the files under
    ``paths`` and nothing else, the program cannot be imported: a non-zero
    exit and no result line."""
    import os
    import shutil
    import subprocess
    import sys

    from pb_helpers import MANIFEST, REPO

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for path in MANIFEST["paths"]:
        shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k not in ("PYTHONPATH", "JAX_COMPILATION_CACHE_DIR")}
    proc = subprocess.run([sys.executable, *MANIFEST["command"][1:], "--workload", CELLS[0], "--seed", "1",
                           "--seconds", "1", "--trace", "0", "--rehearse"],
                          capture_output=True, text=True, timeout=300, env=env, cwd=tmp_path)
    assert proc.returncode != 0
    assert "No module named 'thunder_tpu'" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
