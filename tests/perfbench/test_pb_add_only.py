"""A later PR adds files and entries and edits no file that is here. In a copy
of the benchmark: a model whose blocks the reference already has (a Llama) as
a configuration file, a job of a new name in a file of its own, a traffic mix
that names it, and a per-layer metric with its reader; then the new cell runs
through ``perfbench/run.py --rehearse --trace 1`` with not one file changed."""

import json
import os
import shutil
import subprocess
import sys

from pb_helpers import MANIFEST, REPO, result_of

NEW_JOB = '''"""Job ``forward_pair``: two forward calls a unit of work."""

from perfbench.jobs import forward


class Job(forward.Job):
    def issue(self, idx):
        super().issue(idx)
        return super().issue(idx)

    def flops_per_token(self):
        return 2.0 * self.forward_flops_per_token()


lower_for = forward.lower_for
'''

NEW_METRIC = '''"""Layer ``entry``: the rate by the median interval."""


def read(reading):
    return reading.window.median_units_per_s()
'''


def test_a_configuration_a_job_a_mix_and_a_metric_are_files_and_entries(tmp_path):
    bench = tmp_path / "perfbench"
    shutil.copytree(os.path.join(REPO, "perfbench"), bench, ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "thunder_tpu"), tmp_path / "thunder_tpu")
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    config = json.loads((bench / "configs" / "mistral-7b.json").read_text())
    config.update(source="https://huggingface.co/meta-llama/Llama-2-7b-hf", registry_name="llama-2-7b",
                  model_type="llama", intermediate_size=11008, num_key_value_heads=32, reduced=["num_hidden_layers"])
    del config["program_fields"]["n_query_groups"]  # the registry's entry says None: a key-value head a query head
    config["stand_in"]["num_key_value_heads"] = 2
    (bench / "configs" / "llama-2-7b.json").write_text(json.dumps(config))
    traffic = json.loads((bench / "traffic" / "fwd_b8_t2048.json").read_text())
    (bench / "traffic" / "fwd_pair_b8_t2048.json").write_text(json.dumps(dict(traffic, job="forward_pair")))
    (bench / "jobs" / "forward_pair.py").write_text(NEW_JOB)
    (bench / "layer_metrics" / "median_units_per_s.py").write_text(NEW_METRIC)

    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append({"name": "llama-2-7b", "source": config["source"], "reduced": ["num_hidden_layers"],
                                "file": "perfbench/configs/llama-2-7b.json", "why": "a test's"})
    manifest["workloads"].append({"name": "llama-2-7b.fwd-pair", "config": "llama-2-7b",
                                  "traffic": "fwd_pair_b8_t2048", "chips": 1, "why": "a test's"})
    manifest["per_layer"].append({"name": "median_units_per_s", "unit": "units/s", "better": "higher",
                                  "source": "host_clock", "layer": "entry", "moves": "tokens_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, str(bench / "run.py"), "--workload", "llama-2-7b.fwd-pair", "--seed", "7",
                           "--seconds", "1", "--trace", "1", "--rehearse"],
                          capture_output=True, text=True, timeout=600, env=env, cwd=tmp_path)
    result = result_of(proc)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["median_units_per_s"]["value"] > 0
    assert result["metrics"]["kernels_claimed"]["value"] > 0
    assert all(p.read_bytes() == body for p, body in before.items()), "a file that was there changed"
