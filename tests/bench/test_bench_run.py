"""The command as the benchmark's users run it: on a machine whose JAX
finds no TPU it exits non-zero and prints no result."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_cpu_run_exits_nonzero_without_a_result():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    proc = subprocess.run(
        [sys.executable, *spec["command"][1:], "--workload",
         spec["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "no CPU fallback" in proc.stderr


def test_every_cell_names_files_that_exist():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    configs = {c["name"]: c for c in spec["configs"]}
    for w in spec["workloads"]:
        assert os.path.exists(os.path.join(ROOT, configs[w["config"]]
                                           ["file"]))
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
    for m in spec["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
