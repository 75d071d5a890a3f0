"""Every cell's ``--rehearse`` twin runs end to end on the CPU and prints a
last line with exactly the contract's keys; a cell, a configuration, a traffic
mix and a per-layer metric added as new files are found with no file edited.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent.parent
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [(w["name"], w["chips"]) for w in MANIFEST["workloads"]]


def run_cell(root: Path, name: str, chips: int, trace: int, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}",
               PYTHONPATH=str(REPO))
    env.update(extra_env or {})
    proc = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         name, "--seed", "3", "--seconds", "1", "--trace", str(trace),
         "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def metric_names(kind, cell):
    return {m["name"] for m in MANIFEST[kind]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("name,chips", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_twin_prints_the_contract_line(name, chips, trace):
    line = run_cell(REPO, name, chips, trace)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}      # no breakdown: a CPU has no device plane
    assert line["correct"] is False     # a rehearsal is never a result
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"}
    if trace == 0:
        assert set(line["metrics"]) == metric_names("end_to_end", name)
    else:
        # readers of the device trace find nothing on a CPU and are left out
        assert set(line["metrics"]) <= metric_names("per_layer", name)
        assert line["metrics"]


def test_without_a_chip_the_run_fails_and_prints_nothing():
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmark" / "run.py"), "--workload",
         CELLS[0][0], "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def copy_of_the_benchmark(tmp_path) -> Path:
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(REPO / "cub200_bpe_vsize_7800.json",
               tmp_path / "cub200_bpe_vsize_7800.json")
    return tmp_path / "benchmark"


def test_new_cell_config_mix_and_metric_are_found_as_files(tmp_path):
    bench = copy_of_the_benchmark(tmp_path)
    config = json.loads((bench / "configs" / "cub200.json").read_text())
    config["name"] = "cub200-again"
    (bench / "configs" / "cub200-again.json").write_text(json.dumps(config))
    traffic = json.loads((bench / "traffic" / "train-b16.json").read_text())
    traffic["tiny"]["global_batch"] = 2
    (bench / "traffic" / "train-b2.json").write_text(json.dumps(traffic))
    (bench / "layer_metrics" / "train_steps_counted.py").write_text(
        "def read(run):\n    return run.outcome.host['steps']\n")
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "cub200-again", "source": config["source"],
        "file": "benchmark/configs/cub200-again.json", "reduced": [],
        "why": "test"})
    manifest["workloads"].append({
        "name": "again-train", "config": "cub200-again",
        "traffic": "train-b2", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"]:
        if m["name"] == "train_images_per_s":
            m["workloads"].append("again-train")
    manifest["per_layer"].append({
        "name": "train_steps_counted", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "train_images_per_s", "workloads": ["again-train"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    line = run_cell(tmp_path, "again-train", 1, 1)
    assert set(line["metrics"]) == {"train_steps_counted"}
    assert line["metrics"]["train_steps_counted"]["unit"] == "steps"
    assert line["attempted"] == line["metrics"]["train_steps_counted"]["value"]
    line = run_cell(tmp_path, "again-train", 1, 0)
    assert set(line["metrics"]) == {"train_images_per_s", "setup_s"}
