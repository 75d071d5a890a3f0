"""The readers of the program's compile log and of the sampler's scope
(PR 24): numbers on a rehearsal, None where the program has no log or never
installed it, and the new cell's files found by name."""
import json
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

from benchmark import harness, trace_reduce
from benchmark.layer_metrics import _compiles

REPO = Path(__file__).resolve().parent.parent.parent
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
LOG_READERS = ["setup_trace_lower_s", "setup_compile_load_s",
               "setup_programs", "setup_cache_misses",
               "train_window_compiles", "gen_window_compiles"]


def fake_run(setup_s=5.0, window_s=10.0, trace=None):
    outcome = harness.Outcome(correct=True, attempted=1, failed=0,
                              end_to_end={"setup_s": setup_s},
                              host={"window_s": window_s})
    return harness.Run(cell=None, dalle_cfg=None, vae_cfg=None, devices=[],
                       peaks=None, outcome=outcome, trace=trace)


@pytest.fixture()
def log(monkeypatch):
    """An installed log holding hand-made records around ready = 105 s."""
    from dalle_pytorch_tpu.obs import compiles

    monkeypatch.setattr(compiles, "_installed", True)
    monkeypatch.setitem(sys.modules, "benchmark.run",
                        types.SimpleNamespace(T_START=100.0))
    monkeypatch.delattr(sys.modules["__main__"], "T_START", raising=False)
    compiles.clear()
    with compiles._lock:
        compiles._records.extend([
            {"phase": "trace", "fun_name": "step", "t": 101.0, "dur_s": 1.5},
            {"phase": "lower", "fun_name": "step", "t": 102.0, "dur_s": 0.5},
            {"phase": "cache_request", "fun_name": None, "t": 102.1,
             "dur_s": None},
            {"phase": "cache_miss", "fun_name": None, "t": 103.9,
             "dur_s": None},
            {"phase": "compile", "fun_name": "step", "t": 104.0,
             "dur_s": 1.75},
            {"phase": "trace", "fun_name": "add", "t": 107.0, "dur_s": 0.25},
            {"phase": "compile", "fun_name": "add", "t": 107.5,
             "dur_s": 0.25},
            {"phase": "trace", "fun_name": "late", "t": 115.0, "dur_s": 1.0},
        ])
    yield compiles
    compiles.clear()


def test_log_readers_split_setup_from_the_window(log):
    run = fake_run()
    read = {name: harness.load_reader(name)(run) for name in LOG_READERS}
    assert read == {"setup_trace_lower_s": 2.0, "setup_compile_load_s": 1.75,
                    "setup_programs": 1.0, "setup_cache_misses": 1.0,
                    "train_window_compiles": 1.0, "gen_window_compiles": 1.0}
    # a count that reads 0 is a reading, not a silence
    quiet = fake_run(setup_s=8.0, window_s=2.0)
    assert harness.load_reader("gen_window_compiles")(quiet) == 0.0
    assert isinstance(harness.load_reader("setup_programs")(quiet), float)


def test_log_readers_are_silent_without_the_log(log, monkeypatch):
    run = fake_run()
    monkeypatch.setattr(log, "_installed", False)     # never switched on
    assert all(harness.load_reader(n)(run) is None for n in LOG_READERS)
    monkeypatch.setattr(log, "_installed", True)
    # a program from before the log existed: the import fails, no raise
    import dalle_pytorch_tpu.obs

    monkeypatch.delattr(dalle_pytorch_tpu.obs, "compiles")
    monkeypatch.setitem(sys.modules, "dalle_pytorch_tpu.obs.compiles", None)
    assert all(harness.load_reader(n)(run) is None for n in LOG_READERS)
    monkeypatch.undo()
    assert _compiles.before_ready(fake_run(setup_s=None)) is None


def test_sampler_share_reads_the_sample_scope():
    hlo = ('  %sort.1 = f32[4,8]{1,0} sort(%p), dimensions={1}, metadata='
           '{op_name="jit(f)/graftprof:decode-step/while/body/'
           'graftprof:sample/sort"}\n'
           '  %fusion.2 = f32[4,8]{1,0} fusion(%p), kind=kLoop, metadata='
           '{op_name="jit(f)/graftprof:decode-step/while/body/add"}\n')
    scopes = {"jit_f": trace_reduce.scopes_of(hlo)}
    assert scopes["jit_f"] == {"sort.1": "sample", "fusion.2": "decode-step"}
    raw = {"devices": [{"name": "/device:TPU:0",
                        "ops": [["sort.1", 0, 250, "jit_f"],
                                ["fusion.2", 250, 750, "jit_f"]],
                        "modules": [["jit_f", 0, 1000]], "collectives": []}],
           "host_spans": []}
    reduced = trace_reduce.reduce(raw, scopes=scopes)
    read = harness.load_reader("gen_sampler_share_pct")
    assert read(fake_run(trace=reduced)) == pytest.approx(25.0)
    assert ["sample/sort", pytest.approx(250e-9)] in \
        reduced.breakdown["device_ops"]
    # no trace (a CPU rehearsal), or a program without the scope: silent
    assert read(fake_run(trace=None)) is None
    bare = trace_reduce.reduce(raw, scopes={})
    assert read(fake_run(trace=bare)) is None


def test_the_new_cell_and_its_metrics_are_wired_by_name():
    cell = harness.load_cell("cub200-generate")
    assert cell.chips == 1 and cell.traffic["driver"] == "generate"
    assert cell.traffic["fanout"] == 128
    assert cell.traffic["text"] == {"kind": "captions",
                                    "file": "cub_captions_1024.txt"}
    assert {m["name"] for m in cell.end_to_end} == {"gen_tokens_per_s",
                                                    "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    every_gen = {m["name"] for m in MANIFEST["per_layer"]
                 if m["name"].startswith("gen_")}
    assert every_gen <= reported and "gen_sampler_share_pct" in reported
    assert {"setup_trace_lower_s", "setup_compile_load_s", "setup_programs",
            "setup_cache_misses", "gen_window_compiles"} <= reported
    assert "train_window_compiles" not in reported
    # every metric this PR adds names its cells, and every cell reads the
    # set-up counters
    cells = {w["name"] for w in MANIFEST["workloads"]}
    for m in MANIFEST["per_layer"]:
        if m["layer"] == "start-up":
            assert "workloads" in m and m["source"] == "program_counter"
            if m["moves"] == "setup_s":
                assert set(m["workloads"]) == cells


def test_a_rehearsal_of_the_new_cell_reads_every_log_metric():
    """The real thing on the CPU: the harness switches the log on, the twin
    of ``cub200-generate`` runs, and every reader of the log gives a number
    (the sampler's share needs a device plane and stays silent)."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmark" / "run.py"), "--workload",
         "cub200-generate", "--seed", "2400000011", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu",
                           PYTHONPATH=str(REPO)),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    metrics = json.loads(proc.stdout.strip().splitlines()[-1])["metrics"]
    for name in LOG_READERS:
        assert (name in metrics) == (name != "train_window_compiles"), name
    assert metrics["gen_window_compiles"]["value"] == 0.0
    assert metrics["setup_programs"] == {
        "value": metrics["setup_programs"]["value"], "unit": "programs"}
    assert metrics["setup_programs"]["value"] >= 5
    assert metrics["setup_trace_lower_s"]["value"] > 0
    assert metrics["setup_compile_load_s"]["value"] > 0
    assert "gen_sampler_share_pct" not in metrics
