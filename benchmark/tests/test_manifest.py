"""BENCHMARK.json and the files it names, against the contract's rules."""
import json
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent.parent
BENCH = REPO / "benchmark"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
DATA_SUFFIXES = {".json", ".jsonl", ".toml", ".txt", ".csv"}


@pytest.fixture(scope="module")
def manifest():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def line_ok(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["command"]) <= 32
    assert all(line_ok(w) and not w.startswith("/") and ".." not in w
               for w in manifest["command"])
    assert 1 <= len(manifest["paths"]) <= 16
    assert all(PATH.match(p) for p in manifest["paths"])


def test_configs(manifest):
    names = [c["name"] for c in manifest["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line_ok(c["source"]) and line_ok(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in manifest["paths"]))
        body = json.loads((REPO / c["file"]).read_text())
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert body["name"] == c["name"] and line_ok(body["source"])
        assert {"dalle", "vae", "dtype", "assumed", "tiny"} <= set(body)


def test_workloads(manifest):
    cells = manifest["workloads"]
    names = [w["name"] for w in cells]
    assert len(set(names)) == len(names) and 2 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    configs = {c["name"] for c in manifest["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line_ok(w["why"]), (w["name"], len(w["why"]))
        traffic = json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        assert (BENCH / "drivers" / f"{traffic['driver']}.py").is_file()


def cells_of(metric, manifest):
    return set(metric.get("workloads",
                          [w["name"] for w in manifest["workloads"]]))


def test_metrics(manifest):
    e2e, layer = manifest["end_to_end"], manifest["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in manifest["workloads"]}
    by_name = {m["name"]: m for m in e2e}
    assert "setup_s" in by_name and by_name["setup_s"]["bound"] <= 0.1
    assert "workloads" not in by_name["setup_s"]
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and line_ok(m["layer"])
        assert m["moves"] in by_name
        # reported only where the metric it moves is
        assert cells_of(m, manifest) <= cells_of(by_name[m["moves"]],
                                                 manifest)
        assert (BENCH / "layer_metrics" / f"{m['name']}.py").is_file()
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert cells_of(m, manifest) <= cells
    for cell in cells:
        assert any(cell in cells_of(m, manifest) and m["name"] != "setup_s"
                   for m in e2e), cell
        assert any(cell in cells_of(m, manifest) for m in layer), cell


def test_every_data_file_loads_and_is_well_named():
    for sub in ("configs", "traffic"):
        for path in (BENCH / sub).iterdir():
            assert path.suffix in DATA_SUFFIXES, path
            if path.suffix == ".json":
                json.loads(path.read_text())
    for path in BENCH.rglob("*"):
        rel = path.relative_to(REPO).as_posix()
        if "__pycache__" in rel or rel.startswith("benchmark/out/"):
            continue
        assert PATH.match(rel), rel


def test_every_reader_imports():
    import importlib

    for path in (BENCH / "layer_metrics").glob("[a-z]*.py"):
        mod = importlib.import_module(f"benchmark.layer_metrics.{path.stem}")
        assert callable(mod.read), path


def test_peaks_table_names_its_source():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert "v5e" in peaks["source"]
    for kind, row in peaks["kinds"].items():
        assert {"bf16_flops", "hbm_bytes_per_s"} <= set(row), kind
