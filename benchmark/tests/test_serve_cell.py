"""The serve cell: wired by name, its six readers on reductions with known
answers, silent where there is nothing to read, its rehearsal twin's line,
and what makes ``correct`` false."""
import json
import types
from pathlib import Path

import numpy as np
import pytest

from benchmark import checks, harness, rooflines
from benchmark import trace_reduce as tr
from benchmark.drivers import serve
from benchmark.layer_metrics import _serve
from benchmark.tests.test_rehearse import run_cell

REPO = Path(__file__).resolve().parent.parent.parent
BENCH = REPO / "benchmark"
CELL = "cub200-serve-saturated"
READERS = {"gen_serve_tick_ms": ("ms", "lower", "device_trace"),
           "gen_serve_tick_roofline": ("%", "higher", "device_trace"),
           "gen_serve_prefill_share_pct": ("%", "lower", "device_trace"),
           "gen_serve_stall_pct": ("%", "lower", "program_span"),
           "gen_serve_occupancy_pct": ("%", "higher", "program_counter"),
           "gen_serve_queue_wait_ms": ("ms", "lower", "program_span")}
APPENDED = {"setup_trace_lower_s", "setup_compile_load_s", "setup_programs",
            "setup_cache_misses", "gen_window_compiles",
            "gen_device_idle_pct", "gen_unscoped_share_pct",
            "gen_hbm_planned_gb", "gen_attn_scores_share_pct",
            "gen_attn_cache_share_pct", "gen_ff_share_pct",
            "gen_sampler_share_pct", "gen_vae_decode_share_pct"}


def test_cell_traffic_driver_and_readers_are_wired_by_name():
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    (entry,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "cub200", "serve-saturated", 1)
    traffic = json.loads(
        (BENCH / "traffic" / "serve-saturated.json").read_text())
    assert traffic["driver"] == "serve"
    assert (traffic["num_slots"], traffic["clients"], traffic["stagger"],
            traffic["traced_steps"]) == (128, 160, 8, 256)
    assert (traffic["tiny"]["num_slots"], traffic["tiny"]["clients"],
            traffic["tiny"]["stagger"], traffic["tiny"]["traced_steps"]) == (
        4, 5, 2, 16)
    cell = harness.load_cell(CELL)
    assert harness.load_driver(cell) is serve
    assert [m["name"] for m in cell.end_to_end] == ["gen_tokens_per_s",
                                                    "setup_s"]
    by_name = {m["name"]: m for m in cell.per_layer}
    assert set(by_name) == set(READERS) | APPENDED
    for name, (unit, better, source) in READERS.items():
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"],
                m["workloads"]) == (unit, better, source, "serve",
                                    "gen_tokens_per_s", [CELL])
        assert callable(harness.load_reader(name))
    # the static scan's tick readers key on jit_bench_decode: not this cell's
    assert not {"gen_decode_tick_ms", "gen_decode_roofline"} & set(by_name)


def fake_run(trace=None, host=None, peaks=None):
    cell = harness.load_cell(CELL)
    dalle_cfg, vae_cfg = harness.build_configs(cell.config)
    return harness.Run(cell=cell, dalle_cfg=dalle_cfg, vae_cfg=vae_cfg,
                       devices=[], peaks=peaks, trace=trace,
                       outcome=harness.Outcome(True, 1, 0, {}, host=host or {}))


def reduction():
    """One device, 200 ms: three ticks of 30, 33 and 36 ms, one prefill of
    4 ms and one install of 2 ms; the device runs nothing for the rest."""
    ms = 1_000_000
    calls = [("jit_serve_tick", 0, 30), ("jit_serve_prefill", 40, 4),
             ("jit_serve_admit", 44, 2), ("jit_serve_tick", 50, 33),
             ("jit_serve_tick", 100, 36), ("jit_decode", 150, 5)]
    return {"devices": [{
        "name": "/device:TPU:0", "collectives": [],
        "ops": [["fusion.1", s * ms, d * ms, name] for name, s, d in calls],
        "modules": [[name, s * ms, d * ms] for name, s, d in calls]}],
        "host_spans": []}


def test_tick_roofline_and_admission_share_on_a_known_reduction():
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    run = fake_run(tr.reduce(reduction(), window_s=0.2), {"rows": 128}, peaks)
    assert run.trace.busy_s == pytest.approx(0.110)
    assert harness.load_reader("gen_serve_tick_ms")(run) == pytest.approx(33)
    least = rooflines.decode_tick_least_s(run.dalle_cfg, 128, peaks)
    assert harness.load_reader("gen_serve_tick_roofline")(run) == \
        pytest.approx(100 * least["seconds"] / 0.033)
    assert harness.load_reader("gen_serve_prefill_share_pct")(run) == \
        pytest.approx(100 * 6 / 110)


def test_stall_counts_a_gap_under_an_admit_span_and_no_other(monkeypatch):
    busy = [[0, 100], [200, 300], [400, 500]]
    spans = {"busy": busy, "step": [[0, 320], [390, 500]],
             "admit": [[90, 210]], "retire": [[295, 305]],
             "tick": [[210, 220]]}
    # 100 ns idle under the admit span, 5 under the retire span; the gap
    # [305, 400) lies under neither and [300, 320) + [390, 400) under a step
    assert _serve.idle_under(spans, ["admit"]) == 100
    assert _serve.idle_under(spans, ["retire"]) == 5
    assert _serve.idle_under(spans, ["admit", "retire"]) == 105
    assert _serve.idle_under(spans, ["step"]) == 100 + 20 + 10
    assert _serve.idle_under(spans, ["mem_watermark"]) == 0
    run = fake_run(tr.reduce(reduction(), window_s=1e-6))
    monkeypatch.setattr(harness.Tracer, "xplane", lambda self: "a.xplane.pb")
    monkeypatch.setattr(_serve, "phase_spans", lambda path: spans)
    assert _serve.idle_by_phase(run) == pytest.approx(
        {"retire": 5e-9, "admit": 100e-9, "step": 130e-9, "window": 1e-6})
    assert harness.load_reader("gen_serve_stall_pct")(run) == \
        pytest.approx(100 * 105e-9 / 1e-6)
    # a program that writes no such span: nothing to read
    monkeypatch.setattr(_serve, "phase_spans", lambda path: {"busy": busy})
    assert harness.load_reader("gen_serve_stall_pct")(run) is None


def test_occupancy_and_queue_wait_from_the_traced_records():
    host = {"rows": 4,
            "traced_ticks": [{"clock": 7, "ticks": 1, "active_sum": 4},
                             {"clock": 10, "ticks": 3, "active_sum": 11}],
            "traced_admits": [{"rid": 1, "slot": 0, "queue_wait_s": 0.25},
                              {"rid": 2, "slot": 3, "queue_wait_s": 0.75},
                              {"rid": 3, "slot": 1, "queue_wait_s": 0.5}]}
    run = fake_run(host=host)
    assert harness.load_reader("gen_serve_occupancy_pct")(run) == \
        pytest.approx(100 * 15 / 16)
    assert harness.load_reader("gen_serve_queue_wait_ms")(run) == \
        pytest.approx(500)


def test_readers_are_silent_where_there_is_nothing_to_read():
    run = fake_run()
    for name in READERS:
        assert harness.load_reader(name)(run) is None, name
    # a trace of another program: no jit_serve_* in it
    raw = reduction()
    for row in raw["devices"][0]["modules"] + raw["devices"][0]["ops"]:
        row[-1 if len(row) == 4 else 0] = "jit_bench_decode"
    run = fake_run(tr.reduce(raw, window_s=0.2), {"rows": 128},
                   {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    for name in ("gen_serve_tick_ms", "gen_serve_tick_roofline",
                 "gen_serve_prefill_share_pct"):
        assert harness.load_reader(name)(run) is None, name


def test_rehearsal_twin_prints_the_line_and_a_full_arena():
    line = run_cell(REPO, CELL, 1, 0)
    assert set(line["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    line = run_cell(REPO, CELL, 1, 1)
    assert line["metrics"]["gen_serve_occupancy_pct"] == {"value": 100.0,
                                                          "unit": "%"}
    assert line["metrics"]["gen_serve_queue_wait_ms"]["value"] > 0
    assert line["metrics"]["gen_window_compiles"]["value"] == 0
    assert "gen_hbm_planned_gb" in line["metrics"]
    # a CPU has no device plane: the device readers leave their metrics out
    assert not {"gen_serve_tick_ms", "gen_serve_tick_roofline",
                "gen_serve_prefill_share_pct", "gen_serve_stall_pct"} & set(
        line["metrics"])
    host = json.loads((BENCH / "out" / f"{CELL}.trace1.json").read_text())[
        "host"]
    assert len(host["traced_ticks"]) == 16     # tiny: 16 traced steps
    assert host["check"]["occupancy"] == 1.0
    assert all(v == 1 for v in host["check"]["trace_counts"].values())


@pytest.fixture(scope="module")
def served():
    """The tiny twin served in-process with a top-k narrow enough to plant
    a code outside it (at the mix's 0.9 the twin's 64 codes all pass)."""
    cell = harness.load_cell(CELL, rehearse=True)
    cell.traffic["filter_thres"] = 0.999
    dalle_cfg, vae_cfg = harness.build_configs(cell.config)
    loop = serve.Loop(cell, dalle_cfg, vae_cfg, 11, harness.Tracer(False, CELL))
    loop.fill(cell.traffic["clients"], cell.traffic["stagger"])
    while len(loop.done_at) < 4:
        loop.step()
    return loop, dict(occupancy=1.0, failed=0, pictures_ok=loop.pictures_ok,
                      trace_counts=loop.server.trace_counts())


def test_an_honest_run_is_correct_and_a_hole_in_the_arena_is_not(served):
    loop, facts = served
    handles = list(loop.last)
    assert len(handles) == 2
    verdict = serve.judge(loop.dalle, loop.params, handles, 0.999, **facts)
    assert verdict["ok"] and verdict["top_k_share"] == 1.0
    assert verdict["k"] == checks.top_k_count(loop.dalle.cfg, 0.999) < 64
    for planted in (dict(occupancy=1.0 - 1 / 4096), dict(failed=1),
                    dict(pictures_ok=False),
                    dict(trace_counts={"prefill": 1, "admit": 1, "tick": 2})):
        assert not serve.judge(loop.dalle, loop.params, handles, 0.999,
                               **{**facts, **planted})["ok"], planted
    # the window's occupancy, from two stats() readings
    assert serve.slot_ticks({"occupancy": 0.9375, "ticks": 4}, 4) == 15


def test_a_planted_code_outside_the_top_k_is_not_correct(served):
    loop, facts = served
    first, second = list(loop.last)
    codes = first.result().copy()
    ref = np.asarray(checks.reference_logits(
        loop.params, loop.dalle.cfg, first.text, codes[None]))[0]
    codes[-1] = int(ref[-1].argmin())      # the last code: nothing follows it
    planted = types.SimpleNamespace(text=first.text, result=lambda: codes)
    verdict = serve.judge(loop.dalle, loop.params, [planted, second], 0.999,
                          **facts)
    assert not verdict["ok"]
    assert verdict["top_k_share"] == pytest.approx(31 / 32)
    assert verdict["logit_err_std"] <= checks.LOGIT_TOL
    assert verdict["codes_in_range"]
