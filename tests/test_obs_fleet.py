"""Fleet observability tests (ISSUE 11): clock alignment, merged
reports/traces, the /metrics endpoint, and declarative alerts.

The load-bearing properties, in order:

* **Solver recovery** — the committed three-host fixture (injected skews
  +2.5 s / −0.8 s drifting +3 ms/s, one straggler, one torn span) aligns
  with each recovered offset/drift inside the solver's own reported
  residual bound; step-anchor matching recovers a relative skew with no
  rendezvous at all.
* **Merged views** — one fleet report (per-class serve totals spanning
  hosts, straggler ranking, ckpt/fault/quarantine rollups) and one
  Perfetto trace with one pid lane per host.
* **Metrics** — registry semantics, Prometheus text rendering, the live
  HTTP endpoint, the emit-path feed, and the pinned scrape bound:
  1k series under 50 ms.
* **Alerts** — an injected stall fires a ``stall_fraction`` alert whose
  stream event is causally AFTER its cause (seq order, pinned), burn-
  rate/gap rules fire, cooldown holds, and the monitor's fleet scan
  surfaces per-host alerts.
"""
from __future__ import annotations

import json
import sys
import time
import urllib.request
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dalle_pytorch_tpu.obs import (align, alerts, build_fleet_report,  # noqa: E402
                                   merge_streams, metrics, read_events,
                                   render_text, telemetry, to_chrome_trace)

FLEET = REPO / "tests" / "fixtures" / "obs" / "fleet"
FLEET_DIRS = [FLEET / "host0", FLEET / "host1", FLEET / "host2"]
# the skews make_fleet.py injected (offset at mono0, drift per mono second)
INJECTED = {0: (0.0, 0.0), 1: (2.5, 0.0), 2: (-0.8, 0.003)}


@pytest.fixture(autouse=True)
def _fresh_state():
    yield
    telemetry.shutdown()
    metrics.shutdown()


# --- solver ----------------------------------------------------------------


def test_fixture_solver_recovers_injected_skew():
    """Each lane's recovered offset and drift land inside the solver's own
    reported residual bound — the acceptance criterion, pinned against
    the committed skews."""
    events, clocks = merge_streams(FLEET_DIRS)
    assert [c.lane for c in clocks] == [0, 1, 2]
    for c in clocks:
        want_off, want_drift = INJECTED[c.lane]
        assert c.method == "rendezvous" and c.anchors == 3
        assert c.bound is not None
        assert abs(c.offset - want_off) <= c.bound, (c.lane, c.offset)
        # drift error integrated over the fixture's ~7s window stays
        # inside the bound too
        assert abs(c.drift - want_drift) * 7.0 <= c.bound, (c.lane, c.drift)
    # and the aligned streams agree about when each step happened: the
    # residual cross-host spread is the straggler's true 80ms lateness,
    # never the injected seconds of skew
    rep = build_fleet_report(events, clocks)
    assert rep["fleet"]["step_spread_max_s"] == pytest.approx(0.08, abs=0.01)


def test_step_anchor_matching_without_rendezvous(tmp_path, monkeypatch):
    """No shared reference at all: matched global-step anchors recover the
    relative skew between two hosts (the data-parallel fleet case)."""
    monkeypatch.setenv("GRAFT_CLOCK_SKEW_S", "2.5")
    ta = telemetry.Telemetry(tmp_path / "a", run_id="ra", beacon_every=0)
    for s in range(1, 9):
        ta.event("step", "train", step=s)
    ta.close()
    monkeypatch.setenv("GRAFT_CLOCK_SKEW_S", "-0.8")
    tb = telemetry.Telemetry(tmp_path / "b", run_id="rb", beacon_every=0)
    for s in range(1, 9):
        tb.event("step", "train", step=s)
    tb.close()
    events, clocks = merge_streams([tmp_path / "a", tmp_path / "b"])
    ca, cb = clocks
    assert ca.method == "reference" and ca.offset == 0.0
    assert cb.method == "steps" and cb.anchors == 8
    # both streams were written back-to-back in THIS process, so the true
    # inter-step jitter is micro-scale: recovery error well inside bound
    assert cb.offset == pytest.approx(-0.8 - 2.5, abs=0.05)
    assert abs(cb.offset - (-3.3)) <= cb.bound + 0.05


def test_env_skew_and_rendezvous_roundtrip(tmp_path, monkeypatch):
    """GRAFT_CLOCK_SKEW_S + GRAFT_CLOCK_RDV (the CI chaos-smoke shape):
    ref-bearing beacons align each host to the shared fs clock
    independently — no common workload needed."""
    monkeypatch.setenv("GRAFT_CLOCK_RDV", str(tmp_path / "rdv"))
    monkeypatch.setenv("GRAFT_CLOCK_SKEW_S", "5.0")
    ta = telemetry.Telemetry(tmp_path / "a", run_id="ra")
    ta.event("serve", "submit", rid=1)  # no steps in common on purpose
    ta.close()
    monkeypatch.setenv("GRAFT_CLOCK_SKEW_S", "-1.5")
    tb = telemetry.Telemetry(tmp_path / "b", run_id="rb")
    tb.event("serve", "submit", rid=2)
    tb.close()
    _, clocks = merge_streams([tmp_path / "a", tmp_path / "b"])
    by_lane = {c.lane: c for c in clocks}
    assert by_lane[0].method == by_lane[1].method == "rendezvous"
    # fs mtime is the unskewed local clock, so offsets ARE the skews
    # (mtime granularity + write latency inside the widened bound)
    assert by_lane[0].offset == pytest.approx(5.0, abs=0.05)
    assert by_lane[1].offset == pytest.approx(-1.5, abs=0.05)


def test_heartbeat_clock_payload_and_offsets(tmp_path, monkeypatch):
    """Heartbeats carry the beacon payload, and the monitor-side helper
    recovers a dead host's offset from the heartbeat file alone (mtime =
    the monitor's fs clock) — alignment survives a host that died between
    telemetry rotations."""
    from dalle_pytorch_tpu.utils.failure import Heartbeat

    monkeypatch.setenv("GRAFT_CLOCK_SKEW_S", "4.0")
    hb = Heartbeat(tmp_path / "hb")
    hb.beat(3)
    hb.close()
    info = json.loads((tmp_path / "hb" / "heartbeat-p0.json").read_text())
    assert info["clock"]["boot"]
    offs = align.heartbeat_offsets(tmp_path / "hb")
    assert offs[0]["offset"] == pytest.approx(4.0, abs=0.05)
    assert offs[0]["boot"] == info["clock"]["boot"]


def test_read_events_file_path_includes_rotated_parts(tmp_path):
    """The satellite fix: reading the ACTIVE file pulls its rotated
    siblings first, so reports see the full history."""
    tel = telemetry.Telemetry(tmp_path, run_id="rot", rotate_bytes=600,
                              keep_rotated=8, beacon_every=0)
    for i in range(1, 31):
        tel.event("step", "train", step=i, filler="x" * 30)
    tel.close()
    assert list(tmp_path.glob("events.jsonl.*")), "fixture never rotated"
    recs = read_events(tmp_path / "events.jsonl")
    steps = [r["step"] for r in recs if r["kind"] == "step"]
    assert steps == list(range(1, 31))  # not just the live segment


# --- merged report + trace -------------------------------------------------


def test_merged_fleet_report_totals():
    events, clocks = merge_streams(FLEET_DIRS)
    rep = build_fleet_report(events, clocks)
    assert rep["steps"]["records"] == 60
    assert rep["steps"]["first_step"] == 1
    assert rep["steps"]["last_step"] == 20
    # serve merges across hosts per SLO class
    sv = rep["serve"]["by_class"]
    assert sv["latency"]["completed"] == sv["throughput"]["completed"] == 5
    assert sv["latency"]["attainment"] == pytest.approx(0.8)
    assert sv["latency"]["latency_p50"] == pytest.approx(1.1)
    # fleet-wide rollups: publishes from two hosts, h1's torn save, h2's
    # fault + quarantine
    assert rep["ckpt"]["publishes"] == 8
    assert rep["ckpt"]["torn_saves"] == 1
    assert any(f["site"] == "shard_read" for f in rep["faults"])
    assert rep["data"]["sample_quarantines"] == 1
    # straggler ranking: the 80ms-late host first, by ~0.08s mean lag
    fleet = rep["fleet"]
    assert fleet["common_steps"] == 20
    assert fleet["stragglers"][0]["lane"] == 1
    assert fleet["stragglers"][0]["mean_lag_s"] == pytest.approx(0.08,
                                                                abs=0.01)
    lane1 = next(l for l in fleet["lanes"] if l["lane"] == 1)
    assert lane1["alerts"] == ["stall_fraction"]
    text = render_text(rep)
    for needle in ("-- fleet (aligned timebase) --", "rendezvous",
                   "straggler lane 1", "ALERTS: stall_fraction",
                   "step timeline: 20 common steps"):
        assert needle in text, needle


def test_merged_perfetto_one_pid_lane_per_host():
    events, _ = merge_streams(FLEET_DIRS)
    doc = to_chrome_trace(events)
    ev = doc["traceEvents"]
    pids = {e["pid"] for e in ev if e["ph"] != "M"}
    assert pids == {0, 1, 2}
    names = {e["args"]["name"] for e in ev
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert {"fleet-h0 (host 0)", "fleet-h1 (host 0)",
            "fleet-h2 (host 0)"} <= names
    # complete spans from host0's ckpt writer, the torn one from host1
    assert any(e["ph"] == "X" and e["pid"] == 0 for e in ev)
    assert any(e["ph"] == "i" and e["pid"] == 1
               and "(unfinished)" in e["name"] for e in ev)
    # timestamps are fleet-time: host1's step-1 instant sits ~80ms after
    # host0's, not 2.5s
    t_step1 = {e["pid"]: e["ts"] for e in ev
               if e["ph"] == "i" and e["name"] == "step.train"
               and e["args"].get("step") == 1}
    assert (t_step1[1] - t_step1[0]) / 1e6 == pytest.approx(0.08, abs=0.01)


def test_obs_report_cli_merge(tmp_path, capsys):
    sys.path.insert(0, str(REPO / "tools"))
    import obs_report

    assert obs_report.main(["--merge"] + [str(d) for d in FLEET_DIRS]) == 0
    out = capsys.readouterr().out
    assert "-- fleet (aligned timebase) --" in out
    out_json = tmp_path / "fleet.json"
    assert obs_report.main(["--merge"] + [str(d) for d in FLEET_DIRS]
                           + ["--format", "json", "--output",
                              str(out_json)]) == 0
    capsys.readouterr()
    rep = json.loads(out_json.read_text())
    assert rep["fleet"]["stragglers"][0]["lane"] == 1
    out_trace = tmp_path / "fleet.trace.json"
    assert obs_report.main(["--merge"] + [str(d) for d in FLEET_DIRS]
                           + ["--format", "trace", "--output",
                              str(out_trace)]) == 0
    capsys.readouterr()
    doc = json.loads(out_trace.read_text())
    assert {e["pid"] for e in doc["traceEvents"]} == {0, 1, 2}


# --- metrics ---------------------------------------------------------------


def test_registry_instruments_and_render():
    reg = metrics.MetricsRegistry()
    reg.counter("c_total", "a counter", kind="x").inc()
    reg.counter("c_total", kind="x").inc(2)
    reg.gauge("g", "a gauge").set(1.5)
    h = reg.histogram("h_seconds", "a histogram", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.render()
    assert '# TYPE c_total counter' in text
    assert 'c_total{kind="x"} 3.0' in text
    assert "g 1.5" in text
    assert 'h_seconds_bucket{le="0.1"} 1' in text
    assert 'h_seconds_bucket{le="1.0"} 2' in text
    assert 'h_seconds_bucket{le="+Inf"} 3' in text
    assert "h_seconds_count 3" in text
    # same name, different type = a registration bug, loudly
    with pytest.raises(ValueError):
        reg.gauge("c_total")


def test_emit_path_feeds_registry(tmp_path):
    tel = telemetry.init(tmp_path, run_id="m")
    reg = metrics.MetricsRegistry()
    tel.attach_metrics(reg)
    tel.event("step", "train", step=7, loss=1.25, mfu=0.14,
              loader_stall_frac=0.3)
    tel.event("ckpt", "publish", step=7)
    tel.event("fault", "serve_request", action="fail_after")
    tel.event("data", "sample_quarantine", key="s1")
    telemetry.shutdown()
    assert reg.counter("graft_steps_total").value == 1
    assert reg.gauge("graft_step").value == 7.0
    assert reg.gauge("graft_step_loss").value == 1.25
    assert reg.gauge("graft_loader_stall_frac").value == pytest.approx(0.3)
    assert reg.counter("graft_ckpt_publishes_total").value == 1
    assert reg.counter("graft_faults_total",
                       site="serve_request").value == 1
    assert reg.counter("graft_quarantines_total",
                       what="sample_quarantine").value == 1
    assert reg.counter("graft_events_total", kind="step").value == 1


def test_metrics_endpoint_serves_and_health(tmp_path):
    reg = metrics.MetricsRegistry()
    reg.gauge("graft_serve_occupancy").set(0.75)
    srv = metrics.MetricsServer(0, reg, health_fn=lambda: {"step": 42},
                                host="127.0.0.1")
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(f"{base}/metrics",
                                      timeout=5).read().decode()
        assert "graft_serve_occupancy 0.75" in body
        health = json.loads(urllib.request.urlopen(
            f"{base}/healthz", timeout=5).read())
        assert health["ok"] is True and health["step"] == 42
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope", timeout=5)
    finally:
        srv.close()


def test_metrics_scrape_bound_at_1k_series():
    """The acceptance gate: a 1k-series render stays under 50 ms."""
    reg = metrics.MetricsRegistry()
    for i in range(500):
        reg.counter("graft_events_total", kind=f"k{i}").inc(i)
        reg.gauge("graft_lane_depth", lane=str(i)).set(i * 0.5)
    assert reg.series_count == 1000
    t0 = time.perf_counter()
    text = reg.render()
    dt = time.perf_counter() - t0
    assert len(text.splitlines()) >= 1000
    assert dt <= 0.05, f"1k-series scrape took {dt * 1e3:.1f} ms"


def test_detached_metrics_cost_is_one_check(tmp_path):
    """With no registry attached, the emit path stays on the pinned cheap
    path (same contract as the GRAFT_TELEMETRY=0 gate in test_obs.py)."""
    tel = telemetry.init(tmp_path, run_id="cost", beacon_every=0)
    n = 500
    t0 = time.perf_counter()
    for i in range(n):
        tel.event("step", "train", step=i)
    detached = (time.perf_counter() - t0) / n
    telemetry.shutdown()
    assert detached <= 1e-3, f"detached {detached * 1e6:.1f} us/record"


# --- alerts ----------------------------------------------------------------


def test_injected_stall_fires_causally_ordered_alert(tmp_path):
    """The chaos pin of the acceptance criterion: step records carrying an
    injected stall (loader_stall_frac ~0.9) trip `stall_fraction`, and
    the alert's stream event lands with a seq strictly AFTER its cause —
    provable from the stream alone."""
    tel = telemetry.init(tmp_path, run_id="stall")
    reg = metrics.MetricsRegistry()
    tel.attach_metrics(reg)
    tel.attach_alerts(alerts.AlertEngine())
    for s in range(1, 10):
        tel.event("step", "train", step=s, loss=1.0,
                  loader_stall_frac=(0.9 if s >= 4 else 0.05))
    telemetry.shutdown()
    recs = read_events(tmp_path)
    alert = next(r for r in recs if r["kind"] == "alert")
    assert alert["name"] == "stall_fraction"
    cause = next(r for r in recs if r["seq"] == alert["cause_seq"])
    assert cause["kind"] == "step"
    assert alert["seq"] > cause["seq"]  # causally after its cause
    assert alert["value"] > 0.5 and "stall" in alert["msg"]
    # cooldown: the sustained condition fired exactly once
    assert sum(r["kind"] == "alert" for r in recs) == 1
    # and the metrics feed counted it
    assert reg.counter("graft_alerts_total",
                       rule="stall_fraction").value == 1


def test_slo_burn_and_gap_rules(tmp_path):
    eng = alerts.AlertEngine(rules=(
        alerts.Rule(name="slo_attainment", kind="threshold",
                    select_kind="serve", select_names=("retire",),
                    field="slo_ok", op="<", limit=0.9, window_s=60,
                    min_count=4),
        alerts.Rule(name="heartbeat_gap", kind="gap", select_kind="step",
                    limit=30.0),
    ))

    def rec(kind, name, mono, **f):
        return dict(f, kind=kind, name=name, mono=mono, seq=1)

    fired = []
    for i in range(6):
        fired += eng.observe(rec("serve", "retire", 1.0 + i,
                                 slo_ok=(i < 2)))
    assert [a["rule"] for a in fired] == ["slo_attainment"]
    assert fired[0]["value"] < 0.9
    # a 40s silence between steps trips the gap rule on arrival
    assert eng.observe(rec("step", "train", 50.0, step=1)) == []
    gap = eng.observe(rec("step", "train", 95.0, step=2))
    assert [a["rule"] for a in gap] == ["heartbeat_gap"]
    assert gap[0]["value"] == pytest.approx(45.0)


def test_mfu_drop_vs_run_median(tmp_path):
    eng = alerts.AlertEngine(rules=(
        alerts.Rule(name="mfu_drop", kind="ratio_of_median",
                    select_kind="step", field="mfu", ratio=0.6,
                    window_s=5.0, min_count=3),
    ))
    fired = []
    for i in range(10):  # healthy baseline: mfu 0.15
        fired += eng.observe({"kind": "step", "name": "train",
                              "mono": float(i), "mfu": 0.15, "seq": i})
    assert fired == []
    for i in range(10, 16):  # straggler regime: 0.05 < 0.6 x median
        fired += eng.observe({"kind": "step", "name": "train",
                              "mono": float(i), "mfu": 0.05, "seq": i})
    assert [a["rule"] for a in fired] == ["mfu_drop"]


def test_monitor_fleet_mode(tmp_path, capsys, monkeypatch):
    sys.path.insert(0, str(REPO / "tools"))
    import monitor

    # host a: healthy fresh stream; host b: carries a fired alert
    monkeypatch.setenv("GRAFT_CLOCK_SKEW_S", "1.5")
    ta = telemetry.Telemetry(tmp_path / "a", run_id="ra")
    for s in range(1, 4):
        ta.event("step", "train", step=s, loader_stall_frac=0.01)
    ta.close()
    monkeypatch.delenv("GRAFT_CLOCK_SKEW_S")
    tb = telemetry.Telemetry(tmp_path / "b", run_id="rb")
    tb.attach_alerts(alerts.AlertEngine())
    for s in range(1, 8):
        tb.event("step", "train", step=s, loader_stall_frac=0.95)
    tb.close()
    rc = monitor.main(["--fleet", str(tmp_path / "a"), str(tmp_path / "b"),
                       "--timeout", "300"])
    out = capsys.readouterr().out
    assert rc == 1  # lane b has an active alert
    assert "lane 0 [ra host 0]" in out and "lane 1 [rb host 0]" in out
    assert "ALERTS: stall_fraction" in out
    assert "offset" in out
    # empty dir: nothing readable
    assert monitor.main(["--fleet", str(tmp_path / "empty")]) == 2


# --- serve + trainer integration ------------------------------------------


def test_serve_direct_instruments(tmp_path):
    """GenerationServer publishes the router's feedback signals (queue
    depth, occupancy, SLO verdicts) to the installed registry — with
    telemetry entirely off.  What the dropped series counted (retirements,
    their latency, ticks: no reader outside this file) is carried by
    `stats()` and, with a stream open, by the `serve.retire` and
    `serve.tick` records (tests/test_serve_spans.py)."""
    import jax
    import numpy as np

    from dalle_pytorch_tpu import DALLE, DALLEConfig, VAEConfig
    from dalle_pytorch_tpu.serve import GenerationServer

    vcfg = VAEConfig(image_size=16, num_tokens=32, codebook_dim=16,
                     num_layers=2, hidden_dim=8)
    cfg = DALLEConfig.from_vae(vcfg, dim=32, num_text_tokens=50,
                               text_seq_len=6, depth=2, heads=2, dim_head=8,
                               attn_types=("full",))
    dalle = DALLE(cfg)
    rng = jax.random.PRNGKey(0)
    import jax.numpy as jnp
    text = np.asarray(jax.random.randint(rng, (cfg.text_seq_len,), 1, 50),
                      np.int32)
    codes = jax.random.randint(rng, (1, cfg.image_seq_len), 0, 32)
    params = dalle.init(rng, jnp.asarray(text)[None], codes,
                        return_loss=True)

    reg = metrics.init()
    srv = GenerationServer(dalle, params, num_slots=2, filter_thres=1.0,
                           slo_targets={"latency": 60.0,
                                        "throughput": 60.0})
    h = srv.submit(text)
    assert reg.gauge("graft_serve_queue_depth",
                     slo="throughput").value == 1.0
    srv.run_until_idle(max_ticks=200)
    h.result(timeout=5)
    stats = srv.stats()
    assert stats["queue_depth"] == {"latency": 0, "throughput": 0}
    assert reg.gauge("graft_serve_queue_depth",
                     slo="throughput").value == 0.0
    assert reg.counter("graft_serve_slo_total", slo="throughput",
                       ok="true").value == 1
    assert stats["completed"] == 1 and stats["ticks"] > 0
    assert stats["latency_p50"]["throughput"] == h.latency > 0
    assert 0.0 < reg.gauge("graft_serve_occupancy").value <= 1.0
    rendered = reg.render()
    for gone in ("graft_serve_retired_total", "graft_serve_ticks_total",
                 "graft_serve_latency_seconds"):
        assert gone not in rendered


def test_live_vae_run_with_metrics_port_and_alerts(tmp_path, monkeypatch):
    """Trainer wiring end to end: --metrics_port starts the endpoint,
    --alerts attaches the engine, the stream carries clock beacons, and
    the run finishes clean (endpoint closed on exit)."""
    import socket

    import numpy as np
    from PIL import Image

    rng = np.random.default_rng(0)
    data = tmp_path / "data"
    data.mkdir()
    for i in range(8):
        arr = (rng.uniform(size=(16, 16, 3)) * 255).astype(np.uint8)
        Image.fromarray(arr).save(data / f"s{i}.png")
    monkeypatch.setenv("DALLE_TPU_HPARAMS", json.dumps(dict(
        EPOCHS=1, BATCH_SIZE=4, NUM_TOKENS=32, NUM_LAYERS=2,
        NUM_RESNET_BLOCKS=0, EMB_DIM=16, HID_DIM=16, NUM_IMAGES_SAVE=2)))
    monkeypatch.chdir(tmp_path)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    import train_vae

    train_vae.main(["--image_folder", str(data), "--image_size", "16",
                    "--ckpt_every", "2", "--telemetry_dir", "tel",
                    "--metrics_port", str(port)])
    recs = read_events(tmp_path / "tel")
    assert any(r["kind"] == "clock" and r["name"] == "beacon"
               for r in recs)
    assert any(r["name"] == "run_end" for r in recs)
    # the endpoint died with the run (daemon thread closed in finally)
    with pytest.raises(OSError):
        urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz",
                               timeout=2)


# --- subprocess replica lanes (graftwire, ISSUE 18) ------------------------


def test_subprocess_lane_merges_with_parent_timeline(tmp_path, monkeypatch):
    """The process-remote shape tools/loadgen.py merges: a REAL child
    process writes its own telemetry lane (own boot nonce, own
    rendezvous beacons against the shared clock dir) and merge_streams
    folds it into the parent's timeline — per-class serve rows span the
    process boundary as if one host had served everything."""
    import subprocess

    monkeypatch.setenv("GRAFT_CLOCK_RDV", str(tmp_path / "rdv"))
    parent = telemetry.Telemetry(tmp_path / "parent", run_id="parent")
    parent.event("serve", "retire", rid=1, slo="latency", latency_s=0.5,
                 queue_wait_s=0.01, slo_ok=True, tokens=4)
    parent.close()
    child_src = (
        "import sys; sys.path.insert(0, sys.argv[1])\n"
        "from dalle_pytorch_tpu.obs import telemetry\n"
        "t = telemetry.Telemetry(sys.argv[2], run_id='child')\n"
        "t.event('serve', 'retire', rid=2, slo='latency', latency_s=2.0,\n"
        "        queue_wait_s=0.02, slo_ok=False, tokens=4)\n"
        "t.event('serve', 'retire', rid=3, slo='throughput',\n"
        "        latency_s=1.0, queue_wait_s=0.0, slo_ok=True, tokens=4)\n"
        "t.close()\n"
    )
    subprocess.run([sys.executable, "-c", child_src, str(REPO),
                    str(tmp_path / "child")], check=True, timeout=60)
    events, clocks = merge_streams([tmp_path / "parent",
                                    tmp_path / "child"])
    # two lanes, each aligned via the SHARED fs rendezvous — the only
    # anchor two processes with no common workload can both see
    assert len(clocks) == 2
    assert all(c.method == "rendezvous" for c in clocks)
    boots = {e.get("boot") for e in events if e.get("boot")}
    assert len(boots) == 2  # distinct per-process boot nonces survive
    rep = build_fleet_report(events, clocks)
    by_class = rep["serve"]["by_class"]
    # the latency row spans BOTH processes: parent's hit + child's miss
    assert by_class["latency"]["completed"] == 2
    assert by_class["latency"]["attainment"] == pytest.approx(0.5)
    assert by_class["throughput"]["completed"] == 1
    assert by_class["throughput"]["attainment"] == pytest.approx(1.0)


def test_obs_report_cli_merges_subprocess_lane_with_fixture(tmp_path,
                                                           capsys,
                                                           monkeypatch):
    """obs_report --merge over the committed 3-host fixture PLUS a
    freshly written subprocess-shaped lane: the CLI path the CI
    loadgen_smoke artifact step runs."""
    import subprocess

    monkeypatch.setenv("GRAFT_CLOCK_RDV", str(tmp_path / "rdv"))
    child_src = (
        "import sys; sys.path.insert(0, sys.argv[1])\n"
        "from dalle_pytorch_tpu.obs import telemetry\n"
        "t = telemetry.Telemetry(sys.argv[2], run_id='sub')\n"
        "t.event('serve', 'retire', rid=9, slo='latency', latency_s=0.3,\n"
        "        queue_wait_s=0.0, slo_ok=True, tokens=4)\n"
        "t.close()\n"
    )
    subprocess.run([sys.executable, "-c", child_src, str(REPO),
                    str(tmp_path / "sub")], check=True, timeout=60)
    sys.path.insert(0, str(REPO / "tools"))
    import obs_report

    assert obs_report.main(
        ["--merge"] + [str(d) for d in FLEET_DIRS]
        + [str(tmp_path / "sub")]) == 0
    out = capsys.readouterr().out
    assert "-- fleet (aligned timebase) --" in out
    events, clocks = merge_streams(FLEET_DIRS + [tmp_path / "sub"])
    assert len(clocks) == 4  # 3 fixture hosts + the subprocess lane
    rep = build_fleet_report(events, clocks)
    # fixture had 5 latency retires (4 ok), the child adds 1 ok
    assert rep["serve"]["by_class"]["latency"]["completed"] == 6
    assert rep["serve"]["by_class"]["latency"]["attainment"] == \
        pytest.approx(5 / 6)
