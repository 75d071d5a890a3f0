"""graftscope telemetry tests (dalle_pytorch_tpu/obs + tools/obs_report.py).

The load-bearing properties, in order:

* **Schema round-trip** — every emitted record validates against
  ``EVENT_SCHEMA``, payload fields survive, per-host ``seq`` totally
  orders the stream, and a torn trailing line (the crash signature of the
  O_APPEND discipline) is skipped, never fatal.
* **Rotation** — the stream is bounded: the active file rotates at
  ``rotate_bytes`` and prunes to ``keep_rotated`` parts; readers merge
  the parts in order.
* **Disabled = free** — no file, no I/O, no per-call span allocation, and
  a pinned host-side cost bound for both the enabled and disabled paths
  (the overhead gate of ISSUE 9); ``GRAFT_TELEMETRY=0`` hard-disables.
* **Causal trails under chaos** — the ``ckpt_async`` kill and a
  ``serve_request`` fault each leave a correctly ORDERED event trail
  (span begin < fault < failure, no publish for the torn save; submit <
  admit < fault < fail for the victim request, co-batch unharmed),
  assertable from the stream alone.
* **Read side** — obs_report renders every section from the committed
  fixture stream AND from a live CPU smoke run; the Perfetto export is
  shape-valid with spans from >= 3 threads on one timeline.
"""
from __future__ import annotations

import json
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dalle_pytorch_tpu.obs import (EVENT_SCHEMA, Telemetry,  # noqa: E402
                                   build_report, read_events, render_text,
                                   telemetry, to_chrome_trace)
from dalle_pytorch_tpu.utils import faults  # noqa: E402

FIXTURE = REPO / "tests" / "fixtures" / "obs" / "events.jsonl"


@pytest.fixture(autouse=True)
def _fresh_state():
    faults.install("")
    yield
    faults.reset()
    telemetry.shutdown()


# --- schema / round-trip --------------------------------------------------


def test_schema_roundtrip(tmp_path):
    import jsonschema

    tel = telemetry.init(tmp_path, run_id="rt")
    tel.event("step", "train", step=1, loss=2.5, lr=3e-4)
    with tel.span("ckpt", "save", step=4):
        tel.event("fault", "ckpt_write", action="fail_after", hits=3)
    telemetry.note("health", "spike", "step 9: spike", step=9, loss=40.0)
    telemetry.shutdown()

    recs = read_events(tmp_path)
    # 5 emitted records + the first-event clock beacon (PR 11: every
    # stream periodically carries its wall<->mono offset pair)
    assert len(recs) == 6
    for r in recs:
        jsonschema.validate(r, EVENT_SCHEMA)
    assert [r["seq"] for r in recs] == [1, 2, 3, 4, 5, 6]
    assert all(r["run"] == "rt" and r["host"] == 0 for r in recs)
    beacon = recs[1]
    assert (beacon["kind"], beacon["name"]) == ("clock", "beacon")
    assert beacon["wall"] > 0 and beacon["mono"] > 0 and beacon["boot"]
    step = recs[0]
    assert (step["kind"], step["name"], step["step"], step["loss"]) == \
        ("step", "train", 1, 2.5)
    b, e = recs[2], recs[4]
    assert b["ph"] == "B" and e["ph"] == "E"
    assert e["sid"] == b["seq"] and e["dur_s"] >= 0 and e["ok"] is True
    assert recs[5]["msg"] == "step 9: spike"


def test_envelope_wins_over_colliding_payload(tmp_path):
    tel = telemetry.init(tmp_path, run_id="env", beacon_every=0)
    tel.event("step", "train", seq=999, run="liar", note="kept")
    telemetry.shutdown()
    (rec,) = read_events(tmp_path)
    assert rec["seq"] == 1 and rec["run"] == "env" and rec["note"] == "kept"


def test_torn_trailing_line_skipped(tmp_path):
    tel = telemetry.init(tmp_path, run_id="torn")
    tel.event("step", "train", step=1)
    tel.event("step", "train", step=2)
    telemetry.shutdown()
    path = tmp_path / "events.jsonl"
    with open(path, "ab") as f:  # the crash signature: a half-written line
        f.write(b'{"v":1,"run":"torn","host":0,"pid":1,"seq":3,"t":1.0,"mo')
    recs = read_events(path)
    assert [r["step"] for r in recs if r["kind"] == "step"] == [1, 2]


def test_non_host0_file_name_and_merge(tmp_path):
    t0 = Telemetry(tmp_path, run_id="mh", host=0, beacon_every=0)
    t1 = Telemetry(tmp_path, run_id="mh", host=1, beacon_every=0)
    t0.event("step", "train", step=1)
    t1.event("step", "train", step=1)
    t0.close()
    t1.close()
    assert (tmp_path / "events.jsonl").exists()
    assert (tmp_path / "events-p1.jsonl").exists()
    recs = read_events(tmp_path)
    assert [(r["host"], r["seq"]) for r in recs] == [(0, 1), (1, 1)]


# --- rotation -------------------------------------------------------------


def test_rotation_bounds_and_merges(tmp_path):
    tel = telemetry.init(tmp_path, run_id="rot", rotate_bytes=2000,
                         keep_rotated=2, beacon_every=0)
    for i in range(200):
        tel.event("step", "train", step=i, filler="x" * 40)
    telemetry.shutdown()
    parts = sorted(p.name for p in tmp_path.glob("events.jsonl*"))
    rotated = [p for p in parts if p != "events.jsonl"]
    assert (tmp_path / "events.jsonl").exists()
    assert 1 <= len(rotated) <= 2  # pruned to keep_rotated
    recs = read_events(tmp_path)
    seqs = [r["seq"] for r in recs]
    # pruning drops the oldest records; what remains is contiguous,
    # in order, and ends with the newest
    assert seqs == sorted(seqs) and seqs[-1] == 200
    assert len(seqs) == len(set(seqs))


# --- disabled path / off switch / overhead gates -------------------------


def test_disabled_no_files_no_seq(tmp_path):
    tel = Telemetry.disabled()
    assert not tel.enabled
    for _ in range(100):
        assert tel.event("step", "train", step=1) is None
    with tel.span("ckpt", "save") as s:
        assert s is None
    assert tel.seq == 0
    assert list(tmp_path.iterdir()) == []


def test_disabled_span_is_shared_singleton():
    tel = Telemetry.disabled()
    assert tel.span("a", "b") is tel.span("c", "d")
    telemetry.shutdown()
    assert telemetry.span("a", "b") is telemetry.span("c", "d")
    assert telemetry.emit("a", "b") is None and telemetry.get() is None


def test_env_off_switch(tmp_path, monkeypatch):
    monkeypatch.setenv("GRAFT_TELEMETRY", "0")
    tel = telemetry.init(tmp_path / "t", run_id="off")
    assert not tel.enabled
    assert telemetry.get() is None
    tel.event("step", "train", step=1)
    assert not (tmp_path / "t").exists()
    monkeypatch.setenv("GRAFT_TELEMETRY", "1")
    tel = telemetry.init(tmp_path / "t", run_id="on")
    assert tel.enabled and telemetry.get() is tel


def test_overhead_bounds(tmp_path):
    """The pinned host-cost gate: an enabled step-record costs <= 1 ms on
    CPU (measured ~10-30 us; the bound absorbs CI jitter), the disabled
    path <= 20 us/call (measured well under 1 us)."""
    tel = telemetry.init(tmp_path, run_id="perf")
    n = 500
    t0 = time.perf_counter()
    for i in range(n):
        tel.event("step", "train", step=i, loss=1.0, lr=3e-4,
                  step_time_s=0.1, mfu=0.15, loader_stall_s=0.01)
    enabled_per = (time.perf_counter() - t0) / n
    telemetry.shutdown()
    t0 = time.perf_counter()
    for i in range(n * 10):
        telemetry.emit("step", "train", step=i)
    disabled_per = (time.perf_counter() - t0) / (n * 10)
    assert enabled_per <= 1e-3, f"enabled {enabled_per * 1e6:.1f} us/record"
    assert disabled_per <= 2e-5, f"disabled {disabled_per * 1e6:.2f} us/call"


# --- note(): stderr/stdout line + stream event in one call ----------------


def test_note_prints_and_emits(tmp_path, capsys):
    tel = telemetry.init(tmp_path, run_id="note")
    telemetry.note("ckpt", "save_retry", "save step 3 retrying", step=3)
    telemetry.note("data", "sample_quarantine", "quarantining sample s1",
                   prefix="warning:", stream="stdout", key="s1")
    out = capsys.readouterr()
    assert "[ckpt] save step 3 retrying" in out.err
    assert "warning: quarantining sample s1" in out.out
    telemetry.shutdown()
    recs = [r for r in read_events(tmp_path) if r["kind"] != "clock"]
    assert [(r["kind"], r["name"]) for r in recs] == \
        [("ckpt", "save_retry"), ("data", "sample_quarantine")]
    assert recs[0]["msg"] == "save step 3 retrying"
    # with no active telemetry the stderr line still prints (the stream is
    # additional observability, never a replacement)
    telemetry.note("ckpt", "x", "post-shutdown message")
    assert "post-shutdown message" in capsys.readouterr().err


# --- satellite: StepTimer reservoir ---------------------------------------


def test_steptimer_reservoir_percentiles(monkeypatch):
    from dalle_pytorch_tpu.utils import profiling

    clock = [0.0]
    monkeypatch.setattr(profiling.time, "perf_counter", lambda: clock[0])
    timer = profiling.StepTimer(reservoir=64)
    timer.tick(8)  # arm: the first tick has no previous step to time
    # 100 steps of 10ms with every 10th a 100ms straggler
    dts = [0.1 if i % 10 == 9 else 0.01 for i in range(100)]
    ema_ref = None
    for dt in dts:
        clock[0] += dt
        out = timer.tick(8, stall_s=dt / 10)
        ema_ref = dt if ema_ref is None else 0.9 * ema_ref + 0.1 * dt
    # EMA behavior unchanged by the reservoir
    assert out["step_time_s"] == pytest.approx(ema_ref)
    pcts = timer.percentiles()
    assert pcts["reservoir_n"] == 100
    assert pcts["step_time_p50"] == pytest.approx(0.01)
    assert pcts["step_time_p99"] == pytest.approx(0.1)
    assert pcts["stall_p50"] == pytest.approx(0.001)
    assert pcts["stall_p99"] == pytest.approx(0.01)


def test_steptimer_reservoir_bounded():
    from dalle_pytorch_tpu.utils.profiling import StepTimer

    timer = StepTimer(reservoir=16)
    for _ in range(500):
        timer.tick(1, stall_s=0.0)
    assert len(timer._dt_res) <= 16 and len(timer._stall_res) <= 16
    assert timer.percentiles()["reservoir_n"] == 499


# --- satellite: heartbeat correlation -------------------------------------


def test_heartbeat_carries_run_id_and_telemetry_seq(tmp_path):
    from dalle_pytorch_tpu.utils.failure import Heartbeat

    tel = telemetry.init(tmp_path / "tel", run_id="hb-run")
    tel.event("step", "train", step=1)
    tel.event("step", "train", step=2)
    hb = Heartbeat(tmp_path / "hb")
    hb.beat(2, epoch=0)
    info = json.loads((tmp_path / "hb" / "heartbeat-p0.json").read_text())
    assert info["run_id"] == "hb-run"
    assert info["telemetry_seq"] == 3  # 2 events + the first-event beacon
    # the clock-beacon payload rides every beat (PR 11: monitor-side
    # alignment material even when the host dies between rotations)
    assert info["clock"]["wall"] > 0 and info["clock"]["mono"] > 0
    assert info["clock"]["boot"] == tel.boot
    hb.close(done=True)
    info = json.loads((tmp_path / "hb" / "heartbeat-p0.json").read_text())
    assert info["done"] is True and info["run_id"] == "hb-run"
    # explicit run_id wins over the telemetry-derived one
    hb2 = Heartbeat(tmp_path / "hb2", run_id="explicit")
    hb2.beat(1)
    info = json.loads((tmp_path / "hb2" / "heartbeat-p0.json").read_text())
    assert info["run_id"] == "explicit"
    hb2.close()


def test_monitor_prints_correlation_and_tail(tmp_path, capsys):
    from dalle_pytorch_tpu.utils.failure import Heartbeat

    sys.path.insert(0, str(REPO / "tools"))
    import monitor

    tel = telemetry.init(tmp_path / "tel", run_id="mon-run")
    tel.event("ckpt", "publish", step=4)
    tel.event("health", "spike", step=5, msg="step 5: spike")
    hb = Heartbeat(tmp_path / "hb")
    hb.beat(5)
    hb.close()
    telemetry.shutdown()
    # a fresh heartbeat scans healthy; an aged one is STALLED and the scan
    # prints its telemetry tail (what it was doing when it stalled)
    assert monitor.main([str(tmp_path / "hb"), "--timeout", "300",
                         "--telemetry-dir", str(tmp_path / "tel")]) == 0
    assert monitor.main([str(tmp_path / "hb"), "--timeout", "1e-9",
                         "--telemetry-dir", str(tmp_path / "tel")]) == 1
    out = capsys.readouterr().out
    assert "run mon-run" in out and "tel_seq 3" in out
    assert "last telemetry of process 0" in out
    assert "health.spike" in out


# --- chaos: causally-ordered event trails ---------------------------------


def test_ckpt_async_kill_leaves_causal_trail(tmp_path):
    """The I1 crash window, read back from the stream alone: span begin <
    injected fault < save_failed, NO publish for the killed step (a torn
    span), then the next save publishes normally."""
    from dalle_pytorch_tpu.utils.ckpt_manager import CheckpointManager

    telemetry.init(tmp_path / "tel", run_id="chaos-ckpt")
    faults.install("ckpt_async:at_step=7")
    mgr = CheckpointManager(tmp_path / "run", async_save=True)
    mgr.save(7, {"w": np.zeros(4, np.float32)})
    mgr.wait()
    assert mgr.last_error is not None  # the writer died
    mgr.save(8, {"w": np.ones(4, np.float32)})
    mgr.finish()
    telemetry.shutdown()

    recs = read_events(tmp_path / "tel")
    by_name = {}
    for r in recs:
        by_name.setdefault((r["name"], r.get("ph")), []).append(r)
    b7 = next(r for r in by_name[("save", "B")] if r["step"] == 7)
    fault = next(r for r in recs if r["kind"] == "fault"
                 and r["name"] == "ckpt_async")
    failed = by_name[("save_failed", None)][0]
    assert b7["seq"] < fault["seq"] < failed["seq"]
    publishes = [r["step"] for r in recs if r["name"] == "publish"]
    assert publishes == [8]  # step 7 never committed
    # the in-process InjectedKill unwinds through the span, so save-7's E
    # carries ok=False + the error (a REAL kill would leave the span torn
    # — that shape is pinned by the committed fixture's torn save); save-8
    # closes clean
    e_by_step = {next(b["step"] for b in by_name[("save", "B")]
                      if b["seq"] == r["sid"]): r
                 for r in by_name[("save", "E")]}
    assert e_by_step[7]["ok"] is False
    assert "InjectedKill" in e_by_step[7]["error"]
    assert e_by_step[8]["ok"] is True
    rep = build_report(recs)
    assert rep["ckpt"]["publishes"] == 1
    assert rep["ckpt"]["failed_saves"] == 1
    # and the on-disk contract the trail narrates: 7 invisible, 8 valid
    assert mgr.latest_valid().step == 8


@pytest.fixture(scope="module")
def tiny_serve():
    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu import DALLE, DALLEConfig, VAEConfig

    vcfg = VAEConfig(image_size=16, num_tokens=32, codebook_dim=16,
                     num_layers=2, hidden_dim=8)
    cfg = DALLEConfig.from_vae(vcfg, dim=32, num_text_tokens=50,
                               text_seq_len=6, depth=2, heads=2, dim_head=8,
                               attn_types=("full",))
    dalle = DALLE(cfg)
    rng = jax.random.PRNGKey(0)
    texts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(i), (cfg.text_seq_len,), 1, 50), np.int32)
        for i in range(4)]
    codes = jax.random.randint(rng, (1, cfg.image_seq_len), 0, 32)
    params = dalle.init(rng, jnp.asarray(texts[0])[None], codes,
                        return_loss=True)
    return dalle, params, texts


def test_serve_request_fault_leaves_causal_trail(tmp_path, tiny_serve):
    """One co-batched request fails mid-decode: the stream shows submit <
    admit < fault < fail for the victim (and its slot), while the
    neighbor's trail runs submit < admit < retire with no fault between
    its admit and retire; per-request SLO fields ride the retire."""
    from dalle_pytorch_tpu.serve import GenerationServer

    telemetry.init(tmp_path / "tel", run_id="chaos-serve")
    faults.install("serve_request:fail_after=6")
    srv = GenerationServer(tiny_serve[0], tiny_serve[1], num_slots=2,
                           filter_thres=1.0,
                           slo_targets={"latency": 60.0, "throughput": 60.0})
    h0 = srv.submit(tiny_serve[2][0])
    h1 = srv.submit(tiny_serve[2][1], slo="latency")
    srv.run_until_idle(max_ticks=300)
    assert len(srv.failed) == 1 and len(srv.completed) == 1
    stats = srv.stats()
    telemetry.shutdown()

    recs = read_events(tmp_path / "tel")
    victim = srv.failed[0].request_id
    survivor = (h0 if h1.request_id == victim else h1).request_id

    def seq_of(name, rid):
        # the event of that name, not the span (ph B/E) that shares it
        return next(r["seq"] for r in recs if r.get("name") == name
                    and r.get("rid") == rid and "ph" not in r)

    fault = next(r for r in recs if r["kind"] == "fault"
                 and r["name"] == "serve_request")
    assert seq_of("submit", victim) < seq_of("admit", victim) \
        < fault["seq"] < seq_of("fail", victim)
    assert seq_of("submit", survivor) < seq_of("admit", survivor) \
        < seq_of("retire", survivor)
    retire = next(r for r in recs if r["name"] == "retire"
                  and "ph" not in r)
    assert retire["rid"] == survivor
    assert retire["tokens"] == 16  # image_seq_len at this geometry
    assert retire["slo_ok"] is True and retire["latency_s"] is not None
    fail = next(r for r in recs if r["name"] == "fail")
    assert fail["slot"] == next(r["slot"] for r in recs
                                if r["name"] == "admit"
                                and r.get("rid") == victim)
    # the spans of the same chain: the survivor's retire span closed before
    # its retire event, around the blocking read of its codes
    span = next(r for r in recs if r["name"] == "retire"
                and r.get("ph") == "B")
    assert span["rid"] == survivor and span["seq"] < retire["seq"]
    # stats() attainment mirrors the per-request slo_ok records
    cls = srv.completed[0].slo
    assert stats["slo_attainment"][cls] == 1.0
    rep = build_report(recs)
    assert rep["serve"]["submitted"] == 2
    assert rep["serve"]["completed"] == 1 and rep["serve"]["failed"] == 1


def test_serve_tick_sampling_aggregates_preserve_report(tmp_path,
                                                        tiny_serve):
    """Tick-event sampling (tick_sample=N): the stream shrinks ~N-fold
    but carries the skipped ticks' stats in aggregate records — the
    report's tick totals and occupied-slot-ticks reconstruct EXACTLY the
    unsampled stream's, and the partial window flushes when the server
    drains idle so nothing is lost."""
    from dalle_pytorch_tpu.obs import telemetry
    from dalle_pytorch_tpu.obs.report import build_report
    from dalle_pytorch_tpu.serve import GenerationServer

    def drive(sample):
        telemetry.init(tmp_path / f"tel-s{sample}",
                       run_id=f"sample-{sample}")
        srv = GenerationServer(tiny_serve[0], tiny_serve[1], num_slots=2,
                               tick_sample=sample)
        srv.submit(tiny_serve[2][0])
        for _ in range(3):
            srv.step()
        srv.submit(tiny_serve[2][1])  # mid-flight admission
        srv.run_until_idle(max_ticks=400)
        stats = srv.stats()
        telemetry.shutdown()
        recs = telemetry.read_events(tmp_path / f"tel-s{sample}")
        return stats, [r for r in recs if r.get("kind") == "serve"
                       and r.get("name") == "tick"
                       and "ph" not in r], build_report(recs)

    stats1, ticks1, rep1 = drive(1)
    stats3, ticks3, rep3 = drive(3)
    # the servers ran the identical schedule
    assert stats1["ticks"] == stats3["ticks"] > 0
    # sampled stream: fewer records, same covered totals
    assert len(ticks3) < len(ticks1)
    assert sum(int(r.get("ticks", 1)) for r in ticks3) == stats3["ticks"]
    assert rep3["serve"]["ticks"] == rep1["serve"]["ticks"] \
        == stats1["ticks"]
    occupied = stats1["occupancy"] * stats1["ticks"] * 2  # 2 slots
    assert rep1["serve"]["occupied_slot_ticks"] \
        == rep3["serve"]["occupied_slot_ticks"] \
        == pytest.approx(occupied)
    # every aggregate self-describes its window
    for r in ticks3:
        assert r["ticks"] <= 3
        assert r["active_min"] <= r["active"] <= r["active_max"]
        assert r["active_sum"] == pytest.approx(r["active"] * r["ticks"])


# --- read side: fixture stream, report, Perfetto --------------------------


def test_report_sections_from_committed_fixture():
    recs = read_events(FIXTURE)
    assert len(recs) == 42
    rep = build_report(recs)
    assert rep["steps"]["records"] == 8
    assert rep["steps"]["reservoir"]["step_time_p99"] == pytest.approx(0.14)
    assert rep["health"]["verdicts"].get("spike") == 1
    assert rep["ckpt"]["publishes"] == 2 and rep["ckpt"]["torn_saves"] == 1
    assert rep["serve"]["submitted"] == 2
    assert rep["serve"]["preemptions"] == 1
    assert rep["serve"]["by_class"]["latency"]["attainment"] == 1.0
    assert any(f["site"] == "serve_request" for f in rep["faults"])
    assert rep["data"]["sample_quarantines"] == 1
    text = render_text(rep)
    for needle in ("graftscope run report", "fixture-run", "-- training --",
                   "reservoir", "spike", "-- checkpoints --", "torn 1",
                   "-- serve --", "latency", "injected faults",
                   "torn spans"):
        assert needle in text, needle


def test_perfetto_export_shape_and_threads():
    import jsonschema

    recs = read_events(FIXTURE)
    doc = to_chrome_trace(recs)
    # minimal trace-event shape contract (what ui.perfetto.dev ingests)
    schema = {
        "type": "object", "required": ["traceEvents"],
        "properties": {"traceEvents": {"type": "array", "items": {
            "type": "object", "required": ["ph", "name", "pid"],
            "properties": {"ph": {"enum": ["M", "X", "i", "C"]},
                           "ts": {"type": "number"},
                           "dur": {"type": "number"},
                           "tid": {"type": "integer"}}}}}}
    jsonschema.validate(doc, schema)
    events = doc["traceEvents"]
    thread_names = {e["args"]["name"] for e in events
                    if e["ph"] == "M" and e["name"] == "thread_name"}
    # spans from >= 3 threads on the one timeline: step loop, async ckpt
    # writer(s), serve driver
    span_tids = {e["tid"] for e in events if e["ph"] == "X"}
    assert len(span_tids) >= 3
    assert any(t.startswith("ckpt-async") for t in thread_names)
    assert any(t.startswith("serve") for t in thread_names)
    assert "MainThread" in thread_names
    # the torn ckpt save surfaces as an explicit unfinished marker
    assert any("(unfinished)" in e["name"] for e in events
               if e["ph"] == "i")
    # complete spans carry durations
    assert all(e["dur"] > 0 for e in events if e["ph"] == "X")


def test_obs_report_cli_formats(tmp_path, capsys):
    sys.path.insert(0, str(REPO / "tools"))
    import obs_report

    assert obs_report.main([str(FIXTURE)]) == 0
    assert "graftscope run report" in capsys.readouterr().out
    out_json = tmp_path / "report.json"
    assert obs_report.main([str(FIXTURE), "--format", "json",
                            "--output", str(out_json)]) == 0
    capsys.readouterr()
    rep = json.loads(out_json.read_text())
    assert rep["steps"]["records"] == 8
    out_trace = tmp_path / "run.trace.json"
    assert obs_report.main([str(FIXTURE), "--format", "trace",
                            "--output", str(out_trace)]) == 0
    capsys.readouterr()
    doc = json.loads(out_trace.read_text())
    assert any(e["ph"] == "X" for e in doc["traceEvents"])
    assert obs_report.main([str(FIXTURE), "--tail", "3"]) == 0
    tail = capsys.readouterr().out
    assert len(tail.strip().splitlines()) == 3
    assert obs_report.main([str(tmp_path / "empty")]) == 2


# --- live CPU smoke: trainer emits, obs_report renders --------------------


def test_live_vae_run_emits_stream_and_report(tmp_path, monkeypatch):
    """The acceptance smoke: a real (tiny) train_vae run with
    --telemetry_dir produces one schema-valid events.jsonl whose report
    carries training + checkpoint sections and the reservoir summary."""
    import jsonschema
    from PIL import Image

    rng = np.random.default_rng(0)
    data = tmp_path / "data"
    data.mkdir()
    for i in range(8):
        arr = (rng.uniform(size=(16, 16, 3)) * 255).astype(np.uint8)
        Image.fromarray(arr).save(data / f"s{i}.png")
    monkeypatch.setenv("DALLE_TPU_HPARAMS", json.dumps(dict(
        EPOCHS=2, BATCH_SIZE=4, NUM_TOKENS=32, NUM_LAYERS=2,
        NUM_RESNET_BLOCKS=0, EMB_DIM=16, HID_DIM=16, NUM_IMAGES_SAVE=2)))
    monkeypatch.chdir(tmp_path)
    import train_vae

    train_vae.main(["--image_folder", str(data), "--image_size", "16",
                    "--ckpt_every", "2", "--telemetry_dir", "tel",
                    "--heartbeat_dir", "hb"])
    recs = read_events(tmp_path / "tel")
    assert recs, "trainer produced no events"
    for r in recs:
        jsonschema.validate(r, EVENT_SCHEMA)
    names = {(r["kind"], r["name"]) for r in recs}
    assert {("run", "run_start"), ("run", "run_end"),
            ("step", "train"), ("ckpt", "publish")} <= names
    end = next(r for r in recs if r["name"] == "run_end")
    assert end["completed"] is True and "step_time_p50" in end
    # heartbeat <-> stream correlation
    hb = json.loads((tmp_path / "hb" / "heartbeat-p0.json").read_text())
    assert hb["run_id"] == next(iter({r["run"] for r in recs}))
    assert hb["telemetry_seq"] >= 1
    rep = build_report(recs)
    assert rep["steps"]["records"] >= 2
    assert rep["ckpt"]["publishes"] >= 2
    assert rep["ckpt"]["torn_saves"] == 0
    text = render_text(rep)
    assert "reservoir" in text and "-- checkpoints --" in text
