"""The serve loop's phases as spans (serve/scheduler.py): ``serve.step`` >
``serve.admit`` > ``serve.prefill`` | ``serve.tick`` | ``serve.mem_watermark``
| ``serve.retire``, one ``rid`` chain a request, free with the stream
closed, bounded by ``tick_sample``; the arena's named programs; the report's
phase lines.  (Named late in the alphabet: ROADMAP, the watchdog of
``test_anomaly_resume.py``.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu import DALLE, DALLEConfig, VAEConfig
from dalle_pytorch_tpu.obs import telemetry
from dalle_pytorch_tpu.obs.report import build_report, render_text
from dalle_pytorch_tpu.serve import GenerationServer

IMAGE_LEN = 16


@pytest.fixture(scope="module")
def tiny():
    vcfg = VAEConfig(image_size=16, num_tokens=32, codebook_dim=16,
                     num_layers=2, hidden_dim=8)
    cfg = DALLEConfig.from_vae(vcfg, dim=32, num_text_tokens=50,
                               text_seq_len=6, depth=2, heads=2, dim_head=8,
                               attn_types=("full",))
    assert cfg.image_seq_len == IMAGE_LEN
    dalle = DALLE(cfg)
    texts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(i), (cfg.text_seq_len,), 1, 50), np.int32)
        for i in range(4)]
    codes = jnp.zeros((1, cfg.image_seq_len), jnp.int32)
    params = dalle.init(jax.random.PRNGKey(0), jnp.asarray(texts[0])[None],
                        codes, return_loss=True)
    return dalle, params, texts


@pytest.fixture(autouse=True)
def no_stream_left_open():
    yield
    telemetry.shutdown()


def serve(tiny, directory=None, requests=3, **kwargs):
    """Three requests through two slots (so one waits in the queue and is
    admitted in the step that retires the first two); the stream open under
    ``directory`` or closed.  Returns (server, handles, serve records)."""
    dalle, params, texts = tiny
    if directory is not None:
        telemetry.init(directory, run_id="spans", beacon_every=0)
    srv = GenerationServer(dalle, params, num_slots=2, seed=5, **kwargs)
    handles = [srv.submit(texts[i % len(texts)]) for i in range(requests)]
    srv.run_until_idle(max_ticks=400)
    telemetry.shutdown()
    recs = ([] if directory is None else
            [r for r in telemetry.read_events(directory)
             if r["kind"] == "serve"])
    return srv, handles, recs


def spans_of(recs):
    """``[(B record, E record)]`` paired by ``sid``, in B order."""
    ends = {r["sid"]: r for r in recs if r.get("ph") == "E"}
    return [(r, ends[r["seq"]]) for r in recs if r.get("ph") == "B"]


def test_spans_pair_and_nest_as_the_work_nests(tmp_path, tiny):
    _, _, recs = serve(tiny, tmp_path)
    begun = [r for r in recs if r.get("ph") == "B"]
    pairs = spans_of(recs)
    assert len(pairs) == len(begun)        # every B has its E: nothing torn
    assert all(e["ok"] and e["dur_s"] >= 0 and e["name"] == b["name"]
               for b, e in pairs)

    def inside(outer):
        b, e = outer
        return [p for p in pairs if b["seq"] < p[0]["seq"] < e["seq"]]

    steps = [p for p in pairs if p[0]["name"] == "step"]
    assert steps and all(set(("clock", "running", "queued")) <= set(b)
                         for b, _ in steps)
    # no span of the loop lies outside a step
    covered = {p[0]["seq"] for s in steps for p in inside(s)}
    assert covered == {b["seq"] for b, _ in pairs if b["name"] != "step"}
    # the first step admits two requests (each around its own prefill) and
    # ticks; the queue holds the third
    first = inside(steps[0])
    assert [b["name"] for b, _ in first] == ["admit", "prefill", "admit",
                                             "prefill", "tick"]
    assert steps[0][0]["queued"] == 3 and steps[0][0]["running"] == 0
    for admit in (p for p in pairs if p[0]["name"] == "admit"):
        (child,) = inside(admit)
        assert child[0]["name"] == "prefill"
        assert child[0]["rid"] == admit[0]["rid"]
    # the step that retires two requests frees their slots first, admits
    # the waiting one and ticks; the blocking reads come last, so that the
    # device holds the install and the tick while the host waits
    turn = next(s for s in steps
                if any(b["name"] == "retire" for b, _ in inside(s)))
    assert [b["name"] for b, _ in inside(turn)] == [
        "admit", "prefill", "tick", "retire", "retire"]
    assert turn[0]["running"] == 2          # counted before the release
    tick = next(b for b, _ in inside(turn) if b["name"] == "tick")
    assert tick["clock"] == turn[0]["clock"] and tick["active"] == 1
    # an idle step (nothing retired or admitted) holds its tick alone
    assert any([b["name"] for b, _ in inside(s)] == ["tick"] for s in steps)


def test_one_rid_chains_a_request_from_submit_to_retire(tmp_path, tiny):
    srv, handles, recs = serve(tiny, tmp_path)
    for h in handles:
        chain = [(r["name"], r.get("ph")) for r in recs
                 if r.get("rid") == h.request_id]
        assert chain == [("submit", None), ("admit", "B"), ("prefill", "B"),
                         ("admit", None), ("retire", "B"), ("retire", None)]
        retire = next(r for r in recs if r.get("rid") == h.request_id
                      and r["name"] == "retire" and "ph" not in r)
        assert retire["queue_wait_s"] + retire["decode_s"] == pytest.approx(
            retire["latency_s"], abs=1e-9)
        assert retire["latency_s"] == pytest.approx(h.latency)
        assert retire["tokens"] == IMAGE_LEN
        slots = {r["slot"] for r in recs if r.get("rid") == h.request_id
                 and "slot" in r}
        assert len(slots) == 1             # admit and retire name one slot
    # what the dropped `graft_serve_retired_total` / `_latency_seconds`
    # series counted rides the retire records and stats()
    retired = [r for r in recs if r["name"] == "retire" and "ph" not in r]
    stats = srv.stats()
    assert len(retired) == stats["completed"] == 3
    assert sorted(r["latency_s"] for r in retired)[1] == pytest.approx(
        stats["latency_p50"]["throughput"])


def test_stream_closed_writes_nothing_and_hands_out_the_null_span(
        tmp_path, tiny):
    srv, handles, recs = serve(tiny)
    assert recs == [] and all(h.future.done() for h in handles)
    for name in ("step", "retire", "admit", "prefill", "tick",
                 "mem_watermark"):
        assert srv._span("serve", name, rid=0) is telemetry.NULL_SPAN
    assert not any(tmp_path.iterdir())
    # a lane that is given but disabled is as free
    off = telemetry.Telemetry.disabled()
    lane = GenerationServer(tiny[0], tiny[1], num_slots=1, tel=off)
    assert lane._span("serve", "step") is telemetry.NULL_SPAN


def test_codes_are_bit_identical_with_the_stream_open_and_closed(
        tmp_path, tiny):
    _, closed, _ = serve(tiny)
    _, opened, recs = serve(tiny, tmp_path)
    _, sampled, _ = serve(tiny, tmp_path / "sampled", tick_sample=4)
    assert recs
    for a, b, c in zip(closed, opened, sampled):
        np.testing.assert_array_equal(a.result(), b.result())
        np.testing.assert_array_equal(a.result(), c.result())


@pytest.mark.parametrize("sample", [1, 3, 8])
def test_tick_sample_bounds_the_step_and_tick_spans(tmp_path, tiny, sample):
    srv, _, recs = serve(tiny, tmp_path, tick_sample=sample)
    ticks = srv.stats()["ticks"]
    named = {name: [b for b, _ in spans_of(recs) if b["name"] == name]
             for name in ("step", "tick", "retire", "admit")}
    tick_records = [r for r in recs if r["name"] == "tick" and "ph" not in r]
    # what the dropped `graft_serve_ticks_total` counted: the records'
    # `ticks` sum to every tick run, whatever the sampling
    assert sum(r["ticks"] for r in tick_records) == ticks
    if sample == 1:
        assert len(named["tick"]) == ticks
        assert len(named["step"]) == ticks + 1      # the last step retires
    else:
        # one span a full window: a tick span only where a record was
        # flushed by the window filling up
        assert len(named["tick"]) == ticks // sample
        assert len(named["tick"]) <= len(tick_records) < ticks
        assert len(named["step"]) <= len(named["tick"]) + 1
    # per-request spans are not sampled
    assert len(named["retire"]) == len(named["admit"]) == 3


def test_every_tick_advances_each_active_slot_by_one_code(tmp_path, tiny):
    """One decode algorithm: a tick decodes one code a slot it advances, so
    the tick records' occupied slot-ticks plus the code each admission
    samples are every code decoded, and the records carry nothing else to
    count them by."""
    srv, handles, recs = serve(tiny, tmp_path)
    ticks = [r for r in recs if r["name"] == "tick" and "ph" not in r]
    assert not any({"tokens", "spec"} & set(r) for r in ticks)
    stats = srv.stats()
    assert stats["decoded_tokens"] == len(handles) * IMAGE_LEN == (
        sum(r["active_sum"] for r in ticks) + len(handles))
    assert not [key for key in stats if "accepted" in key]
    assert build_report(telemetry.read_events(tmp_path))["serve"][
        "decoded_tokens"] == len(handles) * IMAGE_LEN


def test_programs_are_named_for_the_trace_and_nothing_retraces(tiny):
    srv, _, _ = serve(tiny)
    assert srv.trace_counts() == {"prefill": 1, "admit": 1, "tick": 1}
    programs = srv.arena.programs()
    assert sorted(programs) == ["jit_serve_admit", "jit_serve_prefill",
                                "jit_serve_tick"]
    for name, program in programs.items():
        assert f"HloModule {name}" in program.as_text().splitlines()[0]
    assert "graftprof:serve-tick" in programs["jit_serve_tick"].as_text()
    assert programs["jit_serve_tick"].memory_analysis() is not None
    # handing them out compiled nothing into the entry points' caches and
    # left the state alive
    assert srv.trace_counts() == {"prefill": 1, "admit": 1, "tick": 1}
    handle = srv.submit(tiny[2][0])
    srv.run_until_idle(max_ticks=100)
    assert handle.result().shape == (IMAGE_LEN,)
    assert srv.trace_counts() == {"prefill": 1, "admit": 1, "tick": 1}


def test_report_prints_the_phases_and_the_steps_self_time(tmp_path, tiny):
    srv, _, _ = serve(tiny, tmp_path)
    report = build_report(telemetry.read_events(tmp_path))
    sv = report["serve"]
    # the events' counts are not doubled by the spans that share their names
    assert sv["completed"] == 3 and sv["submitted"] == 3
    assert sv["ticks"] == srv.stats()["ticks"]
    ph = sv["phases"]
    assert ph["steps"] == srv.stats()["ticks"] + 1
    assert {k: v["count"] for k, v in ph["phases"].items()} == {
        "retire": 3, "admit": 3, "prefill": 3, "tick": srv.stats()["ticks"]}
    direct = sum(ph["phases"][k]["total_s"]
                 for k in ("retire", "admit", "tick"))
    assert 0 <= ph["self_s"] <= ph["step_s"] - direct + 1e-9
    assert ph["phases"]["prefill"]["total_s"] <= \
        ph["phases"]["admit"]["total_s"]
    text = render_text(report)
    assert "-- serve --" in text and "steps recorded" in text
    for name in ("retire", "admit", "prefill", "tick"):
        assert f"  {name}: n=" in text


def test_report_phase_arithmetic_on_a_hand_made_stream():
    def span(seq, name, mono, dur, **fields):
        base = dict(kind="serve", run="r", host=0, pid=1, thread="t")
        return [dict(base, name=name, seq=seq, ph="B", mono=mono, **fields),
                dict(base, name=name, seq=seq + 1, ph="E", sid=seq,
                     mono=mono + dur, dur_s=dur, ok=True)]

    recs = (span(1, "step", 10.0, 1.0, clock=0)
            + span(3, "retire", 10.1, 0.2, rid=0)
            + span(5, "admit", 10.3, 0.3, rid=1)
            + span(7, "prefill", 10.35, 0.1, rid=1)
            + span(9, "tick", 10.7, 0.1, clock=0)
            + span(11, "step", 12.0, 0.5, clock=1)
            + span(13, "tick", 12.1, 0.4, clock=1)
            # an admit outside every recorded step (a sampled stream)
            + span(15, "admit", 20.0, 9.0, rid=2))
    ph = build_report(recs)["serve"]["phases"]
    assert ph["steps"] == 2 and ph["step_s"] == pytest.approx(1.5)
    assert ph["phases"]["retire"] == {
        "count": 1, "total_s": pytest.approx(0.2),
        "median_s": pytest.approx(0.2), "share": pytest.approx(0.2 / 1.5)}
    assert ph["phases"]["admit"]["count"] == 1
    assert ph["phases"]["admit"]["total_s"] == pytest.approx(0.3)
    assert ph["phases"]["prefill"]["share"] == pytest.approx(0.1 / 1.5)
    assert ph["phases"]["tick"]["total_s"] == pytest.approx(0.5)
    # 1.5 s of steps less the direct children (prefill is admit's)
    assert ph["self_s"] == pytest.approx(1.5 - 0.2 - 0.3 - 0.5)
    assert build_report([])["serve"]["phases"] is None


def test_watermark_span_once_per_mem_watermark_ticks(tmp_path, tiny):
    srv, _, recs = serve(tiny, tmp_path, mem_watermark_ticks=8)
    ticks = srv.stats()["ticks"]
    polls = [b for b, _ in spans_of(recs) if b["name"] == "mem_watermark"]
    marks = [r for r in telemetry.read_events(tmp_path)
             if r["kind"] == "mem" and r["name"] == "watermark"]
    assert len(polls) == len(marks) == ticks // 8 > 0
    # the poll runs with the stream closed too; only its span is gone
    closed, _, _ = serve(tiny, mem_watermark_ticks=8)
    assert closed.mem_tracker is not None
    assert closed._ticks_since_watermark == ticks % 8


def test_spans_ride_the_profilers_clock(tmp_path, tiny):
    import gzip

    from dalle_pytorch_tpu.obs import prof

    dalle, params, texts = tiny
    telemetry.init(tmp_path / "run", run_id="r", beacon_every=0)
    srv = GenerationServer(dalle, params, num_slots=1)
    srv.submit(texts[0])
    with prof.capture(tmp_path / "trace"):
        srv.run_until_idle(max_ticks=100)
    telemetry.shutdown()
    (plane,) = (tmp_path / "trace").glob("plugins/profile/*/*.xplane.pb")
    blob = plane.read_bytes()
    if blob[:2] == b"\x1f\x8b":
        blob = gzip.decompress(blob)
    for name in ("step", "retire", "admit", "prefill", "tick"):
        assert f"graft:serve.{name}".encode() in blob, name


def test_a_released_request_resolves_even_if_the_admission_fails(tiny):
    """The codes are read after the step's dispatches; a failure between
    the release and the read must not leave the released future hanging."""
    dalle, params, texts = tiny
    srv = GenerationServer(dalle, params, num_slots=1, seed=5)
    first, second = srv.submit(texts[0]), srv.submit(texts[1])
    for _ in range(IMAGE_LEN - 1):
        srv.step()
    assert not first.future.done()

    def broken(text):
        raise RuntimeError("prefill failed")

    srv.arena.prefill = broken
    with pytest.raises(RuntimeError, match="prefill failed"):
        srv.step()            # releases `first`, fails admitting `second`
    assert first.result(timeout=0).shape == (IMAGE_LEN,)
    assert not second.future.done() and srv._retiring == []
