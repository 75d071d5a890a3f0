"""The compile log (``obs/compiles.py``): one ``jax.monitoring`` listener
behind ``setup_s`` and behind retraces in a timed window.

* ``install()`` is idempotent and is what ``enable_compilation_cache()``
  calls;
* a fresh ``jax.jit`` call leaves ``trace`` / ``lower`` / ``compile`` records
  carrying the function's name, a second call at the same shape none, a call
  at a new shape one more ``trace`` (the retrace the window metrics count);
* ``summarize`` is pure arithmetic over a record list;
* the records reach the telemetry stream, the metrics registry and the run
  report only where those are active.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from dalle_pytorch_tpu.obs import compiles, metrics, telemetry
from dalle_pytorch_tpu.obs.report import build_report, render_text


@pytest.fixture()
def log():
    compiles.install()
    compiles.clear()
    yield compiles
    compiles.clear()


def _named(phase, name, recs):
    return [r for r in recs if r["phase"] == phase and r["fun_name"] == name]


def test_install_twice_registers_one_listener():
    from jax._src import monitoring

    from dalle_pytorch_tpu.cli import enable_compilation_cache

    compiles.install()
    enable_compilation_cache()      # the one place that switches it on
    compiles.install()
    assert compiles.installed()
    assert sum(cb is compiles._on_duration for cb in
               monitoring.get_event_duration_listeners()) == 1
    assert sum(cb is compiles._on_event for cb in
               monitoring.get_event_listeners()) == 1
    assert sum(cb is compiles._on_scalar for cb in
               monitoring.get_scalar_listeners()) == 1


def test_fresh_jit_is_logged_once_and_a_new_shape_retraces(log):
    def compile_log_probe(x):
        return x * 2 + 1

    f = jax.jit(compile_log_probe)
    f(jnp.ones((3,)))
    first = log.records()
    for phase in ("trace", "lower", "compile"):
        rows = _named(phase, "compile_log_probe", first)
        assert len(rows) == 1, (phase, first)
        assert rows[0]["dur_s"] > 0 and rows[0]["t"] > 0

    f(jnp.ones((3,)))               # same shape: nothing traced or compiled
    assert not _named("trace", "compile_log_probe",
                      log.records()[len(first):])
    assert not _named("compile", "compile_log_probe",
                      log.records()[len(first):])

    mark = len(log.records())
    f(jnp.ones((4,)))               # a new shape: the retrace
    later = log.records()[mark:]
    assert len(_named("trace", "compile_log_probe", later)) == 1
    assert len(_named("compile", "compile_log_probe", later)) == 1
    snap = log.snapshot(since=first[0]["t"])
    assert snap["phases"]["trace"]["count"] >= 2
    assert "compile_log_probe" in [f["fun_name"] for f in snap["top"]]


def test_inner_traces_are_dropped_and_threads_do_not_share_depth(log):
    import threading

    def nested_probe(x):
        return jnp.sum(jnp.sin(x) * jnp.cos(x) + jax.jit(jnp.tanh)(x))

    x = jnp.ones((9,))
    log.clear()
    jax.jit(nested_probe)(x)
    traces = [r["fun_name"] for r in log.records() if r["phase"] == "trace"]
    # sin, cos, multiply, tanh, add, sum are jits too: their traces lie
    # inside nested_probe's and are not programs of their own
    assert traces == ["nested_probe"], traces

    seen = []

    def worker():
        mark = len(log.records())
        jax.jit(lambda v: v * 3)(x)
        seen.extend(r for r in log.records()[mark:] if r["phase"] == "trace")

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert len(seen) == 1       # a fresh thread starts at depth 0
    log.clear()
    x + 1                       # an eager op at the top level IS a program
    assert [r["fun_name"] for r in log.records()
            if r["phase"] == "trace"] == ["add"]


def test_summarize_windows_and_ranks_by_seconds():
    recs = [
        {"phase": "trace", "fun_name": "a", "t": 1.0, "dur_s": 0.5},
        {"phase": "lower", "fun_name": "a", "t": 1.5, "dur_s": 0.25},
        {"phase": "cache_request", "fun_name": None, "t": 1.6, "dur_s": None},
        {"phase": "cache_miss", "fun_name": None, "t": 1.7, "dur_s": None},
        {"phase": "compile", "fun_name": "a", "t": 2.0, "dur_s": 2.0},
        {"phase": "trace", "fun_name": "b", "t": 8.0, "dur_s": 4.0},
        {"phase": "compile", "fun_name": "b", "t": 9.0, "dur_s": 0.125},
    ]
    whole = compiles.summarize(recs)
    assert whole["records"] == 7
    assert whole["phases"]["trace"] == {"count": 2, "seconds": 4.5}
    assert whole["phases"]["compile"] == {"count": 2, "seconds": 2.125}
    assert whole["phases"]["cache_miss"]["count"] == 1
    assert whole["phases"]["cache_hit"] == {"count": 0, "seconds": 0.0}
    # busy seconds: the union of [t - dur_s, t], per kind of work
    assert whole["busy_s"] == {"trace_lower": 0.75 + 4.0, "compile": 2.125}
    nested = recs + [{"phase": "trace", "fun_name": "add", "t": 7.5,
                      "dur_s": 0.5}]      # inside b's trace [4, 8]: once
    assert compiles.summarize(nested)["busy_s"]["trace_lower"] == 4.75
    assert compiles.summarize(nested)["phases"]["trace"]["seconds"] == 5.0
    assert [f["fun_name"] for f in whole["top"]] == ["b", "a"]
    assert whole["top"][0] == {"fun_name": "b", "count": 2, "seconds": 4.125}
    # since is inclusive, until exclusive
    cut = compiles.summarize(recs, since=1.5, until=3.0)
    assert cut["records"] == 4
    assert cut["phases"]["trace"]["count"] == 0
    assert cut["phases"]["lower"]["count"] == 1
    assert cut["phases"]["compile"] == {"count": 1, "seconds": 2.0}
    # a span that began before `since` is clipped to the window
    assert cut["busy_s"] == {"trace_lower": 0.0, "compile": 0.5}
    assert compiles.summarize(recs, since=9.5)["records"] == 0
    many = [{"phase": "trace", "fun_name": f"f{i}", "t": 1.0, "dur_s": 1.0 + i}
            for i in range(15)]
    assert len(compiles.summarize(many)["top"]) == compiles.TOP_FUNCTIONS


def test_stream_gets_compile_events_only_while_active(log, tmp_path):
    def streamed_probe(x):
        return x - 1

    telemetry.init(tmp_path / "run", run_id="r")
    try:
        telemetry.emit("step", "train", step=1, loss=1.0)
        jax.jit(streamed_probe)(jnp.ones((5,)))
    finally:
        telemetry.shutdown()
    events = telemetry.read_events(tmp_path / "run")
    comp = [r for r in events if r["kind"] == "compile"]
    assert {"trace", "lower", "compile"} <= {r["name"] for r in comp}
    named = [r for r in comp if r.get("fun") == "streamed_probe"]
    assert {r["name"] for r in named} == {"trace", "lower", "compile"}
    assert all(r["dur_s"] > 0 for r in named)

    rep = build_report(events)
    assert rep["compiles"]["phases"]["compile"]["count"] >= 1
    assert rep["compiles"]["traces_after_first_step"] >= 1
    assert 1 <= len(rep["compiles"]["top"]) <= 5
    text = render_text(rep)
    assert "-- compiles --" in text and "traces after the first step" in text

    # no stream active: the log still fills, nothing is written
    before = sorted(p.name for p in (tmp_path / "run").iterdir())
    size = (tmp_path / "run" / "events.jsonl").stat().st_size
    jax.jit(streamed_probe)(jnp.ones((6,)))
    assert _named("trace", "streamed_probe", log.records())
    assert sorted(p.name for p in (tmp_path / "run").iterdir()) == before
    assert (tmp_path / "run" / "events.jsonl").stat().st_size == size
    assert build_report([r for r in events
                         if r["kind"] != "compile"])["compiles"] is None


def test_registry_gets_counters_only_while_active(log):
    def counted_probe(x):
        return x + 3

    reg = metrics.init()
    try:
        jax.jit(counted_probe)(jnp.ones((7,)))
    finally:
        metrics.shutdown()
    text = reg.render()
    assert "graft_compile_requests_total" in text
    assert 'graft_compile_seconds_count{phase="trace"}' in text
    requests = reg.counter("graft_compile_requests_total").value
    assert requests >= 1
    jax.jit(counted_probe)(jnp.ones((8,)))      # detached: no more counts
    assert reg.counter("graft_compile_requests_total").value == requests


def test_listener_cost_per_event_is_microseconds(log):
    import time

    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        compiles._on_duration("/jax/core/compile/jaxpr_trace_duration",
                              0.001, fun_name="jit(cost_probe)")
    per_event = (time.perf_counter() - t0) / n
    assert per_event < 200e-6, per_event       # measured ~1.5 us
    assert log.records()[-1]["fun_name"] == "cost_probe"
    # events of other names cost a dict miss and leave nothing
    mark = len(log.records())
    compiles._on_duration("/jax/compilation_cache/cache_retrieval_time_sec",
                          0.5)
    compiles._on_event("/jax/compilation_cache/tasks_using_cache")
    assert len(log.records()) == mark
