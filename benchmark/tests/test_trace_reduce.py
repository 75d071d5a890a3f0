"""The reduction from trace events to numbers, on events with known answers
and on a recording of a real v5e trace."""
import json
from pathlib import Path

import pytest

from benchmark import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def test_union_length_subtract():
    assert tr.union([[5, 7], [0, 2], [1, 3], [7, 7]]) == [[0, 3], [5, 7]]
    assert tr.length([[0, 3], [5, 7]]) == 5
    assert tr.subtract([[0, 10]], [[2, 3], [5, 20]]) == [[0, 2], [3, 5]]
    assert tr.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]


def synthetic():
    """10 us of one device: a while op [0, 6) spanning a fusion under
    attn-scores [0, 2) and one under ff [2, 5); an exposed all-reduce
    [6, 7); idle [7, 9); a fusion with no scope [9, 10)."""
    us = 1000
    ops = [["while.1", 0, 6 * us, "jit_train_step"],
           ["fusion.1", 0, 2 * us, "jit_train_step"],
           ["fusion.2", 2 * us, 3 * us, "jit_train_step"],
           ["all-reduce.3", 6 * us, 1 * us, "jit_train_step"],
           ["fusion.1", 9 * us, 1 * us, "jit_other"]]
    modules = [["jit_train_step", 0, 7 * us], ["jit_other", 9 * us, us]]
    host = [["bench:train_step", 0, 7 * us], ["bench:loss_fetch", 7 * us,
                                              2500]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules, "collectives": []}],
            "host_spans": host}


HLO = """
HloModule jit_train_step
  %fusion.1 = f32[2]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(train_step)/transpose(jvp(DALLE))/graftprof:attn-scores/dot_general" source_file="a.py"}
  %fusion.2 = f32[2]{0} fusion(%p), kind=kLoop, calls=%fd, metadata={op_name="jit(train_step)/graftprof:decode-step/graftprof:ff/mul"}
  %all-reduce.3 = f32[2]{0} all-reduce(%fusion.2), replica_groups={}
  ROOT %while.1 = (f32[2]{0}) while(%t), body=%b, metadata={op_name="jit(train_step)/graftprof:decode-step/while"}
"""
SCOPES = {"jit_train_step": tr.scopes_of(HLO)}


def test_scopes_come_from_the_programs_hlo_text():
    assert SCOPES["jit_train_step"] == {
        "fusion.1": "attn-scores", "fusion.2": "ff", "while.1": "decode-step"}
    assert tr.instr_name("%fusion.43 = (f32[1]{0}) fusion(f32[] %x), "
                         "kind=kLoop") == "fusion.43"
    assert tr.program_name("jit_train_step(1544422774294123407)") == (
        "jit_train_step")


def test_known_answers():
    r = tr.reduce(synthetic(), scopes=SCOPES)
    assert r.chips == 1
    assert r.window_s == pytest.approx(10e-6)
    assert r.busy_s == pytest.approx(8e-6)
    assert r.idle_share == pytest.approx(0.2)
    # self time: the while keeps only the 1 us its body does not cover
    assert r.scope_s["decode-step"] == pytest.approx(1e-6)
    assert r.scope_s["attn-scores"] == pytest.approx(2e-6)
    assert r.scope_s["ff"] == pytest.approx(3e-6)
    assert r.scope_s[tr.UNSCOPED] == pytest.approx(2e-6)
    assert sum(r.scope_s.values()) == pytest.approx(r.busy_s)
    assert r.scope_share("ff") == pytest.approx(3 / 8)
    assert r.scope_share("optimizer") is None
    assert r.collective_s == pytest.approx(1e-6)
    assert r.collective_exposed_s == pytest.approx(1e-6)
    assert r.program("jit_train_step") == {
        "seconds": pytest.approx(7e-6), "calls": 1,
        "median_s": pytest.approx(7e-6)}
    assert r.program("jit_missing") is None
    assert r.breakdown["idle_gaps"][0] == ["bench:loss_fetch",
                                           pytest.approx(2e-6)]
    assert r.breakdown["device_ops"][0] == ["ff/fusion",
                                            pytest.approx(3e-6)]


def test_collective_hidden_behind_compute_is_not_exposed():
    raw = synthetic()
    raw["devices"][0]["ops"].append(["fusion.2", 6000, 1000, "jit_train_step"])
    r = tr.reduce(raw, scopes=SCOPES)
    assert r.collective_s == pytest.approx(1e-6)
    assert r.collective_exposed_s == 0.0


def test_given_window_and_two_chips():
    raw = synthetic()
    raw["devices"].append(dict(raw["devices"][0], name="/device:TPU:1"))
    r = tr.reduce(raw, window_s=16e-6, scopes=SCOPES)
    assert r.chips == 2 and r.busy_s == pytest.approx(8e-6)
    assert r.idle_share == pytest.approx(0.5)
    assert r.program("jit_train_step")["calls"] == 1


def test_no_device_plane_gives_nothing():
    assert tr.reduce({"devices": [], "host_spans": []}) is None


def test_extract_of_a_cpu_trace_has_no_device(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:probe"):
        jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    xplane = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    raw = tr.extract(xplane)
    assert raw["devices"] == []
    assert any(name == "bench:probe" for name, _, _ in raw["host_spans"])


def test_recorded_v5e_trace():
    """The first 150 ms (two train steps) of a traced ``cub200-train`` run on
    the v5e (PR 22), cut by ``tools/dump_trace.py --record``.  The expected
    numbers were computed when the recording was made and agree with the full
    run's own line (``attn-scores`` 76% of busy time, 72 ms a step); they pin
    the reduction against later edits."""
    path = DATA / "trace_train_record.json"
    expect = json.loads((DATA / "trace_train_expect.json").read_text())
    raw = json.loads(path.read_text())
    r = tr.reduce(raw, scopes=raw["scopes"])
    assert r.chips == 1
    assert r.busy_s == pytest.approx(expect["busy_s"], rel=1e-9)
    assert r.window_s == pytest.approx(expect["window_s"], rel=1e-9)
    for scope, seconds in expect["scope_s"].items():
        assert r.scope_s[scope] == pytest.approx(seconds, rel=1e-9)
    assert sum(r.scope_s.values()) == pytest.approx(r.busy_s, rel=1e-6)
    assert r.program("jit_train_step")["calls"] == expect["train_step_calls"]
    assert r.program("jit_train_step")["median_s"] == pytest.approx(0.072,
                                                                     rel=0.01)
    assert 0.70 < r.scope_share("attn-scores") < 0.80
    assert r.scope_share(tr.UNSCOPED) < 0.06
