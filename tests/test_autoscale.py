"""graftscale decision-table + brownout ladder tests (serve/autoscale.py).

Pure by construction: the control law (``AutoScaler.decide``) is driven
with hand-built :class:`Signals` and EXPLICIT clocks — no processes, no
sockets, no model, no sleeps.  Actuation (`apply_level`, `_scale_up`,
`_scale_down`, `resync`, `collect`) runs against stub routers/replicas
that record what was done to them.  The live-fleet leg — real spawns,
real surge, real kill — is ``tools/loadgen.py --autoscale`` (the CI
``autoscale_smoke`` chaos row).

Also here: the spawn-orphan regression (a `_wait_ready` timeout must
kill AND reap the child, raising typed :class:`SpawnFailed`) and the
fire/cooldown behavior of the two graftscale alert rules.
"""
import subprocess
import sys
from pathlib import Path

import pytest

from dalle_pytorch_tpu.obs import alerts
from dalle_pytorch_tpu.obs import metrics as obs_metrics
from dalle_pytorch_tpu.obs import telemetry
from dalle_pytorch_tpu.serve import (DRAINING, JOINING, LATENCY, SERVING,
                                     THROUGHPUT, AutoScaler, DegradeLevel,
                                     ScalePolicy, Signals, SpawnFailed)
from dalle_pytorch_tpu.serve.remote import _wait_ready
from dalle_pytorch_tpu.serve.router import _SHED_FACTORS

# ---------------------------------------------------------------------------
# stubs: the autoscaler's full observation/actuation surface, no fleet


class StubServer:
    def __init__(self, queued=None, running=0, num_slots=2,
                 headroom_bytes=None, pbpt=0, fingerprint=""):
        self.queued = dict(queued or {LATENCY: 0, THROUGHPUT: 0})
        self.running = running
        self.num_slots = num_slots
        self.headroom_bytes = headroom_bytes
        self.pbpt = pbpt
        self.fingerprint = fingerprint

    def backlog(self):
        return dict(queued=dict(self.queued),
                    queued_total=sum(self.queued.values()),
                    running=self.running)

    def scale_signals(self):
        return dict(queued=dict(self.queued), running=self.running,
                    num_slots=self.num_slots,
                    headroom_bytes=self.headroom_bytes,
                    predicted_bytes_per_token=self.pbpt,
                    ledger_fingerprint=self.fingerprint)


class StubReplica:
    def __init__(self, name, state=SERVING, num_slots=2, server=None):
        self.name = name
        self.state = state
        self.num_slots = num_slots
        self.server = server or StubServer(num_slots=num_slots)


class StubRouter:
    def __init__(self, replicas=(), factors=None):
        self._reps = list(replicas)
        self._factors = dict(_SHED_FACTORS)
        self._factors.update(factors or {})
        self.audit_state = dict(submitted=0, resolved_ok=0, resolved_err=0,
                                shed=0, outstanding=0, balanced=True)
        self.joined = []
        self.drained = []
        self.factor_calls = []

    def replicas(self):
        return list(self._reps)

    def shed_factors(self):
        return dict(self._factors)

    def set_shed_factors(self, factors=None):
        merged = dict(_SHED_FACTORS)
        merged.update(factors or {})
        self._factors = merged
        self.factor_calls.append(dict(factors) if factors else None)

    def audit(self):
        return dict(self.audit_state)

    def join(self, replica):
        self.joined.append(replica)
        self._reps.append(replica)

    def drain(self, name, **kw):
        self.drained.append(name)
        for r in self._reps:
            if r.name == name:
                r.state = DRAINING


def sig(lat=0, thr=0, **kw):
    kw.setdefault("serving", 1)
    return Signals(queued={LATENCY: lat, THROUGHPUT: thr}, **kw)


def mk(router=None, spawn_fn=None, **pol):
    return AutoScaler(router if router is not None else StubRouter(),
                      spawn_fn, policy=ScalePolicy(**pol))


# ---------------------------------------------------------------------------
# decision table: scaling with hysteresis


def test_scale_up_on_queue_depth():
    """demand 6 slots over 1x2 capacity at 0.75 utilization -> desired 4,
    stepped by max_step."""
    s = mk()
    d = s.decide(sig(lat=6, serving=1, slots_per_replica=2), now=0.0)
    assert d.action == "scale_up"
    assert d.target == 4
    assert d.step == 2              # max_step, not the whole gap at once
    assert d.level == DegradeLevel.HEALTHY
    assert "demand 6 slots" in d.reason


def test_hold_at_target():
    s = mk()
    d = s.decide(sig(lat=1, running=2, serving=2, slots_per_replica=2),
                 now=0.0)
    assert d.action == "hold" and d.reason == "at target"
    assert d.target == 2


def test_shed_delta_forces_scale_up_even_with_empty_queues():
    """Shedding means admission is already refusing work — empty queues
    do not excuse holding."""
    s = mk()
    d = s.decide(sig(serving=1, shed_delta=5), now=0.0)
    assert d.action == "scale_up" and d.target == 2 and d.step == 1
    assert "shed" in d.reason


def test_up_cooldown_gates_consecutive_scale_ups():
    s = mk(up_cooldown_s=1.0)
    over = sig(lat=10, serving=1)
    assert s.decide(over, now=0.0).action == "scale_up"
    d = s.decide(over, now=0.5)
    assert d.action == "hold" and d.reason == "up-cooldown"
    assert s.decide(over, now=1.5).action == "scale_up"


def test_max_replicas_clamps_and_flags_saturation():
    s = mk(max_replicas=4)
    d = s.decide(sig(lat=30, serving=4), now=0.0)
    assert d.action == "hold" and d.target == 4
    assert d.saturated


def test_joining_counts_as_capacity_on_the_way():
    """A spawned-but-warming replica already satisfies its share of
    desired — no double-spawn while the first join warms."""
    s = mk(up_cooldown_s=0.0)
    d = s.decide(sig(lat=3, serving=1, joining=1), now=0.0)
    assert d.action == "hold" and d.target == 2


def test_scale_down_needs_consecutive_below_evals_and_cooldown():
    s = mk(down_after=3, down_cooldown_s=6.0, up_cooldown_s=1.0)
    over = sig(lat=10, serving=1)
    calm = sig(serving=3)
    assert s.decide(over, now=0.0).action == "scale_up"
    d1 = s.decide(calm, now=1.0)
    assert d1.action == "hold" and "below-target 1/3" in d1.reason
    d2 = s.decide(calm, now=2.0)
    assert d2.action == "hold" and "below-target 2/3" in d2.reason
    d3 = s.decide(calm, now=3.0)   # 3rd below eval, but only 3s since scale
    assert d3.action == "hold" and d3.reason == "down-cooldown"
    d4 = s.decide(calm, now=7.0)
    assert d4.action == "scale_down"
    assert d4.step == -2            # max_step bounds retirement too
    assert d4.target == 1


def test_scale_down_blocked_while_drain_in_flight():
    s = mk(down_after=1, down_cooldown_s=0.0)
    d = s.decide(sig(serving=3, draining=1), now=10.0)
    assert d.action == "hold" and d.reason == "drain already in flight"


def test_flap_damping_and_window_expiry():
    """An up->down reversal inside the window counts as a flap; at
    max_flaps further scaling HOLDS until the window drains."""
    s = mk(up_cooldown_s=0.0, down_cooldown_s=0.0, down_after=1,
           max_flaps=1, flap_window_s=30.0)
    over = sig(lat=6, serving=1)
    calm = sig(serving=2)
    assert s.decide(over, now=0.0).action == "scale_up"
    d = s.decide(calm, now=1.0)
    assert d.action == "scale_down" and d.flaps == 1   # the reversal
    d = s.decide(over, now=2.0)
    assert d.action == "hold" and "flap-damped" in d.reason
    # outside the window the old flip no longer damps; the scale-up goes
    # through (and, being itself a down->up reversal, starts a new count)
    d = s.decide(over, now=40.0)
    assert d.action == "scale_up" and d.flaps == 1


def test_min_replicas_floor():
    s = mk(min_replicas=2, down_after=1, down_cooldown_s=0.0)
    d = s.decide(sig(serving=2), now=0.0)
    assert d.action == "hold" and d.target == 2   # never below the floor


# ---------------------------------------------------------------------------
# ledger-cited affordability


def test_headroom_limits_scale_up_step():
    """headroom 4000 B at 1000 B/token x 2 slots affords 2 more
    replicas' worth... no: exactly 2 replicas total of the desired 4."""
    s = mk()
    d = s.decide(sig(lat=10, serving=1, headroom_bytes=4000,
                     predicted_bytes_per_token=1000), now=0.0)
    assert d.action == "scale_up"
    assert d.target == 3           # 1 + 4000 // (1000 * 2)
    assert d.step == 2


def test_headroom_exhausted_escalates_to_brownout():
    """No affordable replica at all -> hold, and persistent overload
    with nowhere to scale walks the brownout ladder instead."""
    s = mk(degrade_after=2)
    starved = sig(lat=10, serving=1, headroom_bytes=1500,
                  predicted_bytes_per_token=1000)
    d = s.decide(starved, now=0.0)
    assert d.action == "hold" and d.target == 1   # affordable == current
    d = s.decide(starved, now=1.0)
    assert d.action == "degrade"
    assert d.level == DegradeLevel.TIGHT_THROUGHPUT
    assert "headroom-limited" in d.reason


def test_unknown_headroom_skips_the_clamp():
    s = mk()
    d = s.decide(sig(lat=10, serving=1, headroom_bytes=None,
                     predicted_bytes_per_token=1000), now=0.0)
    assert d.action == "scale_up" and d.target == 4


# ---------------------------------------------------------------------------
# brownout ladder: every transition, both directions


def test_ladder_descends_rung_by_rung_when_saturated():
    s = mk(degrade_after=1, max_replicas=4)
    over = sig(lat=30, serving=4)
    walked = [s.decide(over, now=float(t)).level for t in range(3)]
    assert walked == [DegradeLevel.TIGHT_THROUGHPUT,
                      DegradeLevel.SHED_THROUGHPUT, DegradeLevel.SHED_LATENCY]
    assert list(DegradeLevel) == [DegradeLevel.HEALTHY] + walked
    # bottom rung: no further degradation, the decision falls through to
    # (saturated) scaling
    d = s.decide(over, now=3.0)
    assert d.action == "hold" and d.level == DegradeLevel.SHED_LATENCY
    assert d.saturated


def test_ladder_restores_in_reverse_and_outranks_scale_down():
    s = mk(degrade_after=1, restore_after=1, max_replicas=4,
           down_after=1, down_cooldown_s=0.0)
    over = sig(lat=30, serving=4)
    for t in range(3):
        s.decide(over, now=float(t))
    assert s.level == DegradeLevel.SHED_LATENCY
    calm = sig(serving=4)
    walked = []
    for t in range(3, 6):
        d = s.decide(calm, now=float(t))
        walked.append((d.action, d.level))
    assert walked == [
        ("restore", DegradeLevel.SHED_THROUGHPUT),
        ("restore", DegradeLevel.TIGHT_THROUGHPUT),
        ("restore", DegradeLevel.HEALTHY),
    ]
    # only once fully healthy does capacity start retiring
    d = s.decide(calm, now=6.0)
    assert d.action == "scale_down"


def test_restore_hysteresis_needs_consecutive_calm_evals():
    s = mk(degrade_after=1, restore_after=3, max_replicas=2,
           up_cooldown_s=0.0)
    sat = sig(lat=30, serving=2)     # at max and overloaded: saturated
    s.decide(sat, now=0.0)
    assert s.level == DegradeLevel.TIGHT_THROUGHPUT
    calm = sig(serving=2)
    assert s.decide(calm, now=1.0).action == "hold"   # calm 1/3
    assert s.decide(calm, now=2.0).action == "hold"   # calm 2/3
    # an overloaded blip — NOT saturated (room to scale), so it cannot
    # degrade further — still resets the calm streak
    blip = sig(lat=30, serving=1)
    assert s.decide(blip, now=3.0).action == "scale_up"
    assert s.decide(calm, now=4.0).action == "hold"
    assert s.decide(calm, now=5.0).action == "hold"
    d = s.decide(calm, now=6.0)
    assert d.action == "restore" and d.level == DegradeLevel.HEALTHY


def test_no_degradation_while_scale_up_has_room():
    """Overload with replicas still affordable scales, never degrades."""
    s = mk(degrade_after=1, up_cooldown_s=0.0)
    over = sig(lat=30, serving=1)
    for t in range(5):
        d = s.decide(over, now=float(t))
        assert d.level == DegradeLevel.HEALTHY
        assert d.action == "scale_up"


def test_decision_record_cites_signals_and_ledger():
    s = mk()
    d = s.decide(sig(lat=3, thr=2, serving=1, shed_delta=1,
                     headroom_bytes=10_000, predicted_bytes_per_token=100,
                     ledger_fingerprint="abc123def456"), now=0.0)
    rec = d.as_record()
    assert rec["ledger_fingerprint"] == "abc123def456"
    assert rec["queued_latency"] == 3 and rec["queued_throughput"] == 2
    assert rec["shed_delta"] == 1
    assert rec["predicted_bytes_per_token"] == 100
    assert rec["level_name"] == "HEALTHY"
    assert rec["action"] in ("hold", "scale_up", "scale_down",
                             "degrade", "restore")


# ---------------------------------------------------------------------------
# actuation onto a stub fleet


def test_apply_level_projects_factors_onto_the_router():
    reps = [StubReplica("a"), StubReplica("b", state=JOINING),
            StubReplica("c", state=DRAINING)]
    router = StubRouter(reps)
    s = AutoScaler(router, policy=ScalePolicy(tight_throughput_factor=1.0))

    s.apply_level(DegradeLevel.TIGHT_THROUGHPUT)
    assert router.shed_factors()[THROUGHPUT] == 1.0
    assert router.shed_factors()[LATENCY] == _SHED_FACTORS[LATENCY]

    s.apply_level(DegradeLevel.SHED_THROUGHPUT)
    assert router.shed_factors()[THROUGHPUT] == 0.0

    s.apply_level(DegradeLevel.SHED_LATENCY)
    assert router.shed_factors()[LATENCY] == 0.0
    assert router.shed_factors()[THROUGHPUT] == 0.0

    # full restore: defaults back — and idempotent
    s.apply_level(DegradeLevel.HEALTHY)
    s.apply_level(DegradeLevel.HEALTHY)
    assert router.shed_factors() == _SHED_FACTORS
    assert router.factor_calls == [
        {THROUGHPUT: 1.0}, {THROUGHPUT: 0.0},
        {THROUGHPUT: 0.0, LATENCY: 0.0}, None, None]
    assert s.level == DegradeLevel.HEALTHY


@pytest.mark.parametrize("factors,expect", [
    (None, DegradeLevel.HEALTHY),
    ({THROUGHPUT: 1.0}, DegradeLevel.TIGHT_THROUGHPUT),
    ({THROUGHPUT: 0.0}, DegradeLevel.SHED_THROUGHPUT),
    ({THROUGHPUT: 0.0, LATENCY: 0.0}, DegradeLevel.SHED_LATENCY),
])
def test_resync_infers_level_from_live_state(factors, expect):
    """The restart contract: a fresh autoscaler over an already-degraded
    fleet resumes the ladder from the router's own observable state."""
    router = StubRouter([StubReplica("a")], factors=factors)
    s = AutoScaler(router, policy=ScalePolicy())
    s.resync()
    assert s.level == expect


def test_resync_rebases_audit_deltas():
    router = StubRouter([StubReplica("a")])
    router.audit_state.update(submitted=10, shed=5)
    s = AutoScaler(router, policy=ScalePolicy())
    s.resync()
    signals = s.collect()
    assert signals.shed_delta == 0 and signals.submitted_delta == 0
    router.audit_state.update(submitted=13, shed=6)
    signals = s.collect()
    assert signals.shed_delta == 1 and signals.submitted_delta == 3


def test_collect_aggregates_fleet_signals():
    a = StubReplica("a", server=StubServer(
        queued={LATENCY: 2, THROUGHPUT: 1}, running=2,
        headroom_bytes=5000, pbpt=100, fingerprint="fp1"))
    b = StubReplica("b", num_slots=4, server=StubServer(
        queued={LATENCY: 1, THROUGHPUT: 0}, running=1,
        headroom_bytes=3000, pbpt=200, fingerprint="fp1"))
    router = StubRouter([a, b, StubReplica("c", state=JOINING),
                         StubReplica("d", state=DRAINING)])
    s = AutoScaler(router, policy=ScalePolicy())
    signals = s.collect()
    assert signals.queued == {LATENCY: 3, THROUGHPUT: 1}
    assert signals.running == 3
    assert signals.serving == 2 and signals.joining == 1
    assert signals.draining == 1
    assert signals.headroom_bytes == 3000          # fleet min
    assert signals.predicted_bytes_per_token == 200  # fleet max
    assert signals.ledger_fingerprint == "fp1"
    assert signals.slots_per_replica == 4


def test_collect_fingerprint_survives_serving_gap():
    """A decision taken while zero replicas are SERVING (mid-migration)
    must still cite the ledger row it scales for."""
    rep = StubReplica("a", server=StubServer(fingerprint="fp-live"))
    router = StubRouter([rep])
    s = AutoScaler(router, policy=ScalePolicy())
    assert s.collect().ledger_fingerprint == "fp-live"
    rep.state = DRAINING                   # nobody serving any more
    assert s.collect().ledger_fingerprint == "fp-live"


def test_scale_up_spawn_failures_backoff_and_budget():
    clock = [0.0]
    calls = []

    def bad_spawn(name):
        calls.append(name)
        raise SpawnFailed(f"{name} never ready", name=name, rc=None)

    router = StubRouter([StubReplica("a")])
    s = AutoScaler(router, bad_spawn,
                   policy=ScalePolicy(spawn_budget=2, spawn_backoff_s=0.5),
                   time_fn=lambda: clock[0])
    s._scale_up(1)                      # t=0: fail #1, backoff till 0.5
    assert s.spawn_failures == 1
    clock[0] = 0.1
    s._scale_up(1)                      # inside backoff: deferred, no call
    assert len(calls) == 1
    clock[0] = 1.0
    s._scale_up(1)                      # fail #2, backoff doubles (till 2.0)
    assert s.spawn_failures == 2
    clock[0] = 3.0
    s._scale_up(1)                      # fail #3 > budget 2: budget spent
    assert s.spawn_failures == 3
    clock[0] = 100.0
    s._scale_up(1)                      # budget spent: deferred forever
    assert len(calls) == 3
    assert router.joined == []


def test_scale_up_success_resets_failure_streak_and_joins():
    clock = [0.0]
    outcome = ["fail"]

    def spawn(name):
        if outcome[0] == "fail":
            raise SpawnFailed("boom", name=name, rc=7)
        return StubReplica(name, state=JOINING)

    router = StubRouter([StubReplica("a")])
    s = AutoScaler(router, spawn, policy=ScalePolicy(spawn_backoff_s=0.5),
                   time_fn=lambda: clock[0])
    s._scale_up(1)
    assert s.spawn_failures == 1
    outcome[0] = "ok"
    clock[0] = 1.0
    s._scale_up(1)
    assert len(router.joined) == 1
    assert s.spawned == router.joined
    assert s._spawn_fails == 0          # streak reset; lifetime count stays


def test_scale_up_born_into_brownout_joins_degraded():
    """A replica spawned during a brownout joins behind the router's shed
    factors, which the spawn leaves as the rung set them."""
    router = StubRouter([StubReplica("a")])
    s = AutoScaler(router, lambda name: StubReplica(name, state=JOINING),
                   policy=ScalePolicy())
    s.apply_level(DegradeLevel.SHED_THROUGHPUT)
    s._scale_up(1)
    assert [r.name for r in router.joined] == ["as1"]
    assert router.shed_factors()[THROUGHPUT] == 0.0
    assert s.level == DegradeLevel.SHED_THROUGHPUT


def test_scale_down_picks_lowest_backlog_and_keeps_floor():
    reps = [StubReplica("busy", server=StubServer(queued={LATENCY: 5,
                                                          THROUGHPUT: 0})),
            StubReplica("idle", server=StubServer()),
            StubReplica("mid", server=StubServer(queued={LATENCY: 2,
                                                         THROUGHPUT: 0}))]
    router = StubRouter(reps)
    s = AutoScaler(router, policy=ScalePolicy(min_replicas=1))
    s._scale_down(1)
    assert router.drained == ["idle"]   # lowest backlog goes first
    s._scale_down(5)                    # floor: never below min_replicas
    assert router.drained == ["idle", "mid"]
    assert "busy" not in router.drained  # the floor survivor is the busiest


# ---------------------------------------------------------------------------
# one full pass: decision emitted with gauges + telemetry record


def test_step_once_emits_decision_record_and_gauges(tmp_path):
    import json

    reg = obs_metrics.init()
    telemetry.init(tmp_path, run_id="as-test")
    try:
        rep = StubReplica("a", server=StubServer(
            queued={LATENCY: 6, THROUGHPUT: 0}, fingerprint="fp-row"))
        s = AutoScaler(StubRouter([rep]), policy=ScalePolicy())
        d = s.step_once()
        assert d.action == "scale_up"
        text = reg.render()
        assert "graft_autoscale_target" in text
        assert "graft_autoscale_level" in text
    finally:
        telemetry.shutdown()
        obs_metrics.shutdown()
    recs = [json.loads(line) for line in
            (tmp_path / "events.jsonl").read_text().splitlines()]
    decisions = [r for r in recs if r.get("kind") == "autoscale"
                 and r.get("name") == "decision"]
    assert decisions, recs
    rec = decisions[0]
    assert rec["action"] == "scale_up"
    assert rec["ledger_fingerprint"] == "fp-row"
    assert rec["queued_latency"] == 6


# ---------------------------------------------------------------------------
# the spawn-orphan regression (satellite bugfix)


def test_wait_ready_timeout_kills_and_reaps_child(tmp_path):
    """A spawn that never reaches the ready handshake must not leak an
    orphan: the child is killed AND reaped before the typed raise."""
    proc = subprocess.Popen([sys.executable, "-c",
                             "import time; time.sleep(60)"])
    ready = tmp_path / "never.ready.json"
    with pytest.raises(SpawnFailed, match="killed and reaped") as ei:
        _wait_ready(ready, proc, "stuck", timeout_s=0.3)
    assert ei.value.name == "stuck"
    assert ei.value.rc is None
    # reaped: poll() returns the exit status, no zombie left behind
    assert proc.poll() is not None


def test_wait_ready_child_exit_raises_typed_with_rc(tmp_path):
    proc = subprocess.Popen([sys.executable, "-c", "import sys; sys.exit(3)"])
    ready = tmp_path / "never.ready.json"
    with pytest.raises(SpawnFailed, match="exited rc=3") as ei:
        _wait_ready(ready, proc, "dead", timeout_s=30.0)
    assert ei.value.rc == 3


# ---------------------------------------------------------------------------
# alert rules: fire + cooldown


def _rule(name):
    matches = [r for r in alerts.DEFAULT_RULES if r.name == name]
    assert matches, f"rule {name} missing from DEFAULT_RULES"
    return matches[0]


def _decision_rec(mono, flaps=0, saturated=0):
    return {"kind": "autoscale", "name": "decision", "mono": mono,
            "flaps": flaps, "saturated": saturated, "seq": int(mono)}


def test_autoscale_flapping_alert_fires_and_cools_down():
    eng = alerts.AlertEngine(rules=(_rule("autoscale_flapping"),))
    # calm decisions never fire
    assert eng.observe(_decision_rec(1.0, flaps=0)) == []
    assert eng.observe(_decision_rec(2.0, flaps=2)) == []   # at limit, not over
    # a real thrash stamps the elevated count on every record: the
    # windowed mean crosses the budget within a couple of ticks
    assert eng.observe(_decision_rec(3.0, flaps=3)) == []   # diluted by calm
    fired = eng.observe(_decision_rec(4.0, flaps=4))
    assert len(fired) == 1
    assert "autoscale_flapping" in fired[0]["msg"]
    # sustained thrash: one alert per cooldown, not one per record
    assert eng.observe(_decision_rec(10.0, flaps=4)) == []
    assert eng.observe(_decision_rec(4.0 + 121.0, flaps=4)) != []


def test_saturated_at_max_alert_needs_sustained_saturation():
    eng = alerts.AlertEngine(rules=(_rule("saturated_at_max"),))
    assert eng.observe(_decision_rec(1.0, saturated=1)) == []   # 1/3 samples
    assert eng.observe(_decision_rec(2.0, saturated=1)) == []   # 2/3
    fired = eng.observe(_decision_rec(3.0, saturated=1))
    assert len(fired) == 1 and "saturated_at_max" in fired[0]["msg"]
    # a healthy fleet never fires it: mean over the window <= 0.5
    eng2 = alerts.AlertEngine(rules=(_rule("saturated_at_max"),))
    for t in range(1, 8):
        assert eng2.observe(_decision_rec(float(t), saturated=0)) == []
