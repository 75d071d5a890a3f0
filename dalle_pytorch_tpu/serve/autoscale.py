"""graftscale: ledger-driven fleet autoscaler + brownout ladder (§22).

The fleet (§17/§21) can lose replicas and migrate work, but its capacity
is static — overload is answered only by shedding.  :class:`AutoScaler`
closes the loop over a live :class:`~.router.FleetRouter` using signals
that all already exist:

* per-SLO-class queue depth (``GenerationServer.backlog()``, cached for
  remote replicas via the graftwire heartbeat),
* the router audit ledger's shed rate (delta between evaluations),
* per-replica HBM headroom (the serve-steady mem watermark), and
* the perf ledger's ``predicted_bytes_per_token`` — affordable capacity
  is ``headroom ÷ (predicted per-slot bytes × slots)``, so every
  scale-up decision **cites the ledger fingerprint**, not a guess.

Every evaluation produces one typed :class:`Decision` emitted to
telemetry (kind ``autoscale``/``decision``) naming the action, the
brownout level, and the full :class:`Signals` snapshot it was computed
from.  Actuation is the fleet's existing machinery: scale-up spawns via
a caller-supplied ``spawn_fn`` (``remote.spawn_replica``) and warm-joins
the hash ring; scale-down rides the drain/rc-74 grace path.  Hysteresis
— separate up/down cooldowns, a max step, and a reversal ("flap")
counter with damping — keeps oscillating load from thrashing the ring.

Between healthy and shed sits the **brownout ladder**: ordered,
reversible :class:`DegradeLevel` rungs applied fleet-wide when the fleet
is saturated at ``max_replicas`` (or headroom-limited) and overload
persists — tighten throughput-class admission, shed throughput
entirely, finally shed latency — and restored rung by rung, in reverse,
once the fleet is calm.  Every rung acts through
:meth:`FleetRouter.set_shed_factors`, so demoted classes fail FAST with a
typed :class:`~.router.ShedError` instead of timing out.

The autoscaler survives its own faults: a spawn that never reaches the
ready-file handshake raises a typed :class:`~.remote.SpawnFailed` (the
child is killed and reaped), failures back off exponentially and are
budget-bounded; and a restarted autoscaler recomputes its world — the
current brownout level included — from ``router.audit()``, the shed
factors, and replica states (:meth:`AutoScaler.resync`): NO state is
persisted anywhere.
"""
from __future__ import annotations

import collections
import dataclasses
import enum
import math
import threading
import time
from typing import Callable, Deque, Dict, List, Mapping, Optional

from ..obs import metrics as obs_metrics
from ..obs import telemetry
from ..utils import locks
from .remote import SpawnFailed
from .replica import DRAINING, JOINING, SERVING
from .scheduler import LATENCY, SLO_CLASSES, THROUGHPUT

__all__ = ["AutoScaler", "Decision", "DegradeLevel", "ScalePolicy",
           "Signals", "SpawnFailed"]


class DegradeLevel(enum.IntEnum):
    """The brownout ladder, mildest rung first.  Rungs are CUMULATIVE
    (level N implies every rung <= N) and strictly reversible — restore
    walks back one rung at a time with its own hysteresis."""

    HEALTHY = 0           # full service: normal admission
    TIGHT_THROUGHPUT = 1  # throughput admission bound 4.0x -> 1.0x slots
    SHED_THROUGHPUT = 2   # shed ALL throughput-class admissions
    SHED_LATENCY = 3      # shed latency too: the rung before falling over


@dataclasses.dataclass(frozen=True)
class Signals:
    """One observation of the fleet — everything a decision may cite.
    Pure data: the decision-table tests build these directly, the live
    loop fills them from the router + replica scale_signals()."""

    queued: Mapping[str, int]            # fleet queue depth per SLO class
    running: int = 0                     # occupied slots fleet-wide
    serving: int = 1                     # replicas in SERVING
    joining: int = 0                     # spawned, still warming
    draining: int = 0                    # retiring (capacity leaving)
    shed_delta: int = 0                  # sheds since last evaluation
    submitted_delta: int = 0             # submits since last evaluation
    headroom_bytes: Optional[int] = None  # min per-replica HBM headroom
    predicted_bytes_per_token: int = 0   # ledger per-slot byte stream
    ledger_fingerprint: str = ""         # the row the capacity math cites
    slots_per_replica: int = 2
    outstanding: int = 0                 # router futures not yet resolved

    @property
    def queued_total(self) -> int:
        return sum(self.queued.values())

    @property
    def demand_slots(self) -> int:
        """Slots the offered load wants RIGHT NOW: everything queued
        plus everything running."""
        return self.queued_total + self.running


@dataclasses.dataclass(frozen=True)
class ScalePolicy:
    """The control law's knobs.  Defaults are the CI chaos-gate shape;
    production tunes cooldowns up by an order of magnitude."""

    min_replicas: int = 1
    max_replicas: int = 4
    # desired = ceil(demand_slots / (slots_per_replica * utilization)):
    # aim to run replicas at 75% so one replica's death has somewhere
    # to migrate to
    target_utilization: float = 0.75
    up_cooldown_s: float = 1.0         # min gap between scale-ups
    down_cooldown_s: float = 6.0       # min gap before ANY scale-down
    down_after: int = 3                # consecutive below-evals required
    max_step: int = 2                  # replicas added/retired per decision
    flap_window_s: float = 30.0        # reversal-counting window
    max_flaps: int = 2                 # reversals tolerated before damping
    degrade_after: int = 2             # overloaded evals before a new rung
    restore_after: int = 3             # calm evals before stepping back
    tight_throughput_factor: float = 1.0  # rung-1 throughput shed factor
    spawn_budget: int = 3              # consecutive SpawnFailed tolerated
    spawn_backoff_s: float = 0.5       # base backoff after a SpawnFailed


@dataclasses.dataclass(frozen=True)
class Decision:
    """One evaluation's typed outcome.  ``as_record()`` is the telemetry
    payload — flat, with every input signal and the ledger fingerprint,
    so the merged fleet stream can replay WHY each action happened."""

    action: str               # hold | scale_up | scale_down | degrade | restore
    target: int               # desired replica count (post-clamp)
    step: int                 # replicas to add (+) / retire (-) now
    level: DegradeLevel       # brownout level AFTER this decision
    reason: str
    saturated: bool           # pinned at max_replicas and still overloaded
    flaps: int                # reversals inside the flap window
    signals: Signals

    def as_record(self) -> dict:
        s = self.signals
        return dict(
            action=self.action, target=self.target, step=self.step,
            level=int(self.level), level_name=self.level.name,
            reason=self.reason, saturated=int(self.saturated),
            flaps=self.flaps,
            queued_latency=s.queued.get(LATENCY, 0),
            queued_throughput=s.queued.get(THROUGHPUT, 0),
            running=s.running, serving=s.serving, joining=s.joining,
            draining=s.draining, shed_delta=s.shed_delta,
            submitted_delta=s.submitted_delta,
            headroom_bytes=s.headroom_bytes,
            predicted_bytes_per_token=s.predicted_bytes_per_token,
            ledger_fingerprint=s.ledger_fingerprint,
            slots_per_replica=s.slots_per_replica,
            outstanding=s.outstanding)


class AutoScaler:
    """The control loop.  ``decide()`` is the pure core (signals in,
    :class:`Decision` out, only scalar control state touched) — the
    decision-table tests drive it with hand-built :class:`Signals` and
    explicit clocks, no processes or sockets.  ``step_once()`` is one
    full pass (collect → decide → emit → actuate); ``start()`` runs it
    on a daemon thread every ``interval_s``."""

    def __init__(self, router, spawn_fn: Optional[Callable] = None, *,
                 policy: Optional[ScalePolicy] = None,
                 interval_s: float = 0.5, name_prefix: str = "as",
                 time_fn=time.monotonic):
        self.router = router
        self.spawn_fn = spawn_fn
        self.policy = policy or ScalePolicy()
        self.interval_s = float(interval_s)
        self.name_prefix = str(name_prefix)
        self._time = time_fn
        self._lock = locks.TracedLock("autoscale")
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # --- control state: ALL of it recomputable.  resync() re-derives
        # the brownout level from the router and re-bases the audit
        # deltas; nothing below ever touches disk (restart contract d).
        self._level = DegradeLevel.HEALTHY
        self._last_scale_at = float("-inf")
        self._last_dir = 0                      # +1 up / -1 down / 0 never
        self._flips: Deque[float] = collections.deque()
        self._below_evals = 0                   # consecutive desired<current
        self._overload_evals = 0
        self._calm_evals = 0
        self._last_audit = {"shed": 0, "submitted": 0}
        self._spawn_fails = 0
        self._spawn_ok_at = float("-inf")       # backoff gate
        self._spawn_seq = 0
        self._budget_spent = False
        self._last_fingerprint = ""             # survives serving gaps
        self.spawned: List = []                 # replicas this loop spawned
        self.decisions: List[Decision] = []
        self.spawn_failures = 0                 # lifetime SpawnFailed count

    # --- lifecycle ----------------------------------------------------------

    @property
    def level(self) -> DegradeLevel:
        with self._lock:
            return self._level

    def start(self) -> "AutoScaler":
        assert self._thread is None, "autoscaler already started"
        self.resync()
        self._thread = threading.Thread(target=self._loop,
                                        name="graftscale", daemon=True)
        self._thread.start()
        return self

    def close(self) -> None:
        self._stop_evt.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None

    def _loop(self) -> None:
        while not self._stop_evt.wait(self.interval_s):
            try:
                self.step_once()
            # graftlint: disable=EXC001 (the control loop must survive any single evaluation error; it is reported in-band as an autoscale event and the next tick retries)
            except Exception as e:
                telemetry.emit("autoscale", "loop_error", error=repr(e))

    def resync(self) -> None:
        """Recompute world state from the live router — the restart
        contract: a fresh autoscaler over an already-degraded fleet must
        resume the ladder where its predecessor left it, from nothing
        but the router's own observable state."""
        level = DegradeLevel.HEALTHY
        factors = self.router.shed_factors()
        if factors.get(LATENCY, 1.0) <= 0.0:
            level = DegradeLevel.SHED_LATENCY
        elif factors.get(THROUGHPUT, 0.0) <= 0.0:
            level = DegradeLevel.SHED_THROUGHPUT
        elif (factors.get(THROUGHPUT, 0.0)
              <= self.policy.tight_throughput_factor):
            level = DegradeLevel.TIGHT_THROUGHPUT
        a = self.router.audit()
        with self._lock:
            self._level = level
            self._last_audit = {"shed": a["shed"],
                                "submitted": a["submitted"]}
        telemetry.emit("autoscale", "resync", level=int(level),
                       level_name=level.name, shed=a["shed"],
                       submitted=a["submitted"],
                       outstanding=a["outstanding"])

    # --- observation --------------------------------------------------------

    def _replica_signals(self) -> List[dict]:
        out = []
        for r in self.router.replicas():
            if r.state != SERVING:
                continue
            scale_signals = getattr(r.server, "scale_signals", None)
            if scale_signals is None:
                continue
            out.append(scale_signals())
        return out

    def collect(self) -> Signals:
        """One fleet observation: replica states + cached scale signals
        + the audit ledger's deltas since the previous evaluation."""
        reps = self.router.replicas()
        serving = joining = draining = 0
        for r in reps:
            state = r.state
            if state == SERVING:
                serving += 1
            elif state == JOINING:
                joining += 1
            elif state == DRAINING:
                draining += 1
        queued = {slo: 0 for slo in SLO_CLASSES}
        running = 0
        headrooms: List[int] = []
        pbpt = 0
        fingerprint = ""
        for sig in self._replica_signals():
            for slo, n in sig.get("queued", {}).items():
                queued[slo] = queued.get(slo, 0) + int(n)
            running += int(sig.get("running", 0))
            if sig.get("headroom_bytes") is not None:
                headrooms.append(int(sig["headroom_bytes"]))
            pbpt = max(pbpt, int(sig.get("predicted_bytes_per_token", 0)))
            fingerprint = sig.get("ledger_fingerprint") or fingerprint
        audit = self.router.audit()
        with self._lock:
            shed_delta = audit["shed"] - self._last_audit["shed"]
            submitted_delta = (audit["submitted"]
                               - self._last_audit["submitted"])
            self._last_audit = {"shed": audit["shed"],
                                "submitted": audit["submitted"]}
            # the fingerprint is static per geometry: remember the last
            # live one so a decision taken in a no-serving-replica gap
            # (mid-migration) still cites the ledger row it scales for
            if fingerprint:
                self._last_fingerprint = fingerprint
            else:
                fingerprint = self._last_fingerprint
        return Signals(
            queued=queued, running=running, serving=serving,
            joining=joining, draining=draining,
            shed_delta=max(0, shed_delta),
            submitted_delta=max(0, submitted_delta),
            headroom_bytes=min(headrooms) if headrooms else None,
            predicted_bytes_per_token=pbpt,
            ledger_fingerprint=fingerprint,
            slots_per_replica=max((r.num_slots for r in reps), default=1),
            outstanding=audit["outstanding"])

    # --- the pure control law ----------------------------------------------

    def decide(self, signals: Signals, now: Optional[float] = None
               ) -> Decision:
        """Signals -> Decision.  Mutates only the scalar control state
        (cooldown clocks, flap window, rung counters) — never the fleet;
        :meth:`actuate` applies the returned decision."""
        now = self._time() if now is None else now
        p = self.policy
        with self._lock:
            decision = self._decide_locked(signals, now, p)
            self.decisions.append(decision)
        return decision

    def _decide_locked(self, s: Signals, now: float, p: ScalePolicy
                       ) -> Decision:
        spr = max(1, s.slots_per_replica)
        current = s.serving + s.joining   # capacity already on the way
        desired = max(1, math.ceil(
            s.demand_slots / (spr * p.target_utilization)))
        if s.shed_delta > 0:
            # shedding means admission is ALREADY refusing work: capacity
            # is short now regardless of what the queues sum to
            desired = max(desired, current + 1)
        want = desired                      # pre-clamp, for saturation
        desired = max(p.min_replicas, min(p.max_replicas, desired))

        # ledger-cited affordability: one more replica costs (per-slot
        # byte stream x slots) of headroom; unknown headroom (no
        # watermark yet / no device limit) skips the clamp
        headroom_limited = False
        if (desired > current and s.headroom_bytes is not None
                and s.predicted_bytes_per_token > 0):
            affordable = current + (s.headroom_bytes
                                    // (s.predicted_bytes_per_token * spr))
            if affordable < desired:
                headroom_limited = True
                desired = max(current, max(p.min_replicas, affordable))

        overloaded = (s.demand_slots > current * spr or s.shed_delta > 0)
        saturated = (overloaded and current >= p.max_replicas
                     and want > p.max_replicas)
        while self._flips and now - self._flips[0] > p.flap_window_s:
            self._flips.popleft()
        flaps = len(self._flips)

        # --- brownout ladder: rung transitions outrank scaling (undo
        # degradation before retiring capacity; degrade only when
        # scale-up has nowhere left to go)
        if (saturated or headroom_limited) and overloaded:
            self._overload_evals += 1
            self._calm_evals = 0
        elif not overloaded and s.shed_delta == 0 \
                and s.demand_slots <= current * spr:
            self._calm_evals += 1
            self._overload_evals = 0
        else:
            # overloaded but with somewhere to scale: not calm either —
            # an overload blip must reset the restore streak
            self._overload_evals = 0
            self._calm_evals = 0
        if (self._overload_evals >= p.degrade_after
                and self._level < DegradeLevel.SHED_LATENCY):
            self._level = DegradeLevel(self._level + 1)
            self._overload_evals = 0
            why = "headroom-limited" if headroom_limited else "saturated"
            return Decision(
                action="degrade", target=desired, step=0, level=self._level,
                reason=f"{why} at {current} replicas and still overloaded "
                       f"for {p.degrade_after} evals: brownout to "
                       f"{self._level.name}",
                saturated=saturated, flaps=flaps, signals=s)
        if (self._calm_evals >= p.restore_after
                and self._level > DegradeLevel.HEALTHY):
            self._level = DegradeLevel(self._level - 1)
            self._calm_evals = 0
            return Decision(
                action="restore", target=desired, step=0, level=self._level,
                reason=f"calm for {p.restore_after} evals: restore to "
                       f"{self._level.name}",
                saturated=saturated, flaps=flaps, signals=s)

        # --- scaling with hysteresis
        if desired > current:
            self._below_evals = 0
            if flaps >= p.max_flaps:
                return self._hold(s, desired, saturated, flaps,
                                  "flap-damped: "
                                  f"{flaps} reversals inside "
                                  f"{p.flap_window_s:g}s")
            if now - self._last_scale_at < p.up_cooldown_s:
                return self._hold(s, desired, saturated, flaps,
                                  "up-cooldown")
            step = min(desired - current, p.max_step)
            self._note_scale(now, +1)
            return Decision(
                action="scale_up", target=desired, step=step,
                level=self._level,
                reason=f"demand {s.demand_slots} slots > "
                       f"{current}x{spr} capacity"
                       + (f" (+{s.shed_delta} shed)" if s.shed_delta
                          else ""),
                saturated=saturated, flaps=len(self._flips), signals=s)
        if desired < current:
            self._below_evals += 1
            if flaps >= p.max_flaps:
                return self._hold(s, desired, saturated, flaps,
                                  "flap-damped: "
                                  f"{flaps} reversals inside "
                                  f"{p.flap_window_s:g}s")
            if self._below_evals < p.down_after:
                return self._hold(s, desired, saturated, flaps,
                                  f"below-target {self._below_evals}/"
                                  f"{p.down_after} evals")
            if now - self._last_scale_at < p.down_cooldown_s:
                return self._hold(s, desired, saturated, flaps,
                                  "down-cooldown")
            if s.draining > 0:
                return self._hold(s, desired, saturated, flaps,
                                  "drain already in flight")
            step = -min(current - desired, p.max_step)
            self._note_scale(now, -1)
            self._below_evals = 0
            return Decision(
                action="scale_down", target=desired, step=step,
                level=self._level,
                reason=f"demand {s.demand_slots} slots <= "
                       f"{desired}x{spr} capacity at "
                       f"{p.target_utilization:g} utilization",
                saturated=saturated, flaps=len(self._flips), signals=s)
        self._below_evals = 0
        return self._hold(s, desired, saturated, flaps, "at target")

    def _hold(self, s: Signals, target: int, saturated: bool, flaps: int,
              reason: str) -> Decision:
        return Decision(action="hold", target=target, step=0,
                        level=self._level, reason=reason,
                        saturated=saturated, flaps=flaps, signals=s)

    def _note_scale(self, now: float, direction: int) -> None:
        if self._last_dir != 0 and direction == -self._last_dir:
            self._flips.append(now)  # graftrace: unguarded (called only from _decide_locked, which always runs under the autoscale lock)
        self._last_dir = direction
        self._last_scale_at = now

    # --- actuation ----------------------------------------------------------

    def step_once(self) -> Decision:
        signals = self.collect()
        decision = self.decide(signals)
        self._emit_decision(decision)
        self.actuate(decision)
        return decision

    def _emit_decision(self, d: Decision) -> None:
        telemetry.emit("autoscale", "decision", **d.as_record())
        reg = obs_metrics.active()
        if reg is not None:
            reg.gauge("graft_autoscale_target",
                      "replica count the control law wants").set(d.target)
            reg.gauge("graft_autoscale_level",
                      "brownout ladder rung (0=healthy)").set(int(d.level))
            reg.gauge("graft_autoscale_flaps",
                      "scale-direction reversals in the flap window"
                      ).set(d.flaps)

    def actuate(self, decision: Decision) -> None:
        """Apply one decision to the fleet.  Runs OUTSIDE the control
        lock: spawn blocks on the ready handshake and drain/join take
        the router's lock."""
        if decision.action == "scale_up" and decision.step > 0:
            self._scale_up(decision.step)
        elif decision.action == "scale_down" and decision.step < 0:
            self._scale_down(-decision.step)
        elif decision.action in ("degrade", "restore"):
            self.apply_level(decision.level)

    def _next_name(self) -> str:
        taken = {r.name for r in self.router.replicas()}
        while True:
            with self._lock:
                self._spawn_seq += 1
                name = f"{self.name_prefix}{self._spawn_seq}"
            if name not in taken:
                return name

    def _scale_up(self, count: int) -> None:
        if self.spawn_fn is None:
            return
        p = self.policy
        for _ in range(count):
            now = self._time()
            with self._lock:
                blocked = self._budget_spent or now < self._spawn_ok_at
                budget_spent, fails = self._budget_spent, self._spawn_fails
            if blocked:
                telemetry.emit("autoscale", "spawn_deferred",
                               budget_spent=budget_spent, fails=fails)
                return
            name = self._next_name()
            try:
                replica = self.spawn_fn(name)
            except SpawnFailed as e:
                with self._lock:
                    self._spawn_fails += 1
                    self.spawn_failures += 1
                    fails = self._spawn_fails
                    self._spawn_ok_at = now + p.spawn_backoff_s * (
                        2 ** (fails - 1))
                    if fails > p.spawn_budget:
                        self._budget_spent = True
                telemetry.emit("autoscale", "spawn_failed", replica=name,
                               fails=fails, budget=p.spawn_budget,
                               budget_spent=fails > p.spawn_budget,
                               error=repr(e))
                reg = obs_metrics.active()
                if reg is not None:
                    reg.counter("graft_autoscale_spawn_failures_total",
                                "spawns that never reached ready").inc()
                return
            with self._lock:
                self._spawn_fails = 0
            self.router.join(replica)
            self.spawned.append(replica)
            telemetry.emit("autoscale", "spawned", replica=name)

    def _scale_down(self, count: int) -> None:
        victims = sorted(
            (r for r in self.router.replicas() if r.state == SERVING),
            key=lambda r: (r.server.backlog()["queued_total"], r.name),
        )[:count]
        keep = self.policy.min_replicas
        serving = sum(1 for r in self.router.replicas()
                      if r.state == SERVING)
        for r in victims:
            if serving <= keep:
                return
            serving -= 1
            self.router.drain(r.name, reason="autoscale scale-down")
            telemetry.emit("autoscale", "retired", replica=r.name)

    def apply_level(self, level: DegradeLevel) -> None:
        """Project one ladder rung onto the fleet.  Idempotent: the full
        factor state is recomputed from the rung, so re-applying (or
        applying after a resync) converges."""
        level = DegradeLevel(level)
        factors: Dict[str, float] = {}
        if level >= DegradeLevel.TIGHT_THROUGHPUT:
            factors[THROUGHPUT] = self.policy.tight_throughput_factor
        if level >= DegradeLevel.SHED_THROUGHPUT:
            factors[THROUGHPUT] = 0.0
        if level >= DegradeLevel.SHED_LATENCY:
            factors[LATENCY] = 0.0
        self.router.set_shed_factors(factors or None)
        with self._lock:
            self._level = level
        telemetry.emit("autoscale", "level_applied", level=int(level),
                       level_name=level.name, factors=factors or None)
