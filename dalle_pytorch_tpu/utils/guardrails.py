"""Training health guardrails: sentinels, anomaly policy, rollback, watchdog.

PR 3 made the trainers survive *loud* failures (kills, torn writes, bad
samples); this layer closes the *silent* ones a preemptible-pod run
actually dies from:

* a NaN/Inf gradient that poisons the optimizer state thousands of steps
  before anyone looks at a curve — caught **on device** by a per-step
  health vector (loss, global grad norm, finite flag, all computed inside
  the jitted step: no host sync in traced code) with the update suppressed
  by ``jnp.where`` masking (``optax.apply_if_finite``-style) so
  params/opt_state are never touched by a non-finite step;
* a loss spike or sustained divergence from pathological data — classified
  host-side by :class:`HealthMonitor` (rolling median + MAD robust
  z-score) and escalated: warn → (the device already skipped non-finite
  steps) → roll back to ``CheckpointManager.latest_valid()`` with the
  offending data window skipped and the LR backed off
  (:class:`RollbackAndSkip` caught by :func:`run_with_rollback`) → abort
  with ``ExitCode.ROLLBACK_BUDGET`` once the rollback budget is spent.
  Every escalation drops an atomic-rename **anomaly bundle**
  (``anomaly-{step:08d}/report.json``) for post-mortem;
* a wedged device call that hangs the step loop forever (it raises no
  exception, so nothing in the loop can catch it) — bounded by
  :class:`StepWatchdog`, a monotonic-clock thread armed around each step
  that dumps all-thread stacks and exits with ``ExitCode.WEDGED`` so a
  supervisor (``tools/monitor.py --restart-cmd``) relaunches with
  ``--resume auto``.

Decision consistency: the health vector is an output of the one SPMD step
program, so under dp/fsdp/tp/pp every host reads identical values and the
skip/rollback decisions agree by construction (the same reasoning as
``GracefulShutdown.average_and_poll``).  Where a value is genuinely
per-shard — the sequence-parallel local loss inside ``shard_map`` —
:func:`collective_all_finite` combines the finite flags with
``lax.pmin`` over the mesh axes so all shards agree before any of them
decides to skip.

Chaos rehearsal (``GRAFT_FAULTS``, utils/faults.py): ``grad_nan:at_step=N``
and ``loss_spike:at_step=N`` drive :func:`fault_scale_for`, a traced
loss-scale input of the health-enabled train steps (``nan`` poisons the
real gradients on device; a large finite factor produces a genuine spike
whose update *does* land — exactly the state a rollback must discard);
``step_hang:at_step=N`` (``faults.maybe_hang``) wedges the step loop so
the watchdog's kill path is rehearsed end to end.  The suites:
tests/test_guardrails.py, tests/test_anomaly_resume.py.
"""
from __future__ import annotations

import collections
import json
import math
import os
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import optax

from . import faults
from ..obs import telemetry
from .failure import ExitCode

# observed loss multiplier for the loss_spike faultpoint: big enough that
# any sane spike_zscore fires, small enough that f32 grads stay finite
# (a non-finite "spike" would be caught by the sentinel instead, which is
# a different path than the one this fault exists to rehearse)
SPIKE_SCALE = 1e4

# --- device side: computed inside the jitted step (no host syncs) --------


def collective_all_finite(value, axis_names):
    """Inside a ``shard_map``/``pmap`` body: True iff every element of
    ``value`` is finite on EVERY shard of the given mesh axes.  The local
    flags are ``lax.pmin``-combined so all shards return the same answer —
    a skip decision must be collective or shards diverge (the same
    reasoning as ``GracefulShutdown.average_and_poll``)."""
    ok = jnp.all(jnp.isfinite(value)).astype(jnp.float32)
    for ax in axis_names:
        ok = jax.lax.pmin(ok, ax)
    return ok > 0


def guarded_update(tx, grads, opt_state, params, *, loss=None,
                   extra_ok=None, guard=True):
    """Optimizer update with a non-finite sentinel, traced-code safe.

    Computes the global grad norm and a finite flag (``isfinite(norm)``
    catches a NaN/Inf in any leaf — both propagate through the norm; a
    non-finite ``loss`` also trips it, as does ``extra_ok=False`` from a
    collective per-shard check).  When ``guard`` and the flag is down, the
    returned params/opt_state are the *inputs*, element-selected by
    ``jnp.where`` — apply_if_finite-style masking, so a poisoned step
    leaves the training state bitwise untouched (the skipped step does not
    advance the Adam count either).  Returns ``(params, opt_state,
    health)`` where ``health`` is a dict of f32 device scalars:
    ``loss``, ``grad_norm``, ``applied`` (1.0 applied / 0.0 skipped).
    """
    gnorm = optax.global_norm(grads)
    ok = jnp.isfinite(gnorm)
    if loss is not None:
        ok = jnp.logical_and(ok, jnp.isfinite(loss))
    if extra_ok is not None:
        ok = jnp.logical_and(ok, extra_ok)
    updates, new_opt = tx.update(grads, opt_state, params)
    new_params = optax.apply_updates(params, updates)
    if guard:
        new_params = jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                                  new_params, params)
        new_opt = jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                               new_opt, opt_state)
    health = {"loss": (jnp.asarray(loss, jnp.float32)
                       if loss is not None else jnp.float32(0.0)),
              "grad_norm": jnp.asarray(gnorm, jnp.float32),
              "applied": ok.astype(jnp.float32)}
    return new_params, new_opt, health


# --- host side: fault ports, anomaly policy, rollback, watchdog ----------


def fault_scale_for(step: int) -> float:
    """The loss-scale injection port for the health-enabled train steps:
    1.0 normally; NaN when ``grad_nan:at_step=step`` fires (the whole
    gradient tree goes non-finite on device — the sentinel must mask the
    update); :data:`SPIKE_SCALE` when ``loss_spike:at_step=step`` fires (a
    genuine finite spike whose poisoned update LANDS — the state a
    rollback must discard).  A plain float: it enters the step as a traced
    scalar argument, so injection never retraces."""
    if "at_step" in faults.fire("grad_nan", step=step):
        return float("nan")
    if "at_step" in faults.fire("loss_spike", step=step):
        return SPIKE_SCALE
    return 1.0


class RollbackAndSkip(Exception):
    """Raised by a trainer's step loop when the anomaly policy escalates:
    caught by :func:`run_with_rollback`, which relaunches the run with
    ``--resume auto`` (→ ``CheckpointManager.latest_valid()``), the data
    window up to ``step`` skipped, and the LR multiplied by
    ``lr_backoff``."""

    def __init__(self, step: int, max_rollbacks: int = 3,
                 lr_backoff: float = 0.5, reason: str = "anomaly"):
        super().__init__(f"rollback requested at step {step} ({reason})")
        self.step = int(step)
        self.max_rollbacks = int(max_rollbacks)
        self.lr_backoff = float(lr_backoff)
        self.reason = reason


def argv_with_resume_auto(argv, drop=("--resume", "--dalle_path",
                                      "--resume_path")):
    """Rebuild a trainer argv for a rollback relaunch: strip any explicit
    checkpoint/resume selection (they are mutually exclusive with
    ``--resume auto`` and would pin the run to a *pre*-rollback
    checkpoint) and append ``--resume auto``."""
    out = []
    skip_value = False
    for a in argv:
        if skip_value:
            skip_value = False
            continue
        if a in drop:
            skip_value = True
            continue
        if any(a.startswith(d + "=") for d in drop):
            continue
        out.append(a)
    return out + ["--resume", "auto"]


def run_with_rollback(run_fn, argv):
    """The rollback-and-skip escalation loop shared by both trainers.

    ``run_fn(argv, lr_scale=..., skip_past=...)`` is the real trainer main
    body; a :class:`RollbackAndSkip` escape relaunches it with ``--resume
    auto`` (latest valid managed checkpoint), the anomalous data window
    skipped, and a compounding LR backoff.  The budget rides in the
    exception (from the trainer's ``--max_rollbacks``); exhausting it
    exits with the documented ``ExitCode.ROLLBACK_BUDGET`` so supervisors
    know a relaunch will NOT help — this needs a human."""
    rollbacks = 0
    lr_scale = 1.0
    skip_past = None
    while True:
        try:
            return run_fn(argv, lr_scale=lr_scale, skip_past=skip_past)
        except RollbackAndSkip as rb:
            rollbacks += 1
            if rollbacks > rb.max_rollbacks:
                telemetry.note(
                    "health", "rollback_budget",
                    f"rollback budget exhausted ({rb.max_rollbacks}): "
                    f"aborting with exit code "
                    f"{int(ExitCode.ROLLBACK_BUDGET)} — automatic recovery "
                    "will not converge, a human must look at the anomaly "
                    "bundles", prefix="[guardrails]", step=rb.step)
                sys.exit(int(ExitCode.ROLLBACK_BUDGET))
            lr_scale *= rb.lr_backoff
            skip_past = rb.step
            argv = argv_with_resume_auto(argv)
            telemetry.note(
                "health", "rollback",
                f"rollback {rollbacks}/{rb.max_rollbacks} ({rb.reason} at "
                f"step {rb.step}): relaunching with --resume auto, skipping "
                f"data through step {rb.step}, lr x{lr_scale:g}",
                prefix="[guardrails]", step=rb.step, reason=rb.reason,
                rollbacks=rollbacks, lr_scale=lr_scale)


class HealthMonitor:
    """Host-side anomaly policy over the per-step health vectors.

    Keeps a rolling window of recent finite losses and classifies each
    observed step with a robust z-score — ``|loss - median| / (1.4826 *
    MAD)`` — plus an EMA trend for sustained divergence.  Median/MAD
    instead of mean/std because the statistic must survive the very
    outliers it exists to flag.  Verdicts: ``ok``, ``nonfinite`` (the
    device sentinel already skipped the update), ``spike`` (finite but
    z > ``spike_zscore``), ``diverged`` (EMA above ``divergence_factor``
    x its best for ``patience`` consecutive observations).

    ``mode`` maps verdicts to actions: ``warn`` logs only; ``skip`` logs
    and relies on the on-device masking; ``rollback`` additionally sets
    :attr:`wants_rollback` on spike / divergence / a ``nonfinite_patience``
    streak of skipped steps (one bad batch is masked for free — a *streak*
    means the data or the state is wrong and replay-from-checkpoint is the
    fix)."""

    def __init__(self, mode: str = "skip", spike_zscore: float = 8.0,
                 window: int = 64, warmup: int = 12,
                 nonfinite_patience: int = 3, patience: int = 5,
                 divergence_factor: float = 2.0, ema_alpha: float = 0.05):
        assert mode in ("warn", "skip", "rollback"), mode
        self.mode = mode
        self.spike_zscore = float(spike_zscore)
        self.warmup = int(warmup)
        self.nonfinite_patience = int(nonfinite_patience)
        self.patience = int(patience)
        self.divergence_factor = float(divergence_factor)
        self.ema_alpha = float(ema_alpha)
        self._losses = collections.deque(maxlen=int(window))
        self._ema = None
        self._best_ema = math.inf
        self._bad_trend = 0
        self._nonfinite_run = 0
        self.last_verdict = "ok"
        self.last_loss = None
        self.last_grad_norm = None
        self.last_step = None
        self.counts = collections.Counter()
        self.wants_rollback = False
        self.rollback_reason = None

    # -- statistics --

    def _zscore(self, loss: float) -> Optional[float]:
        if len(self._losses) < self.warmup:
            return None
        ordered = sorted(self._losses)
        median = ordered[len(ordered) // 2]
        mad = sorted(abs(v - median) for v in ordered)[len(ordered) // 2]
        # relative floor: a degenerate window (near-identical losses, MAD
        # ~ 0) must not turn a 0.1% wiggle into an infinite z-score — the
        # spike gate is for order-of-magnitude outliers, not float noise
        scale = max(1.4826 * mad, 1e-3 * abs(median), 1e-12)
        return abs(loss - median) / scale

    # -- observation --

    def observe(self, step: int, loss: float, grad_norm: float,
                applied: float) -> str:
        """Classify one step's health vector; returns the verdict and
        updates :attr:`wants_rollback` per the mode's policy."""
        self.last_step = int(step)
        self.last_loss = float(loss)
        self.last_grad_norm = float(grad_norm)
        verdict = "ok"
        if applied < 0.5 or not math.isfinite(loss):
            verdict = "nonfinite"
            self._nonfinite_run += 1
        else:
            self._nonfinite_run = 0
            z = self._zscore(loss)
            if z is not None and z > self.spike_zscore:
                verdict = "spike"
            else:
                # only sane losses feed the rolling statistic — a spike
                # must not drag the window toward itself
                self._losses.append(loss)
                self._ema = (loss if self._ema is None else
                             self.ema_alpha * loss
                             + (1 - self.ema_alpha) * self._ema)
                self._best_ema = min(self._best_ema, self._ema)
                if (len(self._losses) >= self.warmup and self._ema
                        > self.divergence_factor * self._best_ema):
                    self._bad_trend += 1
                    if self._bad_trend >= self.patience:
                        verdict = "diverged"
                else:
                    self._bad_trend = 0
        self.counts[verdict] += 1
        self.last_verdict = verdict
        if verdict != "ok":
            detail = {"nonfinite": "update skipped by the on-device "
                                   "sentinel (params/opt_state untouched)",
                      "spike": f"robust z > {self.spike_zscore:g}",
                      "diverged": f"loss EMA > {self.divergence_factor:g}x "
                                  "its best"}[verdict]
            telemetry.note(
                "health", verdict,
                f"step {step}: {verdict} — loss {loss:.6g} "
                f"grad_norm {grad_norm:.6g} ({detail})",
                prefix="[guardrails]", step=int(step), loss=float(loss),
                grad_norm=float(grad_norm))
        if self.mode == "rollback" and not self.wants_rollback:
            if verdict in ("spike", "diverged"):
                self.wants_rollback = True
                self.rollback_reason = verdict
            elif self._nonfinite_run >= self.nonfinite_patience:
                self.wants_rollback = True
                self.rollback_reason = (
                    f"{self._nonfinite_run} consecutive non-finite steps")
            if self.wants_rollback:
                telemetry.emit("health", "rollback_wanted", step=int(step),
                               reason=self.rollback_reason)
        return verdict

    # -- consumers --

    def beat_extras(self) -> dict:
        """Health fields for ``Heartbeat.beat(**extra)`` so an external
        monitor sees sickness without reading logs."""
        out = {"health_state": self.last_verdict}
        if self.last_loss is not None:
            out["loss"] = self.last_loss
        if self.last_grad_norm is not None:
            out["grad_norm"] = self.last_grad_norm
        return out

    def history(self) -> list:
        return list(self._losses)


def write_anomaly_bundle(directory, step: int, report: dict) -> Path:
    """Post-mortem record of an escalation: ``anomaly-{step:08d}/`` with a
    ``report.json`` (loss history, batch window, rng, config fingerprint —
    whatever the trainer hands over), published by atomic directory rename
    so a crash mid-write can never leave a half-bundle that looks whole.
    Idempotent per step (a collective escalation writes once)."""
    directory = Path(directory)
    final = directory / f"anomaly-{int(step):08d}"
    if final.exists():
        return final
    directory.mkdir(parents=True, exist_ok=True)
    tmp = Path(tempfile.mkdtemp(dir=directory, prefix=".anomaly-"))
    try:
        with open(tmp / "report.json", "w") as f:
            json.dump(dict(report, step=int(step), time=time.time()), f,
                      indent=1, default=str)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, final)
    except BaseException:
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)
        raise
    telemetry.note("health", "anomaly_bundle",
                   f"anomaly bundle written to {final}",
                   prefix="[guardrails]", step=int(step), path=str(final))
    return final


class StepWatchdog:
    """Hung-step watchdog: a monotonic-clock thread armed around each
    device step.  A wedged device call raises no exception — the loop just
    never returns (DESIGN.md §6) — so past the deadline the watchdog dumps
    every thread's stack (the post-mortem: *where* it wedged) and exits
    the process with ``ExitCode.WEDGED``, which the supervisors treat as
    restart-with-resume.

    The first :meth:`arm` call is a free pass: step 1 includes the XLA
    compile (minutes at real sizes), which must not read as a wedge —
    the same reasoning as ``Heartbeat``'s None-until-first-beat.  Exit is
    ``os._exit`` because the main thread is, by definition, stuck inside
    a call that will never return; ``on_expire`` exists for tests."""

    def __init__(self, deadline: float, on_expire=None,
                 poll: Optional[float] = None):
        self.deadline = float(deadline)
        self._on_expire = on_expire
        self._armed_at: Optional[float] = None
        self._step: Optional[int] = None
        self._first_pass = True
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._watch, name="step-watchdog", daemon=True)
        self._poll = poll if poll is not None else min(self.deadline / 4, 1.0)
        self._thread.start()

    def arm(self, step: int) -> None:
        if self._first_pass:  # step 1 == XLA compile, not a wedge
            self._first_pass = False
            return
        self._step = int(step)
        self._armed_at = time.monotonic()

    def disarm(self) -> None:
        self._armed_at = None

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5.0)

    def _watch(self) -> None:
        while not self._stop.wait(max(self._poll, 0.01)):
            armed_at = self._armed_at
            if armed_at is None:
                continue
            age = time.monotonic() - armed_at
            if age > self.deadline:
                self._expire(age)
                return

    def _expire(self, age: float) -> None:
        # emitted (and os.write-flushed) BEFORE the stack dump + _exit, so
        # the stream's last record names the wedged step
        telemetry.note(
            "health", "watchdog_expired",
            f"hung step: step {self._step} exceeded the "
            f"{self.deadline:g}s deadline ({age:.0f}s) — a wedged device "
            f"call or collective.  Dumping all thread stacks and exiting "
            f"{int(ExitCode.WEDGED)} (supervisors relaunch with "
            "--resume auto).", prefix="[guardrails]", step=self._step,
            age_s=age, deadline_s=self.deadline)
        if self._on_expire is not None:
            self._on_expire()
            return
        import faulthandler

        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        sys.stderr.flush()
        os._exit(int(ExitCode.WEDGED))
