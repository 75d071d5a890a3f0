"""Training-health guardrails (utils/guardrails.py): unit layer.

Covers each piece of the detection→recovery ladder in isolation — the
on-device sentinel (guarded_update masking, collective finite flags), the
host-side anomaly policy (HealthMonitor verdicts and escalation), the
rollback plumbing (run_with_rollback, argv rewriting, anomaly bundles),
and the hung-step watchdog.  The end-to-end chaos paths (fault-injected
trainer runs) live in tests/test_anomaly_resume.py; the cross-strategy
sentinel equivalence (dp/sp/pp) in tests/test_parallel_training.py.
"""
from __future__ import annotations

import json
import math
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map

from dalle_pytorch_tpu.parallel.mesh import make_mesh
from dalle_pytorch_tpu.utils import faults, guardrails
from dalle_pytorch_tpu.utils.failure import ExitCode
from dalle_pytorch_tpu.utils.guardrails import (HealthMonitor, RollbackAndSkip,
                                                StepWatchdog,
                                                argv_with_resume_auto,
                                                collective_all_finite,
                                                fault_scale_for,
                                                guarded_update,
                                                run_with_rollback,
                                                write_anomaly_bundle)

P = jax.sharding.PartitionSpec


# --- on-device sentinel ---------------------------------------------------


def _tiny_problem():
    params = {"w": jnp.arange(4.0), "b": jnp.ones((2,))}
    tx = optax.adam(1e-2)
    opt = tx.init(params)
    grads = {"w": jnp.full((4,), 0.5), "b": jnp.full((2,), -0.25)}
    return params, tx, opt, grads


def _bitwise_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_guarded_update_applies_finite_step():
    params, tx, opt, grads = _tiny_problem()
    new_p, new_o, hv = jax.jit(
        lambda g, o, p: guarded_update(tx, g, o, p, loss=jnp.float32(1.5))
    )(grads, opt, params)
    assert float(hv["applied"]) == 1.0
    assert float(hv["loss"]) == 1.5
    assert np.isclose(float(hv["grad_norm"]),
                      float(optax.global_norm(grads)))
    assert not _bitwise_equal(params, new_p)
    # the optimizer really advanced (Adam step count is 1)
    assert int(jax.tree.leaves(new_o)[0]) == 1 or not _bitwise_equal(opt,
                                                                     new_o)


@pytest.mark.parametrize("poison", ["nan_grad", "inf_grad", "nan_loss"])
def test_guarded_update_masks_nonfinite(poison):
    """A NaN/Inf anywhere in the gradient tree — or a non-finite loss with
    finite grads — leaves params AND opt_state bitwise untouched (the
    Adam count does not advance either: a skipped step never happened)."""
    params, tx, opt, grads = _tiny_problem()
    loss = jnp.float32(1.5)
    if poison == "nan_grad":
        grads = dict(grads, w=grads["w"].at[2].set(jnp.nan))
    elif poison == "inf_grad":
        grads = dict(grads, b=grads["b"].at[0].set(jnp.inf))
    else:
        loss = jnp.float32(jnp.nan)
    new_p, new_o, hv = jax.jit(
        lambda g, o, p, l: guarded_update(tx, g, o, p, loss=l)
    )(grads, opt, params, loss)
    assert float(hv["applied"]) == 0.0
    assert _bitwise_equal(params, new_p)
    assert _bitwise_equal(opt, new_o)


def test_guarded_update_extra_ok_vetoes():
    """extra_ok=False (a collective per-shard verdict) suppresses the
    update even when the global grads/loss are finite."""
    params, tx, opt, grads = _tiny_problem()
    new_p, new_o, hv = guarded_update(
        tx, grads, opt, params, loss=jnp.float32(1.0),
        extra_ok=jnp.asarray(False))
    assert float(hv["applied"]) == 0.0
    assert _bitwise_equal(params, new_p) and _bitwise_equal(opt, new_o)


def test_guarded_update_warn_mode_reports_but_applies():
    """guard=False (--health warn): the health vector still flags the
    poisoned step, but the update lands — observe-only mode."""
    params, tx, opt, grads = _tiny_problem()
    grads = dict(grads, w=grads["w"].at[0].set(jnp.nan))
    new_p, _, hv = guarded_update(tx, grads, opt, params, guard=False)
    assert float(hv["applied"]) == 0.0  # flagged...
    assert not _bitwise_equal(params, new_p)  # ...but not masked


def test_collective_all_finite_agrees_across_shards():
    """Inside shard_map, one shard's non-finite value must flip the flag
    on EVERY shard (lax.pmin combine) — a skip decision that only some
    shards take would diverge the replicas."""
    mesh = make_mesh(dp=4, devices=jax.devices()[:4])
    values = jnp.ones((4, 2))

    def body(v):
        ok = collective_all_finite(v, ("dp",))
        return ok.astype(jnp.float32)[None]

    f = shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                  out_specs=P("dp"), check_vma=False)
    assert np.array_equal(np.asarray(f(values)), np.ones((4,)))
    poisoned = values.at[2, 1].set(jnp.nan)  # only shard 2 sees the NaN
    assert np.array_equal(np.asarray(f(poisoned)), np.zeros((4,)))


# --- fault ports ----------------------------------------------------------


def test_fault_scale_for_grad_nan_and_spike():
    faults.install("grad_nan:at_step=3,loss_spike:at_step=5")
    try:
        assert fault_scale_for(1) == 1.0
        assert fault_scale_for(2) == 1.0
        assert math.isnan(fault_scale_for(3))
        assert fault_scale_for(3) == 1.0  # at_step fires once
        assert fault_scale_for(4) == 1.0
        assert fault_scale_for(5) == guardrails.SPIKE_SCALE
        assert fault_scale_for(6) == 1.0
    finally:
        faults.reset()


def test_maybe_hang_is_bounded_by_cap():
    faults.install("step_hang:at_step=2")
    try:
        t0 = time.monotonic()
        faults.maybe_hang(1, cap=5.0)  # wrong step: no hang
        assert time.monotonic() - t0 < 1.0
        t0 = time.monotonic()
        faults.maybe_hang(2, cap=0.0)  # fires, but the cap bounds it
        assert time.monotonic() - t0 < 2.0
    finally:
        faults.reset()


# --- host-side anomaly policy ---------------------------------------------


def _feed_baseline(mon, n=20, loss=2.0, start=1):
    for i in range(n):
        mon.observe(start + i, loss=loss + 0.01 * (i % 3), grad_norm=1.0,
                    applied=1.0)
    return start + n


def test_monitor_ok_on_stable_losses(capsys):
    mon = HealthMonitor(mode="skip")
    _feed_baseline(mon)
    assert mon.last_verdict == "ok"
    assert mon.counts["ok"] == 20
    assert not mon.wants_rollback
    assert capsys.readouterr().err == ""  # healthy steps are silent


def test_monitor_flags_spike_without_polluting_window():
    mon = HealthMonitor(mode="skip", spike_zscore=8.0)
    step = _feed_baseline(mon)
    assert mon.observe(step, loss=500.0, grad_norm=1.0,
                       applied=1.0) == "spike"
    # the spike did NOT enter the rolling statistic: the next normal loss
    # is still ok (a polluted window would widen the MAD and mask repeats)
    assert mon.observe(step + 1, loss=2.0, grad_norm=1.0,
                       applied=1.0) == "ok"
    assert 500.0 not in mon.history()
    # skip mode never escalates to a rollback
    assert not mon.wants_rollback


def test_monitor_nonfinite_verdict_and_streak_escalation():
    """One masked step is free; a streak of nonfinite_patience of them in
    rollback mode means the state/data is wrong — escalate."""
    mon = HealthMonitor(mode="rollback", nonfinite_patience=3)
    step = _feed_baseline(mon)
    assert mon.observe(step, loss=float("nan"), grad_norm=float("nan"),
                       applied=0.0) == "nonfinite"
    assert not mon.wants_rollback  # one bad batch is masked for free
    # a healthy step breaks the streak...
    assert mon.observe(step + 1, loss=2.0, grad_norm=1.0,
                       applied=1.0) == "ok"
    # ...so two more skipped steps stay below the patience of three
    # (applied=0.0 counts as nonfinite regardless of the loss value)
    mon.observe(step + 2, loss=2.0, grad_norm=1.0, applied=0.0)
    mon.observe(step + 3, loss=2.0, grad_norm=1.0, applied=0.0)
    assert not mon.wants_rollback
    mon.observe(step + 4, loss=2.0, grad_norm=1.0, applied=0.0)
    assert mon.wants_rollback
    assert "non-finite" in mon.rollback_reason


def test_monitor_spike_escalates_in_rollback_mode():
    mon = HealthMonitor(mode="rollback", spike_zscore=8.0)
    step = _feed_baseline(mon)
    mon.observe(step, loss=500.0, grad_norm=1.0, applied=1.0)
    assert mon.wants_rollback and mon.rollback_reason == "spike"


def test_monitor_divergence_needs_patience():
    mon = HealthMonitor(mode="rollback", warmup=4, window=64, patience=3,
                        divergence_factor=1.5, ema_alpha=0.5,
                        spike_zscore=1e9)  # spikes off: isolate the trend
    step = 1
    for i in range(8):
        mon.observe(step + i, loss=1.0, grad_norm=1.0, applied=1.0)
    # steadily rising loss: EMA climbs past 1.5x best; diverged only after
    # `patience` consecutive bad observations, not on the first
    verdicts = [mon.observe(step + 8 + i, loss=4.0 + i, grad_norm=1.0,
                            applied=1.0) for i in range(4)]
    assert "diverged" in verdicts
    assert verdicts[0] == "ok"  # not triggered instantly
    assert mon.wants_rollback and mon.rollback_reason == "diverged"


def test_monitor_beat_extras():
    mon = HealthMonitor(mode="skip")
    assert mon.beat_extras() == {"health_state": "ok"}
    mon.observe(1, loss=2.5, grad_norm=0.75, applied=1.0)
    extras = mon.beat_extras()
    assert extras == {"health_state": "ok", "loss": 2.5, "grad_norm": 0.75}


# --- rollback plumbing ----------------------------------------------------


def test_argv_with_resume_auto_strips_pinning_flags():
    argv = ["--epochs", "4", "--resume", "auto", "--dalle_path", "x.pt",
            "--resume_path=y", "--keep_checkpoints", "8"]
    out = argv_with_resume_auto(argv)
    assert out == ["--epochs", "4", "--keep_checkpoints", "8",
                   "--resume", "auto"]


def test_run_with_rollback_relaunches_with_backoff():
    calls = []

    def run_fn(argv, lr_scale=1.0, skip_past=None):
        calls.append((list(argv), lr_scale, skip_past))
        if len(calls) < 3:
            raise RollbackAndSkip(step=7 * len(calls), max_rollbacks=3,
                                  lr_backoff=0.5, reason="spike")
        return "done"

    assert run_with_rollback(run_fn, ["--epochs", "4"]) == "done"
    assert len(calls) == 3
    assert calls[0] == (["--epochs", "4"], 1.0, None)
    # each relaunch: --resume auto appended (once effectively), lr halved
    # again, and the data window advanced to the newest offending step
    assert calls[1][0][-2:] == ["--resume", "auto"]
    assert calls[1][1:] == (0.5, 7)
    assert calls[2][1:] == (0.25, 14)


def test_run_with_rollback_budget_exhausts_with_exit_code():
    def run_fn(argv, lr_scale=1.0, skip_past=None):
        raise RollbackAndSkip(step=3, max_rollbacks=2, reason="diverged")

    with pytest.raises(SystemExit) as exc:
        run_with_rollback(run_fn, [])
    assert exc.value.code == int(ExitCode.ROLLBACK_BUDGET) == 70


def test_anomaly_bundle_atomic_and_idempotent(tmp_path):
    report = {"reason": "spike", "loss": 123.0, "loss_history": [1.0, 2.0]}
    path = write_anomaly_bundle(tmp_path, 42, report)
    assert path == tmp_path / "anomaly-00000042"
    data = json.loads((path / "report.json").read_text())
    assert data["step"] == 42 and data["reason"] == "spike"
    # idempotent: a second write (another process in a collective
    # escalation) returns the existing bundle untouched
    before = (path / "report.json").read_bytes()
    assert write_anomaly_bundle(tmp_path, 42, {"reason": "other"}) == path
    assert (path / "report.json").read_bytes() == before
    # no temp droppings: the tmp dir was renamed, not copied
    assert [p.name for p in tmp_path.iterdir()] == ["anomaly-00000042"]


# --- hung-step watchdog ---------------------------------------------------


def test_watchdog_first_arm_is_compile_exempt():
    """The first arm covers the XLA compile (minutes at real sizes) and
    must never fire, however long it takes."""
    fired = threading.Event()
    wd = StepWatchdog(0.05, on_expire=fired.set, poll=0.01)
    try:
        wd.arm(1)  # free pass
        time.sleep(0.3)
        assert not fired.is_set()
    finally:
        wd.close()


def test_watchdog_disarm_prevents_expiry():
    fired = threading.Event()
    wd = StepWatchdog(0.15, on_expire=fired.set, poll=0.01)
    try:
        wd.arm(1)  # free pass
        for step in range(2, 6):  # healthy loop: arm/disarm under deadline
            wd.arm(step)
            time.sleep(0.02)
            wd.disarm()
        time.sleep(0.4)
        assert not fired.is_set()
    finally:
        wd.close()


def test_watchdog_fires_on_hung_step():
    fired = threading.Event()
    wd = StepWatchdog(0.1, on_expire=fired.set, poll=0.01)
    try:
        wd.arm(1)  # free pass
        wd.arm(2)  # armed for real; never disarmed = the wedge
        assert fired.wait(timeout=5.0)
    finally:
        wd.close()


def test_watchdog_default_expiry_is_wedge_exit():
    """Without on_expire the expiry path dumps stacks and os._exit(75) —
    proven in a real subprocess in test_anomaly_resume.py; here just pin
    the contract constant the supervisors key on."""
    assert int(ExitCode.WEDGED) == 75
