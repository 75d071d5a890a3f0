"""Failure-detection subsystem: graceful shutdown + heartbeat/stall watch.

The reference has no failure handling (SURVEY.md §5.3 — recovery is a
manual rerun from the last periodic checkpoint); these cover the
preemption-safe machinery this framework adds.  The end-to-end
SIGTERM-during-training path is covered in test_cli.py
(test_train_dalle_preemption) on the real CLI.
"""
from __future__ import annotations

import json
import signal
import time

from dalle_pytorch_tpu.utils.failure import (ExitCode, GracefulShutdown,
                                             Heartbeat)


def test_exit_codes_are_frozen():
    """The ExitCode enum is THE one place the supervisor contract lives
    (tools/monitor.py and any external scheduler key restart decisions off
    these values) — pin every
    number so a renumbering can never slip through a refactor."""
    assert int(ExitCode.CLEAN) == 0
    # a graceful preemption stop exits CLEANLY (supervisors tell "finished"
    # from "preempted" by the heartbeat done-marker, never by exit code)
    assert int(ExitCode.PREEMPTED) == 0
    assert ExitCode.PREEMPTED is ExitCode.CLEAN  # a true alias
    assert int(ExitCode.MONITOR_STALLED) == 1
    assert int(ExitCode.MONITOR_NO_HEARTBEATS) == 2
    assert int(ExitCode.RESTART_BUDGET) == 3
    assert int(ExitCode.ROLLBACK_BUDGET) == 70  # terminal: never restart
    # transient: the preemption grace window expired mid-save; resume from
    # the last committed manifest (possibly under a different --plan)
    assert int(ExitCode.PREEMPT_EXPIRED) == 74
    assert int(ExitCode.WEDGED) == 75  # transient: restart with --resume
    # the trainer-side codes must never collide with the monitor's own
    assert len({ExitCode.MONITOR_STALLED, ExitCode.MONITOR_NO_HEARTBEATS,
                ExitCode.RESTART_BUDGET, ExitCode.ROLLBACK_BUDGET,
                ExitCode.PREEMPT_EXPIRED, ExitCode.WEDGED,
                ExitCode.CLEAN}) == 7


def test_graceful_shutdown_sets_flag_on_signal():
    with GracefulShutdown() as stopper:
        assert not stopper.requested
        assert not stopper.should_stop()
        signal.raise_signal(signal.SIGTERM)
        assert stopper.requested
        assert stopper.should_stop()
    # handlers restored on exit
    assert signal.getsignal(signal.SIGTERM) is not stopper._handler


def test_graceful_shutdown_sigint_too():
    with GracefulShutdown() as stopper:
        signal.raise_signal(signal.SIGINT)
        assert stopper.requested
    assert signal.getsignal(signal.SIGINT) is not stopper._handler


def test_average_and_poll_single_process():
    """Single process: metric passes through, stop mirrors the local flag."""
    with GracefulShutdown() as stopper:
        avg, stop = stopper.average_and_poll(None, 3.5)
        assert avg == 3.5 and not stop
        signal.raise_signal(signal.SIGTERM)
        avg, stop = stopper.average_and_poll(None, 1.25)
        assert avg == 1.25 and stop


def test_average_and_poll_one_collective(monkeypatch):
    """Multi-process: the loss mean and the OR'd stop flag share ONE
    backend collective (a 2-vector), never two per step."""
    import numpy as np

    import dalle_pytorch_tpu.utils.failure as fail

    class FakeBackend:
        def __init__(self):
            self.calls = []

        def average_all(self, value):
            self.calls.append(np.asarray(value))
            # simulate a peer at loss 2.0 whose stop flag is set
            peer = np.asarray([2.0, 1.0], np.float32)
            return (np.asarray(value, np.float32) + peer) / 2

    monkeypatch.setattr(fail.jax, "process_count", lambda: 2)
    backend = FakeBackend()
    with GracefulShutdown() as stopper:
        avg, stop = stopper.average_and_poll(backend, 4.0)
    assert len(backend.calls) == 1 and backend.calls[0].shape == (2,)
    assert avg == 3.0  # mean(4.0, 2.0)
    assert stop  # any process's flag stops everyone (mean > 0)


def test_heartbeat_file_and_external_stall_check(tmp_path):
    hb = Heartbeat(tmp_path, beat_interval=1000)
    try:
        # a missing heartbeat reads as stalled (dead-before-first-step host)
        assert Heartbeat.is_stalled(hb.path, timeout=1.0)
        hb.beat(1, epoch=0)  # first beat always writes
        payload = Heartbeat.read(hb.path)
        assert payload["step"] == 1 and payload["epoch"] == 0
        # writes are rate-limited by wall-clock time, not step count
        hb.beat(2)
        assert Heartbeat.read(hb.path)["step"] == 1
        hb._last_write -= 2000  # age past the rate limit
        hb.beat(3)
        assert Heartbeat.read(hb.path)["step"] == 3

        now = time.time()
        assert not Heartbeat.is_stalled(hb.path, timeout=60, now=now)
        assert Heartbeat.is_stalled(hb.path, timeout=60, now=now + 120)
    finally:
        hb.close()


def test_heartbeat_stall_check_survives_torn_file(tmp_path):
    path = tmp_path / "heartbeat-p0.json"
    path.write_text('{"step": 3, "ti')  # torn mid-write
    # falls back to mtime: fresh file -> not stalled, old 'now' -> stalled
    assert not Heartbeat.is_stalled(path, timeout=60)
    assert Heartbeat.is_stalled(path, timeout=60, now=time.time() + 120)


def test_watchdog_warns_on_stall(tmp_path, capfd):
    hb = Heartbeat(tmp_path, stall_timeout=0.1)
    try:
        hb.beat(1)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if "possible stall" in capfd.readouterr().err:
                break
            time.sleep(0.05)
        else:
            raise AssertionError("watchdog never warned about the stall")
        # a new beat clears the stall latch so a second stall warns again
        hb.beat(2)
        assert hb._stalled_since is None
    finally:
        hb.close()


def test_monitor_cli(tmp_path, capsys):
    """tools/monitor.py scans heartbeat files: healthy -> 0, stalled -> 1,
    empty dir -> 2, --expect reports never-started processes."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    import monitor

    assert monitor.main([str(tmp_path)]) == 2  # no heartbeats yet

    # a leftover file matching the glob but not the name pattern must be
    # skipped, not crash the babysitter
    (tmp_path / "heartbeat-pXcopy.json").write_text("{}")
    assert monitor.main([str(tmp_path)]) == 2

    hb = Heartbeat(tmp_path)
    try:
        hb.beat(7)
    finally:
        hb.close()
    assert monitor.main([str(tmp_path), "--timeout", "300"]) == 0
    out = capsys.readouterr().out
    assert "process 0: ok" in out and "step 7" in out

    # age the heartbeat beyond the timeout -> stalled
    payload = json.loads(hb.path.read_text())
    payload["time"] -= 1000
    hb.path.write_text(json.dumps(payload))
    assert monitor.main([str(tmp_path), "--timeout", "300"]) == 1
    assert "STALLED" in capsys.readouterr().out

    # --expect flags processes that never wrote a heartbeat
    assert monitor.main([str(tmp_path), "--timeout", "1e9",
                         "--expect", "3"]) == 1
    assert "process 1: MISSING" in capsys.readouterr().out

    # a done marker overrides staleness: finished runs must not read as
    # dead (an auto-restart wrapper would relaunch them forever)
    payload["done"] = True
    hb.path.write_text(json.dumps(payload))
    assert monitor.main([str(tmp_path), "--timeout", "300"]) == 0
    assert "process 0: done" in capsys.readouterr().out


def test_heartbeat_done_marker(tmp_path):
    hb = Heartbeat(tmp_path)
    hb.beat(42)
    hb.close(done=True)
    payload = Heartbeat.read(hb.path)
    assert payload["done"] is True and payload["step"] == 42

    # interrupted close leaves no done marker — restart is desired there
    hb2 = Heartbeat(tmp_path)
    hb2.beat(43)
    hb2.close(done=False)
    assert "done" not in Heartbeat.read(hb2.path)


def test_graceful_shutdown_second_signal_escalates():
    """A second delivery of the same signal restores the PREVIOUS handler
    and re-raises through it — an impatient double ctrl-C/kill must
    terminate immediately instead of waiting on the checkpoint."""
    hits = []
    prev = signal.signal(signal.SIGTERM, lambda s, f: hits.append(s))
    try:
        with GracefulShutdown() as stopper:
            signal.raise_signal(signal.SIGTERM)
            assert stopper.requested and hits == []  # first: flag only
            signal.raise_signal(signal.SIGTERM)
            # second: escalated straight to the pre-existing handler
            assert hits == [signal.SIGTERM]
            assert signal.getsignal(signal.SIGTERM) is not stopper._handler
        # __exit__ after an escalation is a clean no-op (already restored)
        assert hits == [signal.SIGTERM]
    finally:
        signal.signal(signal.SIGTERM, prev)


def test_heartbeat_sweeps_stale_temp_files(tmp_path):
    """A process killed inside _write leaks a .hb-* temp; a new Heartbeat
    in the same dir sweeps temps older than a few beat intervals and keeps
    fresh ones (a peer process may be mid-write right now)."""
    import os

    stale = tmp_path / ".hb-stale123"
    stale.write_text("{")
    old = time.time() - 3600
    os.utime(stale, (old, old))
    fresh = tmp_path / ".hb-fresh456"
    fresh.write_text("{")

    hb = Heartbeat(tmp_path, beat_interval=15.0)
    try:
        assert not stale.exists()
        assert fresh.exists()
    finally:
        hb.close()


def test_monitor_restart_cmd_and_budget(tmp_path, capsys):
    """tools/monitor.py --restart-cmd: a stalled run triggers the restart
    command (which resolves {ckpt} to the newest manifest-valid managed
    checkpoint); the budget bounds the loop (exit 3); with no valid
    checkpoint there is nothing to restart from."""
    import sys as _sys
    from pathlib import Path

    import numpy as np

    _sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    import monitor

    from dalle_pytorch_tpu.utils.ckpt_manager import CheckpointManager

    # a stalled heartbeat (old timestamp)
    hb = Heartbeat(tmp_path)
    hb.beat(5)
    hb.close()
    payload = json.loads(hb.path.read_text())
    payload["time"] -= 1000
    hb.path.write_text(json.dumps(payload))

    ckpts = tmp_path / "ckpts"
    marker = tmp_path / "restarts.log"

    # no valid checkpoint yet -> nothing to restart from, exit 3, no cmd run
    assert monitor.main([str(tmp_path), "--timeout", "300",
                         "--restart-cmd", f"echo r >> {marker}",
                         "--ckpt-dir", str(ckpts)]) == 3
    assert not marker.exists()

    CheckpointManager(ckpts).save(
        9, {"weights": {"w": np.zeros((2,), np.float32)}})

    # single-shot: one restart fires, {ckpt} resolves to the payload path
    code = monitor.main([str(tmp_path), "--timeout", "300",
                         "--restart-cmd", f"echo {{ckpt}} >> {marker}",
                         "--ckpt-dir", str(ckpts)])
    assert code == 1  # the scan itself still reports the stall
    assert "ckpt-00000009" in marker.read_text()

    # watch mode: the budget bounds the loop and exits 3
    marker.unlink()
    code = monitor.main([str(tmp_path), "--timeout", "300",
                         "--watch", "0.01", "--max-restarts", "2",
                         "--restart-cmd", f"echo r >> {marker}",
                         "--ckpt-dir", str(ckpts)])
    assert code == 3
    assert marker.read_text().count("r") == 2
    capsys.readouterr()  # drain scan output


def test_monitor_flags_unhealthy_heartbeats(tmp_path, capsys):
    """The trainers ride loss/grad_norm/health_state on every beat
    (guardrails.HealthMonitor.beat_extras); the monitor prints them and
    flags non-finite values and non-ok verdicts so an operator sees a
    sick run without reading training logs."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    import monitor

    hb = Heartbeat(tmp_path)
    try:
        hb.beat(11, loss=2.125, grad_norm=0.5, health_state="ok")
    finally:
        hb.close()
    assert monitor.main([str(tmp_path), "--timeout", "300"]) == 0
    out = capsys.readouterr().out
    # healthy: values printed, no flag
    assert "loss 2.125" in out and "grad_norm 0.5" in out
    assert "UNHEALTHY" not in out

    hb2 = Heartbeat(tmp_path)
    try:
        hb2._last_write = None  # force the write through the rate limit
        hb2.beat(12, loss=float("nan"), grad_norm=float("inf"),
                 health_state="spike")
    finally:
        hb2.close()
    assert monitor.main([str(tmp_path), "--timeout", "300"]) == 0  # alive...
    out = capsys.readouterr().out
    assert "UNHEALTHY: spike" in out  # ...but visibly sick
    assert "loss=nan" in out and "grad_norm=inf" in out


def test_monitor_restart_stops_on_terminal_exit_code(tmp_path, capsys):
    """A restarted trainer exiting ExitCode.ROLLBACK_BUDGET (70) means
    automatic recovery will not converge: the monitor must stop
    immediately (exit RESTART_BUDGET) instead of burning the remaining
    budget relaunching the same divergence.  A WEDGED (75) exit is
    transient and consumes the budget like any other death."""
    import sys as _sys
    from pathlib import Path

    import numpy as np

    _sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    import monitor

    from dalle_pytorch_tpu.utils.ckpt_manager import CheckpointManager

    hb = Heartbeat(tmp_path)
    hb.beat(5)
    hb.close()
    payload = json.loads(hb.path.read_text())
    payload["time"] -= 1000  # stalled
    hb.path.write_text(json.dumps(payload))
    ckpts = tmp_path / "ckpts"
    CheckpointManager(ckpts).save(
        9, {"weights": {"w": np.zeros((2,), np.float32)}})

    marker = tmp_path / "restarts.log"
    # terminal: the first restart exits 70 and the loop stops right there,
    # with most of the --max-restarts 5 budget unspent
    code = monitor.main([str(tmp_path), "--timeout", "300",
                         "--watch", "0.01", "--max-restarts", "5",
                         "--restart-cmd", f"echo r >> {marker}; exit 70",
                         "--ckpt-dir", str(ckpts)])
    assert code == int(ExitCode.RESTART_BUDGET) == 3
    assert marker.read_text().count("r") == 1
    assert "rollback budget exhausted" in capsys.readouterr().err

    # transient: rc=75 keeps relaunching until the budget runs out
    marker.unlink()
    code = monitor.main([str(tmp_path), "--timeout", "300",
                         "--watch", "0.01", "--max-restarts", "2",
                         "--restart-cmd", f"echo r >> {marker}; exit 75",
                         "--ckpt-dir", str(ckpts)])
    assert code == int(ExitCode.RESTART_BUDGET)
    assert marker.read_text().count("r") == 2
    assert "hung-step watchdog" in capsys.readouterr().err


def test_watchdog_quiet_before_first_step(tmp_path, capfd):
    """The construction->first-beat stretch includes the XLA compile
    (minutes at real sizes) and must not read as a stall."""
    hb = Heartbeat(tmp_path, stall_timeout=0.05)
    try:
        time.sleep(0.5)
        assert "possible stall" not in capfd.readouterr().err
    finally:
        hb.close()
