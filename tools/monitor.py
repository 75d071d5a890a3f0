#!/usr/bin/env python
"""External training-run monitor: scan heartbeat files for dead/stalled hosts.

The in-process side (``dalle_pytorch_tpu.utils.failure``) writes one
``heartbeat-p{process}.json`` per host into ``--heartbeat_dir``; this tool is
the babysitter that watches them from outside — e.g. under cron or a
supervisor loop — and exits non-zero when any host has gone quiet, so a
wrapper script can alert or restart the run.  (SURVEY.md §5.3: the reference
has no failure detection at all.)

With ``--restart-cmd`` the monitor is a full babysitter: a stalled/dead
scan runs the command (typically the trainer relaunched with ``--resume
auto``, which resumes from the newest manifest-valid managed checkpoint,
falling back past torn ones), bounded by ``--max-restarts``.  When
``--ckpt-dir`` is given the restart only fires if that directory holds a
manifest-valid checkpoint, and ``{ckpt}`` in the command expands to its
payload path.  A foreground restart command that exits with the trainer's
``ExitCode.ROLLBACK_BUDGET`` (70) stops the babysitter immediately —
that code means automatic recovery will NOT converge (a human must read
the anomaly bundles), so burning the remaining restart budget on it would
just produce more bundles.  ``ExitCode.WEDGED`` (75, the hung-step
watchdog) is transient by definition and consumes one restart like any
other death.

The trainers ride their health extras (``loss``, ``grad_norm``,
``health_state`` — see utils/guardrails.py) on every heartbeat, and the
scan prints them, flagging non-finite values and non-``ok`` verdicts with
an ``UNHEALTHY`` marker — an operator sees a sick run here without
reading training logs.

Heartbeats carry ``run_id`` + ``telemetry_seq`` (the graftscope stream's
last event number); with ``--telemetry-dir`` a STALLED host's scan line is
followed by its last few telemetry records — what the run was *doing*
when it went quiet, not just that it did.

**Fleet mode** (``--fleet DIR1 DIR2 ...``): tail N hosts' telemetry dirs
instead of heartbeat files.  The scan aligns the streams onto one
timebase (obs/align.py — heartbeats and beacons carry the clock payload,
so a host that died between rotations still aligns), prints each lane's
clock offset + residual bound, its last event age, the ``alert`` events
already in its stream, and re-runs the declarative rules
(obs/alerts.py) offline over the tail so a condition that built up right
before a death still surfaces.  Exit 1 when any lane has active alerts
or a stale stream, 2 when nothing is readable.

With ``--metrics URL ...`` the fleet scan also scrapes each ``/metrics``
endpoint (a serve fleet's ``obs_metrics.serve`` port) and prints one
line per replica: lifecycle state (the one-hot
``graft_replica_state{replica,state}`` gauges the serve tier exports),
queue depth per SLO class, and slot occupancy — the live half of
``obs_report --merge``'s after-the-fact fleet view.  An unreachable
endpoint counts as a failed scan (exit 1); a DEAD replica is
informational (a rolled replica is supposed to be dead).

Usage:
    python tools/monitor.py HEARTBEAT_DIR [--timeout 300] [--expect N] [--watch S]
    python tools/monitor.py hb --watch 60 --ckpt-dir checkpoints \
        --telemetry-dir tel \
        --restart-cmd 'nohup python train_dalle.py --resume auto ... &'
    python tools/monitor.py --fleet telA telB --timeout 120

Exit codes (the ``ExitCode`` table in utils/failure.py): 0 all hosts
healthy, 1 stalled/missing hosts, 2 no heartbeats, 3 restart budget
exhausted (or nothing valid to restart from, or a terminal rc=70 from the
restarted trainer).
"""
from __future__ import annotations

import argparse
import re
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dalle_pytorch_tpu.utils.failure import ExitCode, Heartbeat  # noqa: E402


def _health_flag(info: dict) -> str | None:
    """Operator-visible sickness from the health extras the trainers ride
    on every beat (guardrails.HealthMonitor.beat_extras): a non-``ok``
    verdict, or a non-finite loss/grad_norm (belt-and-braces — a verdict
    should already cover it, but a half-wired trainer must still flag)."""
    import math

    bits = []
    state = info.get("health_state")
    if state and state != "ok":
        bits.append(str(state))
    for key in ("loss", "grad_norm"):
        value = info.get(key)
        if value is not None and not math.isfinite(float(value)):
            bits.append(f"{key}={value}")
    return " ".join(bits) or None


def _telemetry_tail(telemetry_dir: Path, proc: int, run_id: str | None,
                    n: int = 5) -> list[str]:
    """The last ``n`` telemetry records for host ``proc`` (of ``run_id``
    when the heartbeat named one) — the "what was it doing when it
    stalled" answer, printed under a STALLED host's line."""
    from dalle_pytorch_tpu.obs.telemetry import read_events

    try:
        events = read_events(telemetry_dir)
    except OSError:
        return []
    rows = [r for r in events if r.get("host", 0) == proc
            and (run_id is None or r.get("run") == run_id)]
    out = []
    for r in rows[-n:]:
        bits = " ".join(f"{k}={r[k]}" for k in ("step", "ph", "msg")
                        if r.get(k) is not None)
        out.append(f"    seq {r.get('seq')} [{r.get('kind')}."
                   f"{r.get('name')}] {bits}")
    return out


def scan(directory: Path, timeout: float, expect: int | None,
         telemetry_dir: Path | None = None) -> int:
    # filter the glob through the exact name pattern: a leftover temp/copy
    # like heartbeat-p0.json.bak or heartbeat-pX.json must be skipped, not
    # crash the babysitter
    files = sorted(
        (int(m.group(1)), p)
        for p in directory.glob("heartbeat-p*.json")
        if (m := re.fullmatch(r"heartbeat-p(\d+)", p.stem)))
    if not files:
        print(f"no heartbeat files in {directory}", file=sys.stderr)
        return int(ExitCode.MONITOR_NO_HEARTBEATS)

    now = time.time()
    bad = 0
    seen = set()
    for proc, path in files:
        seen.add(proc)
        stalled = Heartbeat.is_stalled(path, timeout, now=now)
        done = False
        sick = None
        run_id = None
        try:
            info = Heartbeat.read(path)
            done = bool(info.get("done"))
            run_id = info.get("run_id")
            age = now - info["time"]
            detail = f"step {info.get('step', '?')} age {age:.0f}s"
            # run_id + telemetry_seq correlate this host with its event
            # stream: "run X stalled at telemetry seq N" is a greppable
            # coordinate, not a guess
            if run_id:
                detail += f" run {run_id}"
            if info.get("telemetry_seq") is not None:
                detail += f" tel_seq {info['telemetry_seq']}"
            # loader_stall_s rides every beat (DevicePrefetcher metering):
            # an input-bound host reads as "stall 2.3" here instead of
            # masquerading as a slow chip
            for key in ("loss", "grad_norm", "loader_stall_s"):
                if info.get(key) is not None:
                    detail += f" {key} {float(info[key]):.5g}"
            # the beat's compact memory snapshot (obs/mem.heartbeat_snapshot
            # via Heartbeat): a host creeping toward OOM shows its RSS/HBM
            # trajectory right here, before the stall — no stream parse
            for key in ("rss_mb", "hbm_used_mb", "hbm_peak_mb"):
                if info.get(key) is not None:
                    detail += f" {key} {float(info[key]):.0f}"
            sick = _health_flag(info)
        # graftlint: disable=EXC001 (a heartbeat mid-write is expected; any parse error = torn file, reported as status below)
        except Exception:
            detail = "unreadable (torn write?)"
        # a finished run's heartbeat ages forever — that's completion, not
        # death, and must not trigger an auto-restart wrapper
        status = "done" if done else ("STALLED" if stalled else "ok")
        flag = f"  << UNHEALTHY: {sick}" if sick and not done else ""
        print(f"process {proc}: {status} ({detail}){flag}")
        if stalled and not done and telemetry_dir is not None:
            tail = _telemetry_tail(telemetry_dir, proc, run_id)
            if tail:
                print(f"  last telemetry of process {proc}:")
                for line in tail:
                    print(line)
        bad += stalled and not done

    if expect is not None:
        missing = set(range(expect)) - seen
        for proc in sorted(missing):
            print(f"process {proc}: MISSING (never wrote a heartbeat)")
        bad += len(missing)
    return int(ExitCode.MONITOR_STALLED) if bad else int(ExitCode.CLEAN)


_METRIC_LINE_RE = re.compile(r"^(\w+)(?:\{(.*)\})?\s+(\S+)$")
_LABEL_RE = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def _scrape_replica_metrics(url: str, timeout: float = 3.0
                            ) -> tuple[dict[str, dict], dict[str, dict],
                                       dict[str, float]]:
    """GET an endpoint's /metrics and fold the per-replica serve series
    into ``{replica: {state, queue: {slo: depth}, occupancy,
    bytes_per_token, hbm_headroom}}`` plus the graftrace witness series
    into ``{lock: {acquires, contended, wait_s, held_s, held_max_s}}``
    plus the router's live audit ledger (``graft_router_audit_*``
    gauges) into ``{field: value}``.  Only replica-labeled (serve) /
    lock-labeled (witness) / router-audit series participate (a
    single-server trainer's unlabeled gauges are not a fleet)."""
    import urllib.request

    target = url if "://" in url else f"http://{url}"
    if not target.rstrip("/").endswith("/metrics"):
        target = target.rstrip("/") + "/metrics"
    with urllib.request.urlopen(target, timeout=timeout) as resp:
        text = resp.read().decode("utf-8", "replace")
    out: dict[str, dict] = {}
    locks: dict[str, dict] = {}
    ledger: dict[str, float] = {}
    lock_fields = {
        "graft_lock_acquires_total": "acquires",
        "graft_lock_contended_total": "contended",
        "graft_lock_wait_seconds_total": "wait_s",
        "graft_lock_held_seconds_total": "held_s",
        "graft_lock_held_seconds_max": "held_max_s",
    }
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        m = _METRIC_LINE_RE.match(line)
        if not m:
            continue
        name, labelstr, value = m.groups()
        labels = dict(_LABEL_RE.findall(labelstr or ""))
        try:
            v = float(value)
        except ValueError:
            continue
        lk = labels.get("lock")
        if lk is not None and name in lock_fields:
            locks.setdefault(lk, {})[lock_fields[name]] = v
            continue
        if name.startswith("graft_router_audit_"):
            ledger[name[len("graft_router_audit_"):]
                   .removesuffix("_total")] = v
            continue
        rep = labels.get("replica")
        if rep is None:
            continue
        info = out.setdefault(rep, {"queue": {}})
        if name == "graft_replica_state" and v == 1.0:
            info["state"] = labels.get("state", "?")
        elif name == "graft_serve_queue_depth":
            info["queue"][labels.get("slo", "?")] = v
        elif name == "graft_serve_occupancy":
            info["occupancy"] = v
        elif name == "graft_serve_predicted_bytes_per_token":
            info["bytes_per_token"] = v
        elif name == "graft_hbm_headroom_bytes":
            info["hbm_headroom"] = v
    return out, locks, ledger


def _print_replica_metrics(urls: list[str]) -> int:
    """The per-replica serve-state lines of a fleet scan; returns the
    number of UNREACHABLE endpoints (scrape failures, not dead replicas)."""
    bad = 0
    for url in urls:
        try:
            reps, lock_stats, ledger = _scrape_replica_metrics(url)
        except OSError as e:
            print(f"metrics {url}: unreachable ({e})", file=sys.stderr)
            bad += 1
            continue
        if not reps and not lock_stats and not ledger:
            print(f"metrics {url}: no replica-labeled serve series")
            continue
        for name in sorted(reps):
            info = reps[name]
            state = info.get("state", "?")
            bits = [f"state {state}"]
            if info["queue"]:
                bits.append("queue " + ",".join(
                    f"{slo}={int(d)}"
                    for slo, d in sorted(info["queue"].items())))
            if info.get("occupancy") is not None:
                bits.append(f"occupancy {info['occupancy']:.2f}")
            if info.get("bytes_per_token") is not None:
                # the arena's cost-model HBM stream per decoded token
                # (scheduler.predicted_bytes_per_token): occupancy says how
                # busy a replica is, this says how heavy each token is
                bits.append(
                    f"pred {info['bytes_per_token'] / 2**20:.2f} MiB/tok")
            if info.get("hbm_headroom") is not None:
                # measured HBM headroom (scheduler watermark gauge) beside
                # the predicted byte stream: "how heavy is a token" and
                # "how close is this replica to OOM" read on one line
                bits.append(
                    f"hbm headroom {info['hbm_headroom'] / 2**20:.0f} MiB")
            flag = "  << DOWN" if state == "dead" else ""
            print(f"replica {name} [{url}]: {' '.join(bits)}{flag}")
        if ledger:
            # the router's live audit ledger (graftscale's input signals):
            # submitted == ok + err + shed + outstanding, and "balanced"
            # says the invariant held at scrape time
            fields = ["submitted", "ok", "err", "shed", "outstanding"]
            bits = [f"{f}={int(ledger[f])}" for f in fields if f in ledger]
            bal = ledger.get("balanced")
            if bal is not None:
                bits.append("balanced" if bal >= 1.0 else "UNBALANCED")
            print(f"router ledger [{url}]: {' '.join(bits)}")
        if lock_stats:
            # graftrace witness rollup: the top held-time locks tell you
            # WHERE serialization lives; contended acquires tell you who
            # is paying for it
            top = sorted(lock_stats.items(),
                         key=lambda kv: -kv[1].get("held_s", 0.0))[:5]
            contended = sum(int(st.get("contended", 0))
                            for st in lock_stats.values())
            print(f"locks [{url}]: {len(lock_stats)} witnessed, "
                  f"{contended} contended acquires")
            for lk, st in top:
                print(f"  lock {lk}: {int(st.get('acquires', 0))} acquires "
                      f"({int(st.get('contended', 0))} contended, wait "
                      f"{st.get('wait_s', 0.0):.3f}s), held "
                      f"{st.get('held_s', 0.0):.3f}s total / "
                      f"{st.get('held_max_s', 0.0) * 1e3:.1f}ms max")
    return bad


def fleet_scan(dirs: list[Path], timeout: float, window: float = 300.0,
               metrics_urls: list[str] | None = None) -> int:
    """One fleet-mode scan over N telemetry dirs: align, tail, alert —
    plus the live per-replica serve state when ``metrics_urls`` name
    scrapeable endpoints."""
    import time as _time

    from dalle_pytorch_tpu.obs import merge_streams
    from dalle_pytorch_tpu.obs.alerts import AlertEngine

    events, clocks = merge_streams(dirs)
    if not events:
        print(f"no readable events under {[str(d) for d in dirs]}",
              file=sys.stderr)
        return int(ExitCode.MONITOR_NO_HEARTBEATS)
    now = _time.time()
    by_lane: dict[int, list[dict]] = {}
    for r in events:
        by_lane.setdefault(int(r.get("host", 0)), []).append(r)
    bad = 0
    for clock in clocks:
        lane = by_lane.get(clock.lane, [])
        last = lane[-1] if lane else None
        # ages compare FLEET time to this box's clock: the solved offset
        # has already removed the host's skew, so "age" means what it says
        age = (now - float(last["t"])) if last and last.get("t") else None
        stale = age is not None and age > timeout
        steps = [r for r in lane if r.get("kind") == "step"
                 and "ph" not in r and r.get("step") is not None]
        last_step = max((int(r["step"]) for r in steps), default=None)
        # alerts already in the stream (the in-process engine fired) ...
        recent_alerts = sorted({
            str(r.get("name")) for r in lane if r.get("kind") == "alert"
            and r.get("t") is not None and now - float(r["t"]) <= window})
        # ... plus an offline re-run over the tail, so a condition that
        # built up right before a death still surfaces here
        engine = AlertEngine()
        for r in lane:
            for fired in engine.observe(r):
                recent_alerts = sorted(set(recent_alerts)
                                       | {fired["rule"]})
        bound = clock.bound
        status = "STALE" if stale else "ok"
        print(f"lane {clock.lane} [{clock.run} host {clock.orig_host}]: "
              f"{status} (last event "
              f"{'-' if age is None else f'{age:.0f}s'} ago, step "
              f"{last_step}, clock offset {clock.offset:+.3f}s "
              f"±{'?' if bound is None else f'{bound:.3f}'} "
              f"[{clock.method}])")
        if recent_alerts:
            print(f"  ALERTS: {', '.join(recent_alerts)}")
        bad += stale or bool(recent_alerts)
    if metrics_urls:
        bad += _print_replica_metrics(metrics_urls)
    return int(ExitCode.MONITOR_STALLED) if bad else int(ExitCode.CLEAN)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("heartbeat_dir", type=Path, nargs="?", default=None)
    parser.add_argument("--fleet", nargs="+", type=Path, default=None,
                        metavar="TEL_DIR",
                        help="fleet mode: scan N telemetry dirs (one per "
                             "host) instead of heartbeat files — aligned "
                             "clock offsets, last-event ages, active "
                             "alerts per host")
    parser.add_argument("--metrics", nargs="+", type=str, default=None,
                        metavar="URL",
                        help="fleet mode add-on: scrape each /metrics "
                             "endpoint and print per-replica serve state "
                             "(lifecycle, queue depth per SLO class, "
                             "occupancy); an unreachable endpoint counts "
                             "as a failed scan")
    parser.add_argument("--timeout", type=float, default=300,
                        help="seconds without a beat before a host counts as "
                             "stalled (default 300)")
    parser.add_argument("--expect", type=int, default=None,
                        help="expected process count; missing heartbeat files "
                             "below this index are reported as failures")
    parser.add_argument("--watch", type=float, default=0,
                        help="re-scan every S seconds instead of exiting; "
                             "on ctrl-C/SIGINT exits with the last scan's "
                             "code")
    parser.add_argument("--restart-cmd", type=str, default=None,
                        help="shell command to run when a scan reports "
                             "stalled/dead hosts (exit 1) — typically the "
                             "trainer relaunched with --resume auto; "
                             "'{ckpt}' expands to the newest valid managed "
                             "checkpoint's payload path when --ckpt-dir is "
                             "given")
    parser.add_argument("--max-restarts", type=int, default=3,
                        help="restart budget: stop restarting (exit 3) "
                             "after this many attempts")
    parser.add_argument("--ckpt-dir", type=Path, default=None,
                        help="managed checkpoint run dir; restarts only "
                             "fire when it holds a manifest-valid "
                             "checkpoint (latest_valid fallback semantics)")
    parser.add_argument("--restart-plan", type=str, default=None,
                        help="elastic relaunch: append '--plan SPEC' to "
                             "--restart-cmd so the restarted trainer "
                             "reshards its resume onto a DIFFERENT "
                             "parallelism plan / topology (e.g. the "
                             "smaller pod the scheduler granted after a "
                             "preemption); checkpoint manifests record "
                             "the written-under plan, the restore "
                             "reshards by construction")
    parser.add_argument("--telemetry-dir", type=Path, default=None,
                        help="graftscope events dir (the trainer's "
                             "--telemetry_dir): a STALLED host's last "
                             "events are printed under its scan line, so "
                             "the report says WHAT it was doing, not just "
                             "that it stopped")
    args = parser.parse_args(argv)

    if args.fleet:
        code = int(ExitCode.MONITOR_NO_HEARTBEATS)
        try:
            while True:
                code = fleet_scan(args.fleet, args.timeout,
                                  metrics_urls=args.metrics)
                if not args.watch:
                    return code
                time.sleep(args.watch)
        except KeyboardInterrupt:
            return code
    if args.heartbeat_dir is None:
        parser.error("heartbeat_dir is required (or use --fleet)")

    def try_restart(restarts: int) -> int | None:
        """Run --restart-cmd once; returns an exit code to stop with, or
        None to keep watching."""
        if restarts >= args.max_restarts:
            print(f"restart budget exhausted ({args.max_restarts}); "
                  "giving up", file=sys.stderr)
            return int(ExitCode.RESTART_BUDGET)
        cmd = args.restart_cmd
        if args.ckpt_dir is not None:
            from dalle_pytorch_tpu.utils.ckpt_manager import latest_valid

            info = latest_valid(args.ckpt_dir)
            if info is None:
                print(f"no manifest-valid checkpoint under {args.ckpt_dir}; "
                      "nothing to restart from", file=sys.stderr)
                return int(ExitCode.RESTART_BUDGET)
            cmd = cmd.replace("{ckpt}", str(info.payload))
            written = (info.manifest.get("plan") or {}).get("spec")
            if written and args.restart_plan \
                    and written != args.restart_plan:
                print(f"elastic restart: checkpoint written under plan "
                      f"{written}; relaunching under --plan "
                      f"{args.restart_plan} (restore reshards on load)",
                      file=sys.stderr)
        if args.restart_plan:
            # '{plan}' in the command places the spec explicitly (compound
            # commands, backgrounded trainers); otherwise the flag pair is
            # appended
            if "{plan}" in cmd:
                cmd = cmd.replace("{plan}", args.restart_plan)
            else:
                cmd = f"{cmd} --plan {args.restart_plan}"
        print(f"restart {restarts + 1}/{args.max_restarts}: {cmd}",
              file=sys.stderr)
        rc = subprocess.run(cmd, shell=True).returncode
        if rc == int(ExitCode.ROLLBACK_BUDGET):
            # terminal by contract: the trainer's anomaly-recovery ladder
            # gave up — a relaunch reruns the same divergence, so stop
            # here instead of burning the rest of the budget on it
            print(f"restarted trainer exited {rc} (rollback budget "
                  "exhausted) — terminal, a human must read the anomaly "
                  "bundles; giving up", file=sys.stderr)
            return int(ExitCode.RESTART_BUDGET)
        if rc == int(ExitCode.WEDGED):
            print(f"restarted trainer exited {rc} (hung-step watchdog) — "
                  "transient, will relaunch on the next stalled scan",
                  file=sys.stderr)
        if rc == int(ExitCode.PREEMPT_EXPIRED):
            print(f"restarted trainer exited {rc} (preemption grace window "
                  "expired mid-save) — transient, the last committed "
                  "manifest resumes it on the next stalled scan",
                  file=sys.stderr)
        return None

    code = int(ExitCode.MONITOR_NO_HEARTBEATS)
    restarts = 0
    try:
        while True:
            code = scan(args.heartbeat_dir, args.timeout, args.expect,
                        telemetry_dir=args.telemetry_dir)
            if args.restart_cmd and code == int(ExitCode.MONITOR_STALLED):
                stop = try_restart(restarts)
                if stop is not None:
                    return stop
                restarts += 1
            if not args.watch:
                return code
            time.sleep(args.watch)
    except KeyboardInterrupt:
        return code


if __name__ == "__main__":
    raise SystemExit(main())
