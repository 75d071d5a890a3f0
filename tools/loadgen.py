#!/usr/bin/env python
"""graftwire loadgen: trace-driven SLO chaos gate over a subprocess fleet.

The proving harness for ISSUE 18 (ROADMAP directions 2c + 2e): open-loop
traffic with a realistic shape — a diurnal rate curve compressed into
``--duration``, Zipf hot-prompt skew (the PR 16 prefix cache's reason to
exist), mixed SLO classes — replayed against ``--replicas`` REAL
subprocess replicas behind a :class:`FleetRouter`, while a chaos
schedule SIGKILLs one replica mid-trace, joins a same-name successor
under traffic, and injects rpc-transport faults
(``rpc_send``/``rpc_recv`` drop / delay_ms / conn_reset) at the
router's edge of the wire.  Open-loop means arrivals NEVER wait for
completions — backpressure surfaces as shedding, not as a politely
self-throttling load generator.

Shed handling honors the router's hint: a :class:`ShedError` carries
``retry_after_s`` (computed from the fleet's resolve rate) and the
loadgen resubmits after exactly that wait, up to ``--shed-retries``
times, reporting the shed-retry success rate.

Exit 0 iff ALL of:

* zero dropped futures (every arrival resolves: codes, shed that
  exhausted its retries, or a typed RouterError);
* the router audit ledger balances with nothing outstanding (and the
  kill was actually observed as a replica death);
* every successful result BIT-MATCHES the single-server greedy
  reference for its prompt — across migration, dedup, and restart;
* per-SLO-class attainment, read from the MERGED fleet telemetry
  (router lane + one lane per child process), meets ``--attain``.

``--autoscale`` runs the graftscale surge scenario instead (the CI
``autoscale_smoke`` row): start from ``--replicas`` (typically 1) with an
:class:`AutoScaler` over the router, step-multiply arrivals by
``--surge-mult`` inside the surge window, SIGKILL one of the
autoscaler's own children mid-scale-up, and gate additionally on: the
fleet reaching ``--max-replicas``, <= ``--max-flaps`` direction
reversals, every acting decision citing its signals + ledger
fingerprint in the merged telemetry, and latency-class attainment back
over ``--attain`` within ``--recovery-window`` of the surge ending.

Usage (the CI ``loadgen_smoke`` / ``autoscale_smoke`` rows)::

    python tools/loadgen.py --replicas 3 --duration 12 --kill-frac 0.35 \
        --restart-frac 0.6 --out loadgen-smoke
    python tools/loadgen.py --replicas 1 --autoscale --max-replicas 2 \
        --surge-mult 3 --surge-frac 0.1 --surge-end-frac 0.6 \
        --duration 60 --kill-frac 0.65 --restart-frac -1 \
        --out autoscale-smoke
    # sizing: a spawned child pays the full jax compile warmup (~15s on
    # a CI core) before it can SERVE, and spawns serialize through the
    # control loop — the surge must start early and the run must be long
    # enough for spawn -> serve -> SIGKILL -> recover to fit
    python tools/obs_report.py --merge loadgen-smoke/router \
        loadgen-smoke/r* loadgen-smoke/gen2/*
"""
from __future__ import annotations

import argparse
import bisect
import heapq
import itertools
import json
import math
import random
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import os  # noqa: E402

# A CPU harness by design: its replicas are child processes, and a chip
# belongs to one process at a time, so parent and children all run on CPU.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from dalle_pytorch_tpu.models.dalle import (decode_codes,  # noqa: E402
                                            prefill_codes)
from dalle_pytorch_tpu.obs import build_fleet_report  # noqa: E402
from dalle_pytorch_tpu.obs import merge_streams  # noqa: E402
from dalle_pytorch_tpu.obs import metrics as obs_metrics  # noqa: E402
from dalle_pytorch_tpu.obs import telemetry  # noqa: E402
from dalle_pytorch_tpu.serve import (LATENCY, SERVING,  # noqa: E402
                                     THROUGHPUT, AutoScaler, FleetRouter,
                                     RouterError, ScalePolicy, ShedError)
from dalle_pytorch_tpu.serve import remote as serve_remote  # noqa: E402
from dalle_pytorch_tpu.utils import faults, locks  # noqa: E402


# --- trace synthesis (pure; tests/test_loadgen.py pins these) --------------


def diurnal_rate(t_frac: float, mean: float, amp: float) -> float:
    """Arrival rate (req/s) at trace fraction ``t_frac`` in [0,1): one
    full diurnal cycle compressed into the trace — trough at the edges,
    peak in the middle, ``mean*(1±amp)`` swing."""
    return max(0.0, mean * (1.0 + amp * math.sin(
        2.0 * math.pi * t_frac - math.pi / 2.0)))


def zipf_weights(n: int, s: float):
    """Normalized Zipf(s) over ``n`` ranks: the hot-prompt skew (rank 0
    is the hot prompt the prefix cache should keep winning on)."""
    w = [1.0 / float(i + 1) ** s for i in range(n)]
    total = sum(w)
    return [x / total for x in w]


def build_trace(*, duration_s: float, rate_mean: float, rate_amp: float,
                prompts: int, zipf_s: float, latency_frac: float,
                seed: int, surge=None):
    """Deterministic open-loop arrival schedule:
    ``[(t_s, prompt_idx, slo), ...]`` sorted by time.  Thinning sampler
    against the diurnal envelope, Zipf prompt choice, Bernoulli SLO
    class mix — all from one seeded RNG so a seed pins the whole
    trace.  ``surge=(start_frac, end_frac, mult)`` multiplies the rate
    by ``mult`` inside that window — the graftscale step burst; ``None``
    (the default) leaves the schedule bit-identical to before."""
    rng = random.Random(seed)
    mult = float(surge[2]) if surge else 1.0
    peak = rate_mean * (1.0 + abs(rate_amp)) * max(1.0, mult)
    if peak <= 0:
        return []
    cum = list(itertools.accumulate(zipf_weights(prompts, zipf_s)))
    out = []
    t = 0.0
    while True:
        t += rng.expovariate(peak)
        if t >= duration_s:
            return out
        # thinning: accept with prob rate(t)/peak -> inhomogeneous Poisson
        rate = diurnal_rate(t / duration_s, rate_mean, rate_amp)
        if surge and surge[0] <= t / duration_s < surge[1]:
            rate *= mult
        if rng.random() * peak <= rate:
            idx = bisect.bisect_left(cum, rng.random())
            slo = LATENCY if rng.random() < latency_frac else THROUGHPUT
            out.append((t, min(idx, prompts - 1), slo))


# --- the gate ---------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--replicas", type=int, default=3)
    parser.add_argument("--slots", type=int, default=2)
    parser.add_argument("--duration", type=float, default=12.0,
                        help="trace length in wall seconds (one compressed "
                             "diurnal cycle)")
    parser.add_argument("--rate-mean", type=float, default=5.0)
    parser.add_argument("--rate-amp", type=float, default=0.6)
    parser.add_argument("--prompts", type=int, default=4)
    parser.add_argument("--zipf-s", type=float, default=1.1)
    parser.add_argument("--latency-frac", type=float, default=0.25)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--kill-frac", type=float, default=0.35,
                        help="SIGKILL replica --kill-index at this trace "
                             "fraction (<0 disables)")
    parser.add_argument("--kill-index", type=int, default=1)
    parser.add_argument("--restart-frac", type=float, default=0.6,
                        help="join a same-name successor at this fraction "
                             "(<0 disables)")
    parser.add_argument("--faults",
                        default="rpc_send:drop=5,rpc_recv:drop=11,"
                                "rpc_send:conn_reset=17,rpc_send:delay_ms=2",
                        help="GRAFT_FAULTS spec installed at --faults-frac "
                             "(client-side rpc sites; children stay clean)")
    parser.add_argument("--faults-frac", type=float, default=0.15)
    parser.add_argument("--faults-clear-frac", type=float, default=0.85)
    parser.add_argument("--shed-retries", type=int, default=3)
    parser.add_argument("--slo-latency", type=float, default=30.0,
                        help="latency-class target (s) the children judge "
                             "retirements against")
    parser.add_argument("--slo-throughput", type=float, default=120.0)
    parser.add_argument("--attain", type=float, default=0.7,
                        help="per-class SLO attainment floor (from merged "
                             "telemetry)")
    parser.add_argument("--prefix-cache", action="store_true", default=True)
    parser.add_argument("--no-prefix-cache", dest="prefix_cache",
                        action="store_false")
    # --- graftscale surge scenario (the autoscale_smoke CI row) ---
    parser.add_argument("--autoscale", action="store_true",
                        help="run an AutoScaler over the router: start "
                             "from --replicas, grow toward --max-replicas "
                             "under load, brownout at saturation")
    parser.add_argument("--max-replicas", type=int, default=3)
    parser.add_argument("--surge-mult", type=float, default=0.0,
                        help="step-multiply the arrival rate by this "
                             "inside [--surge-frac, --surge-end-frac) "
                             "(<=1 disables the surge)")
    parser.add_argument("--surge-frac", type=float, default=0.25)
    parser.add_argument("--surge-end-frac", type=float, default=0.65)
    parser.add_argument("--max-flaps", type=int, default=2,
                        help="scale-direction reversals tolerated by the "
                             "gate (autoscale mode)")
    parser.add_argument("--recovery-window", type=float, default=None,
                        help="seconds after the surge ends by which "
                             "latency-class attainment must be back >= "
                             "--attain (default: 0.25 x --duration)")
    parser.add_argument("--out", type=Path, default=Path("loadgen-out"))
    parser.add_argument("--timeout", type=float, default=420.0,
                        help="bound on the whole run (spawn + trace + "
                             "settle), seconds")
    args = parser.parse_args(argv)

    args.out.mkdir(parents=True, exist_ok=True)
    # shared-file clock rendezvous: each child lane beacons against the
    # same directory, so the merged fleet timeline aligns process-remote
    # lanes with no common workload anchor
    os.environ.setdefault("GRAFT_CLOCK_RDV", str(args.out / "clockrdv"))
    if locks.armed():
        locks.reset()
        print("[loadgen] graftrace lock-order witness armed")
    telemetry.init(args.out / "router", run_id="loadgen-router")
    obs_metrics.init()
    faults.install("")  # chaos installs its spec mid-trace, client-side

    # single-server greedy references (the bit-match baseline) from the
    # SAME toy geometry the children build
    cfg, dalle, params, texts = serve_remote._build_toy_model(
        seed=0, prompts=args.prompts)
    prefill = jax.jit(lambda p, t: prefill_codes(dalle, p, t))
    refs = []
    for t in texts:
        fl, caches = prefill(params, jnp.asarray(t)[None])
        refs.append(np.asarray(decode_codes(
            dalle, params, fl, caches, jax.random.PRNGKey(7),
            filter_thres=1.0))[0])
    print(f"[loadgen] references ready ({len(refs)} prompts)")

    slo_targets = {LATENCY: args.slo_latency,
                   THROUGHPUT: args.slo_throughput}
    t_spawn = time.monotonic()
    remotes = []
    for i in range(args.replicas):
        remotes.append(serve_remote.spawn_replica(
            f"r{i}", out_dir=args.out, slots=args.slots, host_index=i + 1,
            slo_targets=slo_targets, prefix_cache=args.prefix_cache,
            remote_stale_s=5.0,
            ready_timeout_s=max(60.0, args.timeout / 2)))
        print(f"[loadgen] replica r{i} up (pid "
              f"{remotes[-1].proc.pid}, port {remotes[-1]._client.port})")
    router = FleetRouter(
        remotes, retry_backoff_s=0.05, retry_backoff_cap_s=0.5,
        heartbeat_timeout_s=3.0, monitor_interval_s=0.02,
        probe_every_s=0.25, drain_grace_s=15.0).start()
    router.wait_serving(args.replicas,
                        timeout_s=max(30.0, args.timeout / 2))
    print(f"[loadgen] {args.replicas} subprocess replicas serving "
          f"({time.monotonic() - t_spawn:.1f}s to warm)")

    scaler = None
    if args.autoscale:
        auto_dir = args.out / "auto"
        spawn_host = itertools.count(args.replicas + 2)

        def spawn_fn(name):
            return serve_remote.spawn_replica(
                name, out_dir=auto_dir, slots=args.slots,
                host_index=next(spawn_host), slo_targets=slo_targets,
                prefix_cache=args.prefix_cache, remote_stale_s=5.0,
                ready_timeout_s=max(60.0, args.timeout / 2))

        scaler = AutoScaler(
            router, spawn_fn,
            policy=ScalePolicy(min_replicas=1,
                               max_replicas=args.max_replicas,
                               up_cooldown_s=1.0, down_cooldown_s=8.0,
                               down_after=6, max_step=1,
                               flap_window_s=max(30.0, args.duration),
                               max_flaps=args.max_flaps),
            interval_s=0.3).start()
        print(f"[loadgen] graftscale armed: {args.replicas} -> "
              f"{args.max_replicas} replicas max")

    surge = ((args.surge_frac, args.surge_end_frac, args.surge_mult)
             if args.surge_mult > 1.0 else None)
    trace = build_trace(
        duration_s=args.duration, rate_mean=args.rate_mean,
        rate_amp=args.rate_amp, prompts=args.prompts, zipf_s=args.zipf_s,
        latency_frac=args.latency_frac, seed=args.seed, surge=surge)
    if surge:
        print(f"[loadgen] surge: x{args.surge_mult:g} arrivals in "
              f"[{args.surge_frac:g}, {args.surge_end_frac:g}) of the "
              f"trace")
    print(f"[loadgen] trace: {len(trace)} arrivals over "
          f"{args.duration:.0f}s (peak ~"
          f"{args.rate_mean * (1 + args.rate_amp):.1f}/s)")

    # chaos timeline (trace fractions -> absolute trace seconds)
    t_kill = (args.kill_frac * args.duration
              if 0 <= args.kill_frac <= 1 else None)
    t_restart = (args.restart_frac * args.duration
                 if 0 <= args.restart_frac <= 1 else None)
    t_faults_on = (args.faults_frac * args.duration
                   if args.faults and 0 <= args.faults_frac <= 1 else None)
    t_faults_off = (args.faults_clear_frac * args.duration
                    if 0 <= args.faults_clear_frac <= 1 else None)
    kill_name = f"r{args.kill_index}"

    handles = []            # (handle, prompt_idx, shed_tries)
    resubmits: list = []    # heap of (due_t, prompt_idx, slo, tries)
    shed_first = 0
    shed_retry_ok = 0       # filled in after the wait loop
    shed_exhausted = 0

    def submit_one(idx: int, slo: str, tries: int, now_t: float) -> None:
        nonlocal shed_first, shed_exhausted
        h = router.submit(texts[idx], slo=slo)
        if h.future.done():
            exc = h.future.exception()
            if isinstance(exc, ShedError):
                if tries == 0:
                    shed_first += 1
                if tries < args.shed_retries:
                    wait = exc.retry_after_s or 0.25
                    heapq.heappush(resubmits,
                                   (now_t + wait, idx, slo, tries + 1))
                    return  # the resubmit carries this arrival forward
                shed_exhausted += 1
        handles.append((h, idx, tries))

    surge_end_t = (args.surge_end_frac * args.duration if surge else None)
    surge_end_wall = None
    peak_observed = 0  # fleet serving count witnessed outside decisions
    start = time.monotonic()
    i = 0
    new_remote = None
    while True:
        now_t = time.monotonic() - start
        if surge_end_t is not None and now_t >= surge_end_t:
            surge_end_t = None
            surge_end_wall = time.time()
            print(f"[loadgen] t={now_t:.2f}s: surge over, recovery "
                  f"clock running")
        if t_kill is not None and now_t >= t_kill:
            if scaler is not None:
                # kill one of the AUTOSCALER's own children — the
                # mid-scale-up death the gate is about.  Stays armed
                # until a spawned replica is actually SERVING: killing a
                # still-warming JOINING child would only prove the spawn
                # path, not the serve-then-die migration the gate wants
                # (and would make the reach-target gate unreachable
                # inside one run).
                victims = [r for r in scaler.spawned
                           if r.proc is not None and r.proc.poll() is None
                           and r.state == SERVING]
                if victims:
                    t_kill = None
                    victim = victims[0]
                    # the victim filter just witnessed a spawned child
                    # SERVING — snapshot the fleet serving count NOW,
                    # because the SIGKILL below races the scaler's next
                    # collect tick and no decision record may ever
                    # observe the peak the fleet provably reached
                    peak_observed = max(peak_observed, sum(
                        1 for r in router.stats()["replicas"].values()
                        if r["state"] == "serving"))
                    victim.proc.kill()
                    print(f"[loadgen] CHAOS t={now_t:.2f}s: SIGKILL "
                          f"{victim.name} mid-scale-up "
                          f"(pid {victim.proc.pid})")
            else:
                t_kill = None
                victim = next(r for r in remotes if r.name == kill_name)
                victim.proc.kill()
                print(f"[loadgen] CHAOS t={now_t:.2f}s: SIGKILL "
                      f"{kill_name} (pid {victim.proc.pid})")
        if t_restart is not None and now_t >= t_restart:
            t_restart = None
            # same NAME, fresh process + fresh lane dir: the rolling
            # restart join the router's supersede path exists for
            new_remote = serve_remote.spawn_replica(
                kill_name, out_dir=args.out / "gen2", slots=args.slots,
                host_index=args.replicas + 1, slo_targets=slo_targets,
                prefix_cache=args.prefix_cache, remote_stale_s=5.0,
                ready_timeout_s=max(60.0, args.timeout / 2))
            router.join(new_remote)
            print(f"[loadgen] CHAOS t={now_t:.2f}s: joined successor "
                  f"{kill_name} (pid {new_remote.proc.pid})")
        if t_faults_on is not None and now_t >= t_faults_on:
            t_faults_on = None
            faults.install(args.faults)
            print(f"[loadgen] CHAOS t={now_t:.2f}s: rpc faults armed: "
                  f"{args.faults}")
        if t_faults_off is not None and now_t >= t_faults_off:
            t_faults_off = None
            faults.install("")
            print(f"[loadgen] CHAOS t={now_t:.2f}s: rpc faults cleared")
        while resubmits and resubmits[0][0] <= now_t:
            _due, idx, slo, tries = heapq.heappop(resubmits)
            submit_one(idx, slo, tries, now_t)
        while i < len(trace) and trace[i][0] <= now_t:
            _t, idx, slo = trace[i]
            i += 1
            submit_one(idx, slo, 0, now_t)
        if i >= len(trace) and not resubmits and t_restart is None \
                and t_faults_off is None:
            break
        nexts = [trace[i][0] if i < len(trace) else None,
                 resubmits[0][0] if resubmits else None,
                 t_kill, t_restart, t_faults_on, t_faults_off]
        pending = [x for x in nexts if x is not None]
        if not pending and i >= len(trace) and not resubmits:
            break
        time.sleep(max(0.001, min(
            (min(pending) - (time.monotonic() - start)) if pending
            else 0.005, 0.05)))
    faults.install("")  # settle phase: no injection while draining
    print(f"[loadgen] trace replayed: {len(handles)} admitted, "
          f"{shed_first} shed at first touch, "
          f"{shed_exhausted} shed past the retry budget")

    deadline = start + args.duration + args.timeout
    dropped = 0
    mismatched = 0
    typed_errors = 0
    ok_count = 0
    shed_final = 0
    for h, idx, tries in handles:
        try:
            out = h.result(max(0.1, deadline - time.monotonic()))
            ok_count += 1
            if tries > 0:
                shed_retry_ok += 1
            if not np.array_equal(out, refs[idx]):
                mismatched += 1
        except ShedError:
            shed_final += 1
        except RouterError:
            typed_errors += 1  # typed resolution: counted, never a drop
        # graftlint: disable=EXC001 (the gate itself: any untyped resolution or timeout IS the dropped future this harness hunts; counted, fails the run loudly)
        except Exception:
            dropped += 1

    scale_ups = scale_downs = peak_replicas = flaps_seen = level_peak = 0
    if scaler is not None:
        scaler.close()   # stop actuating before the fleet tears down
        for d in scaler.decisions:
            if d.action == "scale_up":
                scale_ups += 1
            elif d.action == "scale_down":
                scale_downs += 1
            peak_replicas = max(peak_replicas, d.signals.serving)
            flaps_seen = max(flaps_seen, d.flaps)
            level_peak = max(level_peak, int(d.level))
        peak_replicas = max(peak_replicas, peak_observed)
    audit = router.audit()
    states = {n: r["state"] for n, r in router.stats()["replicas"].items()}
    retry_rate = (shed_retry_ok / shed_first) if shed_first else None
    router.close()
    lock_cycle = None
    if locks.armed():
        locks.publish_metrics()
        locks.emit_telemetry()
        try:
            locks.assert_acyclic()
            rep = locks.order_report()
            print(f"[loadgen] lock witness: {len(rep['edges'])} order "
                  f"edge(s), acyclic")
        except locks.LockOrderError as e:
            lock_cycle = str(e)
            print(f"[loadgen] {e}", file=sys.stderr)
    telemetry.shutdown()
    faults.reset()

    # --- merged-telemetry SLO gate ---
    lanes = [args.out / "router"]
    lanes += [args.out / f"r{j}" for j in range(args.replicas)]
    if new_remote is not None:
        lanes.append(args.out / "gen2" / kill_name)
    if scaler is not None:
        lanes += [args.out / "auto" / r.name for r in scaler.spawned]
    events, clocks = merge_streams([p for p in lanes if p.exists()])
    fleet = build_fleet_report(events, clocks)
    by_class = fleet["serve"]["by_class"]
    (args.out / "fleet_report.json").write_text(
        json.dumps(fleet, indent=2, default=str))
    attained = {}
    attain_ok = True
    for slo, row in sorted(by_class.items()):
        att = row.get("attainment")
        attained[slo] = att
        print(f"[loadgen] SLO {slo}: completed={row['completed']} "
              f"p50={row['latency_p50']} p99={row['latency_p99']} "
              f"attainment={att}")
        if att is not None and att < args.attain:
            attain_ok = False
    if not by_class:
        attain_ok = False
        print("[loadgen] no per-class serve rows in the merged report",
              file=sys.stderr)

    # --- graftscale gates (autoscale mode only) ---
    auto_ok = True
    recovery_ok = True
    if scaler is not None:
        deci = [r for r in events if r.get("kind") == "autoscale"
                and r.get("name") == "decision"]
        acts = [r for r in deci if r.get("action") != "hold"]
        # every ACTING decision must cite its signals and the ledger row
        uncited = [r for r in acts
                   if not r.get("ledger_fingerprint")
                   or r.get("queued_latency") is None]
        reached = peak_replicas >= args.max_replicas
        auto_ok = (scale_ups >= 1 and reached and bool(acts)
                   and not uncited and flaps_seen <= args.max_flaps)
        print(f"[loadgen] autoscale: {len(deci)} decisions "
              f"({scale_ups} up, {scale_downs} down, "
              f"{len(acts) - len(uncited)}/{len(acts)} acting decisions "
              f"ledger-cited), peak {peak_replicas}/{args.max_replicas} "
              f"serving, flaps {flaps_seen} (<= {args.max_flaps}), "
              f"brownout peak level {level_peak}, "
              f"{scaler.spawn_failures} spawn failures")
        if not auto_ok:
            print(f"[loadgen] autoscale gate FAILED: scale_ups="
                  f"{scale_ups} reached={reached} uncited={len(uncited)} "
                  f"flaps={flaps_seen}", file=sys.stderr)
        if surge_end_wall is not None:
            window = (args.recovery_window if args.recovery_window
                      is not None else 0.25 * args.duration)
            cut = surge_end_wall + window
            lat = [r for r in events if r.get("kind") == "serve"
                   and r.get("name") == "retire"
                   and r.get("slo") == LATENCY
                   and r.get("slo_ok") is not None and r.get("t")]
            tail = ([r for r in lat if float(r["t"]) >= cut]
                    or [r for r in lat if float(r["t"]) >= surge_end_wall])
            if tail:
                rec_att = sum(bool(r["slo_ok"]) for r in tail) / len(tail)
                recovery_ok = rec_att >= args.attain
                print(f"[loadgen] recovery: latency attainment "
                      f"{rec_att:.3f} over {len(tail)} retirements after "
                      f"surge end (+{window:.1f}s window), floor "
                      f"{args.attain}")
            else:
                recovery_ok = False
                print("[loadgen] recovery: NO latency retirements after "
                      "the surge ended", file=sys.stderr)

    print(f"[loadgen] audit: {audit}")
    print(f"[loadgen] replica states: {states}")
    print(f"[loadgen] shed: first={shed_first} retried-ok={shed_retry_ok} "
          f"exhausted={shed_exhausted} final={shed_final} "
          f"retry-success-rate="
          f"{'n/a' if retry_rate is None else f'{retry_rate:.2f}'}")
    print(f"[loadgen] merged lanes: {len(clocks)} "
          f"({', '.join(str(p.name) for p in lanes)})")

    killed = t_kill is None and 0 <= args.kill_frac <= 1
    ok = (dropped == 0 and mismatched == 0 and audit["balanced"]
          and audit["outstanding"] == 0 and ok_count > 0
          and (not killed or audit["replica_deaths"] >= 1)
          and lock_cycle is None and attain_ok and auto_ok
          and recovery_ok)
    if ok:
        print(f"[loadgen] PASS: zero dropped futures over {len(handles)} "
              f"admitted arrivals ({ok_count} ok bit-matched, "
              f"{typed_errors} typed errors, {audit['retries']} retries, "
              f"{audit['replica_deaths']} replica deaths), per-class "
              f"attainment >= {args.attain} from merged telemetry")
        return 0
    print(f"[loadgen] FAIL: dropped={dropped} mismatched={mismatched} "
          f"attain_ok={attain_ok} auto_ok={auto_ok} "
          f"recovery_ok={recovery_ok} "
          f"lock_cycle={'yes' if lock_cycle else 'no'}"
          f" audit={audit}", file=sys.stderr)
    return 1


if __name__ == "__main__":
    raise SystemExit(main())
