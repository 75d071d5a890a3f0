#!/usr/bin/env python
"""Fleet serving chaos smoke: prove zero dropped futures under replica loss.

The CI crash-resume job's fleet row (and the multi-process leg of
tests/test_router.py): build a tiny CPU model, stand up a
``FleetRouter`` over N in-process replicas — each with its OWN
graftscope stream under ``--out`` — inject a mid-decode replica kill
(``replica_down:at_tick``), push a request mix through, and exit 0 only
when:

* every submitted future resolved (result / ShedError / RouterError) —
  the zero-dropped-futures gate;
* the router's audit ledger balances with nothing outstanding;
* every successful result is BIT-IDENTICAL to the single-server
  greedy reference for its prompt;
* the live-buffer census returns to the post-warmup baseline once the
  router drains — the serve leak gate (obs/mem.py): a retire/evict
  path stashing an arena cache reference fails the run, not a pager.

Afterwards the streams replay as one fleet view::

    python tools/fleet_smoke.py --replicas 2 --requests 12 --kill-tick 40 \
        --out fleet-smoke
    python tools/obs_report.py --merge fleet-smoke/router \
        fleet-smoke/replica0 fleet-smoke/replica1
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

# A CPU harness by design: the chaos it rehearses (thread death, drain,
# migration) is host logic on a toy model, so it claims no chip.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from dalle_pytorch_tpu import DALLE, DALLEConfig, VAEConfig  # noqa: E402
from dalle_pytorch_tpu.models.dalle import (decode_codes,  # noqa: E402
                                            prefill_codes)
from dalle_pytorch_tpu.obs import mem as obs_mem  # noqa: E402
from dalle_pytorch_tpu.obs import metrics as obs_metrics  # noqa: E402
from dalle_pytorch_tpu.obs import telemetry  # noqa: E402
from dalle_pytorch_tpu.serve import (LATENCY, THROUGHPUT,  # noqa: E402
                                     FleetRouter, Replica, RouterError)
from dalle_pytorch_tpu.utils import faults, locks  # noqa: E402


def build_model():
    """The test_serve-scale toy: big enough to tick, small enough to
    compile in seconds on a CI box."""
    vcfg = VAEConfig(image_size=16, num_tokens=32, codebook_dim=16,
                     num_layers=2, hidden_dim=8)
    cfg = DALLEConfig.from_vae(
        vcfg, dim=32, num_text_tokens=50, text_seq_len=6, depth=2, heads=2,
        dim_head=8, attn_types=("full", "axial_row"))
    dalle = DALLE(cfg)
    rng = jax.random.PRNGKey(0)
    texts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(i), (cfg.text_seq_len,), 1, 50), np.int32)
        for i in range(4)]
    codes = jax.random.randint(rng, (1, cfg.image_seq_len), 0, 32)
    params = dalle.init(rng, jnp.asarray(texts[0])[None], codes,
                        return_loss=True)
    return cfg, dalle, params, texts


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--replicas", type=int, default=2)
    parser.add_argument("--requests", type=int, default=12)
    parser.add_argument("--slots", type=int, default=2,
                        help="slots per replica arena")
    parser.add_argument("--kill-tick", type=int, default=40,
                        help="replica_down:at_tick value (0 = no kill)")
    parser.add_argument("--out", type=Path, default=Path("fleet-smoke"),
                        help="output root: router/ + replicaN/ streams")
    parser.add_argument("--timeout", type=float, default=300.0,
                        help="bound on the whole drive (seconds)")
    parser.add_argument("--metrics_port", type=int, default=None,
                        help="optionally serve /metrics while running")
    parser.add_argument("--no-leak-gate", action="store_true",
                        help="skip the post-drain live-buffer leak check")
    args = parser.parse_args(argv)

    args.out.mkdir(parents=True, exist_ok=True)
    # graftrace witness: honors GRAFT_LOCK_WITNESS=1 (the CI fleet row
    # sets it); armed, every lock acquisition across router + replica
    # drivers feeds the order graph gated below
    if locks.armed():
        locks.reset()
        print("[fleet_smoke] graftrace lock-order witness armed")
    telemetry.init(args.out / "router", run_id="fleet-router")
    reg = obs_metrics.init()
    metrics_server = (obs_metrics.serve(args.metrics_port, reg)
                      if args.metrics_port is not None else None)

    cfg, dalle, params, texts = build_model()

    # single-server greedy references: the bit-match baseline
    prefill = jax.jit(lambda p, t: prefill_codes(dalle, p, t))
    refs = []
    for t in texts:
        fl, caches = prefill(params, jnp.asarray(t)[None])
        refs.append(np.asarray(decode_codes(
            dalle, params, fl, caches, jax.random.PRNGKey(7),
            filter_thres=1.0))[0])
    print(f"[fleet_smoke] references ready ({len(refs)} prompts)")

    faults.install(f"replica_down:at_tick={args.kill_tick}"
                   if args.kill_tick > 0 else "")
    replicas = [
        Replica(f"r{i}", dalle, params, args.slots,
                telemetry_dir=args.out / f"replica{i}", host_index=i + 1,
                warmup_text=texts[0], filter_thres=1.0)
        for i in range(args.replicas)]
    router = FleetRouter(
        replicas, retry_backoff_s=0.05, retry_backoff_cap_s=0.5,
        heartbeat_timeout_s=1.0, monitor_interval_s=0.02,
        probe_every_s=0.2,
        shed_bounds={LATENCY: 10_000, THROUGHPUT: 10_000}).start()
    router.wait_serving(args.replicas, timeout_s=args.timeout)
    # post-warmup census: every replica has prefilled + decoded once, so
    # the jit caches and arenas are resident — anything the chaos run
    # adds on top of THIS is a leak
    mem_tracker = obs_mem.MemTracker(emit=True)
    base = mem_tracker.baseline(phase="post-warmup")
    print(f"[fleet_smoke] leak-gate baseline: {base['live_count']} live "
          f"buffers / {base['live_bytes']} bytes")
    print(f"[fleet_smoke] {args.replicas} replicas serving; submitting "
          f"{args.requests} requests (kill-tick={args.kill_tick})")

    handles = []
    for i in range(args.requests):
        slo = LATENCY if i % 5 == 4 else THROUGHPUT
        handles.append(router.submit(texts[i % len(texts)], slo=slo))
        time.sleep(0.002)  # a trickle, so the kill lands mid-stream

    deadline = time.monotonic() + args.timeout
    dropped = 0
    mismatched = 0
    errors = 0
    for i, h in enumerate(handles):
        try:
            out = h.result(max(0.1, deadline - time.monotonic()))
            if not np.array_equal(out, refs[i % len(refs)]):
                mismatched += 1
        except RouterError:
            errors += 1  # typed resolution: counted, not a drop
        # graftlint: disable=EXC001 (the gate itself: ANY atypical resolution — timeout, untyped error — must count as a dropped future, and the exit code is the loud failure)
        except Exception:
            dropped += 1
    dropped += sum(not h.future.done() for h in handles)

    audit = router.audit()
    states = {n: r["state"] for n, r in router.stats()["replicas"].items()}
    router.close()
    # leak gate runs AFTER the router threads stop but BEFORE the
    # replicas release their arenas: against a baseline that includes
    # the arenas, a stashed per-request cache reference reads as pure
    # growth instead of hiding under the freed-arena bytes
    leak = None
    if not args.no_leak_gate:
        try:
            delta = mem_tracker.check_baseline("fleet-chaos")
            print(f"[fleet_smoke] leak gate: back to baseline "
                  f"(count delta {delta['count_delta']}, bytes delta "
                  f"{delta['bytes_delta']})")
        except obs_mem.LeakError as e:
            leak = str(e)
            print(f"[fleet_smoke] {e}", file=sys.stderr)
    for r in replicas:
        r.close()
    # lock-order witness gate: with GRAFT_LOCK_WITNESS=1 a cycle in the
    # observed acquisition graph fails the run even when this particular
    # interleaving never deadlocked; stats/graph land in metrics + stream
    lock_cycle = None
    if locks.armed():
        locks.publish_metrics()
        locks.emit_telemetry()
        try:
            locks.assert_acyclic()
            rep = locks.order_report()
            print(f"[fleet_smoke] lock witness: {len(rep['edges'])} order "
                  f"edge(s), acyclic")
        except locks.LockOrderError as e:
            lock_cycle = str(e)
            print(f"[fleet_smoke] {e}", file=sys.stderr)
    if metrics_server is not None:
        metrics_server.close()
    telemetry.shutdown()
    faults.reset()

    print(f"[fleet_smoke] audit: {audit}")
    print(f"[fleet_smoke] replica states: {states}")
    ok = (dropped == 0 and mismatched == 0 and audit["balanced"]
          and audit["outstanding"] == 0 and audit["resolved_ok"] > 0
          and (args.kill_tick == 0 or audit["replica_deaths"] >= 1)
          and leak is None and lock_cycle is None)
    if ok:
        print(f"[fleet_smoke] PASS: zero dropped futures "
              f"({audit['resolved_ok']} ok, {errors} typed errors, "
              f"{audit['shed']} shed, {audit['retries']} retries, "
              f"{audit['replica_deaths']} replica deaths), all completed "
              "results bit-match the single-server path")
        return 0
    print(f"[fleet_smoke] FAIL: dropped={dropped} mismatched={mismatched} "
          f"leak={'yes' if leak else 'no'} "
          f"lock_cycle={'yes' if lock_cycle else 'no'} audit={audit}",
          file=sys.stderr)
    return 1


if __name__ == "__main__":
    raise SystemExit(main())
