#!/usr/bin/env python
"""graftmem: per-scope, per-phase HBM attribution + the committed memory
ledger — the memory-side twin of ``tools/graftprof.py``.

For every ``training.STEP_FACTORIES`` entry under its parallelism plans —
plus the decode scan, the serving arena tick, and the cub-512 scale rung
— this tool builds the memory timeline one run actually traverses (init
-> step peak -> ckpt snapshot -> serve steady-state) from two sources:
XLA's own opt0 buffer assignment (argument/output/temp bytes, the
``lint/spmd.py`` S4 convention, with the S2-verified donation credit)
for the phase totals, and ``obs/mem.py``'s peak-live jaxpr walk for the
attribution (which resident planes — params / opt state / weights /
arena incl. int8 value+scale layout — and which ``prof.scope``
activations were live at the peak).  Each timeline is folded against
``prof.CHIP_SPECS`` HBM into a per-chip headroom verdict and committed
as a ``memory`` sub-row of ``PERF_LEDGER.json`` under the SAME
``prof.fingerprint_payload`` fingerprint graftprof owns — predictions
and memory live on one row, measured watermarks
(``mem.append_measured_memory``) land beside them.

Chip-free by the same construction as graftprof (whose harness this
reuses wholesale): the 8-device virtual CPU mesh, AOT trace/lower/
compile-at-opt0, nothing executes.

Modes:
    --update   recompute memory rows, merge (preserving measured
               history AND every graftprof field), write the ledger
    --check    recompute and diff — the CI drift gate: exit 1 when any
               phase's peak bytes drift >5% without a ledger update,
               naming the guilty scope
    --report   read-only predicted-vs-measured memory table (no jax)
    --quick    tiny geometry (tests / smoke)
    --targets  substring filter over target names
    --json     machine-readable output beside the human table

Usage:
    python tools/graftmem.py --update
    python tools/graftmem.py --check            # CI
    python tools/graftmem.py --report
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

# graftprof owns the sweep harness (and transitively the spmd_check env
# preamble: CPU backend + 8 virtual devices BEFORE jax initializes).
_spec = importlib.util.spec_from_file_location(
    "graftprof", Path(__file__).resolve().parent / "graftprof.py")
graftprof = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(graftprof)
spmd_check = graftprof.spmd_check

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dalle_pytorch_tpu.models.clip import CLIP  # noqa: E402
from dalle_pytorch_tpu.models.dalle import DALLE, decode_codes  # noqa: E402
from dalle_pytorch_tpu.models.vae import DiscreteVAE  # noqa: E402
from dalle_pytorch_tpu.obs import mem, prof  # noqa: E402
from dalle_pytorch_tpu.parallel.mesh import make_mesh  # noqa: E402
from dalle_pytorch_tpu.serve.engine import SlotArena  # noqa: E402
from dalle_pytorch_tpu.training import (make_clip_train_step,  # noqa: E402
                                        make_dalle_pp_train_step,
                                        make_dalle_sp_train_step,
                                        make_dalle_train_step, make_optimizer,
                                        make_vae_train_step)

PLANS = graftprof.PLANS
CHIP = graftprof.CHIP
TRAIN_BATCH = graftprof.TRAIN_BATCH
DECODE_BATCH = graftprof.DECODE_BATCH
SERVE_SLOTS = graftprof.SERVE_SLOTS
_sds = spmd_check._sds


def _wrap(fp: str, target: str, plan: str, memrow: dict) -> dict:
    return {"fingerprint": fp, "target": target, "plan": plan,
            "memory": memrow}


# --- per-target builders ---------------------------------------------------


def _dalle_mem_row(plan: str, make_cfg) -> dict:
    """One DALLE train-step memory row: phase totals from the opt0
    compile (per-device, donation credit applied), attribution from the
    peak-live walk (one shard's program under shard_map plans — the
    planes/scopes split, not the phase totals, which XLA owns)."""
    spec = PLANS[plan]
    cfg = make_cfg(**spec["plan"])
    dalle = DALLE(cfg)
    tx = make_optimizer(1e-3)
    mesh = make_mesh(**spec["mesh"])
    devices = 1
    for n in spec["mesh"].values():
        devices *= int(n)
    text = _sds((TRAIN_BATCH, cfg.text_seq_len), jnp.int32)
    codes = _sds((TRAIN_BATCH, cfg.image_seq_len), jnp.int32)
    rng = _sds((2,), jnp.uint32)
    fs = _sds((), jnp.float32)
    params = jax.eval_shape(dalle.init, jax.random.PRNGKey(0), text,
                            codes)["params"]
    if plan == "pp":
        step, pp_params = make_dalle_pp_train_step(
            dalle, tx, spmd_check._zeros_like_tree(params), mesh,
            num_microbatches=2, health=True)
        params = pp_params
    elif cfg.ring_axis is not None:
        step = make_dalle_sp_train_step(dalle, tx, mesh, health=True)
    else:
        step = make_dalle_train_step(dalle, tx, health=True)
    opt = jax.eval_shape(tx.init, params)
    args = (params, opt, None, text, codes, rng, fs)
    walk = mem.peak_live(
        jax.make_jaxpr(step)(*args),
        planes=mem.arg_planes(("params", params), ("opt-state", opt),
                              ("args", (None, text, codes, rng, fs))))
    compiled = graftprof._compiled_stats(
        spmd_check.dalle_step_lowered(plan, make_cfg=make_cfg,
                                      batch=TRAIN_BATCH),
        arg_labels=spmd_check.DALLE_ARG_LABELS)
    phases = mem.train_phases(compiled)
    factory = ("dalle_pp" if plan == "pp"
               else "dalle_sp" if cfg.ring_axis is not None else "dalle")
    target = f"{factory}/{plan}"
    config = graftprof._cfg_payload(cfg, target=target, plan=plan,
                                    batch=TRAIN_BATCH)
    memrow = mem.memory_row(phases=phases, planes=walk["planes"],
                            scopes=walk["scopes"],
                            walker_peak_bytes=walk["peak_bytes"],
                            devices=devices)
    return _wrap(prof.row_fingerprint(config), target, plan, memrow)


def _scale_mem_row(plan: str) -> dict:
    """A scale rung's memory row — the ones where headroom genuinely
    binds.  Walker-only (dim-512 compiles for ~8 minutes, dim-1024
    longer; the compiled S4 proof is ``spmd_check --presets``' nightly
    concern, cached in S4_PROOFS.json): resident state divided by the
    plan's state-sharding ways (fsdp x tp — both axes cut params and
    moments; lint/plans.py's per-leaf walk is the exact version, this
    uniform factor is the committed-row convention), activations from
    the global peak-live walk divided across the mesh — the analytic
    stand-in the decode row precedent allows, held stable for the drift
    gate."""
    from dalle_pytorch_tpu.parallel.plan import PLAN_REGISTRY
    from dalle_pytorch_tpu.presets import preset_config

    cfg = preset_config(plan)
    dalle = DALLE(cfg)
    tx = make_optimizer(1e-3)
    mesh_kwargs = PLAN_REGISTRY[plan].mesh_kwargs()
    devices = 1
    for n in mesh_kwargs.values():
        devices *= int(n)
    text = _sds((TRAIN_BATCH, cfg.text_seq_len), jnp.int32)
    codes = _sds((TRAIN_BATCH, cfg.image_seq_len), jnp.int32)
    rng = _sds((2,), jnp.uint32)
    fs = _sds((), jnp.float32)
    params = jax.eval_shape(dalle.init, jax.random.PRNGKey(0), text,
                            codes)["params"]
    opt = jax.eval_shape(tx.init, params)
    step = make_dalle_train_step(dalle, tx, health=True)
    args = (params, opt, None, text, codes, rng, fs)
    walk = mem.peak_live(
        jax.make_jaxpr(step)(*args),
        planes=mem.arg_planes(("params", params), ("opt-state", opt),
                              ("args", (None, text, codes, rng, fs))))
    phases = mem.analytic_train_phases(
        params_bytes=mem.tree_bytes(params),
        opt_bytes=mem.tree_bytes(opt),
        walker_peak_bytes=walk["peak_bytes"],
        resident_bytes=walk["resident_bytes"],
        devices=devices,
        shard_factor=PLAN_REGISTRY[plan].fsdp * PLAN_REGISTRY[plan].tp)
    target = f"dalle/{plan}"
    config = graftprof._cfg_payload(cfg, target=target, plan=plan,
                                    batch=TRAIN_BATCH)
    memrow = mem.memory_row(phases=phases, planes=walk["planes"],
                            scopes=walk["scopes"],
                            walker_peak_bytes=walk["peak_bytes"],
                            devices=devices,
                            note="analytic (walker-only; S4 compile "
                                 "under spmd_check --presets)")
    return _wrap(prof.row_fingerprint(config), target, plan, memrow)


def _vae_mem_row(quick: bool) -> dict:
    cfg = graftprof._vae_cfg(quick)
    vae = DiscreteVAE(cfg)
    tx = make_optimizer(1e-3)
    images = _sds((TRAIN_BATCH, cfg.image_size, cfg.image_size, 3),
                  jnp.float32)
    rng = _sds((2,), jnp.uint32)
    temp = _sds((), jnp.float32)
    fs = _sds((), jnp.float32)
    params = jax.eval_shape(
        lambda im: vae.init(jax.random.PRNGKey(0), im,
                            rng=jax.random.PRNGKey(1)), images)["params"]
    opt = jax.eval_shape(tx.init, params)
    step = make_vae_train_step(vae, tx, health=True)
    args = (params, opt, images, rng, temp, fs)
    walk = mem.peak_live(
        jax.make_jaxpr(step)(*args),
        planes=mem.arg_planes(("params", params), ("opt-state", opt),
                              ("args", (images, rng, temp, fs))))
    compiled = graftprof._compiled_stats(
        step.lower(*args), arg_labels=spmd_check.VAE_ARG_LABELS)
    config = graftprof._cfg_payload(cfg, target="vae", plan="single",
                                    batch=TRAIN_BATCH)
    memrow = mem.memory_row(phases=mem.train_phases(compiled),
                            planes=walk["planes"], scopes=walk["scopes"],
                            walker_peak_bytes=walk["peak_bytes"])
    return _wrap(prof.row_fingerprint(config), "vae", "single", memrow)


def _clip_mem_row(quick: bool) -> dict:
    cfg = graftprof._clip_cfg(quick)
    clip = CLIP(cfg)
    tx = make_optimizer(1e-3)
    text = _sds((TRAIN_BATCH, cfg.text_seq_len), jnp.int32)
    images = _sds((TRAIN_BATCH, cfg.visual_image_size,
                   cfg.visual_image_size, 3), jnp.float32)
    mask = _sds((TRAIN_BATCH, cfg.text_seq_len), jnp.bool_)
    fs = _sds((), jnp.float32)
    params = jax.eval_shape(
        lambda t, im, m: clip.init(jax.random.PRNGKey(0), t, im,
                                   text_mask=m), text, images,
        mask)["params"]
    opt = jax.eval_shape(tx.init, params)
    step = make_clip_train_step(clip, tx, health=True)
    args = (params, opt, text, images, mask, fs)
    walk = mem.peak_live(
        jax.make_jaxpr(step)(*args), default_scope="clip",
        planes=mem.arg_planes(("params", params), ("opt-state", opt),
                              ("args", (text, images, mask, fs))))
    compiled = graftprof._compiled_stats(
        step.lower(*args), arg_labels=spmd_check.CLIP_ARG_LABELS)
    config = graftprof._cfg_payload(cfg, target="clip", plan="single",
                                    batch=TRAIN_BATCH)
    memrow = mem.memory_row(phases=mem.train_phases(compiled),
                            planes=walk["planes"], scopes=walk["scopes"],
                            walker_peak_bytes=walk["peak_bytes"])
    return _wrap(prof.row_fingerprint(config), "clip", "single", memrow)


def _decode_mem_row(make_cfg) -> dict:
    """The sampling scan: weights + KV caches resident, per-step
    transients from the scan body's internal peak (no trip-count
    multiplication — the scan reuses its buffers).  No compile, the
    graftprof decode-row carve-out."""
    cfg = make_cfg()
    dalle = DALLE(cfg)
    text = _sds((DECODE_BATCH, cfg.text_seq_len), jnp.int32)
    codes = _sds((DECODE_BATCH, cfg.image_seq_len), jnp.int32)
    variables = jax.eval_shape(dalle.init, jax.random.PRNGKey(0), text,
                               codes)
    logits, kvs = jax.eval_shape(
        lambda v, t: dalle.apply(v, t, method=DALLE.prefill), variables,
        text)
    rng = _sds((2,), jnp.uint32)

    def run(v, first_logits, caches, r):
        return decode_codes(dalle, v, first_logits, caches, r)

    walk = mem.peak_live(
        jax.make_jaxpr(run)(variables, logits, kvs, rng),
        planes=mem.arg_planes(("weights", variables), ("args", logits),
                              ("arena", kvs), ("args", (rng,))))
    phases = mem.decode_phases(
        params_bytes=mem.tree_bytes(variables),
        walker_peak_bytes=walk["peak_bytes"])
    config = graftprof._cfg_payload(cfg, target="decode", plan="single",
                                    batch=DECODE_BATCH)
    memrow = mem.memory_row(phases=phases, planes=walk["planes"],
                            scopes=walk["scopes"],
                            walker_peak_bytes=walk["peak_bytes"],
                            note="walker-only (no compile)")
    return _wrap(prof.row_fingerprint(config), "decode", "single", memrow)


def _serve_mem_row(make_cfg) -> dict:
    """One arena tick, every slot advancing: steady-state = weights +
    the whole arena (int8 cache payloads AND their f32 scale planes are
    both arena state — the avals say so) + tick transients, resident for
    as long as the server is up."""
    cfg = make_cfg()
    dalle = DALLE(cfg)
    text = jnp.zeros((1, cfg.text_seq_len), jnp.int32)
    codes = jnp.zeros((1, cfg.image_seq_len), jnp.int32)
    variables = jax.eval_shape(dalle.init, jax.random.PRNGKey(0), text,
                               codes)
    arena = SlotArena(
        dalle, jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            variables),
        num_slots=SERVE_SLOTS)
    active = jnp.ones((SERVE_SLOTS,), bool)
    write_pos = jnp.int32(0)
    walk = mem.peak_live(
        jax.make_jaxpr(arena._tick)(arena.variables, arena.state, active,
                                    write_pos, arena._qweights),
        planes=mem.arg_planes(("weights", arena.variables),
                              ("arena", arena.state),
                              ("args", (active, write_pos)),
                              ("weights", arena._qweights)))
    phases = mem.serve_phases(walker_peak_bytes=walk["peak_bytes"])
    config = graftprof._cfg_payload(cfg, target="serve-tick", plan="single",
                                    batch=SERVE_SLOTS,
                                    num_slots=SERVE_SLOTS)
    memrow = mem.memory_row(phases=phases, planes=walk["planes"],
                            scopes=walk["scopes"],
                            walker_peak_bytes=walk["peak_bytes"])
    return _wrap(prof.row_fingerprint(config), "serve-tick", "single",
                 memrow)


PREFIX_CAPACITY = 32  # the RadixPrefixCache default in serve/scheduler.py


def _serve_prefix_mem_row(make_cfg) -> dict:
    """The radix prefix cache's worst-case residency: ``capacity``
    retained batch-1 prefill payloads (first_logits + per-layer k/v —
    int8 values AND their f32 scale planes when quantized) held beside
    the serving arena.  Analytic by construction: the cache is host-side
    bookkeeping over device payloads, there is no program to walk — the
    payload is sized via eval_shape on the same ``DALLE.prefill`` the
    scheduler admits from, so a cache-layout change moves this row."""
    cfg = make_cfg()
    dalle = DALLE(cfg)
    text = _sds((1, cfg.text_seq_len), jnp.int32)
    codes = _sds((1, cfg.image_seq_len), jnp.int32)
    variables = jax.eval_shape(dalle.init, jax.random.PRNGKey(0), text,
                               codes)
    first_logits, caches = jax.eval_shape(
        lambda v, t: dalle.apply(v, t, method=DALLE.prefill), variables,
        text)
    logits_b = mem.tree_bytes(first_logits)
    cache_b = mem.tree_bytes(caches)
    total = PREFIX_CAPACITY * (logits_b + cache_b)
    phases = {"prefix_full": int(total)}
    config = graftprof._cfg_payload(cfg, target="serve-prefix",
                                    plan="single", batch=1,
                                    capacity=PREFIX_CAPACITY)
    memrow = mem.memory_row(
        phases=phases,
        planes={"prefix-payloads": int(total)},
        scopes={"attn-cache": int(PREFIX_CAPACITY * cache_b),
                "logits-head": int(PREFIX_CAPACITY * logits_b)},
        walker_peak_bytes=int(total),
        note=f"analytic: capacity {PREFIX_CAPACITY} x batch-1 prefill "
             f"payload ({logits_b + cache_b} B)")
    return _wrap(prof.row_fingerprint(config), "serve-prefix", "single",
                 memrow)


# --- sweep -----------------------------------------------------------------


def sweep(quick: bool = False, targets_filter=None) -> dict:
    """Recompute every memory row.  Returns {fingerprint: wrapped row}."""
    make_cfg = spmd_check.tiny_config if quick else spmd_check.cub_config
    builders = []
    for plan in PLANS:
        builders.append((f"dalle/{plan}",
                         lambda p=plan: _dalle_mem_row(p, make_cfg)))
    if not quick:
        builders.append(("dalle/cub-512",
                         lambda: _scale_mem_row("cub-512")))
        builders.append(("dalle/cub-1024",
                         lambda: _scale_mem_row("cub-1024")))
    builders.append(("vae", lambda: _vae_mem_row(quick)))
    builders.append(("clip", lambda: _clip_mem_row(quick)))
    builders.append(("decode", lambda: _decode_mem_row(make_cfg)))
    builders.append(("serve-tick", lambda: _serve_mem_row(make_cfg)))
    builders.append(("serve-prefix",
                     lambda: _serve_prefix_mem_row(make_cfg)))

    rows = {}
    for label, build in builders:
        if targets_filter and not any(t in label for t in targets_filter):
            continue
        row = build()
        rows[row["fingerprint"]] = row
        m = row["memory"]
        verdict = m["headroom"][CHIP]
        print(f"  {row['target']:>18} [{row['plan']}] "
              f"fp={row['fingerprint']} "
              f"peak={verdict['peak_bytes'] / 2**20:.0f} MiB "
              f"@{verdict['peak_phase']} "
              f"headroom={verdict['headroom_frac']:.0%} "
              f"fits[{CHIP}]={'yes' if verdict['fits'] else 'NO'}")
    return rows


# --- report ----------------------------------------------------------------


def render_report(ledger: dict) -> str:
    """Predicted-vs-measured memory in one table (read-only)."""
    head = (f"{'target':>18} {'plan':>10} {'fp':>12} {'peak':>10} "
            f"{'phase':>12} {'headroom':>9} {'fits':>5} {'measured':>22}")
    lines = ["graftmem ledger report", head, "-" * len(head)]
    for fp, row in sorted(ledger.get("rows", {}).items(),
                          key=lambda kv: (kv[1].get("target", ""),
                                          kv[1].get("plan", ""))):
        m = row.get("memory")
        if not m:
            continue
        verdict = m.get("headroom", {}).get(CHIP, {})
        meas = m.get("measured") or []
        last = meas[-1] if meas else {}
        meas_txt = ("-" if not last else " ".join(
            f"{k}={last[k]:.4g}" if isinstance(last[k], float)
            else f"{k}={last[k]}"
            for k in sorted(last) if k not in ("t",)))
        peak = verdict.get("peak_bytes")
        peak_txt = (f"{peak / 2**20:.0f} MiB"
                    if isinstance(peak, (int, float)) else "-")
        hr = verdict.get("headroom_frac")
        hr_txt = f"{hr:.0%}" if isinstance(hr, (int, float)) else "-"
        fits = verdict.get("fits")
        lines.append(
            f"{row.get('target', '?'):>18} {row.get('plan', '?'):>10} "
            f"{fp:>12} {peak_txt:>10} "
            f"{verdict.get('peak_phase', '-'):>12} {hr_txt:>9} "
            f"{'yes' if fits else 'NO' if fits is not None else '-':>5} "
            f"{meas_txt[:22]:>22}")
    lines.append("")
    lines.append(f"peak/headroom rendered against {CHIP}; measured rows "
                 "append via mem.append_measured_memory (MemTracker "
                 "watermarks on a real chip)")
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    mode = parser.add_mutually_exclusive_group(required=True)
    mode.add_argument("--update", action="store_true",
                      help="recompute memory rows and write the ledger")
    mode.add_argument("--check", action="store_true",
                      help="recompute and diff vs the committed ledger "
                           "(CI drift gate; exit 1 on >5% phase drift)")
    mode.add_argument("--report", action="store_true",
                      help="print predicted-vs-measured memory from the "
                           "ledger")
    parser.add_argument("--quick", action="store_true",
                        help="tiny geometry (tests); rows fingerprint "
                             "differently from the CUB sweep")
    parser.add_argument("--targets", nargs="+", default=None,
                        help="substring filter over target names")
    parser.add_argument("--ledger", type=Path, default=None,
                        help="ledger path (default: committed "
                             "PERF_LEDGER.json, GRAFT_PERF_LEDGER env "
                             "overrides)")
    parser.add_argument("--json", type=Path, default=None,
                        help="also write the mode's result as JSON")
    args = parser.parse_args(argv)
    path = args.ledger or prof.ledger_path()

    if args.report:
        ledger = prof.load_ledger(path)
        out = render_report(ledger)
        print(out)
        if args.json:
            args.json.write_text(json.dumps(ledger, indent=1) + "\n")
        return 0

    print(f"graftmem sweep ({'tiny' if args.quick else 'CUB'} geometry, "
          f"verdicts vs {CHIP}):")
    rows = sweep(quick=args.quick, targets_filter=args.targets)

    if args.update:
        ledger = prof.load_ledger(path)
        if not args.targets:
            # full sweep: retired memory sub-rows leave the ledger (the
            # graftprof fields and measured-only stub rows stay)
            for fp, r in ledger["rows"].items():
                if fp not in rows and "phases" in r.get("memory", {}):
                    meas = r["memory"].get("measured")
                    r["memory"] = {"measured": meas} if meas else {}
                    if not r["memory"]:
                        del r["memory"]
        for row in rows.values():
            mem.upsert_memory(ledger, row["fingerprint"], row["memory"],
                              target=row["target"], plan=row["plan"])
        out_path = prof.save_ledger(ledger, path)
        print(f"wrote {len(rows)} memory row(s) -> {out_path}")
        if args.json:
            args.json.write_text(json.dumps(ledger, indent=1) + "\n")
        return 0

    # --check: the drift gate
    ledger = prof.load_ledger(path)
    if args.targets:
        scoped = {fp for fp, r in ledger["rows"].items()
                  if any(t in str(r.get("target")) for t in args.targets)}
        committed = {"rows": {fp: r for fp, r in ledger["rows"].items()
                              if fp in scoped}}
    else:
        committed = ledger
    problems = mem.diff_memory(committed,
                               {fp: r["memory"] for fp, r in rows.items()})
    doc = {"tool": "graftmem", "mode": "check", "chip": CHIP,
           "quick": args.quick, "problems": problems,
           "rows_checked": len(rows)}
    if args.json:
        args.json.write_text(json.dumps(doc, indent=1) + "\n")
    if problems:
        print(f"\ngraftmem drift gate: {len(problems)} problem(s)")
        for p in problems:
            print(f"  DRIFT {p}")
        return 1
    print(f"\ngraftmem drift gate: green ({len(rows)} memory row(s) match "
          "the committed ledger)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
