#!/usr/bin/env python
"""graftprof: per-module roofline attribution + the committed perf ledger.

Walks the jaxpr of every ``training.STEP_FACTORIES`` entry under its
parallelism plans — plus the decode scan and the serving arena tick — at
the production CUB geometry, attributes analytic flops / bytes to the
``graftprof:`` cost scopes threaded through the models
(``dalle_pytorch_tpu/obs/prof.py``), folds in the chip-spec roofline
(v4-8 / v5e-4), and maintains the committed ``PERF_LEDGER.json``:
config fingerprint -> per-scope flops/bytes -> predicted MFU ceiling.

Chip-free by construction (the same 8-device virtual CPU mesh as
``tools/spmd_check.py``, whose harness this reuses): every number here is
computable on a laptop with no chip attached.  They are predictions —
planning aids, never device measurements.

Modes:
    --update   recompute all rows, merge (preserving measured history),
               write the ledger
    --check    recompute and diff against the committed ledger — the CI
               drift gate: exit 1 on >2% flops / >5% bytes drift without
               a ledger update
    --report   read-only predicted-vs-measured table from the ledger
               (no jax work; runs on a wedged box)
    --quick    tiny geometry instead of CUB (tests / smoke)
    --targets  substring filter over target names
    --json     machine-readable output next to the human table

Shard-map plans (sp-ring / sp-ulysses / pp) trace one shard's program;
their walker numbers are scaled by the mesh device count to recover the
global figures — an approximation (ring exchanges and the pipeline
bubble are not charged), held stable by construction so the drift gate
stays exact.

Usage:
    python tools/graftprof.py --update
    python tools/graftprof.py --check            # CI
    python tools/graftprof.py --report
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

# spmd_check owns the chip-free env preamble (CPU backend + 8 virtual
# devices BEFORE jax initializes) and the plan/geometry harness; load it
# as a module (tools/ is not a package).
_spec = importlib.util.spec_from_file_location(
    "spmd_check", Path(__file__).resolve().parent / "spmd_check.py")
spmd_check = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(spmd_check)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dalle_pytorch_tpu.lint import spmd  # noqa: E402
from dalle_pytorch_tpu.models.clip import CLIP, CLIPConfig  # noqa: E402
from dalle_pytorch_tpu.models.dalle import DALLE  # noqa: E402
from dalle_pytorch_tpu.models.vae import DiscreteVAE, VAEConfig  # noqa: E402
from dalle_pytorch_tpu.obs import prof  # noqa: E402
from dalle_pytorch_tpu.parallel.mesh import make_mesh  # noqa: E402
from dalle_pytorch_tpu.serve.engine import SlotArena  # noqa: E402
from dalle_pytorch_tpu.training import (make_clip_train_step,  # noqa: E402
                                        make_dalle_pp_train_step,
                                        make_dalle_sp_train_step,
                                        make_dalle_train_step, make_optimizer,
                                        make_vae_train_step)

PLANS = spmd_check.PLANS
CHIP = "v4-8"          # the pod the roofline is rendered against
TRAIN_BATCH = 8        # spmd_check's harness batch (pp microbatch law)
DECODE_BATCH = 8
SERVE_SLOTS = 8
_sds = spmd_check._sds


def _cfg_payload(cfg, **extra) -> dict:
    """Fingerprint payload of one geometry: the dataclass fields (dtype
    et al. stringified by row_fingerprint's canonical JSON) + the sweep
    knobs.  A measured run hashes the SAME payload to land beside its
    prediction — the one shared implementation lives in obs.prof."""
    return prof.fingerprint_payload(cfg, **extra)


def _compiled_stats(lowered, arg_labels=None, donate=(0, 1)) -> dict:
    """XLA's own numbers for a lowered program at OPT0 (the spmd_check S4
    convention: buffer assignment matches the full pipeline, compile is
    cheap).  ``donated_bytes`` substitutes the donation-audit fraction
    for the alias stat opt0 zeroes (the _s4_detail substitution) — the
    field the dropped-donation twin trips."""
    with spmd.fresh_stats_compile():
        compiled = lowered.compile(spmd_check.OPT0)
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    est = spmd.hbm_estimate(compiled)
    out = {
        "flops": int(ca.get("flops", 0.0)),
        "bytes_accessed": int(ca.get("bytes accessed", 0.0)),
        "argument_bytes": est.argument_bytes,
        "output_bytes": est.output_bytes,
        "temp_bytes": est.temp_bytes,
    }
    if arg_labels is not None:
        audit = spmd.audit_donation(lowered, arg_labels, donate)
        out["donated_bytes"] = int(audit.donated_fraction
                                   * est.argument_bytes)
    return out


def _traffic(compiled_stats) -> int:
    """Per-device HBM stream of one step for the roofline byte-time:
    arguments + outputs + temps of the compiled program (opt0-stable)."""
    return (compiled_stats["argument_bytes"] + compiled_stats["output_bytes"]
            + compiled_stats["temp_bytes"])


# --- per-target builders ---------------------------------------------------


def _dalle_plan_row(plan: str, make_cfg) -> dict:
    """One DALLE train-step row: jaxpr attribution (scaled to global
    figures under shard_map plans) + opt0 compiled stats."""
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
        opt = jax.eval_shape(tx.init, pp_params)
        args = (pp_params, opt, None, text, codes, rng, fs)
        per_shard = True
    elif cfg.ring_axis is not None:
        step = make_dalle_sp_train_step(dalle, tx, mesh, health=True)
        opt = jax.eval_shape(tx.init, params)
        args = (params, opt, None, text, codes, rng, fs)
        per_shard = True
    else:
        step = make_dalle_train_step(dalle, tx, health=True)
        opt = jax.eval_shape(tx.init, params)
        args = (params, opt, None, text, codes, rng, fs)
        per_shard = False
    attr = prof.attribute(jax.make_jaxpr(step)(*args),
                          scale=devices if per_shard else 1)
    factory = ("dalle_pp" if plan == "pp"
               else "dalle_sp" if cfg.ring_axis is not None else "dalle")
    target = f"{factory}/{plan}"
    prof.check_coverage(attr, label=target)
    compiled = _compiled_stats(spmd_check.dalle_step_lowered(
        plan, make_cfg=make_cfg, batch=TRAIN_BATCH),
        arg_labels=spmd_check.DALLE_ARG_LABELS)
    roof = prof.roofline(attr, CHIP, traffic_bytes=_traffic(compiled))
    config = _cfg_payload(cfg, target=target, plan=plan, batch=TRAIN_BATCH)
    return prof.predicted_row(target=target, plan=plan, chip=CHIP,
                              config=config, attr=attr, roof=roof,
                              compiled=compiled)


def _scale_row(plan: str) -> dict:
    """A scale rung's row (presets.SCALE_PRESETS geometry under its
    registry plan): walker-only — no opt0 compile (dim-512 compiles for
    ~8 minutes, dim-1024 longer; the full S4 proof is ``spmd_check
    --presets``' nightly concern, cached in S4_PROOFS.json), the same
    carve-out as the decode row.  The memory twin in ``tools/graftmem.py``
    gives each rung its binding headroom verdict."""
    from dalle_pytorch_tpu.presets import preset_config

    cfg = preset_config(plan)
    dalle = DALLE(cfg)
    tx = make_optimizer(1e-3)
    text = _sds((TRAIN_BATCH, cfg.text_seq_len), jnp.int32)
    codes = _sds((TRAIN_BATCH, cfg.image_seq_len), jnp.int32)
    rng = _sds((2,), jnp.uint32)
    fs = _sds((), jnp.float32)
    params = jax.eval_shape(dalle.init, jax.random.PRNGKey(0), text,
                            codes)["params"]
    opt = jax.eval_shape(tx.init, params)
    step = make_dalle_train_step(dalle, tx, health=True)
    attr = prof.attribute(jax.make_jaxpr(step)(params, opt, None, text,
                                               codes, rng, fs))
    target = f"dalle/{plan}"
    prof.check_coverage(attr, label=target)
    roof = prof.roofline(attr, CHIP)
    config = _cfg_payload(cfg, target=target, plan=plan, batch=TRAIN_BATCH)
    return prof.predicted_row(target=target, plan=plan, chip=CHIP,
                              config=config, attr=attr, roof=roof)


def _vae_cfg(quick: bool) -> VAEConfig:
    if quick:
        return VAEConfig(image_size=16, num_tokens=16, codebook_dim=16,
                         num_layers=1, hidden_dim=16)
    # the reference stage-1 geometry (ref train_vae.py:42-59)
    return VAEConfig(image_size=128, num_tokens=8192, codebook_dim=512,
                     num_layers=2, num_resnet_blocks=2, hidden_dim=256)


def _vae_row(quick: bool) -> dict:
    cfg = _vae_cfg(quick)
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
    attr = prof.attribute(jax.make_jaxpr(step)(*args))
    prof.check_coverage(attr, label="vae")
    compiled = _compiled_stats(step.lower(*args),
                               arg_labels=spmd_check.VAE_ARG_LABELS)
    roof = prof.roofline(attr, CHIP, traffic_bytes=_traffic(compiled),
                         devices=1)
    config = _cfg_payload(cfg, target="vae", plan="single",
                          batch=TRAIN_BATCH)
    return prof.predicted_row(target="vae", plan="single", chip=CHIP,
                              config=config, attr=attr, roof=roof,
                              compiled=compiled)


def _clip_cfg(quick: bool) -> CLIPConfig:
    if quick:
        return CLIPConfig(dim_text=16, dim_image=16, dim_latent=16,
                          num_text_tokens=64, text_enc_depth=1,
                          text_seq_len=8, text_heads=2,
                          num_visual_tokens=64, visual_enc_depth=1,
                          visual_heads=2, visual_image_size=16,
                          visual_patch_size=8)
    # the CUB-shaped ViT-B/32 ranker geometry
    return CLIPConfig(dim_text=256, dim_image=256, dim_latent=256,
                      num_text_tokens=7800, text_enc_depth=4,
                      text_seq_len=80, text_heads=8, num_visual_tokens=512,
                      visual_enc_depth=6, visual_heads=8,
                      visual_image_size=224, visual_patch_size=32)


def _clip_row(quick: bool) -> dict:
    cfg = _clip_cfg(quick)
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
    # the CLIP towers carry no graftprof scopes of their own yet — the
    # whole model is one "clip" cost center (the embed/logits split is a
    # DALLE/VAE concern); default_scope keeps the coverage gate honest
    attr = prof.attribute(jax.make_jaxpr(step)(*args),
                          default_scope="clip")
    prof.check_coverage(attr, label="clip")
    compiled = _compiled_stats(step.lower(*args),
                               arg_labels=spmd_check.CLIP_ARG_LABELS)
    roof = prof.roofline(attr, CHIP, traffic_bytes=_traffic(compiled),
                         devices=1)
    config = _cfg_payload(cfg, target="clip", plan="single",
                          batch=TRAIN_BATCH)
    return prof.predicted_row(target="clip", plan="single", chip=CHIP,
                              config=config, attr=attr, roof=roof,
                              compiled=compiled)


def _decode_row(make_cfg) -> dict:
    """The sampling scan (prefill state -> full image code sequence) —
    spmd_check's decode harness, attributed per scope.  No compile (the
    1000-step scan at CUB is jaxpr-walkable in seconds but minutes to
    compile); the roofline reads the walker bytes."""
    jaxpr = spmd_check.decode_jaxpr(make_cfg=make_cfg, batch=DECODE_BATCH)
    attr = prof.attribute(jaxpr)
    prof.check_coverage(attr, label="decode")
    roof = prof.roofline(attr, CHIP, devices=1)
    cfg = make_cfg()
    config = _cfg_payload(cfg, target="decode", plan="single",
                          batch=DECODE_BATCH)
    return prof.predicted_row(target="decode", plan="single", chip=CHIP,
                              config=config, attr=attr, roof=roof)


def _serve_tick_row(make_cfg) -> dict:
    """One continuous-batching arena tick (serve/engine.py), all slots
    advancing.  The row carries ``serve.predicted_bytes_per_token`` —
    the number GenerationServer.stats() / the /metrics serve instruments
    export."""
    cfg = make_cfg()
    dalle = DALLE(cfg)
    text = jnp.zeros((1, cfg.text_seq_len), jnp.int32)
    codes = jnp.zeros((1, cfg.image_seq_len), jnp.int32)
    variables = jax.eval_shape(dalle.init, jax.random.PRNGKey(0), text,
                               codes)
    # a real SlotArena on zeroed params — the tick jaxpr IS the serving
    # program (same closure GenerationServer jits), every slot advancing
    arena = SlotArena(
        dalle, jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            variables),
        num_slots=SERVE_SLOTS)
    active = jnp.ones((SERVE_SLOTS,), bool)
    write_pos = jnp.int32(0)
    jaxpr = jax.make_jaxpr(arena._tick)(
        arena.variables, arena.state, active, write_pos, arena._qweights)
    attr = prof.attribute(jaxpr)
    prof.check_coverage(attr, label="serve-tick")
    roof = prof.roofline(attr, CHIP, devices=1)
    config = _cfg_payload(cfg, target="serve-tick", plan="single",
                          batch=SERVE_SLOTS, num_slots=SERVE_SLOTS)
    row = prof.predicted_row(target="serve-tick", plan="single", chip=CHIP,
                             config=config, attr=attr, roof=roof)
    row["serve"] = {"num_slots": SERVE_SLOTS,
                    "predicted_bytes_per_token":
                        prof.predicted_serve_bytes_per_token(cfg,
                                                             SERVE_SLOTS)}
    return row


# --- sweep -----------------------------------------------------------------


def sweep(quick: bool = False, targets_filter=None) -> dict:
    """Recompute every predicted row.  Returns {fingerprint: row}."""
    make_cfg = spmd_check.tiny_config if quick else spmd_check.cub_config
    builders = []
    for plan in PLANS:
        builders.append((f"dalle/{plan}",
                         lambda p=plan: _dalle_plan_row(p, make_cfg)))
    if not quick:
        # the scale rungs ride the full sweep only (their point is the
        # real dim-512/dim-1024 geometry; quick twins would fingerprint
        # apart)
        builders.append(("dalle/cub-512", lambda: _scale_row("cub-512")))
        builders.append(("dalle/cub-1024", lambda: _scale_row("cub-1024")))
    builders.append(("vae", lambda: _vae_row(quick)))
    builders.append(("clip", lambda: _clip_row(quick)))
    builders.append(("decode", lambda: _decode_row(make_cfg)))
    builders.append(("serve-tick", lambda: _serve_tick_row(make_cfg)))

    rows = {}
    for label, build in builders:
        if targets_filter and not any(t in label for t in targets_filter):
            continue
        row = build()
        rows[row["fingerprint"]] = row
        roof = row["roofline"]
        print(f"  {row['target']:>18} [{row['plan']}] "
              f"fp={row['fingerprint']} "
              f"pred_mfu={roof['predicted_mfu']:.3f} "
              f"bound={roof['bound']} "
              f"residual f={row['residual']['flops']:.1%} "
              f"b={row['residual']['bytes']:.1%}")
    return rows


# --- report ----------------------------------------------------------------


def render_report(ledger: dict) -> str:
    """Predicted-vs-measured in one table (read-only: no jax work)."""
    head = (f"{'target':>18} {'plan':>10} {'fp':>12} {'pred mfu':>8} "
            f"{'bound':>5} {'measured':>24} {'gap':>6}")
    lines = ["graftprof ledger report", head, "-" * len(head)]
    for fp, row in sorted(ledger.get("rows", {}).items(),
                          key=lambda kv: (kv[1].get("target", ""),
                                          kv[1].get("plan", ""))):
        roof = row.get("roofline", {})
        pred = roof.get("predicted_mfu")
        meas = row.get("measured") or []
        last = meas[-1] if meas else {}
        meas_txt = ("-" if not last else " ".join(
            f"{k}={last[k]:.4g}" if isinstance(last[k], float)
            else f"{k}={last[k]}"
            for k in sorted(last) if k not in ("t",)))
        gap = "-"
        if pred and isinstance(last.get("mfu"), (int, float)) and pred > 0:
            gap = f"{last['mfu'] / pred:.0%}"
        pred_txt = f"{pred:.3f}" if isinstance(pred, (int, float)) else "-"
        lines.append(
            f"{row.get('target', '?'):>18} {row.get('plan', '?'):>10} "
            f"{fp:>12} {pred_txt:>8} "
            f"{roof.get('bound', '-'):>5} {meas_txt[:24]:>24} {gap:>6}")
    lines.append("")
    lines.append("gap = measured MFU / predicted ceiling; measured rows "
                 "append via prof.append_measured")
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    mode = parser.add_mutually_exclusive_group(required=True)
    mode.add_argument("--update", action="store_true",
                      help="recompute rows and write the ledger")
    mode.add_argument("--check", action="store_true",
                      help="recompute and diff vs the committed ledger "
                           "(CI drift gate; exit 1 on drift)")
    mode.add_argument("--report", action="store_true",
                      help="print predicted-vs-measured from the ledger")
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

    print(f"graftprof sweep ({'tiny' if args.quick else 'CUB'} geometry, "
          f"chip {CHIP}):")
    rows = sweep(quick=args.quick, targets_filter=args.targets)

    if args.update:
        ledger = prof.load_ledger(path)
        if not args.targets:
            # full sweep: retired fingerprints leave the ledger (unless
            # they hold measured history worth keeping: stub rows stay)
            keep = {fp: r for fp, r in ledger["rows"].items()
                    if fp in rows or "total" not in r}
            ledger["rows"] = keep
        for row in rows.values():
            prof.upsert_predicted(ledger, row)
        out_path = prof.save_ledger(ledger, path)
        print(f"wrote {len(rows)} predicted row(s) -> {out_path}")
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
    problems = prof.diff_ledger(committed, rows)
    doc = {"tool": "graftprof", "mode": "check", "chip": CHIP,
           "quick": args.quick, "problems": problems,
           "rows_checked": len(rows)}
    if args.json:
        args.json.write_text(json.dumps(doc, indent=1) + "\n")
    if problems:
        print(f"\ngraftprof drift gate: {len(problems)} problem(s)")
        for p in problems:
            print(f"  DRIFT {p}")
        return 1
    print(f"\ngraftprof drift gate: green ({len(rows)} row(s) match the "
          "committed ledger)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
