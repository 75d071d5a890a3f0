#!/usr/bin/env python
"""graftspmd CLI — jaxpr-level SPMD analysis of every train-step factory.

graftlint reads source and contract_check reads shapes; this tool reads
the *traced programs*.  It builds every train-step factory in
``training.py`` (``STEP_FACTORIES``) plus the decode path in
``models/dalle.py`` under each parallelism plan on a virtual 8-device CPU
mesh and enforces four analyses (``dalle_pytorch_tpu/lint/spmd.py``):

* **S1 collective order** — the per-shard collective sequence is
  identical and unconditionally executed: any psum/ppermute/all_gather/
  all_to_all under data-dependent control flow (a ``while``, or ``cond``
  branches with differing collective signatures) is an SPMD deadlock.
* **S2 donation audit** — params and opt_state leaves of every donating
  jit actually alias outputs (``args_info`` + the optimized HLO's
  ``input_output_alias`` config — jax drops donation silently when a
  donated input matches no output), and large (>1 MiB) undonated array
  args are reported.
* **S3 retrace sentinel** — N simulated steps per factory trace exactly
  once; a weak-hash or unhashable static arg is a per-epoch recompile
  storm.
* **S4 static HBM budget** — per-device live bytes (args + outputs −
  donated aliases + peak XLA temporaries, ``memory_analysis()``) of each
  plan's step at the production CUB geometry must fit the target chip
  (``--chip v4-8|v5e-4|cpu-virtual``).

Zero chip time by the same construction as contract_check: AOT trace/
lower/compile on CPU; only S3 executes, at toy geometry.  S2's alias
check compiles at TINY geometry and full optimization (donation
honoring is structural — and XLA's opt-level-0 path skips the alias
passes entirely, reporting alias=0 for honored donations); S4 compiles
the production geometry at backend optimization level 0 (argument/
output/temp buffer assignment is identical, ~10x faster codegen on one
core) and subtracts the S2-verified donated fraction in place of the
opt0-zeroed alias stat.  CI's lint job uploads the ``--json`` findings.

Usage:
    JAX_PLATFORMS=cpu python tools/spmd_check.py [--chip v4-8] [--quick]
    python tools/spmd_check.py --selftest   # prove S1-S4 catch fixtures

Exit 0 iff every analysis passes on every plan.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import os

# Chip-free by construction: an 8-device virtual CPU mesh, set up BEFORE
# jax initializes a backend, so the analyzer never claims a chip.
os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
        " --xla_force_host_platform_device_count=8"

import jax

from dalle_pytorch_tpu.cli import enable_compilation_cache

enable_compilation_cache()

import jax.numpy as jnp
import numpy as np

from dalle_pytorch_tpu import DALLE
from dalle_pytorch_tpu.lint import spmd
from dalle_pytorch_tpu.models.clip import CLIP, CLIPConfig
from dalle_pytorch_tpu.models.dalle import decode_codes
from dalle_pytorch_tpu.models.vae import DiscreteVAE, VAEConfig
from dalle_pytorch_tpu.parallel.mesh import Partitioner, make_mesh
from dalle_pytorch_tpu.training import (STEP_FACTORIES,
                                        make_clip_train_step,
                                        make_dalle_pp_train_step,
                                        make_dalle_sp_train_step,
                                        make_dalle_train_step, make_optimizer,
                                        make_vae_train_step)

# Backend optimization level 0 skips the LLVM codegen passes whose output
# S4 never reads — argument/output/temp buffer assignment is identical
# (measured on the CUB dp step) but the ALIAS stat is not: opt0 also
# skips XLA's input/output alias passes, so S2 never compiles with this
# and S4 substitutes the S2-verified donated fraction for the alias term.
OPT0 = {"xla_backend_optimization_level": 0}

# The factories this harness knows how to build and feed.  A new entry in
# training.STEP_FACTORIES without a harness here fails check_factory_
# coverage (and the tests/test_spmd_check.py meta-test).
HARNESSED_FACTORIES = frozenset(("vae", "dalle", "dalle_sp", "dalle_pp",
                                 "clip"))

# The parallelism plans of the DALLE model (contract_check C4's matrix
# plus pp) — GENERATED from the declarative plan registry
# (parallel/plan.py), not maintained beside it: the mesh kwargs, the
# DALLEConfig overrides, and the sharding expectations below all derive
# from the same ParallelPlan objects the trainers run, so this harness
# cannot drift from the production contract (ISSUE 10's single source of
# truth).  A new registry plan lands here automatically.
from dalle_pytorch_tpu.parallel.plan import PLAN_REGISTRY
from dalle_pytorch_tpu.presets import (SCALE_PRESETS, cub512_config,
                                       cub_config, tiny_config)

# Scale-preset rungs (cub-512: an ~8-minute opt0 compile at dim-512) are
# excluded from the per-push matrix; ``--presets`` runs their full S4.
PLANS = {name: dict(mesh=p.mesh_kwargs(), plan=p.config_overrides())
         for name, p in PLAN_REGISTRY.items() if name not in SCALE_PRESETS}

DALLE_ARG_LABELS = ("params", "opt_state", "vae_params", "text", "codes",
                    "rng", "fault_scale")
VAE_ARG_LABELS = ("params", "opt_state", "images", "rng", "temp",
                  "fault_scale")
CLIP_ARG_LABELS = ("params", "opt_state", "text", "images", "text_mask",
                   "fault_scale")


# --- geometries: tiny_config / cub_config / cub512_config re-exported
# above from dalle_pytorch_tpu.presets (contract_check's twins; ONE
# source for every scale rung) -------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _zeros_like_tree(sds_tree):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), sds_tree)


# --- per-factory setups ---------------------------------------------------


def dalle_step_lowered(plan: str, make_cfg=cub_config, batch: int = 8):
    """AOT-lower (and return labels for) the DALLE train step under one
    parallelism plan — health-enabled, donating, input shardings as the
    trainers place them (batch over the data axes, params as the
    Partitioner rules shard them, replicated under shard_map plans)."""
    spec = PLANS.get(plan) or dict(
        mesh=PLAN_REGISTRY[plan].mesh_kwargs(),
        plan=PLAN_REGISTRY[plan].config_overrides())
    cfg = make_cfg(**spec["plan"])
    dalle = DALLE(cfg)
    tx = make_optimizer(1e-3)
    mesh = make_mesh(**spec["mesh"])
    text = _sds((batch, cfg.text_seq_len), jnp.int32)
    codes = _sds((batch, cfg.image_seq_len), jnp.int32)
    rng = _sds((2,), jnp.uint32)
    fs = _sds((), jnp.float32)
    variables = jax.eval_shape(dalle.init, jax.random.PRNGKey(0), text,
                               codes)
    params = variables["params"]

    if plan == "pp":
        # the pp factory restructures CONCRETE params (stage stacking)
        step, pp_params = make_dalle_pp_train_step(
            dalle, tx, _zeros_like_tree(params), mesh, num_microbatches=2,
            health=True)
        opt = jax.eval_shape(tx.init, pp_params)
        lowered = step.lower(pp_params, opt, None, text, codes, rng, fs)
    elif cfg.ring_axis is not None:
        step = make_dalle_sp_train_step(dalle, tx, mesh, health=True)
        opt = jax.eval_shape(tx.init, params)
        lowered = step.lower(params, opt, None, text, codes, rng, fs)
    else:
        # the Partitioner derives from the plan object itself — the same
        # construction path the trainers take, so the shardings this
        # analysis gates ARE the shardings production runs
        pt = PLAN_REGISTRY[plan].partitioner(mesh=mesh)
        sharded = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            params, pt.param_shardings(params))
        opt = jax.eval_shape(tx.init, params)
        opt_sharded = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            opt, pt.param_shardings(opt))
        data = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
            s.shape, s.dtype, sharding=pt.data_sharding)
        step = make_dalle_train_step(dalle, tx, health=True, partitioner=pt)
        lowered = step.lower(sharded, opt_sharded, None, data(text),
                             data(codes), rng, fs)
    return lowered


def tiny_dalle_concrete(plan: str, batch: int = 8):
    # batch 8: under pp the per-microbatch rows (batch/2) must divide the
    # dp axis (4 ways on the 8-device (dp, pp) mesh)
    """Concrete tiny step + fresh-args generator for S1 (jaxpr) and S3
    (trace counting).  donate=False: S3 reuses the same concrete
    params/opt across simulated steps."""
    spec = PLANS[plan]
    cfg = tiny_config(**spec["plan"])
    dalle = DALLE(cfg)
    tx = make_optimizer(1e-3)
    mesh = make_mesh(**spec["mesh"])
    text = jnp.zeros((batch, cfg.text_seq_len), jnp.int32)
    codes = jnp.zeros((batch, cfg.image_seq_len), jnp.int32)
    variables = dalle.init(jax.random.PRNGKey(0), text, codes)
    params = variables["params"]
    if plan == "pp":
        step, params = make_dalle_pp_train_step(
            dalle, tx, params, mesh, num_microbatches=2, donate=False,
            health=True)
    elif cfg.ring_axis is not None:
        step = make_dalle_sp_train_step(dalle, tx, mesh, donate=False,
                                        health=True)
    else:
        step = make_dalle_train_step(dalle, tx, donate=False, health=True)
    opt = tx.init(params)

    def make_args(i):
        r = np.random.RandomState(i)
        return (params, opt, None,
                jnp.asarray(r.randint(1, 40, size=(batch, cfg.text_seq_len)),
                            jnp.int32),
                jnp.asarray(r.randint(0, cfg.num_image_tokens,
                                      size=(batch, cfg.image_seq_len)),
                            jnp.int32),
                jnp.asarray([i, i + 1], jnp.uint32), jnp.float32(1.0))

    # S2 for the dalle factories runs at production geometry via
    # dalle_step_lowered — no donating twin needed here
    return step, make_args, None


def tiny_vae_concrete(batch: int = 4):
    cfg = VAEConfig(image_size=16, num_tokens=16, codebook_dim=16,
                    num_layers=1, hidden_dim=16)
    vae = DiscreteVAE(cfg)
    tx = make_optimizer(1e-3)
    images = jnp.zeros((batch, 16, 16, 3), jnp.float32)
    params = vae.init(jax.random.PRNGKey(0), images,
                      rng=jax.random.PRNGKey(1))["params"]
    # donate=False for S3 (the same concrete params feed N simulated
    # steps); the donating twin the trainers actually run feeds S2
    step = make_vae_train_step(vae, tx, donate=False, health=True)
    donating = make_vae_train_step(vae, tx, health=True)
    opt = tx.init(params)

    def make_args(i):
        r = np.random.RandomState(i)
        return (params, opt,
                jnp.asarray(r.rand(batch, 16, 16, 3), jnp.float32),
                jnp.asarray([i, i + 1], jnp.uint32),
                jnp.float32(0.9 / (i + 1)), jnp.float32(1.0))

    return step, make_args, donating


def tiny_clip_concrete(batch: int = 4):
    cfg = CLIPConfig(dim_text=16, dim_image=16, dim_latent=16,
                     num_text_tokens=64, text_enc_depth=1, text_seq_len=8,
                     text_heads=2, num_visual_tokens=64, visual_enc_depth=1,
                     visual_heads=2, visual_image_size=16,
                     visual_patch_size=8)
    clip = CLIP(cfg)
    tx = make_optimizer(1e-3)
    text = jnp.zeros((batch, cfg.text_seq_len), jnp.int32)
    images = jnp.zeros((batch, 16, 16, 3), jnp.float32)
    mask = jnp.ones((batch, cfg.text_seq_len), bool)
    params = clip.init(jax.random.PRNGKey(0), text, images,
                       text_mask=mask)["params"]
    step = make_clip_train_step(clip, tx, donate=False, health=True)
    donating = make_clip_train_step(clip, tx, health=True)
    opt = tx.init(params)

    def make_args(i):
        r = np.random.RandomState(i)
        return (params, opt,
                jnp.asarray(r.randint(1, 63, size=(batch, cfg.text_seq_len)),
                            jnp.int32),
                jnp.asarray(r.rand(batch, 16, 16, 3), jnp.float32), mask,
                jnp.float32(1.0))

    return step, make_args, donating


TINY_FACTORY_SETUPS = {
    "vae": tiny_vae_concrete,
    "clip": tiny_clip_concrete,
    "dalle": lambda: tiny_dalle_concrete("dp"),
    "dalle_sp": lambda: tiny_dalle_concrete("sp-ring"),
    "dalle_pp": lambda: tiny_dalle_concrete("pp"),
}

FACTORY_ARG_LABELS = {
    "vae": VAE_ARG_LABELS,
    "clip": CLIP_ARG_LABELS,
    "dalle": DALLE_ARG_LABELS,
    "dalle_sp": DALLE_ARG_LABELS,
    "dalle_pp": DALLE_ARG_LABELS,
}


def decode_jaxpr(make_cfg=tiny_config, batch: int = 2):
    """Jaxpr of the sampling scan (prefill state -> image codes) — the
    decode path S1 walks.  Collective-free today; the analysis pins that
    a future sharded sampler cannot regress it silently."""
    cfg = make_cfg()
    dalle = DALLE(cfg)
    text = _sds((batch, cfg.text_seq_len), jnp.int32)
    codes = _sds((batch, cfg.image_seq_len), jnp.int32)
    variables = jax.eval_shape(dalle.init, jax.random.PRNGKey(0), text,
                               codes)
    logits, kvs = jax.eval_shape(
        lambda v, t: dalle.apply(v, t, method=DALLE.prefill), variables,
        text)
    rng = _sds((2,), jnp.uint32)

    def run(v, first_logits, caches, rng):
        return decode_codes(dalle, v, first_logits, caches, rng)

    return jax.make_jaxpr(run)(variables, logits, kvs, rng)


def serve_retrace_check(num_slots: int = 3, **cfg_overrides):
    """S3 for the continuous-batching serve tick (ISSUE 6): drive a real
    GenerationServer over the tiny model through admit/retire churn —
    occupancy rising 1 -> num_slots mid-flight, requests retiring at
    staggered ticks, a freed slot re-admitted, the arena clock wrapping
    seq_len — and require every jitted entry point (prefill / admit /
    tick) to have compiled EXACTLY once.  A per-occupancy or per-slot
    shape anywhere in the arena turns every arrival into a recompile on
    the pod (the storm `lint/spmd_fixtures.py::
    make_shape_changing_serve_tick` exhibits, proven caught in the
    selftest).  ``cfg_overrides`` select plan variants — the int8 arena
    (kv_cache_int8 + weights_int8, ISSUE 7) re-runs the same churn over
    the quantized cache/scale planes and the session-quantized weight
    arguments."""
    import numpy as np

    from dalle_pytorch_tpu.serve import GenerationServer

    cfg = tiny_config(**cfg_overrides)
    dalle = DALLE(cfg)
    text = jnp.zeros((1, cfg.text_seq_len), jnp.int32)
    codes = jnp.zeros((1, cfg.image_seq_len), jnp.int32)
    variables = dalle.init(jax.random.PRNGKey(0), text, codes)
    server = GenerationServer(dalle, variables, num_slots=num_slots,
                              filter_thres=0.9)

    def prompt(i):
        r = np.random.RandomState(i)
        return r.randint(1, 40, size=(cfg.text_seq_len,)).astype(np.int32)

    server.submit(prompt(0))
    for _ in range(3):                      # occupancy 1
        server.step()
    for i in range(1, num_slots):
        server.submit(prompt(i))            # fill mid-flight
    for _ in range(3):                      # occupancy num_slots
        server.step()
    server.submit(prompt(num_slots))        # queued; admits on first retire
    server.run_until_idle(max_ticks=40 * cfg.image_seq_len)
    assert server._clock > cfg.seq_len, "churn must wrap the arena clock"
    counts = server.trace_counts()
    bad = {k: v for k, v in counts.items() if v != 1}
    if bad:
        raise spmd.SPMDViolation(
            f"S3 retrace [serve-tick]: admit/retire churn across "
            f"occupancies 1..{num_slots} recompiled {bad} — a serve-path "
            "shape depends on occupancy/slot/clock; every arrival would "
            "recompile on the pod")
    return (f"{len(server.completed)} requests across occupancies "
            f"1..{num_slots}, clock wrapped at {server._clock} ticks: "
            "prefill/admit/tick each compiled once")


def pp_scan_schedule_check(microbatch_counts=(2, 4),
                           microbatch_rows: int = 8) -> str:
    """S1 for the pipeline plan's microbatch scan (PR 5 carried
    follow-up): per-body uniformity proves each scan iteration issues one
    lockstep collective sequence, but the pipeline's deadlock surface is
    the TOTAL schedule — iteration count x per-iteration sequence — across
    the GPipe scan.  Extract the schedule (``spmd.scan_collective_
    schedule``: static because scan's trip count is static and any
    collective under data-dependent control flow inside the body is
    refused) and prove it is exactly ``(m + pp - 1) x seq`` with the SAME
    per-iteration sequence at different microbatch counts — i.e. the knob
    that shapes the schedule scales only the iteration count, never the
    sequence the stages must agree on."""
    spec = PLANS["pp"]
    pp_ways = spec["mesh"]["pp"]
    mesh = make_mesh(**spec["mesh"])
    cfg = tiny_config(**spec["plan"])
    dalle = DALLE(cfg)
    tx = make_optimizer(1e-3)
    init_text = jnp.zeros((2, cfg.text_seq_len), jnp.int32)
    init_codes = jnp.zeros((2, cfg.image_seq_len), jnp.int32)
    params = dalle.init(jax.random.PRNGKey(0), init_text,
                        init_codes)["params"]
    rng = jnp.zeros((2,), jnp.uint32)
    fs = jnp.float32(1.0)

    schedules = {}
    for m in microbatch_counts:
        # batch scales with m so the MICROBATCH geometry (what one scan
        # iteration actually moves) is held constant — the comparison below
        # is then exact down to operand shapes, not just primitive order
        batch = microbatch_rows * m
        text = jnp.zeros((batch, cfg.text_seq_len), jnp.int32)
        codes = jnp.zeros((batch, cfg.image_seq_len), jnp.int32)
        step, pp_params = make_dalle_pp_train_step(
            dalle, tx, params, mesh, num_microbatches=m, donate=False,
            health=True)
        opt = jax.eval_shape(tx.init, pp_params)
        jaxpr = jax.make_jaxpr(step)(pp_params, opt, None, text, codes,
                                     rng, fs)
        scans = spmd.scan_collective_schedule(jaxpr, label=f"dalle_pp/m{m}")
        if not scans:
            raise spmd.SPMDViolation(
                f"S1 scan schedule [dalle_pp/m{m}]: no collective-bearing "
                "scan found — the GPipe microbatch scan lost its stage "
                "handoffs (or the analysis no longer sees them)")
        # the microbatch scan is the one whose trip count is m + pp - 1
        # (forward) — the backward scan mirrors it with the transposed
        # collectives, so every entry must obey the same law
        expect_len = m + pp_ways - 1
        bad = [s for s in scans if s.length != expect_len]
        if bad:
            raise spmd.SPMDViolation(
                f"S1 scan schedule [dalle_pp/m{m}]: collective-bearing "
                f"scan(s) with trip count != microbatches + stages - 1 "
                f"({expect_len}): "
                + "; ".join(s.format() for s in bad))
        schedules[m] = scans

    counts = {m: len(s) for m, s in schedules.items()}
    if len(set(counts.values())) != 1:
        raise spmd.SPMDViolation(
            f"S1 scan schedule [dalle_pp]: different numbers of "
            f"collective-bearing scans across microbatch counts ({counts})")
    m0 = microbatch_counts[0]
    for m in microbatch_counts[1:]:
        for a, b in zip(schedules[m0], schedules[m]):
            if a.per_iteration != b.per_iteration:
                raise spmd.SPMDViolation(
                    "S1 scan schedule [dalle_pp]: the per-iteration "
                    f"collective sequence CHANGES with the microbatch "
                    f"count (m={m0}: {a.format()} vs m={m}: {b.format()}) "
                    "— the schedule is not iteration-count x sequence, so "
                    "stages disagreeing on the count deadlock")
    detail = "; ".join(
        f"m={m}: " + " + ".join(s.format() for s in schedules[m])
        for m in microbatch_counts)
    return f"schedule is (m + pp - 1) x fixed sequence — {detail}"


def s4_drift_check(plan: str = "dp", make_cfg=cub_config,
                   temp_tol: float = 0.15) -> str:
    """S4 opt-0 drift gate (PR 5 carried follow-up): S4 budgets every plan
    from a backend-opt-level-0 compile on the assumption that XLA's
    argument/output/temp buffer assignment is identical to the full
    pipeline's.  That held when measured, but nothing pins it across XLA
    upgrades — so compile ONE plan BOTH ways and diff: argument and
    output bytes must match exactly, temp bytes within ``temp_tol``.
    Scheduled CI runs this (tests.yml full job); a failure means the
    opt-0 shortcut now under- or over-budgets and S4 must recalibrate."""
    lowered = dalle_step_lowered(plan, make_cfg=make_cfg)
    with spmd.fresh_stats_compile():
        full = spmd.hbm_estimate(lowered.compile())
        opt0 = spmd.hbm_estimate(lowered.compile(OPT0))
    problems = []
    for field in ("argument_bytes", "output_bytes"):
        a, b = getattr(full, field), getattr(opt0, field)
        if a != b:
            problems.append(f"{field}: full-opt {a} != opt0 {b}")
    drift = abs(opt0.temp_bytes - full.temp_bytes) / max(full.temp_bytes, 1)
    if drift > temp_tol:
        problems.append(
            f"temp_bytes: full-opt {full.temp_bytes} vs opt0 "
            f"{opt0.temp_bytes} ({drift:.1%} > {temp_tol:.0%})")
    if problems:
        raise spmd.SPMDViolation(
            f"S4 opt0-drift [dalle/{plan}]: " + "; ".join(problems) +
            " — XLA's opt-0 buffer assignment no longer matches the full "
            "pipeline; the S4 budget shortcut is invalid")
    return (f"opt0 == full-opt: args {full.argument_bytes}, out "
            f"{full.output_bytes}, temp drift {drift:.1%}")


def proofs_path() -> Path:
    """The committed S4 proof cache: GRAFT_S4_PROOFS env override (tests,
    scratch runs) > repo-root S4_PROOFS.json."""
    env = os.environ.get("GRAFT_S4_PROOFS")
    return Path(env) if env else REPO / "S4_PROOFS.json"


def _preset_proof_fingerprint(name: str, cfg) -> str:
    """Key of one rung's compiled proof: geometry + registry plan +
    harness point + the jax that compiled it.  Any edit that could change
    buffer assignment re-keys the proof, so a stale cache can never gate."""
    from dalle_pytorch_tpu.obs import prof

    return prof.row_fingerprint(prof.fingerprint_payload(
        cfg, target=f"s4-proof/{name}", plan=PLAN_REGISTRY[name].spec(),
        batch=8, devices=len(jax.devices()), opt0=True,
        jax=jax.__version__))


#: Declared opt0 verdict per rung against the gate chip — the PERF_LEDGER
#: ``fits: false`` pattern applied to the compiled proof.  "fits": the
#: estimate must pass check_hbm_budget (the normal gate).  "over": the
#: rung is KNOWN not to prove fit at opt0 — XLA's opt0 buffer assignment
#: does not reuse buffers across the per-block remat regions, so the
#: cub-1024 temp stat is the *sum* of all 76 blocks' internals (~132 GiB
#: at batch 8) while the liveness-aware jaxpr walker peaks at ~10.7
#: GiB/device.  For an "over" rung the compiled proof is still committed
#: and still gates — as a drift sentinel: the compile must succeed AND
#: the estimate must still exceed the budget.  If a geometry/remat/XLA
#: change makes it FIT, that is news the gate surfaces; flip the entry
#: deliberately.  The fit verdict itself at an "over" rung is owned by
#: the analytic P3 state check (lint/plans.py) and the walker timeline
#: (tools/graftmem.py), both committed to PERF_LEDGER.json.
S4_PRESET_EXPECT = {"cub-512": "fits", "cub-1024": "over"}


def _gate_preset_estimate(name: str, est, chip: str) -> str:
    """Gate one rung's compiled estimate against its DECLARED verdict
    (:data:`S4_PRESET_EXPECT`).  Returns the PASS-line detail; raises
    SPMDViolation on any mismatch in either direction."""
    expect = S4_PRESET_EXPECT.get(name, "fits")
    try:
        spmd.check_hbm_budget(est, chip, label=f"preset/{name}@{chip}")
        verdict = "fits"
    except spmd.SPMDViolation as over:
        if expect == "fits":
            raise
        verdict = "over"
    if verdict == "over":
        return ("over budget as declared (opt0 assignment is reuse-free "
                "across remat blocks; P3 + the walker own the fit "
                "verdict at this rung)")
    if expect == "over":
        raise spmd.SPMDViolation(
            f"S4 hbm [preset/{name}@{chip}]: the estimate now FITS the "
            "budget but S4_PRESET_EXPECT declares the rung over — the "
            "opt0 verdict changed under you (geometry/remat/jax edit); "
            "flip the expectation to 'fits' deliberately and commit")
    return "fits budget"


def run_presets(chip: str = "v5e-4", only=None, refresh: bool = False) -> int:
    """The scale-preset S4 proof (``--presets``): for every
    presets.SCALE_PRESETS rung, lower the real train step at the rung's
    geometry under the rung's registry plan and gate the opt0 HBM
    estimate (with the S2-verified donation credit substituted, the
    _s4_detail convention) against ``chip`` — through the rung's
    declared verdict (:data:`S4_PRESET_EXPECT`): a "fits" rung must
    pass the budget, an "over" rung must still measure over (the
    drift-sentinel form; see the table's docstring).  Minutes per rung at
    dim-512, tens of minutes at dim-1024 — so the compiled estimate is
    persisted to S4_PROOFS.json keyed by a config fingerprint: when the
    stored key matches, the rung re-gates the cached estimate against
    the requested chip WITHOUT recompiling (the budget check is
    arithmetic; the 8-minute compile only re-runs when geometry, plan,
    harness point, or jax version actually changed — or under
    ``--refresh-proofs``).  ``only`` filters to one rung.  Nightly CI
    carries the gate;
    contract_check covers the cheap per-push half (param band +
    shardings lower)."""
    from dalle_pytorch_tpu.presets import check_param_band

    ppath = proofs_path()
    proofs = json.loads(ppath.read_text()) if ppath.exists() else {}
    failures = 0
    dirty = False
    rungs = {k: v for k, v in sorted(SCALE_PRESETS.items())
             if only is None or k == only}
    if only is not None and not rungs:
        print(f"spmd_check --presets: unknown rung {only!r}; known: "
              f"{sorted(SCALE_PRESETS)}", file=sys.stderr)
        return 2
    for name, make_cfg in rungs.items():
        t0 = time.time()
        try:
            band = check_param_band(name)
            fp = _preset_proof_fingerprint(name, make_cfg())
            proof = proofs.get(name)
            if proof and proof.get("fingerprint") == fp and not refresh:
                est = spmd.HBMEstimate(**proof["estimate"])
                detail = _gate_preset_estimate(name, est, chip)
                print(f"PASS S4-preset [{name}@{chip}] "
                      f"({time.time() - t0:.0f}s, cached proof {fp}, "
                      f"compiled in {proof.get('compile_s', '?')}s): "
                      f"{band}; {est.format()}; {detail}")
                continue
            lowered = dalle_step_lowered(name, make_cfg=make_cfg)
            with spmd.fresh_stats_compile():
                compiled = lowered.compile(OPT0)
            est = _s4_estimate(compiled, lowered)
            compile_s = int(time.time() - t0)
            # persist BEFORE gating: the proof records what the compile
            # measured; whether it fits a given chip is re-decided per run
            proofs[name] = {
                "fingerprint": fp,
                "plan": PLAN_REGISTRY[name].spec(),
                "estimate": dataclasses.asdict(est),
                "compile_s": compile_s,
                "jax": jax.__version__,
            }
            dirty = True
            detail = _gate_preset_estimate(name, est, chip)
            print(f"PASS S4-preset [{name}@{chip}] "
                  f"({time.time() - t0:.0f}s): {band}; {est.format()}; "
                  f"{detail}")
        except (spmd.SPMDViolation, ValueError) as e:
            failures += 1
            print(f"FAIL S4-preset [{name}@{chip}] "
                  f"({time.time() - t0:.0f}s): {e}")
    if dirty:
        tmp = ppath.with_name(ppath.name + ".tmp")
        tmp.write_text(json.dumps(proofs, indent=1, sort_keys=True) + "\n")
        os.replace(tmp, ppath)
        print(f"spmd_check --presets: proofs -> {ppath}")
    print(f"\nspmd_check --presets: {'FAIL' if failures else 'PASS'} "
          f"({len(rungs)} rung(s), chip={chip})")
    return 1 if failures else 0


def check_factory_coverage() -> None:
    """The registry/harness sync gate: every training.STEP_FACTORIES entry
    has a harness here, and vice versa."""
    missing = set(STEP_FACTORIES) - set(HARNESSED_FACTORIES)
    stale = set(HARNESSED_FACTORIES) - set(STEP_FACTORIES)
    if missing or stale:
        raise spmd.SPMDViolation(
            f"factory coverage drift: unanalyzed factories {sorted(missing)}"
            f", harnesses without a factory {sorted(stale)} — update "
            "tools/spmd_check.py HARNESSED_FACTORIES alongside "
            "training.STEP_FACTORIES")


# --- driver ---------------------------------------------------------------


def run_all(chip: str = "v4-8", quick: bool = False,
            json_out=None) -> int:
    t_start = time.time()
    results = []
    failures = 0

    def run(analysis: str, target: str, fn):
        nonlocal failures
        t0 = time.time()
        try:
            detail = fn() or ""
            status = "PASS"
        except spmd.SPMDViolation as e:
            detail, status = str(e), "FAIL"
            failures += 1
        # graftlint: disable=EXC001 (recorded as an ERROR result that fails the run — nothing is swallowed)
        except Exception as e:  # harness breakage is a failure, not a pass
            detail, status = f"{type(e).__name__}: {e}", "ERROR"
            failures += 1
        results.append(dict(analysis=analysis, target=target, status=status,
                            detail=str(detail)))
        print(f"{status} {analysis} [{target}] "
              f"({time.time() - t0:.1f}s){': ' + str(detail) if status != 'PASS' else ''}")

    run("coverage", "step-factories", check_factory_coverage)

    # S1 + S3 per factory at tiny geometry (jaxpr structure and trace
    # caching are geometry-independent; S3 is the one analysis that
    # executes, so it must stay toy-sized)
    donating_twins = {}
    for name, setup in TINY_FACTORY_SETUPS.items():
        try:
            step, make_args, donating = setup()
        # graftlint: disable=EXC001 (rethrown into run(), which records a counted ERROR — nothing is swallowed)
        except Exception as e:
            run("setup", name, lambda e=e: (_ for _ in ()).throw(e))
            continue
        donating_twins[name] = (donating, make_args)
        args0 = make_args(0)
        run("S1-collectives", name, lambda s=step, a=args0, n=name: "; ".join(
            x.format() for x in spmd.check_collective_order(
                jax.make_jaxpr(s)(*a), label=n)) or "no collectives")
        run("S3-retrace", name,
            lambda s=step, m=make_args, n=name:
                spmd.check_single_trace(s, m, steps=3, label=n))
    run("S1-collectives", "decode",
        lambda: "; ".join(x.format() for x in spmd.check_collective_order(
            decode_jaxpr(), label="decode")) or "no collectives")
    # the pipeline plan's microbatch scan: iteration-count x per-iteration
    # collective schedule, invariant across microbatch counts (the carried
    # PR 5 follow-up — per-body uniformity alone cannot see a
    # schedule-count mismatch between stages)
    run("S1-scan-schedule", "dalle_pp", pp_scan_schedule_check)
    # the continuous-batching serve tick: admit/retire churn across
    # occupancies must reuse ONE executable per entry point (ISSUE 6
    # acceptance gate, chip-free twin of tests/test_serve.py); the int8
    # arena variant (ISSUE 7) proves the quantized cache/scale planes and
    # session-quantized weight arguments keep the same property
    run("S3-retrace", "serve-tick", serve_retrace_check)
    run("S3-retrace", "serve-tick-int8",
        lambda: serve_retrace_check(kv_cache_int8=True, weights_int8=True))

    # S2 per plan at tiny geometry, FULL-opt compile (donation honoring
    # is structural — layout/sharding mismatches reproduce at any size —
    # and only the full pipeline runs XLA's alias passes; opt0 reports
    # alias=0 even for honored donations).  S4 per plan at the
    # production geometry, opt0 (sizes only); --quick drops S4 to tiny
    # geometry too, for the test suite.
    make_cfg = tiny_config if quick else cub_config

    def s2_plan(plan):
        low_tiny = dalle_step_lowered(plan, make_cfg=tiny_config)
        with spmd.fresh_stats_compile():
            c_tiny = low_tiny.compile()
        return _s2_detail(spmd.check_donation(
            low_tiny, DALLE_ARG_LABELS, (0, 1), compiled=c_tiny,
            label=f"dalle/{plan}"))

    def s4_plan(plan):
        lowered = dalle_step_lowered(plan, make_cfg=make_cfg)
        with spmd.fresh_stats_compile():
            compiled = lowered.compile(OPT0)
        return _s4_detail(compiled, lowered, chip, f"dalle/{plan}")

    for plan in PLANS:
        run("S2-donation", f"dalle/{plan}", lambda p=plan: s2_plan(p))
        run("S4-hbm", f"dalle/{plan}@{chip}", lambda p=plan: s4_plan(p))

    # S2 for the single-chip factories (tiny compile: donation is
    # size-independent, the alias check still needs an executable)
    for name in ("vae", "clip"):
        if name not in donating_twins:
            continue  # setup already reported the failure
        donating, make_args = donating_twins[name]
        lowered = donating.lower(*make_args(0))
        with spmd.fresh_stats_compile():
            compiled = lowered.compile()
        run("S2-donation", name,
            lambda lo=lowered, c=compiled, n=name: _s2_detail(
                spmd.check_donation(lo, FACTORY_ARG_LABELS[n], (0, 1),
                                    compiled=c, label=n)))

    elapsed = time.time() - t_start
    print(f"\nspmd_check: {'FAIL' if failures else 'PASS'} "
          f"({failures} violation(s), {elapsed:.0f}s, chip={chip})")
    if json_out:
        Path(json_out).write_text(json.dumps(
            dict(tool="spmd_check", chip=chip, quick=quick,
                 failures=failures, results=results), indent=2) + "\n")
        print(f"findings -> {json_out}")
    return 1 if failures else 0


def _s2_detail(audit: spmd.DonationAudit) -> str:
    mib = 1024 ** 2
    big = "; ".join(f"{lbl}/{p} {b / mib:.1f} MiB undonated"
                    for lbl, p, b in audit.undonated_big[:4])
    return (f"donated {audit.donated_bytes / mib:.1f} MiB across "
            f"{audit.donated_leaves} leaves, {audit.aliased_params} aliased"
            + (f"; large undonated args: {big}" if big else ""))


def _s4_estimate(compiled, lowered) -> spmd.HBMEstimate:
    est = spmd.hbm_estimate(compiled)
    # opt0 zeroes the compiled alias stat; S2 verified the donation
    # aliases for this plan, so subtract the requested-donated share of
    # the per-device argument bytes in its place (donated and undonated
    # args shard across the same mesh, so the global fraction holds
    # per-device)
    audit = spmd.audit_donation(lowered, DALLE_ARG_LABELS, (0, 1))
    assumed = int(audit.donated_fraction * est.argument_bytes)
    return dataclasses.replace(est, alias_bytes=max(est.alias_bytes, assumed))


def _s4_detail(compiled, lowered, chip: str, label: str) -> str:
    est = _s4_estimate(compiled, lowered)
    spmd.check_hbm_budget(est, chip, label=label)
    return est.format()


# --- selftest: the analyses catch their broken fixtures -------------------


def selftest() -> int:
    """Prove S1-S4 have teeth against lint/spmd_fixtures.py (the CLI twin
    of tests/test_spmd_check.py)."""
    from dalle_pytorch_tpu.lint import spmd_fixtures as fx

    failures = 0

    def expect_catch(label, fn):
        nonlocal failures
        try:
            fn()
        except spmd.SPMDViolation as e:
            print(f"PASS {label}: caught ({str(e)[:90]}...)")
        else:
            print(f"FAIL {label}: broken fixture NOT caught")
            failures += 1

    mesh = make_mesh()
    x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
    expect_catch("S1 conditional ppermute", lambda: spmd.check_collective_order(
        jax.make_jaxpr(fx.make_conditional_collective_step(mesh))(x)))
    spmd.check_collective_order(
        jax.make_jaxpr(fx.make_branch_matched_collective_step(mesh))(x))
    print("PASS S1 branch-matched twin: clean")

    expect_catch(
        "S1 unbalanced microbatch scan",
        lambda: spmd.scan_collective_schedule(
            jax.make_jaxpr(fx.make_unbalanced_microbatch_scan(mesh))(x)))
    scheds = spmd.scan_collective_schedule(
        jax.make_jaxpr(fx.make_pipelined_collective_scan(mesh, length=4))(x))
    assert len(scheds) == 1 and scheds[0].length == 4 \
        and len(scheds[0].per_iteration) == 1, scheds
    print(f"PASS S1 pipelined-scan twin: clean ({scheds[0].format()})")

    tx = make_optimizer(1e-3)
    params = fx.fixture_params()
    opt = tx.init(params)
    low = fx.make_undonated_train_step(tx).lower(
        params, opt, jnp.ones((8, 64), jnp.float32))
    expect_catch("S2 dropped donation", lambda: spmd.check_donation(
        low, ("params", "opt_state", "batch"), (0, 1)))

    expect_catch("S3 weak-hash static arg", lambda: spmd.check_single_trace(
        *fx.make_retracing_step()))
    expect_catch("S3 unhashable static arg", lambda: spmd.check_single_trace(
        *fx.make_unhashable_static_step()))
    spmd.check_single_trace(*fx.make_stable_step())
    print("PASS S3 stable twin: clean")
    expect_catch(
        "S3 occupancy-shaped serve tick",
        lambda: spmd.check_single_trace(
            *fx.make_shape_changing_serve_tick(), steps=4,
            label="serve-fixture"))

    est = spmd.hbm_estimate(fx.oversized_step_compiled())
    toy = dict(spmd.CHIP_HBM_BYTES, toy=1 << 20)
    expect_catch("S4 oversized plan", lambda: _gate_with(toy, est))

    print(f"\nselftest: {'FAIL' if failures else 'PASS'}")
    return 1 if failures else 0


def _gate_with(table, est):
    orig = dict(spmd.CHIP_HBM_BYTES)
    spmd.CHIP_HBM_BYTES.clear()
    spmd.CHIP_HBM_BYTES.update(table)
    try:
        spmd.check_hbm_budget(est, "toy")
    finally:
        spmd.CHIP_HBM_BYTES.clear()
        spmd.CHIP_HBM_BYTES.update(orig)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chip", default="v4-8",
                        choices=sorted(spmd.CHIP_HBM_BYTES),
                        help="HBM capacity table for the S4 budget gate")
    parser.add_argument("--quick", action="store_true",
                        help="tiny geometry for S2/S4 too (tests/dev)")
    parser.add_argument("--json", type=str, default=None,
                        help="write machine-readable results to this path")
    parser.add_argument("--selftest", action="store_true",
                        help="prove each analysis catches its deliberately-"
                             "broken fixture, then exit")
    parser.add_argument("--s4-drift", action="store_true",
                        help="compile ONE plan at opt-0 AND full "
                             "optimization and diff arg/out/temp sizes — "
                             "the scheduled-CI gate that keeps the S4 "
                             "opt-0 shortcut honest across XLA upgrades "
                             "(--quick drops to tiny geometry)")
    parser.add_argument("--presets", action="store_true",
                        help="run the scale-preset S4 HBM proof "
                             "(presets.SCALE_PRESETS, e.g. cub-512) at "
                             "the rung's real geometry — minutes per "
                             "rung on a cold S4_PROOFS.json cache, "
                             "seconds on a hit; the nightly-CI gate")
    parser.add_argument("--preset", type=str, default=None,
                        help="with --presets: run only this rung")
    parser.add_argument("--refresh-proofs", action="store_true",
                        help="with --presets: recompile even on a "
                             "fingerprint hit and rewrite S4_PROOFS.json")
    args = parser.parse_args(argv)
    if args.selftest:
        return selftest()
    if args.presets or args.preset:
        return run_presets(chip=args.chip, only=args.preset,
                           refresh=args.refresh_proofs)
    if args.s4_drift:
        try:
            detail = s4_drift_check(
                make_cfg=tiny_config if args.quick else cub_config)
        except spmd.SPMDViolation as e:
            print(f"FAIL S4-drift: {e}")
            return 1
        print(f"PASS S4-drift [dalle/dp]: {detail}")
        return 0
    return run_all(chip=args.chip, quick=args.quick, json_out=args.json)


if __name__ == "__main__":
    raise SystemExit(main())
