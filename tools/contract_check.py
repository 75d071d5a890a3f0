#!/usr/bin/env python
"""Chip-free tracing contract checker: statically assert the invariants the
generation/training stack otherwise holds only by convention.

Five review rounds' worth of contracts live in comments ("the cache is
bf16 when the flag is on", "attention accumulates in f32", "pjit shardings
resolve on every mesh") — this tool turns them into assertions that run in
seconds on CPU with **zero FLOPs**: everything goes through
``jax.eval_shape`` / ``jax.make_jaxpr`` / AOT lowering on a virtual
8-device host mesh, so a dead invariant is caught before it costs any
chip time.

Checked contracts (see ISSUE 2 / PERF.md "bf16 sliced-KV cache" and
ISSUE 7 "int8 quantized serving"):

* C1 cache dtype — ``DALLE.prefill`` returns bf16 caches iff
  ``kv_cache_bf16`` (or the model itself runs bf16), and ``(int8 values,
  f32 per-head scale)`` pairs iff ``kv_cache_int8``; head logits stay
  f32.
* C2 f32 accumulation — in the decode jaxpr every dot with a bf16 OR
  int8 operand carries ``preferred_element_type=f32`` (the MXU's
  low-precision-in/f32-acc mode); applies to f32-activation models,
  where such an operand can only be the stored cache or a quantized
  weight.
* C3 no full-cache / full-weight dequant materialization — the decode
  jaxpr (and, under the int8 flags, the serve-tick jaxpr) contains no
  bf16/int8 -> f32 convert of a full-cache-sized array and no int8 ->
  f32/bf16 convert of a full-weight-sized array (the XLA hoist that
  defeated the bf16 cache until PR 1 pinned cache-dtype multiplicands —
  the int8 recipe has the same failure mode one byte lower).
* C4 shardings resolve — for all five parallel strategies (dp, fsdp, tp,
  sp-ring, sp-ulysses) the strategy's step traces and its shardings
  lower/partition on a virtual mesh.

Usage:
    JAX_PLATFORMS=cpu python tools/contract_check.py [--quick]

Exit 0 iff every contract holds.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from pathlib import Path
from typing import Optional

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import os

# Chip-free by construction: an 8-device virtual CPU mesh, set up BEFORE
# jax initializes a backend, so the checker never claims a chip.
os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
        " --xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from dalle_pytorch_tpu import DALLE, DALLEConfig
from dalle_pytorch_tpu.models.dalle import decode_codes
from dalle_pytorch_tpu.parallel.mesh import Partitioner, make_mesh
from dalle_pytorch_tpu.training import (make_dalle_sp_train_step,
                                        make_optimizer)


class ContractViolation(AssertionError):
    """A statically-checkable invariant the codebase relies on is broken."""


# --- geometries ----------------------------------------------------------


def tiny_config(**overrides) -> DALLEConfig:
    """Small geometry for the strategy checks: seq 24 (divisible by sp=2),
    heads 4 (divisible by the ulysses sp axis)."""
    base = dict(dim=32, depth=2, heads=4, dim_head=8, num_text_tokens=50,
                text_seq_len=8, num_image_tokens=32, image_size=64,
                image_fmap_size=4)
    base.update(overrides)
    return DALLEConfig(**base)


def cub_config(**overrides) -> DALLEConfig:
    """The production CUB-200 geometry (``presets.cub_config``'s)."""
    base = dict(dim=256, depth=8, heads=8, dim_head=64,
                num_text_tokens=7800, text_seq_len=80,
                num_image_tokens=1024, image_size=256, image_fmap_size=32)
    base.update(overrides)
    return DALLEConfig(**base)


# --- shape/jaxpr plumbing ------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _init_shapes(dalle: DALLE, batch: int = 2):
    cfg = dalle.cfg
    text = _sds((batch, cfg.text_seq_len), jnp.int32)
    # init with image codes present so the full param tree exists (text-only
    # forwards never create image_emb)
    codes = _sds((batch, cfg.image_seq_len), jnp.int32)
    variables = jax.eval_shape(dalle.init, jax.random.PRNGKey(0), text,
                               codes)
    return variables, text


def _prefill_shapes(dalle: DALLE, batch: int = 2):
    variables, text = _init_shapes(dalle, batch)
    logits, kvs = jax.eval_shape(
        lambda v, t: dalle.apply(v, t, method=DALLE.prefill), variables, text)
    return variables, text, logits, kvs


def _iter_eqns(jaxpr):
    """All equations of a jaxpr, recursing into nested jaxprs (pjit bodies,
    scan/while/cond branches)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for v in vals:
                inner = getattr(v, "jaxpr", None)
                if inner is not None:
                    yield from _iter_eqns(inner)
                elif hasattr(v, "eqns"):
                    yield from _iter_eqns(v)


def _decode_jaxpr(cfg: DALLEConfig, dalle=None, batch: int = 2):
    """Jaxpr of the full sampling scan (prefill state -> all image codes) —
    the program whose HBM traffic the bf16-cache contract governs."""
    dalle = dalle or DALLE(cfg)
    variables, _, logits, kvs = _prefill_shapes(dalle, batch)
    rng = _sds((2,), jnp.uint32)  # raw PRNGKey layout

    def run(v, first_logits, caches, rng):
        return decode_codes(dalle, v, first_logits, caches, rng)

    return jax.make_jaxpr(run)(variables, logits, kvs, rng), kvs


# --- C1: cache/Logits dtype ---------------------------------------------


def check_cache_dtype(cfg: DALLEConfig, dalle=None) -> None:
    """prefill caches are bf16 iff kv_cache_bf16 (or a bf16 model), and
    (int8 values, f32 per-head scale) pairs iff kv_cache_int8; the
    logits head output stays f32 regardless."""
    dalle = dalle or DALLE(cfg)
    _, _, logits, kvs = _prefill_shapes(dalle)
    expected = jnp.bfloat16 if (cfg.kv_cache_bf16
                                or cfg.dtype == jnp.bfloat16) else jnp.float32
    for i, (k, v) in enumerate(kvs):
        for name, leaf in (("k", k), ("v", v)):
            if cfg.kv_cache_int8:
                if not (isinstance(leaf, tuple) and len(leaf) == 2):
                    raise ContractViolation(
                        f"layer {i} cache {name} is not an (int8, scale) "
                        f"pair under kv_cache_int8: {type(leaf).__name__}")
                values, scale = leaf
                if values.dtype != jnp.int8:
                    raise ContractViolation(
                        f"layer {i} cache {name} values dtype "
                        f"{values.dtype} != int8 (kv_cache_int8=True)")
                b, h = values.shape[0], values.shape[1]
                if scale.dtype != jnp.float32 or scale.shape != (b, h, 1, 1):
                    raise ContractViolation(
                        f"layer {i} cache {name} scale {scale.dtype}"
                        f"{scale.shape} != f32 per-head plane "
                        f"{(b, h, 1, 1)} — the ops/quant.py scale-layout "
                        "contract")
                leaf = values
            elif leaf.dtype != expected:
                raise ContractViolation(
                    f"layer {i} cache {name} dtype {leaf.dtype} != "
                    f"{jnp.dtype(expected).name} (kv_cache_bf16="
                    f"{cfg.kv_cache_bf16}, dtype={jnp.dtype(cfg.dtype).name})")
            if name == "k" and leaf.shape[2] != cfg.seq_len:
                raise ContractViolation(
                    f"layer {i} cache holds {leaf.shape[2]} positions, "
                    f"expected seq_len={cfg.seq_len}")
    if logits.dtype != jnp.float32:
        raise ContractViolation(
            f"prefill logits dtype {logits.dtype} != float32 — the head "
            "must accumulate and emit f32")
    if logits.shape[-1] != cfg.num_image_tokens:
        raise ContractViolation(
            f"prefill logits vocab {logits.shape[-1]} != image vocab "
            f"{cfg.num_image_tokens}")


# --- C2 + C3: decode jaxpr contracts ------------------------------------


def check_decode_dots_accumulate_f32(cfg: DALLEConfig, dalle=None) -> None:
    """Every dot in the decode program with a bf16 or int8 operand must
    state f32 accumulation.  Only meaningful for f32-activation models
    (checkpoint eval dtype): there, such an operand can only be the
    stored cache or a session-quantized weight."""
    if cfg.dtype != jnp.float32:
        raise ValueError("C2 applies to f32-activation configs only")
    jaxpr, _ = _decode_jaxpr(cfg, dalle)
    low = (jnp.bfloat16, jnp.int8)
    for eqn in _iter_eqns(jaxpr.jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        hits = [v.aval.dtype for v in eqn.invars if v.aval.dtype in low]
        if not hits:
            continue
        pref = eqn.params.get("preferred_element_type")
        if pref is None or jnp.dtype(pref) != jnp.dtype(jnp.float32):
            name = "bf16" if hits[0] == jnp.bfloat16 else "int8"
            raise ContractViolation(
                f"decode dot_general with {name} operand accumulates in "
                f"{pref or 'operand dtype'} (line {eqn.source_info.traceback}"
                f") — must be preferred_element_type=f32")


def _cache_elems(kvs) -> int:
    """Smallest per-layer cache element count; int8 entries are (values,
    scale) pairs."""
    sizes = []
    for k, _ in kvs:
        values = k[0] if isinstance(k, tuple) else k
        sizes.append(int(np.prod(values.shape)))
    return min(sizes)


def _min_weight_elems(cfg: DALLEConfig, variables) -> int:
    """Smallest quantized decode-weight kernel (element count) — the
    threshold above which an int8->float convert means a dequantized
    weight copy, not a per-step activation."""
    from dalle_pytorch_tpu.models.dalle import quantize_decode_weights

    qw = jax.eval_shape(lambda v: quantize_decode_weights(v, cfg),
                        variables)
    sizes = [int(np.prod(leaf.shape))
             for leaf in jax.tree.leaves(qw)
             if leaf.dtype == jnp.int8]
    return min(sizes)


def _scan_dequant_converts(jaxpr, cache_elems: int,
                           weight_elems: Optional[int], label: str) -> None:
    """The shared C3 walk: no low-precision -> f32 convert at or above
    full-cache size, and (when weights are quantized) no int8 -> float
    convert at or above full-weight size."""
    low = (jnp.bfloat16, jnp.int8)
    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        (invar,), (outvar,) = eqn.invars, eqn.outvars
        if getattr(invar, "aval", None) is None:
            continue
        src, dst = invar.aval.dtype, outvar.aval.dtype
        size = int(np.prod(outvar.aval.shape))
        # the weight rule first: an int8 convert that clears the (smaller)
        # weight threshold is a dequantized kernel, the sharper diagnosis
        if weight_elems is not None and src == jnp.int8 \
                and dst in (jnp.float32, jnp.bfloat16) \
                and size >= weight_elems:
            raise ContractViolation(
                f"{label} program materializes a dequantized weight copy: "
                f"convert_element_type int8->{dst} of shape "
                f"{outvar.aval.shape} (>= weight size {weight_elems})")
        if src in low and dst == jnp.float32 and size >= cache_elems:
            raise ContractViolation(
                f"{label} program materializes a full-cache f32 copy: "
                f"convert_element_type {src}->f32 of shape "
                f"{outvar.aval.shape} (>= cache size {cache_elems})")


def check_no_f32_cache_materialization(cfg: DALLEConfig, dalle=None) -> None:
    """The decode program never converts a full-cache-sized bf16/int8
    array to f32 — the hoist that would silently double decode HBM
    traffic and defeat kv_cache_bf16/kv_cache_int8 (PR 1's measured
    failure mode) — nor, under weights_int8, a full-weight-sized int8
    array to any float."""
    dalle = dalle or DALLE(cfg)
    jaxpr, kvs = _decode_jaxpr(cfg, dalle)
    weight_elems = None
    if cfg.weights_int8:
        variables, _ = _init_shapes(dalle)
        weight_elems = _min_weight_elems(cfg, variables)
    _scan_dequant_converts(jaxpr.jaxpr, _cache_elems(kvs), weight_elems,
                           "decode")


def check_serve_tick_no_dequant(cfg: DALLEConfig, num_slots: int = 2) -> None:
    """C3 over the SERVE-TICK jaxpr: the phase-aligned batched decode
    step the arena runs every tick (per-slot index vector, shared write
    column, session-quantized weight arguments) must be as free of
    dequant hoists as the static decode scan — a full-precision copy
    here would re-pay the cache/weight bytes on every tick for every
    slot."""
    dalle = DALLE(cfg)
    variables, _ = _init_shapes(dalle, batch=1)
    S = num_slots
    cache_shape = (S, cfg.heads, cfg.seq_len, cfg.dim_head)
    if cfg.kv_cache_int8:
        entry = (_sds(cache_shape, jnp.int8),
                 _sds((S, cfg.heads, 1, 1), jnp.float32))
    else:
        entry = _sds(cache_shape,
                     jnp.bfloat16 if (cfg.kv_cache_bf16
                                      or cfg.dtype == jnp.bfloat16)
                     else cfg.dtype)
    caches = [(entry, entry) for _ in range(cfg.depth)]
    code = _sds((S,), jnp.int32)
    index = _sds((S,), jnp.int32)
    write_pos = _sds((), jnp.int32)
    weight_elems = None
    qw = None
    if cfg.weights_int8:
        from dalle_pytorch_tpu.models.dalle import quantize_decode_weights

        qw = jax.eval_shape(lambda v: quantize_decode_weights(v, cfg),
                            variables)
        weight_elems = _min_weight_elems(cfg, variables)

    def tick(v, code, caches, index, write_pos, qw):
        return dalle.apply(v, code, caches, index, None, write_pos, qw,
                           method=DALLE.decode_step)

    jaxpr = jax.make_jaxpr(tick)(variables, code, caches, index, write_pos,
                                 qw)
    _scan_dequant_converts(jaxpr.jaxpr, _cache_elems(caches), weight_elems,
                           "serve-tick")


# --- C4: parallel strategies --------------------------------------------

# The framework's five parallel strategies (README "Scaling guide"):
# pure data parallel, ZeRO-style fsdp, tensor parallel, and the two
# sequence-parallel attention implementations.  pp/ep own separate
# trainers and are exercised by their own tier-1 tests.
STRATEGIES = {
    "dp": dict(mesh=dict(), plan=dict()),
    "fsdp": dict(mesh=dict(fsdp=4), plan=dict()),
    "tp": dict(mesh=dict(tp=2), plan=dict()),
    "sp_ring": dict(mesh=dict(sp=2),
                    plan=dict(ring_axis="sp", sp_impl="ring", sp_size=2)),
    "sp_ulysses": dict(mesh=dict(sp=2),
                       plan=dict(ring_axis="sp", sp_impl="ulysses",
                                 sp_size=2)),
}


def check_strategy(name: str, make_cfg=tiny_config, batch: int = 8) -> None:
    """Trace strategy ``name``'s training step on a virtual mesh and prove
    its shardings resolve — shard_map specs divide, partition rules map
    every param, and the dense strategies lower AOT under pjit."""
    spec = STRATEGIES[name]
    cfg = make_cfg(**spec["plan"])
    dalle = DALLE(cfg)
    mesh = make_mesh(**spec["mesh"])
    variables, text = _init_shapes(dalle, batch)
    codes = _sds((batch, cfg.image_seq_len), jnp.int32)
    try:
        if cfg.ring_axis is not None:
            tx = make_optimizer(1e-3)
            step = make_dalle_sp_train_step(dalle, tx, mesh, donate=False)
            opt = jax.eval_shape(tx.init, variables["params"])
            jax.eval_shape(step, variables["params"], opt, None, text, codes,
                           _sds((2,), jnp.uint32))
        else:
            pt = Partitioner(mesh=mesh)
            shardings = pt.param_shardings(variables["params"])

            def loss_fn(p, text, codes):
                return dalle.apply({"params": p}, text, codes,
                                   return_loss=True)

            jax.jit(loss_fn,
                    in_shardings=(shardings, pt.data_sharding,
                                  pt.data_sharding)).lower(
                        variables["params"], text, codes).compile()
    except ContractViolation:
        raise
    except Exception as e:
        raise ContractViolation(
            f"strategy {name!r} failed to trace/partition on mesh "
            f"{dict(mesh.shape)}: {type(e).__name__}: {e}") from e


# --- C6: scale presets (the cheap per-push half) -------------------------


def check_preset(name: str, batch: int = 8) -> None:
    """The scale rung (presets.SCALE_PRESETS) instantiates, its param
    count sits in the declared band, and the rung plan's shardings
    resolve under AOT lowering — no compile (the full opt0 S4 HBM proof
    is ``spmd_check --presets``' nightly concern; this is the chip-free
    gate every push pays, ~15s at dim-512)."""
    from dalle_pytorch_tpu.parallel.plan import PLAN_REGISTRY
    from dalle_pytorch_tpu.presets import SCALE_PRESETS, check_param_band

    try:
        check_param_band(name)
        plan = PLAN_REGISTRY[name]
        cfg = SCALE_PRESETS[name](**plan.config_overrides())
        dalle = DALLE(cfg)
        pt = plan.partitioner()
        variables, text = _init_shapes(dalle, batch)
        codes = _sds((batch, cfg.image_seq_len), jnp.int32)
        shardings = pt.param_shardings(variables["params"])

        def loss_fn(p, text, codes):
            return dalle.apply({"params": p}, text, codes,
                               return_loss=True)

        jax.jit(loss_fn,
                in_shardings=(shardings, pt.data_sharding,
                              pt.data_sharding)).lower(
                    variables["params"], text, codes)
    except ContractViolation:
        raise
    except ValueError as e:
        raise ContractViolation(str(e)) from e
    except Exception as e:
        raise ContractViolation(
            f"preset {name!r} failed to instantiate/lower: "
            f"{type(e).__name__}: {e}") from e


# --- driver --------------------------------------------------------------


def run_all(quick: bool = False) -> int:
    make_cfg = tiny_config if quick else cub_config
    failures = 0

    def run(label, fn, *args, **kwargs):
        nonlocal failures
        try:
            fn(*args, **kwargs)
        except ContractViolation as e:
            failures += 1
            print(f"FAIL {label}: {e}")
        else:
            print(f"PASS {label}")

    for kv_bf16 in (True, False):
        cfg = make_cfg(kv_cache_bf16=kv_bf16)
        tag = f"kv_cache_bf16={kv_bf16}"
        run(f"C1 cache dtype [{tag}]", check_cache_dtype, cfg)
        run(f"C2 f32 accumulation [{tag}]",
            check_decode_dots_accumulate_f32, cfg)
        run(f"C3 no f32 cache materialization [{tag}]",
            check_no_f32_cache_materialization, cfg)
    run("C1 cache dtype [dtype=bf16]", check_cache_dtype,
        make_cfg(dtype=jnp.bfloat16, kv_cache_bf16=False))
    # int8 quantized serving (ISSUE 7): cache-only, then cache + weights;
    # C3 additionally walks the serve-tick jaxpr — both decode programs
    # must stay free of dequant hoists
    cfg_i8 = make_cfg(kv_cache_int8=True)
    run("C1 cache dtype [kv_cache_int8]", check_cache_dtype, cfg_i8)
    run("C2 f32 accumulation [kv_cache_int8]",
        check_decode_dots_accumulate_f32, cfg_i8)
    run("C3 no dequant materialization [kv_cache_int8]",
        check_no_f32_cache_materialization, cfg_i8)
    cfg_i8w = make_cfg(kv_cache_int8=True, weights_int8=True)
    run("C2 f32 accumulation [int8 cache+weights]",
        check_decode_dots_accumulate_f32, cfg_i8w)
    run("C3 no dequant materialization [int8 cache+weights]",
        check_no_f32_cache_materialization, cfg_i8w)
    run("C3 serve-tick no dequant [int8 cache+weights]",
        check_serve_tick_no_dequant, cfg_i8w)
    run("C3 serve-tick no dequant [bf16 cache]",
        check_serve_tick_no_dequant, make_cfg())
    for name in STRATEGIES:
        run(f"C4 shardings resolve [{name}]", check_strategy, name)
    if not quick:
        from dalle_pytorch_tpu.presets import SCALE_PRESETS
        for name in sorted(SCALE_PRESETS):
            run(f"C6 scale preset [{name}]", check_preset, name)

    print(f"\ncontract_check: {'FAIL' if failures else 'PASS'} "
          f"({failures} violation(s))")
    return 1 if failures else 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--quick", action="store_true",
                        help="tiny geometry only (tests/dev smoke)")
    args = parser.parse_args(argv)
    return run_all(quick=args.quick)


if __name__ == "__main__":
    raise SystemExit(main())
