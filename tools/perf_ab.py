#!/usr/bin/env python
"""Interleaved A/B perf experiments on the CUB-200 DALLE train step.

The bench chip is shared and its throughput drifts minutes apart, so single
draws are meaningless; this tool compiles every requested variant once,
then measures them round-robin for `--reps` rounds and reports per-variant
medians — ambient drift hits all variants roughly equally within a round.

Usage:
    python tools/perf_ab.py baseline pallas --reps 3 --steps 30
    python tools/perf_ab.py --list

Variants are train-step configs (see VARIANTS); `gen` measures the KV-cache
sampler instead (`gen64` at batch 64 — the BASELINE target scenario samples
64 images; `gen`'s batch 8 matches bench.py's informational stage).  The
measured loops are bench.py's own (`make_train_measure` /
`make_gen_measure`), so this tool can never drift from the driver-facing
benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import statistics
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

VARIANTS = {
    "baseline": {},
    "pallas": dict(use_pallas=True),
    # sub-128 tiles cannot lower on TPU (lane width 128 — seen on the chip
    # 2026-08-02; flash_pattern_attention now rejects them at the API
    # edge), so the tile ladder is 128 (default) / 256 / 512
    "pallas-b256": dict(use_pallas=True, pallas_block_q=256,
                        pallas_block_k=256),
    "pallas-b512": dict(use_pallas=True, pallas_block_q=512,
                        pallas_block_k=512),
    "fp32": dict(dtype=jnp.float32),
    "full-attn": dict(attn_types=("full",)),
    "reversible": dict(reversible=True),
    "remat": dict(use_remat=True),
    "bf16-logits": dict(logits_bf16=True),
    "onehot-embed": dict(onehot_embed=True),
    "bf16-logits+onehot": dict(logits_bf16=True, onehot_embed=True),
    # measures the phase-sliced-head default against the old full-head +
    # output-slice path (same loss; ~9% fewer analytic step FLOPs)
    "full-head": dict(head_phase_sliced=False),
    # batch-scaling A/B (PERF.md "Raising MFU" lever 1): `batch` binds to
    # make_train_measure's batch param, not DALLEConfig; img/s stay
    # comparable across batch sizes (items_per_step scales with the batch).
    # Named batchN, not bN — the pallas-b64 suffix means block size.
    "batch64": dict(batch=64),
    # plain batch128 OOMs on v5e (measured 2026-08-02: 30.3G of 15.75G
    # HBM) — remat is the framework's own answer to that wall, so the
    # b128 rung is measured with it on
    "batch128": dict(batch=128),
    "batch128-remat": dict(batch=128, use_remat=True),
    # the projected production config: every lever PERF.md's analysis says
    # should stack (batch-scale the compute-starved chip + bf16 head +
    # one-hot embed backward) — A/B'd as ONE variant so interactions show
    "candidate": dict(batch=64, logits_bf16=True, onehot_embed=True),
    # 512px-class geometry (fmap 64 -> 4096 image tokens): where O(n·√n)
    # block-skipping should beat dense masks that blow HBM — the Pallas
    # kernel's re-target case.  batch drops
    # to 4 so the dense control fits HBM at n≈4177.
    "fmap64": dict(batch=4, image_fmap_size=64),
    "fmap64-pallas": dict(batch=4, image_fmap_size=64, use_pallas=True),
    "fmap64-pallas-b256": dict(batch=4, image_fmap_size=64, use_pallas=True,
                               pallas_block_q=256, pallas_block_k=256),
}

# pseudo-variants measuring other bench loops (not train-step configs).
# gen-dense: the sampler with the sliced-KV decode disabled (dense cache
# reads every step) — the A/B control for ops/attention.py's
# decode_key_positions gather.
# gen_bf16 / gen_f32cache: the sampler at f32 activations (the checkpoint-
# loaded eval path's dtype) with the bf16 KV cache ON vs OFF — the wall-
# clock side of the kv_cache_bf16 byte-cut (the compiler gate is
# tests/test_perf_model.py::test_bf16_cache_cuts_decode_cache_bytes).
# gen_fused_rank: the fused generate→VAE-decode→CLIP-rerank pipeline
# (genrank.rank_codes, shared prefill, zero disk round-trips), in
# images-ranked/sec.
# serve64 / serve16: the continuous-batching generation service
# (serve.GenerationServer: slot KV arena, per-tick admission, open-loop
# arrival trace at 1.25x oversubscription) — aggregate tok/s across
# INTERLEAVED requests; serve64 is the direct A/B against gen64's
# static-batch 35.2k tok/s headline.
# gen_int8: the ISSUE 7 quantized-serving recipe on the static sampler at
# eval dtype (f32 activations): int8 KV cache (per-head scales) + int8
# decode weights (per-output-channel scales, one-shot per session) — the
# wall-clock side of the ≤0.55x-cache-bytes compiler gate; its direct
# control is gen_bf16 (same dtype, bf16 cache, f32 weights).
# serve_int8: the same recipe on the 64-slot serve arena (per-SLOT scale
# planes, int8 weight args on every tick) vs serve64's bf16 arena.
# gen_spec: graftspec's self-speculative sampler (shallow-exit drafts from
# the first spec_draft_depth blocks + one K-wide full-model verify per
# iteration) vs the greedy scan — A/B control is `gen` (same batch 8).
# serve_spec: the same lever on the 64-slot arena (tick_spec: variable
# tokens-per-tick commits) vs serve64's greedy ticks.
# serve_prefix: the cross-request radix prefix cache on the 64-slot arena —
# the open-loop trace shares ONE prompt across every arrival, so this
# measures the all-hit admission path (one prefill serves the whole
# drive); control is serve64 (same arena, cache off).
EXTRAS = ("gen", "gen64", "vae", "gen-dense", "gen_bf16", "gen_f32cache",
          "gen_fused_rank", "serve64", "serve16", "gen_int8", "serve_int8",
          "gen_spec", "serve_spec", "serve_prefix")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("variants", nargs="*", default=[],
                        help=f"from: {', '.join(VARIANTS)}, or "
                             f"{'/'.join(EXTRAS)}")
    parser.add_argument("--reps", type=int, default=3,
                        help="interleaved measurement rounds (default 3)")
    parser.add_argument("--steps", type=int, default=30,
                        help="train steps per measurement (default 30)")
    parser.add_argument("--list", action="store_true")
    args = parser.parse_args(argv)
    if args.list or not args.variants:
        print("variants:", ", ".join(list(VARIANTS) + list(EXTRAS)))
        return 0
    if args.reps < 1:
        parser.error("--reps must be >= 1")
    unknown = [v for v in args.variants
               if v not in EXTRAS and v not in VARIANTS]
    if unknown:
        parser.error(f"unknown variant(s) {unknown}; choose from "
                     f"{list(VARIANTS) + list(EXTRAS)}")
    dupes = sorted({v for v in args.variants if args.variants.count(v) > 1})
    if dupes:
        # the measurement dict is keyed by name — a repeated variant would be
        # silently measured once, which reads like two independent draws
        parser.error(f"duplicate variant(s) {dupes}: each name gets one "
                     "measurement slot; use --reps for repeated measurement")

    import bench
    from dalle_pytorch_tpu.cli import enable_compilation_cache
    from dalle_pytorch_tpu.obs import prof

    enable_compilation_cache()  # variant recompiles across runs hit the cache

    measures = {}
    # name -> bench.ledger_keys(...): the PERF_LEDGER.json join key built
    # from the cfg the measured loop actually traced, so each variant's
    # median lands beside graftprof's predicted row (or as a measured-only
    # stub at geometries the sweep doesn't cover)
    ledger_info = {}
    for name in args.variants:
        print(f"compiling {name}...", file=sys.stderr, flush=True)

        def gen_measure(b, **ov):
            compile_fn, cfg = bench.make_gen_measure_deferred(batch=b, **ov)
            ledger_info[name] = bench.ledger_keys(
                cfg, target="decode", plan="single", batch=b)
            return compile_fn()

        if name in ("gen", "gen64"):
            measures[name] = gen_measure(64 if name == "gen64" else 8)
        elif name == "gen-dense":
            # the dense-cache control: the same sampler with
            # DALLEConfig.sliced_kv_decode=False, so the choice is part of
            # the traced config — a retrace can never silently measure the
            # sliced path under the gen-dense label
            measures[name] = gen_measure(8, sliced_kv_decode=False)
        elif name in ("gen_bf16", "gen_f32cache"):
            # f32 activations (the eval path's dtype: checkpoints carry no
            # dtype, so loaded models run f32) with the bf16 KV cache on
            # vs off — like gen-dense, the choice rides the traced config
            measures[name] = gen_measure(
                8, dtype=jnp.float32, kv_cache_bf16=(name == "gen_bf16"))
        elif name == "gen_int8":
            # int8 quantized serving (ISSUE 7) at the eval path's f32
            # activations: int8 cache + int8 decode weights, both riding
            # the traced config — A/B control is gen_bf16
            measures[name] = gen_measure(
                8, dtype=jnp.float32, kv_cache_int8=True, weights_int8=True)
        elif name == "gen_spec":
            # graftspec's self-speculative sampler: drafts from the first
            # spec_draft_depth blocks, one K-wide verify per iteration —
            # the choice rides the traced config, control is `gen`
            compile_fn, cfg = bench.make_gen_measure_deferred(
                batch=8, spec_decode=True)
            ledger_info[name] = bench.ledger_keys(
                cfg, target="decode-spec", plan="single", batch=8)
            measures[name] = compile_fn()
        elif name == "gen_fused_rank":
            measures[name] = bench.make_fused_rank_measure(batch=8)
        elif name in ("serve64", "serve16", "serve_int8", "serve_spec",
                      "serve_prefix"):
            # serve_int8: the quantized 64-slot arena (per-slot scale
            # planes, int8 weight args per tick) vs serve64's bf16 arena.
            # serve_spec: tick_spec's variable tokens-per-tick commits vs
            # serve64's greedy ticks.  serve_prefix: the radix prefix
            # cache's all-hit admission path (one shared prompt) — a
            # SERVER knob, not a config field, so it rides the ledger
            # fingerprint as an extra key instead of the traced config.
            slots = 16 if name == "serve16" else 64
            ov = (dict(kv_cache_int8=True, weights_int8=True)
                  if name == "serve_int8"
                  else dict(spec_decode=True) if name == "serve_spec"
                  else {})
            prefix = name == "serve_prefix"
            target = "serve-spec" if name == "serve_spec" else "serve-tick"
            ledger_info[name] = bench.ledger_keys(
                dataclasses.replace(bench.cub200_config(), **ov),
                target=target, plan="single", batch=slots,
                num_slots=slots, **({"prefix_cache": True} if prefix else {}))
            measures[name] = bench.make_serve_measure(
                num_slots=slots, prefix_cache=prefix, **ov)
        elif name == "vae":
            measures[name] = bench.make_vae_measure()
            ledger_info[name] = bench.ledger_keys(
                bench.vae128_config(), target="vae", plan="single", batch=8)
        else:
            measure, cfg, batch = bench.make_train_measure(
                args.steps, **VARIANTS[name])
            measures[name] = measure
            ledger_info[name] = bench.ledger_keys(
                cfg, target="dalle/dp", plan="dp", batch=batch)

    def unit(name):
        if name == "gen_fused_rank":  # rank_codes reports whole images
            return "img/s"
        if name.startswith(("gen", "serve")):
            return "tok/s"
        return "img/s"

    results = {name: [] for name in measures}
    for rep in range(args.reps):
        for name, measure in measures.items():  # interleaved round-robin
            v, _ = measure()
            results[name].append(v)
            print(f"rep{rep} {name:12s} {v:9.2f} {unit(name)}", flush=True)

    print("\nmedians:")
    for name, vals in results.items():
        print(f"  {name:12s} {statistics.median(vals):9.2f} {unit(name)}  "
              f"(spread {min(vals):.2f}-{max(vals):.2f})")

    # medians join PERF_LEDGER.json under the prediction's fingerprint
    # (real chip only, like bench.record_history's history line;
    # GRAFT_PERF_LEDGER arms a scratch ledger so CPU smoke can exercise
    # the join).  `graftprof --report` renders predicted-vs-measured.
    if ledger_info and (jax.devices()[0].platform != "cpu"
                        # graftlint: disable=ENV001 (path-valued var: set at all arms a scratch ledger)
                        or os.environ.get("GRAFT_PERF_LEDGER")):
        appended = 0
        for name, vals in results.items():
            info = ledger_info.get(name)
            if info is None:  # e.g. gen_fused_rank spans three models
                continue
            prof.append_measured(
                {"metric": f"perf_ab:{name}",
                 "value": round(statistics.median(vals), 2),
                 "unit": unit(name), "reps": args.reps},
                fingerprint=info["ledger_fingerprint"],
                target=info["ledger_target"])
            appended += 1
        if appended:
            print(f"ledger: {appended} measured row(s) -> "
                  f"{prof.ledger_path()}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
