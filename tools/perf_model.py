#!/usr/bin/env python
"""Print the compiler-model perf table (PERF.md "Compiler-model gates").

Compiles (never executes) the production train step, the candidate stack,
the full-head control, and the sliced/dense decode steps, and prints XLA's
own cost model for each — the chip-independent perf numbers that
tests/test_perf_model.py gates.  Run on any backend; CPU is the CI
calibration target:

    JAX_PLATFORMS=cpu python tools/perf_model.py [--fast]

``--fast`` skips the three CUB-sized train-step compiles (minutes on a
small host) and prints only the decode rows.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tests"))

GiB = 2 ** 30


def fmt(costs: dict) -> str:
    parts = [f"flops={costs['flops']:.4g}",
             f"bytes={costs['bytes_accessed']:.4g}"]
    if "temp_bytes" in costs:
        parts.append(f"temp={costs['temp_bytes'] / GiB:.2f}GiB")
    return " ".join(parts)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--fast", action="store_true",
                        help="decode rows only (skip CUB train compiles)")
    args = parser.parse_args(argv)

    from dalle_pytorch_tpu.cli import enable_compilation_cache

    enable_compilation_cache()  # re-runs and the test suite share compiles

    # the same builders the gate tests use — this tool can never drift
    # from what tests/test_perf_model.py asserts
    from test_perf_model import cub_train_costs, layer_decode_costs

    if not args.fast:
        from dalle_pytorch_tpu.utils.profiling import dalle_train_flops

        prod, cfg = cub_train_costs(16)
        print(f"production train step (CUB, b16): {fmt(prod)} "
              f"analytic/xla={dalle_train_flops(cfg, 16) / prod['flops']:.4f}")
        cand, cfg64 = cub_train_costs(64, logits_bf16=True, onehot_embed=True)
        print(f"candidate stack (b64+bf16+onehot): {fmt(cand)} "
              f"flops x{cand['flops'] / prod['flops']:.2f} vs b16")
        full, _ = cub_train_costs(16, head_phase_sliced=False)
        print(f"full-head control (b16): {fmt(full)} "
              f"sliced/full flops={prod['flops'] / full['flops']:.3f}")

    for variant in ("axial_row", "conv_like"):
        d1 = layer_decode_costs(variant, True, 1105)["bytes_accessed"]
        d2 = layer_decode_costs(variant, True, 2210)["bytes_accessed"]
        f1 = layer_decode_costs(variant, False, 1105)["bytes_accessed"]
        f2 = layer_decode_costs(variant, False, 2210)["bytes_accessed"]
        ds, dd = (d2 - d1) / 1105, (f2 - f1) / 1105
        print(f"decode layer {variant}: d(bytes)/d(key) sliced={ds:.0f} "
              f"dense={dd:.0f} (streaming eliminated at n=1105: "
              f"{(dd - ds) * 1105 / 2**20:.1f} MiB/step)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
