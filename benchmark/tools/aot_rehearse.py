#!/usr/bin/env python3
"""Compile a cell's main program at full size for a described v5e:2x2, with
no chip attached (the ``on-chip-measurement`` guide's third rehearsal), and
print the compiler's memory plan per device: what the chip's compiler would
refuse, it refuses here.  A compile is not a run and gives no time.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_rehearse.py <cell> [<cell> ...]

Covers the drivers that expose ``build`` (train, generate).
"""
from __future__ import annotations

import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(REPO))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmark import harness  # noqa: E402


def on(sharding, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), tree)


def compile_cell(name: str, topo):
    cell = harness.load_cell(name)
    dalle_cfg, vae_cfg = harness.build_configs(cell.config)
    devices = topo.devices[:cell.chips]
    driver = harness.load_driver(cell)
    kind = cell.traffic["driver"]
    if kind == "train":
        b = driver.build(cell, devices, dalle_cfg, vae_cfg)
        return b["step"].lower(*b["abstract"]).compile()
    if kind == "generate":
        from dalle_pytorch_tpu.models.dalle import tile_prefill

        b = driver.build(cell, dalle_cfg, vae_cfg)
        chip = SingleDeviceSharding(devices[0])
        key = jax.random.PRNGKey(0)
        variables = {"params": jax.eval_shape(b["init_dalle"], key)}
        text = jax.ShapeDtypeStruct((1, dalle_cfg.text_seq_len), jnp.int32)
        first, caches = jax.eval_shape(
            lambda v, t: tile_prefill(*b["prefill"](v, t),
                                      int(cell.traffic["fanout"])),
            variables, text)
        return b["decode"].lower(
            on(chip, variables), on(chip, first), on(chip, caches),
            on(chip, jax.eval_shape(lambda: key))).compile()
    raise SystemExit(f"driver {kind!r} exposes nothing to compile ahead")


def main(argv) -> int:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in argv:
        t0 = time.perf_counter()
        compiled = compile_cell(name, topo)
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.temp_size_in_bytes
                 + m.output_size_in_bytes - m.alias_size_in_bytes)
        print(f"{name}: compiled in {time.perf_counter() - t0:.0f} s; per "
              f"device: arguments {m.argument_size_in_bytes / 1e9:.2f} GB, "
              f"temp {m.temp_size_in_bytes / 1e9:.2f} GB, outputs "
              f"{m.output_size_in_bytes / 1e9:.2f} GB, aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f} GB, planned total "
              f"{total / 1e9:.2f} GB", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
