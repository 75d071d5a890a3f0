"""What every driver shares: the manifest, a cell's files, the models as the
program builds them, seeded inputs, the device record and the tracer.

Nothing here names a cell, a configuration, a traffic mix or a metric: those
are entries of ``BENCHMARK.json`` and files found by the names it gives.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import time
from pathlib import Path
from typing import Any, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
OUT = BENCH / "out"


class BenchError(Exception):
    """The run cannot give a result (no chip, unknown name, bad file)."""


# --- the manifest and a cell's files ------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json, tiny twin applied if asked
    traffic: dict         # traffic/<traffic>.json, likewise
    end_to_end: list      # the manifest's metric entries this cell reports
    per_layer: list
    rehearse: bool = False


def load_manifest(root: Path = REPO) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, val in over.items():
        out[key] = (_merge(out[key], val)
                    if isinstance(val, dict) and isinstance(out.get(key), dict)
                    else val)
    return out


def load_cell(name: str, rehearse: bool = False, root: Path = REPO) -> Cell:
    manifest = load_manifest(root)
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    with open(root / conf["file"]) as f:
        config = json.load(f)
    bench = root / manifest["paths"][0]
    with open(bench / "traffic" / f"{entry['traffic']}.json") as f:
        traffic = json.load(f)
    if rehearse:
        config = _merge(config, config.get("tiny", {}))
        traffic = _merge(traffic, traffic.get("tiny", {}))
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in manifest["end_to_end"]
                            if _in_cell(m, name)],
                per_layer=[m for m in manifest["per_layer"]
                           if _in_cell(m, name)],
                rehearse=rehearse)


def load_driver(cell: Cell):
    return importlib.import_module(
        f"benchmark.drivers.{cell.traffic['driver']}")


def load_reader(metric_name: str):
    return importlib.import_module(
        f"benchmark.layer_metrics.{metric_name}").read


# --- the device ---------------------------------------------------------------

def setup_jax_cache() -> None:
    """The program's own switch (``JAX_COMPILATION_CACHE_DIR`` if set, else
    the fixed ``<checkout>/.cache/xla``), and every program cached, however
    small, so that a cell's second run compiles nothing."""
    import jax

    from dalle_pytorch_tpu.cli import enable_compilation_cache

    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def claim_devices(cell: Cell) -> list:
    """The chips the cell asks for, or a failure: no result is printed for
    another platform unless the run is a rehearsal."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not cell.rehearse:
        raise BenchError(f"no accelerator: jax reports {platform!r} "
                         "(--rehearse runs the tiny twin on the CPU)")
    if len(devices) < cell.chips:
        raise BenchError(f"cell {cell.name} needs {cell.chips} chips, jax "
                         f"reports {len(devices)}")
    return devices[:cell.chips]


def open_cell(name: str, rehearse: bool = False):
    """``(cell, devices, dalle_cfg, vae_cfg)``: the cell's files loaded, the
    compile cache placed and the chips claimed.  Raises ``BenchError``."""
    cell = load_cell(name, rehearse=rehearse)
    setup_jax_cache()
    devices = claim_devices(cell)
    return (cell, devices) + build_configs(cell.config)


def load_peaks(device_kind: str) -> dict:
    with open(BENCH / "peaks.json") as f:
        kinds = json.load(f)["kinds"]
    if device_kind not in kinds:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         "benchmark/peaks.json; add it with its source")
    return kinds[device_kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes on the fullest chip, as the runtime reports them: the peak
    of live arrays (``peak_bytes_in_use``) plus the peak the runtime reserved
    for running programs' temporaries (``peak_bytes_reserved``).  On this
    runtime the first leaves the second out: a train step whose compiler plan
    holds 7.3 GB of temporaries reads 0.40 GB in use and 7.28 GB reserved (my
    chip run, PR 22)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else 0


def device_record(devices, trace=None, memory_peak=None) -> dict:
    rec = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices),
           "memory_peak_bytes": (memory_peak_bytes(devices)
                                 if memory_peak is None else memory_peak)}
    if trace is not None:
        rec["busy_s"] = trace.busy_s
        rec["window_s"] = trace.window_s
    return rec


# --- the models, as the program builds them -----------------------------------

def build_configs(config: dict):
    """``(DALLEConfig, VAEConfig)`` from a configuration file: the VAE in
    f32 as ``train_dalle.py``/``generate.py`` rebuild it from a checkpoint,
    the transformer in the file's dtype."""
    import jax.numpy as jnp

    from dalle_pytorch_tpu import DALLEConfig, VAEConfig

    vae_cfg = VAEConfig(**config["vae"])
    fields = dict(config["dalle"])
    if fields.get("attn_types") is not None:
        fields["attn_types"] = tuple(fields["attn_types"])
    dalle_cfg = DALLEConfig.from_vae(
        vae_cfg, dtype={"bfloat16": jnp.bfloat16,
                        "float32": jnp.float32}[config["dtype"]], **fields)
    return dalle_cfg, vae_cfg


def init_fns(dalle_cfg, vae_cfg):
    """``(dalle, vae, init_dalle(key), init_vae(key))``; the init functions
    are plain and jittable, so weights are made on the device in one call."""
    import jax.numpy as jnp

    from dalle_pytorch_tpu import DALLE, DiscreteVAE

    dalle, vae = DALLE(dalle_cfg), DiscreteVAE(vae_cfg)
    text1 = jnp.zeros((1, dalle_cfg.text_seq_len), jnp.int32)
    codes1 = jnp.zeros((1, dalle_cfg.image_seq_len), jnp.int32)
    img1 = jnp.zeros((1, vae_cfg.image_size, vae_cfg.image_size, 3))

    def init_dalle(key):
        return dalle.init(key, text1, codes1)["params"]

    def init_vae(key):
        return vae.init({"params": key, "gumbel": key}, img1)["params"]

    return dalle, vae, init_dalle, init_vae


# --- seeded inputs --------------------------------------------------------------

def make_prompts(cell: Cell, dalle_cfg, count: int, seed: int) -> np.ndarray:
    """``[count, text_seq_len]`` int32 prompts from the traffic's ``text``
    entry and the seed: tokenised captions, or random ids of random length
    where the configuration has no tokenizer.  0 pads, as the tokenizers do."""
    spec = cell.traffic["text"]
    rng = np.random.default_rng([seed, 7])
    n = dalle_cfg.text_seq_len
    if spec["kind"] == "captions":
        from dalle_pytorch_tpu.data.tokenizer import HugTokenizer

        lines = (BENCH / "traffic" / spec["file"]).read_text().splitlines()
        pick = rng.permutation(len(lines))[np.arange(count) % len(lines)]
        tok = HugTokenizer(str(REPO / cell.config["tokenizer"]))
        ids = tok.tokenize([lines[i] for i in pick], n, truncate_text=True)
        return np.minimum(ids, dalle_cfg.num_text_tokens - 1).astype(np.int32)
    if spec["kind"] == "random_ids":
        hi = min(int(spec["max_len"]), n)
        lens = rng.integers(min(int(spec["min_len"]), hi), hi + 1, size=count)
        ids = rng.integers(1, dalle_cfg.num_text_tokens, size=(count, n))
        return np.where(np.arange(n)[None] < lens[:, None], ids,
                        0).astype(np.int32)
    raise BenchError(f"unknown text kind {spec['kind']!r}")


# --- the tracer -----------------------------------------------------------------

class Tracer:
    """``jax.profiler`` around one steady stretch, with the benchmark's own
    host spans.  Off (``--trace 0``) every method does nothing."""

    def __init__(self, on: bool, cell: str):
        self.on = on
        self.dir = OUT / "trace" / cell
        self.window = None    # (start, end) on time.perf_counter

    def start(self) -> None:
        if not self.on:
            return
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(self.dir))
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        if not self.on:
            return
        import jax

        t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.window = (self._t0, t1)

    def span(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def xplane(self) -> Optional[Path]:
        files = sorted(self.dir.glob("plugins/profile/*/*.xplane.pb"))
        return files[-1] if files else None


# --- what a driver hands back -----------------------------------------------------

@dataclasses.dataclass
class Outcome:
    """A driver's result.  ``end_to_end`` holds the metrics the driver clocks
    itself; ``host`` what the per-layer readers need from the host side;
    ``programs`` the compiled programs by the name the trace gives them
    (``jit_<function>``): their HLO text maps ops to ``graftprof:`` scopes,
    and readers may ask ``memory_analysis`` of ``main_program``;
    ``memory_peak_bytes`` the peak a driver read before checks that run
    programs of their own (None: read at the end of the run)."""

    correct: bool
    attempted: int
    failed: int
    end_to_end: dict
    host: dict = dataclasses.field(default_factory=dict)
    programs: dict = dataclasses.field(default_factory=dict)
    main_program: str = ""
    notes: list = dataclasses.field(default_factory=list)
    memory_peak_bytes: Optional[int] = None


@dataclasses.dataclass
class Run:
    """Everything a per-layer reader may read."""

    cell: Cell
    dalle_cfg: Any
    vae_cfg: Any
    devices: list
    peaks: Optional[dict]
    outcome: Outcome
    trace: Any            # trace_reduce.Reduced, or None
