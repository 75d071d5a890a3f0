"""Compiler-model perf gates: XLA cost_analysis regression tests.

Chip time is scarce and budgeted, so the structural perf invariants are
pinned here against XLA's own cost model (``utils.profiling.compiled_cost_summary``), which is identical
math on every backend — a regression that lands in the production step,
the candidate stack, or the sliced-KV decode fails in CPU-only CI, no
chip required.  The wall-clock half is the benchmark (``BENCHMARK.json`` +
``benchmark/``, measured on the chip); these numbers are compiler-model,
not wall-clock.

Calibration (XLA:CPU, jax 0.8.x, 2026-08; PERF.md "Compiler-model
gates" table):

* production train step (CUB geometry, batch 16):
  flops 2.380e12, bytes 1.981e11, temp 14.46 GiB; analytic/xla = 0.964
* decode step (batch 8): the sliced-KV path's bytes-per-cache-key
  derivative is variant-independent update plumbing (~114.7 kB/key);
  the ``full`` layers' dense read adds ~35.4 kB/key of cache *streaming*
  on top.
  At n=1105 that streaming is ~21x the sliced path's whole reachable
  read set ((81 text + 32 row) keys) — the cache-traffic claim behind
  the sliced decode (ops/attention.py::decode_key_positions), asserted
  here as a derivative so XLA's per-op double-counting cancels out.

Bands are deliberately loose (a jax upgrade may shift costs a few
percent); a real regression — losing the phase-sliced head, breaking
decode_key_positions, an accidental f32 blow-up — moves them far more.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from dalle_pytorch_tpu import DALLE, DALLEConfig
from dalle_pytorch_tpu.ops.attention import AttnPattern, MultiHeadAttention
from dalle_pytorch_tpu.training import make_dalle_train_step, make_optimizer
from dalle_pytorch_tpu.utils.profiling import (compiled_cost_summary,
                                               dalle_train_flops)

GiB = 2 ** 30


def cub_train_costs(batch=16, **overrides):
    """Cost summary of the production train step at the CUB-200 geometry."""
    from dalle_pytorch_tpu.presets import cub200_config

    cfg = cub200_config()
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    model = DALLE(cfg)
    rng = jax.random.PRNGKey(0)
    text = jax.random.randint(rng, (batch, cfg.text_seq_len), 0,
                              cfg.num_text_tokens)
    codes = jax.random.randint(rng, (batch, cfg.image_seq_len), 0,
                               cfg.num_image_tokens)
    params = jax.jit(
        lambda r: model.init(r, text[:1], codes[:1])["params"])(rng)
    tx = make_optimizer(3e-4)
    opt = jax.jit(tx.init)(params)
    raw = make_dalle_train_step(model, tx, jit=False)
    return compiled_cost_summary(raw, params, opt, None, text, codes,
                                 rng), cfg


def layer_decode_costs(variant, n_cache, batch=8, fmap=32, text=81,
                       dtype=jnp.bfloat16, cache_dtype=None,
                       cache_int8=False):
    """Cost summary of ONE attention layer's KV-cache decode step
    (``full`` reads the whole cache, the axial and conv variants only
    their reachable keys: ``decode_key_positions``).

    ``n_cache`` can exceed the pattern's padded length: extra keys are
    mask-dead, so growing it isolates d(bytes)/d(cache key) — the pure
    cache-traffic component, free of XLA's fixed per-op accounting.
    ``cache_dtype`` decouples the cache storage dtype from the activation
    ``dtype`` (the kv_cache_bf16 lever: f32 activations, bf16 cache);
    ``cache_int8`` builds the quantized layout instead — (int8 values,
    f32 per-head scale) pairs (the kv_cache_int8 lever)."""
    n = text - 1 + fmap * fmap
    pat = AttnPattern(variant=variant, seq_len=n, text_len=text, fmap=fmap)
    m = MultiHeadAttention(pattern=pat, dim=256, heads=8, dim_head=64,
                           dtype=dtype)
    x = jnp.zeros((batch, 1, 256), dtype)
    if cache_int8:
        ck = (jnp.zeros((batch, 8, n_cache, 64), jnp.int8),
              jnp.ones((batch, 8, 1, 1), jnp.float32))
        cv = (jnp.zeros((batch, 8, n_cache, 64), jnp.int8),
              jnp.ones((batch, 8, 1, 1), jnp.float32))
    else:
        ck = jnp.zeros((batch, 8, n_cache, 64), cache_dtype or dtype)
        cv = jnp.zeros_like(ck)
    idx = jnp.asarray(text + 5 * fmap + 3)  # an interior image position
    params = m.init(jax.random.PRNGKey(0), x, ck, cv, idx,
                    method=MultiHeadAttention.decode_step)

    def step(params, x, ck, cv, idx):
        return m.apply(params, x, ck, cv, idx,
                       method=MultiHeadAttention.decode_step)

    # caches donated, as in the real sampler's scan carry
    return compiled_cost_summary(step, params, x, ck, cv, idx,
                                 donate_argnums=(2, 3))


def _tree_bytes(tree) -> int:
    return sum(leaf.size * jnp.dtype(leaf.dtype).itemsize
               for leaf in jax.tree.leaves(tree))


def test_cost_summary_smoke():
    """compiled_cost_summary returns the documented fields on a tiny jit
    (fast tier: everything else in this module pays CUB-sized compiles)."""
    out = compiled_cost_summary(lambda a, b: a @ b,
                                jnp.ones((64, 64)), jnp.ones((64, 64)))
    assert out["flops"] >= 2 * 64 ** 3 * 0.99
    assert out["bytes_accessed"] > 0
    if "temp_bytes" in out:
        assert out["argument_bytes"] >= 2 * 64 * 64 * 4


@pytest.fixture(scope="module")
def prod():
    return cub_train_costs(16)


@pytest.mark.slow
def test_production_step_regression_bands(prod):
    """The headline train step's compiler costs, pinned.  A failure here
    means the production step got cheaper (update the calibration and
    PERF.md) or a perf regression landed (fix it) — either way the number
    moved and the perf story must notice."""
    costs, cfg = prod
    assert 0.85 <= dalle_train_flops(cfg, 16) / costs["flops"] <= 1.0
    assert costs["flops"] == pytest.approx(2.380e12, rel=0.08)
    assert costs["bytes_accessed"] == pytest.approx(1.981e11, rel=0.15)
    if "temp_bytes" in costs:
        assert costs["temp_bytes"] == pytest.approx(14.46 * GiB, rel=0.20)


@pytest.mark.slow
@pytest.mark.parametrize("variant,reachable", [
    ("axial_row", 81 + 32),        # all text + the query's raster row
    ("conv_like", 81 + 3 * 32),    # all text + kernel//2+1 rows (k=5, d=1)
])
def test_sliced_decode_eliminates_cache_streaming(variant, reachable):
    """The sliced-KV decode's cache-traffic claim, as a compiler gate.

    XLA's bytes-accessed totals double-count fixed overhead, so the gate
    differentiates with respect to cache length: extra keys are mask-dead,
    and only *streamed* cache reads scale with them.  The sliced path's
    derivative must be pure update plumbing (no read term), while the
    ``full`` variant's dense read pays at least the true k+v row reads (2 caches x batch x heads x dh x 2B
    = 16 kB/key) on top.  At the CUB cache length, the streaming the
    sliced path eliminates must be >= 8x its whole reachable read set —
    the "~10x less cache traffic" line in PERF.md, made falsifiable."""
    n_k, n_k2 = 1105, 2210
    key_row_bytes = 2 * 8 * 8 * 64 * 2  # k+v rows: batch x heads x dh, bf16

    d_sliced = (layer_decode_costs(variant, n_k2)["bytes_accessed"]
                - layer_decode_costs(variant, n_k)["bytes_accessed"]
                ) / (n_k2 - n_k)
    d_dense = (layer_decode_costs("full", n_k2)["bytes_accessed"]
               - layer_decode_costs("full", n_k)["bytes_accessed"]
               ) / (n_k2 - n_k)

    streaming = (d_dense - d_sliced) * n_k      # what slicing eliminates
    sliced_reads = reachable * key_row_bytes    # what slicing still reads
    assert d_dense - d_sliced >= key_row_bytes, (d_dense, d_sliced)
    assert streaming >= 8 * sliced_reads, (streaming, sliced_reads)


def test_bf16_cache_cuts_decode_cache_bytes():
    """The kv_cache_bf16 byte cut, as a compiler gate (fast tier: the
    decode loop's dominant stream is the one perf claim the eval config
    rides on, and single-layer decode compiles are cheap).

    At f32 activations — the dtype every checkpoint-loaded eval model runs
    at — the decode step's cache I/O footprint (memory_analysis argument +
    output bytes: what the decode scan must stream through HBM every step
    just to carry the caches in and out) with a bf16 cache must be ≤ 0.6x
    the f32-cache baseline, for the sliced read (``axial_row``) and the
    dense read (``full``) alike.

    ``bytes_accessed`` cannot carry this gate on the CPU test backend:
    XLA:CPU has no native bf16 dynamic-update-slice and round-trips bf16
    caches through full f32 converts (TPU executes them natively), so its
    traffic totals charge the bf16 build for backend-local converts the
    chip never runs.  The I/O footprint is storage-dtype-faithful on every
    backend and is exactly the quantity the HBM-bound loop streams."""
    n_k = 1105

    def io_bytes(variant, cache_dtype):
        costs = layer_decode_costs(variant, n_k, dtype=jnp.float32,
                                   cache_dtype=cache_dtype)
        if "argument_bytes" not in costs:  # pragma: no cover
            pytest.skip("backend lacks memory_analysis")
        return costs["argument_bytes"] + costs["output_bytes"]

    for variant in ("axial_row", "full"):
        io16 = io_bytes(variant, jnp.bfloat16)
        io32 = io_bytes(variant, jnp.float32)
        assert io16 <= 0.6 * io32, (variant, io16, io32)


def test_int8_cache_cuts_decode_cache_bytes():
    """The kv_cache_int8 byte cut (ISSUE 7 acceptance): the int8-cache
    decode step's arg/out CACHE bytes must be ≤ 0.55x the bf16-cache
    program's at CUB geometry on the sliced read path (fast tier, single
    layer — the model-level twin is slow-tier).

    The cache component is isolated exactly: argument/output bytes are
    deterministic buffer sums, and the two builds differ ONLY in cache
    storage, so ``non_cache = io(bf16) - analytic bf16 cache bytes`` and
    the int8 build's cache stream is ``io(int8) - non_cache``.  The
    analytic int8 number INCLUDES the f32 scale planes
    (profiling.dalle_decode_cache_bytes counts them for the model-level
    form) — a gate that ignored them would under-measure the stream."""
    n_k, batch, heads, dh = 1105, 8, 8, 64
    c16 = 2 * batch * heads * n_k * dh * 2            # k+v caches, bf16
    c8 = 2 * batch * heads * n_k * dh * 1 \
        + 2 * batch * heads * 4                       # int8 + scale planes

    def io(**kw):
        costs = layer_decode_costs("axial_row", n_k, dtype=jnp.float32,
                                   **kw)
        if "argument_bytes" not in costs:  # pragma: no cover
            pytest.skip("backend lacks memory_analysis")
        return costs["argument_bytes"], costs["output_bytes"]

    in16, out16 = io(cache_dtype=jnp.bfloat16)
    in8, out8 = io(cache_int8=True)
    # the caches really are carried at the quantized sizes, in AND out
    assert in16 - in8 >= 0.95 * (c16 - c8), (in16, in8, c16, c8)
    assert out16 - out8 >= 0.95 * (c16 - c8), (out16, out8)
    # the acceptance ratio: int8 cache stream ≤ 0.55x the bf16 one
    cache_in8 = in8 - (in16 - c16)
    cache_out8 = out8 - (out16 - c16)
    assert cache_in8 <= 0.55 * c16, (cache_in8, c16)
    assert cache_out8 <= 0.55 * c16, (cache_out8, c16)


def test_int8_weights_prune_f32_kernels_tiny():
    """weights_int8 weight-stream gate (fast tier, tiny geometry): with
    the session-quantized tree passed as the decode argument, the
    compiled step must stop consuming the f32 decode kernels — jit's
    unused-argument pruning drops them, so argument bytes fall by ≥ 0.7x
    the f32 kernel footprint (int8 copies + scales take ~0.25x back)."""
    from dalle_pytorch_tpu.models.dalle import quantize_decode_weights

    cfg = DALLEConfig(dim=32, depth=2, heads=4, dim_head=8,
                      num_text_tokens=50, text_seq_len=8,
                      num_image_tokens=32, image_size=64, image_fmap_size=4,
                      attn_types=("full", "axial_row"))
    model = DALLE(cfg)
    rng = jax.random.PRNGKey(0)
    text = jax.random.randint(rng, (2, cfg.text_seq_len), 0, 50)
    params = jax.jit(lambda r: model.init(
        r, text, jnp.zeros((2, cfg.image_seq_len), jnp.int32))["params"])(rng)
    caches = [(jnp.zeros((2, cfg.heads, cfg.seq_len, cfg.dim_head),
                         jnp.bfloat16),
               jnp.zeros((2, cfg.heads, cfg.seq_len, cfg.dim_head),
                         jnp.bfloat16)) for _ in range(cfg.depth)]
    code = jnp.zeros((2,), jnp.int32)
    idx = jnp.asarray(cfg.text_seq_len + 2)

    def step(params, code, caches, idx, qw):
        return model.apply({"params": params}, code, caches, idx, None,
                           None, qw, method=DALLE.decode_step)

    plain = compiled_cost_summary(step, params, code, caches, idx, None,
                                  donate_argnums=(2,))
    qw = jax.jit(lambda p: quantize_decode_weights(p, cfg))(params)
    quant = compiled_cost_summary(step, params, code, caches, idx, qw,
                                  donate_argnums=(2,))
    if "argument_bytes" not in plain:  # pragma: no cover
        pytest.skip("backend lacks memory_analysis")
    kernels = [params["transformer"][f"layers_{i}_attn"]["attn"][m]["kernel"]
               for i in range(cfg.depth) for m in ("to_qkv", "to_out")]
    kernels += [params["transformer"][f"layers_{i}_ff"][m]["kernel"]
                for i in range(cfg.depth) for m in ("dense_in", "dense_out")]
    kernels.append(params["to_logits_dense"]["image_kernel"])
    w_bytes = _tree_bytes(kernels)
    saved = plain["argument_bytes"] - quant["argument_bytes"]
    assert saved >= 0.70 * w_bytes, (saved, w_bytes)


@pytest.mark.slow
def test_model_decode_step_bf16_cache_cheaper():
    """End-to-end decode step (8-layer CUB stack at f32 activations): the
    bf16-cache build's per-step cache I/O must shrink by the full k+v
    cache byte delta — i.e. every one of depth x 2 caches really is stored
    (and therefore carried through the scan) at half the bytes."""
    from dalle_pytorch_tpu.presets import cub200_config

    def decode_costs(cache_bf16: bool, batch=8):
        cfg = dataclasses.replace(cub200_config(), dtype=jnp.float32,
                                  kv_cache_bf16=cache_bf16)
        model = DALLE(cfg)
        rng = jax.random.PRNGKey(0)
        text = jax.random.randint(rng, (batch, cfg.text_seq_len), 0,
                                  cfg.num_text_tokens)
        params = jax.jit(lambda r: model.init(
            r, text[:1],
            jnp.zeros((1, cfg.image_seq_len), jnp.int32))["params"])(rng)
        cache_dtype = jnp.bfloat16 if cache_bf16 else jnp.float32
        caches = [(jnp.zeros((batch, cfg.heads, cfg.seq_len, cfg.dim_head),
                             cache_dtype),
                   jnp.zeros((batch, cfg.heads, cfg.seq_len, cfg.dim_head),
                             cache_dtype))
                  for _ in range(cfg.depth)]
        code = jnp.zeros((batch,), jnp.int32)
        idx = jnp.asarray(cfg.text_seq_len + 5)

        def step(params, code, caches, idx):
            return model.apply({"params": params}, code, caches, idx,
                               method=DALLE.decode_step)

        return compiled_cost_summary(step, params, code, caches, idx,
                                     donate_argnums=(2,)), cfg

    bf16, cfg = decode_costs(True)
    f32, _ = decode_costs(False)
    if "argument_bytes" not in bf16:  # pragma: no cover
        pytest.skip("backend lacks memory_analysis")
    from dalle_pytorch_tpu.utils.profiling import dalle_decode_cache_bytes

    # f32 caches carry exactly 2x the bytes of bf16 ones, in AND out of the
    # step, across all depth x (k, v) caches (0.95: I/O also counts the
    # dtype-invariant params/logits, so the delta is the caches alone)
    floor = 0.95 * dalle_decode_cache_bytes(cfg, 8)
    saved_in = f32["argument_bytes"] - bf16["argument_bytes"]
    saved_out = f32["output_bytes"] - bf16["output_bytes"]
    assert saved_in >= floor, (saved_in, floor)
    assert saved_out >= floor, (saved_out, floor)


@pytest.mark.slow
def test_model_decode_step_int8_quantized_serving():
    """End-to-end decode step (8-layer CUB stack, f32 activations) under
    the full ISSUE 7 recipe — int8 caches AND int8 weights: (a) the
    cache stream shrinks to ≤ 0.55x the bf16 build's
    (dalle_decode_cache_bytes, scale planes included), in AND out; (b)
    the weight stream drops by ≥ 0.7x the f32 decode-kernel footprint
    (jit prunes the unreferenced f32 kernels once the int8 copies ride
    the argument list)."""
    from dalle_pytorch_tpu.models.dalle import quantize_decode_weights
    from dalle_pytorch_tpu.presets import cub200_config
    from dalle_pytorch_tpu.utils.profiling import dalle_decode_cache_bytes

    def decode_costs(cache_int8: bool, qw_params=None, batch=8):
        cfg = dataclasses.replace(cub200_config(), dtype=jnp.float32,
                                  kv_cache_int8=cache_int8,
                                  weights_int8=qw_params is not None)
        model = DALLE(cfg)
        rng = jax.random.PRNGKey(0)
        text = jax.random.randint(rng, (batch, cfg.text_seq_len), 0,
                                  cfg.num_text_tokens)
        params = jax.jit(lambda r: model.init(
            r, text[:1],
            jnp.zeros((1, cfg.image_seq_len), jnp.int32))["params"])(rng)
        shape = (batch, cfg.heads, cfg.seq_len, cfg.dim_head)
        if cache_int8:
            entry = lambda: (jnp.zeros(shape, jnp.int8),  # noqa: E731
                             jnp.ones((batch, cfg.heads, 1, 1), jnp.float32))
        else:
            entry = lambda: jnp.zeros(shape, jnp.bfloat16)  # noqa: E731
        caches = [(entry(), entry()) for _ in range(cfg.depth)]
        code = jnp.zeros((batch,), jnp.int32)
        idx = jnp.asarray(cfg.text_seq_len + 5)
        qw = (jax.jit(lambda p: quantize_decode_weights(p, cfg))(params)
              if qw_params is not None else None)

        def step(params, code, caches, idx, qw):
            return model.apply({"params": params}, code, caches, idx, None,
                               None, qw, method=DALLE.decode_step)

        return compiled_cost_summary(step, params, code, caches, idx, qw,
                                     donate_argnums=(2,)), cfg, params

    bf16, cfg16, params = decode_costs(False)
    int8, cfg8, _ = decode_costs(True)
    if "argument_bytes" not in bf16:  # pragma: no cover
        pytest.skip("backend lacks memory_analysis")
    c16 = dalle_decode_cache_bytes(cfg16, 8)
    c8 = dalle_decode_cache_bytes(cfg8, 8)
    assert c8 <= 0.55 * c16  # the analytic model itself halves (w/ scales)
    for field in ("argument_bytes", "output_bytes"):
        saved = bf16[field] - int8[field]
        assert saved >= 0.95 * (c16 - c8), (field, saved, c16, c8)
        cache8 = int8[field] - (bf16[field] - c16)  # non-cache is invariant
        assert cache8 <= 0.55 * c16, (field, cache8, c16)

    # (b) the weight stream: int8 weights on top of the int8 cache
    quant, cfgq, _ = decode_costs(True, qw_params=True)
    kernels = [params["transformer"][f"layers_{i}_attn"]["attn"][m]["kernel"]
               for i in range(cfg16.depth) for m in ("to_qkv", "to_out")]
    kernels += [params["transformer"][f"layers_{i}_ff"][m]["kernel"]
                for i in range(cfg16.depth) for m in ("dense_in",
                                                      "dense_out")]
    kernels.append(params["to_logits_dense"]["image_kernel"])
    w_bytes = _tree_bytes(kernels)
    saved_w = int8["argument_bytes"] - quant["argument_bytes"]
    assert saved_w >= 0.70 * w_bytes, (saved_w, w_bytes)


@pytest.mark.slow
def test_sharded_step_per_device_costs():
    """Sharding-efficiency compiler gate: the production train step jitted
    over the dp2 x fsdp2 x tp2 mesh (the exact Partitioner shardings the
    trainers and __graft_entry__.dryrun_multichip use) must compile to a
    per-device program whose FLOPs are ~1/8 of the unsharded step's.
    Catches, chip-free, the classic GSPMD regressions: a sharding
    annotation lost somewhere makes XLA fully replicate the compute
    (ratio -> 1.0) or force a resharding blow-up — both far outside the
    band.  Calibration (XLA:CPU, tiny CUB-shaped config): ratio 0.128 vs
    ideal 0.125, temp-memory ratio 0.19."""
    from shard_utils import sharded_cub_setup

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    model, cfg, mesh, part, tx, plain, shard = sharded_cub_setup(batch=8)
    step = make_dalle_train_step(model, tx, jit=False)

    single = compiled_cost_summary(step, plain["params"],
                                   plain["opt_state"], None, plain["text"],
                                   plain["codes"], plain["rng"])
    with mesh:
        sharded = compiled_cost_summary(step, shard["params"],
                                        shard["opt_state"], None,
                                        shard["text"], shard["codes"],
                                        shard["rng"])

    ratio = sharded["flops"] / single["flops"]
    assert 1 / 8 <= ratio <= 1.35 / 8, (
        f"per-device flops ratio {ratio:.3f} vs ideal 0.125: the mesh "
        "sharding is replicating or resharding compute")
    if "temp_bytes" in sharded and "temp_bytes" in single:
        temp_ratio = sharded["temp_bytes"] / single["temp_bytes"]
        assert temp_ratio <= 0.5, (
            f"per-device temp memory ratio {temp_ratio:.2f}: activations "
            "or params no longer shard")


@pytest.mark.slow
@pytest.mark.parametrize("impl,sp", [("ring", 4), ("ulysses", 2)])
def test_sequence_parallel_per_device_costs(impl, sp):
    """Sequence-parallelism compiler gate: the sp train step over a
    dp x sp mesh of 8 devices must compile to ~1/8 the dense step's
    per-device FLOPs.  Ring pays exactness recompute and Ulysses the
    all-to-all reshuffles, and both duplicate the (cheap) embedding and
    run the full-vocab head per shard (_sp_loss), so the band allows up
    to 60% overhead over ideal — but a broken shard_map that
    rematerializes the full sequence per device lands at ~1.0/dp, far
    outside it.  Calibration (XLA:CPU, tiny config): ring 0.159,
    ulysses 0.146 vs ideal 0.125."""
    import __graft_entry__ as g
    from dalle_pytorch_tpu.training import make_dalle_sp_train_step

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    # the EXACT construction the multichip dryrun executes
    mesh, model, dense, cfg, text, codes, params = g.build_sp_setup(
        8, impl, sp)
    tx = make_optimizer(1e-3)
    opt = jax.jit(tx.init)(params)

    dense_step = make_dalle_train_step(dense, tx, jit=False)
    single = compiled_cost_summary(dense_step, params, opt, None, text,
                                   codes, jax.random.PRNGKey(0))
    sp_step = make_dalle_sp_train_step(model, tx, mesh, donate=False)
    with mesh:
        sharded = compiled_cost_summary(sp_step, params, opt, None, text,
                                        codes, jax.random.PRNGKey(2))
    ratio = sharded["flops"] / single["flops"]
    n_dev = 8
    assert 1 / n_dev <= ratio <= 1.6 / n_dev, (
        f"{impl} per-device flops ratio {ratio:.3f} outside "
        f"[{1 / n_dev:.3f}, {1.6 / n_dev:.3f}]: above = sequence sharding "
        "is replicating compute; below = the compiler's loop accounting "
        "changed (re-calibrate if intentional)")


@pytest.mark.slow
def test_pipeline_parallel_per_device_costs():
    """Pipeline-parallelism compiler gate: the GPipe train step over a
    dp4 x pp2 mesh must compile to a per-device program far below the
    dense step's FLOPs.  The band is calibrated, not derived (0.113 at
    the tiny config): XLA's cost model may count a scan body once rather
    than per trip, so the number is a fingerprint of the compiled
    schedule — what the gate catches is the failure mode where pipeline
    staging silently degrades to every device running the whole stack
    (ratio ~0.5 at dp4, ~1.0 unsharded)."""
    import __graft_entry__ as g
    from dalle_pytorch_tpu.training import make_dalle_pp_train_step

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    # the EXACT construction the multichip dryrun executes
    mesh, model, cfg, text, codes, params = g.build_pp_setup(8, pp=2)
    tx = make_optimizer(1e-3)
    opt = jax.jit(tx.init)(params)
    dense_step = make_dalle_train_step(model, tx, jit=False)
    single = compiled_cost_summary(dense_step, params, opt, None, text,
                                   codes, jax.random.PRNGKey(2))
    step, pp_params = make_dalle_pp_train_step(model, tx, params, mesh,
                                               num_microbatches=2,
                                               donate=False)
    pp_opt = jax.jit(tx.init)(pp_params)
    with mesh:
        sharded = compiled_cost_summary(step, pp_params, pp_opt, None,
                                        text, codes, jax.random.PRNGKey(2))
    ratio = sharded["flops"] / single["flops"]
    assert 0.08 <= ratio <= 0.18, (
        f"pp per-device flops ratio {ratio:.3f} vs calibrated 0.113: the "
        "pipeline schedule changed shape — re-calibrate if intentional")


@pytest.mark.slow
def test_expert_parallel_per_device_costs():
    """Expert-parallelism compiler gate: the MoE train step with expert
    kernels sharded over a dp2 x ep4 mesh must compile to per-device
    FLOPs near 1/8 of the unsharded dense-dispatch step (calibrated
    0.151 — attention shards over dp·ep while each device keeps 1/ep of
    the experts).  An ep-sharding regression that replicates the expert
    kernels lands at ~0.5 (dp-only) and fails."""
    import __graft_entry__ as g

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    # the EXACT construction the multichip dryrun executes
    mesh, model, cfg, plain, shard = g.build_ep_setup(8, ep=4)
    params, text, codes = plain
    params_s, text_s, codes_s = shard
    tx = make_optimizer(1e-3)
    opt = jax.jit(tx.init)(params)
    step = make_dalle_train_step(model, tx, donate=False, jit=False)
    single = compiled_cost_summary(step, params, opt, None, text, codes,
                                   jax.random.PRNGKey(2))
    opt_s = jax.jit(tx.init)(params_s)
    with mesh:
        sharded = compiled_cost_summary(step, params_s, opt_s, None,
                                        text_s, codes_s,
                                        jax.random.PRNGKey(2))
    ratio = sharded["flops"] / single["flops"]
    assert 1 / 8 <= ratio <= 1.6 / 8, (
        f"ep per-device flops ratio {ratio:.3f} outside [0.125, 0.2]: "
        "above = expert kernels replicating instead of ep-sharding; below "
        "= the compiler's loop accounting changed (re-calibrate if "
        "intentional)")
