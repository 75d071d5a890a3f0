"""Ahead-of-time compiles for a DESCRIBED TPU v5e (on-chip-measurement guide
§2.3): the main path's kernels and steps at real widths, asked of the
chip's own compiler with no chip attached.

This catches what interpret mode cannot — a slice not aligned to the tiling,
a kernel that wants more VMEM than it may use, a step that does not fit the
device — at no chip time.  Nothing runs: a compile that passes here is a
compile, never a chip run, and says nothing about results or speed.

Skipped as a whole where the TPU compiler cannot describe the chip.  The
persistent compilation cache is bypassed around every compile: such an
executable is written to the cache but cannot be read back without a chip.
"""
from __future__ import annotations

import dataclasses
import math
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from dalle_pytorch_tpu import DALLE  # noqa: E402
from dalle_pytorch_tpu.lint.spmd import fresh_stats_compile  # noqa: E402
from dalle_pytorch_tpu.ops.attention import AttnPattern  # noqa: E402
from dalle_pytorch_tpu.ops.attention_pallas import (  # noqa: E402
    flash_qkv_attention)
from dalle_pytorch_tpu.presets import cub200_config  # noqa: E402

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _bypass_persistent_cache():
    with fresh_stats_compile():
        yield


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _param_shapes(cfg):
    model = DALLE(cfg)
    shapes = jax.eval_shape(
        lambda r: model.init(
            r, jnp.zeros((1, cfg.text_seq_len), jnp.int32),
            jnp.zeros((1, cfg.image_seq_len), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    return model, shapes


# --- the three Pallas kernels, directly -------------------------------------

def _compile_attention(one_chip, variant, block_q, block_k, shape, grad,
                       fmap):
    """The kernel on the projections' own array: ``shape`` is (batch, heads,
    n, dim_head) of a ``qkv [b, n, 3, heads, dh]``."""
    text = 80
    n = text + fmap * fmap
    b, heads, length, dh = shape
    assert length == n
    pattern = AttnPattern(variant=variant, seq_len=n - 1, text_len=text,
                          fmap=fmap)
    x = jax.ShapeDtypeStruct((b, n, 3, heads, dh), jnp.bfloat16,
                             sharding=one_chip)

    def fwd(qkv):
        return flash_qkv_attention(qkv, pattern, block_q=block_q,
                                   block_k=block_k, interpret=False)

    def loss(qkv):
        return jnp.sum(fwd(qkv).astype(jnp.float32))

    fn = jax.grad(loss) if grad else fwd
    return jax.jit(fn).lower(x).compile()


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("blocks", [(128, 128), (256, 512)],
                         ids=["b128x128", "b256x512"])
@pytest.mark.parametrize("variant", chip_smoke.VARIANTS)
def test_kernels_compile_at_cub_shape(one_chip, variant, blocks, grad):
    """_call_fwd alone (1 kernel) / the forward and the one backward kernel
    (2; 3 until PR 41, dq apart from dk/dv) at the CUB train shape (b16, h8,
    n1104, dh64) bf16."""
    compiled = _compile_attention(one_chip, variant, *blocks,
                                  (16, 8, 1104, 64), grad, fmap=32)
    assert compiled.as_text().count("tpu_custom_call") == (2 if grad else 1)


@pytest.mark.parametrize("blocks,grad", [
    ((384, 384), False), ((384, 384), True), ((256, 512), False)],
    ids=["b384x384-fwd", "b384x384-grad", "b256x512-fwd"])
def test_kernels_compile_at_fmap64_shape(one_chip, blocks, grad):
    """The fmap-64 shape (b4, h8, n4176, dh64) of the long-sequence A/B, at
    the tiles the selection gives there (384: the kernels' loops are
    unrolled, and 128 x 128 would leave 561 blocks to each) and one pair
    more."""
    compiled = _compile_attention(one_chip, "full", *blocks,
                                  (4, 8, 4176, 64), grad, fmap=64)
    assert compiled.as_text().count("tpu_custom_call") == (2 if grad else 1)


@pytest.mark.parametrize("blocks,grad,fits", [
    ((256, 512), True, True), ((512, 512), False, True),
    ((2176, 2176), True, True), ((2432, 2432), True, False),
    ((2560, 2560), True, False)],
    ids=["b256x512-grad", "b512x512-fwd", "b2176x2176-grad",
         "b2432x2432-grad", "b2560x2560-grad"])
def test_compiler_refuses_large_tiles_at_fmap64(one_chip, blocks, grad, fits):
    """Turned round in PR 28: the compiler's answer and the guard's estimate
    (ops/attention_pallas.py::_vmem_resident_bytes), side by side, at n =
    4176.  Until PR 28 the kernel held ``[block_q, n_pad]`` bool mask rows
    that the estimate counted at a byte an element, and the compiler refused
    the first two pairs while the guard passed them.  The kernel holds the
    pattern's distinct mask tiles once and may take 96 MiB.  On the
    projections' own arrays (PR 35) both took every tiling up to 2304 x 2304
    and refused the backward at 2560 x 2560.  Re-derived for the one
    backward kernel (PR 41: a float32 dq accumulator of the whole sequence
    and two copies of the three gradients' lane blocks held besides): both
    take 2176 x 2176 (the estimate reads 88.5 MiB) and both refuse 2432 x
    2432 (105.4; the compiler asks 114.1) and 2560 x 2560 (114.5).  At 2304
    the compiler still takes what the guard refuses (96.7): the guard errs
    on the side that costs a smaller tile, never a failed compile."""
    from dalle_pytorch_tpu.ops import attention_pallas as ap

    pattern = AttnPattern(variant="full", seq_len=4175, text_len=80, fmap=64)
    tiles = ap._pattern_blocks(pattern, 4176, *blocks).tiles.shape[0]
    estimate = ap._vmem_resident_bytes(4176, 128, 2, *blocks, tiles)
    assert (estimate <= ap.VMEM_BUDGET_BYTES) == fits
    if fits:
        _compile_attention(one_chip, "full", *blocks, (4, 8, 4176, 64), grad,
                           fmap=64)
        return
    with pytest.raises(ValueError, match="VMEM"):    # the guard, at the edge
        _compile_attention(one_chip, "full", *blocks, (4, 8, 4176, 64), grad,
                           fmap=64)
    with pytest.MonkeyPatch.context() as patch:      # the compiler, past it
        patch.setattr(ap, "VMEM_BUDGET_BYTES", 10 ** 12)
        with pytest.raises(Exception, match="vmem"):
            _compile_attention(one_chip, "full", *blocks, (4, 8, 4176, 64),
                               grad, fmap=64)

# --- the default: the kernel chosen by shape, where the step is lowered ------

def _kernel_calls(hlo_text):
    return [line for line in hlo_text.splitlines()
            if "tpu_custom_call" in line and " custom-call(" in line]


def _train_cell_step(name, devices):
    from benchmark import harness

    cell = harness.load_cell(name)
    dalle_cfg, vae_cfg = harness.build_configs(cell.config)
    b = harness.load_driver(cell).build(cell, devices[:cell.chips],
                                        dalle_cfg, vae_cfg)
    return dalle_cfg, b["step"].lower(*b["abstract"]).compile()


def _assert_flash_step(cfg, compiled):
    """Two kernels a layer (three until PR 41), each under
    ``graftprof:attn-scores`` (or the trace would read the forward alone),
    and no ``f32[.., n, n]`` left."""
    text = compiled.as_text()
    calls = _kernel_calls(text)
    assert len(calls) == 2 * cfg.depth
    assert all("graftprof:attn-scores" in line for line in calls)
    n = cfg.seq_len
    for side in {n, -(-n // 128) * 128}:
        assert not re.search(rf"f32\[[\d,]*{side},{side}\]", text), side


def _glue(hlo_text, n):
    """Instructions that only move a ``bf16[.., n, ..]`` activation about
    under one of the attention layer's scopes: ``(scope, opcode)`` of every
    ``pad``, ``slice``, ``copy`` and ``transpose`` (a fusion that computes
    something is not one, nor is the one in-place ``dynamic-update-slice`` a
    layer that writes dv beside dq and dk)."""
    found = []
    for line in hlo_text.splitlines():
        op = re.search(r"= bf16\[([\d,]+)\]\S* (pad|slice|copy|transpose)\(",
                       line)
        scope = re.search(r"graftprof:(attn-[\w]+)", line)
        if op and scope and str(n) in op.group(1).split(","):
            found.append((scope.group(1), op.group(2)))
    return found


def _gradient_moves(hlo_text, batch, cfg):
    """Instructions, fused or not, that update or copy a whole ``bf16[batch,
    n, 3 * heads * dim_head]`` array: what the backward kernel's gradient
    would cost beside the call (until PR 41 dv went into the dk/dv call's
    buffer by one ``dynamic-update-slice``)."""
    shape = f"bf16[{batch},{cfg.seq_len},{3 * cfg.heads * cfg.dim_head}]"
    return [line.split(" = ")[0].strip() for line in hlo_text.splitlines()
            if f"= {shape}" in line and re.search(
                r" (dynamic-update-slice|copy|copy-start)\(", line)]


def test_default_cub200_train_step_holds_the_kernel(topo):
    """``cub200-train``'s step as the benchmark builds it (batch 16, the VAE
    inside): 16 kernels on the projections' own arrays (24 until PR 41,
    which made the backward one call; PR 35): nothing is
    padded or sliced under ``attn-scores`` (the parent held 48 pads and 56
    slices of 1104 -> 1152 about its kernels), and no copy or transposition
    of a ``bf16[.., 1104, ..]`` activation lies between ``to_qkv``'s product
    and a kernel or between a kernel and ``to_out``'s (the parent: 64
    copies); the compiler plans 2.34 GB (my AOT compile, PR 35) where the
    padded, head-major residuals made it 3.11 and the dense scores 7.57
    (ledger, PR 27).  Nor does the gradient of ``qkv`` move beside the
    backward call: no update or copy of a ``bf16[16, 1104, 1536]``."""
    cfg, compiled = _train_cell_step("cub200-train", topo.devices)
    _assert_flash_step(cfg, compiled)
    assert _glue(compiled.as_text(), cfg.seq_len) == []
    assert _gradient_moves(compiled.as_text(), 16, cfg) == []
    mem = compiled.memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            < 2.4 * 2 ** 30)


def test_default_lucid1024_dp_step_splits_the_kernel(topo):
    """``lucid1024-train-dp4``'s step under the ``dp`` plan on the described
    2x2: the kernel calls run inside a ``shard_map`` over the batch axis (a
    Mosaic kernel cannot be partitioned by GSPMD), so the step compiles, holds
    no all-gather, and its collectives are the gradient all-reduces alone:
    559.6 MB of result bytes, ``collective_bytes_per_step`` of the ledger's
    PR 27.  No update or copy of a chip's ``bf16[4, 1280, 3072]`` gradient
    of ``qkv`` lies beside the backward call."""
    from benchmark.layer_metrics import collective_bytes_per_step as reader

    cfg, compiled = _train_cell_step("lucid1024-train-dp4", topo.devices)
    _assert_flash_step(cfg, compiled)
    text = compiled.as_text()
    assert _glue(text, cfg.seq_len) == []   # nor a copy about the kernels
    assert _gradient_moves(text, 4, cfg) == []    # 16 images over 4 chips
    assert " all-gather(" not in text and " all-gather-start(" not in text

    class Run:
        devices = topo.devices
        outcome = type("Outcome", (), {
            "programs": {"step": compiled}, "main_program": "step"})

    assert round(reader.read(Run) / 1e6, 1) == 559.6


def test_rematerialised_layer_compiles(one_chip):
    """``use_remat``: the custom VJP under ``jax.checkpoint`` compiles for
    the chip (forward, its recomputation, the backward: 3 kernels a layer;
    4 until PR 41)."""
    cfg = dataclasses.replace(cub200_config(), depth=1, use_remat=True)
    model, shapes = _param_shapes(cfg)
    batch = jax.ShapeDtypeStruct((16, cfg.text_seq_len), jnp.int32,
                                 sharding=one_chip)
    codes = jax.ShapeDtypeStruct((16, cfg.image_seq_len), jnp.int32,
                                 sharding=one_chip)
    compiled = jax.jit(jax.grad(
        lambda p, t, c: model.apply({"params": p}, t, c, return_loss=True))
    ).lower(_on(one_chip, shapes), batch, codes).compile()
    assert len(_kernel_calls(compiled.as_text())) == 3


@pytest.mark.parametrize("cell", ["lucid1024-generate", "cub200-generate",
                                  "jamba2-3b-generate"])
def test_generate_prefill_holds_no_kernel(one_chip, cell):
    """The generate cells' prefill (one batch-1 pass a request, over the
    whole padded sequence) keeps the dense branch: lowered for the chip, the
    program holds no custom call."""
    from benchmark import harness
    from dalle_pytorch_tpu.models.dalle import prefill_codes

    cell = harness.load_cell(cell)
    cfg = harness.build_configs(cell.config)[0]
    model, shapes = _param_shapes(cfg)
    lowered = jax.jit(
        lambda v, t: prefill_codes(model, v, t)).lower(
        {"params": _on(one_chip, shapes)},
        jax.ShapeDtypeStruct((1, cfg.text_seq_len), jnp.int32,
                             sharding=one_chip))
    assert "tpu_custom_call" not in lowered.as_text()


# --- the serving entry points at CUB width ----------------------------------

@pytest.fixture(scope="module")
def serve_arena():
    """A CUB-width SlotArena built on the CPU: its jitted entry points are
    what gets lowered for the described chip.  Serving runs f32 activations
    over the bf16 KV cache (checkpoints carry no dtype)."""
    from dalle_pytorch_tpu.serve import SlotArena

    cfg = dataclasses.replace(cub200_config(), dtype=jnp.float32)
    model, shapes = _param_shapes(cfg)
    return cfg, SlotArena(model, {"params": shapes}, num_slots=4,
                          filter_thres=1.0)


def _scalar(sharding, dtype):
    return jax.ShapeDtypeStruct((), dtype, sharding=sharding)


def test_serve_prefill_and_admit_compile(one_chip, serve_arena):
    cfg, arena = serve_arena
    variables = _on(one_chip, arena.variables)
    text = jax.ShapeDtypeStruct((1, cfg.text_seq_len), jnp.int32,
                                sharding=one_chip)
    prefill = arena._prefill.lower(variables, text)
    first_logits, caches = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        prefill.out_info)
    prefill.compile()
    arena._admit.lower(
        _on(one_chip, arena.state), _scalar(one_chip, jnp.int32),
        first_logits, caches,
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
        _scalar(one_chip, jnp.float32), _scalar(one_chip, jnp.int32)
    ).compile()


def test_serve_tick_compiles_and_fits(one_chip, serve_arena):
    cfg, arena = serve_arena
    compiled = arena._tick.lower(
        _on(one_chip, arena.variables), _on(one_chip, arena.state),
        jax.ShapeDtypeStruct((4,), jnp.bool_, sharding=one_chip),
        _scalar(one_chip, jnp.int32), None).compile()
    mem = compiled.memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            < V5E_HBM_BYTES)


def test_serve_programs_hold_no_copy_of_a_whole_cache(one_chip):
    """ISSUE 37: at 128 slots of ``dim_head`` 64 the chip kept the arena's
    plain ``[slots, 8, n, 64]`` caches with the SLOTS on the lanes, and the
    tick copied each of them in and out (at these shapes the parent's tick
    held 16 such copies, 302 MB).  Stored by ``MultiHeadAttention.
    arena_form`` the compiled tick and install hold none: every cache is
    read and written where it lies.  One cycle of the ``cub200`` patterns at
    fmap 8 (n = 144): the choice of layout follows slots and ``dim_head``,
    not the length."""
    from dalle_pytorch_tpu.serve import SlotArena
    from dalle_pytorch_tpu.serve.engine import relayout_bytes

    slots = 128
    cfg = dataclasses.replace(cub200_config(), depth=4, image_fmap_size=8)
    model, shapes = _param_shapes(cfg)
    arena = SlotArena(model, {"params": shapes}, num_slots=slots,
                      filter_thres=0.9)
    sizes = {a.size for pair in arena.state["caches"] for a in pair}
    assert sizes == {slots * cfg.heads * cfg.seq_len * cfg.dim_head}
    variables, state = _on(one_chip, arena.variables), _on(one_chip,
                                                           arena.state)
    tick = arena._tick.lower(
        variables, state,
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip),
        _scalar(one_chip, jnp.int32), None).compile()
    assert relayout_bytes(tick.as_text(), sizes) == 0
    prefill = arena._prefill.lower(variables, jax.ShapeDtypeStruct(
        (1, cfg.text_seq_len), jnp.int32, sharding=one_chip))
    first_logits, caches = _on(one_chip, prefill.out_info)
    admit = arena._admit.lower(
        state, _scalar(one_chip, jnp.int32), first_logits, caches,
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
        _scalar(one_chip, jnp.float32), _scalar(one_chip, jnp.int32)
    ).compile()
    assert relayout_bytes(admit.as_text(), sizes) == 0
    # an install's temporaries are of one slot's size, not of the arena's
    one_slot = sum(a.nbytes for pair in arena.state["caches"]
                   for a in pair) // slots
    assert admit.memory_analysis().temp_size_in_bytes < 2 * one_slot


# --- the decode scan's carried KV caches: no lane padding --------------------

_LAID_OUT = re.compile(r"\b(?:bf16|s8)\[([\d,]+)\]\{([\d,]+):T\(")


def _lane_widths(hlo_text, elements):
    """The lane (minor-most) dimension of every tiled 1- or 2-byte array of
    ``elements`` elements in a compiled program's text."""
    widths = set()
    for dims, minor_to_major in _LAID_OUT.findall(hlo_text):
        dims = [int(d) for d in dims.split(",")]
        if math.prod(dims) == elements:
            widths.add(dims[int(minor_to_major.split(",")[0])])
    return widths


@pytest.mark.parametrize("cell", ["lucid1024-generate", "cub200-generate"])
def test_decode_scan_carries_unpadded_caches(one_chip, cell):
    """``decode_codes`` at the two generate cells' shapes (depth cut to 2):
    lucid1024 x 32 rows (heads 16 x 64, n 1280), where XLA pads ``dim_head``
    64 to the 128 lanes unless the caches are carried head-folded, and
    cub200 x 128 rows (heads 8 x 64, n 1104), where the batch fills the
    lanes.  Neither program may hold a cache-sized array 64 lanes wide, nor
    plan temporaries much over the caches' own bytes: a jax or libtpu that
    pads again is caught here, without a chip."""
    from benchmark import harness
    from dalle_pytorch_tpu.models.dalle import (decode_codes, prefill_codes,
                                                tile_prefill)

    cell = harness.load_cell(cell)
    rows = int(cell.traffic["fanout"])
    cfg = dataclasses.replace(harness.build_configs(cell.config)[0], depth=2)
    model, shapes = _param_shapes(cfg)
    variables = {"params": shapes}
    first, caches = jax.eval_shape(
        lambda v, t: tile_prefill(*prefill_codes(model, v, t), rows),
        variables, jnp.zeros((1, cfg.text_seq_len), jnp.int32))
    compiled = jax.jit(
        lambda v, f, c, k: decode_codes(model, v, f, c, k, filter_thres=0.9)
    ).lower(_on(one_chip, variables), _on(one_chip, first),
            _on(one_chip, caches),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
            ).compile()
    one_cache = math.prod(caches[0][0].shape)
    assert caches[0][0].dtype == jnp.bfloat16
    widths = _lane_widths(compiled.as_text(), one_cache)
    assert widths and min(widths) >= 128, widths
    cache_bytes = 2 * cfg.depth * one_cache * 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1.3 * cache_bytes


# --- the sampler's top-k cut-off: selected, not sorted -----------------------

def _sampler_sorts(hlo_text):
    """The instructions under the ``sample`` scope that order a row: a
    ``sort``, or a ``top_k`` under either of its names."""
    return [line.split(" = ")[0].strip() for line in hlo_text.splitlines()
            if "graftprof:sample" in line
            and re.search(r"\bsort\(|top_?k", line.split("metadata=")[0],
                          re.IGNORECASE)]


@pytest.mark.parametrize("top_p", [None, 0.9], ids=["top_k", "top_k+top_p"])
def test_decode_program_sorts_only_for_the_nucleus(one_chip, top_p):
    """``decode_codes`` of ``cub200-generate``'s tiny twin, compiled for the
    chip: the top-k filter finds its cut-off by counting passes, so nothing
    under the ``sample`` scope sorts; the nucleus filter, which needs the
    sorted cumulative mass, still does when ``top_p`` is set."""
    from benchmark import harness
    from dalle_pytorch_tpu.models.dalle import (decode_codes, prefill_codes,
                                                tile_prefill)

    cell = harness.load_cell("cub200-generate", rehearse=True)
    cfg = harness.build_configs(cell.config)[0]
    model, shapes = _param_shapes(cfg)
    variables = {"params": shapes}
    first, caches = jax.eval_shape(
        lambda v, t: tile_prefill(*prefill_codes(model, v, t),
                                  int(cell.traffic["fanout"])),
        variables, jnp.zeros((1, cfg.text_seq_len), jnp.int32))
    # 6 of the twin's 64 logits: the cell's own 0.9 would keep them all
    thres = 1.0 - 6.5 / cfg.total_tokens
    compiled = jax.jit(
        lambda v, f, c, k: decode_codes(model, v, f, c, k,
                                        filter_thres=thres, top_p=top_p)
    ).lower(_on(one_chip, variables), _on(one_chip, first),
            _on(one_chip, caches),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
            ).compile()
    text = compiled.as_text()
    assert "graftprof:sample" in text
    assert bool(_sampler_sorts(text)) == (top_p is not None)


# --- the long compiles: kept, but outside the quick tier --------------------

@pytest.mark.slow
def test_dense_train_step_compiles_and_fits(topo):
    """The full dense CUB train step (batch 16, Adam, codes path): the
    compiler's 2026-09-26 answer was temp 7.3 GB + arguments 0.23 GB of
    16 GB, in ~13 s."""
    _, _, step, abstract = chip_smoke.plan_step(
        "dp", topo.devices[:1], cub200_config(), 16)
    mem = step.lower(*abstract).compile().memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            < V5E_HBM_BYTES)


@pytest.mark.slow
def test_generate_b8_compiles(one_chip):
    """Prefill + the 1024-step decode scan at batch 8 (~18 s)."""
    from dalle_pytorch_tpu.models.dalle import decode_codes, prefill_codes

    cfg = dataclasses.replace(cub200_config(), dtype=jnp.float32)
    model, shapes = _param_shapes(cfg)

    def generate(variables, text, key):
        first_logits, caches = prefill_codes(model, variables, text)
        return decode_codes(model, variables, first_logits, caches, key,
                            filter_thres=0.9)

    jax.jit(generate).lower(
        {"params": _on(one_chip, shapes)},
        jax.ShapeDtypeStruct((8, cfg.text_seq_len), jnp.int32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)).compile()


def test_the_absorbed_latent_tick_compiles_and_decompresses_nothing(one_chip):
    """One latent-attention layer at GLM-4.7-Flash's published widths, 8 rows
    against a 4,352-slot cache (ops/latent_attention.py): the tick compiles
    for the v5e, holds one branch a bucket of ``read_bounds`` and no array as
    long as the cache and as wide as the heads' decompressed keys and values
    (20 x 448): it reads the latent in the absorbed form."""
    from dalle_pytorch_tpu.ops.attention import read_bounds
    from dalle_pytorch_tpu.ops.latent_attention import LatentAttention

    rows, slots, dim = 8, 4352, 2048
    layer = LatentAttention(
        pattern=AttnPattern(variant="full", seq_len=slots, text_len=257,
                            fmap=64, causal=True),
        dim=dim, heads=20, q_rank=768, kv_rank=512, nope_dim=192,
        rope_dim=64, value_dim=256, rope_theta=1e6, eps=1e-5,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((rows, 1, dim), jnp.bfloat16, sharding=one_chip)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4, dim), jnp.bfloat16))
    caches = jax.eval_shape(lambda: layer.init_cache(rows, slots,
                                                     jnp.bfloat16))
    compiled = jax.jit(lambda p, x, c, kr, i: layer.apply(
        p, x, c, kr, i, method=LatentAttention.decode_step)).lower(
            _on(one_chip, params), x, *_on(one_chip, caches),
            _scalar(one_chip, jnp.int32)).compile()
    text = compiled.as_text()
    assert len(read_bounds(slots)) == 7 and text.count(" conditional(") == 1
    for decompressed in ("4352,20,448", "20,4352,448", "4352,8960",
                         "4352,20,192", "4352,20,256"):
        assert decompressed not in text, decompressed
    assert f"bf16[{rows},{slots},512]" in text


def test_the_one_pass_latent_read_compiles_at_the_cells_shape(one_chip):
    """ops/latent_attention_pallas.py at ``glm-4.7-flash-generate``'s shape
    (128 rows x 4,352 slots of 512 + 64 values, 20 heads, PERF.md PR 39): the
    fold and the read lower for the v5e from this CPU host, 16 rows a
    program; and one layer's tick against the folded cache holds the kernel,
    no switch, and no copy of the cache."""
    from dalle_pytorch_tpu.ops.latent_attention import (LatentAttention,
                                                        rows_per_program)
    from dalle_pytorch_tpu.ops.latent_attention_pallas import (
        fold_latent_blocks, latent_read)

    rows, slots, heads, rank, rope, dim = 128, 4352, 20, 512, 64, 2048

    def on(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    per = rows_per_program(rows, rank + rope, jnp.bfloat16)
    assert per == 16
    folded = on(rows, slots // 2, 2 * (rank + rope))
    compiled = jax.jit(lambda c, kr: fold_latent_blocks(
        c, kr, rows_per_program=8)).lower(
            on(rows, slots, rank), on(rows, slots, rope)).compile()
    # one pass: nothing as large as a layer's cache beside the result
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 28
    text = jax.jit(lambda q, qr, lat, i: latent_read(
        q, qr, lat, i, rows_per_program=per)).lower(
            on(rows, heads, rank), on(rows, heads, rope), folded,
            _scalar(one_chip, jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1

    layer = LatentAttention(
        pattern=AttnPattern(variant="full", seq_len=slots, text_len=257,
                            fmap=64, causal=True),
        dim=dim, heads=heads, q_rank=768, kv_rank=rank, nope_dim=192,
        rope_dim=rope, value_dim=256, rope_theta=1e6, eps=1e-5,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4, dim), jnp.bfloat16))
    compiled = jax.jit(lambda p, x, lat, i: layer.apply(
        p, x, lat, None, i, method=LatentAttention.decode_step),
        donate_argnums=2).lower(
            _on(one_chip, params), on(rows, 1, dim), folded,
            _scalar(one_chip, jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert " conditional(" not in text
    cache = rows * (slots // 2) * 2 * (rank + rope) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < cache // 4


def test_the_one_pass_delta_step_compiles_at_the_cells_shape(one_chip):
    """ops/linear_attention_pallas.py at ``olmo-hybrid-7b-generate``'s shape
    (64 rows, 30 heads of 96 x 192, the state ``[64, 15, 96, 384]`` float32,
    PERF.md): the kernel lowers for the v5e from this CPU host and
    takes the state's buffer for the updated state; one linear layer's tick
    holds the kernel and no copy of the state, nor temporaries of its
    size."""
    from dalle_pytorch_tpu.ops.linear_attention import GatedDeltaMixer
    from dalle_pytorch_tpu.ops.linear_attention_pallas import delta_step

    rows, heads, dk, dv, dim = 64, 30, 96, 192, 3840
    state = (rows, heads // 2, dk, 2 * dv)
    state_bytes = math.prod(state) * 4

    def on(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(delta_step, donate_argnums=0).lower(
        on(*state), on(rows, heads, dk), on(rows, heads, dk),
        on(rows, heads, dv), on(rows, heads), on(rows, heads)).compile()
    assert len(_kernel_calls(compiled.as_text())) == 1
    assert compiled.memory_analysis().alias_size_in_bytes == state_bytes

    layer = GatedDeltaMixer(dim=dim, heads=heads, key_dim=dk, value_dim=dv,
                            dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4, dim), jnp.bfloat16))
    compiled = jax.jit(lambda p, x, w, S: layer.apply(
        p, x, w, S, method=GatedDeltaMixer.decode_step),
        donate_argnums=(2, 3)).lower(
            _on(one_chip, params), on(rows, 1, dim, dtype=jnp.bfloat16),
            on(rows, 3, heads * (2 * dk + dv), dtype=jnp.bfloat16),
            on(*state)).compile()
    text = compiled.as_text()
    assert len(_kernel_calls(text)) == 1
    shape = f"f32[{','.join(map(str, state))}]"
    assert not [line for line in text.splitlines() if f"= {shape}" in line
                and re.search(r" (copy|copy-start|fusion)\(", line)]
    assert compiled.memory_analysis().temp_size_in_bytes < state_bytes // 4


def test_the_window_and_global_scan_compiles_at_published_widths(one_chip):
    """``laguna-s-2.1-generate``'s decode program at the cell's 96 rows and
    published widths, depth cut to 2 (a YaRN-rotated global layer of 48
    heads and a 512-key window layer of 72, over 8 key heads of 128, both
    gated): it compiles for the v5e, carries the global cache whole and the
    window's ring at 512 slots, holds one bounded read a layer (a switch of
    ``read_bounds`` of the slots each holds), and plans temporaries of about
    the scan's own copy of the caches, no more."""
    from benchmark import harness
    from dalle_pytorch_tpu.models.dalle import (decode_codes, prefill_codes,
                                                tile_prefill)

    cell = harness.load_cell("laguna-s-2.1-generate")
    rows, n_prime = int(cell.traffic["fanout"]), int(
        cell.traffic["prime_codes"])
    cfg = dataclasses.replace(harness.build_configs(cell.config)[0], depth=2)
    assert cfg.mixers == ("rotated", "window") and rows == 96
    model, shapes = _param_shapes(cfg)
    variables = {"params": shapes}
    text = jnp.zeros((1, cfg.text_seq_len), jnp.int32)
    prime = jnp.zeros((1, n_prime), jnp.int32)
    first, caches = jax.eval_shape(
        lambda v, t, p: tile_prefill(*prefill_codes(model, v, t,
                                                    prime_codes=p), rows),
        variables, text, prime)
    assert [c[0].shape for c in caches] == [(rows, 8, 4352, 128),
                                           (rows, 8, 512, 128)]
    compiled = jax.jit(lambda v, f, c, k, p: decode_codes(
        model, v, f, c, k, n_prime=n_prime,
        prime_codes=jnp.repeat(p, rows, axis=0), filter_thres=0.9)).lower(
            _on(one_chip, variables), _on(one_chip, first),
            _on(one_chip, caches),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
            jax.ShapeDtypeStruct(prime.shape, jnp.int32,
                                 sharding=one_chip)).compile()
    assert compiled.as_text().count(" conditional(") == 2
    cache_bytes = sum(2 * math.prod(c[0].shape) * 2 for c in caches)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.3 * cache_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < V5E_HBM_BYTES)


@pytest.mark.slow
@pytest.mark.parametrize("spec", chip_smoke.FULL.plan_specs)
def test_sharded_step_compiles_for_four_chips(topo, spec):
    """``chip_smoke.py --chips 4``'s sharded step, compiled over a Mesh of
    the four described devices: collectives present, per-device bytes
    inside one chip."""
    _, _, step, abstract = chip_smoke.plan_step(
        spec, topo.devices, cub200_config(), 16)
    compiled = step.lower(*abstract).compile()
    assert chip_smoke.collectives_in(compiled.as_text())
    mem = compiled.memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            < V5E_HBM_BYTES)


def test_the_mamba2_decode_step_compiles_at_published_widths(one_chip):
    """One Mamba-2 layer's decode step at ``nemotron-3-nano-30b-a3b-
    generate``'s shape (256 rows, 64 heads of 64 channels, the state ``[256,
    64, 64, 128]`` float32, 2 MiB a row; 8 groups of 128): it compiles for
    the v5e, the state's buffer is donated to the updated state, and the
    step holds no copy of the state nor temporaries of its size (the update
    and the read-out in one pass)."""
    from dalle_pytorch_tpu.ops.ssm import Mamba2Mixer

    rows, dim = 256, 2688
    layer = Mamba2Mixer(dim=dim, heads=64, head_dim=64, groups=8, state=128,
                        conv=4, chunk=128, dtype=jnp.bfloat16,
                        param_dtype=jnp.bfloat16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4, dim), jnp.bfloat16))
    state = (rows, 64, 64, 128)
    state_bytes = math.prod(state) * 4

    def on(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(lambda p, x, w, h: layer.apply(
        p, x, w, h, method=Mamba2Mixer.decode_step),
        donate_argnums=(2, 3)).lower(
            _on(one_chip, params), on(rows, 1, dim, dtype=jnp.bfloat16),
            on(rows, 3, 4096 + 2 * 8 * 128, dtype=jnp.bfloat16),
            on(*state)).compile()
    text = compiled.as_text()
    shape = f"f32[{','.join(map(str, state))}]"
    assert not [line for line in text.splitlines() if f"= {shape}" in line
                and re.search(r" (copy|copy-start|transpose)\(", line)]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < state_bytes // 4
