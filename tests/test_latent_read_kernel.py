"""The absorbed latent read in one pass (PERF.md, Findings PR 39): the Pallas
kernel of ops/latent_attention_pallas.py run through the interpreter on the
CPU and held to the whole-cache masked ``_read_latent``; the fold the scan
carries and its tick write; the predicate that says which shapes take the
kernel; and ``decode_codes`` over a twin of the GLM-4.7-Flash trunk with the
kernel forced, against the plain path.

The kernel itself is lowered for a TPU only: tests/test_tpu_compile.py
compiles it for a described v5e at the benchmark cell's shape.
"""
from __future__ import annotations

import contextlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dalle_pytorch_tpu import DALLE, DALLEConfig  # noqa: E402
from dalle_pytorch_tpu.models.dalle import decode_codes  # noqa: E402
from dalle_pytorch_tpu.ops import kept, latent_attention  # noqa: E402
from dalle_pytorch_tpu.ops.attention import AttnPattern, read_bounds  # noqa: E402
from dalle_pytorch_tpu.ops.latent_attention import (  # noqa: E402
    READ_BLOCK, LatentAttention, _read_latent, _write_folded, fold_latent,
    one_pass_read, rows_per_program, unfold_latent)
from dalle_pytorch_tpu.ops.latent_attention_pallas import (  # noqa: E402
    fold_latent_blocks, latent_read)

ROWS, HEADS, RANK, ROPE = 4, 5, 128, 64


def operands(dtype, slots, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (
        (jax.random.normal(ks[0], (ROWS, HEADS, RANK)) * 0.3).astype(dtype),
        (jax.random.normal(ks[1], (ROWS, HEADS, ROPE)) * 0.3).astype(dtype),
        jax.random.normal(ks[2], (ROWS, slots, RANK)).astype(dtype),
        jax.random.normal(ks[3], (ROWS, slots, ROPE)).astype(dtype))


def whole_cache_read(q_lat, q_rope, c, kr, index):
    row = (jnp.arange(c.shape[1]) <= index)[None]
    return _read_latent(q_lat, q_rope, c, kr, row, bound=c.shape[1])


#: float32 differs in the order of sums (an online softmax), bfloat16 also in
#: where the probabilities are rounded (before the division by their sum);
#: the outputs are of order 1
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# --- the kernel against the whole-cache masked read ---------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("slots", [512, 768])
@pytest.mark.parametrize("index", [0, 254, 255, 256, -1],
                         ids=["first", "under", "at", "over", "last"])
def test_one_pass_equals_the_masked_read_and_stops_at_its_block(dtype, slots,
                                                                index):
    """``index`` at 0, one under, at and one over a block's end and at the
    last slot; the cache past the position's block is poisoned with NaN: the
    kernel neither computes nor fetches a block that starts past ``index``,
    so it reads finite and equal."""
    index = index % slots
    q_lat, q_rope, c, kr = operands(dtype, slots)
    want = whole_cache_read(q_lat, q_rope, c, kr, index)
    end = (index // READ_BLOCK + 1) * READ_BLOCK
    poisoned = fold_latent(c.at[:, end:].set(jnp.nan),
                           kr.at[:, end:].set(jnp.nan))
    got = latent_read(q_lat, q_rope, poisoned, jnp.asarray(index),
                      rows_per_program=2, interpret=True)
    assert got.dtype == jnp.float32 and got.shape == (ROWS, HEADS, RANK)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=TOL[dtype])


def test_a_traced_index_inside_a_scan():
    q_lat, q_rope, c, kr = operands(jnp.float32, 512, seed=1)
    folded = fold_latent(c, kr)

    def tick(_, index):
        return None, latent_read(q_lat, q_rope, folded, index,
                                 rows_per_program=4, interpret=True)

    indices = jnp.arange(250, 262)            # crosses the block's end
    _, got = jax.lax.scan(tick, None, indices)
    for index, o_lat in zip(np.asarray(indices), got):
        np.testing.assert_allclose(
            o_lat, whole_cache_read(q_lat, q_rope, c, kr, int(index)),
            atol=TOL[jnp.float32])


@pytest.mark.parametrize("per", [1, 2, 4])
def test_rows_a_program_do_not_change_the_result(per):
    q_lat, q_rope, c, kr = operands(jnp.bfloat16, 512, seed=2)
    got = latent_read(q_lat, q_rope, fold_latent(c, kr), jnp.asarray(300),
                      rows_per_program=per, interpret=True)
    np.testing.assert_allclose(
        got, whole_cache_read(q_lat, q_rope, c, kr, 300),
        atol=TOL[jnp.bfloat16])


# --- the fold and its tick write ------------------------------------------------------------

def test_the_fold_puts_a_blocks_halves_side_by_side():
    _, _, c, kr = operands(jnp.float32, 768)
    folded = fold_latent(c, kr)
    assert folded.shape == (ROWS, 384, 2 * RANK + 2 * ROPE)
    half = READ_BLOCK // 2
    for position in (0, 5, 127, 128, 255, 256, 300, 500, 767):
        row = position // READ_BLOCK * half + position % half
        second = position % READ_BLOCK // half
        np.testing.assert_array_equal(
            folded[:, row, second * RANK:(second + 1) * RANK], c[:, position])
        np.testing.assert_array_equal(
            folded[:, row, 2 * RANK + second * ROPE:][:, :ROPE],
            kr[:, position])
    for got, want in zip(unfold_latent(folded, RANK), (c, kr)):
        np.testing.assert_array_equal(got, want)


def test_the_fold_kernel_is_the_fold():
    _, _, c, kr = operands(jnp.bfloat16, 768)
    np.testing.assert_array_equal(
        fold_latent_blocks(c, kr, rows_per_program=2, interpret=True),
        fold_latent(c, kr))


@pytest.mark.parametrize("index", [0, 3, 127, 128, 255, 256, 300, 400, 767])
def test_the_tick_writes_one_position_of_the_fold(index):
    _, _, c, kr = operands(jnp.float32, 768)
    new_c = jnp.full((ROWS, 1, RANK), 99.0)
    new_kr = jnp.full((ROWS, 1, ROPE), -99.0)
    folded = _write_folded(fold_latent(c, kr), new_c, new_kr,
                           jnp.asarray(index))
    got_c, got_kr = unfold_latent(folded, RANK)
    np.testing.assert_array_equal(got_c, c.at[:, index].set(99.0))
    np.testing.assert_array_equal(got_kr, kr.at[:, index].set(-99.0))


# --- which shapes take the kernel ---------------------------------------------------------------

@pytest.mark.parametrize("slots,kv_rank,rope_dim,dtype,want", [
    (4352, 512, 64, jnp.bfloat16, True),      # the benchmark's cell
    (512, 128, 64, jnp.bfloat16, True),       # two whole blocks
    (4352, 512, 64, jnp.float32, False),      # a float32 toy
    (256, 512, 64, jnp.bfloat16, False),      # one block: nothing to bound
    (272, 512, 64, jnp.bfloat16, False),      # 16 + 256: a ragged block
    (4352, 20, 4, jnp.bfloat16, False),       # the tiny twin's widths
    (4352, 512, 32, jnp.bfloat16, False),     # rotary keys off the half tile
    (4352, 576, 64, jnp.bfloat16, False),     # a latent off the lane tiles
], ids=["cell", "two-blocks", "float32", "one-block", "ragged", "narrow",
        "rope-32", "rank-576"])
def test_the_predicate_table(slots, kv_rank, rope_dim, dtype, want):
    assert one_pass_read(slots, kv_rank, rope_dim, dtype) is want


def test_rows_a_program_stay_within_eight_mebibytes():
    # the cell: 128 rows of 256 positions x 576 values x 2 bytes
    assert rows_per_program(128, 576, jnp.bfloat16) == 16
    assert rows_per_program(2, 576, jnp.bfloat16) == 2
    assert rows_per_program(3, 576, jnp.bfloat16) == 1
    assert rows_per_program(96, 576, jnp.bfloat16) == 16
    assert rows_per_program(128, 3 * 512, jnp.bfloat16) == 8


def _layer(slots, dtype=jnp.bfloat16, kv_rank=RANK, rope_dim=ROPE):
    return LatentAttention(
        pattern=AttnPattern(variant="full", seq_len=slots, text_len=9,
                            fmap=0, causal=True),
        dim=32, heads=4, q_rank=24, kv_rank=kv_rank, nope_dim=12,
        rope_dim=rope_dim, value_dim=10, rope_theta=1e6, eps=1e-5,
        dtype=dtype)


def test_the_layer_says_where_its_reads_end_and_how_the_scan_carries_it():
    layer = _layer(768)
    assert layer.dense_read_bounds(jnp.bfloat16) == (256, 512, 768)
    # a key-padding mask, a float32 cache: the buckets of every dense read
    assert layer.dense_read_bounds(jnp.bfloat16, masked=True) == read_bounds(768)
    assert layer.dense_read_bounds(jnp.float32) == read_bounds(768)
    assert _layer(272).dense_read_bounds(jnp.bfloat16) == (128, 256, 272)
    c, kr = layer.init_cache(2, 768, jnp.bfloat16)
    folded, none = layer.lane_dense_cache(c, kr)
    assert none is None and folded.shape == (2, 384, 2 * (RANK + ROPE))
    assert layer.lane_dense_cache(c, kr, masked=True) == (c, kr)
    c32, kr32 = layer.init_cache(2, 768, jnp.float32)
    assert layer.lane_dense_cache(c32, kr32) == (c32, kr32)


# --- the layer's tick, folded against unfolded ----------------------------------------------------

@contextlib.contextmanager
def _force_the_kernel(monkeypatch):
    """Take the TPU arm of every ``platform_dependent`` switch (the test
    steers, no option of the program does) and interpret its kernels, kept
    in no file."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    jax.clear_caches()
    try:
        with pltpu.force_tpu_interpret_mode():
            yield
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.clear_caches()


@pytest.mark.parametrize("forced", [False, True], ids=["plain", "kernel"])
def test_the_folded_tick_is_the_unfolded_tick(monkeypatch, forced):
    """One layer, float32 activations over a bfloat16 cache of 512 slots:
    ticks across the first block's end against the folded cache (the CPU's
    arm unfolds and reads in two passes; the TPU's arm, interpreted, reads in
    one) give what the unfolded cache gives, and leave the same cache."""
    layer = _layer(512, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 512, 32))
    params = layer.init(jax.random.PRNGKey(1), x[:, :4])
    _, (c, kr) = layer.apply(params, x, return_kv=True)
    start = 250
    pair = tuple(jnp.where(jnp.arange(512)[None, :, None] < start, a, 0)
                 .astype(jnp.bfloat16) for a in (c, kr))
    with (_force_the_kernel(monkeypatch) if forced
          else contextlib.nullcontext()):
        folded = layer.apply(params, *pair,
                             method=LatentAttention.lane_dense_cache)
        assert folded[1] is None
        for t in range(start, start + 12):
            want, *pair = layer.apply(
                params, x[:, t:t + 1], *pair, jnp.asarray(t),
                method=LatentAttention.decode_step)
            got, *folded = layer.apply(
                params, x[:, t:t + 1], *folded, jnp.asarray(t),
                method=LatentAttention.decode_step)
            np.testing.assert_allclose(got, want, atol=2e-2)
    for got, want in zip(unfold_latent(folded[0], RANK), pair):
        np.testing.assert_array_equal(got, want)


# --- decode_codes over a twin of the trunk, the kernel forced --------------------------------

TRUNK = dict(mixers=["mla"], ff_dim=80, norm_eps=1e-5, ff="moe_swiglu_shared",
             rope_theta=1e6, q_rank=24, kv_rank=RANK, nope_dim=12,
             rope_dim=ROPE, value_dim=10, dense_layers=1, experts=8,
             experts_per_token=2, expert_dim=24, experts_held=2,
             experts_first=2, shared_experts=1, route_scale=1.8,
             tied_table=False, param_dtype="float32")
#: 192 + 24 x 24 = 768 positions: three blocks
GEOMETRY = dict(dim=32, depth=2, heads=4, dim_head=16, num_text_tokens=50,
                text_seq_len=192, num_image_tokens=32, image_size=192,
                image_fmap_size=24)


@pytest.fixture(scope="module")
def twin():
    cfg = DALLEConfig(trunk=dict(TRUNK), kv_cache_bf16=True, **GEOMETRY)
    dalle = DALLE(cfg)
    key = jax.random.PRNGKey(0)
    text = jax.random.randint(key, (2, cfg.text_seq_len), 1, 50)
    codes = jax.random.randint(jax.random.fold_in(key, 1),
                               (2, cfg.image_seq_len), 0, 32)
    variables = dalle.init(key, text, codes)
    return cfg, dalle, variables, text, codes


def test_decode_codes_with_the_kernel_forced_agrees_with_the_plain_path(
        twin, monkeypatch):
    """Greedy ``decode_codes`` from a primed prefill that stops short of the
    second block's end, so that the ticks cross it: the scan carries the
    fold on both sides; the kernel's codes (interpreted) are the plain
    path's but where two logits lie closer than bfloat16 tells apart."""
    cfg, dalle, variables, text, codes = twin
    n_prime = 512 - (cfg.text_seq_len + 1) - 6      # 6 ticks to the block end
    first, caches = dalle.apply(variables, text, codes[:, :n_prime],
                                method=DALLE.prefill)
    assert caches[0][0].dtype == jnp.bfloat16
    assert dalle.apply(variables, method=DALLE.dense_read_bounds) == [
        (256, 512, 768)] * 2

    def run():
        decode = jax.jit(lambda v, f, c: decode_codes(
            dalle, v, f, c, jax.random.PRNGKey(3), n_prime=n_prime,
            prime_codes=codes[:, :n_prime], filter_thres=1.0))
        lowered = decode.lower(variables, first, caches).as_text()
        return np.asarray(decode(variables, first, caches)), lowered

    want, lowered = run()
    # the CPU's arm: two passes over a prefix that a switch chooses
    assert "stablehlo.case" in lowered and "tpu_custom_call" not in lowered
    with _force_the_kernel(monkeypatch):
        got, _ = run()
    assert got.shape == want.shape == (2, cfg.image_seq_len)
    np.testing.assert_array_equal(got[:, :n_prime], want[:, :n_prime])
    sampled = slice(n_prime, n_prime + 16)    # a flip feeds on itself later
    assert (got[:, sampled] == want[:, sampled]).mean() >= 0.9


def test_a_key_padding_mask_keeps_the_pair_and_the_two_pass_read(twin):
    cfg, dalle, variables, text, codes = twin
    n_prime = cfg.image_seq_len - 4
    first, caches = dalle.apply(variables, text, codes[:, :n_prime],
                                method=DALLE.prefill)
    mask = jnp.ones((2, cfg.text_seq_len), bool).at[:, -5:].set(False)

    def lowered(mask):
        return jax.jit(lambda v, f, c: decode_codes(
            dalle, v, f, c, jax.random.PRNGKey(3), n_prime=n_prime,
            prime_codes=codes[:, :n_prime], filter_thres=1.0,
            mask=mask)).lower(variables, first, caches).as_text()

    folded = f"tensor<2x{cfg.seq_len // 2}x{2 * (RANK + ROPE)}xbf16>"
    assert folded not in lowered(mask) and folded in lowered(None)
    assert dalle.apply(variables, True, method=DALLE.dense_read_bounds) == [
        read_bounds(cfg.seq_len)] * 2


# --- the kernels, kept beside the compile cache -------------------------------------------------

def test_kept_kernels_leave_the_programs_results_uncommitted(tmp_path):
    """With a compile cache the two kernels are written as ``jax.export``
    files and read back by a later trace, and a program that holds them
    returns what a program without them returns: arrays committed to no
    device, so that a jitted consumer warmed on a fresh array (the
    benchmark's VAE decode) does not trace again on the program's result
    (``call_exported`` in the traced program itself would commit them)."""
    pattern = AttnPattern(variant="full", seq_len=512, text_len=9, fmap=0,
                          causal=True)
    q_lat, q_rope, c, kr = operands(jnp.bfloat16, 512)

    def program(c, kr, q_lat, q_rope, index):
        folded = latent_attention._fold(c, kr)
        o_lat = latent_attention._one_pass_read(pattern, q_lat, q_rope,
                                                folded, index)
        return jnp.argmax(o_lat[:, 0], -1)

    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.clear_caches()
    try:
        consumer = jax.jit(lambda x: x + 1)
        consumer(jnp.zeros((ROWS,), jnp.int32))
        text = str(jax.make_jaxpr(program)(c, kr, q_lat, q_rope, 300))
        assert text.count("kept_kernel") == 2
        assert "call_exported" not in text
        names = sorted(p.name.split("-")[1] for p in tmp_path.iterdir()
                       if p.suffix == ".jaxexport")
        assert names == ["fold_latent_blocks", "latent_read"]
        kept.exported.cache_clear()                   # a later process
        got = jax.jit(program)(c, kr, q_lat, q_rope, jnp.asarray(300))
        consumer(got)
        assert consumer._cache_size() == 1
        want = jnp.argmax(whole_cache_read(q_lat, q_rope, c, kr, 300)[:, 0],
                          -1)
        np.testing.assert_array_equal(got, want)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.clear_caches()
