"""The lane-dense decode KV cache (PERF.md, Findings PR 26): where XLA:TPU
would pad ``dim_head`` to the 128 lanes, ``decode_codes`` carries the
dense-read layers' caches head-folded (``quant.fold_heads``) and
``decode_step`` reads them with dots against a block-diagonal ``q``.

On the CPU the folded step must give what the plain one gives up to the order
of an f32 sum (the block-diagonal zeros are exact), for every cache dtype,
with and without a key-padding mask; the choice of layout is by shape and
dtype alone; ``decode_codes`` draws the same codes either way; and the
trace-time counters reach the telemetry stream, the registry and
``tools/obs_report.py``'s text.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dalle_pytorch_tpu import DALLE, DALLEConfig  # noqa: E402
from dalle_pytorch_tpu.models.dalle import (  # noqa: E402
    decode_codes, prefill_codes, tile_prefill)
from dalle_pytorch_tpu.obs import metrics, telemetry  # noqa: E402
from dalle_pytorch_tpu.obs.report import build_report, render_text  # noqa: E402
from dalle_pytorch_tpu.ops import attention  # noqa: E402
from dalle_pytorch_tpu.ops.attention import (  # noqa: E402
    AttnPattern, MultiHeadAttention, kv_fold_factor)
from dalle_pytorch_tpu.ops.quant import (  # noqa: E402
    cache_values, fold_cache, fold_heads, quantize_per_head)

TEXT, FMAP = 7, 4
N = TEXT + FMAP * FMAP              # seq_len: the cache's length


# --- the choice ------------------------------------------------------------

@pytest.mark.parametrize("heads,dim_head,dtype,want", [
    (16, 64, jnp.bfloat16, 2),    # lucid1024-generate: padded before PR 26
    (16, 64, jnp.int8, 2),
    (8, 32, jnp.bfloat16, 4),
    (8, 64, jnp.bfloat16, 2),     # cub200-generate: at any rows since PR 33
    (2, 64, jnp.int8, 2),
    (4, 32, jnp.float32, 1),
    (3, 64, jnp.bfloat16, 1),     # an odd head count
    (6, 32, jnp.bfloat16, 1),     # 4 does not divide 6
    (16, 128, jnp.bfloat16, 1),   # dim_head fills the lanes
    (16, 96, jnp.bfloat16, 1),    # dim_head does not divide them
    (16, 64, jnp.float32, 1),     # a dot would round f32 multiplicands
])
def test_fold_factor_is_decided_by_shape_and_dtype(heads, dim_head, dtype,
                                                   want):
    assert kv_fold_factor(heads, dim_head, dtype) == want


def test_fold_heads_puts_a_group_side_by_side():
    kv = jnp.arange(2 * 4 * 3 * 5, dtype=jnp.float32).reshape(2, 4, 3, 5)
    folded = np.asarray(fold_heads(kv, 2))
    assert folded.shape == (2, 2, 3, 10)
    for g in range(2):
        for f in range(2):
            np.testing.assert_array_equal(
                folded[:, g, :, f * 5:(f + 1) * 5],
                np.asarray(kv)[:, g * 2 + f])
    assert fold_heads(kv, 1) is kv


# --- one decode step, folded against plain ---------------------------------

def _attn_and_state(heads, dim_head, rows, cache, with_mask, seed=0):
    """A full-attention layer, its parameters, one token's input, and a
    prefilled (k, v) cache pair of the asked storage."""
    pattern = AttnPattern(variant="full", seq_len=N, text_len=TEXT + 1,
                          fmap=FMAP)
    attn = MultiHeadAttention(pattern=pattern, dim=32, heads=heads,
                              dim_head=dim_head, dtype=jnp.float32)
    kx, kk, kv_, kp = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(kx, (rows, 1, 32), jnp.float32)
    params = attn.init(kp, jnp.zeros((1, N, 32), jnp.float32))
    shape = (rows, heads, N, dim_head)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv_, shape, jnp.float32)
    if cache == "int8":
        k, v = quantize_per_head(k), quantize_per_head(v)
    else:
        dt = {"bf16": jnp.bfloat16, "f32": jnp.float32}[cache]
        k, v = k.astype(dt), v.astype(dt)
    mask = None
    if with_mask:
        # key padding over the text positions, another length in each row
        lens = 2 + jnp.arange(rows) % (TEXT - 1)
        mask = jnp.arange(TEXT + 1)[None, :] < lens[:, None]
    return attn, params, x, k, v, mask


@pytest.mark.parametrize("with_mask", [False, True], ids=["nomask", "keypad"])
@pytest.mark.parametrize("cache", ["bf16", "f32", "int8"])
@pytest.mark.parametrize("heads,dim_head,rows", [
    (4, 64, 4), (8, 32, 4), (3, 64, 4), (4, 64, 128)],
    ids=["h4x64-r4", "h8x32-r4", "h3x64-r4-odd", "h4x64-r128"])
def test_folded_decode_step_matches_plain(heads, dim_head, rows, cache,
                                          with_mask):
    """``decode_step`` on the layout ``lane_dense_cache`` chooses against
    the plain layout: the same attended output and the same cache after the
    write.  Where the choice is plain (odd heads, an f32 cache) the
    arithmetic of the folded read is still held to the plain one, on a
    cache folded by hand."""
    attn, params, x, k, v, mask = _attn_and_state(heads, dim_head, rows,
                                                  cache, with_mask)
    index = jnp.asarray(TEXT + 6)

    def step(ck, cv):
        return attn.apply(params, x, ck, cv, index, mask,
                          method=MultiHeadAttention.decode_step)

    out_p, k_p, v_p = jax.jit(step)(k, v)

    chosen_k = attn.apply(params, k,
                          method=MultiHeadAttention.lane_dense_cache)
    want = kv_fold_factor(heads, dim_head, cache_values(k).dtype)
    assert cache_values(chosen_k).shape == (
        rows, heads // want, N, want * dim_head)
    assert (want > 1) == (cache in ("bf16", "int8") and heads % 2 == 0)

    fold = 128 // dim_head
    if heads % fold:
        return                      # no folded layout exists for these heads
    out_f, k_f, v_f = jax.jit(step)(fold_cache(k, fold), fold_cache(v, fold))
    # the write: the same row, quantised per head, at the same position
    for got, ref in ((k_f, k_p), (v_f, v_p)):
        np.testing.assert_array_equal(
            np.asarray(cache_values(got), np.float32),
            np.asarray(fold_heads(cache_values(ref), fold), np.float32))
    # the read: f32 sums of the same products in another order
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_p),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("variant,shape", [
    ("axial_row", (4, 4, N, 64)),       # reads slices: as given
    ("full", (4, 2, N, 128))])          # reads the whole cache: head-folded
def test_sliced_layers_keep_the_plain_layout(variant, shape):
    pattern = AttnPattern(variant=variant, seq_len=N, text_len=TEXT + 1,
                          fmap=FMAP)
    k = jnp.zeros((4, 4, N, 64), jnp.bfloat16)
    attn = MultiHeadAttention(pattern=pattern, dim=32, heads=4, dim_head=64)
    got = attn.apply({}, k, method=MultiHeadAttention.lane_dense_cache)
    assert got.shape == shape


# --- the model: teacher-forced logits and sampled codes ---------------------

def _tiny(**overrides):
    cfg = DALLEConfig(dim=64, depth=2, heads=4, dim_head=64,
                      num_text_tokens=50, text_seq_len=TEXT,
                      num_image_tokens=32, image_fmap_size=FMAP,
                      attn_types=("full", "axial_row"), dtype=jnp.float32,
                      **overrides)
    dalle = DALLE(cfg)
    rng = jax.random.PRNGKey(0)
    text = jax.random.randint(rng, (4, TEXT), 1, 50)
    codes = jax.random.randint(rng, (4, cfg.image_seq_len), 0, 32)
    params = dalle.init(rng, text[:1], codes[:1])
    return cfg, dalle, params, text, codes


def _decode_logits(dalle, params, text, codes, mask, lane_dense):
    """Teacher-forced image logits through prefill and the cached decode
    step, the caches carried plain or as ``decode_codes`` carries them."""
    cfg = dalle.cfg

    def run(variables, text, codes):
        first, caches = dalle.apply(variables, text, None, mask,
                                    method=DALLE.prefill)
        if lane_dense:
            caches = dalle.apply(variables, caches,
                                 method=DALLE.lane_dense_caches)

        def step(carry, code):
            caches, index = carry
            logits, caches = dalle.apply(variables, code, caches, index,
                                         mask, method=DALLE.decode_step)
            return (caches, index + 1), logits

        (caches, _), rest = jax.lax.scan(
            step, (caches, jnp.asarray(cfg.text_seq_len + 1)),
            codes[:, :-1].T)
        return (jnp.concatenate([first[:, None], rest.transpose(1, 0, 2)], 1),
                [cache_values(k) for k, _ in caches])

    logits, keys = jax.jit(run)(params, text, codes)
    return logits, [k.shape for k in keys]


#: decode-through-cache against the full forward, by cache storage: f32 is
#: the same arithmetic, bf16 and int8 round the cached k/v (the bounds of
#: tests/test_generation_equiv.py at this width)
FORWARD_TOL = {"f32": 2e-4, "bf16": 0.05, "int8": 0.15}


@pytest.mark.parametrize("with_mask", [False, True], ids=["nomask", "keypad"])
@pytest.mark.parametrize("cache", ["bf16", "f32", "int8"])
def test_lane_dense_logits_match_plain_and_the_full_forward(cache, with_mask):
    cfg, dalle, params, text, codes = _tiny(
        kv_cache_bf16=cache == "bf16", kv_cache_int8=cache == "int8")
    mask = None
    if with_mask:
        mask = jnp.arange(TEXT)[None, :] < jnp.asarray([[3], [7], [5], [2]])
    got, shapes = _decode_logits(dalle, params, text, codes, mask, True)
    ref, _ = _decode_logits(dalle, params, text, codes, mask, False)
    # layer 0 reads the whole cache and is folded unless f32; layer 1 slices
    assert shapes == [(4, 4, N, 64) if cache == "f32" else (4, 2, N, 128),
                      (4, 4, N, 64)]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    forward = dalle.apply(params, text, codes, mask)
    img = np.asarray(forward[:, TEXT:, -cfg.num_image_tokens:])
    np.testing.assert_allclose(np.asarray(got), img, rtol=0,
                               atol=FORWARD_TOL[cache] * float(img.std()))


@pytest.mark.parametrize("cache", ["bf16", "f32"])
def test_decode_codes_draws_the_same_codes_either_layout(cache, monkeypatch):
    """f32 activations, one key: the codes drawn from the lane-dense scan
    are the codes drawn with every cache kept plain."""
    cfg, dalle, params, text, _ = _tiny(kv_cache_bf16=cache == "bf16")

    def draw():
        first, caches = prefill_codes(dalle, params, text[:1])
        first, caches = tile_prefill(first, caches, 4)
        return np.asarray(jax.jit(
            lambda p, f, c, k: decode_codes(dalle, p, f, c, k,
                                            filter_thres=0.9))(
            params, first, caches, jax.random.PRNGKey(7)))

    folded = draw()
    monkeypatch.setattr(attention, "kv_fold_factor", lambda *a: 1)
    plain = draw()
    assert folded.shape == (4, cfg.image_seq_len)
    np.testing.assert_array_equal(folded, plain)


# --- the counters ----------------------------------------------------------

def test_decode_trace_reports_its_cache_layout(tmp_path):
    """One trace of a tiny ``decode_codes`` (a full layer and an axial one,
    four rows): one ``decode.kv_layout`` record, two gauges, and a line in
    the report ``tools/obs_report.py`` prints."""
    cfg, dalle, params, text, _ = _tiny()
    reg = metrics.init()
    tel = telemetry.init(tmp_path, run_id="lane-dense")
    try:
        first, caches = prefill_codes(dalle, params, text[:1])
        first, caches = tile_prefill(first, caches, 4)
        jax.jit(lambda p, f, c, k: decode_codes(dalle, p, f, c, k))(
            params, first, caches, jax.random.PRNGKey(0))
        rendered = reg.render()
    finally:
        telemetry.shutdown()
        metrics.shutdown()
    events = telemetry.read_events(tel.path)
    layout = [e for e in events
              if e["kind"] == "decode" and e["name"] == "kv_layout"]
    assert len(layout) == 1
    assert layout[0]["kv_lane_dense_layers"] == 1
    assert layout[0]["kv_plain_layers"] == 1
    assert layout[0]["rows"] == 4
    assert "graft_decode_kv_lane_dense_layers 1" in rendered
    assert "graft_decode_kv_plain_layers 1" in rendered
    report = build_report(events)
    assert report["decode"] == {"traces": 1, "rows": 4,
                                "kv_lane_dense_layers": 1,
                                "kv_plain_layers": 1, "kv_layers": 2,
                                "ssm_layers": 0,
                                "state_bytes_per_row": report["decode"][
                                    "state_bytes_per_row"],
                                # 23 slots: one read (PR 33; its own test
                                # is in tests/test_kv_bounded_read.py)
                                "reach": {"bounded_layers": 0,
                                          "unbounded_layers": 2,
                                          "buckets": 0, "read_share": 1.0}}
    assert ("kv cache layout: 1 layers lane-dense, 1 plain (4 rows; last of 1 "
            "decode_codes traces)") in render_text(report)
