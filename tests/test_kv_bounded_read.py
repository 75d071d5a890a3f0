"""The static sampler's dense cache read, bounded by the position (PERF.md,
Findings PR 33): where ``index`` is a traced scalar the slots a layer has
written are a prefix of its cache, and ``decode_step`` reads a static prefix
``[:bound]`` of keys, values and mask, chosen per tick among
``attention.read_bounds`` by a ``lax.switch`` around the read alone.

On the CPU the bounded read must give what the unbounded masked read gives
(the slots left out were masked to ``exp(...) = 0``) for every kind of cache
and at every edge of every bucket; the widths follow the cache's length
alone; a teacher-forced scan over a whole sequence matches the full forward;
``decode_codes`` draws the same codes; and the trace-time counter reaches the
telemetry stream, the registry and ``tools/obs_report.py``'s text.
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
    LANES, AttnPattern, MultiHeadAttention, read_bounds)
from dalle_pytorch_tpu.ops.quant import (  # noqa: E402
    cache_values, fold_cache, quantize_per_head)

TEXT, FMAP = 7, 17
N = TEXT + FMAP * FMAP              # 296 slots: prefixes of 128, 256, 296
WINDOW = 300                        # a ring of 300 slots under 684 positions
RING_N = TEXT + 1 + 26 * 26


# --- the bucket rule ---------------------------------------------------------

@pytest.mark.parametrize("slots,width,buckets", [
    (1280, 256, 5),      # lucid1024, jamba2-3b
    (1104, 256, 5),      # cub200
    (4096, 512, 8),      # smallthinker-21ba3b's rings
    (4352, 640, 7),      # its global layer
    (1024, 128, 8),
    (1025, 256, 5),
    (256, 128, 2),
    (296, 128, 3),
    (255, 255, 1),       # under two lane widths: the single read
    (23, 23, 1),
])
def test_bucket_width_follows_the_cache_length_alone(slots, width, buckets):
    bounds = read_bounds(slots)
    assert len(bounds) == buckets and bounds[-1] == slots
    assert bounds[0] == width and (width % LANES == 0 or buckets == 1)
    assert all(b - a == width for a, b in zip(bounds[:-2], bounds[1:-1]))
    assert 0 < bounds[-1] - (bounds[-2] if buckets > 1 else 0) <= width
    assert len(bounds) <= attention.READ_BUCKETS


# --- one decode step, bounded against unbounded ------------------------------

KINDS = ["bf16", "f32", "folded", "int8", "grouped", "ring"]
#: by whether the cache is f32; a key left out would cost its whole weight,
#: 3e-3 on average (and test_bounded_read_leaves_no_written_key_out)
TOL = {True: dict(rtol=2e-6, atol=2e-7), False: dict(rtol=1e-4, atol=5e-5)}


def _layer(kind, with_mask, rows=3, seed=0):
    """A dense-read layer of the asked kind, its parameters, one token's
    input, a filled (k, v) cache pair, a key-padding mask or None, and the
    positions to try: ``bound - 2``, ``bound - 1``, ``bound`` of every
    prefix, the first position after the text and the last one."""
    ring = kind == "ring"
    grouped = kind in ("grouped", "ring")
    n = RING_N if ring else N
    pattern = AttnPattern(variant="full", seq_len=n, text_len=TEXT + 1,
                          fmap=26 if ring else FMAP,
                          window=WINDOW if ring else 0)
    heads, dim_head = 4, 64
    attn = MultiHeadAttention(
        pattern=pattern, dim=32, heads=heads, dim_head=dim_head,
        kv_heads=2 if grouped else None,
        rope_theta=1e4 if ring else None, dtype=jnp.float32)
    kx, kk, kv_, kp = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(kx, (rows, 1, 32), jnp.float32)
    params = attn.init(kp, jnp.zeros((1, n, 32), jnp.float32))
    slots = pattern.cache_len
    shape = (rows, 2 if grouped else heads, slots, dim_head)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv_, shape, jnp.float32)
    if kind == "int8":
        k, v = quantize_per_head(k), quantize_per_head(v)
    elif kind == "f32":
        pass
    else:
        k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    if kind == "folded":
        k, v = fold_cache(k, 2), fold_cache(v, 2)
    mask = None
    if with_mask:
        lens = 2 + jnp.arange(rows) % (TEXT - 1)
        mask = jnp.arange(TEXT + 1)[None, :] < lens[:, None]
    bounds = read_bounds(slots)
    assert len(bounds) == 3
    edges = sorted({TEXT + 1, n - 1} | {
        b + d for b in bounds for d in (-2, -1, 0) if b + d < n})
    if ring:                    # past the wrap: every slot holds a key
        edges += [WINDOW + 1, 2 * WINDOW - 1, 2 * WINDOW, 2 * WINDOW + 5]
    return attn, params, x, k, v, mask, edges


@pytest.mark.parametrize("with_mask", [False, True], ids=["nomask", "keypad"])
@pytest.mark.parametrize("kind", KINDS)
def test_bounded_read_matches_the_unbounded_masked_read(kind, with_mask,
                                                        monkeypatch):
    attn, params, x, k, v, mask, edges = _layer(kind, with_mask)

    def step(ck, cv, index):
        return attn.apply(params, x, ck, cv, index, mask,
                          method=MultiHeadAttention.decode_step)

    bounded = jax.jit(step)
    assert "cond" in str(jax.make_jaxpr(step)(k, v, jnp.asarray(9)))
    got = [bounded(k, v, jnp.asarray(i)) for i in edges]

    monkeypatch.setattr(attention, "read_bounds", lambda slots: (slots,))
    def unbounded(ck, cv, index):   # a function jax has not traced yet
        return step(ck, cv, index)

    whole = jax.jit(unbounded)
    assert "cond" not in str(jax.make_jaxpr(unbounded)(k, v, jnp.asarray(9)))
    for i, (out, new_k, new_v) in zip(edges, got):
        ref, ref_k, ref_v = whole(k, v, jnp.asarray(i))
        # the same products and f32 sums, less the masked slots' exact
        # zeros; where the values are not f32 the weights meet them rounded
        # to bf16, and an ulp of the f32 normaliser can turn one rounding
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   err_msg=f"at {i}", **TOL[kind == "f32"])
        for a, b in ((new_k, ref_k), (new_v, ref_v)):       # the write
            np.testing.assert_array_equal(
                np.asarray(cache_values(a), np.float32),
                np.asarray(cache_values(b), np.float32))


def test_bounded_read_leaves_no_written_key_out():
    """The unbounded read cannot tell a key left out from one masked: plant
    a key that takes all the weight in the last slot each prefix must hold,
    and see its value come back."""
    attn, params, x, k, v, _, _ = _layer("f32", False, rows=1)

    def read(q, k, v, filled):
        row = jnp.arange(N)[None, None, None, :] < filled
        return attn.apply(params, q, k, None, v, None, row, jnp.float32,
                          filled, method=MultiHeadAttention._masked_read)

    q = jnp.ones((1, 4, 1, 64), jnp.float32)
    for filled in (1, 127, 128, 129, 256, 257, N):
        at = filled - 1
        hot_k = jnp.zeros_like(k).at[:, :, at].set(100.0)
        hot_v = jnp.zeros_like(v).at[:, :, at].set(7.0)
        out = jax.jit(read)(q, hot_k, hot_v, jnp.asarray(filled))
        np.testing.assert_allclose(np.asarray(out), 7.0, rtol=1e-6)


def test_rows_at_their_own_positions_read_the_whole_ring():
    """A ring with a per-row ``index`` (the arena) shares no prefix: the
    single read, no conditional."""
    attn, params, x, k, v, _, _ = _layer("ring", False)
    index = jnp.asarray([9, 140, 650])
    jaxpr = str(jax.make_jaxpr(lambda ck, cv: attn.apply(
        params, x, ck, cv, index, method=MultiHeadAttention.decode_step))(
        k, v))
    assert "cond" not in jaxpr


# --- the model: teacher-forced logits and sampled codes ----------------------

def _model(**overrides):
    cfg = DALLEConfig(dim=64, depth=2, heads=4, dim_head=64,
                      num_text_tokens=50, text_seq_len=TEXT,
                      num_image_tokens=32, image_fmap_size=FMAP,
                      attn_types=("full", "axial_row"), dtype=jnp.float32,
                      **overrides)
    dalle = DALLE(cfg)
    rng = jax.random.PRNGKey(0)
    text = jax.random.randint(rng, (2, TEXT), 1, 50)
    codes = jax.random.randint(rng, (2, cfg.image_seq_len), 0, 32)
    params = dalle.init(rng, text[:1], codes[:1])
    return cfg, dalle, params, text, codes


#: decode-through-cache against the full forward, by cache storage
#: (tests/test_lane_dense_decode.py::FORWARD_TOL)
FORWARD_TOL = {"f32": 2e-4, "bf16": 0.05, "int8": 0.15}


@pytest.mark.parametrize("with_mask", [False, True], ids=["nomask", "keypad"])
@pytest.mark.parametrize("cache", ["bf16", "f32", "int8"])
def test_teacher_forced_scan_matches_the_full_forward(cache, with_mask):
    """Prefill, then the cached decode step scanned over every image
    position (289 ticks through the prefixes 128, 256 and 296, the caches
    carried as ``decode_codes`` carries them) against one full forward."""
    cfg, dalle, params, text, codes = _model(
        kv_cache_bf16=cache == "bf16", kv_cache_int8=cache == "int8")
    mask = None
    if with_mask:
        mask = jnp.arange(TEXT)[None, :] < jnp.asarray([[3], [6]])

    def run(variables, text, codes):
        first, caches = dalle.apply(variables, text, None, mask,
                                    method=DALLE.prefill)
        caches = dalle.apply(variables, caches,
                             method=DALLE.lane_dense_caches)

        def step(carry, code):
            caches, index = carry
            logits, caches = dalle.apply(variables, code, caches, index,
                                         mask, method=DALLE.decode_step)
            return (caches, index + 1), logits

        _, rest = jax.lax.scan(
            step, (caches, jnp.asarray(cfg.text_seq_len + 1)),
            codes[:, :-1].T)
        return jnp.concatenate([first[:, None], rest.transpose(1, 0, 2)], 1)

    assert dalle.apply(params, method=DALLE.dense_read_bounds) == [
        (128, 256, N), None]
    got = jax.jit(run)(params, text, codes)
    forward = dalle.apply(params, text, codes, mask)
    img = np.asarray(forward[:, TEXT:, -cfg.num_image_tokens:])
    np.testing.assert_allclose(np.asarray(got), img, rtol=0,
                               atol=FORWARD_TOL[cache] * float(img.std()))


@pytest.mark.parametrize("cache", ["bf16", "f32"])
def test_decode_codes_draws_the_same_codes_bounded_or_not(cache, monkeypatch):
    """f32 activations, one key: the codes drawn with the read bounded are
    the codes drawn with every tick reading the whole cache."""
    cfg, dalle, params, text, _ = _model(kv_cache_bf16=cache == "bf16")

    def draw():
        first, caches = prefill_codes(dalle, params, text[:1])
        first, caches = tile_prefill(first, caches, 2)
        return np.asarray(jax.jit(
            lambda p, f, c, k: decode_codes(dalle, p, f, c, k,
                                            filter_thres=0.9))(
            params, first, caches, jax.random.PRNGKey(7)))

    bounded = draw()
    monkeypatch.setattr(attention, "read_bounds", lambda slots: (slots,))
    whole = draw()
    assert bounded.shape == (2, cfg.image_seq_len)
    np.testing.assert_array_equal(bounded, whole)


# --- the counter -------------------------------------------------------------

def test_decode_trace_reports_what_its_reads_reach(tmp_path):
    """One trace of ``decode_codes`` over a ``full`` and an ``axial_row``
    layer: one ``decode.kv_reach`` record, two gauges, and a line under
    ``-- decode --`` in the report ``tools/obs_report.py`` prints."""
    cfg, dalle, params, text, _ = _model()
    reg = metrics.init()
    tel = telemetry.init(tmp_path, run_id="kv-reach")
    try:
        first, caches = prefill_codes(dalle, params, text[:1])
        first, caches = tile_prefill(first, caches, 2)
        jax.jit(lambda p, f, c, k: decode_codes(dalle, p, f, c, k)).lower(
            params, first, caches, jax.random.PRNGKey(0))
        rendered = reg.render()
    finally:
        telemetry.shutdown()
        metrics.shutdown()
    events = telemetry.read_events(tel.path)
    reach = [e for e in events
             if e["kind"] == "decode" and e["name"] == "kv_reach"]
    assert len(reach) == 1
    # ticks at positions 8..295 fill 9..296 slots: 120 read 128, 128 read
    # 256, 40 read all 296
    share = (120 * 128 + 128 * 256 + 40 * 296) / 288 / 296
    want = {"bounded_layers": 1, "unbounded_layers": 1, "buckets": 3,
            "read_share": pytest.approx(share)}
    assert {k: reach[0][k] for k in want} == want and reach[0]["rows"] == 2
    assert "graft_decode_kv_bounded_layers 1" in rendered
    assert f"graft_decode_kv_read_share {share:.4f}"[:-1] in rendered
    report = build_report(events)
    assert report["decode"]["reach"] == want
    text_report = render_text(report)
    assert text_report.index("-- decode --") < text_report.index(
        "kv cache reach: 1 layers' dense reads bounded by the position (3 "
        f"prefixes), 1 as before; {100 * share:.1f}% of their slots read a "
        "tick")


@pytest.mark.parametrize("n_pre,slots,want", [
    (257, 1280, 896 / 1280),                    # lucid1024-generate
    (81, 1104, None),                           # cub200-generate
    (2049, 4352, None),                         # smallthinker's global layer
])
def test_read_share_is_the_mean_prefix_over_the_ticks(n_pre, slots, want):
    """``_kv_reach``'s arithmetic against a walk over the ticks."""
    from dalle_pytorch_tpu.models import dalle as dalle_mod

    bounds = read_bounds(slots)
    walked = np.mean([min(b for b in bounds if b >= index + 1)
                      for index in range(n_pre, slots)]) / slots
    if want is not None:
        assert walked == pytest.approx(want, abs=1e-3)

    class Stub:
        cfg = type("Cfg", (), {"seq_len": slots, "mixers": ("attention",)})

        @staticmethod
        def apply(params, masked, method):
            return [bounds]

    cache = (jnp.zeros((1, 1, slots, 2), jnp.bfloat16),) * 2
    got = dalle_mod._kv_reach(Stub, {}, [cache], n_pre)
    assert got["read_share"] == pytest.approx(walked)
    assert got["bounded_layers"] == 1 and got["buckets"] == len(bounds)
