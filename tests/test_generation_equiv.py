"""Generation-stack equivalence pins (was tests/test_chip_equiv.py; the
tool's own cases moved to tests/test_chip_smoke.py with its checks).

The equivalence tests for the decode-path byte levers this repo ships: the
bf16 KV cache (``DALLEConfig.kv_cache_bf16``), the int8 cache and weights,
and the fused generate->decode->rerank pipeline (``genrank.rank_codes``) —
each pinned against the f32 forward within tolerance.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dalle_pytorch_tpu import DALLE, DALLEConfig, VAEConfig  # noqa: E402
from dalle_pytorch_tpu.models.dalle import generate_codes  # noqa: E402


# --- bf16 KV cache equivalence ------------------------------------------

VCFG = VAEConfig(image_size=16, num_tokens=32, codebook_dim=16, num_layers=2,
                 hidden_dim=8)


def _build(attn_types=("full", "axial_row", "axial_col", "conv_like"),
           **overrides):
    cfg = DALLEConfig.from_vae(
        VCFG, dim=32, num_text_tokens=50, text_seq_len=5,
        depth=len(attn_types), heads=2, dim_head=8, attn_types=attn_types,
        **overrides)
    dalle = DALLE(cfg)
    rng = jax.random.PRNGKey(0)
    text = jax.random.randint(rng, (2, cfg.text_seq_len), 1, 50)
    codes = jax.random.randint(rng, (2, cfg.image_seq_len), 0, 32)
    params = dalle.init(rng, text, codes, return_loss=True)
    return cfg, dalle, params, text, codes


def test_bf16_cache_is_default_and_stored_bf16():
    """kv_cache_bf16 defaults ON and prefill really returns bf16 caches at
    f32 activations (the byte cut exists only if the storage dtype actually
    changes); the control flag restores f32 storage.  Plan field: never in
    checkpoint hparams."""
    cfg, dalle, params, text, _ = _build()
    assert cfg.kv_cache_bf16 and cfg.dtype == jnp.float32
    _, caches = dalle.apply(params, text, method=DALLE.prefill)
    assert all(k.dtype == jnp.bfloat16 and v.dtype == jnp.bfloat16
               for k, v in caches)

    dalle_f32 = DALLE(dataclasses.replace(cfg, kv_cache_bf16=False))
    _, caches = dalle_f32.apply(params, text, method=DALLE.prefill)
    assert all(k.dtype == jnp.float32 and v.dtype == jnp.float32
               for k, v in caches)

    assert "kv_cache_bf16" not in cfg.to_dict()


def test_bf16_cache_sampler_matches_f32_forward():
    """The bf16-cache sampler (default build) against the f32 forward:
    greedy tokens equal the full-forward argmax on this geometry, and the
    decode-path logits track the forward logits within bf16 tolerance.
    The f32-cache control must match the forward exactly (already pinned
    by test_dalle's sampler tests; asserted here so the bf16 comparison
    has its reference in-file)."""
    cfg, dalle, params, text, _ = _build()
    thres = 1.0 - 1.0 / cfg.total_tokens  # k=1: greedy
    bf16_tokens = np.asarray(generate_codes(
        dalle, params, text, jax.random.PRNGKey(0), filter_thres=thres))

    dalle_f32 = DALLE(dataclasses.replace(cfg, kv_cache_bf16=False))
    f32_tokens = np.asarray(generate_codes(
        dalle_f32, params, text, jax.random.PRNGKey(0), filter_thres=thres))

    # reference-style full-forward greedy decode (f32 end to end), teacher
    # forced: ONE forward over the finished sequence gives, by causality,
    # at position T + cur exactly the logits the reference's loop computes
    # from the first `cur` codes — so "every position's argmax is the next
    # code" IS the loop's result, by induction on cur, without 16 forwards
    # at 16 different shapes (~50 s of eager dispatch).  Two iterations of
    # the loop proper pin the indexing and the causality it rests on.
    T = cfg.text_seq_len
    full = np.asarray(dalle.apply(params, text, jnp.asarray(f32_tokens)))
    for cur in (0, 3):
        prefix = jnp.asarray(f32_tokens[:, :cur]) if cur else None
        looped = np.asarray(dalle.apply(params, text, prefix))[:, -1]
        np.testing.assert_allclose(looped, full[:, T + cur], atol=1e-5)
    out_codes = (full[:, T:T + cfg.image_seq_len].argmax(-1)
                 - cfg.total_text_tokens).astype(np.int32)

    np.testing.assert_array_equal(f32_tokens, out_codes)
    np.testing.assert_array_equal(bf16_tokens, out_codes)

    # logits-level tolerance: one decode step vs the forward's logits at
    # the same position, through the bf16 cache
    first_logits, caches = dalle.apply(params, text, method=DALLE.prefill)
    code0 = jnp.asarray(out_codes[:, 0])
    step_logits, _ = dalle.apply(params, code0, caches,
                                 jnp.asarray(cfg.text_seq_len + 1),
                                 method=DALLE.decode_step)
    fwd = dalle.apply(params, text, jnp.asarray(out_codes[:, :1]))
    fwd_img = np.asarray(fwd)[:, -1, cfg.total_text_tokens:]
    np.testing.assert_allclose(np.asarray(step_logits), fwd_img,
                               rtol=2e-2, atol=2e-2)


# --- int8 quantized serving equivalence (ISSUE 7) ------------------------


def test_int8_cache_is_stored_quantized():
    """kv_cache_int8 really stores (int8 values, f32 per-head scale)
    pairs at f32 activations, takes precedence over kv_cache_bf16, and —
    plan field — never reaches checkpoint hparams."""
    cfg, dalle, params, text, _ = _build()
    cfg8 = dataclasses.replace(cfg, kv_cache_int8=True)
    dalle8 = DALLE(cfg8)
    _, caches = dalle8.apply(params, text, method=DALLE.prefill)
    for k, v in caches:
        for values, scale in (k, v):
            assert values.dtype == jnp.int8
            assert scale.dtype == jnp.float32
            assert scale.shape == (text.shape[0], cfg.heads, 1, 1)
    assert "kv_cache_int8" not in cfg8.to_dict()
    assert "weights_int8" not in cfg8.to_dict()


@pytest.mark.parametrize("overrides", [
    dict(kv_cache_int8=True),
    dict(kv_cache_int8=True, weights_int8=True),
    dict(weights_int8=True, kv_cache_bf16=False),
])
def test_int8_sampler_matches_f32_forward_tiny(overrides):
    """Tiny-geometry exactness floor: greedy decode through the int8
    cache and/or int8 weights reproduces the f32 sampler's tokens on
    this geometry (quantization noise is far below the tiny model's
    logit gaps; the CUB-geometry statistical bound is the slow twin)."""
    cfg, dalle, params, text, _ = _build()
    thres = 1.0 - 1.0 / cfg.total_tokens  # k=1: greedy
    f32_tokens = np.asarray(generate_codes(
        DALLE(dataclasses.replace(cfg, kv_cache_bf16=False)), params, text,
        jax.random.PRNGKey(0), filter_thres=thres))
    q_tokens = np.asarray(generate_codes(
        DALLE(dataclasses.replace(cfg, **overrides)), params, text,
        jax.random.PRNGKey(0), filter_thres=thres))
    np.testing.assert_array_equal(q_tokens, f32_tokens)


@pytest.mark.slow
def test_int8_equivalence_bounds_cub_geometry():
    """The ISSUE 7 equivalence bound at the PRODUCTION geometry: greedy
    token match rate vs the f32 sampler ≥ 0.95 with the int8 cache and
    ≥ 0.75 with int8 cache + int8 weights (calibrated 2026-08-04 on
    XLA:CPU with random init: 0.991 / 0.868 — greedy sequences compound
    any single-token divergence, so these are sequence-level bounds, far
    above what a broken scale layout produces, ~1/8192 ≈ 0)."""
    from dalle_pytorch_tpu.presets import cub200_config

    cfg = dataclasses.replace(cub200_config(), dtype=jnp.float32,
                              kv_cache_bf16=False)
    model = DALLE(cfg)
    rng = jax.random.PRNGKey(0)
    text = jax.random.randint(rng, (2, cfg.text_seq_len), 0,
                              cfg.num_text_tokens)
    params = jax.jit(lambda r: model.init(
        r, text[:1],
        jnp.zeros((1, cfg.image_seq_len), jnp.int32))["params"])(rng)

    def greedy(**kw):
        d = DALLE(dataclasses.replace(cfg, **kw))
        return np.asarray(jax.jit(lambda p, t, k: generate_codes(
            d, {"params": p}, t, k, filter_thres=1.0))(params, text, rng))

    ref = greedy()
    cache8 = greedy(kv_cache_int8=True)
    assert (cache8 == ref).mean() >= 0.95, (cache8 == ref).mean()
    full8 = greedy(kv_cache_int8=True, weights_int8=True)
    assert (full8 == ref).mean() >= 0.75, (full8 == ref).mean()


# --- fused rank path equivalence ----------------------------------------


def test_fused_rank_path_matches_f32_host_scoring(tmp_path):
    """genrank.rank_codes (the fused on-device generate->decode->rerank
    default) against the f32 host path: with a deterministic greedy
    sampler, the fused pipeline's images must equal the chunked host
    generation's, and its device-side CLIP logits must match scoring the
    same pixels through the legacy host-side ranking math within
    tolerance."""
    import genrank
    from dalle_pytorch_tpu.cli import generate_chunked, iter_generated_chunks
    from dalle_pytorch_tpu.models.clip import CLIP, CLIPConfig
    from dalle_pytorch_tpu.utils.checkpoint import save_checkpoint

    cfg, dalle, params, text, _ = _build(attn_types=("full", "axial_row"))
    thres = 1.0 - 1.0 / cfg.total_tokens  # greedy: chunk-invariant output
    tokens = np.repeat(np.asarray(text[:1]), 5, axis=0)  # one shared prompt

    # a stand-in VAE decode: deterministic codes -> pixels map
    table = jax.random.uniform(jax.random.PRNGKey(3),
                               (cfg.num_image_tokens, 3))
    fmap = cfg.image_fmap_size

    @jax.jit
    def decode(codes):
        grid = jnp.take(table, codes, axis=0).reshape(-1, fmap, fmap, 3)
        return jnp.repeat(jnp.repeat(grid, 4, 1), 4, 2)  # [b, 16, 16, 3]

    clip_cfg = CLIPConfig(
        dim_text=16, dim_image=16, dim_latent=8, num_text_tokens=64,
        text_enc_depth=1, text_seq_len=5, text_heads=2, num_visual_tokens=64,
        visual_enc_depth=1, visual_heads=2, visual_image_size=16,
        visual_patch_size=8)
    clip = CLIP(clip_cfg)
    clip_params = clip.init(jax.random.PRNGKey(4),
                            jnp.zeros((1, 5), jnp.int32),
                            jnp.zeros((1, 16, 16, 3)))["params"]
    clip_path = tmp_path / "clip.pt"
    save_checkpoint(clip_path, {"hparams": clip_cfg.to_dict(),
                                "weights": jax.device_get(clip_params)})

    class TinyTok:
        def tokenize(self, texts, seq_len, truncate_text=False):
            return np.full((len(texts), seq_len), 7, np.int32)

    caption = "a bird"
    score_fn = genrank.make_clip_scorer(str(clip_path), TinyTok(), caption)

    images, logits = genrank.rank_codes(
        dalle, params["params"], decode, score_fn, tokens,
        batch_size=2, top_k=thres, rng=jax.random.PRNGKey(0))
    assert images.shape[0] == 5 and logits.shape == (5,)

    # same pixels as the host chunked path (greedy => sampler-invariant)
    host_images, _ = generate_chunked(
        dalle, params["params"], decode, tokens, batch_size=2, top_k=thres,
        rng=jax.random.PRNGKey(0))
    np.testing.assert_allclose(images, host_images, rtol=1e-6, atol=1e-6)

    # device logits vs the legacy host-side ranking math on the SAME pixels
    _, host_logits = genrank.clip_ranking(
        clip, jax.tree.map(jnp.asarray, clip_params), TinyTok(),
        host_images, caption)
    np.testing.assert_allclose(logits, host_logits, rtol=1e-4, atol=1e-4)

    # the shared-prefill path really was the one exercised: all rows equal
    chunks, _ = iter_generated_chunks(
        dalle, params["params"], tokens, batch_size=2, top_k=thres,
        rng=jax.random.PRNGKey(0))
    outs = [np.asarray(c)[:v] for c, v in chunks]
    assert sum(o.shape[0] for o in outs) == 5
