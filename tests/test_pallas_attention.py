"""Pallas flash/block-sparse attention vs the dense masked reference.

Runs the kernels in interpret mode (CPU), checking forward outputs and
gradients for every attention variant against the plain XLA dense-with-mask
computation that `MultiHeadAttention` uses (SURVEY.md §4: 'sparse-attention
equivalence vs dense-with-mask').  Direct kernel calls pass
``interpret=True``; the model never does (``use_pallas`` always asks for the
compiled kernel), so the model-level tests steer the interpreter from here,
with Pallas' own ``force_tpu_interpret_mode`` context.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from dalle_pytorch_tpu.ops.attention import AttnPattern
from dalle_pytorch_tpu.ops.attention_pallas import flash_pattern_attention

from attention_refs import dense_reference

TEXT, FMAP = 5, 4
N = TEXT + FMAP * FMAP  # 21
B, H, DH = 2, 2, 8
BLOCK = 8


def make_pattern(variant, **kw):
    return AttnPattern(variant=variant, seq_len=N - 1, text_len=TEXT,
                       fmap=FMAP, **kw)


def rand_qkv(key, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    shape = (B, H, N, DH)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("variant", ["full", "axial_row", "axial_col",
                                     "conv_like", "sparse"])
def test_forward_matches_dense(variant):
    pattern = make_pattern(variant)
    q, k, v = rand_qkv(jax.random.PRNGKey(0))
    out = flash_pattern_attention(q, k, v, pattern, block_q=BLOCK,
                                  block_k=BLOCK, interpret=True)
    ref = dense_reference(q, k, v, pattern)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("variant", ["full", "axial_row", "conv_like",
                                     "sparse"])
def test_grads_match_dense(variant):
    pattern = make_pattern(variant)
    q, k, v = rand_qkv(jax.random.PRNGKey(1))
    tangent = jax.random.normal(jax.random.PRNGKey(2), q.shape)

    def loss_flash(q, k, v):
        out = flash_pattern_attention(q, k, v, pattern, block_q=BLOCK,
                                      block_k=BLOCK, interpret=True)
        return jnp.sum(out * tangent)

    def loss_dense(q, k, v):
        return jnp.sum(dense_reference(q, k, v, pattern) * tangent)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name} mismatch ({variant})")


def test_key_padding_bias():
    pattern = make_pattern("full", causal=False)
    q, k, v = rand_qkv(jax.random.PRNGKey(3))
    pad = np.zeros((B, N), np.float32)
    pad[:, -4:] = -1e30  # mask the last 4 keys
    bias = jnp.asarray(pad)
    out = flash_pattern_attention(q, k, v, pattern, key_pad_bias=bias,
                                  block_q=BLOCK, block_k=BLOCK,
                                  interpret=True)
    ref = dense_reference(q, k, v, pattern, key_pad_bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_fully_masked_rows_do_not_leak():
    """A sample whose key_pad_bias drops every key must produce zeros, not a
    uniform average over (causally disallowed) keys."""
    pattern = make_pattern("full", causal=False)
    q, k, v = rand_qkv(jax.random.PRNGKey(5))
    bias = jnp.full((B, N), -1e30, jnp.float32)  # drop everything
    out = flash_pattern_attention(q, k, v, pattern, key_pad_bias=bias,
                                  block_q=BLOCK, block_k=BLOCK,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(out), 0.0)
    # and grads through it are finite (zero)
    g = jax.grad(lambda q: jnp.sum(flash_pattern_attention(
        q, k, v, pattern, key_pad_bias=bias, block_q=BLOCK, block_k=BLOCK,
        interpret=True)))(q)
    assert np.isfinite(np.asarray(g)).all()


def test_bf16_forward_close():
    pattern = make_pattern("full")
    q, k, v = rand_qkv(jax.random.PRNGKey(4), dtype=jnp.bfloat16)
    out = flash_pattern_attention(q, k, v, pattern, block_q=BLOCK,
                                  block_k=BLOCK, interpret=True)
    ref = dense_reference(q, k, v, pattern)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.slow
def test_dalle_use_pallas_matches_dense():
    """Full DALLE forward loss with the Pallas kernels == dense path."""
    from dalle_pytorch_tpu import DALLE, DALLEConfig

    def make(use_pallas):
        cfg = DALLEConfig(
            dim=32, num_text_tokens=32, text_seq_len=4, depth=2, heads=2,
            dim_head=16, attn_types=("full", "axial_row", "conv_like",
                                     "sparse"),
            num_image_tokens=16, image_size=16, image_fmap_size=4,
            use_pallas=use_pallas)
        return DALLE(cfg), cfg

    dalle_d, cfg = make(False)
    dalle_p, _ = make(True)
    rng = jax.random.PRNGKey(0)
    text = jax.random.randint(rng, (2, cfg.text_seq_len), 0, 32)
    codes = jax.random.randint(rng, (2, cfg.image_seq_len), 0, 16)
    params = dalle_d.init(rng, text, codes)["params"]

    loss_d = dalle_d.apply({"params": params}, text, codes, return_loss=True)
    with pltpu.force_tpu_interpret_mode():
        loss_p = dalle_p.apply({"params": params}, text, codes,
                               return_loss=True)
    np.testing.assert_allclose(float(loss_d), float(loss_p), rtol=1e-4)

    gd = jax.grad(lambda p: dalle_d.apply({"params": p}, text, codes,
                                          return_loss=True))(params)
    with pltpu.force_tpu_interpret_mode():
        gp = jax.grad(lambda p: dalle_p.apply({"params": p}, text, codes,
                                              return_loss=True))(params)
    flat_d, flat_p = jax.tree.leaves(gd), jax.tree.leaves(gp)
    for a, b in zip(flat_d, flat_p):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4)


def test_block_sparsity_actually_skips():
    """The block summary must mark disallowed blocks 0 (the compute-skip
    guarantee: axial patterns touch far fewer blocks than full)."""
    from dalle_pytorch_tpu.ops.attention_pallas import _pattern_blocks

    full = _pattern_blocks(make_pattern("full"), N, 24, BLOCK, BLOCK)[1]
    axial = _pattern_blocks(make_pattern("axial_row"), N, 24, BLOCK, BLOCK)[1]
    assert axial.sum() <= full.sum()
    # causal: upper-triangle blocks (beyond diagonal) are skipped
    assert full[0, 1] == 0 and full[0, 2] == 0


def test_block_size_config_override(monkeypatch):
    """pallas_block_q/k thread from the layer config to the kernel launch
    (perf_ab's pallas-b* variants sweep them) and results stay equivalent."""
    import dalle_pytorch_tpu.ops.attention_pallas as ap
    from dalle_pytorch_tpu.ops.attention import AttnPattern, MultiHeadAttention

    seen = {}
    orig = ap.flash_pattern_attention

    def spy(*args, **kwargs):
        seen.update(block_q=kwargs.get("block_q"),
                    block_k=kwargs.get("block_k"))
        return orig(*args, **kwargs)

    monkeypatch.setattr(ap, "flash_pattern_attention", spy)

    pattern = AttnPattern(variant="full", seq_len=24, text_len=8, fmap=4)
    attn = MultiHeadAttention(pattern=pattern, dim=32, heads=2, dim_head=16,
                              use_pallas=True, pallas_block_q=256,
                              pallas_block_k=256)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32))
    dense = MultiHeadAttention(pattern=pattern, dim=32, heads=2, dim_head=16)
    params = dense.init(jax.random.PRNGKey(1), x)
    with pltpu.force_tpu_interpret_mode():
        out = attn.apply(params, x)
    assert seen == {"block_q": 256, "block_k": 256}

    ref = dense.apply(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_use_pallas_off_tpu_fails_loudly():
    """``use_pallas`` asks for the compiled Mosaic kernel whatever the
    backend: off-TPU that is an error at lowering, never a silent drop to
    the interpreter (which would report interpreter results — and
    interpreter speed — under the kernel's name)."""
    from dalle_pytorch_tpu.ops.attention import AttnPattern, MultiHeadAttention

    pattern = AttnPattern(variant="full", seq_len=24, text_len=8, fmap=4)
    attn = MultiHeadAttention(pattern=pattern, dim=32, heads=2, dim_head=16,
                              use_pallas=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32))
    params = MultiHeadAttention(pattern=pattern, dim=32, heads=2,
                                dim_head=16).init(jax.random.PRNGKey(1), x)
    with pytest.raises(ValueError, match="[Ii]nterpret"):
        attn.apply(params, x)


def test_vmem_budget_guard():
    """Sequences whose VMEM-resident K/V would overflow the per-core budget
    must fail fast with an actionable error, not an opaque Mosaic failure."""
    from dalle_pytorch_tpu.ops.attention import AttnPattern
    from dalle_pytorch_tpu.ops.attention_pallas import (
        VMEM_BUDGET_BYTES, _vmem_resident_bytes, flash_pattern_attention)

    n = 40960  # ~21 MB of f32 K/V at dh=64: over budget
    assert _vmem_resident_bytes(n, 64, 4, 128) > VMEM_BUDGET_BYTES
    pattern = AttnPattern(variant="full", seq_len=n, text_len=16, fmap=0,
                          causal=True)
    q = jnp.zeros((1, 1, n, 64), jnp.float32)
    # guard fires before any tracing/lowering, so no TPU needed here
    with pytest.raises(ValueError, match="VMEM"):
        flash_pattern_attention(q, q, q, pattern)
    # ...but the interpreter (CPU/GPU correctness path) has no VMEM limit
    # and must NOT be blocked.  Guard check only — actually running n=40k
    # through the interpreter takes minutes.
    import dalle_pytorch_tpu.ops.attention_pallas as ap

    try:
        called = {}
        orig = ap._flash_attention
        ap._flash_attention = lambda *a: called.setdefault("yes", True)
        flash_pattern_attention(q, q, q, pattern, interpret=True)
        assert called.get("yes")
    finally:
        ap._flash_attention = orig

    # the CUB geometry stays comfortably inside the budget
    assert _vmem_resident_bytes(1152, 64, 4, 128) < VMEM_BUDGET_BYTES // 4
