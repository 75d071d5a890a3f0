"""Attention pattern tests: mask semantics per variant, decode-row
consistency, block-sparse layout properties (SURVEY.md §4)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dalle_pytorch_tpu.ops.attention import (
    AttnPattern, MultiHeadAttention, dense_pattern_mask,
    make_variable_sparse_layout, pattern_mask_row,
)

# small grid: text_seq_len=5 (text_len=6 incl bos), fmap=4 -> seq_len=21
TEXT_LEN, FMAP = 6, 4
SEQ_LEN = (TEXT_LEN - 1) + FMAP * FMAP


def make_pattern(variant, **kw):
    return AttnPattern(variant=variant, seq_len=SEQ_LEN, text_len=TEXT_LEN,
                       fmap=FMAP, **kw)


def test_full_is_causal():
    m = dense_pattern_mask(make_pattern("full"), SEQ_LEN, SEQ_LEN)
    assert np.array_equal(m, np.tril(np.ones((SEQ_LEN, SEQ_LEN), bool)))


def test_text_rows_identical_across_sparse_variants():
    """Sparse variants treat text queries as full-causal over text only
    (ref attention.py:113-123)."""
    for variant in ("axial_row", "axial_col", "conv_like"):
        m = dense_pattern_mask(make_pattern(variant), SEQ_LEN, SEQ_LEN)
        for i in range(TEXT_LEN):
            expected = np.zeros(SEQ_LEN, bool)
            expected[: i + 1] = True
            assert np.array_equal(m[i], expected), (variant, i)


def test_image_rows_attend_all_text():
    N = SEQ_LEN + 1  # padded grid: full image raster
    for variant in ("axial_row", "axial_col", "conv_like"):
        m = dense_pattern_mask(make_pattern(variant), N, N)
        assert m[TEXT_LEN:, :TEXT_LEN].all(), variant


def test_axial_row_pattern():
    N = SEQ_LEN + 1
    m = dense_pattern_mask(make_pattern("axial_row"), N, N)
    # query at image raster (r, c) attends image keys in same row, col <= c
    for r in range(FMAP):
        for c in range(FMAP):
            i = TEXT_LEN + r * FMAP + c
            img_part = m[i, TEXT_LEN:].reshape(FMAP, FMAP)
            expected = np.zeros((FMAP, FMAP), bool)
            expected[r, : c + 1] = True
            assert np.array_equal(img_part, expected), (r, c)


def test_axial_col_pattern():
    N = SEQ_LEN + 1
    m = dense_pattern_mask(make_pattern("axial_col"), N, N)
    for r in range(FMAP):
        for c in range(FMAP):
            i = TEXT_LEN + r * FMAP + c
            img_part = m[i, TEXT_LEN:].reshape(FMAP, FMAP)
            expected = np.zeros((FMAP, FMAP), bool)
            expected[: r + 1, c] = True
            assert np.array_equal(img_part, expected), (r, c)


def test_conv_like_pattern():
    kernel = 3
    N = SEQ_LEN + 1
    m = dense_pattern_mask(make_pattern("conv_like", kernel=kernel), N, N)
    pad = kernel // 2
    for r in range(FMAP):
        for c in range(FMAP):
            i = TEXT_LEN + r * FMAP + c
            img_part = m[i, TEXT_LEN:].reshape(FMAP, FMAP)
            expected = np.zeros((FMAP, FMAP), bool)
            for rr in range(max(0, r - pad), min(FMAP, r + pad + 1)):
                for cc in range(max(0, c - pad), min(FMAP, c + pad + 1)):
                    if rr * FMAP + cc <= r * FMAP + c:  # causal
                        expected[rr, cc] = True
            assert np.array_equal(img_part, expected), (r, c)


def test_sparse_layout_properties():
    nb = 8
    lay = make_variable_sparse_layout(nb, global_blocks=2, num_random_blocks=1,
                                      causal=True, seed=0)
    assert not np.triu(lay, 1).any()            # causal at block level
    assert lay[:, 0].all() and lay[2:, 1].all() # global text columns
    assert all(lay[i, i] for i in range(nb))    # diagonal reachable (local)


def test_sparse_layout_deterministic():
    a = make_variable_sparse_layout(16, 2, 3, seed=7)
    b = make_variable_sparse_layout(16, 2, 3, seed=7)
    c = make_variable_sparse_layout(16, 2, 3, seed=8)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)


VARIANTS = ("full", "axial_row", "axial_col", "conv_like", "sparse")


@pytest.mark.parametrize("variant", VARIANTS)
def test_decode_row_matches_dense_mask(variant):
    """pattern_mask_row(i) must equal row i of the dense mask, for all
    variants — this is what makes KV-cache decode output-equivalent."""
    pattern = make_pattern(variant)
    dense = dense_pattern_mask(pattern, pattern.padded_len, SEQ_LEN)
    layout = pattern.block_layout()
    layout_j = jnp.asarray(layout) if layout is not None else None
    for i in range(TEXT_LEN, pattern.padded_len):
        row = np.asarray(pattern_mask_row(pattern, jnp.asarray(i), SEQ_LEN,
                                          layout=layout_j))
        assert np.array_equal(row, dense[i]), (variant, i)


@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "keymask"])
@pytest.mark.parametrize("variant", VARIANTS)
def test_attention_forward_decode_equivalence(variant, masked):
    """Full-sequence forward vs token-by-token decode with KV cache — for
    every variant (``full`` and ``sparse`` read the whole cache, the other
    three only their reachable keys: ``decode_key_positions``), from a
    TEXT-region start (decode_step is a public position-agnostic API:
    aliased negative-row candidates must not double-count text keys in the
    sliced-cache path), and both without and with a partial key-padding
    mask (the sliced branch gathers its scoped pad mask and could drift
    from the dense path unobserved otherwise)."""
    rng = jax.random.PRNGKey(0)
    mask = jnp.asarray(np.arange(SEQ_LEN)[None, :] < np.asarray(
        [[3], [SEQ_LEN]])) if masked else None
    pattern = make_pattern(variant)
    attn = MultiHeadAttention(pattern=pattern, dim=32, heads=2, dim_head=8)
    x = jax.random.normal(rng, (2, SEQ_LEN, 32))
    params = attn.init(rng, x)
    out_full, (k, v) = attn.apply(params, x, mask, return_kv=True)

    # decode from INSIDE the text region using prefilled caches
    ck = jnp.zeros((2, 2, SEQ_LEN, 8))
    cv = jnp.zeros((2, 2, SEQ_LEN, 8))
    start = 2
    ck = ck.at[:, :, :start].set(k[:, :, :start])
    cv = cv.at[:, :, :start].set(v[:, :, :start])
    for i in range(start, SEQ_LEN):
        out_i, ck, cv = attn.apply(
            params, x[:, i : i + 1], ck, cv, jnp.asarray(i),
            mask=mask, method=MultiHeadAttention.decode_step)
        np.testing.assert_allclose(
            np.asarray(out_i[:, 0]), np.asarray(out_full[:, i]),
            rtol=2e-4, atol=2e-5, err_msg=f"pos {i}")


def test_decode_equivalence_window_taller_than_raster():
    """conv_like with a kernel window taller than the fmap: the contiguous
    decode window degenerates to the whole raster and its clamped start
    lands one position INTO the text region (cache is one shorter than the
    padded grid) — the shifted-in text key must not be double-counted
    against the text segment."""
    rng = jax.random.PRNGKey(3)
    T, W = 4, 2
    seq = (T - 1) + W * W
    pattern = AttnPattern(variant="conv_like", seq_len=seq, text_len=T,
                          fmap=W, kernel=5)
    attn = MultiHeadAttention(pattern=pattern, dim=16, heads=2, dim_head=8)
    x = jax.random.normal(rng, (2, seq, 16))
    params = attn.init(rng, x)
    out_full, (k, v) = attn.apply(params, x, return_kv=True)
    ck = jnp.zeros((2, 2, seq, 8)).at[:, :, :1].set(k[:, :, :1])
    cv = jnp.zeros((2, 2, seq, 8)).at[:, :, :1].set(v[:, :, :1])
    for i in range(1, seq):
        out_i, ck, cv = attn.apply(
            params, x[:, i: i + 1], ck, cv, jnp.asarray(i),
            method=MultiHeadAttention.decode_step)
        np.testing.assert_allclose(
            np.asarray(out_i[:, 0]), np.asarray(out_full[:, i]),
            rtol=2e-4, atol=2e-5, err_msg=f"pos {i}")


def test_key_pad_mask_full_variant():
    pattern = make_pattern("full")
    attn = MultiHeadAttention(pattern=pattern, dim=16, heads=2, dim_head=8)
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (1, SEQ_LEN, 16))
    params = attn.init(rng, x)
    mask = jnp.ones((1, SEQ_LEN), bool).at[0, 2].set(False)
    out_masked = attn.apply(params, x, mask)
    x_perturbed = x.at[0, 2].add(10.0)
    out_masked2 = attn.apply(params, x_perturbed, mask)
    # position 2 is masked as a key: queries > 2 must not see the change
    np.testing.assert_allclose(np.asarray(out_masked[0, 3:]),
                               np.asarray(out_masked2[0, 3:]), atol=1e-5)
