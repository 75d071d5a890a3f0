"""Pallas flash/block-sparse attention vs the dense masked reference.

Runs the kernels in interpret mode (CPU), checking forward outputs and
gradients for every attention variant against the plain XLA dense-with-mask
computation that `MultiHeadAttention` uses (SURVEY.md §4: 'sparse-attention
equivalence vs dense-with-mask').  Direct kernel calls pass
``interpret=True``; the model never does (its switched core always asks for
the compiled kernel), so the tests of that core's two halves steer the
interpreter from here, with Pallas' own ``force_tpu_interpret_mode`` context.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from dalle_pytorch_tpu.ops import attention
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


@pytest.mark.parametrize("variant,causal", [("full", False), ("full", True),
                                            ("axial_row", True)])
def test_fully_masked_rows_do_not_leak(variant, causal):
    """A sample whose key_pad_bias drops every key must produce zeros, not a
    uniform average over (causally disallowed) keys."""
    pattern = make_pattern(variant, causal=causal)
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


def test_block_sparsity_actually_skips():
    """The block table must mark disallowed blocks SKIP (the compute-skip
    guarantee: axial patterns touch far fewer blocks than full)."""
    from dalle_pytorch_tpu.ops.attention_pallas import SKIP, _pattern_blocks

    full = _pattern_blocks(make_pattern("full"), N, BLOCK, BLOCK).table
    axial = _pattern_blocks(make_pattern("axial_row"), N, BLOCK, BLOCK).table
    assert (axial != SKIP).sum() <= (full != SKIP).sum()
    # causal: upper-triangle blocks (beyond diagonal) are skipped
    assert full[0, 1] == SKIP and full[0, 2] == SKIP


def test_compiled_kernel_off_tpu_fails_loudly():
    """Without ``interpret`` the call asks for the compiled Mosaic kernel
    whatever the backend: off-TPU that is an error at lowering, never a
    silent drop to the interpreter (which would report interpreter results
    — and interpreter speed — under the kernel's name)."""
    q = jnp.zeros((1, 2, 128, 64), jnp.bfloat16)   # a shape the chip takes
    pattern = AttnPattern(variant="full", seq_len=127, text_len=64, fmap=8)
    with pytest.raises(ValueError, match="[Ii]nterpret"):
        flash_pattern_attention(q, q, q, pattern)


@pytest.mark.parametrize("shape,blocks,what", [
    ((1, 3, 128, 64), (128, 128), "lane"),     # 192 columns: no lane blocks
    ((1, 1, 128, 64), (128, 128), "lane"),     # half a lane block
    ((1, 2, 136, 64), (128, 128), "16-row"),   # a tail off the sublane tiles
    ((1, 2, 128, 64), (64, 128), "multiples of the TPU lane width"),
    ((1, 2, 64, 64), (128, 128), "multiples of the TPU lane width"),
], ids=["3-heads", "1-head", "n-136", "tile-64", "n-under-a-tile"])
def test_compiled_kernel_refuses_what_the_chip_would(shape, blocks, what):
    """The operand contract at the API's edge (no interpreter): whole
    128-lane blocks of whole heads, lengths on the bf16 sublane tiles, tiles
    of whole lane widths no longer than the sequence."""
    q = jnp.zeros(shape, jnp.bfloat16)
    n = shape[2]
    pattern = AttnPattern(variant="full", seq_len=n - 1, text_len=n - 64,
                          fmap=8)
    with pytest.raises(ValueError, match=what):
        flash_pattern_attention(q, q, q, pattern, block_q=blocks[0],
                                block_k=blocks[1])


def test_vmem_budget_guard():
    """Sequences whose VMEM-resident K/V would overflow the per-core budget
    must fail fast with an actionable error, not an opaque Mosaic failure."""
    from dalle_pytorch_tpu.ops.attention import AttnPattern
    from dalle_pytorch_tpu.ops.attention_pallas import (
        VMEM_BUDGET_BYTES, _vmem_resident_bytes, flash_pattern_attention)

    n = 40960  # ~21 MB of f32 K/V at dh=64: over budget
    assert _vmem_resident_bytes(n, 128, 4, 128, 128) > VMEM_BUDGET_BYTES
    pattern = AttnPattern(variant="full", seq_len=n, text_len=16, fmap=0,
                          causal=True)
    q = jnp.zeros((1, 2, n, 64), jnp.float32)
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
        ap._flash_attention = lambda *a: called.setdefault("yes", True)  # noqa: E731
        ap.flash_qkv_attention(jnp.zeros((1, n, 3, 2, 64), jnp.float32),
                               pattern, interpret=True)
        assert called.get("yes")
    finally:
        ap._flash_attention = orig

    # the CUB geometry stays comfortably inside the budget
    assert _vmem_resident_bytes(1104, 128, 4, 128, 128) < VMEM_BUDGET_BYTES // 4


# --- at the train cells' lengths, bf16 (PR 28; PR 35: the operand contract) --

CUB = dict(text=80, fmap=32)        # n = 1104, the cub200 cycle
LUCID = dict(text=256, fmap=32)     # n = 1280, all full
# variant, geometry, heads, dim_head: two heads of 64 share a program's lanes
# (one program a sample); one head of 128 fills them; four heads of 64 are
# two lane blocks.  At n = 1104 the tail block shares 48 rows with the block
# before it: 336 new rows at tile 384, 80 at tile 128 (axial_row, conv_like).
AT_WIDTH = [("full", CUB, 2, 64), ("axial_row", CUB, 2, 64),
            ("axial_col", CUB, 2, 64), ("conv_like", CUB, 2, 64),
            ("full", LUCID, 2, 64), ("full", CUB, 1, 128),
            ("axial_row", CUB, 4, 64)]
AT_WIDTH_IDS = ["cub-full", "cub-axial_row", "cub-axial_col",
                "cub-conv_like", "lucid-full", "cub-full-dh128",
                "cub-axial_row-4heads"]


def width_pattern(variant, text, fmap):
    n = text + fmap * fmap
    return AttnPattern(variant=variant, seq_len=n - 1, text_len=text,
                       fmap=fmap), n


def dense_branch(pattern, dtype):
    """The model's own dense-masked branch, called directly."""
    from dalle_pytorch_tpu.ops.attention import dense_attention

    return lambda q, k, v: dense_attention(pattern, dtype, q, k, v, None)


@pytest.mark.parametrize("variant,geom,heads,dh", AT_WIDTH, ids=AT_WIDTH_IDS)
def test_bf16_matches_dense_branch_within_its_own_spread(variant, geom, heads,
                                                         dh):
    """bf16 inputs at the train cells' lengths, the tiles the selection
    gives: forward and gradients lie as close to the float32 dense answer as
    the dense branch at bf16 does (its spread, measured here, is the
    yardstick: the kernel rounds where the dense branch rounds)."""
    from dalle_pytorch_tpu.ops.attention import flash_tiles

    pattern, n = width_pattern(variant, **geom)
    tiles = flash_tiles(n, heads, dh, jnp.bfloat16, pattern)
    assert tiles is not None
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v = (jax.random.normal(kk, (1, heads, n, dh), jnp.float32)
               for kk in ks[:3])
    tangent = jax.random.normal(ks[3], (1, heads, n, dh), jnp.float32)

    def outputs(fn, dtype):
        def loss(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * tangent), out
        args = tuple(a.astype(dtype) for a in (q, k, v))
        grads, out = jax.grad(loss, (0, 1, 2), has_aux=True)(*args)
        return [np.asarray(a, np.float32) for a in (out, *grads)]

    exact = outputs(dense_branch(pattern, jnp.float32), jnp.float32)
    dense = outputs(dense_branch(pattern, jnp.bfloat16), jnp.bfloat16)
    flash = outputs(
        lambda q, k, v: flash_pattern_attention(
            q, k, v, pattern, block_q=tiles[0], block_k=tiles[1],
            interpret=True), jnp.bfloat16)
    for name, e, d, f in zip(("out", "dq", "dk", "dv"), exact, dense, flash):
        spread = np.sqrt(np.mean((d - e) ** 2))
        mine = np.sqrt(np.mean((f - e) ** 2))
        assert mine <= 1.5 * spread + 1e-6, (name, mine, spread)
        assert np.abs(f - e).max() <= 2.0 * np.abs(d - e).max() + 1e-6, name


@pytest.mark.parametrize("variant,geom,heads,dh", AT_WIDTH[:5],
                         ids=AT_WIDTH_IDS[:5])
def test_three_block_kinds_counted(variant, geom, heads, dh):
    """Skipped, partly and wholly allowed blocks of each pattern at the
    train cells' lengths and the selection's tiles: they add up, a causal
    pattern skips, ``full`` has wholly allowed blocks below the diagonal,
    the distinct mask tiles are far fewer than the partly allowed blocks'
    rows would be, and the blocks, each laid where the kernel reads it (the
    tail block pulled back to the last full tile), cover every allowed pair
    of positions exactly once."""
    from dalle_pytorch_tpu.ops.attention import (dense_pattern_mask,
                                                 flash_tiles)
    from dalle_pytorch_tpu.ops.attention_pallas import (
        HBM_PAD_ROWS, PARTIAL, SKIP, WHOLE, _pattern_blocks, block_counts,
        block_starts)

    pattern, n = width_pattern(variant, **geom)
    bq, bk = flash_tiles(n, heads, dh, jnp.bfloat16, pattern)
    q_starts, k_starts = block_starts(n, bq), block_starts(n, bk)
    assert HBM_PAD_ROWS == 0 and q_starts[-1] == n - bq     # nothing padded
    assert all(s % 16 == 0 for s in q_starts + k_starts)
    assert len(q_starts) == -(-n // bq) and len(k_starts) == -(-n // bk)
    blocks = _pattern_blocks(pattern, n, bq, bk)
    skipped, partly, wholly = block_counts(pattern, n, bq, bk)
    assert skipped + partly + wholly == len(q_starts) * len(k_starts)
    assert skipped == (blocks.table == SKIP).sum() > 0
    assert wholly == (blocks.table == WHOLE).sum()
    assert partly == (blocks.table >= PARTIAL).sum() > 0
    assert (wholly > 0) == (variant == "full")
    assert blocks.tiles.shape[0] <= partly
    # the table says what the mask says, no pair of positions twice
    counted = np.zeros((n, n), np.int32)
    for qb, q0 in enumerate(q_starts):
        for kb, k0 in enumerate(k_starts):
            code = blocks.table[qb, kb]
            counted[q0:q0 + bq, k0:k0 + bk] += (
                blocks.tiles[code - PARTIAL] if code >= PARTIAL
                else int(code == WHOLE))
    np.testing.assert_array_equal(
        counted, np.broadcast_to(dense_pattern_mask(pattern, n, n), (n, n)))


@pytest.mark.parametrize("variant,text,fmap,tile", [
    ("full", 32, 12, 128),          # n = 176: the tail shares 80 of 128 rows
    ("axial_row", 32, 12, 128),
    ("conv_like", 16, 16, 128),     # n = 272: three blocks, 112 shared
    ("full", 16, 16, 256),          # n = 272: two blocks, 240 shared
], ids=["full-176", "axial_row-176", "conv_like-272", "full-272-t256"])
def test_tail_gradients_count_no_position_twice(variant, text, fmap, tile):
    """The tail block overlaps the block before it: in float32, o, dq, dk
    and dv at every position are those of ``jax.vjp`` of the dense branch (a
    shared query row counted twice would double its part of dk and dv, a
    shared key row stored from the tail's masked copy would zero dk and
    dv there)."""
    from dalle_pytorch_tpu.ops.attention import dense_attention

    pattern, n = width_pattern(variant, text, fmap)
    assert n % tile and n % 16 == 0
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q, k, v, g = (jax.random.normal(kk, (1, 2, n, 64), jnp.float32)
                  for kk in ks)
    want, vjp = jax.vjp(lambda q, k, v: dense_attention(
        pattern, jnp.float32, q, k, v, None), q, k, v)
    got, flash_vjp = jax.vjp(lambda q, k, v: flash_pattern_attention(
        q, k, v, pattern, block_q=tile, block_k=tile, interpret=True),
        q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    for name, a, b in zip(("dq", "dk", "dv"), flash_vjp(g), vjp(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=name)


@pytest.mark.parametrize("variant", ["full", "axial_col"])
def test_wholly_allowed_blocks_change_nothing(variant):
    """Treating every computed block as partly allowed (mask tile + select
    everywhere) gives the same bits: the wholly allowed kind only leaves
    work out."""
    text, fmap = 16, 16
    pattern, n = width_pattern(variant, text, fmap)
    q, k, v = (jax.random.normal(kk, (1, 2, n, 16), jnp.float32)
               for kk in jax.random.split(jax.random.PRNGKey(8), 3))

    def run(all_partial):
        fn = lambda q, k, v: jnp.sum(flash_pattern_attention(  # noqa: E731
            q, k, v, pattern, block_q=64, block_k=64, interpret=True,
            all_partial=all_partial) ** 2)
        return jax.value_and_grad(fn, (0, 1, 2))(q, k, v)

    for a, b in zip(jax.tree.leaves(run(False)), jax.tree.leaves(run(True))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture
def no_kernel_files():
    """The model's switched core keeps its traced kernels as files where
    the program keeps a compile cache (for the TPU only): off for a test
    that builds the core's kernels here."""
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def _core(variant="axial_row"):
    """The static part of a layer's switched core at the narrowest shape
    the compiled kernel takes (n = 128, two heads of 64), with the pattern
    the dense branch takes."""
    pattern = AttnPattern(variant=variant, seq_len=127, text_len=64, fmap=8)
    return pattern, attention._Core(attention.kernel_pattern(pattern),
                                    jnp.dtype(jnp.float32), (128, 128), None,
                                    2, 64)


@pytest.mark.parametrize("variant", ["full", "axial_row"])
def test_layer_key_padding_mask(variant, no_kernel_files):
    """The layer's ``mask=`` reaches the kernel as ``key_pad_bias`` with the
    per-variant scope of ``_scope_key_pad`` (every key for full, the text
    keys for the sparse variants): the core's kernel half against its dense
    branch, on the mask as the model has it."""
    pattern, core = _core(variant)
    q, k, v = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 2, 128, 64))
    qkv = jnp.stack([q, k, v]).transpose(1, 3, 0, 2, 4)  # [b, n, 3, h, dh]
    mask = jnp.asarray(np.r_[[[True] * 5 + [False] * 3],
                             [[True] * 8]])          # text keys, [b, 8]
    ref = attention.dense_attention(pattern, jnp.float32, q, k, v, mask)
    with pltpu.force_tpu_interpret_mode():
        out, _ = core.halves(qkv, mask)[0](qkv, mask)
    assert out.shape == (2, 128, 128)        # to_out's input as it stands
    np.testing.assert_allclose(
        np.asarray(out.reshape(2, 128, 2, 64).transpose(0, 2, 1, 3)),
        np.asarray(ref), atol=2e-5, rtol=2e-5)
    unmasked = attention.dense_attention(pattern, jnp.float32, q, k, v, None)
    assert not np.allclose(np.asarray(ref[0]), np.asarray(unmasked[0]))


def test_kernel_under_checkpoint():
    """The custom VJP survives ``jax.checkpoint`` (``use_remat`` wraps the
    block in one): the gradients of the dense reference.  On the plain
    interpreter: the TPU interpreter's callbacks are effects, which
    ``checkpoint`` does not take; ``tests/test_tpu_compile.py`` compiles the
    rematerialised layer for the chip."""
    pattern = make_pattern("axial_row")
    q, k, v = rand_qkv(jax.random.PRNGKey(6))

    def flash(q, k, v):
        return jnp.sum(flash_pattern_attention(
            q, k, v, pattern, block_q=BLOCK, block_k=BLOCK,
            interpret=True) ** 2)

    got = jax.grad(jax.checkpoint(flash), (0, 1, 2))(q, k, v)
    ref = jax.grad(lambda q, k, v: jnp.sum(
        dense_reference(q, k, v, pattern) ** 2), (0, 1, 2))(q, k, v)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def _equations(jaxpr, outer=""):
    """``(name stack, equation)`` of every equation, nested jaxprs included:
    an equation inside a nested jit names its scopes from that jit on, the
    jit's own equation carries the rest."""
    for eqn in jaxpr.eqns:
        here = f"{outer}/{eqn.source_info.name_stack}"
        yield here, eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, here)


@pytest.mark.parametrize("what", ["scope", "no_update", "record"])
def test_backward_kernels_carry_the_forwards_scope(monkeypatch,
                                                   no_kernel_files, what):
    """A differentiated layer holds two ``pallas_call``s, the forward and one
    backward (three until PR 41: dq, then dk/dv), both under
    ``graftprof:attn-scores`` (a custom VJP's backward does not inherit the
    forward's name scope by itself); the backward writes dq, dk and dv
    itself, so no ``dynamic_update_slice`` of the ``[b, n, 3 * heads *
    dim_head]`` gradient follows it (until PR 41 dv went in by one); and the
    ``attention.kernel`` record says so, ``backward_calls`` 1.  Traced, not
    lowered: the layer's default path with a shape that asks for the kernel,
    whose TPU branch the jaxpr holds beside the dense one."""
    from dalle_pytorch_tpu.ops.attention import MultiHeadAttention

    monkeypatch.setattr(attention, "flash_tiles", lambda *a: (128, 128))
    pattern, _ = _core()
    layer = MultiHeadAttention(pattern=pattern, dim=32, heads=2, dim_head=64)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 32))
    params = layer.init(jax.random.PRNGKey(1), x)
    records = []
    monkeypatch.setattr(attention.telemetry, "emit",
                        lambda kind, name, **kw: records.append(kw))
    with attention.record_kernel_choices("layer"):
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p: jnp.sum(layer.apply(p, x) ** 2)))(params)
    equations = list(_equations(jaxpr.jaxpr))

    if what == "scope":
        found = [stack for stack, eqn in equations
                 if eqn.primitive.name == "pallas_call"]
        assert len(found) == 2
        assert all("graftprof:attn-scores" in s for s in found), found
    elif what == "no_update":
        gradient = (2, 128, 3 * 2 * 64)
        assert any(eqn.primitive.name == "pallas_call"
                   and eqn.outvars[0].aval.shape == gradient
                   for _, eqn in equations)
        assert not [stack for stack, eqn in equations
                    if eqn.primitive.name == "dynamic_update_slice"
                    and eqn.invars[0].aval.shape == gradient]
    else:
        assert len(records) == 1
        assert records[0]["flash_layers"] == 1
        assert records[0]["backward_calls"] == 1
