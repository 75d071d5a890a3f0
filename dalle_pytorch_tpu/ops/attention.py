"""Attention variants, unified as *pattern-masked* attention.

The reference implements four attention layers as separate torch modules
(`/root/reference/dalle_pytorch/attention.py`):

* ``Attention`` — full causal softmax attention (:27-66);
* ``SparseConvCausalAttention`` — image attends all text + a causal local
  kernel_size x kernel_size (dilated) neighborhood, via ``F.unfold``
  (:70-176);
* ``SparseAxialCausalAttention`` — image attends all text + causally along
  its row (axis=0) or column (axis=1) (:180-282);
* ``SparseAttention`` — DeepSpeed ``SparseSelfAttention`` CUDA/Triton kernel
  with ``VariableSparsityConfig`` (block 16, local window, random blocks,
  global text blocks, unidirectional) (:284-342).

TPU-native redesign: every variant is a *boolean attention pattern* over
absolute sequence positions.  One predicate (`_allowed`) defines each
pattern; it is evaluated three ways:

1. as a static dense [n, n] mask (numpy at trace time) for training — at the
   reference's sequence lengths (~1104) a dense masked softmax attention is
   already MXU-optimal, and XLA fuses the mask;
2. as a traced single row for the KV-cache decode step inside ``lax.scan``
   (the reference has no KV cache and reruns the full forward per token,
   dalle_pytorch.py:400-415 — we keep output parity, not work parity);
3. (later rounds) as a block mask feeding the Pallas flash/block-sparse
   kernels in ``ops/attention_pallas.py``.

Positions use the *padded* grid of the reference (:98-102): length
``seq_len + 1`` where the first ``text_len = text_seq_len + 1`` positions are
text (incl <bos>) and the rest is the ``fmap x fmap`` image raster.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Iterator, List, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics, prof, telemetry
from ..utils.helpers import max_neg_value
from .quant import (CacheForm, cache_values, cache_write, cache_write_rows,
                    circular_slice_in_dim, fold_cache, qdense, scaled_qdot,
                    split_cache)
from .ssm import fan_in_normal, rms_norm

VARIANTS = ("full", "axial_row", "axial_col", "conv_like", "sparse")

#: minor dimension of the TPU's tiled layouts (a vector register's lanes)
LANES = 128


def kv_fold_factor(heads: int, dim_head: int, dtype) -> int:
    """How many heads share the minor dimension of a lane-dense decode cache
    (``quant.fold_heads``); 1 keeps the plain ``[b, heads, n, dh]``.

    Inside the decode scan XLA:TPU lays a carried cache out with either
    ``dim_head`` or the batch on the 128 lanes and pads it to them: at
    ``dim_head`` 64 and 32 rows every tick streams twice the cache's bytes
    (PERF.md, Findings PR 26).  Folding ``128 / dim_head`` heads into the
    minor dimension fills the lanes exactly.  Decided from what the trace
    can see: nothing to fold at a ``dim_head`` that already fills the lanes
    or does not divide them, or at a head count the fold does not divide;
    4-byte caches stay plain too, since the folded reads are dots and a dot
    on f32 multiplicands rounds them unless it runs six passes.  The row
    count has no say: a batch that fills the lanes could carry the plain
    layout at the memory rate, but the bounded read's conditional
    (:meth:`MultiHeadAttention._masked_read`) takes its operands row-major,
    ``dim_head`` on the lanes again (PERF.md, Findings PR 33)."""
    fold = LANES // dim_head if LANES % dim_head == 0 else 1
    if fold == 1 or heads % fold or jnp.dtype(dtype).itemsize >= 4:
        return 1
    return fold


def grouped_dots(q, k):
    """``q`` ``[b, h, i, d]`` against ``k`` ``[b, g, j, d]`` where each of
    the ``g`` key heads serves ``h / g`` query heads (``g`` 1: multi-query).
    Multiplicands in ``k``'s dtype, float32 sums: ``[b, h, i, j]``."""
    b, h, i, d = q.shape
    g = k.shape[1]
    return jnp.einsum("bgrid,bgjd->bgrij",
                      q.reshape(b, g, h // g, i, d).astype(k.dtype), k,
                      preferred_element_type=jnp.float32).reshape(b, h, i, -1)


def grouped_values(attn, v):
    """``attn`` ``[b, h, i, j]`` over the values ``v`` ``[b, g, j, d]`` of
    :func:`grouped_dots`' grouping: float32 ``[b, h, i, d]``."""
    b, h, i, j = attn.shape
    g = v.shape[1]
    return jnp.einsum("bgrij,bgjd->bgrid",
                      attn.reshape(b, g, h // g, i, j).astype(v.dtype), v,
                      preferred_element_type=jnp.float32).reshape(b, h, i, -1)


def _block_diag_q(q, fold: int):
    """``[b, h, 1, dh]`` -> ``[b, h / fold, fold * dh, fold]``: column ``f``
    of group ``g`` holds head ``g * fold + f``'s query at rows
    ``[f * dh, (f + 1) * dh)`` and exact zeros elsewhere, so a dot against a
    head-folded cache row yields each head's own q.k."""
    b, h, _, dh = q.shape
    own = jnp.eye(fold, dtype=bool)[:, None, :]
    return jnp.where(own, q.reshape(b, h // fold, fold, dh, 1), 0).reshape(
        b, h // fold, fold * dh, fold)


def apply_rope(x, positions, theta: float, rotary_dim: Optional[int] = None,
               freq=None, scale: float = 1.0):
    """Rotary position embedding of ``x`` ``[b, h, n, d]`` at integer
    ``positions`` ``[n]`` or ``[b, n]`` over its first ``rotary_dim``
    dimensions (None: all ``d``; the rest pass as they are, HF's
    partial-rotary convention): dimension ``i < r / 2`` pairs with ``i + r /
    2`` (rotate-half) and turns by ``p * freq_i``, ``freq_i = theta^(-2i /
    r)`` unless a table ``freq`` ``[r / 2]`` is given (:func:`yarn_table`),
    the cosines and sines times ``scale`` (YaRN's attention factor).  Angles,
    sines and the rotation in float32; the result in ``x``'s dtype."""
    rot = x.shape[-1] if rotary_dim is None else rotary_dim
    half = rot // 2
    if freq is None:
        freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.asarray(positions, jnp.float32)[..., None] * jnp.asarray(
        freq, jnp.float32)
    if angle.ndim == 3:                 # per-row positions: [b, 1, n, d / 2]
        angle = angle[:, None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    lo, hi = x[..., :half].astype(jnp.float32), x[..., half:rot].astype(
        jnp.float32)
    turned = [lo * cos - hi * sin, hi * cos + lo * sin]
    if rot < x.shape[-1]:
        turned.append(x[..., rot:].astype(jnp.float32))
    return jnp.concatenate(turned, axis=-1).astype(x.dtype)


#: YaRN's turns over the original length above which a frequency is kept
#: and below which it is interpolated (HF's defaults, and Laguna's values)
YARN_BETA_FAST, YARN_BETA_SLOW = 32.0, 1.0


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN's long-context rotation (arXiv:2309.00071, as HF's ``rope_type:
    yarn`` computes it): frequencies that turn fewer than
    :data:`YARN_BETA_SLOW` times over ``original_len`` positions are divided
    by ``factor``, those that turn more than :data:`YARN_BETA_FAST` times are
    kept, a linear ramp between (its ends rounded outwards), and the cosines
    and sines are multiplied by :attr:`scale`."""

    factor: float
    original_len: int

    def __post_init__(self):
        assert self.factor > 1 and self.original_len > 0, self

    @property
    def scale(self) -> float:
        """The attention factor, ``0.1 ln(factor) + 1`` (1.4852 at 128)."""
        return 0.1 * float(np.log(self.factor)) + 1.0


def yarn_ramp(theta: float, rotary_dim: int, yarn: YaRN) -> Tuple[int, int]:
    """``(low, high)``: the rotary pairs below ``low`` keep their frequency,
    those from ``high`` on are interpolated; HF's ``find_correction_range``
    with ``truncate`` (theta 5e5, 64 dimensions, 8,192 positions, betas 32
    and 1: 9.04 -> 9, 17.49 -> 18)."""
    def dim(turns):
        return (rotary_dim * np.log(yarn.original_len / (turns * 2 * np.pi))
                / (2 * np.log(theta)))

    low = max(int(np.floor(dim(YARN_BETA_FAST))), 0)
    high = min(int(np.ceil(dim(YARN_BETA_SLOW))), rotary_dim - 1)
    return low, high


@functools.lru_cache(maxsize=16)
def yarn_table(theta: float, rotary_dim: int, yarn: YaRN) -> np.ndarray:
    """The float32 frequencies ``[rotary_dim / 2]`` of a YaRN rotation:
    ``lerp(base / factor, base, 1 - ramp)`` with ``base_i = theta^(-2i /
    rotary_dim)`` and ``ramp`` 0 below :func:`yarn_ramp`'s ``low``, 1 from
    its ``high``.  Read-only."""
    low, high = yarn_ramp(theta, rotary_dim, yarn)
    base = theta ** (-np.arange(0, rotary_dim, 2, dtype=np.float64)
                     / rotary_dim)
    ramp = np.clip((np.arange(rotary_dim // 2) - low)
                   / max(high - low, 1e-3), 0, 1)
    table = (base / yarn.factor * ramp + base * (1 - ramp)).astype(np.float32)
    table.setflags(write=False)
    return table


def ring_positions(index, slots: int):
    """The position each slot of a ring cache holds once position ``index``
    (traced scalar or ``[b]``) is written: the largest ``p <= index`` with
    ``p mod slots == slot``; negative where the slot was never written."""
    index = jnp.asarray(index, jnp.int32)
    slot = jnp.arange(slots, dtype=jnp.int32)
    return index[..., None] - jnp.remainder(index[..., None] - slot, slots)


#: most static prefixes one cache's bounded decode read chooses among
READ_BUCKETS = 8


def read_bounds(slots: int) -> Tuple[int, ...]:
    """The static prefix lengths a decode tick's dense read of a cache of
    ``slots`` chooses among (:meth:`MultiHeadAttention._masked_read`), the
    last one the whole cache.  Buckets are the smallest multiple of the 128
    lanes that gives at most :data:`READ_BUCKETS` of them: 1280 and 1104 ->
    256 (5 buckets), 4096 -> 512 (8), 4352 -> 640 (7); a cache under two
    lane widths keeps the single read."""
    if slots < 2 * LANES:
        return (slots,)
    width = LANES
    while -(-slots // width) > READ_BUCKETS:
        width += LANES
    return tuple(range(width, slots, width)) + (slots,)


def switch_read_prefix(bounds, reads, operands, filled):
    """One of ``reads`` (one a static prefix of ``bounds``, the last the
    whole cache) on ``operands``: the first whose prefix holds ``filled``
    slots (a traced scalar), chosen by a ``lax.switch`` around the read
    alone; the whole cache where ``filled`` is None or there is one bucket.
    A compare and a sum find the bucket: ``searchsorted`` lowers to a loop
    of its own."""
    if filled is None or len(bounds) == 1:
        return reads[-1](*operands)
    bucket = jnp.sum(filled > jnp.asarray(bounds[:-1], jnp.int32))
    return jax.lax.switch(bucket, reads, *operands)


def make_variable_sparse_layout(
    num_blocks: int,
    global_blocks: int,
    num_random_blocks: int,
    local_window_blocks: Tuple[int, ...] = (4,),
    causal: bool = True,
    seed: int = 0,
) -> np.ndarray:
    """Block-level layout with DeepSpeed ``VariableSparsityConfig`` semantics
    (ref attention.py:296-312): local windows, per-row random blocks, global
    (column-attended) text blocks, optionally unidirectional.  Deterministic
    via `seed` — the TPU analog of the kernel's fixed random layout.
    """
    layout = np.zeros((num_blocks, num_blocks), dtype=bool)

    # local windows: consecutive row groups attend within their own group;
    # the last window size repeats to cover the sequence.
    sizes = list(local_window_blocks)
    start = 0
    i = 0
    while start < num_blocks:
        w = sizes[i] if i < len(sizes) else sizes[-1]
        end = min(start + w, num_blocks)
        layout[start:end, start:end] = True
        start = end
        i += 1

    # random blocks: per block-row, `num_random_blocks` random block-columns
    # (restricted to <= row when causal).
    rng = np.random.default_rng(seed)
    for row in range(num_blocks):
        hi = row + 1 if causal else num_blocks
        cols = rng.integers(0, hi, size=num_random_blocks)
        layout[row, cols] = True

    # global blocks: every row attends the global (text) block-columns.
    layout[:, :global_blocks] = True

    if causal:
        layout &= np.tril(np.ones((num_blocks, num_blocks), dtype=bool))
    return layout


@dataclasses.dataclass(frozen=True)
class AttnPattern:
    """Static description of one layer's attention pattern."""

    variant: str
    seq_len: int          # transformer seq len (text_seq_len + image_seq_len)
    text_len: int         # text positions incl <bos> = text_seq_len + 1
    fmap: int             # image feature-map side; fmap**2 = image_seq_len
    causal: bool = True   # CLIP encoders use bidirectional 'full' attention
    kernel: int = 5       # conv_like kernel size (ref attention.py:71)
    dilation: int = 1
    block: int = 16       # sparse block size (ref attention.py:292)
    num_random_blocks: Optional[int] = None
    layout_seed: int = 0
    # sliding window of a causal ``full`` layer: query i reaches keys j in
    # (i - window, i]; 0 is unbounded.  No flash kernel bounds its keys yet:
    # a windowed layer takes the dense core (:func:`flash_tiles`).
    window: int = 0

    def __post_init__(self):
        assert self.variant in VARIANTS, f"unknown attention variant {self.variant}"
        if self.variant == "conv_like":
            assert self.kernel % 2 == 1, "kernel size must be odd"
        assert self.window >= 0 and (not self.window or (
            self.variant == "full" and self.causal)), (
            "a sliding window bounds a causal 'full' layer only")

    @property
    def cache_len(self) -> int:
        """Slots of this layer's decode cache: every position, or a ring of
        the window's length (position p lives in slot ``p mod window``)."""
        return min(self.window, self.seq_len) if self.window else self.seq_len

    @property
    def padded_len(self) -> int:
        return self.seq_len + 1

    def block_layout(self) -> Optional[np.ndarray]:
        if self.variant != "sparse":
            return None
        n = self.padded_len
        nb = (n + self.block - 1) // self.block
        # defaults from the reference wrapper (attention.py:299-300):
        # random blocks = seq_len // block // 4, global blocks cover the text.
        num_random = (
            self.num_random_blocks
            if self.num_random_blocks is not None
            else self.seq_len // self.block // 4
        )
        global_blocks = -(-self.text_len // self.block)  # ceil
        return make_variable_sparse_layout(
            nb, global_blocks, num_random, causal=True, seed=self.layout_seed
        )


def _allowed(pattern: AttnPattern, i, j, xp, layout=None):
    """The pattern predicate: may query position `i` attend key position `j`?

    Works for both numpy (broadcast grid, static) and jnp (traced row).
    `i`/`j` are absolute positions on the padded grid.
    """
    T, W = pattern.text_len, pattern.fmap
    causal = (j <= i) if pattern.causal else (j == j)
    if pattern.window:
        causal = causal & (j > i - pattern.window)
    v = pattern.variant

    if v == "full":
        return causal

    if v == "sparse":
        if layout is None:
            layout = pattern.block_layout()
        lay = xp.asarray(layout)
        return causal & lay[i // pattern.block, j // pattern.block]

    # text queries attend text causally only (ref attention.py:113-123)
    text_q_allowed = causal & (j < T)

    # image query / key raster coordinates
    ri, ci = (i - T) // W, (i - T) % W
    rj, cj = (j - T) // W, (j - T) % W

    if v == "axial_row":
        img_pat = (rj == ri) & (cj <= ci)
    elif v == "axial_col":
        img_pat = (cj == ci) & (rj <= ri)
    elif v == "conv_like":
        pad = ((pattern.kernel - 1) * pattern.dilation + 1) // 2
        dr, dc = rj - ri, cj - ci
        in_window = (
            (xp.abs(dr) <= pad)
            & (xp.abs(dc) <= pad)
            & (dr % pattern.dilation == 0)
            & (dc % pattern.dilation == 0)
        )
        img_pat = in_window & causal
    else:  # pragma: no cover
        raise ValueError(v)

    img_q_allowed = xp.where(j < T, True, img_pat)
    return xp.where(i < T, text_q_allowed, img_q_allowed)


def dense_pattern_mask(pattern: AttnPattern, n_q: int, n_k: int) -> np.ndarray:
    """Static [n_q, n_k] boolean mask (True = attend), built with numpy at
    trace time so it becomes an XLA constant.  Read-only: the layers of one
    variant, forward and backward, share one array."""
    return _pattern_mask(kernel_pattern(pattern), n_q, n_k)


@functools.lru_cache(maxsize=16)
def _pattern_mask(pattern: AttnPattern, n_q: int, n_k: int) -> np.ndarray:
    i = np.arange(n_q)[:, None]
    j = np.arange(n_k)[None, :]
    layout = pattern.block_layout()
    mask = np.asarray(_allowed(pattern, i, j, np, layout=layout))
    mask.setflags(write=False)
    return mask


def pattern_mask_row(pattern: AttnPattern, index, n_k: int,
                     layout: Optional[jax.Array] = None) -> jax.Array:
    """Traced mask row for decode: which of the `n_k` cached keys may the
    query at (traced) position `index` attend?"""
    j = jnp.arange(n_k)
    return _allowed(pattern, index, j, jnp, layout=layout)


def decode_key_positions(
        pattern: AttnPattern, index
) -> Optional[Tuple[jax.Array, jax.Array, bool]]:
    """Candidate key positions for ONE decode query at (traced) `index`.

    Decode queries are always image positions (only image tokens are
    sampled), and for the axial/conv patterns their reachable key set is a
    small, position-computable subset of the cache: all text plus the
    query's raster row / column / causal neighborhood rows.  Returning that
    superset (exactness is restored by ``_allowed`` over the returned
    positions) lets the decode step GATHER ~10% of the KV cache instead of
    streaming all of it through the masked dots — the decode loop is HBM-
    bandwidth-bound, so cache traffic is the throughput (the training path
    is unaffected; dense-masked attention there is MXU-optimal).

    Returns traced ``(positions [m] int32, valid [m] bool, contiguous)``
    with m static and ``contiguous`` a STATIC bool, or None for variants
    whose reachable set isn't smaller (full) or isn't position-local
    (sparse's random blocks).

    When ``contiguous`` is True the image segment ``positions[T:]`` is the
    ascending run ``positions[T] + arange(...)`` — the decode step then
    reads it with one ``dynamic_slice`` (cheap on TPU) instead of a general
    gather.  Contiguous candidate windows are CLIPPED into the raster
    (never just range-clipped at gather time): an out-of-image candidate
    clipped independently of its reported position would ALIAS onto a text
    position the text segment already carries, pass ``_allowed`` and
    double-count that key in the softmax.  Clipping the window start keeps
    reported positions == read positions; any extra in-window keys the
    query can't reach (shifted conv windows near the raster top, an
    image-row window under a text-region query) are exact-masked by
    ``_allowed``.  ``valid`` carries the residual validity for the strided
    (non-contiguous) conv case, whose out-of-raster rows can't be clipped
    without breaking the stride.
    """
    T, W = pattern.text_len, pattern.fmap
    v = pattern.variant
    ii = index - T
    ri, ci = ii // W, ii % W
    contiguous = False
    if v == "axial_row":
        # clip into the raster: a text-region query (legal through the
        # public decode_step API) has ri < 0; row 0's keys are then read
        # but fully masked by _allowed (text queries reach no image keys)
        row0 = jnp.clip(ri, 0, W - 1)
        img = T + row0 * W + jnp.arange(W)
        img_valid = jnp.ones((W,), bool)
        contiguous = True
    elif v == "axial_col":
        # ci = ii % W is non-negative even for text-region queries (jnp
        # remainder semantics), so every candidate is a real image position
        img = T + ci + jnp.arange(W) * W
        img_valid = jnp.ones((W,), bool)
    elif v == "conv_like":
        pad = ((pattern.kernel - 1) * pattern.dilation + 1) // 2
        # causality kills every row below the query's, so candidates are
        # the query row and the window rows above it, at the dilation
        # stride; each row is taken whole (W keys) and the window's column
        # extent is enforced by the predicate
        n_rows = pad // pattern.dilation + 1
        if pattern.dilation == 1:
            # contiguous ascending window [row0, row0 + n_rows), clipped
            # into the raster; shifted-in future rows are _allowed-masked.
            # A window taller than the raster (big kernel on a tiny fmap)
            # degenerates to the whole raster — never a negative clip bound
            n_rows = min(n_rows, W)
            row0 = jnp.clip(ri - (n_rows - 1), 0, W - n_rows)
            rows = row0 + jnp.arange(n_rows)
            img_valid = jnp.ones((n_rows * W,), bool)
            contiguous = True
        else:
            rows = ri - pattern.dilation * jnp.arange(n_rows)
            img_valid = jnp.broadcast_to(
                ((rows >= 0) & (rows < W))[:, None], (n_rows, W)).reshape(-1)
        img = (T + rows[:, None] * W + jnp.arange(W)[None, :]).reshape(-1)
    else:  # full: everything is reachable; sparse: random blocks aren't local
        return None
    positions = jnp.concatenate([jnp.arange(T), img]).astype(jnp.int32)
    valid = jnp.concatenate([jnp.ones((T,), bool), img_valid])
    return positions, valid, contiguous


def _scope_key_pad(pattern: AttnPattern, key_mask, n_k: int):
    """Per-variant scope of a [b, m] key padding mask (True = keep) -> [b,
    n_k] bool.  Parity: the full variant applies it to every key
    (attention.py:51-54); sparse variants apply it to the text keys only
    (:99-102, :208-211) — positions beyond its scope are kept."""
    if pattern.variant != "full":
        key_mask = key_mask[:, : pattern.text_len]
    m = key_mask.shape[1]
    if m >= n_k:
        return key_mask[:, :n_k]
    return jnp.pad(key_mask, ((0, 0), (0, n_k - m)), constant_values=True)


def _merge_key_pad_mask(pattern: AttnPattern, allow, key_mask):
    """`allow` is [..., n_q, n_k]; returns [b, 1, n_q, n_k]-broadcastable
    boolean mask with the scoped key padding applied."""
    if key_mask is None:
        return allow
    pad = _scope_key_pad(pattern, key_mask, allow.shape[-1])
    return allow & pad[:, None, None, :]


# --- which attention core a forward without a cache runs ----------------------

#: Below this length the dense-masked branch stays: forward + backward of one
#: layer on the chip, dense against kernel, 0.55 / 0.62 ms at n = 81 and 0.56
#: / 0.59 at 257, but 1.89 / 1.30 at 592, 7.23 / 2.1-2.8 at 1104, 4.65 / 1.45
#: at 1280 and 34.7 / 5.5 at 4176 (PERF.md, Findings PR 28).
FLASH_MIN_LEN = 512
#: What a computed block costs besides its ``block_q x block_k`` scores, in
#: scores: the tilings of one pattern rank on the chip as ``computed scores +
#: this x computed blocks`` does (n = 1104, full: 128-tiles 45 blocks, 2.83
#: ms, 384-tiles 6 blocks and a fifth more scores, 2.78 ms; axial_row: 24
#: blocks 2.13 ms against 6 blocks 2.79 ms; n = 1280, full: 256-tiles 1.45
#: ms, 128-tiles 1.63, 640-tiles 1.65; PERF.md, Findings PR 28).
FLASH_BLOCK_COST = 4096
#: Computed blocks one kernel may unroll (its loops are static): past this
#: the program and its compile (17-29 s at n = 4176 with 66 blocks) grow
#: out of bounds.
FLASH_MAX_BLOCKS = 128


def lane_block(heads: int, dim_head: int) -> Optional[int]:
    """Columns of the block one program of the flash kernel takes of the
    projections' arrays ``[b, n, .. heads * dim_head]``: ``dim_head`` where
    that is whole lane widths (one head a program), else the 128 lanes with
    ``128 / dim_head`` heads side by side; None where ``heads * dim_head``
    does not cut into such blocks of whole heads."""
    if dim_head % LANES == 0:
        return dim_head
    if LANES % dim_head or (heads * dim_head) % LANES:
        return None
    return LANES


def flash_tiles(n: int, heads: int, dim_head: int, dtype,
                pattern: AttnPattern, kv_heads: Optional[int] = None,
                ring_axis: Optional[str] = None) -> Optional[Tuple[int, int]]:
    """``(block_q, block_k)`` for ``ops/attention_pallas.py``'s flash kernel
    where a forward of this shape should run it, None where the dense-masked
    branch stays.  Decided from what a trace can see; which of the two a
    program really holds is settled where it is lowered (the kernel exists
    for the TPU only: :meth:`MultiHeadAttention._kernel_core`).

    Dense stays for grouped keys, a sliding window and the sequence-parallel
    plans (their own branches), for float32 activations (the kernel's products would run as
    several bf16 passes where XLA's default precision takes one), for a
    ``dim_head`` that does not fill a whole number of half-lanes, for
    ``heads`` (a shard's, under a plan that splits them) that do not fill
    whole lane blocks (:func:`lane_block`: the kernel reads ``to_qkv``'s
    own array, 128 columns a program), for a length off the 16-row sublane
    tiles (a tail block starts at ``n - tile``), for sequences under
    :data:`FLASH_MIN_LEN`, and where no tiling fits.
    Tiles: nothing is padded in HBM; the kernel takes the last block of a
    length that is no multiple of the tile as the last full tile (1104: rows
    720-1103 at 384, 976-1103 at 128).  The sequence is cut into equal
    square tiles of a width that divides its length rounded up to the lanes
    (1104 -> 1152, 1280, 4176 -> 4224); of those, the one whose computed
    blocks cost least by :data:`FLASH_BLOCK_COST` (the wider at a tie: less
    to unroll), among those that leave at most :data:`FLASH_MAX_BLOCKS`
    blocks to compute and fit VMEM: 384 for ``full`` and ``axial_col`` and
    128 for ``axial_row`` and ``conv_like`` at 1104, 256 at 1280, 384 at
    4176."""
    lanes = lane_block(heads, dim_head)
    if (kv_heads is not None or ring_axis is not None or pattern.window
            or jnp.dtype(dtype).itemsize != 2 or dim_head % (LANES // 2)
            or lanes is None or n % 16 or n < FLASH_MIN_LEN):
        return None
    return _cheapest_tiles(n, lanes, kernel_pattern(pattern))


def kernel_pattern(pattern: AttnPattern) -> AttnPattern:
    """``pattern`` as the kernel and its choice of tiles are keyed: the seed
    draws the sparse layout alone, so without it the layers of one variant
    are one pattern, decided and traced once."""
    if pattern.variant == "sparse":
        return pattern
    return dataclasses.replace(pattern, layout_seed=0)


@functools.lru_cache(maxsize=64)
def _cheapest_tiles(n: int, lanes: int,
                    pattern: AttnPattern) -> Optional[Tuple[int, int]]:
    from . import attention_pallas as ap

    n_pad = -(-n // LANES) * LANES
    best = None
    for tile in range(n_pad, 0, -LANES):
        if n_pad % tile or tile > n:
            continue
        blocks = ap._pattern_blocks(pattern, n, tile, tile)
        computed = blocks.counts[1] + blocks.counts[2]
        if (computed > FLASH_MAX_BLOCKS or ap._vmem_resident_bytes(
                n, lanes, 2, tile, tile, blocks.tiles.shape[0],
                has_bias=True) > ap.VMEM_BUDGET_BYTES):
            continue
        cost = computed * (tile * tile + FLASH_BLOCK_COST)
        if best is None or cost < best[0]:
            best = cost, tile
    return best and (best[1], best[1])


class KernelMesh(NamedTuple):
    """Where the kernel call is split under a plan: the mesh, the axes the
    batch is sharded over and the axis the heads are (None: whole)."""
    mesh: Any
    batch_axes: Tuple[str, ...]
    head_axis: Optional[str]

    @property
    def batch_ways(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes]))

    @property
    def head_ways(self) -> int:
        return self.mesh.shape[self.head_axis] if self.head_axis else 1


_kernel_mesh: List[KernelMesh] = []
_choices: List[dict] = []   # per open record: {layer's pattern: choice}


@contextlib.contextmanager
def kernel_mesh(partitioner) -> Iterator[None]:
    """The step factories open this around the model's trace: a Mosaic
    kernel cannot be partitioned by GSPMD, so under a plan's mesh the kernel
    call runs inside a ``shard_map`` with q, k, v, o split over the batch
    axes and (the ``tp`` rules' head axis) the heads, whole on the sequence
    and ``dim_head``.  No partitioner, one device: no wrap."""
    if partitioner is None or partitioner.mesh.size == 1:
        yield
        return
    mesh = partitioner.mesh
    _kernel_mesh.append(KernelMesh(
        mesh, tuple(partitioner.batch_axes),
        "tp" if mesh.shape.get("tp", 1) > 1 else None))
    try:
        yield
    finally:
        _kernel_mesh.pop()


@contextlib.contextmanager
def record_kernel_choices(model: str) -> Iterator[None]:
    """Collect every attention layer's choice during one trace of a model
    and say what was chosen: one ``attention.kernel`` telemetry record and
    its gauges (flash layers, dense layers, latent layers where the model has
    any, the share of blocks the flash
    layers compute, skipped blocks left out, the kernel's operand
    contract: heads a program shares the lanes between, positions of
    padding a call adds to a sequence in HBM, and the ``pallas_call``s a
    flash layer's backward makes).  A layer traced twice (a
    reversible stack's custom VJP) counts once: its pattern is its key."""
    _choices.append({})
    try:
        yield
    finally:
        layers = list(_choices.pop().values())
        if layers:
            flash = [c for c in layers if c["tiles"] is not None]
            # latent-attention layers (ops/latent_attention.py) run their own
            # published form over a sequence: counted apart, and only where
            # there are any, so that other models' records stay as they were
            latent = [c for c in layers if c.get("latent", False)]
            computed = sum(c["computed"] for c in flash)
            blocks = sum(c["blocks"] for c in flash)
            counts = {
                "flash_layers": len(flash),
                "dense_layers": len(layers) - len(flash) - len(latent),
                **({"latent_layers": len(latent)} if latent else {}),
                "blocks_computed_share":
                    round(computed / blocks, 4) if blocks else 0.0,
                "heads_per_program":
                    max((c["heads_per_program"] for c in flash), default=0),
                "hbm_pad_rows":
                    max((c["hbm_pad_rows"] for c in flash), default=0),
                "backward_calls":
                    max((c["backward_calls"] for c in flash), default=0)}
            telemetry.emit(
                "attention", "kernel", model=model,
                n=max(c["n"] for c in layers),
                tiles=sorted({"x".join(map(str, c["tiles"])) for c in flash}),
                **counts,
                # how a tick of the latent layers reads their cache: one
                # pass in a kernel, by blocks of so many positions, or two
                # in plain XLA (ops/latent_attention.py::one_pass_read)
                **({k: latent[0][k] for k in ("latent_read", "block")}
                   if latent else {}))
            reg = metrics.active()
            if reg is not None:
                for name, value in counts.items():
                    reg.gauge(f"graft_attn_{name}",
                              "the model's last trace (attention.kernel)"
                              ).set(value)


def dense_attention(pattern: AttnPattern, act_dtype, q, k, v, mask,
                    grouped: bool = False):
    """Scores, masked softmax and ``attn.v`` as plain XLA ops: the ``[b, h,
    n, n]`` float32 scores are written out, the probabilities cast to the
    activations' dtype.  ``grouped``: fewer key heads than query heads."""
    n = q.shape[2]
    scale = q.shape[-1] ** -0.5
    if grouped:
        dots = grouped_dots(q * scale, k)
    else:
        dots = jnp.einsum("bhid,bhjd->bhij", q * scale, k,
                          preferred_element_type=jnp.float32)
    allow = jnp.asarray(dense_pattern_mask(pattern, n, n))[None, None]
    allow = _merge_key_pad_mask(pattern, allow, mask)
    dots = jnp.where(allow, dots, max_neg_value(dots.dtype))
    attn = jax.nn.softmax(dots, axis=-1).astype(act_dtype)
    if grouped:
        return grouped_values(attn, v)
    # graftlint: disable=DOT001 (uniform: attn is cast to the activations' dtype above, matching v; parity pinned by tests/attention_refs)
    return jnp.einsum("bhij,bhjd->bhid", attn, v)


class _Core(NamedTuple):
    """What a switched attention core is built from (static, hashable)."""
    pattern: AttnPattern
    act_dtype: Any
    tiles: Tuple[int, int]
    mesh: Optional[KernelMesh]
    heads: int
    dim_head: int

    @property
    def shard_heads(self) -> int:
        """The heads one shard of the plan's mesh holds."""
        return self.heads // (self.mesh.head_ways if self.mesh else 1)

    @property
    def fused(self) -> bool:
        """Whether the core takes ``to_qkv``'s result as one ``[b, n, 3 *
        heads * dim_head]`` array (no axis of it is a mesh axis's to split)
        or as ``[b, n, 3, heads, dim_head]`` (the plan splits the heads)."""
        return self.mesh is None or self.mesh.head_axis is None

    def halves(self, qkv, mask):
        """The kernel's forward and backward for ``to_qkv``'s result ``qkv``
        (:attr:`fused`) (``ops/attention_pallas.py::flash_attention_halves``),
        taking the key padding mask as the model has it; under a plan's mesh
        (:func:`kernel_mesh`) each inside a ``shard_map`` over the batch
        and head axes, whole on the sequence and ``dim_head``."""
        from .attention_pallas import flash_attention_halves

        pattern, mesh = self.pattern, self.mesh
        n = qkv.shape[1]
        fwd, bwd = flash_attention_halves(
            n, self.shard_heads, self.dim_head, qkv.dtype, pattern,
            mask is not None, block_q=self.tiles[0],
            block_k=self.tiles[1], cache_kernels=True)

        def forward(qkv, mask):
            bias = None
            if mask is not None:
                pad = _scope_key_pad(pattern, mask, n)
                bias = jnp.where(pad, 0.0, -1e30).astype(jnp.float32)
            return fwd(qkv, bias)

        def backward(residuals, g):
            return bwd(residuals, g)[0]

        if mesh is None:
            return forward, backward
        from jax.sharding import PartitionSpec as P

        batch, head = mesh.batch_axes, mesh.head_axis
        fused = P(batch, None, None) if self.fused else P(batch, None, None, head, None)  # graftlint: disable=PLAN001 (shard_map arg placement of the activations qkv and dqkv, [b, n, 3 * heads * dh] or [b, n, 3, heads, dh]: batch and heads over the plan's own axes; not a param-tree sharding, so the rule table does not apply)
        wide = P(batch, None, head)  # graftlint: disable=PLAN001 (same: o and its cotangent, [b, n, heads * dh])
        stats = P(batch, head, None, None)  # graftlint: disable=PLAN001 (same: logsumexp [b, heads, blocks, tile])
        per_sample = P(batch, None, None)  # graftlint: disable=PLAN001 (same: the key bias in the key blocks' layout, a sample's)
        key_mask = P(batch, None)  # graftlint: disable=PLAN001 (same: the [b, m] key padding mask)
        # residuals: qkv, the blocked bias, o, logsumexp
        res = (fused, None if mask is None else per_sample, wide, stats)
        wrap = functools.partial(jax.shard_map, mesh=mesh.mesh,
                                 check_vma=False)
        return (wrap(forward,
                     in_specs=(fused, None if mask is None else key_mask),
                     out_specs=(wide, res)),
                wrap(backward, in_specs=(res, wide), out_specs=fused))


def _dense_core(core: _Core, qkv, mask):
    """The dense branch on the kernel's operands: its own transpositions to
    head-major and back, around :func:`dense_attention`."""
    b, n = qkv.shape[:2]
    q, k, v = qkv.reshape(b, n, 3, core.heads, core.dim_head).transpose(
        2, 0, 3, 1, 4)
    out = dense_attention(core.pattern, core.act_dtype, q, k, v, mask)
    return out.transpose(0, 2, 1, 3).reshape(b, n, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _switched_core(core: _Core, qkv, mask):
    """The attention core, ``to_qkv``'s result (:attr:`_Core.fused`) to
    ``to_out``'s input ``[b, n, heads * dim_head]``, as the kernel where
    the program is lowered for a TPU and as :func:`dense_attention` anywhere
    else (``jax.lax.platform_dependent``, forward and backward each: no
    ``jax.default_backend()``, and a compile from a CPU host for a described
    chip gets the kernel).  One VJP around both, so that differentiation
    never goes through the switch: the kernel brings its own backward, and
    the dense branch's is ``jax.vjp`` of the same function on the saved
    ``qkv`` (it recomputes its scores; on the platforms that run it, the
    arithmetic of the dense branch called directly, bit for bit).  Both
    switches are jitted on the static ``core``: the layers of one variant
    agree on it and on their shapes, so a model traces and lowers each
    switch, with both of its branches, once a variant and not once a layer
    (``lucid1024``'s twelve layers: once)."""
    return _switched_fwd(core, qkv, mask)[0]


def _switched_fwd(core: _Core, qkv, mask):
    out, residuals = _forward_switch(core, qkv, mask)
    return out, (mask, residuals)


def _switched_bwd(core: _Core, saved, g):
    return _backward_switch(core, *saved, g), None


@functools.partial(jax.jit, static_argnums=(0,))
def _forward_switch(core: _Core, qkv, mask):
    """``(out, residuals)``; the residuals are the kernel's (``qkv`` first)
    on either branch, the statistics blank on the dense one."""
    forward, _ = core.halves(qkv, mask)
    blank = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         jax.eval_shape(forward, qkv, mask)[1][1:])

    def dense(qkv, mask):
        return _dense_core(core, qkv, mask), (qkv, *blank)

    return jax.lax.platform_dependent(qkv, mask, tpu=forward, default=dense)


@functools.partial(jax.jit, static_argnums=(0,))
def _backward_switch(core: _Core, mask, residuals, g):
    _, backward = core.halves(residuals[0], mask)

    def kernel(mask, residuals, g):
        return backward(residuals, g)

    def dense(mask, residuals, g):
        with prof.scope("attn-scores"):
            return jax.vjp(lambda qkv: _dense_core(core, qkv, mask),
                           residuals[0])[1](g)[0]

    return jax.lax.platform_dependent(mask, residuals, g, tpu=kernel,
                                      default=dense)


_switched_core.defvjp(_switched_fwd, _switched_bwd)


class MultiHeadAttention(nn.Module):
    """One attention layer of any variant (see module docstring).

    Projections follow the reference shapes (`attention.py:27-41`): fused QKV
    without bias, output projection with bias + dropout.  Softmax runs in
    f32 regardless of the activation dtype (bf16-safe).
    """

    pattern: AttnPattern
    dim: int = 256
    heads: int = 8
    dim_head: int = 64
    dropout: float = 0.0
    ring_axis: Optional[str] = None  # sequence-parallel axis (inside shard_map)
    sp_impl: str = "ring"            # 'ring' (k/v rotation) | 'ulysses' (all-to-all)
    aligned_span_decode: bool = True  # serve-path sliced reads as circular
    #   dynamic_slice spans (<=2 per row) instead of the per-key vmapped
    #   gather; bit-identical (same key order/masks), False is the control
    #   no cell has judged yet — part of the traced config
    # ``kv_heads`` keys and values serve ``heads`` queries (1: multi-query);
    # None is one each, the fused ``to_qkv`` kernel.  The dense paths take
    # it (``__call__``, ``decode_step``, the arena's aligned read), in the
    # plain cache layouts; the Pallas, ring and int8 paths do not.
    kv_heads: Optional[int] = None
    use_bias: bool = True             # the output projection's
    # rotate queries and keys by their position (:func:`apply_rope`, the
    # index in the sequence the layer sees); None: no position encoding.
    # Keys enter the cache rotated, so a ring's slot order does not matter.
    # ``rope_dim``: the leading dimensions of a head that turn (None: all);
    # ``rope_yarn``: YaRN's frequencies and attention factor (:class:`YaRN`)
    rope_theta: Optional[float] = None
    rope_dim: Optional[int] = None
    rope_yarn: Optional[YaRN] = None
    # multiply each head's attended values by a sigmoid of the layer's input
    # (``to_gate`` [dim, heads], one scalar a head and position: the
    # head-wise gate of Gated Attention, arXiv:2505.06708), before
    # ``to_out``; scope ``attn-gate``
    head_gate: bool = False
    # RMS-norm the projected queries and keys, each over its projection's
    # whole width (all heads together, before the split into heads and
    # before any rotation), with a float32 gain of that width: ``q_norm``,
    # ``k_norm``.  Keys enter the cache normed.
    qk_norm: bool = False
    norm_eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def setup(self):
        self.drop = nn.Dropout(self.dropout)
        assert not self.qk_norm or self.kv_heads is not None, (
            "normed queries and keys are built with kv_heads (the trunk's "
            "blocks)")
        if self.kv_heads is not None:
            assert self.heads % self.kv_heads == 0, (self.heads, self.kv_heads)
            assert self.ring_axis is None, (
                "grouped keys run the dense attention paths only")
            if self.qk_norm:
                self.q_norm = self.param(
                    "q_norm", nn.initializers.ones,
                    (self.heads * self.dim_head,), jnp.float32)
                self.k_norm = self.param(
                    "k_norm", nn.initializers.ones,
                    (self.kv_heads * self.dim_head,), jnp.float32)
            proj = dict(axis=-1, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype,
                        kernel_init=fan_in_normal(self.dim))
            self.to_q = nn.DenseGeneral(
                features=(self.heads, self.dim_head), name="to_q", **proj)
            self.to_kv = nn.DenseGeneral(
                features=(2, self.kv_heads, self.dim_head), name="to_kv",
                **proj)
            self.to_out = nn.Dense(
                self.dim, use_bias=self.use_bias, dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=fan_in_normal(self.heads * self.dim_head),
                name="to_out")
            if self.head_gate:
                self.to_gate = nn.Dense(
                    self.heads, use_bias=False, dtype=self.dtype,
                    param_dtype=self.param_dtype,
                    kernel_init=fan_in_normal(self.dim), name="to_gate")
            return
        assert not self.head_gate, (
            "the head gate is built with kv_heads (the trunk's blocks)")
        # fused QKV as a [dim, 3, heads, dh] DenseGeneral: the (3,) axis is
        # never sharded, so splitting q/k/v is a free unsharded-axis index,
        # and tensor parallelism shards the heads axis cleanly (a flat
        # [dim, 3*inner] kernel sharded on tp makes the q/k/v split a
        # cross-shard slice that GSPMD can only fully rematerialize)
        self.to_qkv = nn.DenseGeneral(
            features=(3, self.heads, self.dim_head), axis=-1, use_bias=False,
            dtype=self.dtype, name="to_qkv")
        self.to_out = nn.Dense(self.dim, use_bias=self.use_bias,
                               dtype=self.dtype, name="to_out")

    def _width_norm(self, a, gain):
        """RMS norm of a projection ``[b, n, heads, dh]`` over all its
        heads' width at once (``qk_norm``), in the projection's dtype."""
        flat = a.reshape(a.shape[:2] + (-1,))
        return rms_norm(flat, gain, self.norm_eps).astype(a.dtype).reshape(
            a.shape)

    def _qkv(self, x, positions=None):
        """``positions`` (``[n]`` or ``[b, n]``; None: 0..n-1) matter to a
        rotary layer only."""
        with prof.scope("attn-qkv"):
            if self.kv_heads is not None:
                q = self.to_q(x)                            # [b, n, heads, dh]
                if self.qk_norm:
                    q = self._width_norm(q, self.q_norm)
                q = q.transpose(0, 2, 1, 3)                 # [b, heads, n, dh]
                kv = self.to_kv(x)                          # [b, n, 2, g, dh]
                if self.qk_norm:
                    kv = kv.at[:, :, 0].set(
                        self._width_norm(kv[:, :, 0], self.k_norm))
                kv = kv.transpose(2, 0, 3, 1, 4)            # [2, b, g, n, dh]
                k, v = kv[0], kv[1]
                if self.rope_theta is not None:
                    if positions is None:
                        positions = jnp.arange(x.shape[1])
                    q, k = self._rotate(q, positions), self._rotate(
                        k, positions)
                return q, k, v
            assert self.rope_theta is None, (
                "rotary layers are built with kv_heads (the trunk's blocks)")
            qkv = self.to_qkv(x)  # [b, n, 3, heads, dh]
            qkv = qkv.transpose(2, 0, 3, 1, 4)  # [3, b, heads, n, dh]
            return qkv[0], qkv[1], qkv[2]

    def _rotate(self, a, positions):
        """:func:`apply_rope` by this layer's rotation."""
        if self.rope_yarn is None:
            return apply_rope(a, positions, self.rope_theta, self.rope_dim)
        rot = self.rope_dim or self.dim_head
        return apply_rope(a, positions, self.rope_theta, rot,
                          yarn_table(self.rope_theta, rot, self.rope_yarn),
                          self.rope_yarn.scale)

    def _gated(self, out, x):
        """``out`` ``[b, n, heads * dh]`` with each head's part times
        ``sigmoid(x W_gate)`` of its own column (``head_gate``; else as it
        is); ``x`` ``[b, n, dim]`` the layer's input."""
        if not self.head_gate:
            return out
        with prof.scope("attn-gate"):
            b, n, _ = out.shape
            gate = jax.nn.sigmoid(self.to_gate(x).astype(jnp.float32))
            return (out.reshape(b, n, self.heads, self.dim_head)
                    * gate[..., None]).astype(out.dtype).reshape(b, n, -1)

    def _kernel_qkv(self, x, core: _Core):
        """The fused projection as the flash kernel reads it.  Where no
        mesh axis splits the heads, one product onto ``[b, n, 3 * heads *
        dh]`` (``to_qkv``'s kernel seen as ``[dim, 3 * heads * dh]``: the
        weights are reshaped, never the activations): on the TPU a ``[..,
        heads, dh]`` array is tiled over its last two axes, ``dh`` 64 padded
        to the 128 lanes, and XLA can reach the kernel's ``[.., 3 * heads *
        dh]`` from it only through a copy (PERF.md, Findings PR 35)."""
        with prof.scope("attn-qkv"):
            if not core.fused:
                return self.to_qkv(x)                   # [b, n, 3, heads, dh]
            kernel = self.to_qkv.variables["params"]["kernel"]
            x, kernel = nn.dtypes.promote_dtype(x, kernel, dtype=self.dtype)
            # graftlint: disable=DOT001 (uniform: x and the kernel are promoted to self.dtype above, nn.DenseGeneral's own product)
            return jnp.dot(x, kernel.reshape(kernel.shape[0], -1))

    def __call__(self, x, mask=None, deterministic: bool = True,
                 return_kv: bool = False):
        b, n, _ = x.shape
        ring = self.ring_axis is not None and not self.is_initializing()
        core = None if ring else self._kernel_core(
            b, n, self.dtype or x.dtype, x.dtype, cached=return_kv)
        if core is not None:
            # q, k, v stay where the projection wrote them and the kernel
            # writes to_out's input: no transposition on this path
            qkv = self._kernel_qkv(x, core)
            with prof.scope("attn-scores"):
                out = _switched_core(core, qkv, mask)
            return self._project_out(out, x, deterministic)

        q, k, v = self._qkv(x)
        if ring:
            # sequence parallelism: x is this device's sequence shard and we
            # are inside a shard_map over `ring_axis` — exact attention via
            # k/v ring rotation (parallel/ring.py) or head<->sequence
            # all-to-all (parallel/ulysses.py).  During flax init there is
            # no shard_map (the axis name is unbound), so init falls through
            # to dense attention — the param tree is identical either way,
            # which is what lets sp checkpoints stay topology-free.
            assert mask is None, (
                "sequence-parallel attention does not take a key padding "
                "mask; fold it into the token stream instead")
            assert self.sp_impl in ("ring", "ulysses"), (
                f"unknown sp_impl {self.sp_impl!r}")
            if self.sp_impl == "ulysses":
                from ..parallel.ulysses import ulysses_attention as sp_attn
            else:
                from ..parallel.ring import ring_attention as sp_attn
            with prof.scope("attn-scores"):
                out = sp_attn(q, k, v, axis_name=self.ring_axis,
                              pattern=self.pattern,
                              causal=self.pattern.causal)
        else:
            with prof.scope("attn-scores"):
                out = dense_attention(self.pattern, x.dtype, q, k, v, mask,
                                      grouped=self.kv_heads is not None)

        out = self._project_out(out, x, deterministic)
        if return_kv:
            return out, (k, v)
        return out

    def _project_out(self, out, x, deterministic: bool):
        """The head gate, ``to_out`` and its dropout on the core's result:
        ``[b, heads, n, dh]`` of the dense branches, ``[b, n, heads * dh]``
        of the kernel; ``x`` the layer's input."""
        with prof.scope("attn-out"):
            out = out.astype(x.dtype)
            if out.ndim == 4:
                b, _, n, _ = out.shape
                out = out.transpose(0, 2, 1, 3).reshape(b, n, self.heads * self.dim_head)
            out = self.to_out(self._gated(out, x))
            return self.drop(out, deterministic=deterministic)

    def _kernel_core(self, b: int, n: int, dtype, act_dtype,
                     cached: bool = False) -> Optional[_Core]:
        """The switched core of a forward without a cache over ``b``
        sequences of ``n`` positions projected to ``dtype``: the flash
        kernel where the shape allows (:func:`flash_tiles`, on the heads a
        shard of the plan's mesh holds) and the program is lowered for a
        TPU, the dense-masked branch otherwise; None where the dense branch
        stays whatever the platform.  A prefill (``cached``: it returns its
        keys and values, one batch-1 pass a request) keeps it, as does
        flax's shape pass."""
        mesh = _kernel_mesh[-1] if _kernel_mesh else None
        core = None
        # a batch or heads the mesh does not divide: GSPMD's to place, dense
        if not (cached or self.is_initializing() or mesh is not None and (
                b % mesh.batch_ways or self.heads % mesh.head_ways)):
            tiles = flash_tiles(
                n, self.heads // (mesh.head_ways if mesh else 1),
                self.dim_head, dtype, self.pattern, self.kv_heads,
                self.ring_axis)
            if tiles is not None:
                core = _Core(kernel_pattern(self.pattern),
                             jnp.dtype(act_dtype), tiles, mesh, self.heads,
                             self.dim_head)
        if _choices:
            choice = dict(n=n, tiles=core and core.tiles, computed=0,
                          blocks=0)
            if core is not None:
                from .attention_pallas import (BACKWARD_CALLS, HBM_PAD_ROWS,
                                               block_counts)

                skipped, partly, wholly = block_counts(core.pattern, n,
                                                       *core.tiles)
                choice.update(
                    computed=partly + wholly,
                    blocks=skipped + partly + wholly,
                    heads_per_program=lane_block(
                        core.shard_heads, self.dim_head) // self.dim_head,
                    hbm_pad_rows=HBM_PAD_ROWS,
                    backward_calls=BACKWARD_CALLS)
            _choices[-1][self.pattern] = choice
        return core

    def _qkv_decode(self, x, qw, index=None):
        """Decode-path QKV projection: the f32/bf16 kernel, or — under
        ``weights_int8`` — the session-quantized int8 kernel as a direct
        dot multiplicand (ops/quant.py::qdense; per-output-channel scales
        applied to the small product, never to the kernel).  ``index`` is
        the token's position, which a rotary layer turns q and k by."""
        if qw is None:
            return self._qkv(x, None if self.rope_theta is None else
                             jnp.asarray(index, jnp.int32)[..., None])
        with prof.scope("attn-qkv"):
            q8, s = qw["qkv"]                   # [dim, 3, h, dh] int8
            qkv = qdense(x, q8, s).astype(self.dtype)
            qkv = qkv.transpose(2, 0, 3, 1, 4)  # [3, b, heads, n, dh]
            return qkv[0], qkv[1], qkv[2]

    def _out_proj(self, out, qw, x):
        """``to_out`` (under ``weights_int8`` its int8 kernel) on ``out``
        ``[b, n, heads * dh]``, after the head gate of the layer's input
        ``x``."""
        out = self._gated(out, x)
        with prof.scope("attn-out"):
            if qw is None:
                return self.to_out(out)
            q8, s, bias = qw["out"]
            return qdense(out, q8, s, bias).astype(self.dtype)

    def _cache_dots(self, q_scaled, k_sub, k_scale):
        """:meth:`_dots` by this layer's grouping."""
        return self._dots(q_scaled, k_sub, k_scale, self.kv_heads is not None)

    @staticmethod
    def _dots(q_scaled, k_sub, k_scale, grouped: bool):
        """q·k over a cache read of either storage layout.  Plain caches
        keep the calibrated form (multiplicands in the cache dtype, f32
        accumulation); int8 caches keep the int8 tensor as the
        multiplicand and apply the per-head scale to the f32 dots —
        either way no full-precision cache copy ever exists for XLA to
        hoist (contract_check C2/C3).  ``grouped``: ``kv_heads`` is set."""
        if grouped:
            assert k_scale is None, "grouped keys take no int8 cache"
            return grouped_dots(q_scaled, k_sub)
        fold = q_scaled.shape[1] // k_sub.shape[1]
        if fold > 1:
            # head-folded cache [b, h/fold, n, fold*dh]: the same products
            # and f32 sums as below plus the block-diagonal q's exact zeros
            b, h = q_scaled.shape[:2]
            mul = k_sub.dtype if k_scale is None else jnp.bfloat16
            dots = scaled_qdot(
                "bglf,bgnl->bgfn", _block_diag_q(q_scaled.astype(mul), fold),
                k_sub, mul_dtype=mul).reshape(b, h, 1, k_sub.shape[2])
            return dots if k_scale is None else dots * k_scale
        if k_scale is None:
            return jnp.einsum("bhid,bhjd->bhij",
                              q_scaled.astype(k_sub.dtype), k_sub,
                              preferred_element_type=jnp.float32)
        return scaled_qdot("bhid,bhjd->bhij", q_scaled, k_sub, k_scale)

    def decode_step(self, x, cache_k, cache_v, index, mask=None,
                    write_pos=None, qw=None):
        """Single-token decode with KV cache.

        x: [b, 1, dim]; cache_k/v: [b, heads, n_cache, dim_head] — or,
        under ``kv_cache_int8``, the pair ``(values int8, scale f32
        [b, heads, 1, 1])`` (ops/quant.py); `index` is the traced
        absolute position of this token.  Returns (out, new_k, new_v).
        On the dense read path the values may come head-folded
        (:meth:`lane_dense_cache`) and are returned so.

        ``write_pos`` selects the PHASE-ALIGNED mode the serving arena
        (serve/engine.py) runs in: ``index`` may then be a per-sequence
        ``[b]`` vector (continuous batching: every sequence sits at its own
        depth) while all rows write their k/v at the SAME physical cache
        column ``write_pos`` (a traced scalar — the arena clock mod
        n_cache).  The caches then come in the form the arena stores them
        in (:meth:`arena_form`: head-folded ``[b, heads / fold, n, fold *
        dh]``, the sliced layers' position-major ``[b, n, heads / fold,
        fold * dh]``, or plain; :meth:`_stored_form` tells which) and are
        returned so.  Each row's cache is stored rotated by
        ``r = (write_pos - index) mod n_cache``, so the one shared-column
        ``dynamic_update_slice`` IS each row's logically-next position —
        a per-row write position would lower to an XLA scatter, which
        copies the whole cache on backends that don't alias it (measured
        ~2x the decode step on CPU; the arena admit establishes the
        rotation by rolling the prefilled caches once).  Masks translate
        physical -> logical per row; with ``write_pos=None`` (the static
        sampler) behavior is bit-identical to before the serve work.

        ``qw`` (``weights_int8``) carries this layer's session-quantized
        projection kernels ``{"qkv": (int8, scale), "out": (int8, scale,
        bias)}`` — models/dalle.py::quantize_decode_weights builds it
        once per generate/serve session.
        """
        b = x.shape[0]
        q, k, v = self._qkv_decode(x, qw, index)  # [b, h, 1, dh]
        if self.pattern.window:
            return self._decode_step_ring(x, q, k, v, cache_k, cache_v,
                                          index, mask, qw)
        if write_pos is not None:
            return self._decode_step_aligned(x, q, k, v, cache_k, cache_v,
                                             index, write_pos, mask, qw)
        with prof.scope("attn-cache"):
            # a head-folded cache (lane_dense_cache) is told by its shape
            fold = (1 if self.kv_heads is not None else
                    self.heads // cache_values(cache_k).shape[1])
            cache_k = cache_write(cache_k, k, index, CacheForm(fold))
            cache_v = cache_write(cache_v, v, index, CacheForm(fold))
            k_vals, k_scale = split_cache(cache_k)
            v_vals, v_scale = split_cache(cache_v)
        n_k = k_vals.shape[2]
        scale = self.dim_head ** -0.5
        sliced = decode_key_positions(self.pattern, index)
        assert fold == 1 or sliced is None, (
            "only the dense read path takes a head-folded cache")
        if sliced is not None:
            # sliced-cache decode: read only the reachable keys (text +
            # row/col/neighborhood) — the decode loop is HBM-bound on cache
            # reads, and the axial/conv patterns reach ~10% of the cache.
            # Same math as the dense path: softmax over the masked subset
            # equals softmax over the masked full row (excluded entries
            # contribute exp(-inf) = 0).
            positions, valid, contiguous = sliced
            T = self.pattern.text_len
            if contiguous:
                # text prefix (static slice) + one dynamic_slice for the
                # image window — cheaper on TPU than a general gather.  The
                # window start is clamped so the slice stays inside the
                # cache (the padded grid is one longer than the cache, so
                # the last image row's window overruns by one), and the
                # mask is computed from the positions ACTUALLY read — a
                # clamp-shifted window must never be scored under the
                # unshifted positions.  Shifted-in keys below T would
                # duplicate the text segment, hence the img_actual >= T
                # validity.
                m_img = positions.shape[0] - T
                start = jnp.clip(positions[T], 0, n_k - m_img)
                img_actual = start + jnp.arange(m_img)
                positions = jnp.concatenate(
                    [jnp.arange(T), img_actual]).astype(jnp.int32)
                valid = jnp.concatenate(
                    [jnp.ones((T,), bool), img_actual >= T])

                def seg(cache):
                    return jnp.concatenate(
                        [cache[:, :, :T],
                         jax.lax.dynamic_slice_in_dim(cache, start, m_img,
                                                      axis=2)], axis=2)

                with prof.scope("attn-cache"):
                    k_sub, v_sub = seg(k_vals), seg(v_vals)
                safe = positions  # all in [0, n_k) by the clamp above
            else:
                valid = valid & (positions >= 0) & (positions < n_k)
                safe = jnp.clip(positions, 0, n_k - 1)
                with prof.scope("attn-cache"):
                    k_sub = jnp.take(k_vals, safe, axis=2)  # [b, h, m, dh]
                    v_sub = jnp.take(v_vals, safe, axis=2)
            with prof.scope("attn-scores"):
                dots = self._cache_dots(q * scale, k_sub, k_scale)
                row = (_allowed(self.pattern, index, positions, jnp)
                       & valid)[None, None, None, :]
                if mask is not None:
                    pad = _scope_key_pad(self.pattern, mask, n_k)
                    row = row & jnp.take(pad, safe, axis=1)[:, None, None, :]
                dots = jnp.where(row, dots, max_neg_value(dots.dtype))
                attn = jax.nn.softmax(dots, axis=-1)  # f32
                out = self._cache_values(attn, v_sub, v_scale, x.dtype)
                out = out.transpose(0, 2, 1, 3).reshape(
                    b, 1, self.heads * self.dim_head)
            return self._out_proj(out, qw, x), cache_k, cache_v
        with prof.scope("attn-scores"):
            layout = self.pattern.block_layout()
            row = pattern_mask_row(
                self.pattern, index, n_k,
                layout=jnp.asarray(layout) if layout is not None else None,
            )[None, None, None, :]
            row = _merge_key_pad_mask(self.pattern, row, mask)
            # a causal row is False past ``index``: the slots written so far
            out = self._masked_read(
                q * scale, k_vals, k_scale, v_vals, v_scale, row, x.dtype,
                filled=index + 1 if self.pattern.causal else None)
            out = out.transpose(0, 2, 1, 3).reshape(
                b, 1, self.heads * self.dim_head)
        return self._out_proj(out, qw, x), cache_k, cache_v

    def _masked_read(self, q_scaled, k_vals, k_scale, v_vals, v_scale, row,
                     out_dtype, filled=None):
        """The dense read of a decode cache: softmax of ``q_scaled`` against
        every key under the mask ``row`` (broadcastable to ``[b, h, 1,
        slots]``), over the values: ``[b, h, 1, dh]``.

        ``filled`` (a traced scalar: the static sampler's position) says
        that the slots written so far are the prefix ``[0, filled)`` and
        that ``row`` is False past it.  The read then runs over a static
        prefix ``[:bound]`` of keys, values and mask, ``bound >= filled``
        chosen per tick among :func:`read_bounds` by a ``lax.switch`` around
        the read alone (:func:`_read_prefix`, one branch a prefix): the
        decode loop is bound by the cache's bytes, and the slots left out
        were masked to ``exp(...) = 0`` (the same mathematics, no key left
        out).  ``None`` (rows at their own positions, a non-causal row)
        reads the whole cache."""
        bounds = read_bounds(k_vals.shape[2])
        reads = [functools.partial(
            _read_prefix, bound=bound, grouped=self.kv_heads is not None,
            attn_v=self._attn_v, out_dtype=jnp.dtype(out_dtype))
            for bound in bounds]
        operands = (q_scaled, k_vals, k_scale, v_vals, v_scale, row)
        return switch_read_prefix(bounds, reads, operands, filled)

    def _decode_step_ring(self, x, q, k, v, cache_k, cache_v, index, mask,
                          qw=None):
        """Decode against a sliding-window layer's ring cache ``[b, kv
        heads, slots, dh]`` (``AttnPattern.cache_len`` slots): position p
        lives in slot ``p mod slots``, so the step overwrites the key that
        just left the window.  ``index`` is a traced scalar (the static
        sampler: one ``dynamic_update_slice``) or per-row ``[b]`` (the
        serving arena, whose rows sit at different depths: a per-row write,
        and no rotation, since each row's slots follow its own positions).
        Validity comes from the position each slot holds
        (:func:`ring_positions`) through the same ``_allowed`` as every other
        path; keys are stored rotated, so slot order is of no account."""
        b = x.shape[0]
        slots = split_cache(cache_k)[0].shape[2]
        index = jnp.asarray(index, jnp.int32)
        with prof.scope("attn-cache"):
            if index.ndim == 0:
                at = jnp.remainder(index, slots)
                cache_k = cache_write(cache_k, k, at)
                cache_v = cache_write(cache_v, v, at)
            else:
                at = jnp.remainder(index, slots)                   # [b]
                cache_k = cache_write_rows(cache_k, k, at)
                cache_v = cache_write_rows(cache_v, v, at)
            k_vals, k_scale = split_cache(cache_k)
            v_vals, v_scale = split_cache(cache_v)
        with prof.scope("attn-scores"):
            held = ring_positions(index, slots)       # [slots] or [b, slots]
            row = _allowed(self.pattern, index[..., None], held, jnp) & (
                held >= 0)
            row = row[:, None, None, :] if index.ndim else row[None, None,
                                                               None, :]
            if mask is not None:
                assert index.ndim == 0, (
                    "per-row decode takes no key padding mask")
                pad = _scope_key_pad(self.pattern, mask, self.pattern.seq_len)
                row = row & jnp.take(
                    pad, jnp.clip(held, 0, self.pattern.seq_len - 1),
                    axis=1)[:, None, None, :]
            # until the wrap a ring fills from the left (slot = p mod slots)
            out = self._masked_read(
                q * self.dim_head ** -0.5, k_vals, k_scale, v_vals, v_scale,
                row, x.dtype,
                filled=None if index.ndim else jnp.minimum(index + 1, slots))
            out = out.transpose(0, 2, 1, 3).reshape(
                b, 1, self.heads * self.dim_head)
        return self._out_proj(out, qw, x), cache_k, cache_v

    def dense_read_bounds(self) -> Optional[Tuple[int, ...]]:
        """The prefixes the static sampler's :meth:`decode_step` chooses
        among where it reads this layer's whole cache (:func:`read_bounds`
        of its slots); None where it reads slices."""
        if decode_key_positions(self.pattern, jnp.int32(0)) is not None:
            return None
        return read_bounds(self.pattern.cache_len)

    def lane_dense_cache(self, cache):
        """One of this layer's decode caches in the layout the static
        decode scan should carry: head-folded (``quant.fold_cache`` by
        :func:`kv_fold_factor`) where :meth:`decode_step` reads the whole
        cache, as given where it reads slices (they touch a tenth of it).
        The serving arena stores its caches by :meth:`arena_form` instead."""
        if self.kv_heads is not None or decode_key_positions(
                self.pattern, jnp.int32(0)) is not None:
            return cache    # grouped keys' reads have no folded form
        values = cache_values(cache)
        return fold_cache(cache, kv_fold_factor(
            self.heads, self.dim_head, values.dtype))

    def arena_form(self, dtype) -> CacheForm:
        """The form the serving arena STORES this layer's key and value
        caches in between its programs (serve/engine.py), for a cache of
        ``dtype``: the one place that decides it.  ``SlotArena`` allocates
        and installs by it; the aligned step (:meth:`_stored_form`) reads
        and writes the array where it lies.

        Why a form of its own: the chip keeps an array whose minor dimension
        does not fill the 128 lanes in a layout of its choosing, and at
        ``dim_head`` 64 and 128 slots it chose the SLOTS for the lanes, so
        every tick copied every cache into the order its reads want and
        back, and an install rewrote one lane of every tile (PERF.md,
        Findings PR 37).  So, decided from what the trace can see:

        * a rotated cache the static scan's predicate folds
          (:func:`kv_fold_factor`) is stored head-folded, the lanes filled
          and the slot axis major: the one-column write is in place, a
          slot's rows are one run of memory;
        * of those, a layer that reads SLICES along the position axis
          (spans and gathers per row: every pattern but ``full``) is stored
          position-major besides, ``[slots, n, heads / fold, lanes]``: a
          position's keys of every head are one tile, and the axes a gather
          indexes (slot, position) are the major ones, which is the order
          the compiler otherwise copies the whole array into before it
          gathers;
        * what the predicate declines (a 4-byte cache, an odd head count, a
          ``dim_head`` that fills or does not divide the lanes), grouped
          keys (their reads have no folded form) and a sliding-window
          layer's ring (written per row) keep the plain form and the code
          they ran before."""
        if self.kv_heads is not None or self.pattern.window:
            return CacheForm()
        fold = kv_fold_factor(self.heads, self.dim_head, dtype)
        sliced = decode_key_positions(self.pattern, jnp.int32(0)) is not None
        return CacheForm(fold, position_major=fold > 1 and sliced)

    def _stored_form(self, cache) -> CacheForm:
        """The form of a rotated cache as it was handed over: the arena's
        (:meth:`arena_form`), or plain ``[b, heads, n, dh]`` from a caller
        that brings arrays of its own (the tools that trace one tick); told
        apart by the minor dimension."""
        values = cache_values(cache)
        if values.shape[-1] == self.dim_head:
            return CacheForm()
        form = self.arena_form(values.dtype)
        assert values.shape[-1] == form.fold * self.dim_head, values.shape
        return form

    def _decode_step_aligned(self, x, q, k, v, cache_k, cache_v, index,
                             write_pos, mask, qw=None):
        """Phase-aligned decode (see ``decode_step``): per-row logical
        ``index`` [b] (or scalar, broadcast), one shared physical write
        column ``write_pos``.  Row caches are rotated by
        ``r = (write_pos - index) mod n``; attention reads the full cache
        in physical order (sums are order-free) and masks by the LOGICAL
        position of each physical column, which also hides the previous
        resident's stale keys (they map to logical positions the causal
        pattern can't reach).

        Sliced reads through the rotation: with ``aligned_span_decode``
        (default) each row's circular window is read as at most TWO
        contiguous ``dynamic_slice`` spans (text prefix + image window,
        each via ops/quant.py::circular_slice_in_dim, reassembled in
        logical order) — bit-identical to the per-key vmapped gather (the
        False control) because key order, values at valid lanes, and
        masks are all equal; only the HBM access pattern differs.
        Non-contiguous windows (axial_col, dilated conv) keep the
        gather, and so does every window of a cache the arena stores
        position-major (:meth:`arena_form`), where a key is a whole tile."""
        assert mask is None, (
            "phase-aligned decode does not take a key padding mask; serve "
            "requests carry fully-valid prompts")
        b = x.shape[0]
        form = self._stored_form(cache_k)
        n_k = cache_values(cache_k).shape[form.position_axis]
        idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (b,))
        r = jnp.remainder(write_pos - idx, n_k)  # [b] rotation per row
        # the ONE aligned write: every row's next token lands in the same
        # physical column, so this stays a dynamic_update_slice (in-place
        # under donation) instead of a scatter
        with prof.scope("attn-cache"):
            cache_k = cache_write(cache_k, k, write_pos, form)
            cache_v = cache_write(cache_v, v, write_pos, form)
            k_vals, k_scale = split_cache(cache_k)
            v_vals, v_scale = split_cache(cache_v)
        out = self._aligned_read(q, k_vals, k_scale, v_vals, v_scale,
                                 idx, r, x.dtype, form)
        out = out.transpose(0, 2, 1, 3).reshape(b, 1, self.heads * self.dim_head)
        return self._out_proj(out, qw, x), cache_k, cache_v

    def _aligned_read(self, q, k_vals, k_scale, v_vals, v_scale, idx, r,
                      out_dtype, form: CacheForm):
        """The read half of the phase-aligned decode step: one query per
        row (``q`` [b, heads, 1, dh]) at logical position ``idx`` [b]
        against row caches rotated by ``r`` [b], their values stored in
        ``form``.  Returns the attended values [b, heads, 1, dh].  The
        gathers run along the form's position axis, over the array as it
        lies; the folded branches of the dots (:meth:`_dots`,
        :meth:`_attn_v`) tell the fold by the shape, and take what a
        position-major read brings as one group of all the heads
        (``CacheForm.for_dots``)."""
        ax = form.position_axis
        n_k = k_vals.shape[ax]
        scale = self.dim_head ** -0.5
        sliced = decode_key_positions(self.pattern, jnp.int32(0))
        if sliced is not None:
            # batched positions: every row computes its own reachable set
            # (decode_key_positions is shape-static over index, so the
            # vmap is one gathered program, not b programs)
            positions, valid, _ = jax.vmap(
                lambda i: decode_key_positions(self.pattern, i))(idx)
            valid = valid & (positions >= 0) & (positions < n_k)
            T = self.pattern.text_len
            if (sliced[2] and self.aligned_span_decode
                    and not form.position_major):
                # span reads: per row, the text prefix is the circular
                # span [r, r+T) and the image window [pos[T]+r, ...+m)
                # — two block reads instead of T+m key gathers.  Values
                # at out-of-range lanes (the padded grid's one-position
                # overrun) differ from the gather path's clamped reads
                # but are masked to -inf either way, so the softmax
                # consumes identical arrays lane-for-lane.
                m_img = positions.shape[1] - T
                img_start = positions[:, T] + r

                def spans(cache):
                    # the static prefixes are row-invariant: slice them
                    # once for the whole batch, outside the per-row map
                    text_lo = jax.lax.slice_in_dim(cache, 0, T, axis=2)
                    img_lo = jax.lax.slice_in_dim(cache, 0, m_img, axis=2)
                    text = jax.vmap(lambda c, s, lo: circular_slice_in_dim(
                        c, s, T, axis=1, prefix=lo))(cache, r, text_lo)
                    img = jax.vmap(lambda c, s, lo: circular_slice_in_dim(
                        c, s, m_img, axis=1, prefix=lo))(cache, img_start,
                                                         img_lo)
                    return jnp.concatenate([text, img], axis=2)

                with prof.scope("attn-cache"):
                    k_sub, v_sub = spans(k_vals), spans(v_vals)
            else:
                # one key a gather: what a non-contiguous window takes, and
                # every window of a position-major cache, where a key of
                # every head is one whole tile and the spans' second pass
                # (block reads, then a reorder) costs more than it saves
                # (3.51 against 4.50 ms a tick; PERF.md, Findings PR 37)
                safe = jnp.clip(positions, 0, n_k - 1)
                phys = jnp.remainder(safe + r[:, None], n_k)     # [b, m]
                at = jnp.expand_dims(
                    phys, (2, 3) if form.position_major else (1, 3))
                # ``phys`` is in range by the remainder; said so, the chip
                # gathers a position-major cache without a pass to fill
                # what is out of range, and without the two copies that
                # pass drew after it (0.5 ms a pass over a tick's keys)
                mode = "promise_in_bounds" if form.position_major else None
                with prof.scope("attn-cache"):
                    k_sub = form.for_dots(jnp.take_along_axis(
                        k_vals, at, axis=ax, mode=mode))         # [b,h,m,dh]
                    v_sub = form.for_dots(jnp.take_along_axis(
                        v_vals, at, axis=ax, mode=mode))
            with prof.scope("attn-scores"):
                dots = self._cache_dots(q * scale, k_sub, k_scale)
                row = (_allowed(self.pattern, idx[:, None], positions, jnp)
                       & valid)[:, None, None, :]
                dots = jnp.where(row, dots, max_neg_value(dots.dtype))
                attn = jax.nn.softmax(dots, axis=-1)  # f32
                return self._cache_values(attn, v_sub, v_scale, out_dtype)
        with prof.scope("attn-scores"):
            dots = self._cache_dots(q * scale, k_vals, k_scale)
            logical = jnp.remainder(
                jnp.arange(n_k, dtype=jnp.int32)[None, :] - r[:, None],
                n_k)
            layout = self.pattern.block_layout()
            row = _allowed(self.pattern, idx[:, None], logical, jnp,
                           layout=(jnp.asarray(layout)
                                   if layout is not None else None))
            dots = jnp.where(row[:, None, None, :], dots,
                             max_neg_value(dots.dtype))
            attn = jax.nn.softmax(dots, axis=-1)  # f32
            return self._cache_values(attn, v_vals, v_scale, out_dtype)

    def _cache_values(self, attn, v, v_scale, out_dtype):
        """``attn`` (f32) over a cache read's values: :meth:`_attn_v`, or
        the grouped contraction where ``kv_heads`` is set."""
        if self.kv_heads is not None:
            return grouped_values(attn, v).astype(out_dtype)
        return self._attn_v(attn, v, v_scale, out_dtype)

    @staticmethod
    def _attn_v(attn, v, v_scale, out_dtype):
        """Decode-step attn (f32) x cached-v contraction.

        When the cache dtype differs from the activation dtype (the
        kv_cache_bf16 case: f32 activations, bf16 storage) the
        multiplicands stay in the CACHE dtype with f32 ACCUMULATION
        (preferred_element_type) — the MXU's native bf16-in/f32-acc mode.
        Upcasting v to the activation dtype instead would let XLA hoist
        the convert through the cache update and materialize a full f32
        copy of the bf16 cache (measured: it more than doubles the decode
        step's cache bytes, defeating DALLEConfig.kv_cache_bf16 entirely).
        Int8 caches (``v_scale`` present) follow the same discipline one
        level down: the int8 values are the multiplicand, the per-head
        scale multiplies the small f32 product.  When the dtypes already
        match, the contraction keeps the exact form the decode-byte gates
        are calibrated against."""
        fold = attn.shape[1] // v.shape[1]
        if fold > 1:
            # head-folded cache [b, h/fold, n, fold*dh]: each head's attn
            # row meets its group's folded values in one f32-accumulating
            # dot and keeps its own dh lanes of the product
            b, h, _, n = attn.shape
            wide = scaled_qdot(
                "bgfn,bgnl->bgfl", attn.reshape(b, h // fold, fold, n), v,
                mul_dtype=v.dtype if v_scale is None else jnp.bfloat16,
            ).reshape(b, h // fold, fold, fold, -1)
            out = jnp.stack([wide[:, :, f, f] for f in range(fold)],
                            axis=2).reshape(b, h, 1, -1)
            if v_scale is not None:
                out = out * v_scale
            return out.astype(out_dtype)
        if v_scale is not None:
            return scaled_qdot("bhij,bhjd->bhid", attn, v,
                               v_scale).astype(out_dtype)
        if v.dtype == out_dtype:
            # graftlint: disable=DOT001 (uniform: guarded by v.dtype == out_dtype, attn cast to it)
            return jnp.einsum("bhij,bhjd->bhid", attn.astype(out_dtype), v)
        return jnp.einsum("bhij,bhjd->bhid", attn.astype(v.dtype), v,
                          preferred_element_type=jnp.float32
                          ).astype(out_dtype)



@functools.partial(jax.jit, static_argnames=("bound", "grouped", "attn_v",
                                             "out_dtype"))
def _read_prefix(q_scaled, k_vals, k_scale, v_vals, v_scale, row, *,
                 bound: int, grouped: bool, attn_v, out_dtype):
    """One branch of :meth:`MultiHeadAttention._masked_read`: the masked
    softmax read over the first ``bound`` slots.  Jitted, with everything a
    layer brings static (``attn_v`` is its ``_attn_v``), so that the layers
    of one shape share one traced and lowered function per prefix: 12 layers
    x 5 prefixes traced inline cost `lucid1024-generate` 1.6 s of set-up
    (PERF.md, Findings PR 33)."""
    dots = MultiHeadAttention._dots(q_scaled, k_vals[:, :, :bound], k_scale,
                                    grouped)
    dots = jnp.where(row[..., :bound], dots, max_neg_value(dots.dtype))
    attn = jax.nn.softmax(dots, axis=-1)  # f32
    v = v_vals[:, :, :bound]
    if grouped:
        return grouped_values(attn, v).astype(out_dtype)
    return attn_v(attn, v, v_scale, out_dtype)
