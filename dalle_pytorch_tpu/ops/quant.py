"""Int8 quantization primitives for the decode/serve path.

The decode loop is measured HBM-bandwidth-bound (PERF.md: sliced-KV
2.16x, bf16 cache <=0.6x cache I/O — every win so far cut *bytes*), so
the next multiplicative lever is storing the two dominant byte streams
at one byte per element: the KV caches (``DALLEConfig.kv_cache_int8``)
and the decode-path weight matrices (``DALLEConfig.weights_int8``).
This module is the shared math; the consumers are
``ops/attention.py`` (cache write/read on both decode paths),
``models/dalle.py`` (prefill quantization + one-shot weight
quantization per generate session) and ``serve/engine.py`` (the slot
arena's int8 planes).

Scale-layout contract (DESIGN.md §12):

* **KV caches** — symmetric per-head scales: an int8 values tensor
  ``[b, heads, n, dh]`` rides with an f32 scale plane ``[b, heads, 1,
  1]`` (per *slot* per head in the serve arena, where the batch axis is
  slots).  The scale is computed once at prefill write time over the
  whole prefilled cache; later single-token decode writes quantize with
  that frozen scale and SATURATE (new outliers clip at +-127 rather
  than rescaling — rescaling would rewrite the whole cache and defeat
  the byte cut).  A cache entry is the pair ``(values int8, scale
  f32)`` wherever a plain array was before; every consumer goes through
  :func:`split_cache` so the two layouts share one code path.
* **Weights** — symmetric per-output-channel scales: kernel ``[in,
  ...out]`` quantizes along ``axis=0`` to int8 with an f32 scale of
  shape ``[1, ...out]``.  Quantization happens ONCE per generate/serve
  session (:func:`models.dalle.quantize_decode_weights`); the decode
  program's weight inputs are then int8 + scales, never the f32
  originals.
* **Dots** — the int8 tensor is a *multiplicand*: every contraction
  runs ``int8 x bf16`` (or f32) operands with
  ``preferred_element_type=f32`` accumulation and applies the scale to
  the (small) f32 *product*, so XLA never sees — and can never hoist —
  a dequantized full-cache or full-weight copy (the exact failure mode
  the bf16 cache work caught, pinned by contract_check C3).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

QMAX = 127.0
# floor for the symmetric scale: an all-zero tensor (a fresh arena slot,
# a zero-padded prefill tail) must quantize to zeros, not NaNs
_EPS = 1e-12

CacheLike = Union[jax.Array, Tuple[jax.Array, jax.Array]]


def quantize_symmetric(x, axis, *, eps: float = _EPS):
    """Symmetric int8 quantization of ``x`` over ``axis`` (kept as size-1
    dims in the returned f32 scale): ``x ~= q * scale``."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    s = jnp.maximum(s, eps) / QMAX
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -QMAX, QMAX)
    return q.astype(jnp.int8), s


def quantize_per_head(kv) -> Tuple[jax.Array, jax.Array]:
    """KV-cache quantization: ``[b, heads, n, dh]`` -> (int8 values,
    f32 ``[b, heads, 1, 1]`` scale) — the cache-entry layout the decode
    paths consume (one scale per head per sequence/slot)."""
    return quantize_symmetric(kv, axis=(2, 3))


def quantize_weight(w, axis: int = 0) -> Tuple[jax.Array, jax.Array]:
    """Per-output-channel weight quantization: reduce over the input
    ``axis`` so every output column keeps its own dynamic range."""
    return quantize_symmetric(w, axis=axis)


def split_cache(cache: CacheLike):
    """``(values, scale)`` of a cache entry: the int8 pair as-is, a plain
    f32/bf16 array as ``(array, None)`` — every cache consumer branches
    on the returned scale instead of the config flag, so the two layouts
    cannot drift."""
    if isinstance(cache, (tuple, list)):
        values, scale = cache
        return values, scale
    return cache, None


def cache_values(cache: CacheLike) -> jax.Array:
    return split_cache(cache)[0]


def requantize(new, scale: Optional[jax.Array], dtype):
    """A single decode-step k/v row, prepared for its cache write: cast
    for plain caches, saturating int8 quantization under the entry's
    frozen scale for quantized ones."""
    if scale is None:
        return new.astype(dtype)
    q = jnp.clip(jnp.round(new.astype(jnp.float32) / scale), -QMAX, QMAX)
    return q.astype(jnp.int8)


def fold_heads(kv, fold: int):
    """``[b, heads, n, dh]`` -> ``[b, heads / fold, n, fold * dh]``: each
    group of ``fold`` consecutive heads side by side in the minor dimension
    (head ``g * fold + f`` at lanes ``[f * dh, (f + 1) * dh)`` of group
    ``g``) — the lane-dense decode-cache layout
    (ops/attention.py::kv_fold_factor)."""
    if fold == 1:
        return kv
    b, h, n, dh = kv.shape
    return kv.reshape(b, h // fold, fold, n, dh).transpose(
        0, 1, 3, 2, 4).reshape(b, h // fold, n, fold * dh)


def fold_cache(cache: CacheLike, fold: int) -> CacheLike:
    """:func:`fold_heads` of a cache entry of either layout (the per-head
    scale plane keeps its ``[b, heads, 1, 1]``)."""
    values, scale = split_cache(cache)
    values = fold_heads(values, fold)
    return values if scale is None else (values, scale)


class CacheForm(NamedTuple):
    """The form a decode cache's values are STORED in: ``fold`` heads side
    by side in the minor dimension (:func:`fold_heads`), and the position
    axis before or after the heads.  ``CacheForm()`` is the plain ``[b,
    heads, n, dh]``; ``CacheForm(2)`` the static scan's lane-dense ``[b,
    heads / 2, n, 2 dh]``; ``position_major`` gives ``[b, n, heads / fold,
    fold * dh]``: one position's keys of every head are one run of memory
    (the serving arena's sliced layers,
    ops/attention.py::MultiHeadAttention.arena_form).  A per-head scale
    plane keeps its ``[b, heads, 1, 1]`` under every form."""

    fold: int = 1
    position_major: bool = False

    @property
    def position_axis(self) -> int:
        return 1 if self.position_major else 2

    def shape(self, rows: int, heads: int, slots: int, dim_head: int):
        groups, lanes = heads // self.fold, self.fold * dim_head
        return ((rows, slots, groups, lanes) if self.position_major
                else (rows, groups, slots, lanes))

    def store(self, kv):
        """``[b, heads, n, dh]`` in this form."""
        kv = fold_heads(kv, self.fold)
        return kv.transpose(0, 2, 1, 3) if self.position_major else kv

    def for_dots(self, values):
        """Stored values (a read of ``m`` positions) as the folded dots
        take them (ops/attention.py::MultiHeadAttention._dots, ``_attn_v``:
        ``[b, groups, m, lanes]``, the fold told by the groups).  Position-
        major values are ONE group of every head side by side, ``[b, 1, m,
        heads * dh]``: a reshape, nothing moves."""
        if not self.position_major:
            return values
        b, m = values.shape[:2]
        return values.reshape(b, 1, m, -1)


def cache_write(cache: CacheLike, new, column,
                form: CacheForm = CacheForm()) -> CacheLike:
    """``dynamic_update_slice`` of one decode-step row ``new`` ``[b, heads,
    1, dh]`` into a cache entry of either layout at position ``column`` (the
    scale plane is write-position-invariant).  The entry's values are
    stored in ``form``: the row is quantized per head first, then brought
    into the same form."""
    values, scale = split_cache(cache)
    start = [0, 0, 0, 0]
    start[form.position_axis] = column
    updated = jax.lax.dynamic_update_slice(
        values, form.store(requantize(new, scale, values.dtype)), start)
    if scale is None:
        return updated
    return (updated, scale)


def cache_write_rows(cache: jax.Array, new, columns) -> jax.Array:
    """Write one decode-step row ``new`` ``[b, heads, 1, dh]`` into a plain
    ``[b, heads, n, dh]`` cache at a column of each row's own, ``columns``
    ``[b]`` int32 — the ring's per-row write
    (``ops/attention.py::MultiHeadAttention._decode_step_ring``, whose rows
    sit at different depths; a ring is never int8, a trunk refuses
    ``kv_cache_int8``).  Unlike :func:`cache_write` this lowers to a
    scatter: per-row columns cannot share one dynamic_update_slice."""
    return cache.at[jnp.arange(cache.shape[0]), :, columns, :].set(
        new[:, :, 0].astype(cache.dtype))


def scaled_qdot(einsum_spec: str, a, qb, scale=None, *,
                mul_dtype=jnp.bfloat16):
    """Contraction with an int8 multiplicand: ``a`` (activations /
    attention weights) is cast to ``mul_dtype`` and contracted DIRECTLY
    against the int8 tensor with f32 accumulation; the f32 scale then
    multiplies the (small) product.  Keeping ``qb`` int8 inside the dot
    is the load-bearing property: upcasting it first would hand XLA a
    full-size dequantized copy to hoist out of the decode loop
    (contract_check C3 pins its absence)."""
    out = jnp.einsum(einsum_spec, a.astype(mul_dtype), qb,
                     preferred_element_type=jnp.float32)
    if scale is not None:
        out = out * scale
    return out


def qdense(x, qkernel, scale, bias=None, *, mul_dtype=jnp.bfloat16):
    """Quantized dense layer: ``x @ qkernel * scale (+ bias)`` with the
    int8 kernel as a direct multiplicand (f32 accumulation).  ``scale``
    is the per-output-channel plane ``[1, ...out]``; ``bias`` stays
    f32."""
    spec = {2: "...a,ab->...b", 4: "...a,abcd->...bcd"}[qkernel.ndim]
    out = jnp.einsum(spec, x.astype(mul_dtype), qkernel,
                     preferred_element_type=jnp.float32)
    out = out * scale
    if bias is not None:
        out = out + bias
    return out


def circular_slice_in_dim(values, start, size: int, axis: int = 2,
                          prefix=None):
    """Read a length-``size`` circular span ``[start, start+size) mod n``
    along ``axis`` with ONE dynamic_slice of HBM (plus a static prefix
    slice shared by every caller), then a cheap in-tile reorder.

    The rotated serve caches (ops/attention.py::_decode_step_aligned)
    need per-row circular windows; a general per-row gather touches the
    cache one key-row at a time, while this form reads two CONTIGUOUS
    blocks — ``hi`` at ``min(start, n - size)`` (covers the whole span
    when it doesn't wrap, its tail ``[start, n)`` when it does) and the
    static prefix ``[0, size)`` (covers the wrapped head) — and
    reassembles the span IN LOGICAL ORDER from the 2*size-element tile.
    The reorder is a take over the extracted tile, not the cache, so
    HBM sees only the block reads.  (The wrapped head has length
    ``start + size - n < size``, so it always fits the static prefix —
    any ``size <= n`` works.)

    ``prefix`` lets a vmapped caller hoist the row-invariant static
    prefix ``values[..., :size, :]`` OUT of the per-row map — it is read
    once for the whole batch, so the per-row dynamic work is exactly one
    span."""
    n = values.shape[axis]
    assert size <= n, f"span of {size} exceeds the cache length {n}"
    start = jnp.remainder(start, n)
    lo_bound = jnp.minimum(start, n - size)
    hi = jax.lax.dynamic_slice_in_dim(values, lo_bound, size, axis=axis)
    lo = (prefix if prefix is not None
          else jax.lax.slice_in_dim(values, 0, size, axis=axis))
    tile = jnp.concatenate([hi, lo], axis=axis)
    pos = start + jnp.arange(size)
    idx = jnp.where(pos < n, pos - lo_bound, size + pos - n)
    return jnp.take(tile, idx, axis=axis)
