"""Multi-head latent attention (the DeepSeek-V2 form, as GLM-4.7-Flash
publishes it): keys and values of every head come from ONE normed latent a
position plus one rotary key shared by all heads, and the decode cache holds
those two and nothing else.

With ``h`` the (normed) input of position ``t``, per head ``i``::

    c_q                = rms(h W_qa)                     # [q_rank]
    [q_nope_i|q_rope_i] = c_q W_qb                       # nope_dim | rope_dim
    [c_raw | k_raw]    = h W_kva                         # kv_rank | rope_dim
    c                  = rms(c_raw)
    k_rope             = rope(k_raw, t)                  # one for all heads
    q_rope_i           = rope(q_rope_i, t)
    [k_nope_i | v_i]   = c W_kvb                         # nope_dim | value_dim
    s_i(t, u)          = (q_nope_i(t).k_nope_i(u) + q_rope_i(t).k_rope(u))
                         / sqrt(nope_dim + rope_dim)     for u <= t
    o_i                = sum_u softmax_u(s_i) v_i(u)
    out                = [o_1 .. o_heads] W_o

A SEQUENCE (a train step, a prefill) runs the equations as written: every
position's ``k_nope`` and ``v`` are decompressed through ``W_kvb`` once.  A
TICK must not (``kv_rank x heads x (nope_dim + value_dim) x 2`` FLOPs a
cached position), so :meth:`LatentAttention.decode_step` reads the latent in
the ABSORBED form, ``W_kvb`` seen as ``W_uk_i [kv_rank, nope_dim]`` and
``W_uv_i [kv_rank, value_dim]``::

    q_lat_i = q_nope_i W_uk_i^T                          # [kv_rank]
    s_i(u)  = (q_lat_i . c(u) + q_rope_i . k_rope(u)) / sqrt(nope + rope)
    o_lat_i = sum_u p_i(u) c(u)                          # [kv_rank]
    o_i     = o_lat_i W_uv_i

the same mathematics with the products re-associated: per cached position a
layer reads ``(kv_rank + rope_dim)`` values and spends ``heads x (2 kv_rank +
rope_dim) x 2`` FLOPs, as batched products of ``heads`` query rows.

The decode state of a layer is the pair ``(c [rows, slots, kv_rank], k_rope
[rows, slots, rope_dim])``: normed, rotated, no head axis, the position axis
second.  It rides where an attention layer's ``(k, v)`` does
(ops/transformer.py::TrunkLatentBlock); the serving arena's read runs
phase-aligned over rotated slots (``write_pos``), the static sampler's is
bounded by the position, in one of two ways:

* **two passes** (the pair as published): three einsums and a softmax in
  plain XLA over a static prefix chosen per tick by a ``lax.switch``
  (:func:`~dalle_pytorch_tpu.ops.attention.read_bounds`, the rule of every
  dense-read cache).  The compiler reads the latent once for the scores and
  once for the weighted sum, with ``[rows, heads, reach]`` float32 scores in
  HBM between.
* **one pass** (the pair FOLDED into one array, :func:`fold_latent`: ``[rows,
  slots / 2, 2 kv_rank + 2 rope_dim]``, a block of 256 positions in 128 rows,
  its two halves side by side as ``[c | c | k_rope | k_rope]``): where the
  program is lowered for a TPU, one Pallas kernel that walks the folded
  latent a block at a time up to the block that holds ``index``, keeps each
  block in VMEM for both products and runs an online softmax
  (ops/latent_attention_pallas.py); anywhere else the two-pass read of the
  unfolded pair (``jax.lax.platform_dependent``, as the train path's flash
  kernel is chosen).  ``decode_codes``' scan carries the fold
  (:meth:`LatentAttention.lane_dense_cache`) where :func:`one_pass_read` says
  the kernel takes the shapes and the call has no key-padding mask.  Why a
  fold: a Pallas operand is row-major, where 64 rotary values a position pad
  to a 128-lane row (a ninth more bytes walked, a quarter more at rest); a
  separate ``[rows, rope_dim, slots]`` array is dense, but the v5e's compiler
  stages any array of its size (71 MB a layer) through its alternate memory
  and back around every use, every tick, as it did around the two-pass read,
  and its tick write is one lane of 64 x 128 rows (0.1 ms a layer as
  transfers of 256 bytes); folded, a row of 1,152 lanes is nine whole tiles,
  the layer's cache is one array too large to stage and one stream for the
  kernel, and the tick's write is one row.

Scopes: ``mla-proj`` (the five products, the two norms, the rotation,
``q_lat`` and ``o_lat W_uv``), ``mla-read`` (scores over the latent, softmax,
the weighted sum of ``c``); the cache write stays under ``attn-cache``.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..obs import prof
from ..utils.helpers import max_neg_value
from . import kept
from .attention import (LANES, AttnPattern, _choices, _scope_key_pad,
                        apply_rope, dense_attention, pattern_mask_row,
                        read_bounds, switch_read_prefix)
from .quant import CacheForm
from .ssm import fan_in_normal, rms_norm

#: positions a block of the one-pass read holds.  The position bounds the
#: walk at the block, so the slack over what the position reaches is half a
#: block a row: at 4,352 slots and a mean reach of 3,201, 4.0% at 256 and 7.5%
#: at 512 (the two-pass read's buckets of 640: 9.1%)
READ_BLOCK = 256

#: bytes of latent a program of the one-pass read moves at most: rows are
#: added to a program until its block is this large (16 rows of 256 positions
#: at GLM-4.7-Flash's widths: 4.7 MB, twice that in VMEM).  A step of the
#: kernel's grid costs about a microsecond of the scalar core's bookkeeping
#: that the transfers do not hide (PERF.md PR 39: 0.766 ms a layer at 8 rows
#: a program, 0.731 at 16, 0.721 at 32); the blocks stay at 256 positions
#: because larger ones lose at the bound what they win at the start
READ_PROGRAM_BYTES = 8 * 2 ** 20


def one_pass_read(slots: int, kv_rank: int, rope_dim: int, dtype) -> bool:
    """Whether the static sampler's read of a latent cache takes one pass
    over a FOLDED cache (:func:`fold_latent`), from static shapes alone: a
    bfloat16 cache, the latent in whole 128-lane tiles, two positions' rotary
    keys filling one (``rope_dim`` 64, as every published latent attention
    has it), slots two whole blocks or more.  A float32 toy, a narrow twin, a
    short or a ragged cache keep the two-pass read."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and kv_rank % LANES == 0
            and 2 * rope_dim == LANES and slots >= 2 * READ_BLOCK
            and slots % READ_BLOCK == 0)


def rows_per_program(rows: int, width: int, dtype) -> int:
    """Cache rows a program of the one-pass read takes: the largest power of
    two that divides ``rows`` and keeps the program's block (:data:`READ_BLOCK`
    positions of ``width`` values a row) within :data:`READ_PROGRAM_BYTES`."""
    per = 1
    while (rows % (2 * per) == 0 and 2 * per * READ_BLOCK * width
           * jnp.dtype(dtype).itemsize <= READ_PROGRAM_BYTES):
        per *= 2
    return per


def fold_latent(cache_c, cache_kr):
    """``(c [b, slots, kv_rank], k_rope [b, slots, rope_dim])`` as one array
    ``[b, slots / 2, 2 kv_rank + 2 rope_dim]``, a block of :data:`READ_BLOCK`
    positions in 128 rows, its two halves side by side: row ``i`` of block
    ``j`` holds positions ``j 256 + i`` and ``j 256 + 128 + i`` as ``[c | c |
    k_rope | k_rope]`` (module docstring)."""
    b, slots, _ = cache_c.shape
    halves = [a.reshape(b, slots // READ_BLOCK, 2, READ_BLOCK // 2, -1)
              for a in (cache_c, cache_kr)]
    return jnp.concatenate([a[:, :, h] for a in halves for h in (0, 1)],
                           axis=-1).reshape(b, slots // 2, -1)


def unfold_latent(folded, kv_rank: int):
    """:func:`fold_latent` undone: ``(c, k_rope)``."""
    b, rows, _ = folded.shape

    def unfold(a):
        a = a.reshape(b, 2 * rows // READ_BLOCK, READ_BLOCK // 2, 2, -1)
        return a.transpose(0, 1, 3, 2, 4).reshape(b, 2 * rows, -1)

    return unfold(folded[..., :2 * kv_rank]), unfold(folded[..., 2 * kv_rank:])


class LatentAttention(nn.Module):
    """One latent-attention layer (module docstring).  ``pattern`` is the
    causal ``full`` pattern of the sequence the layer sees."""

    pattern: AttnPattern
    dim: int
    heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    value_dim: int
    rope_theta: float = 10000.0
    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def setup(self):
        assert self.pattern.variant == "full" and self.pattern.causal, (
            "latent attention is causal and global", self.pattern)
        assert self.rope_dim % 2 == 0, self.rope_dim
        bank = dict(dtype=self.param_dtype)
        h, qk = self.heads, self.nope_dim + self.rope_dim
        self.w_qa = self.param("w_qa", fan_in_normal(self.dim),
                               (self.dim, self.q_rank), **bank)
        self.q_norm = self.param("q_norm", nn.initializers.ones,
                                 (self.q_rank,), jnp.float32)
        self.w_qb = self.param("w_qb", fan_in_normal(self.q_rank),
                               (self.q_rank, h, qk), **bank)
        self.w_kva = self.param("w_kva", fan_in_normal(self.dim),
                                (self.dim, self.kv_rank + self.rope_dim),
                                **bank)
        self.kv_norm = self.param("kv_norm", nn.initializers.ones,
                                  (self.kv_rank,), jnp.float32)
        self.w_kvb = self.param("w_kvb", fan_in_normal(self.kv_rank),
                                (self.kv_rank, h,
                                 self.nope_dim + self.value_dim), **bank)
        self.w_o = self.param("w_o", fan_in_normal(h * self.value_dim),
                              (h, self.value_dim, self.dim), **bank)

    @property
    def scale(self) -> float:
        return (self.nope_dim + self.rope_dim) ** -0.5

    def _project(self, x, positions):
        """``(q_nope [b, h, n, nope], q_rope [b, h, n, rope], c [b, n,
        kv_rank], k_rope [b, n, rope])`` of ``x`` ``[b, n, dim]`` at
        ``positions`` (``[n]`` or ``[b, n]``): queries rotated, the latent
        normed and the shared key rotated, as the cache holds them."""
        x = x.astype(self.dtype)
        # graftlint: disable=DOT001 (uniform: x and the kernel are both cast to self.dtype)
        c_q = rms_norm(jnp.dot(x, self.w_qa.astype(self.dtype)),
                       self.q_norm, self.eps).astype(self.dtype)
        # graftlint: disable=DOT001 (uniform: c_q and the kernel are both self.dtype)
        q = jnp.einsum("bnr,rhe->bhne", c_q, self.w_qb.astype(self.dtype))
        # graftlint: disable=DOT001 (uniform: x and the kernel are both cast to self.dtype)
        ckv = jnp.dot(x, self.w_kva.astype(self.dtype))
        c = rms_norm(ckv[..., :self.kv_rank], self.kv_norm,
                     self.eps).astype(self.dtype)
        k_rope = apply_rope(ckv[:, None, :, self.kv_rank:], positions,
                            self.rope_theta)[:, 0]
        q_rope = apply_rope(q[..., self.nope_dim:], positions,
                            self.rope_theta)
        return q[..., :self.nope_dim], q_rope, c, k_rope

    def _out(self, o):
        """``[b, h, n, value_dim]`` (float32 or the activations' dtype)
        through ``W_o``: ``[b, n, dim]``."""
        # graftlint: disable=DOT001 (uniform: o and the kernel are both cast to self.dtype)
        return jnp.einsum("bhnv,hvd->bnd", o.astype(self.dtype),
                          self.w_o.astype(self.dtype))

    def __call__(self, x, mask=None, return_kv: bool = False):
        """The published form over a sequence ``x`` ``[b, n, dim]``;
        ``return_kv`` hands back what the cache holds of it, ``(c [b, n,
        kv_rank], k_rope [b, n, rope_dim])``."""
        b, n, _ = x.shape
        if _choices and not self.is_initializing():
            # how a tick of this layer would read a cache held in the
            # activations' dtype (the decode records speak for the cache a
            # call is handed: ``decode.kv_reach``)
            one_pass = self.one_pass_read(self.pattern.cache_len, self.dtype)
            _choices[-1][self.pattern] = dict(
                n=n, tiles=None, computed=0, blocks=0, latent=True,
                latent_read="one_pass" if one_pass else "two_pass",
                block=READ_BLOCK if one_pass else 0)
        with prof.scope("mla-proj"):
            q_nope, q_rope, c, k_rope = self._project(x, jnp.arange(n))
            # graftlint: disable=DOT001 (uniform: c and the kernel are both self.dtype)
            kv = jnp.einsum("bnc,che->bhne", c,
                            self.w_kvb.astype(self.dtype))
            k = jnp.concatenate(
                [kv[..., :self.nope_dim],
                 jnp.broadcast_to(k_rope[:, None],
                                  (b, self.heads, n, self.rope_dim))], -1)
            q = jnp.concatenate([q_nope, q_rope], -1)
        with prof.scope("mla-read"):
            o = dense_attention(self.pattern, x.dtype, q, k,
                                kv[..., self.nope_dim:], mask)
        with prof.scope("mla-proj"):
            out = self._out(o).astype(x.dtype)
        return (out, (c, k_rope)) if return_kv else out

    # --- decode ----------------------------------------------------------------

    def init_cache(self, batch: int, slots: int, dtype):
        return (jnp.zeros((batch, slots, self.kv_rank), dtype),
                jnp.zeros((batch, slots, self.rope_dim), dtype))

    def one_pass_read(self, slots: int, dtype) -> bool:
        """:func:`one_pass_read` of this layer's widths."""
        return one_pass_read(slots, self.kv_rank, self.rope_dim, dtype)

    def dense_read_bounds(self, dtype,
                          masked: bool = False) -> Tuple[int, ...]:
        """The prefixes the static sampler's read of this layer's latent, a
        cache of ``dtype``, ends at: the ends of the one-pass read's blocks
        where :func:`one_pass_read` holds and the call is not ``masked``,
        else the buckets the two-pass read chooses among
        (ops/attention.py::read_bounds of its slots)."""
        slots = self.pattern.cache_len
        if masked or not self.one_pass_read(slots, dtype):
            return read_bounds(slots)
        return tuple(range(READ_BLOCK, slots + 1, READ_BLOCK))

    def lane_dense_cache(self, cache_c, cache_kr, masked: bool = False):
        """The pair as ``decode_codes``' scan should carry it: folded into
        one array, ``(fold_latent(c, k_rope), None)``, where the read takes
        one pass (one relayout a call), else as given.  ``masked``: the call
        has a key-padding mask, which the kernel does not take."""
        if masked or not self.one_pass_read(cache_c.shape[1], cache_c.dtype):
            return cache_c, cache_kr
        return _fold(cache_c, cache_kr), None

    def arena_form(self, dtype) -> CacheForm:
        """The form the serving arena stores this layer's pair in: as the
        layer itself carries it, ``[slots, n, kv_rank]`` and ``[slots, n,
        rope_dim]``.  There is no head axis to fold or to put after the
        positions: the slot axis is major, a position's latent is one run of
        ``kv_rank`` lanes, and the phase-aligned step writes one column and
        reads the array where it lies."""
        return CacheForm(position_major=True)

    def decode_step(self, x, cache_c, cache_kr, index, mask=None,
                    write_pos=None):
        """One position ``x`` ``[b, 1, dim]`` at ``index`` against the latent
        cache ``(cache_c [b, slots, kv_rank], cache_kr [b, slots,
        rope_dim])``, in the absorbed form.  Returns ``(out [b, 1, dim],
        cache_c, cache_kr)``.

        ``index`` a traced scalar (the static sampler): the new latent is
        written at slot ``index`` and the read runs over a static prefix of
        the slots that holds ``index + 1`` of them (:func:`_bounded_read`).
        With ``cache_kr`` None, ``cache_c`` is the folded pair
        (:meth:`lane_dense_cache`): the read takes one pass
        (:func:`_one_pass_read`), no ``mask`` and no ``write_pos`` are
        taken, and the fold is returned with None beside it.
        With ``write_pos`` (the serving arena's phase-aligned mode, see
        ops/attention.py::MultiHeadAttention.decode_step) ``index`` may be
        per row ``[b]``: every row writes physical column ``write_pos``, its
        slots rotated by ``(write_pos - index) mod slots``, and the mask
        goes by each column's logical position."""
        b = x.shape[0]
        folded = cache_kr is None
        slots = cache_c.shape[1] * (2 if folded else 1)
        index = jnp.asarray(index, jnp.int32)
        with prof.scope("mla-proj"):
            q_nope, q_rope, c, k_rope = self._project(x, index[..., None])
            w_uk = self.w_kvb[..., :self.nope_dim].astype(self.dtype)
            # graftlint: disable=DOT001 (uniform: q_nope and the kernel are both self.dtype)
            q_lat = jnp.einsum("bhe,che->bhc", q_nope[:, :, 0], w_uk)
            q_lat = (q_lat * self.scale).astype(cache_c.dtype)
            q_rope = (q_rope[:, :, 0] * self.scale).astype(cache_c.dtype)
        if folded:
            assert mask is None and write_pos is None, (
                "the folded latent is the static sampler's, without a mask")
            with prof.scope("attn-cache"):
                cache_c = _write_folded(cache_c, c, k_rope, index)
            with prof.scope("mla-read"):
                o_lat = _one_pass_read(self.pattern, q_lat, q_rope, cache_c,
                                       index)
            return self._absorbed_out(o_lat, x.dtype), cache_c, None
        with prof.scope("attn-cache"):
            at = index if write_pos is None else write_pos
            cache_c = jax.lax.dynamic_update_slice(
                cache_c, c.astype(cache_c.dtype), (0, at, 0))
            cache_kr = jax.lax.dynamic_update_slice(
                cache_kr, k_rope.astype(cache_kr.dtype), (0, at, 0))
        with prof.scope("mla-read"):
            if write_pos is None:
                row = pattern_mask_row(self.pattern, index, slots)[None, :]
                if mask is not None:
                    row = row & _scope_key_pad(self.pattern, mask, slots)
                o_lat = _bounded_read(q_lat, q_rope, cache_c, cache_kr, row,
                                      index + 1)
            else:
                assert mask is None, (
                    "phase-aligned decode takes no key padding mask")
                idx = jnp.broadcast_to(index, (b,))
                r = jnp.remainder(write_pos - idx, slots)
                logical = jnp.remainder(
                    jnp.arange(slots, dtype=jnp.int32)[None] - r[:, None],
                    slots)
                o_lat = _read_latent(q_lat, q_rope, cache_c, cache_kr,
                                     logical <= idx[:, None], bound=slots)
        return self._absorbed_out(o_lat, x.dtype), cache_c, cache_kr

    def _absorbed_out(self, o_lat, dtype):
        """``o_lat [b, h, kv_rank]`` through ``W_uv`` and ``W_o``: ``[b, 1,
        dim]``."""
        with prof.scope("mla-proj"):
            w_uv = self.w_kvb[..., self.nope_dim:].astype(self.dtype)
            # graftlint: disable=DOT001 (uniform: o_lat and the kernel are both cast to self.dtype)
            o = jnp.einsum("bhc,chv->bhv", o_lat.astype(self.dtype), w_uv)
            return self._out(o[:, :, None]).astype(dtype)


# --- the kernels, kept between processes (ops/kept.py) -------------------------

def _kernel(name: str, *args, **statics):
    """``latent_attention_pallas.<name>`` (one result), kept beside the
    compile cache (ops/kept.py::kernel)."""
    return kept.kernel("latent_attention_pallas", name, *args,
                       salt=(READ_BLOCK,), **statics)


@jax.jit
def _fold(cache_c, cache_kr):
    """:func:`fold_latent`; where the program is lowered for a TPU, as a
    kernel that copies block by block (ops/latent_attention_pallas.py): the
    v5e's compiler writes the parts out before it concatenates them, 0.6 GB
    of temporaries at 128 rows x 4,352 slots."""
    def kernel(cache_c, cache_kr):
        # a program of the copy holds a block of the pair and of the fold
        return _kernel("fold_latent_blocks", cache_c, cache_kr,
                       rows_per_program=rows_per_program(
                           cache_c.shape[0], 3 * cache_c.shape[-1],
                           cache_c.dtype))

    return jax.lax.platform_dependent(cache_c, cache_kr, tpu=kernel,
                                      default=fold_latent)


def _write_folded(folded, c, k_rope, index):
    """The tick's write into the folded latent (:func:`fold_latent`), ``c [b,
    1, kv_rank]`` and ``k_rope [b, 1, rope_dim]`` of position ``index``: into
    its half's lanes of row ``(index // 256) 128 + index mod 128``.  The row
    is taken out, takes the new values under a static lane pattern selected
    by the half, and goes back: both moves are dynamic in the row alone, as
    the unfolded write is."""
    b, _, width = folded.shape
    rank, rope = c.shape[-1], k_rope.shape[-1]
    half = READ_BLOCK // 2
    at = (0, index // READ_BLOCK * half + index % half, 0)
    new = jnp.concatenate([c, c, k_rope, k_rope], -1).astype(folded.dtype)
    lane = jnp.arange(width)
    second = jnp.where(lane < 2 * rank, lane // rank,
                       (lane - 2 * rank) // rope)
    row = jax.lax.dynamic_slice(folded, at, (b, 1, width))
    return jax.lax.dynamic_update_slice(
        folded, jnp.where(second == index % READ_BLOCK // half, new, row), at)


@functools.partial(jax.jit, static_argnames=("pattern",))
def _one_pass_read(pattern: AttnPattern, q_lat, q_rope, folded, index):
    """The static sampler's maskless read of the folded latent: the one-pass
    kernel where the program is lowered for a TPU, the two-pass read of the
    unfolded pair anywhere else (``jax.lax.platform_dependent``: no
    ``jax.default_backend()``, and a compile from a CPU host for a described
    chip gets the kernel).  Jitted on its static, so that the layers of one
    shape share one traced switch."""
    def kernel(q_lat, q_rope, folded, index):
        return _kernel("latent_read", q_lat, q_rope, folded, index,
                       rows_per_program=rows_per_program(
                           folded.shape[0], folded.shape[-1] // 2,
                           folded.dtype))

    def plain(q_lat, q_rope, folded, index):
        cache_c, cache_kr = unfold_latent(folded, q_lat.shape[-1])
        row = pattern_mask_row(pattern, index, cache_c.shape[1])[None, :]
        return _bounded_read(q_lat, q_rope, cache_c, cache_kr, row, index + 1)

    return jax.lax.platform_dependent(q_lat, q_rope, folded, index,
                                      tpu=kernel, default=plain)


def _bounded_read(q_lat, q_rope, cache_c, cache_kr, row, filled):
    """The static sampler's two-pass read of the latent: the slots written so
    far are the prefix ``[0, filled)`` and ``row`` is False past it, so the
    read runs over a static prefix chosen per tick among :func:`read_bounds`
    (ops/attention.py::switch_read_prefix, as
    ``MultiHeadAttention._masked_read`` does for keys and values): the slots
    left out were masked to ``exp(...) = 0``."""
    bounds = read_bounds(cache_c.shape[1])
    reads = [functools.partial(_read_latent, bound=bound) for bound in bounds]
    return switch_read_prefix(
        bounds, reads, (q_lat, q_rope, cache_c, cache_kr, row), filled)


@functools.partial(jax.jit, static_argnames=("bound",))
def _read_latent(q_lat, q_rope, cache_c, cache_kr, row, *, bound: int):
    """The absorbed read over the first ``bound`` slots: ``q_lat`` ``[b, h,
    kv_rank]`` and ``q_rope`` ``[b, h, rope_dim]`` (both scaled, in the
    cache's dtype) against ``cache_c`` / ``cache_kr`` under the mask ``row``
    (``[1 or b, slots]``); float32 sums and softmax; ``o_lat`` ``[b, h,
    kv_rank]`` float32.  Jitted with the prefix static, so that the layers of
    one shape share one traced function a prefix."""
    c, kr = cache_c[:, :bound], cache_kr[:, :bound]
    dots = (jnp.einsum("bhc,bnc->bhn", q_lat, c,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bhr,bnr->bhn", q_rope, kr,
                         preferred_element_type=jnp.float32))
    dots = jnp.where(row[:, None, :bound], dots, max_neg_value(dots.dtype))
    attn = jax.nn.softmax(dots, axis=-1)
    return jnp.einsum("bhn,bnc->bhc", attn.astype(c.dtype), c,
                      preferred_element_type=jnp.float32)
