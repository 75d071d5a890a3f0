"""Pallas TPU flash attention with block-sparse pattern skipping.

This is the framework's flagship custom kernel, replacing the reference's
DeepSpeed ``SparseSelfAttention`` CUDA/Triton block-sparse kernel
(`/root/reference/dalle_pytorch/attention.py:284-342`) — and, beyond parity,
accelerating *every* attention variant (full / axial_row / axial_col /
conv_like / sparse), since they are all boolean patterns over absolute
positions (see ``ops/attention.py``).

Design (TPU-first):
* **flash**: online-softmax accumulation over key blocks — the [n, n]
  attention matrix is never materialized in HBM, forward or backward.  At
  the reference's CUB geometry (b16 h8 n1104) the dense f32 scores alone are
  624 MB a layer; this kernel keeps them in VMEM tiles.
* **three kinds of block**: a static table derived from the pattern
  predicate marks each (query block, key block) *skipped* (no pair allowed:
  no work at all), *wholly allowed* (no mask tile, no select) or *partly
  allowed* (one ``[block_q, block_k]`` mask tile, fetched by its number from
  a table of the pattern's distinct tiles that stays in VMEM for the whole
  call) — axial patterns touch O(n·sqrt(n)) score entries, matching the
  asymptotics DeepSpeed's kernel gave the reference.
* **MXU operands in the inputs' dtype**: ``q``, ``k``, ``v``, ``do`` and the
  probabilities enter every ``dot_general`` in ``q.dtype`` with float32
  accumulation — with bf16 inputs the rounding the dense path has (it casts
  the probabilities to the activation dtype before ``attn.v``), with f32
  inputs nothing is rounded.  Running max, sum, ``exp``, logsumexp, delta
  and the accumulators are float32 whatever the inputs.
* **keys/values stay VMEM-resident** per (batch*head) program: at n≈1104,
  dh=64 they fit comfortably, so the inner loop does no HBM traffic at all.
* full custom VJP: flash backward (dq, then dk/dv on transposed tiles so
  that every product is in the MXU's native form) with the same block
  table, using the saved logsumexp rows; both backward kernels are traced
  under the forward's ``graftprof:attn-scores`` scope.

All shapes are padded to block multiples with masked-off (never-attended)
positions.
"""
from __future__ import annotations

import functools
import hashlib
import os
from pathlib import Path
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import prof
from .attention import (LANES, AttnPattern, dense_pattern_mask,
                        kernel_pattern)

NEG_INF = -1e30  # finite mask value: keeps (s - lse) well-defined everywhere

#: block kinds in :class:`PatternBlocks`' table; a value >= ``PARTIAL`` is a
#: partly allowed block whose mask is tile ``value - PARTIAL``
SKIP, WHOLE, PARTIAL = 0, 1, 2

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


class PatternBlocks(NamedTuple):
    """A pattern at one length and tiling, as the kernels take it."""
    table: np.ndarray   # [NQ, NK] int32: SKIP, WHOLE or PARTIAL + tile number
    tiles: np.ndarray   # [T, block_q, block_k] int8: the distinct mask tiles
    counts: Tuple[int, int, int]   # blocks skipped, partly, wholly allowed
    guard: np.ndarray   # [NQ, NK] bool: some query of the block has met no
    #                     allowed key yet but will meet one further on


@functools.lru_cache(maxsize=8)
def _padded_mask(pattern: AttnPattern, n: int, n_pad: int) -> np.ndarray:
    """The pattern's mask with padded queries and keys allowed nothing
    (read-only: the tilings of one pattern share it)."""
    mask = np.zeros((n_pad, n_pad), dtype=bool)
    mask[:n, :n] = dense_pattern_mask(pattern, n, n)
    mask.setflags(write=False)
    return mask


@functools.lru_cache(maxsize=64)
def _pattern_blocks(pattern: AttnPattern, n: int, n_pad: int,
                    block_q: int, block_k: int,
                    all_partial: bool = False) -> PatternBlocks:
    """Static (trace-time) block table and mask tiles for a pattern at
    length ``n`` padded to ``n_pad``.  Padded queries and keys are allowed
    nothing.  ``all_partial`` (a test hook) treats wholly allowed blocks as
    partly allowed: the result must not change."""
    mask = _padded_mask(pattern, n, n_pad)
    nq, nk = n_pad // block_q, n_pad // block_k
    blocks = mask.reshape(nq, block_q, nk, block_k).transpose(0, 2, 1, 3)
    some, every = blocks.any((2, 3)), blocks.all((2, 3))
    if all_partial:
        every = np.zeros_like(every)
    table = np.where(every, WHOLE, SKIP).astype(np.int32)
    tiles, seen = [], {}
    for qb, kb in np.argwhere(some & ~every):
        tile = blocks[qb, kb]
        number = seen.setdefault(tile.tobytes(), len(tiles))
        if number == len(tiles):
            tiles.append(tile)
        table[qb, kb] = PARTIAL + number
    if not tiles:   # the kernels take a table of at least one tile
        tiles.append(np.zeros((block_q, block_k), bool))
    partial = int((table >= PARTIAL).sum())
    whole = int((table == WHOLE).sum())
    # keys each query has met after each key block, and in the end
    met = mask.reshape(n_pad, nk, block_k).any(2).cumsum(1) > 0
    waiting = ~met & met[:, -1:]
    return PatternBlocks(table, np.stack(tiles).astype(np.int8),
                         (nq * nk - partial - whole, partial, whole),
                         waiting.reshape(nq, block_q, nk).any(1))


def block_counts(pattern: AttnPattern, n: int, block_q: int,
                 block_k: int) -> Tuple[int, int, int]:
    """(skipped, partly allowed, wholly allowed) blocks of one layer."""
    n_pad = _padded_len(n, block_q, block_k)
    return _pattern_blocks(pattern, n, n_pad, block_q, block_k).counts


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
#
# One program holds one (batch, head)'s whole padded sequence, and every loop
# over blocks is unrolled at trace time from the static table: no branch, no
# dynamic slice, no carried loop state, so the scheduler overlaps one block's
# MXU passes with the next one's VPU work.  On the chip (PERF.md, Findings
# PR 28) this form runs the CUB shape in 2.1-2.8 ms a layer where the same
# arithmetic under ``fori_loop`` + ``cond`` over a (batch*head, block) grid
# took 5.5-9.5 ms and the dense branch 7.2 ms.  ``q`` arrives scaled.


def _scores(a, b_blk, bias, tile):
    """f32 scores ``a @ b_blk.T`` of one block with the key bias and the
    mask tile where the block has them."""
    s = jax.lax.dot_general(a, b_blk, _NT,
                            preferred_element_type=jnp.float32)
    if bias is not None:
        s = s + bias
    if tile is not None:
        s = jnp.where(tile != 0, s, NEG_INF)
    return s


def _fwd_block(q, k_blk, v_blk, bias, tile, carry, *, guard: bool):
    """Online softmax over one key block: ``carry`` is (running max, sum,
    accumulator), None at a query block's first key block."""
    s = _scores(q, k_blk, bias, tile)
    m_new = s.max(axis=1, keepdims=True)
    if carry is not None:
        m_new = jnp.maximum(carry[0], m_new)
    p = jnp.exp(s - m_new)
    if guard:
        # rows with every key so far masked have s == m_new == NEG_INF,
        # where exp(s - m_new) = 1 would leak uniform attention onto
        # disallowed keys — force those terms to 0
        p = jnp.where(s <= NEG_INF * 0.5, 0.0, p)
    l_new = p.sum(axis=1, keepdims=True)
    pv = jax.lax.dot_general(p.astype(v_blk.dtype), v_blk, _NN,
                             preferred_element_type=jnp.float32)
    if carry is None:
        return m_new, l_new, pv
    m, l, acc = carry
    alpha = jnp.exp(m - m_new)
    return m_new, l * alpha + l_new, acc * alpha + pv


def _dq_block(q, do, lse, delta, k_blk, v_blk, bias, tile, dq):
    s = _scores(q, k_blk, bias, tile)
    p = jnp.exp(s - lse)                      # [bq, bk]
    dp = jax.lax.dot_general(do, v_blk, _NT,
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    return dq + jax.lax.dot_general(ds.astype(k_blk.dtype), k_blk, _NN,
                                    preferred_element_type=jnp.float32)


def _dkv_block(k_blk, v_blk, q, do, lse, delta, bias, tile, dk, dv):
    """On *transposed* tiles ``[bk, bq]``: the scores as ``k @ q.T``, so
    that logsumexp and delta (``[1, bq]``, stored along the lanes)
    broadcast down the sublanes and all five products are ``a @ b`` or ``a
    @ b.T`` — no transposed operand, no lane-to-sublane move.  ``bias`` is
    a column, ``tile`` a transposed mask tile."""
    s_t = _scores(k_blk, q, bias, tile)
    p_t = jnp.exp(s_t - lse)                                 # [bk, bq]
    dv = dv + jax.lax.dot_general(p_t.astype(do.dtype), do, _NN,
                                  preferred_element_type=jnp.float32)
    dp_t = jax.lax.dot_general(v_blk, do, _NT,
                               preferred_element_type=jnp.float32)
    ds_t = p_t * (dp_t - delta)
    dk = dk + jax.lax.dot_general(ds_t.astype(q.dtype), q, _NN,
                                  preferred_element_type=jnp.float32)
    return dk, dv


def _computed(blocks: PatternBlocks, qb=None, kb=None):
    """``(index, mask tile number or None)`` of the computed blocks of one
    query block's row or one key block's column of the table."""
    codes = blocks.table[qb] if kb is None else blocks.table[:, kb]
    return [(int(i), int(c) - PARTIAL if c >= PARTIAL else None)
            for i, c in enumerate(codes) if c != SKIP]


def _fwd_kernel(q_ref, k_ref, v_ref, tiles_ref, *rest, blocks: PatternBlocks,
                block_q: int, block_k: int, has_bias: bool):
    bias_ref = rest[0] if has_bias else None
    o_ref, lse_ref = rest[-2:]
    for qb in range(blocks.table.shape[0]):
        rows = slice(qb * block_q, (qb + 1) * block_q)
        q = q_ref[0, rows, :]
        carry = None
        for kb, number in _computed(blocks, qb=qb):
            cols = slice(kb * block_k, (kb + 1) * block_k)
            carry = _fwd_block(
                q, k_ref[0, cols, :], v_ref[0, cols, :],
                bias_ref[0, :, cols] if has_bias else None,
                None if number is None else tiles_ref[number], carry,
                guard=has_bias or bool(blocks.guard[qb, kb]))
        if carry is None:       # a block row of padding only
            o_ref[0, rows, :] = jnp.zeros((block_q, q.shape[1]), o_ref.dtype)
            lse_ref[0, 0, rows] = jnp.full((block_q,), jnp.inf, jnp.float32)
            continue
        m, l, acc = carry
        # rows with no attendable key (padding, a sample whose every key is
        # dropped) give zeros, and lse = +inf so that the backward's
        # exp(s - lse) is exactly 0
        dead = m <= NEG_INF * 0.5
        o_ref[0, rows, :] = jnp.where(dead, 0.0, acc / l).astype(o_ref.dtype)
        lse_ref[0, 0, rows] = jnp.where(dead, jnp.inf, m + jnp.log(l))[:, 0]


def _bwd_dq_kernel(q_ref, k_ref, v_ref, tiles_ref, *rest,
                   blocks: PatternBlocks, block_q: int, block_k: int,
                   has_bias: bool):
    bias_ref = rest[0] if has_bias else None
    do_ref, lse_ref, delta_ref, dq_ref = rest[-4:]
    for qb in range(blocks.table.shape[0]):
        rows = slice(qb * block_q, (qb + 1) * block_q)
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        lse = lse_ref[0, 0, rows][:, None]      # [bq, 1]
        delta = delta_ref[0, 0, rows][:, None]
        dq = jnp.zeros(q.shape, jnp.float32)
        for kb, number in _computed(blocks, qb=qb):
            cols = slice(kb * block_k, (kb + 1) * block_k)
            dq = _dq_block(
                q, do, lse, delta, k_ref[0, cols, :], v_ref[0, cols, :],
                bias_ref[0, :, cols] if has_bias else None,
                None if number is None else tiles_ref[number], dq)
        dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, tiles_ref, *rest,
                    blocks: PatternBlocks, block_q: int, block_k: int,
                    has_bias: bool):
    """dk and dv, a key block at a time (:func:`_dkv_block`);
    ``tiles_ref`` holds the mask tiles transposed."""
    bias_ref = rest[0] if has_bias else None
    do_ref, lse_ref, delta_ref, dk_ref, dv_ref = rest[-5:]
    for kb in range(blocks.table.shape[1]):
        cols = slice(kb * block_k, (kb + 1) * block_k)
        k_blk, v_blk = k_ref[0, cols, :], v_ref[0, cols, :]
        # the bias over this key block, as a column
        bias = bias_ref[0, 0, cols][:, None] if has_bias else None
        dk = jnp.zeros(k_blk.shape, jnp.float32)
        dv = jnp.zeros(v_blk.shape, jnp.float32)
        for qb, number in _computed(blocks, kb=kb):
            rows = slice(qb * block_q, (qb + 1) * block_q)
            dk, dv = _dkv_block(
                k_blk, v_blk, q_ref[0, rows, :], do_ref[0, rows, :],
                lse_ref[0, :, rows], delta_ref[0, :, rows], bias,
                None if number is None else tiles_ref[number], dk, dv)
        dk_ref[0, cols, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, cols, :] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------

#: what Mosaic may take of the v5e's 128 MiB of VMEM for one program (its
#: default, 16 MiB, is too little for the unrolled loops at n = 4176)
VMEM_LIMIT_BYTES = 96 * 1024 * 1024


def _pallas(kernel, blocks: PatternBlocks, tiles, bias, heads: int, q, k, v,
            extra, n_out: int, stats_out: int, *, block_q, block_k,
            interpret):
    """One of the three kernels over a ``(batch*head,)`` grid: q, k, v, every
    ``extra`` operand and each of the ``n_out`` outputs one whole sequence a
    program (``extra``'s last two and the ``stats_out`` last outputs: one
    row of float32 statistics), the mask tiles one block for the whole
    call, the key bias one row a sample.  Every program writes its own
    (batch, head)'s outputs exactly once, so the grid axis is parallel.

    Pallas is imported here, not with the module: a process that finds its
    kernels in the cache (:func:`_kernels`) never pays that second."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, n_pad, dh = q.shape

    def spec(shape, index_map):
        return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)

    seq = spec((1, n_pad, dh), lambda ib: (ib, 0, 0))
    stat = spec((1, 1, n_pad), lambda ib: (ib, 0, 0))   # along the lanes
    in_specs = [seq] * 3 + [spec(tiles.shape, lambda ib: (0, 0, 0))]
    args = [q, k, v, tiles]
    if bias is not None:
        in_specs.append(spec((1, 1, n_pad),
                             lambda ib: (jax.lax.div(ib, heads), 0, 0)))
        args.append(bias)
    if extra:
        in_specs += [seq] * (len(extra) - 2) + [stat] * 2
    out_specs = [seq] * (n_out - stats_out) + [stat] * stats_out
    out_shape = (
        [jax.ShapeDtypeStruct((bh, n_pad, dh), q.dtype)] * (n_out - stats_out)
        + [jax.ShapeDtypeStruct((bh, 1, n_pad), jnp.float32)] * stats_out)
    return pl.pallas_call(
        functools.partial(kernel, blocks=blocks, block_q=block_q,
                          block_k=block_k, has_bias=bias is not None),
        grid=(bh,), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret)(*args, *extra)


class _Static(NamedTuple):
    """The static half of a call: what the kernels are built from.  Layers
    that agree on it (and on their shapes) share one traced kernel: the
    calls below are jitted on it, so a model traces each kernel's unrolled
    body once a pattern, not once a layer and differentiation pass."""
    pattern: AttnPattern
    n: int              # the sequence's own length, before padding
    block_q: int
    block_k: int
    interpret: bool
    all_partial: bool
    cache_kernels: bool

    @property
    def blocks(self) -> PatternBlocks:
        return _pattern_blocks(
            self.pattern, self.n,
            _padded_len(self.n, self.block_q, self.block_k), self.block_q,
            self.block_k, self.all_partial)

    @property
    def tiling(self) -> dict:
        return dict(block_q=self.block_q, block_k=self.block_k,
                    interpret=self.interpret)


@functools.partial(jax.jit, static_argnums=(0,))
def _call_fwd(static: _Static, q, k, v, bias):
    blocks = static.blocks
    heads = 1 if bias is None else q.shape[0] // bias.shape[0]
    return _pallas(_fwd_kernel, blocks, jnp.asarray(blocks.tiles), bias,
                   heads, q, k, v, [], 2, 1, **static.tiling)


@functools.partial(jax.jit, static_argnums=(0,))
def _call_bwd(static: _Static, q, k, v, bias, do, lse, delta):
    blocks = static.blocks
    heads = 1 if bias is None else q.shape[0] // bias.shape[0]
    extra = [do, lse, delta]
    dq, = _pallas(_bwd_dq_kernel, blocks, jnp.asarray(blocks.tiles), bias,
                  heads, q, k, v, extra, 1, 0, **static.tiling)
    dk, dv = _pallas(
        _bwd_dkv_kernel, blocks,
        jnp.asarray(blocks.tiles.transpose(0, 2, 1)), bias, heads, q, k, v,
        extra, 2, 0, **static.tiling)
    return dq, dk, dv


# --- the kernels, kept between processes -------------------------------------
#
# Tracing a kernel's unrolled body and lowering it to Mosaic's MLIR is
# Python: with the import of Pallas, 2.5-3 s a process for cub200's four
# patterns, every run, where the XLA executable around them loads from the
# compile cache in a fraction of that (PERF.md, Findings PR 28).  So the two
# calls above are kept beside that cache as ``jax.export`` artefacts, keyed by
# what they are built from; a later process reads the bytes and binds one
# ``call_exported`` a call, without importing Pallas.

_SOURCE_DIGEST = hashlib.sha256(Path(__file__).read_bytes()).hexdigest()
_CALLS = {"fwd": _call_fwd, "bwd": _call_bwd}


@functools.lru_cache(maxsize=None)
def _exported(cache_dir: str, name: str, static: _Static, avals):
    """``_call_fwd`` / ``_call_bwd`` for ``static`` and the arguments'
    ``avals`` as a ``jax.export.Exported`` for the TPU: read from
    ``cache_dir``, or traced, lowered and written there."""
    from jax import export

    import jaxlib

    key = hashlib.sha256(repr((
        name, static, avals, jax.__version__, jaxlib.__version__,
        _SOURCE_DIGEST)).encode()).hexdigest()
    path = Path(cache_dir) / f"flash-{name}-{key[:40]}.jaxexport"
    try:
        return export.deserialize(bytearray(path.read_bytes()))
    except OSError:
        pass
    structs = [None if a is None else jax.ShapeDtypeStruct(*a) for a in avals]
    exported = export.export(
        jax.jit(functools.partial(_CALLS[name], static)),
        platforms=("tpu",))(*structs)
    tmp = path.with_suffix(f".{os.getpid()}.tmp")
    try:    # whole or not at all: another process may be writing the same
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp.write_bytes(exported.serialize())
        tmp.replace(path)
    except OSError:
        pass    # a cache that cannot be written is a cache that misses
    return exported


def _kernels(name: str, static: _Static, *args):
    """``_call_fwd`` / ``_call_bwd``: through the kept artefact where the
    call may be kept (``cache_kernels``: the compiled kernel for the TPU,
    never an interpreter's) and the program keeps a compile cache."""
    cache_dir = jax.config.jax_compilation_cache_dir
    if static.cache_kernels and not static.interpret and cache_dir:
        avals = tuple(None if a is None else (a.shape, a.dtype) for a in args)
        return _exported(cache_dir, name, static, avals).call(*args)
    return _CALLS[name](static, *args)


# ---------------------------------------------------------------------------
# custom-vjp public op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_attention(static: _Static, q, k, v, bias):
    out, _ = _flash_fwd(static, q, k, v, bias)
    return out


def _padded_len(n: int, block_q: int, block_k: int) -> int:
    """The kernel's actual padded sequence length — shared with the VMEM
    guard so its estimate can never diverge from what _prepare allocates."""
    n_pad = _round_up(n, max(block_q, block_k))
    n_pad = _round_up(n_pad, block_q)
    return _round_up(n_pad, block_k)


def _flash_fwd(static: _Static, q, k, v, bias):
    b, h, n, dh = q.shape
    n_pad = _padded_len(n, static.block_q, static.block_k)
    bias_p = None if bias is None else jnp.pad(
        bias.astype(jnp.float32), ((0, 0), (0, n_pad - n)))[:, None, :]

    def flat_pad(t):
        t = t.reshape(b * h, n, dh)
        return jnp.pad(t, ((0, 0), (0, n_pad - n), (0, 0)))

    # the dense branch's own form: q * scale in the input dtype, then the
    # dot; the kernels take q scaled, forward and backward
    qf, kf, vf = flat_pad(q * dh ** -0.5), flat_pad(k), flat_pad(v)
    o, lse = _kernels("fwd", static, qf, kf, vf, bias_p)
    out = o[:, :n, :].reshape(b, h, n, dh)
    return out, (qf, kf, vf, bias_p, o, lse)


def _flash_bwd(static: _Static, residuals, g):
    # a custom VJP's backward is traced outside the forward's name scope:
    # put the backward kernels (and delta) under the scope the forward's
    # callers give it, or a trace reads the forward alone
    with prof.scope("attn-scores"):
        qf, kf, vf, bias_p, o, lse = residuals
        bh, n_pad, dh = qf.shape
        b, h, n = g.shape[:3]
        do = jnp.pad(g.reshape(bh, n, dh).astype(qf.dtype),
                     ((0, 0), (0, n_pad - n), (0, 0)))
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)[:, None, :]  # [bh, 1, n_pad]
        dq, dk, dv = _kernels("bwd", static, qf, kf, vf, bias_p, do, lse,
                              delta)

        def unflat(t):
            return t[:, :n, :].reshape(b, h, n, dh)

        # the key-padding bias is not trainable
        dbias = None if bias_p is None else jnp.zeros((b, n), jnp.float32)
        return unflat(dq) * dh ** -0.5, unflat(dk), unflat(dv), dbias


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


#: What the estimate below may come to: the limit the kernels give Mosaic.
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """Bytes of a ``[rows, cols]`` array in VMEM: the minor dimension padded
    to the 128 lanes, the other to the dtype's sublane packing."""
    return (_round_up(rows, 8 * (4 // itemsize)) * _round_up(cols, LANES)
            * itemsize)


def _vmem_resident_bytes(n_pad: int, dh: int, itemsize: int, block_q: int,
                         block_k: int, tiles: int = 1,
                         has_bias: bool = False) -> int:
    """VMEM one program holds in the hungriest of the three kernels (dk/dv:
    q, k, v, do in and dk, dv out, one whole sequence each, logsumexp and
    delta), in VMEM's padded layouts: every operand that moves with the grid
    twice (Pallas double-buffers them), the mask tiles once (their block
    never changes), and one float32 ``[block_q, block_k]`` tile of
    intermediates.  Held against the compiler's own answers by
    ``tests/test_tpu_compile.py``: at n = 4176 it takes every tiling up to
    2304 x 2304 and refuses the backward of one 4224 x 4224 block, where
    this reads 98 MiB and Mosaic 113."""
    seq = _tile_bytes(n_pad, dh, itemsize)
    stat = _tile_bytes(1, n_pad, 4)
    moving = 6 * seq + (3 if has_bias else 2) * stat
    return (2 * moving + tiles * _tile_bytes(block_q, block_k, 1)
            + _tile_bytes(block_q, block_k, 4))


def _checked_static(q, pattern: AttnPattern, has_bias: bool, block_q: int,
                    block_k: int, interpret: bool, all_partial: bool,
                    cache_kernels: bool) -> _Static:
    """The static description of a call on ``q``-shaped arguments, or a
    ValueError where the TPU's compiler would refuse it."""
    n, dh = q.shape[2:]
    if not interpret:
        if block_q % LANES or block_k % LANES:
            # Mosaic requires the last block dim be a multiple of the
            # 128-lane width (the lse output [b, h, n] blocks the q axis in
            # its last dim; k blocks stream through the same lanes) —
            # sub-128 tiles fail deep inside lowering, so reject them at
            # the API edge.  Seen on the chip: manual session 2026-08-02.
            raise ValueError(
                f"block_q/block_k must be multiples of the TPU lane width "
                f"128 (got {block_q}/{block_k})")
        n_pad = _padded_len(n, block_q, block_k)
        estimate = functools.partial(
            _vmem_resident_bytes, n_pad, dh, q.dtype.itemsize, block_q,
            block_k, has_bias=has_bias)
        tiles, resident = 1, estimate(1)
        if resident <= VMEM_BUDGET_BYTES:   # else: no need to draw the mask
            tiles = _pattern_blocks(pattern, n, n_pad, block_q, block_k,
                                    all_partial).tiles.shape[0]
            resident = estimate(tiles)
        if resident > VMEM_BUDGET_BYTES:
            raise ValueError(
                f"flash_pattern_attention keeps one (batch, head)'s whole "
                f"sequences and the pattern's mask tiles ({tiles}+) "
                f"VMEM-resident: n={n} (padded "
                f"{n_pad}), dh={dh}, tiles {block_q}x{block_k} need "
                f"~{resident / 1e6:.1f} MB of the "
                f"~{VMEM_BUDGET_BYTES / 1e6:.0f} MB budget. Use smaller "
                "tiles, the dense path or sequence parallelism (ring_axis) "
                "for sequences this long.")
    # layers of one variant share their kernels
    return _Static(kernel_pattern(pattern), n, block_q, block_k, interpret,
                   all_partial, cache_kernels)


def flash_pattern_attention(q, k, v, pattern: AttnPattern,
                            key_pad_bias: Optional[jax.Array] = None, *,
                            block_q: int = 128, block_k: int = 128,
                            interpret: bool = False,
                            all_partial: bool = False,
                            cache_kernels: bool = False) -> jax.Array:
    """Block-sparse flash attention for any `AttnPattern`.

    q/k/v: [b, heads, n, dim_head]; `key_pad_bias` is an optional additive
    f32 [b, n] key bias (0 keep / -1e30 drop) carrying the per-sample key
    padding mask.  Returns [b, heads, n, dim_head] in q's dtype.
    ``all_partial`` is a test hook (:func:`_pattern_blocks`);
    ``cache_kernels`` keeps the traced kernels between processes
    (:func:`_kernels`: the model's default path asks for it).

    Raises ValueError when the sequence is long enough that one program's
    whole sequences and mask tiles would overflow the VMEM the kernels may
    take — callers should fall back to the dense-masked XLA path (or
    sequence parallelism, parallel/ring.py) instead of letting Mosaic fail
    opaquely mid-compile.  The guard only applies to real TPU compilation;
    the interpreter (CPU/GPU correctness runs) has no VMEM limit.
    """
    return _flash_attention(
        _checked_static(q, pattern, key_pad_bias is not None, block_q,
                        block_k, interpret, all_partial, cache_kernels),
        q, k, v, key_pad_bias)


def flash_attention_halves(q, pattern: AttnPattern, has_bias: bool, *,
                           block_q: int, block_k: int,
                           cache_kernels: bool = False):
    """:func:`flash_pattern_attention`'s two halves for a caller with a VJP
    of its own (``ops/attention.py`` switches platforms inside one):
    ``forward(q, k, v, key_pad_bias) -> (out, residuals)`` and
    ``backward(residuals, cotangent) -> (dq, dk, dv, dbias)``."""
    static = _checked_static(q, pattern, has_bias, block_q, block_k, False,
                             False, cache_kernels)
    return (functools.partial(_flash_fwd, static),
            functools.partial(_flash_bwd, static))
