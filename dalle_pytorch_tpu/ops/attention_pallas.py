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
* **the projections' own arrays** (PR 35): the kernels read q, k and v
  straight out of ``to_qkv``'s result ``[b, n, 3 * heads * dim_head]`` (the
  same array under three block specs), write ``o`` as ``[b, n, heads *
  dim_head]``, which is ``to_out``'s input as it stands, and write dq, dk
  and dv into one ``[b, n, 3 * heads * dim_head]`` array where q, k and v
  lie in ``qkv``: the backward call leaves that array in HBM and copies its
  program's three lane blocks into it itself (PR 41; until then a dq call,
  a dk/dv call that took its buffer through ``input_output_aliases``, and
  an in-place update for dv).  A program's block
  is the whole sequence, at its own length, by one lane block
  (:func:`~.attention.lane_block`: 128 lanes, two heads of 64 side by side,
  or one head of 128); the grid is ``(batch, lane blocks)``, and a program
  goes through its heads in a loop: each trip takes its head's lanes of the
  operands with a select and writes its lanes of the block's outputs.
  Nothing is padded, sliced or transposed in HBM around a call.
* **the ragged tail is the kernel's**: where the length is no multiple of
  the tile, the last block of queries (and of keys) is the *last full tile*,
  rows ``[n - tile, n)``; its mask tile disallows the rows and columns the
  block before it already covered, and only its new rows are stored.  So
  every shape inside the kernel is a whole tile.  Logsumexp and delta are
  kept in that block layout, ``[b, heads, blocks, tile]``, and the key bias
  as ``[b, blocks, tile]``: no statistic is cut at an unaligned lane offset.
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
  inputs nothing is rounded.  ``q * dim_head ** -0.5`` is taken in the input
  dtype, before the product, as the dense path does.  Running max, sum,
  ``exp``, logsumexp, delta (computed in the backward from ``do`` and
  ``o``) and the accumulators are float32 whatever the inputs.
* **keys/values stay VMEM-resident** per program: at n≈1104 a lane block's
  q, k, v fit comfortably, so the inner loop does no HBM traffic at all.
* full custom VJP: ONE flash backward kernel (PR 41) with the same block
  table, using the saved logsumexp rows: on transposed tiles, a key block
  at a time, each computed block's scores, probabilities and their
  gradient made once and used for dk, dv and dq (five products where the
  two kernels before it made seven and two passes of the scores); dq
  accumulates in a float32 VMEM array of the whole sequence, which fits
  because a program holds one sample's whole sequence.  It is traced under
  the forward's ``graftprof:attn-scores`` scope.
"""
from __future__ import annotations

import functools
import hashlib
import os
from pathlib import Path
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import prof
from .attention import (LANES, AttnPattern, dense_pattern_mask,
                        kernel_pattern, lane_block)

NEG_INF = -1e30  # finite mask value: keeps (s - lse) well-defined everywhere

#: block kinds in :class:`PatternBlocks`' table; a value >= ``PARTIAL`` is a
#: partly allowed block whose mask is tile ``value - PARTIAL``
SKIP, WHOLE, PARTIAL = 0, 1, 2

#: positions of padding a call adds to a sequence in HBM (48 at n = 1104
#: until PR 35; the ``attention.kernel`` record carries it)
HBM_PAD_ROWS = 0

#: ``pallas_call``s one flash layer's backward makes (2 until PR 41: dq, then
#: dk and dv; the ``attention.kernel`` record carries it)
BACKWARD_CALLS = 1

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def block_starts(n: int, block: int) -> List[int]:
    """First row of each block of a length-``n`` sequence: multiples of
    ``block``, the last one pulled back to ``n - block`` (the last full
    tile) where ``n`` is no multiple.  Block ``i``'s *new* rows, those no
    block before it holds, start at ``i * block``."""
    assert 0 < block <= n, (block, n)
    return [min(i * block, n - block) for i in range(-(-n // block))]


class PatternBlocks(NamedTuple):
    """A pattern at one length and tiling, as the kernels take it."""
    table: np.ndarray   # [NQ, NK] int32: SKIP, WHOLE or PARTIAL + tile number
    tiles: np.ndarray   # [T, block_q, block_k] int8: the distinct mask tiles
    counts: Tuple[int, int, int]   # blocks skipped, partly, wholly allowed
    guard: np.ndarray   # [NQ, NK] bool: some query of the block has met no
    #                     allowed key yet but will meet one further on


@functools.lru_cache(maxsize=64)
def _pattern_blocks(pattern: AttnPattern, n: int, block_q: int, block_k: int,
                    all_partial: bool = False) -> PatternBlocks:
    """Static (trace-time) block table and mask tiles for a pattern at
    length ``n``.  A tail block (:func:`block_starts`) is allowed nothing in
    the rows and columns it shares with the block before it: each pair of
    positions lies in exactly one block.  ``all_partial`` (a test hook)
    treats wholly allowed blocks as partly allowed: the result must not
    change."""
    mask = np.broadcast_to(dense_pattern_mask(pattern, n, n), (n, n))
    q_starts, k_starts = block_starts(n, block_q), block_starts(n, block_k)
    nq, nk = len(q_starts), len(k_starts)
    blocks = np.zeros((nq, nk, block_q, block_k), bool)
    for qb, q0 in enumerate(q_starts):
        for kb, k0 in enumerate(k_starts):
            new_q, new_k = qb * block_q, kb * block_k
            blocks[qb, kb, new_q - q0:, new_k - k0:] = mask[
                new_q:q0 + block_q, new_k:k0 + block_k]
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
    # keys each row of a query block has met after each key block, and in
    # the end
    met = blocks.any(3).cumsum(1) > 0                       # [nq, nk, bq]
    waiting = ~met & met[:, -1:]
    return PatternBlocks(table, np.stack(tiles).astype(np.int8),
                         (nq * nk - partial - whole, partial, whole),
                         waiting.any(2))


def block_counts(pattern: AttnPattern, n: int, block_q: int,
                 block_k: int) -> Tuple[int, int, int]:
    """(skipped, partly allowed, wholly allowed) blocks of one layer."""
    return _pattern_blocks(pattern, n, block_q, block_k).counts


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
#
# One program holds one sample's whole sequence by one lane block of heads,
# and every loop over blocks is unrolled at trace time from the static table:
# no branch, no dynamic slice, no carried loop state, so the scheduler
# overlaps one block's MXU passes with the next one's VPU work.  On the chip
# (PERF.md, Findings PR 28) this form runs the CUB shape in 2.1-2.8 ms a
# layer where the same arithmetic under ``fori_loop`` + ``cond`` over a
# (batch*head, block) grid took 5.5-9.5 ms and the dense branch 7.2 ms.  The
# heads of a program are the trips of one ``fori_loop`` around that body
# (:func:`_each_head`), not a second unrolled copy of it: the trace and
# Mosaic's lowering stay the size they were.  Nor are they a grid axis: the
# pipeline fetches the next step's blocks during the current step, and a
# program that is one step hides the next program's q, k, v behind all of
# its work, not behind its last head's (PERF.md, Findings PR 35: as a grid
# axis the then dk/dv kernel waited 4.7 us a program for them).
#
# Two heads share the 128 lanes of a block.  A step has its head's operand
# by a select over the lanes (:func:`_only`), never by a slice: a product
# that contracts over all 128 lanes with the other head's zeroed costs the
# MXU what the 64-deep product padded to 128 cost, and ``p @ v`` gives
# ``[rows, 128]`` whose other half is never stored (:func:`_store`).


def _each_head(static: "_Static", body) -> None:
    """``body(head)`` for every head of the program's lane block."""
    if static.heads_per_program == 1:
        body(0)
    else:
        jax.lax.fori_loop(0, static.heads_per_program,
                          lambda head, _: body(head), None)


def _own_lanes(static: "_Static", head):
    """``[1, lanes]`` bool: the lanes of head number ``head`` (traced: the
    loop's trip) of the program's block; None where one head fills it."""
    if static.heads_per_program == 1:
        return None
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, static.lanes), 1)
    first = head * static.dim_head
    return (lane >= first) & (lane < first + static.dim_head)


def _only(x, own):
    """``x`` with the other heads' lanes zeroed."""
    return x if own is None else jnp.where(own, x, jnp.zeros_like(x))


def _store(ref, at: tuple, first: int, value, own):
    """Rows ``[first, first + len(value))`` of ``ref[at]``: this head's lanes
    of the float32 ``value``, the other heads' left as they are."""
    rows = slice(first, first + value.shape[0])
    if own is not None:
        value = jnp.where(own, value, ref[(*at, rows, slice(None))].astype(
            jnp.float32))
    ref[(*at, rows, slice(None))] = value.astype(ref.dtype)


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


def _bwd_block(k_blk, v_blk, q, do, lse, delta, bias, tile, dk, dv):
    """One computed block of the backward, on *transposed* tiles ``[bk,
    bq]``: the scores as ``k @ q.T``, so that logsumexp and delta (``[1,
    bq]``, stored along the lanes) broadcast down the sublanes; the scores,
    probabilities and their gradient made once for all three gradients.
    Returns dk and dv with the block's part added, and the block's part of
    dq, ``ds_t.T @ k`` (unscaled): of the five products it is the one with a
    transposed operand.  ``bias`` is a column, ``tile`` a transposed mask
    tile."""
    s_t = _scores(k_blk, q, bias, tile)
    p_t = jnp.exp(s_t - lse)                                 # [bk, bq]
    dv = dv + jax.lax.dot_general(p_t.astype(do.dtype), do, _NN,
                                  preferred_element_type=jnp.float32)
    dp_t = jax.lax.dot_general(v_blk, do, _NT,
                               preferred_element_type=jnp.float32)
    ds_t = (p_t * (dp_t - delta)).astype(q.dtype)
    dk = dk + jax.lax.dot_general(ds_t, q, _NN,
                                  preferred_element_type=jnp.float32)
    dq = jax.lax.dot_general(ds_t, k_blk, _TN,
                             preferred_element_type=jnp.float32)
    return dk, dv, dq


def _computed(blocks: PatternBlocks, qb=None, kb=None):
    """``(index, mask tile number or None)`` of the computed blocks of one
    query block's row or one key block's column of the table."""
    codes = blocks.table[qb] if kb is None else blocks.table[:, kb]
    return [(int(i), int(c) - PARTIAL if c >= PARTIAL else None)
            for i, c in enumerate(codes) if c != SKIP]


def _fwd_kernel(q_ref, k_ref, v_ref, tiles_ref, *rest, static: "_Static",
                has_bias: bool):
    bias_ref = rest[0] if has_bias else None
    o_ref, lse_ref = rest[-2:]
    blocks, bq, bk = static.blocks, static.block_q, static.block_k

    def one_head(head):
        own = _own_lanes(static, head)
        for qb, q0 in enumerate(static.q_starts):
            q = _only(q_ref[0, q0:q0 + bq, :], own) * static.scale
            carry = None
            for kb, number in _computed(blocks, qb=qb):
                cols = slice(static.k_starts[kb], static.k_starts[kb] + bk)
                carry = _fwd_block(
                    q, k_ref[0, cols, :], v_ref[0, cols, :],
                    bias_ref[0, kb:kb + 1, :] if has_bias else None,
                    None if number is None else tiles_ref[number], carry,
                    guard=has_bias or bool(blocks.guard[qb, kb]))
            new = qb * bq - q0      # the rows before it are the last block's
            if carry is None:       # no key allowed to any query of the block
                out = jnp.zeros((bq, static.lanes), jnp.float32)
                lse = jnp.full((bq,), jnp.inf, jnp.float32)
            else:
                m, l, acc = carry
                # rows with no attendable key (a tail block's shared rows, a
                # sample whose every key is dropped) give zeros, and lse =
                # +inf so that the backward's exp(s - lse) is exactly 0
                dead = m <= NEG_INF * 0.5
                out = jnp.where(dead, 0.0, acc / l)
                lse = jnp.where(dead, jnp.inf, m + jnp.log(l))[:, 0]
            _store(o_ref, (0,), qb * bq, out[new:], own)
            lse_ref[0, head, qb, :] = lse

    _each_head(static, one_head)


def _bwd_kernel(q_ref, k_ref, v_ref, tiles_ref, *rest, static: "_Static",
                has_bias: bool):
    """dq, dk and dv in one pass over the computed blocks, a key block at a
    time (:func:`_bwd_block`); ``tiles_ref`` holds the mask tiles
    transposed.  A head first makes its scaled, selected q, its selected do
    and delta (the row sums of ``do * o`` over its lanes, in the statistics'
    lane layout) once, and zeroes a float32 dq accumulator of the whole
    sequence, to which each block adds its part in its query block's rows.
    The gradients are gathered in a VMEM copy of the program's three lane
    blocks (``grads``, two of them: the programs of a sample's lane blocks
    take turns), copied into ``dqkv`` (left in HBM) at the program's end and
    waited for at the next program's end, so that the copies run under the
    next program's blocks and no branch lies among the blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bias_ref = rest[0] if has_bias else None
    (do_ref, o_ref, lse_ref, dqkv_ref, q_own, do_own, delta, dq, grads,
     sems) = rest[-10:]
    blocks, bq, bk = static.blocks, static.block_q, static.block_k
    sample, block = pl.program_id(0), pl.program_id(1)
    slot = block % 2

    def one_head(head):
        own = _own_lanes(static, head)
        q_own[...] = _only(q_ref[0], own) * static.scale
        do_own[...] = _only(do_ref[0], own)
        for qb, q0 in enumerate(static.q_starts):
            rows = slice(q0, q0 + bq)
            delta[qb, :] = jnp.sum(do_own[rows, :].astype(jnp.float32)
                                   * o_ref[0, rows, :].astype(jnp.float32),
                                   axis=1)
        dq[...] = jnp.zeros(dq.shape, jnp.float32)
        for kb, k0 in enumerate(static.k_starts):
            k_blk, v_blk = k_ref[0, k0:k0 + bk, :], v_ref[0, k0:k0 + bk, :]
            # the bias over this key block, as a column
            bias = bias_ref[0, kb, :][:, None] if has_bias else None
            dk = jnp.zeros((bk, static.lanes), jnp.float32)
            dv = jnp.zeros((bk, static.lanes), jnp.float32)
            for qb, number in _computed(blocks, kb=kb):
                rows = slice(static.q_starts[qb], static.q_starts[qb] + bq)
                dk, dv, dq_blk = _bwd_block(
                    k_blk, v_blk, q_own[rows, :], do_own[rows, :],
                    lse_ref[0, pl.ds(head, 1), qb, :],
                    delta[qb:qb + 1, :], bias,
                    None if number is None else tiles_ref[number], dk, dv)
                dq[rows, :] = dq[rows, :] + dq_blk
            new = kb * bk - k0
            _store(grads, (slot, 1), kb * bk, dk[new:], own)
            _store(grads, (slot, 2), kb * bk, dv[new:], own)
        _store(grads, (slot, 0), 0, dq[...] * static.scale, own)

    _each_head(static, one_head)

    def copies(turn, lane_block):
        """The copies of ``grads[turn]`` into the sample's lane block
        ``lane_block`` of each third."""
        return [pltpu.make_async_copy(
            grads.at[turn, third], dqkv_ref.at[sample, :, pl.ds(
                pl.multiple_of((third * static.lane_blocks + lane_block)
                               * static.lanes, static.lanes), static.lanes)],
            sems.at[turn, third]) for third in range(3)]

    @pl.when(block > 0)
    def _():
        for copy in copies(1 - slot, block - 1):
            copy.wait()

    for copy in copies(slot, block):
        copy.start()

    @pl.when(block == static.lane_blocks - 1)
    def _():
        for copy in copies(slot, block):
            copy.wait()


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------

#: what Mosaic may take of the v5e's 128 MiB of VMEM for one program (its
#: default, 16 MiB, is too little for the unrolled loops at n = 4176)
VMEM_LIMIT_BYTES = 96 * 1024 * 1024


def _pallas(kernel, static: "_Static", tiles, bias, qkv, wide, stats, outs,
            scratch=(), lanes_in_order=False):
    """One of the two kernels over the grid ``(batch, lane blocks)``: q, k
    and v as column blocks of ``qkv`` (``[b, n, 3 * heads * dim_head]``,
    passed three times), each ``wide`` operand (``[b, n, heads *
    dim_head]``: do, o) the same column block of its array, each of
    ``stats`` the ``[blocks, tile]`` float32 statistics of the block's
    heads, the mask tiles one block for the whole call, the key bias one
    sample's.  ``outs``: ``("wide", thirds)`` is an array of ``thirds *
    heads * dim_head`` columns, of which the program writes its lane block
    of the first third through the pipeline, or, with ``thirds`` over 1, its
    lane block of every third itself (the array stays in HBM); ``("stat",)``
    statistics.  ``scratch``: the kernel's scratch shapes.  Every program
    writes its own blocks exactly once, so both grid axes are parallel,
    unless the programs of a sample must run in the order of its lane
    blocks (``lanes_in_order``: one waits for the copies of the one
    before it).

    Pallas is imported here, not with the module: a process that finds its
    kernels in the cache (:func:`_kernels`) never pays that second."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n, _ = qkv.shape
    lanes, per, groups = (static.lanes, static.heads_per_program,
                          static.lane_blocks)
    nq = len(static.q_starts)

    def spec(shape, index_map):
        return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)

    def column(third):
        return spec((1, n, lanes), lambda ib, j: (ib, 0, third * groups + j))

    stat = spec((1, per, nq, static.block_q), lambda ib, j: (ib, j, 0, 0))
    in_specs = [column(0), column(1), column(2),
                spec(tiles.shape, lambda ib, j: (0, 0, 0))]
    args = [qkv, qkv, qkv, tiles]
    if bias is not None:
        in_specs.append(spec((1,) + bias.shape[1:], lambda ib, j: (ib, 0, 0)))
        args.append(bias)
    in_specs += [column(0)] * len(wide) + [stat] * len(stats)
    args += [*wide, *stats]
    out_specs, out_shape = [], []
    for kind, *thirds in outs:
        if kind == "wide":
            out_specs.append(column(0) if thirds == [1] else
                             pl.BlockSpec(memory_space=pl.ANY))
            out_shape.append(jax.ShapeDtypeStruct(
                (b, n, thirds[0] * static.heads * static.dim_head),
                qkv.dtype))
        else:
            out_specs.append(stat)
            out_shape.append(jax.ShapeDtypeStruct(
                (b, static.heads, nq, static.block_q), jnp.float32))
    return pl.pallas_call(
        functools.partial(kernel, static=static, has_bias=bias is not None),
        grid=(b, groups), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "arbitrary" if lanes_in_order else "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=static.interpret)(*args)


class _Static(NamedTuple):
    """The static half of a call: what the kernels are built from.  Layers
    that agree on it (and on their shapes) share one traced kernel: the
    calls below are jitted on it, so a model traces each kernel's unrolled
    body once a pattern, not once a layer and differentiation pass."""
    pattern: AttnPattern
    n: int
    heads: int          # of the arrays the call is given (a shard's)
    dim_head: int
    block_q: int
    block_k: int
    interpret: bool
    all_partial: bool
    cache_kernels: bool

    @property
    def blocks(self) -> PatternBlocks:
        return _pattern_blocks(self.pattern, self.n, self.block_q,
                               self.block_k, self.all_partial)

    @property
    def q_starts(self) -> List[int]:
        return block_starts(self.n, self.block_q)

    @property
    def k_starts(self) -> List[int]:
        return block_starts(self.n, self.block_k)

    @property
    def lanes(self) -> int:
        """Columns of one program's block: :func:`~.attention.lane_block`,
        or every head's at once where the width cuts into no lane blocks
        (the interpreter's toy shapes; the compiler is never asked)."""
        return (lane_block(self.heads, self.dim_head)
                or self.heads * self.dim_head)

    @property
    def heads_per_program(self) -> int:
        return self.lanes // self.dim_head

    @property
    def lane_blocks(self) -> int:
        return self.heads * self.dim_head // self.lanes

    @property
    def scale(self) -> float:
        return self.dim_head ** -0.5


@functools.partial(jax.jit, static_argnums=(0,))
def _call_fwd(static: _Static, qkv, bias):
    """``(o [b, n, heads * dim_head], logsumexp [b, heads, blocks, tile])``."""
    return _pallas(_fwd_kernel, static, jnp.asarray(static.blocks.tiles),
                   bias, qkv, [], [], [("wide", 1), ("stat",)])


@functools.partial(jax.jit, static_argnums=(0,))
def _call_bwd(static: _Static, qkv, bias, do, o, lse):
    """``dqkv [b, n, 3 * heads * dim_head]``: dq, dk and dv where q, k and v
    lie in ``qkv``, all three written by the one call (:func:`_bwd_kernel`)."""
    from jax.experimental.pallas import tpu as pltpu

    n, lanes, dtype = static.n, static.lanes, qkv.dtype
    scratch = [pltpu.VMEM((n, lanes), dtype),                  # q, scaled
               pltpu.VMEM((n, lanes), dtype),                  # do
               pltpu.VMEM((len(static.q_starts), static.block_q),
                          jnp.float32),                        # delta
               pltpu.VMEM((n, lanes), jnp.float32),            # dq
               pltpu.VMEM((2, 3, n, lanes), dtype),            # dq, dk, dv
               pltpu.SemaphoreType.DMA((2, 3))]
    (dqkv,) = _pallas(
        _bwd_kernel, static,
        jnp.asarray(static.blocks.tiles.transpose(0, 2, 1)), bias, qkv,
        [do, o], [lse], [("wide", 3)], scratch, lanes_in_order=True)
    return dqkv


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
def _flash_attention(static: _Static, qkv, bias):
    return _flash_fwd(static, qkv, bias)[0]


def _flash_fwd(static: _Static, qkv, bias):
    """``qkv``: ``to_qkv``'s result, ``[b, n, 3, heads, dim_head]`` or flat
    over its last three axes; ``bias``: None or the additive ``[b, n]`` key
    bias.  Returns ``o [b, n, heads * dim_head]`` and the residuals the
    backward needs beside ``qkv``."""
    b, n = qkv.shape[:2]
    if bias is not None:    # in the key blocks' layout, a row a block
        bias = bias.astype(jnp.float32)
        bias = jnp.stack([bias[:, k0:k0 + static.block_k]
                          for k0 in static.k_starts], axis=1)
    o, lse = _kernels("fwd", static, qkv.reshape(b, n, -1), bias)
    return o, (qkv, bias, o, lse)


def _flash_bwd(static: _Static, residuals, g):
    # a custom VJP's backward is traced outside the forward's name scope:
    # put the backward kernel under the scope the forward's callers give
    # it, or a trace reads the forward alone
    with prof.scope("attn-scores"):
        qkv, bias, o, lse = residuals
        b, n = qkv.shape[:2]
        dqkv = _kernels("bwd", static, qkv.reshape(b, n, -1), bias,
                        g.astype(qkv.dtype), o, lse)
        # the key-padding bias is not trainable
        dbias = None if bias is None else jnp.zeros((b, n), jnp.float32)
        return dqkv.reshape(qkv.shape), dbias


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


#: What the estimate below may come to: the limit the kernels give Mosaic.
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """Bytes of a ``[rows, cols]`` array in VMEM: the minor dimension padded
    to the 128 lanes, the other to the dtype's sublane packing."""
    return (_round_up(rows, 8 * (4 // itemsize)) * _round_up(cols, LANES)
            * itemsize)


def _vmem_resident_bytes(n: int, lanes: int, itemsize: int, block_q: int,
                         block_k: int, tiles: int = 1,
                         has_bias: bool = False) -> int:
    """VMEM one program holds in the hungrier of the two kernels (the
    backward: q, k, v, do and o in, a lane block of the whole sequence each;
    logsumexp and the bias in their block layouts; its scratch: the scaled
    q, the selected do, two copies of the three gradients' lane blocks, the
    float32 dq accumulator and delta), in VMEM's padded layouts: every
    operand that moves with the grid twice (Pallas double-buffers them), the
    scratch and the mask tiles once (the tiles' block never changes), and
    three float32 ``[block_q, block_k]`` tiles of intermediates (scores,
    probabilities, their gradient).  Held against the compiler's own answers
    by ``tests/test_tpu_compile.py``: at n = 4176 both take every tiling up
    to 2176 x 2176 (this reads 88.5 MiB) and refuse the backward of 2432 x
    2432 (105.4; the compiler asks 114.1 MiB there).  Between them, at 2304,
    the compiler still takes what this refuses (96.7)."""
    seq = _tile_bytes(n, lanes, itemsize)
    nq = -(-n // block_q)
    stats = 2 * _tile_bytes(nq, block_q, 4)
    if has_bias:
        stats += _tile_bytes(-(-n // block_k), block_k, 4)
    scratch = (8 * seq + _tile_bytes(n, lanes, 4)
               + _tile_bytes(nq, block_q, 4))
    return (2 * (5 * seq + stats) + scratch
            + tiles * _tile_bytes(block_q, block_k, 1)
            + 3 * _tile_bytes(block_q, block_k, 4))


def _checked_static(n: int, heads: int, dim_head: int, dtype,
                    pattern: AttnPattern, has_bias: bool, block_q: int,
                    block_k: int, interpret: bool, all_partial: bool,
                    cache_kernels: bool) -> _Static:
    """The static description of a call on ``[b, n, 3 * heads * dim_head]``,
    or a ValueError where the TPU's compiler would refuse it.  Tiles wider
    than the sequence are cut to it (one block)."""
    block_q, block_k = min(block_q, n), min(block_k, n)
    # layers of one variant share their kernels
    static = _Static(kernel_pattern(pattern), n, heads, dim_head, block_q,
                     block_k, interpret, all_partial, cache_kernels)
    if not interpret:
        if block_q % LANES or block_k % LANES:
            # Mosaic requires the last block dim be a multiple of the
            # 128-lane width (the statistics block the q axis in their last
            # dim; k blocks stream through the same lanes) — sub-128 tiles
            # fail deep inside lowering, so reject them at the API edge.
            # Seen on the chip: manual session 2026-08-02.
            raise ValueError(
                f"block_q/block_k must be multiples of the TPU lane width "
                f"128 and no longer than the sequence (got {block_q}/"
                f"{block_k} at n={n})")
        if lane_block(heads, dim_head) is None or n % 16:
            raise ValueError(
                f"the compiled kernel takes heads * dim_head in whole "
                f"{LANES}-lane blocks of whole heads and n in whole 16-row "
                f"sublane tiles (got {heads} x {dim_head}, n={n})")
        estimate = functools.partial(
            _vmem_resident_bytes, n, static.lanes, jnp.dtype(dtype).itemsize,
            block_q, block_k, has_bias=has_bias)
        tiles, resident = 1, estimate(1)
        if resident <= VMEM_BUDGET_BYTES:   # else: no need to draw the mask
            tiles = static.blocks.tiles.shape[0]
            resident = estimate(tiles)
        if resident > VMEM_BUDGET_BYTES:
            raise ValueError(
                f"the flash kernel keeps a lane block of one sample's whole "
                f"sequences and the pattern's mask tiles ({tiles}+) "
                f"VMEM-resident: n={n}, {static.lanes} lanes, tiles "
                f"{block_q}x{block_k} need ~{resident / 1e6:.1f} MB of the "
                f"~{VMEM_BUDGET_BYTES / 1e6:.0f} MB budget. Use smaller "
                "tiles, the dense path or sequence parallelism (ring_axis) "
                "for sequences this long.")
    return static


def flash_qkv_attention(qkv, pattern: AttnPattern,
                        key_pad_bias: Optional[jax.Array] = None, *,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False, all_partial: bool = False,
                        cache_kernels: bool = False) -> jax.Array:
    """Block-sparse flash attention for any `AttnPattern`, on the
    projections' own arrays.

    ``qkv``: ``to_qkv``'s result ``[b, n, 3, heads, dim_head]``;
    `key_pad_bias` is an optional additive f32 [b, n] key bias (0 keep /
    -1e30 drop) carrying the per-sample key padding mask.  Returns ``[b, n,
    heads * dim_head]`` (``to_out``'s input) in qkv's dtype.
    ``all_partial`` is a test hook (:func:`_pattern_blocks`);
    ``cache_kernels`` keeps the traced kernels between processes
    (:func:`_kernels`: the model's default path asks for it).

    Raises ValueError where the compiler would refuse the call: heads that
    do not fill whole lane blocks, a length off the 16-row sublane tiles, or
    a sequence long enough that one program's whole sequences and mask tiles
    would overflow the VMEM the kernels may take — callers should fall back
    to the dense-masked XLA path (or sequence parallelism, parallel/ring.py)
    instead of letting Mosaic fail opaquely mid-compile.  The guards only
    apply to real TPU compilation; the interpreter (CPU/GPU correctness
    runs) has no such limits.
    """
    _, n, _, heads, dim_head = qkv.shape
    return _flash_attention(
        _checked_static(n, heads, dim_head, qkv.dtype, pattern,
                        key_pad_bias is not None, block_q, block_k, interpret,
                        all_partial, cache_kernels), qkv, key_pad_bias)


def flash_pattern_attention(q, k, v, pattern: AttnPattern,
                            key_pad_bias: Optional[jax.Array] = None,
                            **options) -> jax.Array:
    """:func:`flash_qkv_attention` for head-major operands, ``[b, heads, n,
    dim_head]`` in and out: a wrapper for the tests and ``chip_smoke.py``,
    which hold q, k and v apart.  It stacks them into the kernel's layout
    and undoes that on ``o``; the model never calls it."""
    b, heads, n, dim_head = q.shape
    qkv = jnp.stack([q, k, v]).transpose(1, 3, 0, 2, 4)
    out = flash_qkv_attention(qkv, pattern, key_pad_bias, **options)
    return out.reshape(b, n, heads, dim_head).transpose(0, 2, 1, 3)


def flash_attention_halves(n: int, heads: int, dim_head: int, dtype,
                           pattern: AttnPattern, has_bias: bool, *,
                           block_q: int, block_k: int,
                           cache_kernels: bool = False):
    """:func:`flash_qkv_attention`'s two halves for a caller with a VJP of
    its own (``ops/attention.py`` switches platforms inside one), on
    ``qkv`` of ``heads`` heads, ``[b, n, 3, heads, dim_head]`` or flat over
    the last three axes: ``forward(qkv, key_pad_bias) -> (out, residuals)``
    and ``backward(residuals, cotangent) -> (dqkv, dbias)``."""
    static = _checked_static(n, heads, dim_head, dtype, pattern, has_bias,
                             block_q, block_k, False, False, cache_kernels)
    return (functools.partial(_flash_fwd, static),
            functools.partial(_flash_bwd, static))
