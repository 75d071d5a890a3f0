"""The absorbed read of a latent cache (ops/latent_attention.py) as one
Pallas TPU kernel that passes over the reachable latent ONCE.

Plain XLA turns the read's three products and its softmax into two fusions a
layer: the latent is read for the scores, ``[rows, heads, reach]`` float32
scores go to HBM and come back, and the latent is read again for the weighted
sum.  Here a block of positions is fetched once and serves both products:

* **Operands where they lie**: ``q_lat [rows, heads, kv_rank]`` and ``q_rope
  [rows, heads, rope_dim]`` (scaled, in the cache's dtype) and the FOLDED
  latent ``[rows, slots / 2, 2 kv_rank + 2 rope_dim]``
  (ops/latent_attention.py::fold_latent: a block of 256 positions in 128
  rows, its halves side by side; row ``i`` of block ``j`` holds positions ``j
  256 + i`` and ``j 256 + 128 + i`` as ``[c | c | k_rope | k_rope]``: nine
  whole 128-lane tiles a row, nothing padded).  Output ``o_lat [rows, heads,
  kv_rank]`` float32.  No ``[rows, heads, reach]`` array exists outside VMEM.
* **A program** is ``rows_per_program`` cache rows by one block.  Per cache
  row, for the first and the second half of the block alike: ``scores = q_lat
  . c^T + q_rope . k_rope^T`` for all heads (float32 sums; the two halves'
  rotary keys share a tile, so one product of ``[q_rope | 0]`` over ``[0 |
  q_rope]`` against it gives both halves' part), the mask by logical
  position, the online softmax over both halves (float32 running
  max, sum and ``[heads, kv_rank]`` accumulator in VMEM scratch), and ``p .
  c`` from the same ``c`` in VMEM, the probabilities cast to the cache's
  dtype before the product as the plain read casts them.  The latent stays
  in the MXU for both products and the query rows stream through it (timed
  against the other order, the queries stationary for the scores: 0.785
  against 0.965 ms a layer, PERF.md PR 39).
* **The position bounds the walk at the block.**  Positions ``[0, index]``
  are reachable, so a row group needs blocks ``0 .. index // 256``, and the
  grid is one axis of ``groups x needed`` steps, a bound known when the
  kernel starts and not when it is compiled: step ``s`` works on group ``s
  // needed``, block ``s mod needed``.  No step is left without work (one
  that computes nothing still costs 1.1 us of the scalar core's
  bookkeeping, PERF.md PR 39), and the pipeline's next fetch always overlaps
  a step that computes.  Where each step works comes in as two prefetched
  tables, made outside from ``index``: the scalar core divides slowly.

No key-padding mask is taken: a caller that has one keeps the pair unfolded
and runs the plain read.

:func:`fold_latent_blocks` makes the fold, once a call: a copy block by
block.  It takes the rotary keys as ``[rows, rope_dim, slots]``, which is how
the v5e lays a ``[rows, slots, 64]`` array out anyway (the transposition
outside is free, where the array as it stands would be copied into a form
padded to the lanes), and turns each block's back inside.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .latent_attention import READ_BLOCK

NEG_INF = -1e30  # finite: the running max of a block with no position yet
HALF = READ_BLOCK // 2


def _kernel(index_ref, group_ref, block_ref, q_lat_ref, q_rope_ref, lat_ref,
            o_ref, m_ref, l_ref, acc_ref, *, rows: int, rank: int):
    index = index_ref[0]
    j = block_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    first = j * READ_BLOCK + jax.lax.broadcasted_iota(jnp.int32, (1, HALF), 1)
    nt = (((1,), (1,)), ((), ()))
    heads = q_lat_ref.shape[1]
    padded = q_rope_ref.shape[1] // 2       # heads in whole sublane tiles
    for r in range(rows):
        s_rope = jax.lax.dot_general(
            q_rope_ref[r], lat_ref[r, :, 2 * rank:], nt,
            preferred_element_type=jnp.float32)
        parts = []
        for second in (0, 1):
            c = lat_ref[r, :, second * rank:(second + 1) * rank]
            s = jax.lax.dot_general(q_lat_ref[r], c, nt,
                                    preferred_element_type=jnp.float32)
            s += s_rope[second * padded:second * padded + heads]
            parts.append((jnp.where(first + second * HALF <= index, s,
                                    NEG_INF), c))
        m_prev = m_ref[r]
        m_new = functools.reduce(jnp.maximum, [m_prev] + [
            s.max(axis=-1, keepdims=True) for s, _ in parts])
        alpha = jnp.exp(m_prev - m_new)
        total, weighted = alpha * l_ref[r], alpha * acc_ref[r]
        for s, c in parts:
            p = jnp.exp(s - m_new)
            total += p.sum(axis=-1, keepdims=True)
            weighted += jnp.dot(p.astype(c.dtype), c,
                                preferred_element_type=jnp.float32)
        m_ref[r], l_ref[r], acc_ref[r] = m_new, total, weighted

    @pl.when(j == index // READ_BLOCK)
    def _():
        o_ref[...] = acc_ref[...] / l_ref[...]


@functools.partial(jax.jit, static_argnames=("rows_per_program", "interpret"))
def latent_read(q_lat, q_rope, folded, index, *, rows_per_program: int,
                interpret: bool = False):
    """``o_lat [rows, heads, kv_rank]`` float32 of the absorbed read over
    positions ``[0, index]`` of the folded latent (module docstring).
    ``index`` a traced int32 scalar; ``rows_per_program`` a divisor of the
    rows.  Jitted on its statics, so that the layers of one shape share one
    traced kernel."""
    rows, heads, rank = q_lat.shape
    rope = q_rope.shape[-1]
    blocks = folded.shape[1] // HALF
    assert folded.shape == (rows, blocks * HALF, 2 * (rank + rope)), (
        folded.shape, q_lat.shape, q_rope.shape)
    assert rows % rows_per_program == 0, (rows, rows_per_program)
    groups, per = rows // rows_per_program, rows_per_program

    # step -> (row group, block of positions), as tables
    index = jnp.reshape(index, (1,)).astype(jnp.int32)
    needed = index[0] // READ_BLOCK + 1
    step = jnp.arange(groups * blocks)

    def group_map(step, index_ref, group_ref, block_ref):
        return group_ref[step], 0, 0

    def block_map(step, index_ref, group_ref, block_ref):
        return group_ref[step], block_ref[step], 0

    # the two halves' rotary keys share a tile: [q_rope | 0] meets the first
    # half's, [0 | q_rope] the second's, each on whole 8-row sublane tiles
    pad = -heads % 8
    q_rope = jnp.concatenate(
        [jnp.pad(q_rope, ((0, 0), (0, pad), (0, rope))),
         jnp.pad(q_rope, ((0, 0), (0, pad), (rope, 0)))], axis=1)
    kernel = functools.partial(_kernel, rows=per, rank=rank)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, heads, rank), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(groups * needed,),
            in_specs=[
                pl.BlockSpec((per, heads, rank), group_map),
                pl.BlockSpec((per, *q_rope.shape[1:]), group_map),
                pl.BlockSpec((per, HALF, folded.shape[-1]), block_map),
            ],
            out_specs=pl.BlockSpec((per, heads, rank), group_map),
            scratch_shapes=[
                pltpu.VMEM((per, heads, 1), jnp.float32),
                pltpu.VMEM((per, heads, 1), jnp.float32),
                pltpu.VMEM((per, heads, rank), jnp.float32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=4 * rows * blocks * HALF * heads * (2 * rank + rope),
            transcendentals=2 * rows * blocks * HALF * heads,
            bytes_accessed=folded.size * folded.dtype.itemsize),
        name="latent_read",
        interpret=interpret,
    )(index, step // needed, step % needed, q_lat, q_rope, folded)


def _fold_kernel(c_ref, kr_ref, o_ref):
    rank = c_ref.shape[2]
    o_ref[:, :, :rank] = c_ref[:, :HALF]
    o_ref[:, :, rank:2 * rank] = c_ref[:, HALF:]
    for r in range(o_ref.shape[0]):
        k_rope = kr_ref[r].T                          # [block, rope_dim]
        o_ref[r, :, 2 * rank:] = jnp.concatenate(
            [k_rope[:HALF], k_rope[HALF:]], axis=1)


@functools.partial(jax.jit, static_argnames=("rows_per_program", "interpret"))
def fold_latent_blocks(cache_c, cache_kr, *, rows_per_program: int,
                       interpret: bool = False):
    """ops/latent_attention.py::fold_latent of ``(cache_c [rows, slots,
    kv_rank], cache_kr [rows, slots, rope_dim])``: one read of the pair and
    one write of the fold (module docstring)."""
    rows, slots, rank = cache_c.shape
    rope = cache_kr.shape[-1]
    per = rows_per_program
    assert rows % per == 0 and slots % READ_BLOCK == 0, (rows, per, slots)
    return pl.pallas_call(
        _fold_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, slots // 2, 2 * (rank + rope)),
                                       cache_c.dtype),
        grid=(rows // per, slots // READ_BLOCK),
        in_specs=[pl.BlockSpec((per, READ_BLOCK, rank),
                               lambda group, j: (group, j, 0)),
                  pl.BlockSpec((per, rope, READ_BLOCK),
                               lambda group, j: (group, 0, j))],
        out_specs=pl.BlockSpec((per, HALF, 2 * (rank + rope)),
                               lambda group, j: (group, j, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="latent_fold",
        interpret=interpret,
    )(cache_c, cache_kr.transpose(0, 2, 1))
