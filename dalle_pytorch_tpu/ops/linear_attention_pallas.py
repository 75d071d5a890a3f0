"""One position of the gated delta rule (ops/linear_attention.py) as one
Pallas TPU kernel that reads each layer's float32 state ONCE and writes it
ONCE, in place.

Plain XLA needs two passes over the state: the read-outs ``S^T k`` and ``S^T
q`` are a reduction over ``d_k`` that must end before the rank-one write can
start, so the state is read again to be written.  Here a program holds a
block of the state in VMEM and does everything on that one copy:

* **Operands where they lie**: the carried state ``S [b, heads / f, d_k, f
  d_v]`` float32 (ops/linear_attention.py::fold_state: ``f`` heads' columns
  side by side, whole lane tiles, nothing padded); the keys and queries as
  columns, ``[b, d_k, 2 heads]`` (``k`` then ``q``: ``d_k`` on the sublanes,
  so that a head's column is spread over its lanes by a lane broadcast, in
  VMEM); ``g`` and ``beta`` as ``[b, 2, heads]``; ``v`` as ``[b, heads / f, f
  d_v]``, the state's own lanes.  Outputs: ``o [b, heads / f, f d_v]``
  float32 and ``S'``, which takes ``S``'s buffer (``input_output_aliases``),
  so that a scan's carry is updated where it lies.
* **A program** is one row by every head group (a row's state is 2.2 MB at
  ``olmo-hybrid-7b``'s 30 heads of 96 x 192; two rows a program were no
  faster on a v5e, PERF.md, Findings).  For each group, a sublane tile of 8
  rows of ``d_k`` at a time: each head's ``k`` and ``q`` spread over its own lanes by a broadcast and a select by head, the
  two sublane sums taken; then ``alpha``, ``u = beta (v - alpha S^T k)`` and
  ``o = alpha S^T q + u (k . q)`` on one row of lanes; then ``alpha S + k
  u^T`` written from the same VMEM copy.  Elementwise float32 on the VPU, in
  the order of operations of the ``jnp`` form; no MXU product (a float32
  product there rounds its operands to bfloat16 unless made in several
  passes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .linear_attention import SUBLANES


def _kernel(cols_ref, gb_ref, v_ref, s_ref, o_ref, s_out_ref, *, fold: int):
    groups, dk, width = s_ref.shape
    heads = groups * fold
    dv = width // fold
    owner = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // dv

    def spread(parts):
        """``[n, 1]`` values of the group's ``fold`` heads -> ``[n,
        width]``, each over its own head's lanes (a select, as the ``jnp``
        form's ``lanes``)."""
        out = jnp.broadcast_to(parts[0], (parts[0].shape[0], width))
        for i in range(1, fold):
            out = jnp.where(owner == i, parts[i], out)
        return out

    gb = gb_ref[...]                                         # [2, heads]
    for grp in range(groups):
        heads_of = [grp * fold + i for i in range(fold)]
        alpha = spread([jnp.exp(gb[0:1, c:c + 1]) for c in heads_of])
        beta = spread([gb[1:2, c:c + 1] for c in heads_of])
        read_k = jnp.zeros((SUBLANES, width), jnp.float32)
        read_q = jnp.zeros((SUBLANES, width), jnp.float32)
        kq = [jnp.zeros((SUBLANES, 1), jnp.float32) for _ in heads_of]
        for t in range(0, dk, SUBLANES):
            rows_t = slice(t, t + SUBLANES)
            k_cols = [cols_ref[rows_t, c:c + 1] for c in heads_of]
            q_cols = [cols_ref[rows_t, heads + c:heads + c + 1]
                      for c in heads_of]
            S = s_ref[grp, rows_t, :]
            read_k += S * spread(k_cols)
            read_q += S * spread(q_cols)
            kq = [a + kc * qc for a, kc, qc in zip(kq, k_cols, q_cols)]
        read_k = alpha * jnp.sum(read_k, axis=0, keepdims=True)
        read_q = alpha * jnp.sum(read_q, axis=0, keepdims=True)
        kq = spread([jnp.sum(a, axis=0, keepdims=True) for a in kq])
        u = beta * (v_ref[grp:grp + 1, :] - read_k)
        o_ref[grp:grp + 1, :] = read_q + u * kq
        for t in range(0, dk, SUBLANES):
            rows_t = slice(t, t + SUBLANES)
            k_l = spread([cols_ref[rows_t, c:c + 1] for c in heads_of])
            s_out_ref[grp, rows_t, :] = alpha * s_ref[grp, rows_t, :] + k_l * u


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_step(S, q, k, v, g, beta, *, interpret: bool = False):
    """``(o [b, heads, d_v] float32, S')`` of one position of the rule
    (ops/linear_attention.py::gated_delta_step's operands and results),
    ``S'`` in ``S``'s buffer.  Jitted, so that the layers of one shape share
    one traced kernel."""
    b, heads, dk = k.shape
    groups, width = S.shape[1], S.shape[-1]
    fold = heads // groups
    assert S.shape == (b, groups, dk, width) and S.dtype == jnp.float32, (
        S.shape, S.dtype, k.shape)
    assert dk % SUBLANES == 0, dk
    f32 = jnp.float32
    cols = jnp.concatenate([k, q], axis=1).astype(f32).transpose(0, 2, 1)
    gb = jnp.stack([g, beta], axis=1).astype(f32)
    v = v.astype(f32).reshape(b, groups, width)

    def row(*shape):
        """A program's block: one row (squeezed), the rest whole."""
        return pl.BlockSpec((None,) + shape,
                            lambda i: (i,) + (0,) * len(shape))

    o, S = pl.pallas_call(
        functools.partial(_kernel, fold=fold),
        out_shape=(jax.ShapeDtypeStruct((b, groups, width), f32),
                   jax.ShapeDtypeStruct(S.shape, f32)),
        grid=(b,),
        in_specs=[row(dk, 2 * heads), row(2, heads), row(groups, width),
                  row(groups, dk, width)],
        out_specs=(row(groups, width), row(groups, dk, width)),
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 * 2 ** 20),
        cost_estimate=pl.CostEstimate(
            flops=8 * S.size, transcendentals=b * heads,
            bytes_accessed=2 * S.size * 4),
        name="delta_step",
        interpret=interpret,
    )(cols, gb, v, S)
    return o.reshape(b, heads, width // fold), S
