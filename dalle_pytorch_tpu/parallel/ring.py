"""Ring attention — sequence/context parallelism over a mesh axis.

The reference has no long-context machinery (SURVEY.md §5.7: its levers are
sparse attention and reversible depth at a fixed seq_len ≈ 1104).  A
TPU-native framework treats long context as a first-class scaling axis:
shard the *sequence* over an ``sp`` mesh axis and compute exact attention by
rotating key/value shards around the ICI ring (`lax.ppermute`) while
accumulating the softmax online — per-device memory O(n/sp · n/sp) instead
of O(n²), full overlap of compute with neighbor transfers, and exact (not
approximate) results.

Two entry points:
* ``ring_attention(q, k, v, axis_name=...)`` — call inside ``shard_map``
  with q/k/v already sequence-sharded ([b, h, n_local, dh] per device).
* ``ring_attention_sharded(q, k, v, mesh, ...)`` — standalone: wraps the
  shard_map over ``mesh`` with the batch on 'dp' and sequence on 'sp'.

Masking reuses the same `AttnPattern` predicate as every other attention in
the framework (ops/attention.py), evaluated at *global* positions, so the
DALLE variants (full / axial / conv_like / sparse) all work sequence-
parallel.  Differentiable by construction (ppermute's transpose is the
inverse ppermute; the scan is unrolled by XLA's autodiff).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import AttnPattern, _allowed

NEG_INF = -1e30


def _chunk_mask(pattern: Optional[AttnPattern], causal: bool,
                q_off, k_off, n_q: int, n_k: int, layout=None):
    """Boolean [n_q, n_k] mask for a (query-chunk, key-chunk) pair whose
    global offsets are (traced) ``q_off`` / ``k_off``."""
    i = q_off + jax.lax.broadcasted_iota(jnp.int32, (n_q, n_k), 0)
    j = k_off + jax.lax.broadcasted_iota(jnp.int32, (n_q, n_k), 1)
    if pattern is None:
        return (j <= i) if causal else jnp.ones((n_q, n_k), bool)
    return _allowed(pattern, i, j, jnp, layout=layout)


def ring_attention(q, k, v, *, axis_name: str,
                   pattern: Optional[AttnPattern] = None,
                   causal: bool = True) -> jax.Array:
    """Exact attention over a sequence sharded on ``axis_name``.

    q/k/v: local shards [b, h, n_local, dh]; every device holds a distinct
    contiguous chunk of the global sequence, ordered by its axis index.
    Returns the local output shard [b, h, n_local, dh].
    """
    sp = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, nl, dh = q.shape
    scale = dh ** -0.5
    layout = None
    if pattern is not None and pattern.variant == "sparse":
        layout = jnp.asarray(pattern.block_layout())

    qf = q.astype(jnp.float32) * scale
    m0 = jnp.full((b, h, nl, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, nl, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, nl, dh), jnp.float32)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def accumulate(r, k_r, v_r, m, l, acc):
        """Online-softmax update against the chunk currently held, which
        originated on device (idx - r) mod sp."""
        src = jax.lax.rem(idx - r + sp, sp)
        s = jnp.einsum("bhid,bhjd->bhij", qf, k_r.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        allow = _chunk_mask(pattern, causal, idx * nl, src * nl, nl, nl,
                            layout=layout)
        s = jnp.where(allow[None, None], s, NEG_INF)

        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(s <= NEG_INF * 0.5, 0.0, p)  # fully-masked rows -> 0
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhij,bhjd->bhid", p, v_r.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    def step(r, carry):
        k_r, v_r, m, l, acc = carry
        m, l, acc = accumulate(r, k_r, v_r, m, l, acc)
        # rotate k/v to the next device; overlaps with the next step's
        # compute under XLA's async collective scheduling
        k_nxt = jax.lax.ppermute(k_r, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_r, axis_name, perm)
        return k_nxt, v_nxt, m, l, acc

    # sp-1 rotations; the final chunk is consumed without a (dead) rotation
    k_r, v_r, m, l, acc = jax.lax.fori_loop(0, sp - 1, step,
                                            (k, v, m0, l0, acc0))
    m, l, acc = accumulate(sp - 1, k_r, v_r, m, l, acc)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / l_safe).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh: Mesh, *, sp_axis: str = "sp",
                           dp_axis: Optional[str] = "dp",
                           pattern: Optional[AttnPattern] = None,
                           causal: bool = True) -> jax.Array:
    """Standalone wrapper: q/k/v are global [b, h, n, dh]; the sequence dim
    is sharded over `sp_axis` (and batch over `dp_axis` if present)."""
    dp = dp_axis if dp_axis and dp_axis in mesh.axis_names else None
    spec = P(dp, None, sp_axis, None)

    fn = partial(ring_attention, axis_name=sp_axis, pattern=pattern,
                 causal=causal)
    sharded = shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return sharded(q, k, v)
