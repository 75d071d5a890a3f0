"""Ulysses attention — all-to-all sequence/context parallelism.

The second of the framework's two exact sequence-parallel schemes (the
first, k/v-rotation ring attention, lives in ``parallel/ring.py``).  The
reference has no long-context machinery at all (SURVEY.md §5.7); on TPU we
treat the sequence as a shardable axis and let the user pick the scheme
that matches their mesh:

* **ring** — O(sp) neighbor `ppermute` hops; bandwidth rides the ICI ring,
  per-device memory O(n_local²).  Best when `sp` is large and heads are few.
* **ulysses** (this module, after DeepSpeed-Ulysses, arXiv:2309.14509) —
  two `all_to_all` collectives re-shard the *sequence* axis into the *head*
  axis, so each device computes full-sequence attention for `h / sp` heads,
  then the inverse all-to-all restores sequence sharding.  Communication is
  O(1) collectives per layer regardless of `sp`; requires ``heads % sp ==
  0``.  Best when heads are plentiful (h >= sp) and the per-device full
  [n, n] score tile fits, i.e. moderate n scaled over many heads.

Both schemes are exact (bitwise-independent of `sp` up to float
reassociation), differentiable (all_to_all's transpose is the inverse
all_to_all), and reuse the same `AttnPattern` predicate as every other
attention in the framework, so the DALLE variants (full / axial / conv_like
/ sparse) all run sequence-parallel.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import AttnPattern
from .ring import NEG_INF, _chunk_mask


def ulysses_attention(q, k, v, *, axis_name: str,
                      pattern: Optional[AttnPattern] = None,
                      causal: bool = True) -> jax.Array:
    """Exact attention over a sequence sharded on ``axis_name`` via
    head<->sequence all-to-all re-sharding.

    q/k/v: local shards [b, h, n_local, dh] (full heads, 1/sp of the
    sequence, contiguous chunks ordered by axis index).  Returns the local
    output shard [b, h, n_local, dh].  Requires ``h % sp == 0``.
    """
    sp = jax.lax.psum(1, axis_name)
    b, h, nl, dh = q.shape
    assert h % sp == 0 if isinstance(sp, int) else True, (
        f"ulysses needs heads ({h}) divisible by the sp axis size")
    scale = dh ** -0.5
    layout = None
    if pattern is not None and pattern.variant == "sparse":
        layout = jnp.asarray(pattern.block_layout())

    # one collective in: [3, b, h, n_local, dh] -> [3, b, h/sp, n, dh]
    # (scatter heads, gather sequence)
    qg, kg, vg = jax.lax.all_to_all(
        jnp.stack([q, k, v]), axis_name, split_axis=2, concat_axis=3,
        tiled=True)
    n = qg.shape[2]

    s = jnp.einsum("bhid,bhjd->bhij", qg.astype(jnp.float32) * scale,
                   kg.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    allow = _chunk_mask(pattern, causal, 0, 0, n, n, layout=layout)
    s = jnp.where(allow[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(allow[None, None], p, 0.0)  # fully-masked rows -> 0
    out = jnp.einsum("bhij,bhjd->bhid", p, vg.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    # one collective out: split the sequence back, gather heads
    return jax.lax.all_to_all(out.astype(q.dtype), axis_name, split_axis=2,
                              concat_axis=1, tiled=True)


def ulysses_attention_sharded(q, k, v, mesh: Mesh, *, sp_axis: str = "sp",
                              dp_axis: Optional[str] = "dp",
                              pattern: Optional[AttnPattern] = None,
                              causal: bool = True) -> jax.Array:
    """Standalone wrapper: q/k/v are global [b, h, n, dh]; the sequence dim
    is sharded over `sp_axis` (and batch over `dp_axis` if present)."""
    dp = dp_axis if dp_axis and dp_axis in mesh.axis_names else None
    spec = P(dp, None, sp_axis, None)

    fn = partial(ulysses_attention, axis_name=sp_axis, pattern=pattern,
                 causal=causal)
    sharded = shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return sharded(q, k, v)
