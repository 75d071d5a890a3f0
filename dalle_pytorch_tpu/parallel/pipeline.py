"""Pipeline parallelism — GPipe-style microbatched stage execution.

The reference has no pipeline parallelism (SURVEY.md §2.2: DP is its only
strategy); this module is scaling headroom the TPU mesh design reserves
alongside dp/fsdp/tp (mesh.py) and sp (ring.py / ulysses.py).

Design: the layer stack is cut into ``pp`` equal stages; each device on the
``pp`` mesh axis holds one stage's params (leading-axis sharded).  Inside a
``shard_map``, a `lax.scan` runs the classic GPipe schedule: at step ``t``
stage ``s`` computes microbatch ``t - s`` (bubbles at the edges), then
hands its activation to stage ``s+1`` via a neighbor `lax.ppermute` — the
point-to-point transfer rides one ICI hop, exactly like the k/v rotation
in ring attention.  Everything is differentiable (`scan` + `ppermute` have
transpose rules), so one `jax.grad` over the wrapped function trains the
whole pipeline; per-step `jax.checkpoint` keeps activation memory at
O(microbatches + steps·stage_depth) instead of O(steps·depth).

The stage function must be *uniform* across stages (same param pytree
structure), which holds for this framework's Transformer whenever
``depth % pp == 0`` and the attention-type cycle length divides the stage
depth — true for the CUB config (cycle 4, depth 8: each stage is one full
[full, axial_row, axial_col, conv_like] cycle).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(params: dict, depth: int, pp: int,
                       layer_prefixes: tuple = ("layers_{i}_attn",
                                                "layers_{i}_ff")) -> dict:
    """Restructure a Transformer param tree (flat ``layers_{i}_attn`` /
    ``layers_{i}_ff`` children) into a stage-stacked tree: the same names
    re-indexed per stage (``i`` in [0, depth/pp)), every leaf gaining a
    leading ``pp`` axis to shard over the pipeline mesh axis."""
    assert depth % pp == 0, f"depth {depth} not divisible by pp {pp}"
    per = depth // pp
    out: dict = {}
    for local in range(per):
        for prefix in layer_prefixes:
            name_local = prefix.format(i=local)
            stages = [params[prefix.format(i=stage * per + local)]
                      for stage in range(pp)]
            out[name_local] = jax.tree.map(
                lambda *leaves: jnp.stack(leaves), *stages)
    # non-layer params (none in Transformer today) would need replication;
    # be loud rather than silently dropping them.
    layer_names = {prefix.format(i=i) for prefix in layer_prefixes
                   for i in range(depth)}
    extra = set(params) - layer_names
    assert not extra, f"non-layer params not supported in pipeline: {extra}"
    return out


def unstack_stage_params(stacked: dict, depth: int, pp: int,
                         layer_prefixes: tuple = ("layers_{i}_attn",
                                                  "layers_{i}_ff")) -> dict:
    """Inverse of :func:`stack_stage_params`: stage-stacked leaves (leading
    ``pp`` axis) back to the flat ``layers_{i}_*`` tree — for writing
    standard checkpoints and running the (non-pipelined) sampler."""
    assert depth % pp == 0, f"depth {depth} not divisible by pp {pp}"
    per = depth // pp
    out: dict = {}
    for local in range(per):
        for prefix in layer_prefixes:
            stacked_leaf = stacked[prefix.format(i=local)]
            for stage in range(pp):
                out[prefix.format(i=stage * per + local)] = jax.tree.map(
                    lambda leaf, s=stage: leaf[s], stacked_leaf)
    return out


def pipeline_apply(stage_fn: Callable, stacked_params, x, *,
                   mesh: Mesh, pp_axis: str = "pp",
                   num_microbatches: int, remat: bool = True,
                   dp_axis: Optional[str] = None) -> jax.Array:
    """Run ``stage_fn`` as a ``pp``-stage GPipe pipeline over ``mesh``.

    stage_fn(stage_params, h) -> h, applied by every pipeline stage to its
    shard of ``stacked_params`` (leading axis ``pp``).  ``x`` is the global
    batch [b, n, d]; it is split into ``num_microbatches`` equal
    microbatches along axis 0.  Returns [b, n, d].
    """
    pp = mesh.shape[pp_axis]
    b = x.shape[0]
    m = num_microbatches
    assert b % m == 0, f"batch {b} not divisible by microbatches {m}"
    mb = b // m
    xs = x.reshape(m, mb, *x.shape[1:])

    body = jax.checkpoint(stage_fn) if remat else stage_fn

    def run(params, xs):
        params = jax.tree.map(lambda p: p[0], params)  # my stage's slice
        idx = jax.lax.axis_index(pp_axis)
        steps = m + pp - 1
        state0 = jnp.zeros_like(xs[0])
        out0 = jnp.zeros_like(xs)

        def step(carry, t):
            state, outs = carry
            # stage 0 ingests microbatch t (clamped during drain bubbles);
            # later stages consume the neighbor's activation
            feed = xs[jnp.minimum(t, m - 1)]
            h_in = jnp.where(idx == 0, feed, state)
            h_out = body(params, h_in)
            # the last stage completed microbatch t-(pp-1) at this step
            done = t - (pp - 1)
            outs = jax.lax.cond(
                (idx == pp - 1) & (done >= 0),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, h_out, jnp.maximum(done, 0), axis=0),
                lambda o: o, outs)
            state_next = jax.lax.ppermute(
                h_out, pp_axis, [(d, d + 1) for d in range(pp - 1)])
            return (state_next, outs), None

        (_, outs), _ = jax.lax.scan(step, (state0, out0), jnp.arange(steps))
        # only the last stage holds real outputs; broadcast them to every
        # stage so the out_spec can be pp-replicated
        outs = jax.lax.psum(
            jnp.where(idx == pp - 1, outs, jnp.zeros_like(outs)), pp_axis)
        return outs

    if dp_axis is not None:
        assert dp_axis in mesh.axis_names, (
            f"dp_axis {dp_axis!r} is not a mesh axis {mesh.axis_names}")
    # microbatch axis stays whole per stage; batch-within-microbatch on dp
    x_spec = P(None, dp_axis)
    fn = shard_map(
        run, mesh=mesh, in_specs=(P(pp_axis), x_spec), out_specs=x_spec,
        check_vma=False)
    outs = fn(stacked_params, xs)
    return outs.reshape(b, *x.shape[1:])


def pipeline_transformer(tf, params: dict, *, mesh: Mesh,
                         pp_axis: str = "pp", num_microbatches: int,
                         dp_axis: Optional[str] = None,
                         remat: bool = True):
    """Pipeline a framework Transformer: cut its depth into ``pp`` stages
    and run the GPipe schedule.  ``tf`` is the *full* Transformer module,
    ``params`` its params; returns (stage module, stacked params, apply fn)
    so callers can reuse the stacking across steps.

    Requires ``depth % pp == 0`` and the attn-type cycle to divide the
    stage depth (so every stage is structurally identical).  Executors
    whose semantics span the whole depth (reversible two-stream), per-layer
    sparse layout seeds, in-attention sequence parallelism, and dropout are
    rejected rather than silently diverging from ``tf.apply``.
    """
    pp = mesh.shape[pp_axis]
    assert tf.depth % pp == 0, f"depth {tf.depth} not divisible by pp={pp}"
    per = tf.depth // pp
    cycle = len(tf.attn_types) if tf.attn_types else 1
    assert per % cycle == 0, (
        f"stage depth {per} must be a multiple of the attn-type cycle "
        f"{cycle} so all stages share one structure")
    attn_types = tf.attn_types or ("full",)
    assert "sparse" not in attn_types, (
        "pipeline stages re-derive sparse layouts from stage-local layer "
        "indices, diverging from the full model's per-layer seeds; "
        "pipelining the 'sparse' variant is not supported")
    assert not tf.reversible, (
        "the reversible two-stream executor spans the whole depth and "
        "cannot be cut into independent stages")
    assert tf.ring_axis is None, (
        "combining in-attention sequence parallelism with pipelining is "
        "not supported")
    assert tf.attn_dropout == 0 and tf.ff_dropout == 0, (
        "pipeline stages run deterministically; dropout would be silently "
        "disabled")
    assert tf.ff_experts <= 1, (
        "pipeline stages apply without mutable collections, so the MoE "
        "load-balance aux losses would silently vanish")

    # clone so every other field (dtype, remat, ...) carries over
    stage = tf.clone(depth=per, name=None)
    stacked = stack_stage_params(params, tf.depth, pp)

    def stage_fn(stage_params, h):
        return stage.apply({"params": stage_params}, h)

    def apply_fn(stacked_params, x):
        return pipeline_apply(
            stage_fn, stacked_params, x, mesh=mesh, pp_axis=pp_axis,
            num_microbatches=num_microbatches, dp_axis=dp_axis, remat=remat)

    return stage, stacked, apply_fn
