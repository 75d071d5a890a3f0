"""Deliberately-broken step programs — the teeth-proof for graftspmd.

One fixture per analysis, each reproducing the bug class its analysis
exists to catch (mirrors the broken-model pattern of
tests/test_contract_check.py): a data-dependent ``ppermute`` (S1 SPMD
deadlock), a train step built without donation (S2 doubled HBM), a step
whose static arg is a fresh object per call and one whose static arg is a
list (S3 recompile storm / cache defeat), and a plan gated against a chip
it cannot fit (S4).  Used by tests/test_spmd_check.py and by
``tools/spmd_check.py --selftest``; never imported by production code.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import shard_map


# --- S1: a collective dominated by data-dependent control flow ------------


def make_conditional_collective_step(mesh, axis: str = "dp"):
    """A shard_map'd step whose ``ppermute`` only runs when the local batch
    mean is positive — a data-dependent predicate that can disagree across
    shards, leaving part of the mesh blocked in a collective its peers
    never enter.  The canonical SPMD deadlock."""
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def local(x):
        def rotate(v):
            return jax.lax.ppermute(v, axis, perm)

        # divergent predicate: each shard sees its OWN slice's statistics
        return jax.lax.cond(jnp.mean(x) > 0.0, rotate, lambda v: v, x)

    # graftlint: disable=DON001 (stateless S1 toy step: nothing to donate)
    return jax.jit(shard_map(local, mesh=mesh, in_specs=(P(axis),),
                             out_specs=P(axis), check_vma=False))


def make_branch_matched_collective_step(mesh, axis: str = "dp"):
    """The clean twin: both branches issue the IDENTICAL collective
    sequence, so shards stay in lockstep whichever branch each takes
    (the parallel/pipeline.py drain-bubble pattern).  Must PASS S1."""
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def local(x):
        def fwd(v):
            return jax.lax.ppermute(v, axis, perm) * 2.0

        def bwd(v):
            return jax.lax.ppermute(v, axis, perm) * 0.5

        return jax.lax.cond(jnp.mean(x) > 0.0, fwd, bwd, x)

    # graftlint: disable=DON001 (stateless S1 toy step: nothing to donate)
    return jax.jit(shard_map(local, mesh=mesh, in_specs=(P(axis),),
                             out_specs=P(axis), check_vma=False))


# --- S2: a dropped donation -----------------------------------------------


def make_undonated_train_step(tx):
    """A params/opt_state update jitted WITHOUT ``donate_argnums`` — the
    forgotten-donation bug: params and opt_state are live twice across the
    step (inputs held by the caller, outputs fresh buffers)."""
    import optax

    def train_step(params, opt_state, batch):
        def loss_fn(p):
            pred = batch @ p["w"] + p["b"]
            return jnp.mean(pred ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    # graftlint: disable=DON001 (the broken S2 fixture: the dropped donation IS the bug check_donation must catch)
    return jax.jit(train_step)


def fixture_params(dim: int = 64):
    params = {"w": jnp.zeros((dim, dim), jnp.float32),
              "b": jnp.zeros((dim,), jnp.float32)}
    return params


# --- S3: weak-hash / unhashable static args -------------------------------


@dataclasses.dataclass(eq=False)
class WeakHashSchedule:
    """Hashes by identity (eq=False): two equal-valued instances are
    different jit cache keys, so rebuilding it per step — the natural way
    to write a schedule — retraces every call."""

    lr: float


def make_retracing_step():
    """A step whose schedule rides in as a static arg and is rebuilt per
    call: every invocation is a cache miss (the recompile storm S3
    exists to catch).  Returns ``(jitted, make_args)``."""

    def step(x, sched):
        return x * sched.lr

    # graftlint: disable=DON001 (stateless S3 toy step: nothing to donate)
    jitted = jax.jit(step, static_argnums=(1,))

    def make_args(i):
        return (jnp.ones((4,), jnp.float32) * (i + 1),
                WeakHashSchedule(lr=1e-3))  # fresh object per step

    return jitted, make_args


def make_unhashable_static_step():
    """The list-keyed variant: a list static arg cannot hash at all, so
    the call never reaches the cache — jax raises instead.  Returns
    ``(jitted, make_args)``."""

    def step(x, dims):
        return x.reshape(dims)

    # graftlint: disable=DON001 (stateless S3 toy step: nothing to donate)
    jitted = jax.jit(step, static_argnums=(1,))

    def make_args(i):
        return jnp.ones((4,), jnp.float32), [2, 2]  # list: unhashable

    return jitted, make_args


def make_stable_step():
    """The clean twin: schedule values ride as traced scalars; N steps,
    one trace.  Must PASS S3."""

    def step(x, lr):
        return x * lr

    # graftlint: disable=DON001 (stateless S3 toy step: nothing to donate)
    jitted = jax.jit(step)

    def make_args(i):
        return (jnp.ones((4,), jnp.float32) * (i + 1),
                jnp.float32(1e-3 * (i + 1)))

    return jitted, make_args


# --- S4: an oversized plan ------------------------------------------------


def oversized_step_compiled(mib: int = 64):
    """Compile a step whose arguments alone exceed ``mib`` MiB — gate it
    against a toy capacity to prove the budget check fires.  (The real
    CLI gates production plans against real chip tables; the fixture
    keeps the compile tiny.)"""

    from . import spmd

    def step(a, b):
        return a @ b

    n = 1024
    a = jax.ShapeDtypeStruct((n, n * 16), jnp.float32)  # 64 MiB
    b = jax.ShapeDtypeStruct((n * 16, 8), jnp.float32)
    with spmd.fresh_stats_compile():  # cached executables report zero stats
        return jax.jit(step).lower(a, b).compile()


# --- S1 (scan schedule): microbatch-scan collective schedules -------------


def make_pipelined_collective_scan(mesh, axis: str = "dp",
                                   length: int = 4):
    """The clean microbatch-scan shape: every iteration issues the same
    one-hop ``ppermute`` (the GPipe stage handoff), so the schedule is a
    static ``length x [ppermute]`` fact.  Must PASS
    ``scan_collective_schedule`` and report exactly that schedule."""
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def local(x):
        def body(carry, _):
            return jax.lax.ppermute(carry, axis, perm), ()

        out, _ = jax.lax.scan(body, x, None, length=length)
        return out

    return jax.jit(shard_map(local, mesh=mesh, in_specs=(P(axis),),
                             out_specs=P(axis), check_vma=False))


def make_unbalanced_microbatch_scan(mesh, axis: str = "dp",
                                    length: int = 4):
    """The anti-pattern the scan-schedule analysis exists to refuse: an
    epilogue collective folded into the LAST scan iteration via a cond
    whose other branch issues nothing — the per-iteration collective
    sequence is no longer a static fact (it depends on the traced
    iteration index), so no ``iteration-count x per-iteration`` schedule
    exists and shards whose predicates disagree deadlock.  Must FAIL
    ``scan_collective_schedule``."""
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def local(x):
        def body(carry, t):
            def epilogue(v):
                return jax.lax.psum(
                    jax.lax.ppermute(v, axis, perm), axis)

            carry = jax.lax.cond(t == length - 1, epilogue,
                                 lambda v: v * n, carry)
            return carry, ()

        out, _ = jax.lax.scan(body, x, jnp.arange(length))
        return out

    return jax.jit(shard_map(local, mesh=mesh, in_specs=(P(axis),),
                             out_specs=P(axis), check_vma=False))


# --- S3 (serve): a shape-changing decode tick -----------------------------


def make_shape_changing_serve_tick(num_slots: int = 4):
    """The continuous-batching anti-pattern the serve arena exists to
    prevent: a decode tick whose cache tensors are CROPPED to the current
    occupancy ("why compute the idle slots?").  Every occupancy change is
    a new shape, so admitting or retiring one request recompiles the tick
    — on a real pod that is a recompile per arrival, the exact storm the
    S3 serve gate (tools/spmd_check.py serve-tick harness) pins the real
    arena against.  Returns ``(jitted, make_args)``: ``make_args(i)``
    cycles through occupancies 1..num_slots like an admit/retire churn.
    Must FAIL check_single_trace."""

    def tick(caches, codes):
        return caches + 1.0, codes + 1

    jitted = jax.jit(tick)

    def make_args(i):
        n = (i % num_slots) + 1  # occupancy churn: 1, 2, ..., S, 1, ...
        return (jnp.zeros((n, 8, 16), jnp.float32),
                jnp.zeros((n,), jnp.int32))

    return jitted, make_args
