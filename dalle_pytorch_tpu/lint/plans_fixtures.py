"""Deliberately-broken plan contracts — the teeth-proof for graftplan.

One fixture twin per analysis, each reproducing the bug class its
analysis exists to catch (the spmd_fixtures/threads_fixtures pattern):
a param tree with a leaf no rule covers (P1 orphan), a rule table whose
order is load-bearing (P1 ambiguity), a head count the tp axis cannot
divide (P2), a param tree whose sharded state cannot fit the chip (P3),
and a step whose ``all_gather`` crosses the DCN boundary (P4).  Used by
``tests/test_plan_check.py`` and ``tools/plan_check.py --selftest``;
never imported by production code.

This file hand-builds meshes and specs on purpose — it is exempt from
PLAN001 (the ``_fixtures.py`` suffix), like every fixture module that
must construct the pathology the rule bans.
"""
from __future__ import annotations

# --- P1: an orphan leaf ----------------------------------------------------

#: A plausible new param surface (a perceiver-style bank of learned
#: latents) added without touching PARTITION_RULES: the '/'-joined path
#: matches neither a rule (not a ``kernel``/``embedding`` leaf, so even
#: the terminal catch-all misses it) nor plans.P1_REPLICATED, so every
#: mesh silently replicates its 2-D weight.  Must FAIL
#: check_rule_coverage.
ORPHAN_SHAPES = {
    "transformer/layers_0_attn/to_qkv/kernel": ((256, 3, 8, 64), 4),
    "resampler/latents": ((256, 2048), 4),
}

#: The clean twin: the same tree without the uncovered surface.  Must
#: PASS check_rule_coverage.
COVERED_SHAPES = {
    "transformer/layers_0_attn/to_qkv/kernel": ((256, 3, 8, 64), 4),
    "transformer/layers_0_ff/dense_in/kernel": ((256, 2048), 4),
}


# --- P1: a load-bearing rule order -----------------------------------------


def ambiguous_rules():
    """A rule table where a second, CONFLICTING pattern also matches the
    fused-qkv kernel — first-hit-wins silently shadows it, so whether the
    heads dim shards over tp depends on table order.  Must FAIL
    check_rule_coverage (ambiguity arm) against AMBIGUOUS_SHAPES."""
    from jax.sharding import PartitionSpec as P

    return (
        (r".*/to_qkv/kernel$", P("fsdp", None, "tp", None)),
        (r".*qkv/kernel$", P("tp", None, "fsdp", None)),  # the shadowed rival
        (r".*/kernel$", P(None, None)),                    # terminal default
    )


def benign_overlap_rules():
    """The clean twin: the second match is the TERMINAL catch-all — the
    declared default every kernel falls through to, so the overlap is the
    design, not an ambiguity.  Must PASS check_rule_coverage."""
    from jax.sharding import PartitionSpec as P

    return (
        (r".*/to_qkv/kernel$", P("fsdp", None, "tp", None)),
        (r".*/kernel$", P(None, None)),
    )


AMBIGUOUS_SHAPES = {
    "transformer/layers_0_attn/to_qkv/kernel": ((256, 3, 8, 64), 4),
}


# --- P2: an indivisible axis -----------------------------------------------

#: A to_qkv kernel with SIX heads: rule #0 shards the heads dim over tp,
#: and tp=4 does not divide 6 — mesh._prune_spec silently drops the axis
#: and the leaf replicates.  Must FAIL check_divisibility under a tp-4
#: plan (plans_fixture_plan_tp4) on an 8-device topology.
INDIVISIBLE_SHAPES = {
    "transformer/layers_0_attn/to_qkv/kernel": ((256, 3, 6, 64), 4),
}

#: The clean twin: eight heads, every sharded dim divides.  Must PASS.
DIVISIBLE_SHAPES = {
    "transformer/layers_0_attn/to_qkv/kernel": ((256, 3, 8, 64), 4),
}


# --- P3: state that cannot fit ---------------------------------------------


def overweight_cost(plans_module):
    """A synthetic PresetCost whose params alone are 4 GiB (12 GiB with
    Adam moments): under a pure-dp plan the full state is resident per
    device and the ckpt phase (2x) busts v5e-4's 0.9 x 16 GiB budget.
    Must FAIL check_hbm_fit under dp @ v5e-4 and PASS under fsdp4 (the
    leaf shards 4-way through rule #2).  ``plans_module`` is lint.plans
    (passed in to keep this module import-light)."""
    shapes = {"transformer/layers_0_ff/dense_in/kernel": ((131072, 8192), 4)}
    params = 131072 * 8192 * 4
    return plans_module.PresetCost(
        preset="fixture-overweight", batch=8, param_shapes=shapes,
        params_bytes=params, opt_bytes=2 * params,
        flops=10**12, walker_bytes=4 * params,
        walker_peak_bytes=params, resident_bytes=params,  # act term zero
        jaxpr=None, config=None)


# --- P4: a collective that crosses DCN -------------------------------------


def _dp_mesh():
    import jax
    import numpy as np

    devs = jax.devices()
    if len(devs) < 2:
        raise RuntimeError("P4 fixtures need >= 2 devices "
                           "(set --xla_force_host_platform_device_count)")
    return jax.sharding.Mesh(np.asarray(devs[:2]).reshape(2), ("dp",))


def dcn_crossing_jaxpr():
    """A step that ``all_gather``s activations over the dp axis — on a
    multi-slice topology dp is the DCN-crossing axis, and an all-gather
    there streams the whole tensor over the data-center network every
    step (the exact mistake of sharding fsdp across slices).  Must FAIL
    check_collective_placement for a dcn plan."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    def local(x):
        return jax.lax.all_gather(x, "dp").sum(axis=0)

    fn = shard_map(local, mesh=_dp_mesh(), in_specs=(P("dp"),),
                   out_specs=P("dp"), check_vma=False)
    return jax.make_jaxpr(fn)(jnp.zeros((8, 16), jnp.float32))


def dcn_clean_jaxpr():
    """The clean twin: the only dp-axis collective is the ``psum`` grad
    all-reduce — the one collective allowed to cross DCN.  Must PASS."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    def local(x):
        return jax.lax.psum(x * 2.0, "dp")

    fn = shard_map(local, mesh=_dp_mesh(), in_specs=(P("dp"),),
                   out_specs=P(), check_vma=False)
    return jax.make_jaxpr(fn)(jnp.zeros((8, 16), jnp.float32))
