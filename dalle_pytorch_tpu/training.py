"""Jit-compiled train steps for DiscreteVAE and DALLE.

The reference's training loop shape (forward -> backward -> allreduce ->
step, `train_vae.py:165-236`, `train_dalle.py:357-416`) collapses on TPU
into a single jitted function per model: loss + grads + optimizer update in
one XLA program, with gradient all-reduce inserted by GSPMD from the input
shardings.  Optimizer is optax Adam wrapped in ``inject_hyperparams`` so the
host-side schedules (utils/schedule.py) can set the lr between steps without
retracing — replacing torch's stateful ``ExponentialLR`` /
``ReduceLROnPlateau`` and the DeepSpeed engine's fused step.

Training health (utils/guardrails.py): every factory takes ``health=True``
to additionally return an on-device health vector — loss, global grad
norm, finite flag, computed *inside* the jitted step (no host syncs in
traced code) — and, with ``guard=True``, to suppress the optimizer update
by ``jnp.where`` masking when the gradients are non-finite, so one
pathological batch can never poison params/opt_state.  Health-enabled
steps take one extra traced scalar, ``fault_scale``, multiplying the loss
before differentiation: 1.0 in production, NaN / a spike factor under the
``grad_nan``/``loss_spike`` GRAFT_FAULTS sites (guardrails.fault_scale_for)
so the chaos suites poison the *real* gradients without retracing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import shard_map

from .obs import prof
from .ops.attention import kernel_mesh
from .utils import guardrails


def _adam_chain(learning_rate, grad_clip_norm=0.0):
    steps = []
    if grad_clip_norm and float(grad_clip_norm) > 0:
        steps.append(optax.clip_by_global_norm(float(grad_clip_norm)))
    steps.append(optax.adam(learning_rate=learning_rate))
    return optax.chain(*steps)


def make_optimizer(learning_rate: float, grad_clip_norm: float = 0.0):
    """Adam, matching the reference's torch.optim.Adam defaults
    (train_dalle.py:284, train_vae.py:123), with optional global-norm clip
    (train_dalle.py:371-372).  The lr is an injected hyperparam so host-side
    schedules can change it without retracing."""
    return optax.inject_hyperparams(_adam_chain, static_args=("grad_clip_norm",))(
        learning_rate=learning_rate, grad_clip_norm=grad_clip_norm)


def set_learning_rate(opt_state, lr: float):
    """Host-side lr override for the next steps (plateau/exp schedules)."""
    opt_state.hyperparams["learning_rate"] = jnp.asarray(lr, dtype=jnp.float32)
    return opt_state


def _pin_update_shardings(partitioner, params, opt_state):
    """Constrain the updated params/opt_state to the Partitioner's input
    sharding rules.  Without this, GSPMD output-sharding propagation is
    free to place some updated leaves differently from their inputs — and
    jax silently DROPS buffer donation for exactly those leaves (graftspmd
    S2 caught ~2/3 of the donated leaves losing their aliases under the tp
    plan), so those params/opt_state buffers live twice across the
    update.

    The pin derives from the SAME Partitioner (itself built from the run's
    declarative ParallelPlan, parallel/plan.py) that sharded the inputs at
    init and restore — this function holds no sharding table of its own,
    so the three former hand-kept copies of the contract cannot drift."""
    if partitioner is None:
        return params, opt_state
    params = jax.lax.with_sharding_constraint(
        params, partitioner.param_shardings(params))
    opt_state = jax.lax.with_sharding_constraint(
        opt_state, partitioner.param_shardings(opt_state))
    return params, opt_state


def make_vae_train_step(vae, tx, donate: bool = True, health: bool = False,
                        guard: bool = True, partitioner=None):
    """(params, opt_state, images, rng, temp) -> (params, opt_state, loss, recons).

    `temp` is a traced scalar so the gumbel temperature anneal
    (train_vae.py:211-217) never retraces.  With ``health=True`` the step
    takes a trailing ``fault_scale`` scalar and additionally returns the
    on-device health vector (module docstring).  ``partitioner`` (the
    run's mesh Partitioner) pins the updated params/opt_state to the
    input sharding rules so donation survives GSPMD propagation.
    """

    def train_step(params, opt_state, images, rng, temp, *fault_scale):
        def loss_fn(p):
            loss, recons = vae.apply(
                {"params": p}, images, rng=rng, return_loss=True,
                return_recons=True, temp=temp)
            if health:
                loss = loss * fault_scale[0]
            return loss, recons

        (loss, recons), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        if health:
            with prof.scope("optimizer"):
                params, opt_state, hv = guardrails.guarded_update(
                    tx, grads, opt_state, params, loss=loss, guard=guard)
                params, opt_state = _pin_update_shardings(partitioner, params,
                                                          opt_state)
            return params, opt_state, loss, recons, hv
        with prof.scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            params, opt_state = _pin_update_shardings(partitioner, params,
                                                      opt_state)
        return params, opt_state, loss, recons

    return jax.jit(train_step, donate_argnums=(0, 1) if donate else ())


def _dalle_loss(dalle, params, text, codes, rng):
    """Training loss incl. the MoE load-balance aux when the model routes
    its FFs through experts (the sown 'losses' collection would silently
    vanish without mutable=['losses'])."""
    if dalle.cfg.ff_experts > 1:
        loss, state = dalle.apply(
            {"params": params}, text, codes, return_loss=True,
            deterministic=False, rngs={"dropout": rng}, mutable=["losses"])
        aux = sum(jax.tree.leaves(state["losses"]))
        return loss + dalle.cfg.ff_aux_weight * aux
    return dalle.apply({"params": params}, text, codes, return_loss=True,
                       deterministic=False, rngs={"dropout": rng})


def make_dalle_train_step(dalle, tx, vae=None, donate: bool = True,
                          jit: bool = True, health: bool = False,
                          guard: bool = True, partitioner=None):
    """DALLE step.  If `vae` is given, batches carry raw images and the
    (frozen) VAE encodes them to codes inside the step, mirroring the
    reference's in-forward `vae.get_codebook_indices` under no_grad
    (dalle_pytorch.py:459, :144-149); otherwise batches carry codes.

    ``jit=False`` returns the raw function (for embedding in a larger jitted
    program, e.g. a scan-of-steps benchmark loop).  With ``health=True``
    the step takes a trailing ``fault_scale`` scalar and additionally
    returns the on-device health vector (module docstring).
    ``partitioner`` (the run's mesh Partitioner) pins the updated
    params/opt_state to the input sharding rules so donation survives
    GSPMD propagation, and tells the attention kernel which mesh axes its
    call is split over.
    """

    def train_step(params, opt_state, vae_params, text, images_or_codes,
                   rng, *fault_scale):
        if vae is not None:
            codes = vae.apply({"params": vae_params}, images_or_codes,
                              method=type(vae).get_codebook_indices)
            codes = jax.lax.stop_gradient(codes)
        else:
            codes = images_or_codes

        def loss_fn(p):
            loss = _dalle_loss(dalle, p, text, codes, rng)
            return loss * fault_scale[0] if health else loss

        # the attention kernel's call is split over the plan's mesh, forward
        # and backward (ops/attention.py::kernel_mesh)
        with kernel_mesh(partitioner):
            loss, grads = jax.value_and_grad(loss_fn)(params)
        if health:
            with prof.scope("optimizer"):
                params, opt_state, hv = guardrails.guarded_update(
                    tx, grads, opt_state, params, loss=loss, guard=guard)
                params, opt_state = _pin_update_shardings(partitioner, params,
                                                          opt_state)
            return params, opt_state, loss, hv
        with prof.scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            params, opt_state = _pin_update_shardings(partitioner, params,
                                                      opt_state)
        return params, opt_state, loss

    if not jit:
        return train_step
    return jax.jit(train_step, donate_argnums=(0, 1) if donate else ())


def make_dalle_sp_train_step(dalle, tx, mesh, dp_axis: str = "dp",
                             donate: bool = True, health: bool = False,
                             guard: bool = True):
    """Sequence-parallel DALLE step: the loss runs inside a ``shard_map``
    over (dp, sp) — batch sharded over ``dp_axis``, the sequence over
    ``cfg.ring_axis`` with ring/Ulysses collectives making attention exact
    (parallel/ring.py, parallel/ulysses.py), params replicated.  Output-
    equivalent to the dense step (DALLE._sp_loss psums the per-shard phase
    CE against global positions); the backward differentiates straight
    through the shard_map (ppermute/all-to-all have transpose rules).

    The reference's only strategy is DP (SURVEY.md §2.2); this is how the
    framework trains sequences a single chip's HBM can't hold.
    """
    from jax.sharding import PartitionSpec as P

    cfg = dalle.cfg
    axis = cfg.ring_axis
    assert axis is not None and cfg.sp_size > 1, (
        "sequence-parallel step needs cfg.ring_axis + cfg.sp_size > 1 "
        "(set DALLEConfig(ring_axis='sp', sp_size=N))")
    assert axis in mesh.axis_names and mesh.shape[axis] == cfg.sp_size, (
        f"mesh axis {axis!r} of size {cfg.sp_size} required, "
        f"got mesh {dict(mesh.shape)}")
    assert cfg.ff_experts <= 1, (
        "combining MoE with sequence parallelism is not supported")

    def global_loss(params, text, codes, rng):
        def local(params, text, codes, rng):
            # decorrelate dropout across sequence shards (same key + same
            # local shape would otherwise draw identical masks per shard)
            rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
            loss = dalle.apply({"params": params}, text, codes,
                               return_loss=True, deterministic=False,
                               rngs={"dropout": rng})
            if health:
                # the skip decision must be COLLECTIVE: the per-shard
                # losses are genuinely different values, so the finite
                # flags are pmin-combined over the whole (dp, sp) mesh —
                # every shard sees the same verdict or they would diverge
                # (the average_and_poll pattern, on device)
                ok = guardrails.collective_all_finite(loss, (dp_axis, axis))
                return jax.lax.pmean(loss, dp_axis), ok
            return jax.lax.pmean(loss, dp_axis)

        out_specs = (P(), P()) if health else P()  # graftlint: disable=PLAN001 (shard_map arg placement for the sp step — batch over dp, params replicated; not a param-tree sharding, so the rule table does not apply)
        return shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(dp_axis), P(dp_axis), P()),  # graftlint: disable=PLAN001 (same: per-arg shard_map specs, not PARTITION_RULES territory)
            out_specs=out_specs, check_vma=False)(params, text, codes, rng)

    def train_step(params, opt_state, _vae_params, text, codes, rng,
                   *fault_scale):
        if health:
            def loss_fn(p):
                loss, ok = global_loss(p, text, codes, rng)
                return loss * fault_scale[0], ok

            (loss, ok), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            with prof.scope("optimizer"):
                params, opt_state, hv = guardrails.guarded_update(
                    tx, grads, opt_state, params, loss=loss, extra_ok=ok,
                    guard=guard)
            return params, opt_state, loss, hv
        loss, grads = jax.value_and_grad(global_loss)(params, text, codes, rng)
        with prof.scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(train_step, donate_argnums=(0, 1) if donate else ())


def make_dalle_pp_train_step(dalle, tx, params, mesh, *,
                             num_microbatches: int, pp_axis: str = "pp",
                             dp_axis: str = "dp", donate: bool = True,
                             health: bool = False, guard: bool = True):
    """Pipeline-parallel DALLE step (GPipe schedule, parallel/pipeline.py).

    The transformer stack — where the params and FLOPs are — is cut into
    ``mesh.shape[pp_axis]`` stages; embeddings and the logits head run
    replicated outside the pipeline (they are a few percent of the work).
    Returns ``(train_step, pp_params)`` where ``pp_params`` is the
    restructured tree ``{'outer': <non-transformer params>, 'stages':
    <stage-stacked transformer params>}`` the step trains on; convert back
    with :func:`pp_params_to_dense` for checkpoints/sampling.
    """
    from .models.dalle import DALLE, transformer_kwargs
    from .ops.transformer import Transformer
    from .parallel.pipeline import pipeline_transformer

    cfg = dalle.cfg
    assert cfg.trunk is None, (
        "the pipeline step stacks identical (attn, ff) stages; a TrunkSpec "
        "trunk's layers differ in kind (mixers, cache lengths) and a routed "
        "layer's two halves share its router logits")
    tf = Transformer(**transformer_kwargs(cfg))
    _, stacked, apply_fn = pipeline_transformer(
        tf, params["transformer"], mesh=mesh, pp_axis=pp_axis,
        num_microbatches=num_microbatches, dp_axis=dp_axis)
    pp_params = {"outer": {k: v for k, v in params.items()
                           if k != "transformer"},
                 "stages": stacked}

    def loss_fn(p, text, codes):
        tokens = dalle.apply({"params": p["outer"]}, text, codes,
                             method=DALLE.embed_sequence)
        # "pipeline" charges the schedule machinery (microbatch buffers,
        # ppermute shifts); the blocks' own scopes win inside (innermost
        # graftprof frame takes the eqn)
        with prof.scope("pipeline"):
            h = apply_fn(p["stages"], tokens)
        return dalle.apply({"params": p["outer"]}, h, text, codes,
                           method=DALLE.loss_from_hidden)

    def train_step(pp_params, opt_state, _vae_params, text, codes, _rng,
                   *fault_scale):
        def scaled(p, text, codes):
            loss = loss_fn(p, text, codes)
            return loss * fault_scale[0] if health else loss

        loss, grads = jax.value_and_grad(scaled)(pp_params, text, codes)
        if health:
            # grads/loss here are jit-level global values (GSPMD reduces
            # them identically on every host and stage), so the plain
            # sentinel is already a collective decision
            with prof.scope("optimizer"):
                pp_params, opt_state, hv = guardrails.guarded_update(
                    tx, grads, opt_state, pp_params, loss=loss, guard=guard)
            return pp_params, opt_state, loss, hv
        with prof.scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, pp_params)
            pp_params = optax.apply_updates(pp_params, updates)
        return pp_params, opt_state, loss

    return (jax.jit(train_step, donate_argnums=(0, 1) if donate else ()),
            pp_params)


def pp_params_to_dense(dalle, pp_params, mesh, pp_axis: str = "pp"):
    """Invert the pipeline restructuring: ``{'outer', 'stages'}`` back to
    the standard DALLE param tree (for checkpoints and the sampler)."""
    from .parallel.pipeline import unstack_stage_params

    dense = dict(pp_params["outer"])
    dense["transformer"] = unstack_stage_params(
        pp_params["stages"], dalle.cfg.depth, mesh.shape[pp_axis])
    return dense


def make_clip_train_step(clip, tx, donate: bool = True, health: bool = False,
                         guard: bool = True, partitioner=None):
    """CLIP contrastive step (text/image towers, symmetric CE).
    ``partitioner`` pins the updated params/opt_state to the input
    sharding rules so donation survives GSPMD propagation."""
    def train_step(params, opt_state, text, images, text_mask, *fault_scale):
        def loss_fn(p):
            loss = clip.apply({"params": p}, text, images,
                              text_mask=text_mask, return_loss=True)
            return loss * fault_scale[0] if health else loss

        # the attention kernel's call is split over the plan's mesh, forward
        # and backward (ops/attention.py::kernel_mesh)
        with kernel_mesh(partitioner):
            loss, grads = jax.value_and_grad(loss_fn)(params)
        if health:
            with prof.scope("optimizer"):
                params, opt_state, hv = guardrails.guarded_update(
                    tx, grads, opt_state, params, loss=loss, guard=guard)
                params, opt_state = _pin_update_shardings(partitioner, params,
                                                          opt_state)
            return params, opt_state, loss, hv
        with prof.scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            params, opt_state = _pin_update_shardings(partitioner, params,
                                                      opt_state)
        return params, opt_state, loss

    return jax.jit(train_step, donate_argnums=(0, 1) if donate else ())


# Every train-step factory in this module, by name.  tools/spmd_check.py
# (the graftspmd analyzer) traces each entry under every applicable
# parallelism plan — collective order, donation audit, retrace sentinel,
# static HBM budget — and asserts its harness coverage matches THIS
# registry exactly, so a new factory cannot land unanalyzed.
STEP_FACTORIES = {
    "vae": make_vae_train_step,
    "dalle": make_dalle_train_step,
    "dalle_sp": make_dalle_sp_train_step,
    "dalle_pp": make_dalle_pp_train_step,
    "clip": make_clip_train_step,
}
