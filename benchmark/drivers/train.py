"""The trainer's loop, as ``train_dalle.py`` runs it: one jitted step per call
on raw images with the frozen VAE inside the step, a fresh dropout key split
per step, and the loss of step k fetched after step k+1 was dispatched.

Traffic parameters: ``plan`` (a ``ParallelPlan`` spec, over the cell's
chips), ``global_batch``, ``distinct_batches`` (cycled), ``learning_rate``,
``text``, ``warmup_steps``, ``trace_steps``, and optionally
``reference_step`` (``{"micro_batch": k}``: hold the first step to the plain
reference, k images at a time; for configurations without dropout).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import checks, harness


def build(cell, devices, dalle_cfg, vae_cfg):
    """Partitioner, optimizer, the jitted step and its abstract arguments
    (shapes with shardings): the same for the run on the chip and for the
    compile against described devices (``tools/aot_rehearse.py``).  After
    ``chip_smoke.plan_step``, with the VAE in the step as the trainer has it.
    """
    from dalle_pytorch_tpu.parallel.plan import ParallelPlan
    from dalle_pytorch_tpu.training import (make_dalle_train_step,
                                            make_optimizer)

    tr = cell.traffic
    part = ParallelPlan.parse(tr["plan"]).partitioner(devices=list(devices))
    dalle, vae, init_dalle, init_vae = harness.init_fns(dalle_cfg, vae_cfg)
    tx = make_optimizer(float(tr["learning_rate"]))
    batch = int(tr["global_batch"])
    key = jax.random.PRNGKey(0)
    p_shapes = jax.eval_shape(init_dalle, key)
    shard = part.param_shardings

    def with_shardings(shapes, shardings):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, shardings)

    o_shapes = jax.eval_shape(tx.init, p_shapes)
    v_shapes = jax.eval_shape(init_vae, key)
    size = vae_cfg.image_size
    abstract = (
        with_shardings(p_shapes, shard(p_shapes)),
        with_shardings(o_shapes, shard(o_shapes)),
        jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=part.repl_sharding), v_shapes),
        jax.ShapeDtypeStruct((batch, dalle_cfg.text_seq_len), jnp.int32,
                             sharding=part.data_sharding),
        jax.ShapeDtypeStruct((batch, size, size, 3), jnp.float32,
                             sharding=part.data_sharding),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=part.repl_sharding))
    step = make_dalle_train_step(dalle, tx, vae=vae, partitioner=part)
    return dict(part=part, tx=tx, step=step, abstract=abstract, vae=vae,
                init_dalle=init_dalle, init_vae=init_vae, p_shapes=p_shapes)


def run(cell, devices, dalle_cfg, vae_cfg, seed, seconds, tracer, mark_ready):
    tr = cell.traffic
    b = build(cell, devices, dalle_cfg, vae_cfg)
    part, tx = b["part"], b["tx"]
    batch, n_batches = int(tr["global_batch"]), int(tr["distinct_batches"])
    root = jax.random.PRNGKey(seed)
    k_model, k_vae, k_data, rng = jax.random.split(root, 4)

    # weights, optimizer state and images are made on the device, each in
    # one jitted call from the seed, already in their shardings
    params = jax.jit(b["init_dalle"],
                     out_shardings=part.param_shardings(b["p_shapes"]))(k_model)
    vae_params = jax.jit(b["init_vae"],
                         out_shardings=part.repl_sharding)(k_vae)
    opt_state = part.init_opt_state(tx, params)
    size = vae_cfg.image_size
    make_images = jax.jit(
        lambda k: jax.random.uniform(k, (batch, size, size, 3), jnp.float32),
        out_shardings=part.data_sharding)
    image_batches = [make_images(k)
                     for k in jax.random.split(k_data, n_batches)]
    prompts = harness.make_prompts(cell, dalle_cfg, n_batches * batch, seed)
    texts = [part.shard_batch(prompts[i * batch:(i + 1) * batch])
             for i in range(n_batches)]
    rng = part.replicate(rng)
    split = jax.jit(lambda k: tuple(jax.random.split(k)),
                    out_shardings=(part.repl_sharding, part.repl_sharding))

    compiled = b["step"].lower(*b["abstract"]).compile()

    losses, step_ends = [], []
    state = dict(params=params, opt=opt_state, rng=rng, n=0, pending=None)

    def one_step():
        """Dispatch step n, then fetch step n-1's loss (the trainer's
        ``flush(pending)``): the host runs one step ahead of the device."""
        i = state["n"] % n_batches
        with tracer.span("bench:train_step"):
            state["rng"], step_rng = split(state["rng"])
            state["params"], state["opt"], loss = compiled(
                state["params"], state["opt"], vae_params, texts[i],
                image_batches[i], step_rng)
        with tracer.span("bench:loss_fetch"):
            if state["pending"] is not None:
                losses.append(float(jax.device_get(state["pending"])))
                step_ends.append(time.perf_counter())
        state["pending"] = loss
        state["n"] += 1

    def drain():
        losses.append(float(jax.device_get(state["pending"])))
        step_ends.append(time.perf_counter())
        state["pending"] = None
        jax.block_until_ready(state["params"])

    # the first step is also the one held to the reference: keep what it
    # started from and what it added, before the next step takes the buffers
    ref = tr.get("reference_step")
    if ref:
        start = jax.tree.map(jnp.copy, params)
    one_step()
    if ref:
        update = jax.jit(lambda new, old: jax.tree.map(
            jnp.subtract, new, old))(state["params"], start)
    for _ in range(int(tr["warmup_steps"]) - 1):
        one_step()
    drain()

    mark_ready()
    t0 = time.perf_counter()
    n0 = state["n"]
    while time.perf_counter() - t0 < seconds:
        one_step()
    drain()
    t1 = time.perf_counter()
    steps = state["n"] - n0
    step_ms = np.diff(step_ends[-steps:]) * 1e3
    bad_steps = int(np.sum(~np.isfinite(losses[-steps:])))

    if tracer.on:
        tracer.start()
        for _ in range(int(tr["trace_steps"])):
            one_step()
        drain()
        tracer.stop()

    # the checks run programs of their own: the cell's memory is read first
    memory_peak = harness.memory_peak_bytes(devices)
    t_check = time.perf_counter()
    verdict = checks.train_losses(dalle_cfg, losses)
    verdict["replicas"] = checks.replicas_agree(part.mesh, state["params"])
    verdict["ok"] = verdict["ok"] and verdict["replicas"]["ok"]
    if ref:
        verdict["reference_step"] = checks.reference_step(
            dalle_cfg, b["vae"], vae_cfg, start, vae_params, texts[0],
            image_batches[0], losses[0], update, int(ref["micro_batch"]))
        verdict["ok"] = verdict["ok"] and verdict["reference_step"]["ok"]
    verdict["seconds"] = time.perf_counter() - t_check
    images_per_s = steps * batch / (t1 - t0)
    return harness.Outcome(
        correct=verdict["ok"], attempted=steps,
        failed=bad_steps,
        end_to_end={"train_images_per_s": images_per_s},
        host={"step_ms_median": float(np.median(step_ms)) if len(
                  step_ms) else None,
              "images_per_s": images_per_s, "global_batch": batch,
              "steps": steps, "trace_steps": int(tr["trace_steps"]),
              "window_s": t1 - t0, "check": verdict},
        programs={"jit_train_step": compiled},
        main_program="jit_train_step", memory_peak_bytes=memory_peak,
        notes=[f"losses first {losses[0]:.4f} last {losses[-1]:.4f} over "
               f"{len(losses)} steps"])
