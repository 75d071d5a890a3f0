"""``drivers/generate_glm_4_7_flash.py``'s closed loop (one prompt prefilled at
batch 1, its decode state tiled over the candidates, one jitted
``decode_codes`` scan named ``jit_bench_decode``, the VAE decode a chunk of
candidates at a time, images fetched to the host; the two programs compiled
ahead of time in set-up) with no prime codes, for DALL-E over the
Nemotron-3-Nano-30B-A3B trunk (Mamba-2 mixers, expert layers and one
attention layer, each layer one sublayer), held to
``benchmark/reference_nemotron_3_nano_30b_a3b.py``.

The loop is imported, not copied: :func:`run` is ``generate_glm_4_7_flash.
run`` with this module's :func:`compare` in place of its own for the one
call.  What decides ``correct``, on what the timed program produced at the
timed sizes (module constants below, each with its two readings; the checked
candidates are the first and the last row of the fan-out):

(a) teacher-forced logits through ``DALLE.prefill`` (the chunked form over
    the prompt) and ``DALLE.decode_step`` (one step a code against the
    carried state) against the reference's full forward pass (the
    per-position recurrence), at every image position, the reference using
    the experts the program chose (d);
(b) the timed codes themselves, redrawn from the reference's logits under the
    timed keys (``generate_smallthinker_21ba3b.redraw``): (a) cannot see the
    timed scan at the full fan-out, the tiled state or the sampler;
(c) **the Mamba-2 state, in the check's pass and on the timed path.**  A
    state carried in bfloat16 moves the logits less than the program's own
    rounding does, so (a) and (b) would pass it.  So the timed path runs
    once more on the checked request, as it ran in the window
    (``prefill_codes`` at batch 1, ``tile_prefill`` over the fan-out,
    ``decode_codes`` under the timed key), handing back its scan's final
    carry beside the codes; a teacher-forced pass over the codes it drew
    (``DALLE.prefill``, then ``DALLE.decode_step``) has its Mamba-2 layers
    ``sow`` what their recurrence is given (``x, B, C, delta`` at every
    position); the reference's sequential float32 rule runs over exactly
    those, and the state it leaves is compared, a head at a time as
    ``generate_olmo_hybrid_7b.state_error`` does, with the pass's own state
    in every Mamba-2 layer and with the timed path's in the layers that no
    expert layer precedes (``STATE_TOL`` says why only those).  Both are
    compiled with ``xla_allow_excess_precision`` off, for the reason given
    there: as XLA compiles by default a fused tick consumes ``x`` and ``B``
    unrounded where the trace rounds them to bfloat16, and on the CPU twin
    that alone reads 0.032, five times a bfloat16 state, against 7e-8 with
    the option off;
(d) routing: the program's own choices (what its expert layers ``sow``) are
    handed to the reference, which weights them by *its* scores and reports
    how far down its own ranking of ``score + bias`` they reach; and the
    WEIGHTS the program gave its choices (sown beside them) against the
    reference's for the same experts: the selection bias must have entered
    the choice and not the weight, and the routed scaling the weight.

Every run plants controls, and each must FAIL one of the limits above inside
``ok``: the reference with matrix operands rounded to e4m3 (the nearest
precision below bfloat16; logits and redraw, both checked rows), the
sequential rule of (c) with the state rounded to bfloat16 after every update,
and, on the first checked row, the reference with a fault planted
(``reference.FAULTS``): head ``h`` reading group ``h % G``, the gated norm
over all 4,096 channels, relu for relu^2, no shared expert, no convolution
bias, the banks of experts 16-31 in place of 0-15 (each by the logits, (a));
no routed scaling and the selection bias in the weights (each by the
weights, (d)); and the program's own choices with every expert shifted by
one, which the routing rule (d) must refuse.

Traffic parameters: ``fanout``, ``filter_thres``, ``temperature``, ``text``,
``prime_codes`` (0), ``vae_decode_chunk``, ``check_sequences``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import checks
from benchmark import reference_nemotron_3_nano_30b_a3b as reference
from benchmark.drivers import generate_glm_4_7_flash as glm
from benchmark.drivers.generate_olmo_hybrid_7b import state_error
from benchmark.drivers.generate_smallthinker_21ba3b import redraw

#: Largest |program logit - reference logit| allowed, in units of the
#: reference logits' standard deviation over the image vocabulary at that
#: position, the reference using the program's experts (d).  The program
#: multiplies bfloat16 weights and activations with float32 sums through 9
#: layers of one sublayer on a bfloat16 residual stream, the Mamba-2 state,
#: its decays and norms in float32.  Readings on the v5e (PERF.md, section
#: 6; nineteen seeds): the program's largest over 2 x 1,024 x 8,192 logits,
#: ``LOGIT_READ``; the reference with every matrix product on operands
#: rounded to e4m3 (the nearest precision below the configuration's
#: bfloat16), ``LOWPREC_READ``, which every run takes again as
#: ``lowprec_err_std`` and which must fail; the planted faults of (a),
#: ``FAULT_READ`` (the least over the faults and seeds), which must fail
#: too.  0.25 is 3.8 times the program's worst and 3.0 times under the
#: e4m3 reference's least; on the CPU twin (dim 64) the program reads
#: 0.12-0.15 and the e4m3 reference 0.91-0.97.
LOGIT_TOL = 0.25
LOGIT_READ = (0.0555, 0.0655)
LOWPREC_READ = (0.761, 0.896)
FAULT_READ = 3.12

#: Least share of the timed sampled codes that the reference's logits must
#: give back under the timed keys (b), over the 2 x 1,024 codes of the
#: checked rows.  Readings on the v5e (PERF.md, section 6; nineteen
#: seeds): the program's ``REDRAW_READ`` (one code in a hundred has two
#: perturbed logits closer than the program's error), the e4m3 reference's
#: ``LOWPREC_REDRAW_READ``, which every run takes again and which must fail.
#: 0.96 is 13 standard deviations of a 2,048-code sample under the first's
#: least and 4 over the second's largest; a scan that tiles its state
#: wrongly or draws otherwise reads near 0.
REDRAW_SHARE = 0.96
REDRAW_READ = (0.9893, 0.9951)
LOWPREC_REDRAW_READ = (0.9067, 0.9390)

#: Largest relative error of a head's state after the last position (c),
#: ``|h - h_rule| / |h_rule|`` in the Frobenius norm, over the checked rows
#: and every head; ``h_rule`` is what the reference's sequential float32
#: rule leaves of the inputs the pass of (c) sowed.  It holds the pass's own
#: state in every Mamba-2 layer, and the timed path's in the Mamba-2 layers
#: that no expert layer precedes: after one, a near-tie in the routing may
#: fall one way in the 256-row timed program and the other in the 2-row
#: pass, and the rule is given another input at that position (0.003-0.07
#: there on the v5e, ``timed_state_err_by_layer``).  Readings on the v5e
#: (PERF.md, section 6): the pass's ``STATE_READ`` (float32 sums in another
#: order: the chunked form's products, then the decode steps; fourteen
#: seeds), the timed path's ``TIMED_STATE_READ`` (four seeds), and the same
#: rule with the state rounded to bfloat16 after every update,
#: ``BF16_STATE_READ`` (nineteen seeds), which every run takes again and
#: which must fail.  1e-3 is 48 times the worst of the first two (the pass's
#: spreads fivefold over its seeds; fresh seeds may read higher) and 92
#: times under the bfloat16 state's least.
STATE_TOL = 1e-3
STATE_READ = (4.0e-6, 2.1e-5)
TIMED_STATE_READ = (3.8e-9, 3.5e-6)
BF16_STATE_READ = (0.0924, 0.170)

#: How far below the reference's own k-th ``score + bias`` a handed expert's
#: may lie (d), as a share of it: the program ranks bfloat16 router products,
#: so near-ties may fall the other way; a choice made without the bias, or
#: from another layer's router, reaches far below.  Readings on the v5e
#: (PERF.md, section 6): the program's least reach, ``REACH_READ``
#: (nineteen seeds); the program's choices with every expert shifted by one
#: (``fault_reach``: a shifted expert's ``score + bias`` can be negative),
#: ``FAULT_REACH_READ`` (nine seeds), which must fail.
ROUTE_MARGIN = 0.05
REACH_READ = (0.9914, 0.9941)
FAULT_REACH_READ = (-0.0856, -0.0442)

#: Largest |program weight - reference weight| of a chosen expert (the six
#: weights of a position sum to 2.5), the reference weighting the program's
#: own choices: both sides take the sigmoid of 128 float32 sums over the
#: same normed input, the program's of bfloat16 operands.  Readings on the
#: v5e (PERF.md, section 6; nineteen seeds): the program's
#: ``ROUTE_WEIGHT_READ``; the selection bias in the weights (0.067-0.077)
#: and no routed scaling (0.296-0.312; ``fault_weight_err``, the least,
#: ``FAULT_WEIGHT_READ``), which must fail.  0.04 is 10 times the program's
#: worst and 1.7 times under the bias fault's least; on the CPU twin (dim
#: 64, where a bfloat16 router input is coarser) the program reads 0.017-0.031
#: and the bias fault 0.145-0.153.
ROUTE_WEIGHT_TOL = 0.04
ROUTE_WEIGHT_READ = (0.0025, 0.0039)
FAULT_WEIGHT_READ = 0.0666

#: The planted faults, run on the first checked row: those the logits (a)
#: must catch, and those the routing weights (d) must catch.
LOGIT_FAULTS = ("group_mod", "whole_norm", "relu", "no_shared_expert",
                "no_conv_bias", "other_experts")
WEIGHT_FAULTS = ("no_route_scale", "bias_in_weights")


def program_logits(dalle, params, prompts, codes, excess_precision=True):
    """Teacher-forced logits ``[b, image_seq_len, num_image_tokens]`` through
    the program's prefill and cached decode step; the experts its expert
    layers chose at every input position and the weights it gave them,
    ``[2, expert layers, b, seq_len, k]`` (the experts, then their weights,
    float32); the Mamba-2 layers' states after the last input position,
    ``[Mamba-2 layers, b, H, P, N]``; and what their recurrence was given at
    every input position: ``x, B, C, delta``, each ``[Mamba-2 layers, b,
    seq_len, ...]``.  ``excess_precision`` False compiles with
    ``xla_allow_excess_precision`` off (module docstring, (c))."""
    from dalle_pytorch_tpu.models.dalle import DALLE

    cfg = dalle.cfg
    n_pre = cfg.text_seq_len + 1
    ssd = [i for i, kind in enumerate(cfg.mixers) if kind == "mamba2"]
    experts = [i for i, kind in enumerate(cfg.mixers) if kind == "none"]

    def sown(state):
        layers = state["intermediates"]["transformer"]
        given = [layers[f"layers_{i}_ssd"]["ssd"]["rule_inputs"][0]
                 for i in ssd]
        chosen = jnp.stack([jnp.stack([
            layers[f"layers_{i}_ff"]["moe"][name][0].astype(jnp.float32)
            for i in experts]) for name in ("top_idx", "top_weight")])
        return tuple(jnp.stack(x) for x in zip(*given)), chosen

    def run(variables, text, codes):
        (first, caches), state = dalle.apply(
            variables, text, method=DALLE.prefill, mutable=["intermediates"])

        def step(carry, code):
            caches, index = carry
            (logits, caches), state = dalle.apply(
                variables, code, caches, index, method=DALLE.decode_step,
                mutable=["intermediates"])
            given, chosen = sown(state)
            return (caches, index + 1), (logits, given, chosen[:, :, :, 0])

        (caches, _), (rest, ticks, routed) = jax.lax.scan(
            step, (caches, jnp.asarray(n_pre)), codes[:, :-1].T)
        logits = jnp.concatenate([first[:, None], rest.transpose(1, 0, 2)], 1)
        given, chosen = sown(state)
        routing = jnp.concatenate(
            [chosen, routed.transpose(1, 2, 3, 0, 4)], axis=3)
        states = jnp.stack([caches[i][1] for i in ssd])
        # a tick's [steps, layers, b, ...] behind the prompt's [layers, b,
        # n_pre, ...]
        inputs = tuple(jnp.concatenate([x, jnp.moveaxis(y, 0, 2)], axis=2)
                       for x, y in zip(given, ticks))
        return logits, routing, states, inputs

    args = {"params": params}, jnp.asarray(prompts), jnp.asarray(codes)
    options = {} if excess_precision else {"xla_allow_excess_precision":
                                           False}
    return jax.jit(run).lower(*args).compile(compiler_options=options)(*args)


def timed_state(dalle, params, prompt, key, *, rows, fanout: int,
                filter_thres: float, temperature: float):
    """The timed path once more on one ``[1, text_seq_len]`` prompt, as
    ``generate_glm_4_7_flash.build``'s two programs run it (no prime codes):
    ``prefill_codes`` at batch 1, ``tile_prefill`` over ``fanout`` rows and
    ``decode_codes`` under ``key``, which here hands back its scan's final
    carry too; both compiled with ``xla_allow_excess_precision`` off, as the
    pass that sows the rule's inputs is (module docstring, (c)).  Returns
    the codes of ``rows``, ``[len(rows), image_seq_len]``, and their Mamba-2
    states after the last tick, ``[Mamba-2 layers, len(rows), H, P, N]``."""
    from dalle_pytorch_tpu.models.dalle import (decode_codes, prefill_codes,
                                                tile_prefill)

    ssd = [i for i, kind in enumerate(dalle.cfg.mixers) if kind == "mamba2"]
    options = {"xla_allow_excess_precision": False}
    picked = jnp.asarray(rows)

    def compiled(fn, *args):
        return jax.jit(fn).lower(*args).compile(compiler_options=options)(
            *args)

    def prefill(v, text, prime):
        return prefill_codes(dalle, v, text, prime_codes=prime)

    def decode(v, first, caches, key, prime):
        codes, caches = decode_codes(
            dalle, v, first, caches, key, n_prime=prime.shape[1],
            prime_codes=jnp.repeat(prime, fanout, axis=0),
            filter_thres=filter_thres, temperature=temperature,
            return_caches=True)
        return codes[picked], jnp.stack([caches[i][1][picked] for i in ssd])

    variables = {"params": params}
    prime = jnp.zeros((1, 0), jnp.int32)
    first, caches = tile_prefill(*compiled(
        prefill, variables, jnp.asarray(prompt), prime), fanout)
    return compiled(decode, variables, first, caches, key, prime)


@functools.partial(jax.jit, static_argnames="state_dtype")
def rule_states(inputs, A, state_dtype):
    """The reference's sequential rule over ``program_logits``' inputs, a
    layer at a time (``A`` ``[Mamba-2 layers, H]``): the states after the
    last position, ``[Mamba-2 layers, b, H, P, N]``."""
    return jax.lax.map(
        lambda layer: reference.rule(*layer[0], layer[1], state_dtype)[1],
        (inputs, A))


def compare(dalle, params, prompts, codes, n_prime: int, *, rows, fanout: int,
            key, filter_thres: float, temperature: float) -> dict:
    """(a)-(d) of the module docstring and the controls on ``[k,
    text_seq_len]`` prompts (one prompt, repeated) and the ``[k,
    image_seq_len]`` codes that rows ``rows`` of the timed request under
    ``key`` returned for them."""
    assert n_prime == 0, n_prime
    cfg = dalle.cfg
    codes = np.asarray(codes)
    in_range = bool(((codes >= 0) & (codes < cfg.num_image_tokens)).all())
    clipped = jnp.asarray(np.clip(codes, 0, cfg.num_image_tokens - 1))
    prompts = jnp.asarray(prompts)

    # (c) first, while nothing of the reference is on the chip: the timed
    # path's state, and the rule over what a pass over its codes sows
    drawn, timed = timed_state(dalle, params, prompts[:1], key, rows=rows,
                               fanout=fanout, filter_thres=filter_thres,
                               temperature=temperature)
    drawn = np.asarray(drawn)
    _, _, states, inputs = program_logits(
        dalle, params, jnp.repeat(prompts[:1], len(drawn), axis=0),
        jnp.asarray(np.clip(drawn, 0, cfg.num_image_tokens - 1)),
        excess_precision=False)
    layers = params["transformer"]
    A = jnp.stack([-jnp.exp(jnp.asarray(
        layers[f"layers_{i}_ssd"]["ssd"]["A_log"], jnp.float32))
        for i, kind in enumerate(cfg.mixers) if kind == "mamba2"])
    want = rule_states(inputs, A, jnp.float32)
    state_err = state_error(states, want)
    timed_err = [state_error(timed[j:j + 1], want[j:j + 1])
                 for j in range(len(want))]
    early = sum(i < cfg.mixers.index("none")
                for i, kind in enumerate(cfg.mixers) if kind == "mamba2")
    bf16_state_err = state_error(rule_states(inputs, A, jnp.bfloat16), want)
    del timed, states, inputs, want

    got, routing, _, _ = program_logits(dalle, params, prompts, clipped)
    routing, weights = routing[0].astype(jnp.int32), np.asarray(routing[1])
    sampler_dtype = got.dtype
    got = np.asarray(got, np.float32)

    def reference_logits(prompts, codes, routing, **kw):
        logits, extras = reference.image_logits(params, cfg, prompts, codes,
                                                routing=routing, **kw)
        return np.asarray(logits), extras

    ref, extras = reference_logits(prompts, clipped, routing)
    reach = np.asarray(extras["reach"])
    weight_err = float(np.abs(weights - np.asarray(extras["weight"])).max())
    low, _ = reference_logits(prompts, clipped, routing,
                              matmul_dtype=jnp.float8_e4m3fn)
    std = ref.std(-1, keepdims=True)
    logit_err = float((np.abs(got - ref) / std).max())
    lowprec_err = float((np.abs(low - ref) / std).max())
    fault_err, fault_weight = {}, {}
    for fault in LOGIT_FAULTS + WEIGHT_FAULTS:
        planted, planted_extras = reference_logits(
            prompts[:1], clipped[:1], routing[:, :1], fault=fault)
        if fault in LOGIT_FAULTS:
            fault_err[fault] = float((np.abs(planted - ref[:1])
                                      / std[:1]).max())
        else:
            fault_weight[fault] = float(np.abs(
                weights[:, :1] - np.asarray(planted_extras["weight"])).max())
    # the routing rule's own control: the program's choices with every
    # expert shifted by one are not the reference's ranking
    shifted = (routing[:, :1] + 1) % cfg.trunk.experts
    fault_reach = float(np.asarray(reference_logits(
        prompts[:1], clipped[:1], shifted)[1]["reach"]).min())

    k = checks.top_k_count(cfg, filter_thres)
    draw = functools.partial(redraw, key=key, rows=jnp.asarray(rows),
                             fanout=fanout, k=k, temperature=temperature)
    share = float((np.asarray(draw(jnp.asarray(ref, sampler_dtype)))
                   == codes).mean())
    share_low = float((np.asarray(draw(jnp.asarray(low, sampler_dtype)))
                       == codes).mean())
    controls_fail = (lowprec_err > LOGIT_TOL and share_low < REDRAW_SHARE
                     and bf16_state_err > STATE_TOL
                     and min(fault_err.values()) > LOGIT_TOL
                     and min(fault_weight.values()) > ROUTE_WEIGHT_TOL
                     and fault_reach < 1 - ROUTE_MARGIN)
    return {"codes_in_range": in_range, "logit_err_std": logit_err,
            "lowprec_err_std": lowprec_err, "fault_err_std": fault_err,
            "route_weight_err": weight_err, "fault_weight_err": fault_weight,
            "redraw_share": share, "lowprec_redraw_share": share_low,
            "k": k, "rows": [int(r) for r in rows],
            "state_err": state_err, "bf16_state_err": bf16_state_err,
            "timed_state_err": max(timed_err[:early]),
            "timed_state_err_by_layer": timed_err,
            "timed_codes_redrawn": float((drawn == codes).mean()),
            "route_reach_min": float(reach.min()),
            "fault_reach": fault_reach,
            "ok": bool(in_range and np.isfinite(logit_err)
                       and logit_err <= LOGIT_TOL
                       and share >= REDRAW_SHARE
                       and np.isfinite(state_err) and state_err <= STATE_TOL
                       and max(timed_err[:early]) <= STATE_TOL
                       and reach.min() >= 1 - ROUTE_MARGIN
                       and weight_err <= ROUTE_WEIGHT_TOL
                       and controls_fail)}


def run(cell, devices, dalle_cfg, vae_cfg, seed, seconds, tracer, mark_ready):
    """``generate_glm_4_7_flash.run``, the loop every fan-out trunk cell with
    chunked VAE decoding shares, with this module's :func:`compare` in place
    of its own for the one call."""
    own = glm.compare
    glm.compare = compare
    try:
        return glm.run(cell, devices, dalle_cfg, vae_cfg, seed, seconds,
                       tracer, mark_ready)
    finally:
        glm.compare = own
