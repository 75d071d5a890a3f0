"""``drivers/generate.py``'s closed loop (one prompt prefilled at batch 1, its
decode state tiled over the candidates, one jitted ``decode_codes`` scan named
``jit_bench_decode``, the VAE decode, images fetched to the host) for a
configuration that ``benchmark/reference.py`` does not cover: DALL-E over the
Olmo-Hybrid family's trunk (gated-delta-rule linear attention among full
attention layers, norms on the sublayers' outputs, an untied head), held to
``benchmark/reference_olmo_hybrid_7b.py``.

The loop is ``generate.py``'s call for call and shares its ``build``; what
differs is the comparison that decides ``correct``, on what the timed program
produced at the timed sizes (module constants below, each with its two
readings; the checked candidates are the first and the last row of the
fan-out):

(a) teacher-forced logits through ``DALLE.prefill`` and ``DALLE.decode_step``
    (a program of its own at batch 2, along the timed codes) against the
    reference's full forward pass at every image position;
(b) **the timed codes themselves, redrawn**
    (``generate_smallthinker_21ba3b.redraw``).  (a) cannot see the timed scan
    at the full fan-out, the tiled ``(window, S)``, the folded carry or the
    sampler, and ``checks.compare``'s top-k share says nothing here: at
    ``filter_thres`` 0.9 the sampler keeps int(0.1 x 100,352) = 10,035 ids,
    more than the 8,192 codes there are.  So the reference's logits along
    the timed codes are sampled again with the key the timed request used at
    each tick (the same ``categorical`` over ``[fanout, codes]``, the checked
    rows in their own places): where the timed program computed what the
    reference computes, the same noise picks the same code, except where two
    codes' perturbed logits lie closer than the bfloat16 program's error;
(c) **the recurrent state itself.**  A state carried in bfloat16 moves the
    logits by no more than the program's own rounding does (PERF.md,
    Findings PR 34), so (a) and (b) pass it; and set against the reference's
    own state, the rounding of the bfloat16 activations that feed the rule
    hides it as well.  So the linear layers ``sow`` what their rule is given
    (``q, k, v, g, beta`` at every position, in the pass of (a)), the
    reference's sequential float32 rule runs over exactly those, and the
    state it leaves is compared with the one the program carried through
    ``prefill``'s chunked form and 1,023 ``decode_step``s, a head at a time:
    the same inputs on both sides, so what is left is the rule's own
    arithmetic and the precision the state is kept in.  That pass is
    compiled a second time for this, with ``xla_allow_excess_precision``
    off: as XLA compiles by default, a fused consumer may take a product's
    float32 sum where the written copy was rounded to bfloat16 as the trace
    says, so what a layer sows is not bit for bit what its rule consumed,
    and the difference (2^-9 of ``k`` and ``v``) reads as large as a
    bfloat16 state (0.016 on the v5e against 3.9e-7 without the excess).

Every run reads the reference a second time with matrix operands rounded to
e4m3: its logits (``lowprec_err_std``) must fail ``LOGIT_TOL`` and its redraw
(``lowprec_redraw_share``) must fail ``REDRAW_SHARE``; and runs the
sequential rule of (c) once more with the state rounded to bfloat16 after
every update, which (``bf16_state_err``) must fail ``STATE_TOL``.  The
decode state and the last request's images are freed before the reference
runs: the model's 4.9 GB stay, and the reference upcasts one layer at a time.

Traffic parameters: ``fanout``, ``filter_thres``, ``temperature``, ``text``,
``check_sequences``.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import checks, harness, reference_olmo_hybrid_7b
from benchmark.drivers.generate import MAX_REQUESTS, build
from benchmark.drivers.generate_smallthinker_21ba3b import redraw

#: Largest |program logit - reference logit| allowed, in units of the
#: reference logits' standard deviation over the image vocabulary at that
#: position.  The program multiplies bfloat16 weights and activations (8 bits
#: of mantissa) with float32 sums through 8 layers whose every sublayer's
#: output is normed to unit scale before it joins the stream (no LayerScale:
#: each of 16 sublayers adds its rounding at full size), with the linear
#: layers' state, its decay, the l2 norms and every norm in float32.  Two
#: readings on the v5e set the limit (PERF.md, Findings PR 34): the
#: program's largest over 2 x 1024 x 8192 logits, ``LOGIT_READ`` over the
#: seeds run; and the reference with every layer's matrix products on
#: operands rounded to an 8-bit float (e4m3, scaled per tensor: the nearest
#: precision below the configuration's bfloat16), ``LOWPREC_READ``, which
#: every run takes again as ``lowprec_err_std`` and which must fail.  1.25
#: leaves 2.3 times of room above the first and 1.5 under the second, whose
#: readings lie close together; the first has a long tail (fresh seeds read
#: higher: with ``beta`` up to 2 and heads that hardly decay, a read-out
#: ``S^T q`` can cancel to a small vector that the gated norm scales back up,
#: rounding and all), and one run over the limit refuses a PR; a dropped
#: norm or a wrong mask moves logits by whole stds.
LOGIT_TOL = 1.25
LOGIT_READ = (0.161, 0.541)         # least and largest over the seeds run
LOWPREC_READ = (1.901, 2.647)

#: Least share of the timed sampled codes that the reference's logits must
#: give back under the timed keys (b), over the 2 x 1,024 codes of the
#: checked rows.  Readings on the v5e (PERF.md, Findings PR 34): the
#: program's ``REDRAW_READ`` (one or two codes in a hundred have two
#: perturbed logits closer than the program's error), the e4m3 reference's
#: ``LOWPREC_REDRAW_READ`` (one in five), which every run takes again and
#: which must fail.  0.93 allows 3.4 times the codes the program's worst
#: reading lost and 0.41 of those the control's best reading lost, 16 and 12
#: standard deviations of a 2,048-code sample away; a scan that tiles its
#: carry wrongly, loses the state between ticks or draws otherwise reads
#: near 0 (0.375 on the CPU twin with the state zeroed at the tiling).
REDRAW_SHARE = 0.93
REDRAW_READ = (0.9795, 0.9917)         # eighteen seeds
LOWPREC_REDRAW_READ = (0.7988, 0.8276)

#: Largest relative error of a head's state after the last position (c),
#: ``|S - S_rule| / |S_rule|`` in the Frobenius norm, over the checked rows
#: and every head of every linear layer; ``S_rule`` is what the reference's
#: sequential float32 rule leaves of the inputs the program's own rule was
#: given.  Readings on the v5e (PERF.md, Findings PR 34): the program's
#: ``STATE_READ`` (float32 sums in another order, the chunked form's
#: products at ``Precision.HIGHEST``), and the same sequential rule with the
#: state rounded to bfloat16 after every update, ``BF16_STATE_READ``, which
#: every run takes again and which must fail.  The first spreads over two
#: orders of magnitude from seed to seed (twelve seeds), the second hardly
#: (nineteen); 5e-4 is their geometric middle, 24 times over the one and 27
#: under the other (1e-4 in the runs that read them, set from the first
#: seed's 3.9e-7).
STATE_TOL = 5e-4
STATE_READ = (2.4e-7, 2.1e-5)
BF16_STATE_READ = (0.0136, 0.0258)


def program_logits(dalle, params, prompts, codes, excess_precision=True):
    """``checks.program_logits`` (teacher-forced logits ``[b, image_seq_len,
    num_image_tokens]`` through the program's prefill and cached decode
    step), the linear layers' states after the last input position,
    ``[linear layers, b, heads, d_k, d_v]``, and what their rule was given at
    every input position (what the layers ``sow``): ``q, k, v, g, beta``,
    each ``[linear layers, b, seq_len, heads, ...]``.  ``excess_precision``
    False compiles with ``xla_allow_excess_precision`` off: every value is
    rounded where the trace rounds it, so the inputs handed back are the
    ones the rule consumed (module docstring, (c))."""
    from dalle_pytorch_tpu.models.dalle import DALLE
    from dalle_pytorch_tpu.ops.linear_attention import unfold_state

    cfg = dalle.cfg
    n_pre = cfg.text_seq_len + 1
    linear = [i for i, kind in enumerate(cfg.mixers) if kind == "gdn"]

    def given(state):
        layers = state["intermediates"]["transformer"]
        per_layer = [layers[f"layers_{i}_gdn"]["gdn"]["rule_inputs"][0]
                     for i in linear]
        return tuple(jnp.stack(x) for x in zip(*per_layer))

    def run(variables, text, codes):
        (first, caches), state = dalle.apply(
            variables, text, method=DALLE.prefill, mutable=["intermediates"])

        def step(carry, code):
            caches, index = carry
            (logits, caches), state = dalle.apply(
                variables, code, caches, index, method=DALLE.decode_step,
                mutable=["intermediates"])
            return (caches, index + 1), (logits, given(state))

        (caches, _), (rest, ticks) = jax.lax.scan(
            step, (caches, jnp.asarray(n_pre)), codes[:, :-1].T)
        logits = jnp.concatenate([first[:, None], rest.transpose(1, 0, 2)], 1)
        states = jnp.stack([unfold_state(caches[i][1], cfg.heads)
                            for i in linear])
        # a tick's [steps, layers, b, ...] behind the prompt's [layers, b,
        # n_pre, ...]
        inputs = tuple(jnp.concatenate([x, jnp.moveaxis(y, 0, 2)], axis=2)
                       for x, y in zip(given(state), ticks))
        return logits, states, inputs

    args = {"params": params}, jnp.asarray(prompts), jnp.asarray(codes)
    options = {} if excess_precision else {"xla_allow_excess_precision":
                                           False}
    return jax.jit(run).lower(*args).compile(compiler_options=options)(*args)


@functools.partial(jax.jit, static_argnames="state_dtype")
def rule_states(inputs, state_dtype):
    """The reference's sequential rule over ``program_logits``' inputs, a
    layer at a time: the states after the last position, ``[linear layers,
    b, heads, d_k, d_v]``."""
    return jax.lax.map(
        lambda layer: reference_olmo_hybrid_7b.delta_rule(
            *layer, state_dtype)[1], inputs)


def state_error(states, ref) -> float:
    """The largest ``|S - S_ref| / |S_ref|`` (Frobenius, a head's matrix)
    over layers, rows and heads."""
    states, ref = np.asarray(states, np.float64), np.asarray(ref, np.float64)
    norm = lambda a: np.sqrt((a * a).sum((-2, -1)))  # noqa: E731
    return float((norm(states - ref) / norm(ref)).max())


def compare(dalle, params, prompts, codes, *, rows, fanout: int, key,
            filter_thres: float, temperature: float) -> dict:
    """(a), (b) and (c) of the module docstring on ``[k, text_seq_len]``
    prompts and the ``[k, image_seq_len]`` codes that rows ``rows`` of the
    timed request under ``key`` returned for them."""
    cfg = dalle.cfg
    codes = np.asarray(codes)
    in_range = bool(((codes >= 0) & (codes < cfg.num_image_tokens)).all())
    clipped = jnp.asarray(np.clip(codes, 0, cfg.num_image_tokens - 1))
    prompts = jnp.asarray(prompts)
    reference = functools.partial(reference_olmo_hybrid_7b.image_logits,
                                  params, cfg, prompts, clipped)

    # the logits as the timed program is compiled, the state without excess
    got = program_logits(dalle, params, prompts, clipped)[0]
    sampler_dtype = got.dtype
    got = np.asarray(got, np.float32)
    _, states, inputs = program_logits(dalle, params, prompts, clipped,
                                       excess_precision=False)
    want = rule_states(inputs, jnp.float32)
    state_err = state_error(states, want)
    bf16_state_err = state_error(rule_states(inputs, jnp.bfloat16), want)
    del states, inputs, want

    ref = np.asarray(reference())
    low = np.asarray(reference(matmul_dtype=jnp.float8_e4m3fn))
    std = ref.std(-1, keepdims=True)
    logit_err = float((np.abs(got - ref) / std).max())
    lowprec_err = float((np.abs(low - ref) / std).max())

    k = checks.top_k_count(cfg, filter_thres)
    draw = functools.partial(redraw, key=key, rows=jnp.asarray(rows),
                             fanout=fanout, k=k, temperature=temperature)
    share = float((np.asarray(draw(jnp.asarray(ref, sampler_dtype)))
                   == codes).mean())
    share_low = float((np.asarray(draw(jnp.asarray(low, sampler_dtype)))
                       == codes).mean())
    return {"codes_in_range": in_range, "logit_err_std": logit_err,
            "lowprec_err_std": lowprec_err, "redraw_share": share,
            "lowprec_redraw_share": share_low, "k": k,
            "rows": [int(r) for r in rows], "state_err": state_err,
            "bf16_state_err": bf16_state_err,
            "ok": bool(in_range and np.isfinite(logit_err)
                       and logit_err <= LOGIT_TOL
                       and share >= REDRAW_SHARE
                       and np.isfinite(state_err) and state_err <= STATE_TOL
                       and lowprec_err > LOGIT_TOL
                       and share_low < REDRAW_SHARE
                       and bf16_state_err > STATE_TOL)}


def run(cell, devices, dalle_cfg, vae_cfg, seed, seconds, tracer, mark_ready):
    from dalle_pytorch_tpu.cli import make_decode_fn
    from dalle_pytorch_tpu.models.dalle import tile_prefill

    tr = cell.traffic
    fanout = int(tr["fanout"])
    b = build(cell, dalle_cfg, vae_cfg)
    k_model, k_vae, k_run = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = jax.jit(b["init_dalle"])(k_model)
    vae_params = jax.jit(b["init_vae"])(k_vae)
    variables = {"params": params}
    vae_decode = make_decode_fn(b["vae"], vae_params)
    prompts = harness.make_prompts(cell, dalle_cfg, MAX_REQUESTS, seed)
    keys = jax.random.split(k_run, MAX_REQUESTS)
    image_len = dalle_cfg.image_seq_len

    def request(i):
        """One whole ``generate`` call; returns codes (device) and images
        (host)."""
        with tracer.span("bench:generate"):
            first1, caches1 = b["prefill"](variables,
                                           jnp.asarray(prompts[i:i + 1]))
            first, caches = tile_prefill(first1, caches1, fanout)
            codes = b["decode"](variables, first, caches, keys[i])
        with tracer.span("bench:vae_decode"):
            images = np.asarray(jax.device_get(vae_decode(codes)))
        return codes, images

    request(0)                       # compiles and warms every program

    mark_ready()
    done, last = [], None
    t0 = time.perf_counter()
    i = 1
    while time.perf_counter() - t0 < seconds and i < MAX_REQUESTS:
        last = request(i)
        done.append(time.perf_counter())
        i += 1
    t1 = done[-1]
    n_req = len(done)

    if tracer.on:
        tracer.start()
        last = request(i)
        tracer.stop()
        i += 1
    memory_peak = harness.memory_peak_bytes(devices)

    # correctness, outside the window: the last request's first and last
    # candidates (and those evenly between them, were more asked for).
    # Nothing of the decode state is held any more (a request keeps its
    # codes and images only), so the reference finds the chip with the model
    # alone on it.
    codes, images = last
    rows = np.linspace(0, fanout - 1, int(tr["check_sequences"])
                       ).round().astype(int)
    codes_host = np.asarray(jax.device_get(codes))
    complete = (images.shape == (fanout, vae_cfg.image_size,
                                 vae_cfg.image_size, 3)
                and bool(np.isfinite(images).all())
                and codes_host.shape == (fanout, image_len))
    del last, images
    verdict = compare(b["dalle"], params,
                      np.repeat(prompts[i - 1:i], len(rows), axis=0),
                      codes_host[rows], rows=rows, fanout=fanout,
                      key=keys[i - 1], filter_thres=float(tr["filter_thres"]),
                      temperature=float(tr["temperature"]))
    retraced = {name: int(fn._cache_size())
                for name, fn in (("prefill", b["prefill"]),
                                 ("decode", b["decode"]),
                                 ("vae_decode", vae_decode))}
    ok = (complete and verdict["ok"]
          and bool(((codes_host >= 0)
                    & (codes_host < dalle_cfg.num_image_tokens)).all())
          and all(v == 1 for v in retraced.values()))
    tokens_per_s = n_req * fanout * image_len / (t1 - t0)
    gaps = np.diff([t0] + done)
    return harness.Outcome(
        correct=ok, attempted=n_req * fanout, failed=0,
        end_to_end={"gen_tokens_per_s": tokens_per_s},
        host={"tokens_per_s": tokens_per_s, "requests": n_req,
              "rows": fanout, "request_s_median": float(np.median(gaps)),
              "decode_steps_traced": image_len - 1, "window_s": t1 - t0,
              "check": verdict, "trace_counts": retraced},
        programs={"jit_bench_decode": b["decode"].lower(
            variables, *jax.eval_shape(
                lambda v, t: tile_prefill(*b["prefill"](v, t), fanout),
                variables, prompts[:1]), keys[0]).compile()}
        if tracer.on else {},
        main_program="jit_bench_decode", memory_peak_bytes=memory_peak,
        notes=[f"{n_req} requests x {fanout} images; check {verdict}"])
