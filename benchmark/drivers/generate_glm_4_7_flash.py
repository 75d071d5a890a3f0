"""``drivers/generate_smallthinker_21ba3b.py``'s closed loop for a primed
request over a latent-attention, shared-expert trunk, held to
``benchmark/reference_glm_4_7_flash.py``: one prompt *and its prime codes*
prefilled at batch 1 in the published form (``jit_bench_prefill``), the latent
cache tiled over the candidates, one jitted ``decode_codes`` scan over the
codes that are left, every tick in the absorbed form (``jit_bench_decode``:
128 rows of a 4,352-position latent are 3.2 GB, and the scan holds its own
copy beside the tiled argument; both fit, so nothing is donated),
the VAE decode a chunk of candidates at a time, images fetched to the host.
Closed loop, one client.  Only sampled codes count as tokens.

Set-up compiles the two programs ahead of time and runs everything of a
request but the scan (the prefill, the tiling, the VAE chunks on stand-in
codes): the scan is nearly all of a request and has one shape.

What decides ``correct``, on what the timed program produced at the timed
sizes (module constants below, each with its two readings):

(a) teacher-forced logits through ``DALLE.prefill`` (text and prime, the
    published form) and ``DALLE.decode_step`` (every later code, the absorbed
    form over the latent cache) against the reference's full forward pass in
    the published form, at every sampled position of the checked candidates
    (the first and the last row of the fan-out);
(b) the timed codes themselves, redrawn from the reference's logits under the
    timed keys (``generate_smallthinker_21ba3b.redraw``, imported);
(c) **the latent cache itself**: what the teacher-forced program's cache holds
    after the last position, every layer, every position of the checked rows,
    against the reference's ``c`` (normed) and ``k_rope`` (rotated): the
    largest relative distance of a position's vector;
(d) routing, sets compared as sets (on the chip ``x / x`` may read one ulp
    under 1): the program's own choices (what its expert layers ``sow``) are
    handed to the reference, which weights them by *its* scores and reports
    how far down its own ranking of ``score + bias`` they reach; and the
    WEIGHTS the program gave its choices (sown beside them) against the
    reference's for the same experts: the selection bias must have entered
    the choice and not the weight.

Every run plants seven controls, and each must FAIL one of the limits above
inside ``ok``: the reference with matrix operands rounded to e4m3 (the
nearest precision below bfloat16; logits and redraw, both checked rows), and,
on the first checked row, the reference with a fault planted
(``reference.FAULTS``): an un-normed latent (c, the first layer's), an
un-rotated ``k_rope`` (c, the first layer's), the selection bias added to the
weights (d, the first routed layer's), the shared expert left out (a), experts
8-15 in place of 0-7 (a); and the program's own choices with every expert
shifted by one, which the routing rule (d) must refuse.

Traffic parameters: ``fanout``, ``filter_thres``, ``temperature``, ``text``,
``prime_codes``, ``vae_decode_chunk``, ``check_sequences``.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import checks, harness
from benchmark import reference_glm_4_7_flash as reference
from benchmark.drivers.generate import MAX_REQUESTS
from benchmark.drivers.generate_smallthinker_21ba3b import (make_primes,
                                                             redraw)

#: Largest |program logit - reference logit| allowed, in units of the
#: reference logits' standard deviation over the image vocabulary at that
#: position, the reference using the program's experts (d).  The program
#: multiplies bfloat16 weights and activations with float32 sums through 5
#: layers of 2 sublayers on a bfloat16 residual stream, and its ticks round
#: ``q_lat`` and the softmax weights to the cache's bfloat16 where the
#: published form rounds ``k_nope`` and ``v``.  Readings on the v5e (PERF.md,
#: Findings PR 38; fourteen seeds): the program's largest over 2 x 2304 x 8192
#: logits, 0.081 to 0.092; the e4m3 reference (the nearest precision below
#: bfloat16), 1.26 to 1.58, which every run takes again as
#: ``lowprec_err_std`` and which must fail; the shared expert left out 5.2 to
#: 5.95 and experts 8-15 for 0-7 3.14 to 3.83 (``fault_err_std``), which must
#: fail too.  0.3 is 3.3 times the first and a quarter of the second.
LOGIT_TOL = 0.3

#: Least share of the timed sampled codes that the reference's logits must
#: give back under the timed keys (b), over all 2 x 2,304 sampled positions.
#: Readings on the v5e (PERF.md, Findings PR 38; fourteen seeds): the program 0.9878 to
#: 0.9928 (one position in a hundred has two perturbed logits closer than the
#: program's error), the e4m3 reference 0.863 to 0.882, which every run takes
#: again as ``lowprec_redraw_share`` and which must fail.  0.95 lies between
#: them, 25 standard deviations of a 4,608-position sample under the first
#: and 13 over the second; a scan that tiles its carry wrongly, reads a
#: wrong prefix of the latent or draws otherwise reads near 0.
REDRAW_SHARE = 0.95

#: Largest relative distance ``|got - want| / |want|`` of one position's
#: cached latent ``c`` (c) over all layers, checked rows and positions.  The
#: latent is normed, so a position's vector has length sqrt(512) on both
#: sides and the distance is the program's bfloat16 error alone: 0.0174 to
#: 0.0200 on the v5e (fourteen seeds).  An un-normed latent differs from the normed one by the
#: factor ``1 / rms(c_raw)``, with seeded weights 1 +- 3% a position (512
#: samples of unit variance): the first layer's reads 0.116 to 0.149 at the
#: worst of its 4,352 positions (``fault_latent_err``; 0.21 over all five
#: layers), and must fail.  0.05 is the geometric middle.
LATENT_TOL = 0.05

#: The same of the cached rotated key ``k_rope``: the program 0.0222 to 0.0254
#: on the v5e (64 values of bfloat16 activations), an un-rotated key
#: (``fault_rope_err``) 1.53 to 1.68, another vector altogether from position 1
#: on.  0.2 is the geometric middle.
ROPE_TOL = 0.2

#: A chosen expert may rank below the reference's 4th only if its reference
#: ``score + bias`` is within this share of the 4th's: the two are tied as
#: far as bfloat16 can tell.  Readings on the v5e: the program's least reach
#: 0.985 to 0.994 (the largest gap a rightful flip bridged was 1.5%); the
#: program's choices with every expert shifted by one (``fault_reach``, the
#: first routed layer) -0.09 to -0.04 (a shifted expert's score + bias can
#: be negative), which must fail.
ROUTE_MARGIN = 0.05

#: Most positions at which program and reference may choose different
#: experts in any routed layer.  Readings on the v5e: the program 0.154 to
#: 0.178 (3.5 / 4.4 / 4.9 / 5.2% by layer: the router reads the normed state after
#: attention, a bfloat16 one in the program); the e4m3 reference's own
#: choices against the program's (``lowprec_route_tie_share``, reported, not
#: required to fail: the e4m3 control fails by its logits and its redraw)
#: 0.960 to 0.972.  0.4 lies between.
ROUTE_TIE_CAP = 0.4

#: Largest |program weight - reference weight| of a chosen expert (the four
#: weights of a position sum to 1.8), the reference weighting the program's
#: own choices.  Both sides take the sigmoid of 64 float32 sums over the same
#: normed input, the program's in bfloat16: 0.0039 to 0.0060 on the v5e.  A
#: selection bias drawn in [-0.1, 0.1] and added to the weights moves a
#: weight of 0.45 by up to a fifth of it: ``fault_weight_err`` 0.070 to 0.085
#: (the first routed layer), which must fail.  0.02 is the geometric middle.
ROUTE_WEIGHT_TOL = 0.02

#: the planted faults that the logits (a) must catch
EXPERT_FAULTS = ("no_shared_expert", "other_experts")


def build(cell, dalle_cfg, vae_cfg):
    from dalle_pytorch_tpu.models.dalle import decode_codes, prefill_codes

    tr = cell.traffic
    fanout = int(tr["fanout"])
    dalle, vae, init_dalle, init_vae = harness.init_fns(dalle_cfg, vae_cfg)

    # named, so that the trace's programs are jit_bench_prefill/_decode
    def bench_prefill(v, t, prime):
        return prefill_codes(dalle, v, t, prime_codes=prime)

    def bench_decode(v, first, caches, key, prime):
        return decode_codes(dalle, v, first, caches, key,
                            n_prime=prime.shape[1],
                            prime_codes=jnp.repeat(prime, fanout, axis=0),
                            filter_thres=float(tr["filter_thres"]),
                            temperature=float(tr["temperature"]))

    return dict(dalle=dalle, vae=vae, init_dalle=init_dalle,
                init_vae=init_vae, prefill=jax.jit(bench_prefill),
                decode=jax.jit(bench_decode))


def program_logits(dalle, params, prompts, codes, n_prime: int):
    """Teacher-forced logits ``[b, image_seq_len - n_prime,
    num_image_tokens]`` through the program's primed prefill (published form)
    and cached decode step (absorbed form), the experts its routed layers
    chose at every input position and the weights it gave them, each
    ``[routed layers, b, seq_len, k]``, and the latent cache after the last
    position, per layer ``(c [b, seq_len, kv_rank], k_rope [b, seq_len,
    rope_dim])``."""
    from dalle_pytorch_tpu.models.dalle import DALLE

    cfg = dalle.cfg
    n_pre = cfg.text_seq_len + 1 + n_prime
    routed = range(cfg.trunk.dense_layers, cfg.depth)

    def chosen(state):
        """``[2, routed layers, b, n, k]``: the experts, then their weights
        (exact in float32: the indices are small integers)."""
        layers = state["intermediates"]["transformer"]
        return jnp.stack([jnp.stack([
            layers[f"layers_{i}_ff"]["moe"][name][0].astype(jnp.float32)
            for i in routed]) for name in ("top_idx", "top_weight")])

    def run(variables, text, codes):
        (first, caches), state = dalle.apply(
            variables, text, codes[:, :n_prime], method=DALLE.prefill,
            mutable=["intermediates"])

        def step(carry, code):
            caches, index = carry
            (logits, caches), state = dalle.apply(
                variables, code, caches, index, method=DALLE.decode_step,
                mutable=["intermediates"])
            return (caches, index + 1), (logits, chosen(state)[:, :, :, 0])

        (caches, _), (rest, picked) = jax.lax.scan(
            step, (caches, jnp.asarray(n_pre)), codes[:, n_prime:-1].T)
        logits = jnp.concatenate([first[:, None], rest.transpose(1, 0, 2)], 1)
        routing = jnp.concatenate(
            [chosen(state), picked.transpose(1, 2, 3, 0, 4)], axis=3)
        return logits, routing[0].astype(jnp.int32), routing[1], caches

    return jax.jit(run)({"params": params}, jnp.asarray(prompts),
                        jnp.asarray(codes))


def _distance(got, want) -> float:
    """Largest relative distance of a position's vector: ``got``, ``want``
    ``[..., positions, width]``."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float((np.linalg.norm(got - want, axis=-1)
                  / np.maximum(np.linalg.norm(want, axis=-1), 1e-30)).max())


def compare(dalle, params, prompts, codes, n_prime: int, *, rows, fanout: int,
            key, filter_thres: float, temperature: float) -> dict:
    """(a)-(d) of the module docstring and the six controls on ``[k,
    text_seq_len]`` prompts and the ``[k, image_seq_len]`` codes (prime, then
    sampled) that rows ``rows`` of the timed request under ``key`` returned
    for them; the reference one sequence at a time."""
    cfg = dalle.cfg
    codes = np.asarray(codes)
    in_range = bool(((codes >= 0) & (codes < cfg.num_image_tokens)).all())
    clipped = np.clip(codes, 0, cfg.num_image_tokens - 1)
    got, routing, weights, caches = program_logits(dalle, params, prompts,
                                                   clipped, n_prime)
    weights = np.asarray(weights, np.float32)
    sampler_dtype = got.dtype
    got = np.asarray(got, np.float32)
    caches = [(np.asarray(c, np.float32), np.asarray(kr, np.float32))
              for c, kr in caches]

    ref, low, reach, differs, low_differs = [], [], [], [], []
    latent_err = rope_err = weight_err = 0.0
    fault_latent = fault_rope = fault_weight = 0.0
    fault_reach = 1.0
    faulty = {}
    for i in range(codes.shape[0]):
        args = (params, cfg, jnp.asarray(prompts[i:i + 1]),
                jnp.asarray(clipped[i:i + 1]))
        handed = dict(routing=routing[:, i:i + 1])
        logits, extras = reference.image_logits(*args, **handed)
        ref.append(np.asarray(logits[:, n_prime:]))
        reach.append(np.asarray(extras["reach"]))
        # sets compared as sets: on the chip x / x may read one ulp under 1
        differs.append((np.sort(np.asarray(routing[:, i:i + 1]), -1)
                        != np.sort(np.asarray(extras["top_idx"]), -1)
                        ).any(-1))
        weight_err = max(weight_err, float(np.abs(
            weights[:, i:i + 1] - np.asarray(extras["weight"])).max()))
        for (c, kr), (want_c, want_kr) in zip(caches, extras["latent"]):
            latent_err = max(latent_err, _distance(c[i], want_c[0]))
            rope_err = max(rope_err, _distance(kr[i], want_kr[0]))
        low_logits, low_extras = reference.image_logits(
            *args, **handed, matmul_dtype=jnp.float8_e4m3fn)
        low.append(np.asarray(low_logits[:, n_prime:]))
        low_differs.append((np.sort(np.asarray(routing[:, i:i + 1]), -1)
                            != np.sort(np.asarray(low_extras["top_idx"]), -1)
                            ).any(-1))
        if i:
            continue       # the planted faults: on the first checked row
        # the cache faults and the weight fault: what the reference would
        # cache in its first layer, or weight the first routed layer's
        # experts by, with the fault planted, against what the program did
        # (what the first layers cache and route does not depend on the
        # rest: the reference stops after them)
        first_routed = cfg.trunk.dense_layers
        for fault, depth in (("unnormed_latent", 1), ("unrotated_key", 1),
                             ("bias_in_weights", first_routed + 1)):
            planted = reference.hidden(*args, **handed, fault=fault,
                                       depth=depth)[1]
            if fault == "bias_in_weights":
                fault_weight = float(np.abs(
                    weights[:1, :1] - np.asarray(planted["weight"])).max())
                continue
            got_c, got_kr = caches[0]
            want_c, want_kr = planted["latent"][0]
            if fault == "unnormed_latent":
                fault_latent = _distance(got_c[0], want_c[0])
            else:
                fault_rope = _distance(got_kr[0], want_kr[0])
        # the routing rule's own control: the program's choices with every
        # expert shifted by one are not the reference's ranking
        shifted = (routing[:1, :1] + 1) % cfg.trunk.experts
        fault_reach = float(np.asarray(reference.hidden(
            *args, routing=shifted, depth=first_routed + 1)[1]["reach"]).min())
        for fault in EXPERT_FAULTS:
            faulty[fault] = np.asarray(reference.image_logits(
                *args, **handed, fault=fault)[0][:, n_prime:])
    ref, low = np.concatenate(ref), np.concatenate(low)
    reach = np.concatenate(reach, axis=1).min((1, 2))     # [routed layers]
    differs = np.concatenate(differs, axis=1)   # [routed layers, k, seq_len]
    std = ref.std(-1, keepdims=True)

    def err(other):
        return float((np.abs(got - other) / std).max())

    logit_err, lowprec_err = err(ref), err(low)
    fault_err = {name: float((np.abs(got[:1] - other) / std[:1]).max())
                 for name, other in faulty.items()}

    sampled = codes[:, n_prime:]
    k = checks.top_k_count(cfg, filter_thres)
    draw = functools.partial(redraw, key=key, rows=jnp.asarray(rows),
                             fanout=fanout, k=k, temperature=temperature)
    share = float((np.asarray(draw(jnp.asarray(ref, sampler_dtype)))
                   == sampled).mean())
    share_low = float((np.asarray(draw(jnp.asarray(low, sampler_dtype)))
                       == sampled).mean())
    tie_share = float(differs.any(0).mean())
    low_tie_share = float(np.concatenate(low_differs, axis=1).any(0).mean())
    controls_fail = bool(
        lowprec_err > LOGIT_TOL and share_low < REDRAW_SHARE
        and fault_latent > LATENT_TOL and fault_rope > ROPE_TOL
        and fault_weight > ROUTE_WEIGHT_TOL
        and fault_reach < 1 - ROUTE_MARGIN
        and all(e > LOGIT_TOL for e in fault_err.values()))
    return {"codes_in_range": in_range, "logit_err_std": logit_err,
            "lowprec_err_std": lowprec_err, "redraw_share": share,
            "lowprec_redraw_share": share_low, "k": k,
            "rows": [int(r) for r in rows],
            "latent_err": latent_err, "rope_err": rope_err,
            "fault_latent_err": fault_latent, "fault_rope_err": fault_rope,
            "route_weight_err": weight_err, "fault_weight_err": fault_weight,
            "fault_err_std": fault_err,
            "route_tie_share": tie_share,
            "lowprec_route_tie_share": low_tie_share,
            "fault_reach": fault_reach,
            "route_reach_min": [float(x) for x in reach],
            "route_differs_by_layer": [float(x) for x in
                                       differs.mean((1, 2))],
            "controls_fail": controls_fail,
            "ok": bool(in_range and np.isfinite(logit_err)
                       and logit_err <= LOGIT_TOL
                       and share >= REDRAW_SHARE
                       and latent_err <= LATENT_TOL and rope_err <= ROPE_TOL
                       and reach.min() >= 1 - ROUTE_MARGIN
                       and tie_share <= ROUTE_TIE_CAP
                       and weight_err <= ROUTE_WEIGHT_TOL
                       and controls_fail)}


def run(cell, devices, dalle_cfg, vae_cfg, seed, seconds, tracer, mark_ready):
    from dalle_pytorch_tpu.cli import make_decode_fn
    from dalle_pytorch_tpu.models.dalle import tile_prefill

    tr = cell.traffic
    fanout, n_prime = int(tr["fanout"]), int(tr["prime_codes"])
    chunk = int(tr["vae_decode_chunk"])
    assert fanout % chunk == 0, (fanout, chunk)
    b = build(cell, dalle_cfg, vae_cfg)
    k_model, k_vae, k_run = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = jax.jit(b["init_dalle"])(k_model)
    vae_params = jax.jit(b["init_vae"])(k_vae)
    variables = {"params": params}
    vae_decode = make_decode_fn(b["vae"], vae_params)
    prompts = harness.make_prompts(cell, dalle_cfg, MAX_REQUESTS, seed)
    primes = make_primes(dalle_cfg, MAX_REQUESTS, n_prime, seed)
    keys = jax.random.split(k_run, MAX_REQUESTS)
    image_len = dalle_cfg.image_seq_len
    sampled_len = image_len - n_prime

    # the two programs, compiled for their one shape each
    prefill = b["prefill"].lower(variables, prompts[:1], primes[:1]).compile()
    decode = b["decode"].lower(
        variables, *jax.eval_shape(
            lambda v, t, p: tile_prefill(*b["prefill"](v, t, p), fanout),
            variables, prompts[:1], primes[:1]), keys[0],
        primes[:1]).compile()

    def prefilled(i):
        prime = jnp.asarray(primes[i:i + 1])
        first1, caches1 = prefill(variables, jnp.asarray(prompts[i:i + 1]),
                                  prime)
        return tile_prefill(first1, caches1, fanout), prime

    def pictures(codes):
        with tracer.span("bench:vae_decode"):
            return np.concatenate([
                np.asarray(jax.device_get(vae_decode(codes[at:at + chunk])))
                for at in range(0, fanout, chunk)])

    def request(i):
        """One whole primed ``generate`` call; returns codes (device) and
        images (host)."""
        with tracer.span("bench:generate"):
            (first, caches), prime = prefilled(i)
            codes = decode(variables, first, caches, keys[i], prime)
        return codes, pictures(codes)

    # everything of a request but the scan, so that nothing is traced,
    # compiled or first run inside the window but the scan itself
    jax.block_until_ready(prefilled(0))
    pictures(jnp.zeros((fanout, image_len), jnp.int32))

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
                and codes_host.shape == (fanout, image_len)
                and bool((codes_host[:, :n_prime] == primes[i - 1]).all()))
    del last, images
    t_check = time.perf_counter()
    verdict = compare(b["dalle"], params,
                      np.repeat(prompts[i - 1:i], len(rows), axis=0),
                      codes_host[rows], n_prime, rows=rows, fanout=fanout,
                      key=keys[i - 1], filter_thres=float(tr["filter_thres"]),
                      temperature=float(tr["temperature"]))
    ok = (complete and verdict["ok"]
          and bool(((codes_host >= 0)
                    & (codes_host < dalle_cfg.num_image_tokens)).all())
          and vae_decode._cache_size() == 1)
    tokens_per_s = n_req * fanout * sampled_len / (t1 - t0)
    gaps = np.diff([t0] + done)
    return harness.Outcome(
        correct=ok, attempted=n_req * fanout, failed=0,
        end_to_end={"gen_tokens_per_s": tokens_per_s},
        host={"tokens_per_s": tokens_per_s, "requests": n_req,
              "rows": fanout, "request_s_median": float(np.median(gaps)),
              "decode_steps_traced": sampled_len - 1, "n_prime": n_prime,
              "window_s": t1 - t0, "check": verdict,
              "check_s": time.perf_counter() - t_check,
              "trace_counts": {"vae_decode": int(vae_decode._cache_size())}},
        programs={"jit_bench_decode": decode} if tracer.on else {},
        main_program="jit_bench_decode", memory_peak_bytes=memory_peak,
        notes=[f"{n_req} requests x {fanout} images, {sampled_len} sampled "
               f"codes each after {n_prime} primed; check {verdict}"])
