"""``drivers/generate.py``'s closed loop for a primed request over a routed,
windowed trunk, held to ``benchmark/reference_smallthinker_21ba3b.py``: one
prompt *and its prime codes* prefilled at batch 1 (``jit_bench_prefill``), the
decode state tiled over the candidates, one jitted ``decode_codes`` scan over
the codes that are left (``jit_bench_decode``), the VAE decode a chunk of
candidates at a time (64 pictures of 512 px at once would hold 4.3 GB in the
decoder's last activation beside the model), images fetched to the host.
Closed loop, one client.  Only sampled codes count as tokens.

Set-up compiles the two programs ahead of time and runs everything of a
request but the scan (the prefill, the tiling, the VAE chunks on stand-in
codes): the scan is 17.7 s of a 17.7 s request and has one shape, so running
it to warm it would double the cell's set-up in every run.

What decides ``correct``, on what the timed program produced at the timed
sizes (module constants below, each with its two readings):

(a) teacher-forced logits through ``DALLE.prefill`` (text and prime) and
    ``DALLE.decode_step`` (every later code, through the rings) against the
    reference's full forward pass, at every sampled position of the checked
    candidates (the first and the last row of the fan-out);
(b) **the timed codes themselves, redrawn.**  (a) runs a program of its own
    at batch 2; it cannot see the timed scan at the full fan-out, the tiled
    and lane-dense carry, the ring's wrap inside the scan or the sampler.
    So the reference's logits, teacher-forced along the timed codes, are
    cut at the reference's own top-k and sampled again with the key the
    timed request used at each tick (``decode_codes``' schedule: one key
    for the first code, one a tick after it; the same ``categorical`` over
    ``[fanout, codes]``, the checked rows in their own places).  Where the
    timed program computed what the reference computes, the same noise picks
    the same code, except where two codes' perturbed logits lie closer than
    the bfloat16 program's error: ``redraw_share`` of the sampled codes must
    come out the same, and the share among the positions past the window
    (``redraw_share_wrapped``: the ring has wrapped there) as well.  (This
    replaces the generate cells' top-k share, which says nothing here: at
    ``filter_thres`` 0.9 the sampler keeps int(0.1 x 151,936) = 15,193 ids,
    more than the 8,192 codes there are.)
(c) **routing by rule, not by tolerance.**  With random weights the 6th and
    7th router probabilities of a token often differ by less than bfloat16
    rounding moves them (one position-layer in twenty at full width), and
    there the program may rightly choose the other expert.  The program's
    own choices (what its expert layers ``sow``) are handed to the
    reference, which weights them by *its* probabilities and reports how
    far down its own ranking they reach: every handed expert must have at
    least ``1 - ROUTE_MARGIN`` of the reference's 6th probability
    (``1 - ROUTE_MARGIN_FIRST`` in layer 0), and the share of positions
    where the two sets differ at all (``route_tie_share``) must stay under
    ``ROUTE_TIE_CAP``.  (a) and (b) then hold at *every* position, tied or
    not, and no tolerance is widened for a tie.

As for ``jamba2-3b``, every run reads the reference a second time with matrix
operands rounded to e4m3 (same routing, same positions): its logits
(``lowprec_err_std``) must fail ``LOGIT_TOL`` and its redraw
(``lowprec_redraw_share``) must fail ``REDRAW_SHARE``.  The decode state and
the last request's images are freed before the reference runs.

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
from benchmark import reference_smallthinker_21ba3b as reference
from benchmark.drivers.generate import MAX_REQUESTS

#: Largest |program logit - reference logit| allowed, in units of the
#: reference logits' standard deviation over the image vocabulary at that
#: position, the reference using the program's experts (c).  The program
#: multiplies bfloat16 weights and activations with float32 sums through 4
#: layers of 2 sublayers without LayerScale, on a bfloat16 residual stream.
#: Two readings on the v5e set the limit (PERF.md, Findings PR 32; eleven
#: seeds): the program's largest over 2 x 2304 x 8192 logits, 0.043 to 0.048;
#: and the reference with every matrix product on operands rounded to e4m3
#: (the nearest precision below bfloat16), 0.59 to 0.74, which every run
#: takes again as ``lowprec_err_std`` and which must fail.  0.15 is three
#: times the first and a quarter of the second; one wrongly chosen expert
#: moves a position's logits by 0.4 (median, CPU twin at full width).
LOGIT_TOL = 0.15

#: Least share of the timed sampled codes that the reference's logits must
#: give back under the timed keys (b), over all 2 x 2,304 sampled positions
#: and over the 2 x 256 past the window alone.  Readings on the v5e (PERF.md,
#: Findings PR 32; ten seeds): the program 0.9887 to 0.9922 (0.984 to 0.994
#: past the window: one position in a hundred has two perturbed logits closer
#: than the program's error), the e4m3 reference 0.933 to 0.942, which every
#: run takes again as ``lowprec_redraw_share`` and which must fail.  0.965
#: lies between them, four standard deviations of a 512-position sample under
#: the first and six of a 4,608-position one over the second; a scan that
#: loses its ring at the wrap, tiles its carry wrongly or draws otherwise
#: reads near 0.
REDRAW_SHARE = 0.965

#: In layers 1-3 a chosen expert may rank below the reference's 6th only if
#: its reference probability is within this share of the 6th's: the two are
#: tied as far as bfloat16 can tell.  Readings through ``compare`` at full
#: size on the v5e (PERF.md, Findings PR 32): the program's least reach
#: 0.970 to 0.993 (twenty-one seeds: the largest gap a rightful flip bridged
#: was 3.0%); with a routing fault planted (``benchmark/tests/
#: test_smallthinker_21ba3b.py::planted``) 0.11 to 0.30 for a router that
#: reads the state after attention, 0.14 to 0.55 for one fed the normed
#: input, 0.22 to 0.64 for five experts with one taken twice.
ROUTE_MARGIN = 0.1

#: The same in layer 0, where the embedding's 0.02 keeps all 64
#: probabilities within 5% of each other and the margin above would pass
#: any six.  Its router reads the embedding itself: both sides multiply the
#: same bfloat16 rows into float32 sums and differ in the order of the sums
#: alone, so the sets are equal (no position differed in any run: reach
#: 1 - 6e-8, one ulp) but for a tie at float32's own rounding, which set
#: equality would refuse about one run in twenty-five.  The router after
#: attention reads 0.911 here: 1e-4 is the geometric middle of the two.
ROUTE_MARGIN_FIRST = 1e-4

#: Most positions at which program and reference may choose different
#: experts in any layer: 0.126 to 0.152 read on the v5e for the program
#: (0 / 4 / 5 / 5% by layer), 0.996 to 1.0 for each planted fault.
ROUTE_TIE_CAP = 0.25


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


def make_primes(dalle_cfg, count: int, n_prime: int, seed: int) -> np.ndarray:
    """``[count, n_prime]`` seeded image codes: they stand for the encoded
    half-given picture as random ids stand for the caption."""
    rng = np.random.default_rng([seed, 11])
    return rng.integers(0, dalle_cfg.num_image_tokens,
                        size=(count, n_prime)).astype(np.int32)


def program_logits(dalle, params, prompts, codes, n_prime: int):
    """Teacher-forced logits ``[b, image_seq_len - n_prime,
    num_image_tokens]`` through the program's primed prefill and cached
    decode step, and the experts its layers chose at every input position,
    ``[layers, b, seq_len, k]``."""
    from dalle_pytorch_tpu.models.dalle import DALLE

    cfg = dalle.cfg
    n_pre = cfg.text_seq_len + 1 + n_prime

    def chosen(state):
        layers = state["intermediates"]["transformer"]
        return jnp.stack([layers[f"layers_{i}_ff"]["moe"]["top_idx"][0]
                          for i in range(cfg.depth)])

    def run(variables, text, codes):
        (first, caches), state = dalle.apply(
            variables, text, codes[:, :n_prime], method=DALLE.prefill,
            mutable=["intermediates"])

        def step(carry, code):
            caches, index = carry
            (logits, caches), state = dalle.apply(
                variables, code, caches, index, method=DALLE.decode_step,
                mutable=["intermediates"])
            return (caches, index + 1), (logits, chosen(state)[:, :, 0])

        _, (rest, routed) = jax.lax.scan(
            step, (caches, jnp.asarray(n_pre)), codes[:, n_prime:-1].T)
        logits = jnp.concatenate([first[:, None], rest.transpose(1, 0, 2)], 1)
        routing = jnp.concatenate(
            [chosen(state), routed.transpose(1, 2, 0, 3)], axis=2)
        return logits, routing

    return jax.jit(run)({"params": params}, jnp.asarray(prompts),
                        jnp.asarray(codes))


@functools.partial(jax.jit, static_argnames=("fanout", "k", "temperature"))
def redraw(logits, key, rows, *, fanout: int, k: int, temperature: float):
    """``[r, steps]`` codes: what ``decode_codes``' sampler draws from
    ``logits`` ``[r, steps, codes]`` (row ``rows[i]`` of a fan-out of
    ``fanout``) under the request's ``key``.  The key schedule is
    ``decode_codes``' (one split for the first code, then one key a tick);
    the cut at the k-th largest and the draw are written here, the draw as
    the same ``jax.random.categorical`` over ``[fanout, codes]`` so that each
    checked row meets the noise the timed row met."""
    steps, codes = logits.shape[1:]
    key, key0 = jax.random.split(key)
    keys = jnp.concatenate([key0[None], jax.random.split(key, steps - 1)])

    def tick(args):
        key, x = args                                   # [r, codes]
        if temperature != 1:
            x = x / temperature
        if k < codes:
            x = jnp.where(x < jax.lax.top_k(x, k)[0][..., -1:], -jnp.inf, x)
        every = jnp.zeros((fanout, codes), x.dtype).at[rows].set(x)
        return jax.random.categorical(key, every, axis=-1)[rows]

    return jax.lax.map(tick, (keys, logits.transpose(1, 0, 2))).T


def compare(dalle, params, prompts, codes, n_prime: int, *, rows, fanout: int,
            key, filter_thres: float, temperature: float) -> dict:
    """(a), (b) and (c) of the module docstring on ``[k, text_seq_len]``
    prompts and the ``[k, image_seq_len]`` codes (prime, then sampled) that
    rows ``rows`` of the timed request under ``key`` returned for them; the
    reference one sequence at a time."""
    cfg = dalle.cfg
    codes = np.asarray(codes)
    in_range = bool(((codes >= 0) & (codes < cfg.num_image_tokens)).all())
    clipped = np.clip(codes, 0, cfg.num_image_tokens - 1)
    got, routing = program_logits(dalle, params, prompts, clipped, n_prime)
    sampler_dtype = got.dtype
    got = np.asarray(got, np.float32)
    ref, low, reach, differs = [], [], [], []
    for i in range(codes.shape[0]):
        args = (params, cfg, jnp.asarray(prompts[i:i + 1]),
                jnp.asarray(clipped[i:i + 1]))
        logits, routes = reference.image_logits(
            *args, routing=routing[:, i:i + 1])
        ref.append(np.asarray(logits[:, n_prime:]))
        reach.append(np.asarray(routes["reach"]))
        # sets compared as sets: on the chip x / x may read one ulp under 1
        differs.append((np.sort(np.asarray(routing[:, i:i + 1]), -1)
                        != np.sort(np.asarray(routes["top_idx"]), -1)
                        ).any(-1))
        low.append(np.asarray(reference.image_logits(
            *args, routing=routing[:, i:i + 1],
            matmul_dtype=jnp.float8_e4m3fn)[0][:, n_prime:]))
    ref, low = np.concatenate(ref), np.concatenate(low)
    reach = np.concatenate(reach, axis=1).min((1, 2))     # [layers]
    differs = np.concatenate(differs, axis=1)        # [layers, k, seq_len]
    std = ref.std(-1, keepdims=True)
    logit_err = float((np.abs(got - ref) / std).max())
    lowprec_err = float((np.abs(low - ref) / std).max())

    sampled = codes[:, n_prime:]
    k = checks.top_k_count(cfg, filter_thres)
    draw = functools.partial(redraw, key=key, rows=jnp.asarray(rows),
                             fanout=fanout, k=k, temperature=temperature)
    same = np.asarray(draw(jnp.asarray(ref, sampler_dtype))) == sampled
    same_low = np.asarray(draw(jnp.asarray(low, sampler_dtype))) == sampled
    # the ring of a window layer has wrapped where the input position has
    # passed its slots
    wrapped = (cfg.text_seq_len + n_prime + np.arange(sampled.shape[1])
               >= min(cfg.cache_lens))
    share, share_low = float(same.mean()), float(same_low.mean())
    share_wrapped = float(same[:, wrapped].mean()) if wrapped.any() else None

    tie_share = float(differs.any(0).mean())
    return {"codes_in_range": in_range, "logit_err_std": logit_err,
            "lowprec_err_std": lowprec_err, "redraw_share": share,
            "redraw_share_wrapped": share_wrapped,
            "lowprec_redraw_share": share_low, "k": k,
            "rows": [int(r) for r in rows],
            "route_tie_share": tie_share,
            "route_reach_min": [float(x) for x in reach],
            "route_differs_by_layer": [float(x) for x in
                                       differs.mean((1, 2))],
            "ok": bool(in_range and np.isfinite(logit_err)
                       and logit_err <= LOGIT_TOL
                       and share >= REDRAW_SHARE
                       and (share_wrapped is None
                            or share_wrapped >= REDRAW_SHARE)
                       and reach[0] >= 1 - ROUTE_MARGIN_FIRST
                       and reach[1:].min() >= 1 - ROUTE_MARGIN
                       and tie_share <= ROUTE_TIE_CAP
                       and lowprec_err > LOGIT_TOL
                       and share_low < REDRAW_SHARE)}


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
              "trace_counts": {"vae_decode": int(vae_decode._cache_size())}},
        programs={"jit_bench_decode": decode} if tracer.on else {},
        main_program="jit_bench_decode", memory_peak_bytes=memory_peak,
        notes=[f"{n_req} requests x {fanout} images, {sampled_len} sampled "
               f"codes each after {n_prime} primed; check {verdict}"])
