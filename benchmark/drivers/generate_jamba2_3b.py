"""``drivers/generate.py``'s closed loop (one prompt prefilled at batch 1, its
decode state tiled over the candidates, one jitted ``decode_codes`` scan named
``jit_bench_decode``, the VAE decode, images fetched to the host) for a
configuration that ``benchmark/reference.py`` does not cover: DALL-E over the
Jamba family's trunk, held to ``benchmark/reference_jamba2_3b.py``.

The loop is ``generate.py``'s call for call and shares its ``build``; what
differs is the comparison that decides ``correct``: the same two as
``checks.compare`` (teacher-forced logits through ``DALLE.prefill`` +
``DALLE.decode_step`` against the reference's full forward; the timed
program's codes inside the reference's top-k), against this configuration's
reference and with this configuration's tolerance.  The decode state and the
last request's images are freed before the reference runs: the model's 6 GB
stay, and the reference upcasts one layer at a time.

Traffic parameters: ``fanout``, ``filter_thres``, ``temperature``, ``text``,
``check_sequences``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import checks, harness, reference_jamba2_3b
from benchmark.drivers.generate import MAX_REQUESTS, build

#: Largest |program logit - reference logit| allowed, in units of the
#: reference logits' standard deviation over the image vocabulary at that
#: position.  The program multiplies bfloat16 weights and activations (8 bits
#: of mantissa) with float32 sums through 28 layers with no LayerScale (the
#: 2021 block's LayerScale of 0.1 damps every layer's rounding tenfold; here
#: each of 56 sublayers adds its own at full size: 0.03-0.04 std on average,
#: no one projection more than a fifth of it, and a float32 residual stream
#: takes off an eighth), with the recurrent state, its decay and the norms
#: in float32.  Two readings on the v5e set the limit (PERF.md, Findings
#: PR 27; seven seeds): the program's largest over 4 x 1024 x 8192 logits,
#: 0.364 to 0.408; and the reference with every layer's matrix products on
#: operands rounded to an 8-bit float (e4m3, scaled per tensor: the nearest
#: precision below the configuration's bfloat16), 3.49 to 4.00, which every
#: run takes again as ``lowprec_err_std`` and which must fail.  1.0 is 2.5
#: times the largest of the first and 3.5 times under the least of the
#: second; a wrong mask or a dropped norm moves logits by whole stds (4.2 on
#: the CPU twin).
LOGIT_TOL = 1.0

#: Share of sampled codes that must pass the top-k test: ``checks``' own.
TOP_K_SHARE = checks.TOP_K_SHARE


def compare(dalle, params, prompts, codes, filter_thres: float) -> dict:
    """``checks.compare`` against this configuration's reference, plus the
    tolerance's second reading."""
    cfg = dalle.cfg
    codes = np.asarray(codes)
    in_range = bool(((codes >= 0) & (codes < cfg.num_image_tokens)).all())
    clipped = jnp.asarray(np.clip(codes, 0, cfg.num_image_tokens - 1))
    prompts = jnp.asarray(prompts)
    ref = np.asarray(reference_jamba2_3b.image_logits(params, cfg, prompts,
                                                      clipped))
    low = np.asarray(reference_jamba2_3b.image_logits(
        params, cfg, prompts, clipped, matmul_dtype=jnp.float8_e4m3fn))
    got = np.asarray(checks.program_logits(dalle, params, prompts, codes),
                     np.float32)
    std = ref.std(-1, keepdims=True)
    logit_err = float((np.abs(got - ref) / std).max())
    lowprec_err = float((np.abs(low - ref) / std).max())
    k = checks.top_k_count(cfg, filter_thres)
    kth = np.partition(ref, -k, axis=-1)[..., -k]
    chosen = np.take_along_axis(ref, codes[..., None], -1)[..., 0]
    share = float((chosen >= kth - LOGIT_TOL * std[..., 0]).mean())
    return {"codes_in_range": in_range, "logit_err_std": logit_err,
            "lowprec_err_std": lowprec_err, "top_k_share": share, "k": k,
            "ok": bool(in_range and np.isfinite(logit_err)
                       and logit_err <= LOGIT_TOL and share >= TOP_K_SHARE)}


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

    # correctness, outside the window: the last request's first sequences.
    # Nothing of the decode state is held any more (a request keeps its
    # codes and images only), so the reference finds the chip with the model
    # alone on it.
    codes, images = last
    k = int(tr["check_sequences"])
    codes_host = np.asarray(jax.device_get(codes))
    complete = (images.shape == (fanout, vae_cfg.image_size,
                                 vae_cfg.image_size, 3)
                and bool(np.isfinite(images).all())
                and codes_host.shape == (fanout, image_len))
    verdict = compare(b["dalle"], params,
                      np.repeat(prompts[i - 1:i], k, axis=0),
                      codes_host[:k], float(tr["filter_thres"]))
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
