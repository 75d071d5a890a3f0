"""One prompt fanned out to N candidates, as ``generate.py`` does it for
``--text``: the prompt prefilled once at batch 1, its caches tiled over the
candidates, one jitted ``decode_codes`` scan, the VAE decode, images fetched
to the host.  Closed loop, one client: the next request starts when the last
one's images are on the host.

The three programs are jitted once in set-up and reused, as a process that
serves many prompts would (``cli.iter_generated_chunks`` builds new ``jit``
objects on every call, so it retraces per prompt: PERF.md, Open questions).

Traffic parameters: ``fanout``, ``filter_thres``, ``temperature``, ``text``,
``check_sequences``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import checks, harness

MAX_REQUESTS = 256


def build(cell, dalle_cfg, vae_cfg):
    from dalle_pytorch_tpu.models.dalle import decode_codes, prefill_codes

    tr = cell.traffic
    dalle, vae, init_dalle, init_vae = harness.init_fns(dalle_cfg, vae_cfg)
    # named, so that the trace's programs are jit_bench_prefill/_decode
    def bench_prefill(v, t):
        return prefill_codes(dalle, v, t)

    def bench_decode(v, first, caches, key):
        return decode_codes(dalle, v, first, caches, key,
                            filter_thres=float(tr["filter_thres"]),
                            temperature=float(tr["temperature"]))

    prefill, decode = jax.jit(bench_prefill), jax.jit(bench_decode)
    return dict(dalle=dalle, vae=vae, init_dalle=init_dalle,
                init_vae=init_vae, prefill=prefill, decode=decode)


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

    # correctness, outside the window: the last request's first sequences
    codes, images = last
    k = int(tr["check_sequences"])
    codes_host = np.asarray(jax.device_get(codes))
    complete = (images.shape == (fanout, vae_cfg.image_size,
                                 vae_cfg.image_size, 3)
                and bool(np.isfinite(images).all())
                and codes_host.shape == (fanout, image_len))
    verdict = checks.compare(b["dalle"], params,
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
        main_program="jit_bench_decode",
        notes=[f"{n_req} requests x {fanout} images; check {verdict}"])
