"""Benchmark: DALLE CUB-200 train-step throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "meta",
"platform", "device_kind", "device_count"}.

Config matches the reference's CUB-200 run (ref train_dalle.py:74-97): dim
256, depth 8, heads 8, d_head 64, text_seq 80, image fmap 32 (8192-token
VAE), attn cycle full/axial_row/axial_col/conv_like, batch 16 — the setup
whose loss curves are the repo's only committed perf artifact
(all-logs/cool-frog-21.txt, BASELINE.md).  The reference publishes no
throughput numbers ("published": {} in BASELINE.json), so vs_baseline is
null.

Measurement: the production train step (training.make_dalle_train_step,
codes path) is iterated inside a jitted ``lax.scan`` — one dispatch covers
all steps, so the number is device time for the steps and not the host's
per-step dispatch (at ~80 ms a step the Python loop, the loss fetch and the
rng split would otherwise sit between steps).  The final loss is fetched
with ``device_get``, which cannot complete before the whole scan has run.

Every stage is a plain call: whatever raises ends the process non-zero.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

STEPS = 50


def device_record() -> dict:
    """What every record says about where it ran, as jax reports it."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def ledger_keys(cfg, *, target, plan, batch, **extra):
    """The perf-ledger join keys for one measured point: hash the SAME
    payload tools/graftprof.py hashes for its predicted row at this
    (config, target, plan, batch), so a real-chip measurement lands
    beside its roofline prediction in PERF_LEDGER.json (a point with no
    prediction still lands, as a measured-only stub).  Spread the result
    into a ``record_history`` record."""
    from dalle_pytorch_tpu.obs import prof

    payload = prof.fingerprint_payload(cfg, target=target, plan=plan,
                                       batch=batch, **extra)
    return {"ledger_fingerprint": prof.row_fingerprint(payload),
            "ledger_target": target}


def record_history(record):
    """Self-record one measurement: a ``bench`` event into the graftscope
    stream (always — CPU dev runs included, marked by their platform) and,
    for REAL-CHIP runs only, the same line appended to
    all-logs-tpu/bench-history.jsonl.  The event payload IS the history
    line, so the committed history is derivable from telemetry alone
    (``tools/obs_report.py --bench-jsonl``); arm the stream with
    BENCH_TELEMETRY_DIR (or run under a trainer-installed telemetry).

    Records carrying ``ledger_keys(...)`` additionally append a measured
    row to PERF_LEDGER.json under the prediction's fingerprint —
    real-chip runs only, unless GRAFT_PERF_LEDGER redirects the ledger
    (CPU smoke tests exercise the join against a scratch file)."""
    from dalle_pytorch_tpu.obs import prof, telemetry

    where = device_record()
    # "device" is the history envelope's original key (the 2026-08-02 rows
    # and obs_report --bench-jsonl carry it); the three fields beside it
    # are what every record says since
    line = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "device": where["device_kind"], **where, **record}
    telemetry.emit("bench", str(record.get("metric", "bench")), **line)
    on_cpu = where["platform"] == "cpu"
    scratch_ledger = os.environ.get("GRAFT_PERF_LEDGER")  # set at all: armed
    if record.get("ledger_fingerprint") and (not on_cpu or scratch_ledger):
        prof.append_measured(
            {k: record[k] for k in ("metric", "value", "unit",
                                    "mfu", "tflops") if k in record},
            fingerprint=record["ledger_fingerprint"],
            target=record.get("ledger_target", ""))
    if on_cpu:
        return  # CPU runs (tests, dev smoke) are not chip evidence
    # graftlint: disable=ENV001 (path-valued var: empty/unset mean default)
    history = os.environ.get("BENCH_HISTORY") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "all-logs-tpu", "bench-history.jsonl")
    with open(history, "a") as f:
        f.write(json.dumps(line) + "\n")


def cub200_config(use_pallas: bool = False):
    """The CUB-200 benchmark model (ref train_dalle.py:74-97), shared by the
    train and generate stages."""
    from dalle_pytorch_tpu import DALLEConfig

    return DALLEConfig(
        dim=256, num_text_tokens=7800, text_seq_len=80, depth=8, heads=8,
        dim_head=64, attn_types=("full", "axial_row", "axial_col", "conv_like"),
        num_image_tokens=8192, image_size=256, image_fmap_size=32,
        use_pallas=use_pallas, dtype=jnp.bfloat16,
    )


def _scan_measure(run_steps, params, opt_state, rng, steps, items_per_step):
    """Shared warmup + timing harness for the scan-of-steps benchmarks: one
    compile, then each measure() times a scan and syncs on the final loss.
    All bench loops go through here so their measured semantics can't drift
    (and the device sync is a plain statement — never inside an assert,
    which python -O would strip, leaving only async dispatch time)."""
    _, _, loss = run_steps(params, opt_state, rng, steps)
    warm = float(jax.device_get(loss))
    assert jnp.isfinite(warm), "non-finite warmup loss"

    def measure():
        t0 = time.perf_counter()
        _, _, loss = run_steps(params, opt_state, rng, steps)
        final = float(jax.device_get(loss))  # forces the whole scan to finish
        dt = time.perf_counter() - t0
        assert jnp.isfinite(final), "non-finite bench loss"
        return items_per_step * steps / dt, dt

    return measure


def make_train_measure(steps: int = STEPS, batch: int = 16, **overrides):
    """Build + compile the scan-of-steps train loop once.  Returns
    ``(measure, cfg, batch)`` where each ``measure()`` call times one scan
    and returns ``(images_per_sec, dt)`` — shared by run() and
    tools/perf_ab.py so the measured loop can never drift between them.
    ``overrides`` replace DALLEConfig fields (e.g. use_pallas=True).
    ``batch`` defaults to the reference's 16 (ref train_dalle.py:87) —
    the headline number always uses it; other values are for the
    batch-scaling A/B (perf_ab ``batch64``/``batch128``)."""
    import dataclasses

    from dalle_pytorch_tpu import DALLE
    from dalle_pytorch_tpu.training import make_dalle_train_step, make_optimizer

    cfg = cub200_config()
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    model = DALLE(cfg)

    rng = jax.random.PRNGKey(0)
    text = jax.random.randint(rng, (batch, cfg.text_seq_len), 0, cfg.num_text_tokens)
    codes = jax.random.randint(rng, (batch, cfg.image_seq_len), 0, cfg.num_image_tokens)
    params = jax.jit(lambda r: model.init(r, text[:1], codes[:1])["params"])(rng)
    tx = make_optimizer(3e-4)
    opt_state = jax.jit(tx.init)(params)

    step_fn = make_dalle_train_step(model, tx, vae=None, jit=False)

    @functools.partial(jax.jit, static_argnames="n_steps")
    def run_steps(params, opt_state, rng, n_steps):
        def body(carry, _):
            params, opt_state, rng = carry
            rng, k = jax.random.split(rng)
            params, opt_state, loss = step_fn(params, opt_state, None, text,
                                              codes, k)
            return (params, opt_state, rng), loss

        (params, opt_state, rng), losses = jax.lax.scan(
            body, (params, opt_state, rng), None, length=n_steps)
        return params, opt_state, losses[-1]

    measure = _scan_measure(run_steps, params, opt_state, rng, steps, batch)
    return measure, cfg, batch


def run(use_pallas: bool = False, steps: int = STEPS):
    # BENCH_BATCH: record a candidate headline at a different batch without
    # editing code.  The JSON meta carries the batch either way, and
    # images/sec stays the per-image basis across batch sizes.  BENCH_PALLAS
    # / BENCH_PALLAS_BLOCK likewise select the flash-kernel path and its
    # tile size (the manual session of 2026-08-02 had 512-tiles above the
    # dense path; older code, log removed, not re-measured).
    from dalle_pytorch_tpu.utils.helpers import env_flag

    batch = int(os.environ.get("BENCH_BATCH", 16))
    use_pallas = use_pallas or env_flag("BENCH_PALLAS")
    overrides = dict(use_pallas=use_pallas)
    # graftlint: disable=ENV001 (value-valued: the value IS the tile size; 0 is not a valid block)
    if use_pallas and os.environ.get("BENCH_PALLAS_BLOCK"):
        blk = int(os.environ["BENCH_PALLAS_BLOCK"])
        overrides.update(pallas_block_q=blk, pallas_block_k=blk)
    measure, cfg, batch = make_train_measure(steps, batch=batch, **overrides)
    images_per_sec, dt = measure()
    return images_per_sec, dt, cfg, batch


def vae128_config():
    """The reference's stage-1 trainer config at 128px (ref train_vae.py:
    42-59): 8192 tokens, 2 conv layers, 2 resblocks, emb 512, hid 256 —
    BASELINE.json config 1."""
    from dalle_pytorch_tpu import VAEConfig

    return VAEConfig(image_size=128, num_tokens=8192, codebook_dim=512,
                     num_layers=2, num_resnet_blocks=2, hidden_dim=256)


def make_vae_measure(steps: int = 20, batch: int = 8):
    """Compile a scan-of-steps DiscreteVAE train loop (the reference's
    stage-1 batch size 8); each ``measure()`` returns (images_per_sec, dt)."""
    from dalle_pytorch_tpu import DiscreteVAE
    from dalle_pytorch_tpu.training import make_optimizer, make_vae_train_step

    cfg = vae128_config()
    vae = DiscreteVAE(cfg)
    rng = jax.random.PRNGKey(0)
    images = jax.random.uniform(rng, (batch, cfg.image_size, cfg.image_size, 3))
    params = jax.jit(lambda r: vae.init({"params": r, "gumbel": r},
                                        images[:1])["params"])(rng)
    tx = make_optimizer(1e-3)
    opt_state = jax.jit(tx.init)(params)
    raw_step = make_vae_train_step(vae, tx, donate=False)

    @functools.partial(jax.jit, static_argnames="n")
    def run_steps(params, opt_state, rng, n):
        def body(carry, _):
            p, o, r = carry
            r, k = jax.random.split(r)
            p, o, loss, _ = raw_step(p, o, images, k, jnp.float32(1.0))
            return (p, o, r), loss

        (p, o, r), losses = jax.lax.scan(body, (params, opt_state, rng),
                                         None, length=n)
        return p, o, losses[-1]

    return _scan_measure(run_steps, params, opt_state, rng, steps, batch)


def make_gen_measure(batch: int = 8, **overrides):
    """Compile the jitted KV-cache sampler once; each ``measure()`` call
    returns ``(image_tokens_per_sec, dt)``.

    The first compile of the 1024-step decode scan is the single most
    expensive compile in the repo; callers that want to report it apart
    from the measurement use ``make_gen_measure_deferred`` — this
    convenience form compiles eagerly."""
    compile_fn, _ = make_gen_measure_deferred(batch, **overrides)
    return compile_fn()


def make_gen_measure_deferred(batch: int = 8, **overrides):
    """Build the sampler without touching the device; returns
    ``(compile_fn, cfg)`` where ``compile_fn()`` pays the decode-scan
    compile and returns the ``measure`` closure — so a caller can time (or
    bound) compile and measurement apart.  ``overrides`` replace
    DALLEConfig fields (e.g. ``sliced_kv_decode=False`` for the dense-cache
    A/B control)."""
    import dataclasses

    from dalle_pytorch_tpu import DALLE
    from dalle_pytorch_tpu.models.dalle import generate_codes

    cfg = cub200_config()
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    model = DALLE(cfg)

    def compile_fn():
        # ALL device work lives in here — even PRNGKey/randint dispatch to
        # the backend — so building the closure touches no device
        rng = jax.random.PRNGKey(0)
        text = jax.random.randint(rng, (batch, cfg.text_seq_len), 0,
                                  cfg.num_text_tokens)
        params = jax.jit(lambda r: model.init(
            r, text[:1],
            jnp.zeros((1, cfg.image_seq_len), jnp.int32))["params"])(rng)
        gen = jax.jit(lambda p, t, k: generate_codes(
            model, {"params": p}, t, k, filter_thres=0.9))
        _ = jax.device_get(gen(params, text, rng))  # compile + one warm run

        def measure():
            t0 = time.perf_counter()
            codes = gen(params, text, jax.random.PRNGKey(1))
            _ = jax.device_get(codes)
            dt = time.perf_counter() - t0
            return batch * cfg.image_seq_len / dt, dt

        return measure

    return compile_fn, cfg


def make_serve_measure(num_slots: int = 64, requests_per_slot: int = 2,
                       oversubscribe: float = 1.25,
                       prefix_cache: bool = False, **overrides):
    """Compile the continuous-batching generation service
    (serve.GenerationServer over the slot-based KV arena) at the CUB
    geometry; each ``measure()`` drives a synthetic OPEN-LOOP arrival
    trace and returns ``(aggregate_image_tokens_per_sec, dt)``.

    The trace is calibrated from a closed-loop warm-up run: arrivals are
    spaced at ``service_time / num_slots / oversubscribe`` so ingress
    slightly outpaces service — the queue stays non-empty, slots refill
    the tick they free, and the measured number is sustained
    continuous-batching throughput with requests arriving mid-flight (the
    ROADMAP direction-1 scenario), directly comparable to the static
    ``gen64`` A/B at ``num_slots=64``.  Per-request p50/p99 latency, slot
    occupancy and the no-recompile sentinel are printed to stderr
    (PERF.md "Serve throughput/latency" row schema).  ``overrides``
    replace DALLEConfig fields, exactly like ``make_gen_measure``;
    ``prefix_cache`` is a SERVER knob (the radix prefix cache lives in
    the scheduler, not the model config) — every arrival in the trace
    shares one prompt, so the prefix A/B measures the all-hit admission
    path (one prefill serves the whole drive)."""
    import dataclasses

    import numpy as np

    from dalle_pytorch_tpu import DALLE
    from dalle_pytorch_tpu.serve import GenerationServer

    cfg = cub200_config()
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    model = DALLE(cfg)
    rng = jax.random.PRNGKey(0)
    text = np.asarray(jax.random.randint(
        rng, (cfg.text_seq_len,), 0, cfg.num_text_tokens), np.int32)
    params = jax.jit(lambda r: model.init(
        r, jnp.asarray(text)[None],
        jnp.zeros((1, cfg.image_seq_len), jnp.int32)))(rng)
    server = GenerationServer(model, params, num_slots=num_slots,
                              filter_thres=0.9, prefix_cache=prefix_cache)

    # two closed-loop warm-up passes: the first pays every compile
    # (prefill/admit/tick), the second — compile-warm — calibrates the
    # per-request service time the open loop is paced by (calibrating on
    # the cold pass would stretch the arrival gap by the compile time and
    # the "open-loop" trace would never saturate the slots)
    def closed_loop(seed):
        t0 = time.perf_counter()
        for i in range(num_slots):
            server.submit(text, key=np.asarray([seed, i], np.uint32))
        server.run_until_idle(max_ticks=4 * cfg.image_seq_len)
        server.reset()
        return time.perf_counter() - t0

    closed_loop(7)
    service_time = closed_loop(8)
    gap = service_time / num_slots / oversubscribe

    n_requests = num_slots * requests_per_slot

    def measure():
        arrivals = [(i * gap,
                     dict(text=text, key=np.asarray([13, i], np.uint32)))
                    for i in range(n_requests)]
        t0 = time.perf_counter()
        stats = server.drive(arrivals,
                             max_ticks=4 * n_requests * cfg.image_seq_len)
        dt = time.perf_counter() - t0
        assert stats["failed"] == 0, f"{stats['failed']} serve failures"
        decode_key = "tick_spec" if cfg.spec_decode else "tick"
        assert stats["trace_counts"] == {
            "prefill": 1, "admit": 1, decode_key: 1}, (
            f"serve retraced mid-drive: {stats['trace_counts']}")
        lp50, lp99 = stats["latency_p50"], stats["latency_p99"]
        print(f"serve[{num_slots} slots]: occupancy "
              f"{stats['occupancy']:.2f}, p50 "
              f"{lp50['throughput']:.2f}s, p99 {lp99['throughput']:.2f}s, "
              f"{stats['completed']} requests, "
              f"{stats['preemptions']} preemptions", file=sys.stderr)
        if stats.get("prefix"):
            px = stats["prefix"]
            print(f"serve prefix cache: hit-rate {px['hit_rate']:.2f} "
                  f"({px['hits']} hits / {px['misses']} misses), "
                  f"{px['prefill_flops_saved']:.3g} prefill FLOPs saved",
                  file=sys.stderr)
        if stats.get("spec_accepted_k") is not None:
            print(f"serve spec decode: accepted-K "
                  f"{stats['spec_accepted_k']:.2f}", file=sys.stderr)
        server.reset()
        return stats["decoded_tokens"] / dt, dt

    return measure


def make_ingest_measure(data_format: str, src, shards, batch: int = 16,
                        image_size: int = 64, num_workers: int = 8,
                        sim_step_s: float = 0.005):
    """Host-only input-pipeline throughput: one full epoch of the given
    pipeline (``folder`` = the loose-file datasets, ``shards`` = the
    streaming tar pipeline) pulled through the DevicePrefetcher, with a
    simulated ``sim_step_s`` device step per batch so the measured *stall
    fraction* (prefetcher wait over wall-clock) means what it means in a
    real run: ~0 = the loader hides behind the step, ~1 = the chip would
    idle on input.  Each ``measure()`` returns ``(images_per_sec, dt)``
    and prints the stall fraction to stderr — the BENCH_INGEST stage runs
    it for both formats so a regression in either pipeline (or the gap
    between them) is a number, not a hunch."""
    from dalle_pytorch_tpu.data import stream as dstream
    from dalle_pytorch_tpu.data.dataset import DataLoader, TextImageDataset

    class _HashTok:  # host-only stand-in: ingest measures IO+decode, not BPE
        def tokenize(self, text, context_length, truncate_text=False):
            import numpy as np

            ids = [sum(map(ord, w)) % 997 + 1 for w in text.split()]
            out = np.zeros((1, context_length), np.int64)
            out[0, : len(ids[:context_length])] = ids[:context_length]
            return out

    tok = _HashTok()
    if data_format == "shards":
        ds = dstream.ShardStreamDataset(
            shards, tok, text_len=16, image_size=image_size,
            resize_ratio=0.8)
        dl = dstream.StreamingDataLoader(ds, batch, shuffle=True, seed=0,
                                         num_workers=num_workers)
    else:
        ds = TextImageDataset(src, tok, text_len=16, image_size=image_size,
                              resize_ratio=0.8)
        dl = DataLoader(ds, batch, shuffle=True, seed=0,
                        num_workers=num_workers)

    def measure():
        pf = dstream.DevicePrefetcher(dl, depth=1)
        n = 0
        t0 = time.perf_counter()
        for b in pf:
            n += len(b[0])
            if sim_step_s:
                time.sleep(sim_step_s)
        dt = time.perf_counter() - t0
        frac = min(pf.total_wait_s / dt, 1.0) if dt else 0.0
        print(f"ingest[{data_format}]: stall fraction {frac:.2f} "
              f"({pf.batches} batches)", file=sys.stderr)
        return n / dt, dt

    return measure


def make_fused_rank_measure(batch: int = 8, num_images: int = 16,
                            **overrides):
    """Compile the fused generate -> VAE-decode -> CLIP-rerank pipeline
    (genrank.rank_codes) at the CUB geometry; each ``measure()`` returns
    ``(images_ranked_per_sec, dt)``.

    The DALLE/VAE/CLIP weights are randomly initialized — the measure is
    pipeline wall-clock (decode scan + VAE decoder + CLIP tower, chunked
    and double-buffered, zero disk round-trips), not ranking quality.  The
    prompt rows are identical, so the shared-prefill path is what gets
    measured, exactly as genrank runs it.  ``overrides`` replace DALLEConfig
    fields (e.g. ``kv_cache_bf16=False`` for the f32-cache control)."""
    import dataclasses

    import numpy as np

    import genrank
    from dalle_pytorch_tpu import DALLE, DiscreteVAE, VAEConfig
    from dalle_pytorch_tpu.models.clip import CLIP, CLIPConfig

    cfg = cub200_config()
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    model = DALLE(cfg)
    # a CUB-shaped dVAE decoder (256px, 8192 codes, fmap 32) + a ViT-B/32-
    # shaped trained-CLIP ranker — stand-ins with the production geometry
    vae = DiscreteVAE(VAEConfig(
        image_size=cfg.image_size, num_tokens=cfg.num_image_tokens,
        codebook_dim=256, num_layers=3, num_resnet_blocks=1, hidden_dim=64))
    clip_cfg = CLIPConfig(
        dim_text=256, dim_image=256, dim_latent=256,
        num_text_tokens=cfg.num_text_tokens, text_enc_depth=4,
        text_seq_len=cfg.text_seq_len, text_heads=8, num_visual_tokens=512,
        visual_enc_depth=6, visual_heads=8, visual_image_size=224,
        visual_patch_size=32)
    clip = CLIP(clip_cfg)

    rng = jax.random.PRNGKey(0)
    prompt = jax.random.randint(rng, (1, cfg.text_seq_len), 0,
                                cfg.num_text_tokens)
    text = np.repeat(np.asarray(prompt), num_images, axis=0)
    params = jax.jit(lambda r: model.init(
        r, prompt, jnp.zeros((1, cfg.image_seq_len), jnp.int32))["params"])(rng)
    vae_params = jax.jit(lambda r: vae.init(
        {"params": r, "gumbel": r},
        jnp.zeros((1, cfg.image_size, cfg.image_size, 3)))["params"])(rng)
    clip_params = jax.jit(lambda r: clip.init(
        r, prompt, jnp.zeros((1, 224, 224, 3)))["params"])(rng)

    decode = jax.jit(lambda codes: vae.apply(
        {"params": vae_params}, codes, method=DiscreteVAE.decode))

    @jax.jit
    def score(ims):
        text_lat = clip.apply({"params": clip_params}, prompt,
                              method=CLIP.encode_text)
        img_lat = clip.apply({"params": clip_params},
                             genrank._preprocess(ims, 224),
                             method=CLIP.encode_image)
        temp = jnp.exp(clip_params["temperature"])
        return ((text_lat @ img_lat.T) * temp)[0]

    def run_once(key):
        return genrank.rank_codes(model, params, decode, score, text,
                                  batch_size=batch, top_k=0.9, rng=key)

    run_once(jax.random.PRNGKey(1))  # compile + warm

    def measure():
        t0 = time.perf_counter()
        _, logits = run_once(jax.random.PRNGKey(2))
        dt = time.perf_counter() - t0  # rank_codes returns host arrays: synced
        assert np.isfinite(logits).all(), "non-finite fused-rank logits"
        return num_images / dt, dt

    return measure


def main():
    # persistent XLA compile cache: bench and perf_ab processes of one
    # session share compiles (keyed by HLO)
    from dalle_pytorch_tpu.cli import enable_compilation_cache
    from dalle_pytorch_tpu.obs import telemetry as obs
    from dalle_pytorch_tpu.utils.helpers import env_flag
    from dalle_pytorch_tpu.utils.profiling import (dalle_train_flops,
                                                   device_peak_flops)

    enable_compilation_cache()
    # graftscope: every bench stage emits a `bench` event (record_history),
    # so bench-history.jsonl is derivable from the run's telemetry stream
    # (obs_report --bench-jsonl).  BENCH_TELEMETRY_DIR arms the stream.
    # graftlint: disable=ENV001 (path-valued var: empty/unset mean disabled)
    if os.environ.get("BENCH_TELEMETRY_DIR"):
        obs.init(os.environ["BENCH_TELEMETRY_DIR"],
                 run_id=time.strftime("bench-%Y%m%d-%H%M%S"))
    steps = int(os.environ.get("BENCH_STEPS", STEPS))
    images_per_sec, dt, cfg, batch = run(steps=steps)
    # FLOPs are dense-equivalent (sparse layers counted as full attention),
    # the convention MFU is normally quoted in for sparse models.  MFU needs
    # the device's peak: on a kind the peaks table does not know it is
    # absent ("not measured"), never computed from a guess.
    flops = dalle_train_flops(cfg, batch) * steps / dt
    peak = device_peak_flops()
    mfu = {"mfu": round(flops / peak, 4)} if peak else {}
    print(f"achieved {flops/1e12:.2f} TFLOP/s (dense-equivalent), MFU "
          + (f"{flops/peak:.2%}" if peak else "not measured (unknown "
             f"device kind {jax.devices()[0].device_kind!r})"),
          file=sys.stderr)
    # `meta` makes the measurement self-describing: codes_path=True means
    # the hot loop consumes pre-tokenized VAE codes (the reference
    # re-encodes images every step, ref dalle_pytorch.py:459; the
    # VAE-in-loop number is the opt-in BENCH_VAE stage).
    payload = {
        "metric": "dalle_cub200_train_throughput",
        "value": round(images_per_sec, 2),
        "unit": "images/sec/chip",
        "vs_baseline": None,
        "meta": {
            "steps": steps, "batch": batch, "codes_path": True,
            "use_pallas": cfg.use_pallas,
            **({"pallas_block": cfg.pallas_block_q} if cfg.use_pallas else {}),
        },
    }
    print(json.dumps({**payload, **device_record()}), flush=True)
    record_history({"tflops": round(flops / 1e12, 2), **mfu, **payload,
                    **ledger_keys(cfg, target="dalle/dp", plan="dp",
                                  batch=batch)})

    # informational stages (stderr + history records)
    stats = jax.devices()[0].memory_stats() or {}
    print("device HBM in use after bench: "
          f"{stats['bytes_in_use'] / 2**30:.2f} GiB (peak "
          f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB)"
          if "bytes_in_use" in stats and "peak_bytes_in_use" in stats
          else "device HBM stats not reported by this backend",
          file=sys.stderr)
    # generation (north-star metric #2).  BENCH_GEN_BATCHES selects which
    # gen batches run ("" skips the stage entirely).
    gen_batches = tuple(
        int(b) for b in
        os.environ.get("BENCH_GEN_BATCHES", "8,64").split(",") if b.strip())
    for gen_batch in gen_batches:
        compile_fn, gen_cfg = make_gen_measure_deferred(batch=gen_batch)
        tok_per_sec, _ = compile_fn()()
        print(f"generation (batch {gen_batch}): {tok_per_sec:.1f} "
              "image-tokens/sec (KV-cache sampler)", file=sys.stderr)
        record_history({
            "metric": "dalle_cub200_gen_throughput",
            "value": round(tok_per_sec, 1),
            "unit": "image_tokens/sec",
            "meta": {"batch": gen_batch, "image_only_head": True},
            **ledger_keys(gen_cfg, target="decode", plan="single",
                          batch=gen_batch)})
    if env_flag("BENCH_VAE"):  # opt-in stage-1 number (BASELINE cfg 1)
        vae_ips, _ = make_vae_measure()()
        print(f"vae train (128px): {vae_ips:.2f} images/sec",
              file=sys.stderr)
        record_history({"metric": "vae128_train_throughput",
                        "value": round(vae_ips, 2), "unit": "images/sec",
                        "meta": {"batch": 8},
                        **ledger_keys(vae128_config(), target="vae",
                                      plan="single", batch=8)})
    if env_flag("BENCH_INGEST"):
        # opt-in host-only ingest stage: synthetic corpus -> folder vs
        # shards img/s + stall fraction.  No device work at all — this is
        # the "is the input pipeline the bottleneck" number.
        for fmt, (ips, _dt) in _ingest_stage().items():
            print(f"ingest: {fmt} {ips:.1f} img/s", file=sys.stderr)
            record_history({"metric": "ingest_throughput",
                            "value": round(ips, 1), "unit": "images/sec",
                            "meta": {"format": fmt, "host_only": True}})
    if env_flag("BENCH_SERVE"):  # opt-in continuous-batching serve stage
        serve_slots = int(os.environ.get("BENCH_SERVE_SLOTS", "64"))
        serve_tps, _ = make_serve_measure(num_slots=serve_slots)()
        print(f"serve ({serve_slots} slots, open-loop): {serve_tps:.1f} "
              "image-tokens/sec aggregate", file=sys.stderr)
        record_history({
            "metric": "dalle_cub200_serve_throughput",
            "value": round(serve_tps, 1),
            "unit": "image_tokens/sec",
            "meta": {"slots": serve_slots, "open_loop": True,
                     "oversubscribe": 1.25},
            **ledger_keys(cub200_config(), target="serve-tick",
                          plan="single", batch=serve_slots,
                          num_slots=serve_slots)})
    obs.shutdown()  # flush/close the bench-armed stream (no-op when off)


def _ingest_stage() -> dict:
    """{format: (images_per_sec, dt)} over a synthetic corpus, one warm
    pass (thread-pool spin-up + page cache) before the measured one."""
    import tempfile
    from pathlib import Path

    import numpy as np
    from PIL import Image

    from dalle_pytorch_tpu.data import stream as dstream

    out = {}
    with tempfile.TemporaryDirectory(prefix="bench-ingest-") as tmp:
        tmp = Path(tmp)
        src = tmp / "src"
        src.mkdir()
        rng = np.random.default_rng(0)
        for i in range(int(os.environ.get("BENCH_INGEST_SAMPLES", "128"))):
            img = (rng.uniform(size=(96, 96, 3)) * 255).astype(np.uint8)
            Image.fromarray(img).save(src / f"s{i:05d}.png")
            (src / f"s{i:05d}.txt").write_text("a synthetic caption\n")
        dstream.build_shards(src, tmp / "shards", samples_per_shard=32)
        for fmt in ("folder", "shards"):
            measure = make_ingest_measure(fmt, src, tmp / "shards")
            measure()
            out[fmt] = measure()
    return out


if __name__ == "__main__":
    main()
