#!/usr/bin/env python
"""chip_smoke.py — the quickest proof the system still starts on the chip.

One process drives the main path once, through the entry points a user
calls, at the full width of the model the repo was built for (the
reference's CUB-200 DALL-E: dim 256, depth 8, heads 8 x 64, text 80, fmap 32
-> n = 1104, 8192-token dVAE at 128 px, the bundled CUB BPE, bf16 compute):

1. data     a seeded image folder with caption files, under the work dir;
2. vae      ``train_vae.main`` -> a VAE checkpoint;
3. dalle    ``train_dalle.main --vae_path ... --fp16`` with the script's own
            constants (raw images -> codes -> step), managed checkpoints
            written by ``CheckpointManager`` and verified back;
4. generate ``generate.main`` from that managed checkpoint: shared prefill,
            decode scan, VAE decode, image files;
5. serve    a ``GenerationServer`` on the same checkpoint, greedy requests
            bit-matched against ``decode_codes``, no retrace;
6. pallas   the compiled flash kernel against the dense reference at
            n = 1104, forward and gradients, and present in the HLO.

``--chips 4`` runs ONLY the path that exists across chips and what it is
compared with: one CUB-width train step under ``fsdp4`` and ``dp2.tp2``
against the same step on device 0, and four one-chip replicas behind a
``FleetRouter`` against the single-server sampler.

Weights are random, made from ``--seed``; a few optimizer steps only.  Every
phase asserts what it checks and any failure ends the run non-zero.  The
seconds printed are set-up/run times for reading a log, NOT performance
metrics.  The last stdout line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

Phases are plain functions of a :class:`SmokeSize`, so
``tests/test_chip_smoke.py`` rehearses every one of them at toy width on
the CPU; the command line itself refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import shutil
import sys
import time
import types
import zlib
from pathlib import Path
from typing import Optional

REPO = Path(__file__).resolve().parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

VARIANTS = ("full", "axial_row", "axial_col", "conv_like")


@dataclasses.dataclass(frozen=True)
class SmokeSize:
    """Everything a phase is sized by.  ``FULL`` is what the chip runs."""

    image_size: int = 128
    n_images: int = 96                 # / batch 16 = 6 DALL-E steps per epoch
    bpe_path: Path = REPO / "cub200_bpe_vsize_7800.json"
    # $DALLE_TPU_HPARAMS for train_vae: its width constants (8192 tokens,
    # emb 512, hid 256, 2 layers) stay the script's own; only the epoch
    # count is cut (20 -> 2: 24 steps instead of 240)
    vae_hparams: Optional[dict] = dataclasses.field(
        default_factory=lambda: {"EPOCHS": 2})
    # $DALLE_TPU_HPARAMS for train_dalle: None = no override, the script's
    # own CUB constants (full width); only the toy rehearsal sets one
    dalle_hparams: Optional[dict] = None
    ckpt_every: int = 4                # managed checkpoints at it = 0, 4
    gen_images: int = 8
    serve_requests: int = 4
    serve_slots: int = 4
    # Pallas-vs-dense geometry (the checks tools/chip_equiv.py ran)
    attn_text: int = 80
    attn_fmap: int = 32
    attn_shape: tuple = (2, 8, 64)     # batch, heads, dim_head
    attn_blocks: tuple = (128, 512)
    # --chips 4
    plan_specs: tuple = ("fsdp4", "dp2.tp2")
    plan_batch: int = 16
    fleet_replicas: int = 4
    fleet_requests: int = 8
    fleet_slots: int = 2


FULL = SmokeSize()


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


@contextlib.contextmanager
def _hparams(override: Optional[dict]):
    """Scoped $DALLE_TPU_HPARAMS (the trainers' documented sweep hook)."""
    prev = os.environ.pop("DALLE_TPU_HPARAMS", None)
    if override is not None:
        os.environ["DALLE_TPU_HPARAMS"] = json.dumps(override)
    try:
        yield
    finally:
        os.environ.pop("DALLE_TPU_HPARAMS", None)
        if prev is not None:
            os.environ["DALLE_TPU_HPARAMS"] = prev


class SmokeFailure(Exception):
    """A phase checked something and it did not hold."""


def check(ok, msg: str) -> None:
    """``assert`` that survives ``python -O``."""
    if not ok:
        raise SmokeFailure(msg)


class CompileLedger:
    """What jax says about its own compiles (``jax.monitoring`` events):
    persistent-cache hits and misses, and the seconds spent tracing,
    lowering, compiling and reading the cache — so a log shows whether a
    phase's first call paid compiles or found them cached."""

    KEYS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "compile_s",
        "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
        "/jax/compilation_cache/compile_time_saved_sec": "saved_s"}

    def __init__(self):
        self.totals = dict.fromkeys(self.KEYS.values(), 0.0)
        self._mark = dict(self.totals)
        jax.monitoring.register_event_listener(self._on)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, amount=1.0, **_):
        """Count events get no amount (1 each), duration events seconds."""
        if event in self.KEYS:
            self.totals[self.KEYS[event]] += amount

    def log_phase(self, phase: str) -> None:
        """One line for everything since the last call, then re-mark."""
        d = {k: self.totals[k] - self._mark[k] for k in self.totals}
        self._mark = dict(self.totals)
        # backend_compile_duration spans the cache lookup too: a hit shows
        # up as a short "compile" with its retrieval time inside it
        log(f"phase {phase}: jax saw {int(d['requests'])} cacheable compile "
            f"requests, {int(d['hits'])} persistent-cache hits (read "
            f"{d['cache_read_s']:.1f}s, saved {d['saved_s']:.1f}s); trace "
            f"{d['trace_s']:.1f}s + lower {d['lower_s']:.1f}s + "
            f"compile-or-load {d['compile_s']:.1f}s")


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def assert_on_platform(tree, platform: str, what: str) -> None:
    """Every array leaf of ``tree`` lives on devices of ``platform``."""
    seen = {d.platform for leaf in jax.tree.leaves(tree)
            if isinstance(leaf, jax.Array) for d in leaf.devices()}
    check(seen == {platform}, f"{what} lives on {seen}, expected {platform}")


def peak_bytes(device=None) -> Optional[int]:
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _step_records(tel_dir: Path) -> list:
    from dalle_pytorch_tpu.obs import telemetry

    return [e for e in telemetry.read_events([tel_dir])
            if e.get("kind") == "step" and e.get("name") == "train"]


def _last_step_seconds(steps: list) -> Optional[float]:
    """Host seconds per step between the last two step records (compiles
    are behind them by then) — a run time for reading the log."""
    if len(steps) < 2 or steps[-1]["step"] == steps[-2]["step"]:
        return None
    return ((steps[-1]["mono"] - steps[-2]["mono"])
            / (steps[-1]["step"] - steps[-2]["step"]))


# --- phase 1: data ----------------------------------------------------------

_COLORS = ("red", "yellow", "blue", "black", "white", "brown", "grey",
           "green", "orange")
_PARTS = ("wings", "belly", "crown", "breast", "beak", "tail", "throat")


def make_dataset(work: Path, size: SmokeSize, seed: int):
    """A seeded image folder with stem-paired caption files: flat-colour
    birds-on-a-background blobs at the real image size, captions from a
    CUB-flavoured vocabulary the bundled BPE tokenises.  Returns
    ``(folder, captions)``."""
    from PIL import Image

    folder = work / "data"
    folder.mkdir(parents=True)
    rng = np.random.default_rng(seed)
    px = size.image_size
    yy, xx = np.mgrid[0:px, 0:px]
    captions = []
    for i in range(size.n_images):
        c1, c2 = rng.choice(len(_COLORS), 2, replace=False)
        p1, p2 = rng.choice(len(_PARTS), 2, replace=False)
        img = rng.uniform(0.0, 0.25, (px, px, 3)) + rng.uniform(0.2, 0.7, 3)
        cy, cx = rng.uniform(0.3, 0.7, 2) * px
        blob = ((yy - cy) ** 2 + (xx - cx) ** 2) < (0.25 * px) ** 2
        img[blob] = rng.uniform(0.0, 1.0, 3)
        Image.fromarray((np.clip(img, 0, 1) * 255).astype(np.uint8)).save(
            folder / f"bird_{i:04d}.png")
        caption = (f"this bird has {_COLORS[c1]} {_PARTS[p1]} and a "
                   f"{_COLORS[c2]} {_PARTS[p2]}")
        (folder / f"bird_{i:04d}.txt").write_text(caption + "\n")
        captions.append(caption)
    return folder, captions


# --- phase 2: VAE -----------------------------------------------------------

def phase_train_vae(work: Path, size: SmokeSize, data: Path) -> dict:
    import train_vae

    tel = work / "tel_vae"
    # the CLIs write vae.pt / dalle.pt / samples/ / logs into the cwd
    with contextlib.chdir(work), _hparams(size.vae_hparams):
        _, secs = _timed(lambda: train_vae.main([
            "--image_folder", str(data), "--image_size",
            str(size.image_size), "--telemetry_dir", str(tel),
            "--ckpt_dir", str(work / "ckpt_vae")]))
    ckpt = work / "vae-final.pt"
    check(ckpt.exists(), "train_vae wrote no vae-final.pt")
    steps = _step_records(tel)
    losses = [float(s["loss"]) for s in steps]
    check(losses and np.isfinite(losses).all(), f"VAE losses {losses}")
    return {"ckpt": ckpt, "losses": losses, "first_call_s": secs,
            "last_step_s": _last_step_seconds(steps)}


# --- phase 3: DALL-E --------------------------------------------------------

def expected_first_loss(cfg) -> float:
    """ln-uniform loss of THIS geometry: text and image cross-entropies at
    uniform logits, weighted the way the model weights them."""
    w = cfg.loss_img_weight
    return (np.log(cfg.total_text_tokens)
            + w * np.log(cfg.num_image_tokens)) / (w + 1)


def phase_train_dalle(work: Path, size: SmokeSize, data: Path,
                      vae_ckpt: Path) -> dict:
    import train_dalle
    from dalle_pytorch_tpu import DALLEConfig
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint
    from dalle_pytorch_tpu.utils.ckpt_manager import CheckpointManager

    tel, ckpt_dir = work / "tel_dalle", work / "ckpt_dalle"
    # the CLIs write vae.pt / dalle.pt / samples/ / logs into the cwd
    with contextlib.chdir(work), _hparams(size.dalle_hparams):
        _, secs = _timed(lambda: train_dalle.main([
            "--vae_path", str(vae_ckpt), "--image_text_folder", str(data),
            "--bpe_path", str(size.bpe_path), "--truncate_captions",
            "--fp16", "--epochs", "1", "--telemetry_dir", str(tel),
            "--ckpt_dir", str(ckpt_dir),
            "--ckpt_every", str(size.ckpt_every)]))
    check((work / "dalle-final.pt").exists(), "no dalle-final.pt")
    check(any((work / "samples" / "dalle").glob("*.png")),
          "train_dalle wrote no sample image")
    steps = _step_records(tel)
    losses = [float(s["loss"]) for s in steps]
    check(len(losses) >= 4, f"only {len(losses)} optimizer steps ran")
    check(np.isfinite(losses).all(), f"DALL-E losses {losses}")

    # the managed checkpoint, read back: manifest + per-file crc32 verified
    # by latest_valid(), then the payload itself
    info = CheckpointManager(ckpt_dir).latest_valid()
    check(info is not None, f"no manifest-valid checkpoint under {ckpt_dir}")
    ckpt = load_checkpoint(info.payload)
    check(all(np.isfinite(np.asarray(leaf)).all()
              for leaf in jax.tree.leaves(ckpt["weights"])),
          "non-finite weights in the managed checkpoint")
    cfg = DALLEConfig.from_dict(dict(ckpt["hparams"]))
    want = expected_first_loss(cfg)
    check(want - 0.5 <= losses[0] <= want + 1.5,
          f"first loss {losses[0]:.3f} far from the ln-uniform {want:.3f} "
          "of this geometry")
    return {"ckpt": info.payload, "ckpt_step": info.step, "cfg": cfg,
            "losses": losses, "ln_uniform": float(want),
            "first_call_s": secs,
            "last_step_s": _last_step_seconds(steps)}


# --- phase 4: generate ------------------------------------------------------

def phase_generate(work: Path, size: SmokeSize, dalle_ckpt: Path,
                   caption: str, platform: str) -> dict:
    import generate
    from dalle_pytorch_tpu.cli import (iter_generated_chunks,
                                       load_dalle_checkpoint, make_decode_fn,
                                       select_tokenizer)

    out_dir = work / "outputs"
    n = size.gen_images
    with contextlib.chdir(work):
        _, first_s = _timed(lambda: generate.main([
            "--dalle_path", str(dalle_ckpt), "--text", caption,
            "--num_images", str(n), "--batch_size", str(n),
            "--bpe_path", str(size.bpe_path),
            "--outputs_dir", str(out_dir)]))
    files = sorted(out_dir.rglob("*.jpg"))
    check(len(files) == n, f"{len(files)} image files written, wanted {n}")

    # the same pipeline once more, as arrays: generate.main only leaves
    # files behind, and the codes and pixels themselves are what is checked
    dalle, cfg, params, vae, vae_params = load_dalle_checkpoint(dalle_ckpt)
    tokens = select_tokenizer(str(size.bpe_path)).tokenize(
        [caption], cfg.text_seq_len, truncate_text=True)
    tokens = np.repeat(tokens, n, axis=0)
    decode = make_decode_fn(vae, vae_params)

    def again():
        chunks, _ = iter_generated_chunks(
            dalle, params, tokens, batch_size=n, top_k=0.9,
            rng=jax.random.PRNGKey(0))
        (codes, n_valid), = list(chunks)
        images = decode(codes)
        jax.block_until_ready(images)
        return codes, images, n_valid

    (codes, images, n_valid), second_s = _timed(again)
    check(n_valid == n and codes.shape == (n, cfg.image_seq_len),
          f"generated {n_valid} valid rows of shape {codes.shape}")
    assert_on_platform((params, codes, images), platform, "generate arrays")
    codes_h, images_h = np.asarray(codes), np.asarray(images)
    check(codes_h.min() >= 0 and codes_h.max() < cfg.num_image_tokens,
          f"codes outside [0, {cfg.num_image_tokens}): "
          f"[{codes_h.min()}, {codes_h.max()}]")
    check(images_h.shape == (n, vae.cfg.image_size, vae.cfg.image_size, 3),
          f"decoded images of shape {images_h.shape}")
    check(np.isfinite(images_h).all(), "non-finite pixels")
    # the dVAE decoder ends in a linear conv: after a few steps its output
    # is finite but not yet inside [0, 1]; the range is what save_image
    # enforces (clip), so it is checked on the files generate.main wrote
    from PIL import Image

    for f in files:
        px = np.asarray(Image.open(f), np.float32) / 255.0
        check(px.shape == images_h.shape[1:] and px.min() >= 0.0
              and px.max() <= 1.0, f"{f.name}: shape {px.shape}, range "
              f"[{px.min()}, {px.max()}]")
    return {"files": len(files), "first_call_s": first_s,
            "second_call_s": second_s,
            "distinct_codes": int(np.unique(codes_h).size),
            "decoder_range": (float(images_h.min()), float(images_h.max()))}


# --- phase 5: serve ---------------------------------------------------------

def greedy_references(dalle, variables, texts, key) -> list:
    """The static sampler on each prompt: batch-1 prefill + ``decode_codes``
    at ``filter_thres=1.0`` (k = 1: greedy) — what the arena must equal."""
    from dalle_pytorch_tpu.models.dalle import decode_codes, prefill_codes

    @jax.jit
    def ref(v, t):
        first_logits, caches = prefill_codes(dalle, v, t)
        return decode_codes(dalle, v, first_logits, caches, key,
                            filter_thres=1.0)

    return [np.asarray(ref(variables, jnp.asarray(t)[None]))[0]
            for t in texts]


def _mismatch_report(got: list, refs: list) -> str:
    bad = [(i, int((g != r).sum()), int(np.argmax(g != r)))
           for i, (g, r) in enumerate(zip(got, refs)) if (g != r).any()]
    return ", ".join(f"request {i}: {n} codes differ, first at {at}"
                     for i, n, at in bad)


@contextlib.contextmanager
def exact_matmuls():
    """f32 matmuls at full precision, process-wide (config, not the
    thread-local context manager: replica driver threads trace too).

    At the TPU's default precision XLA rounds a batch-1 and a batch-S
    product differently, so two greedy samplers over a near-uniform model
    part at near-ties — the static sampler disagrees with ITSELF across
    batch sizes there (chip run, PR 21).  Equivalence of the serving logic
    is therefore checked where arithmetic is exact; the deployed precision
    gets an agreement floor."""
    prev = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        yield
    finally:
        jax.config.update("jax_default_matmul_precision", prev)


def _agreement(got: list, refs: list) -> float:
    return float(np.mean([(g == r).mean() for g, r in zip(got, refs)]))


def phase_serve(size: SmokeSize, dalle_ckpt: Path, captions: list,
                platform: str) -> dict:
    """A GenerationServer on the trained checkpoint, the way README
    "Serving" builds one, greedy requests against ``decode_codes``:

    * as deployed (default matmul precision): every request completes with
      valid codes, no entry point retraces across two drives, and the codes
      agree with the static sampler up to near-tie divergence (floor 0.75 of
      positions — the sequence-level bound tests/test_generation_equiv.py
      uses between two numerics of one model; on the CPU it is 1.0);
    * with exact matmuls: bit-identical to the static sampler, mixed
      depths and all — continuous batching is a scheduling change, not a
      model change."""
    from dalle_pytorch_tpu.cli import load_dalle_checkpoint, select_tokenizer
    from dalle_pytorch_tpu.serve import GenerationServer

    dalle, cfg, params, _, _ = load_dalle_checkpoint(dalle_ckpt)
    variables = {"params": params}
    tok = select_tokenizer(str(size.bpe_path))
    prompts = list(dict.fromkeys(captions))[:size.serve_requests]
    check(len(prompts) == size.serve_requests, "too few distinct captions")
    texts = [tok.tokenize([c], cfg.text_seq_len,
                          truncate_text=True)[0].astype(np.int32)
             for c in prompts]

    def serve_and_reference():
        refs, ref_s = _timed(lambda: greedy_references(
            dalle, variables, texts, jax.random.PRNGKey(7)))
        server = GenerationServer(dalle, variables,
                                  num_slots=size.serve_slots,
                                  filter_thres=1.0)

        def drive():
            handles = [server.submit(t) for t in texts]
            server.run_until_idle(
                max_ticks=8 * len(texts) * cfg.image_seq_len)
            outs = [np.asarray(h.result(0)) for h in handles]
            for o in outs:
                check(o.shape == (cfg.image_seq_len,)
                      and o.min() >= 0 and o.max() < cfg.num_image_tokens,
                      f"served codes of shape {o.shape} in "
                      f"[{o.min()}, {o.max()}]")
            return outs

        return server, refs, ref_s, drive

    server, refs, ref_s, drive = serve_and_reference()
    got, first_s = _timed(drive)
    got2, second_s = _timed(drive)
    # the second drive admits at another arena clock, i.e. another cache
    # rotation: at default TPU precision it is one more numerics, held to
    # the same floor (on the CPU both drives equal the references)
    agreement = min(_agreement(got, refs), _agreement(got2, refs))
    check(agreement >= 0.75,
          f"serve agrees with decode_codes on {agreement:.3f} of positions: "
          + _mismatch_report(got, refs))
    counts = server.trace_counts()
    check(set(counts.values()) == {1}, f"serve retraced: {counts}")
    assert_on_platform((server.arena.state, server.arena.variables),
                       platform, "serve arena + params")

    with exact_matmuls():
        server, refs, _, drive = serve_and_reference()
        exact, exact_s = _timed(drive)
        check(all((g == r).all() for g, r in zip(exact, refs)),
              "serve does not bit-match decode_codes with exact matmuls: "
              + _mismatch_report(exact, refs))
        exact_counts = server.trace_counts()
    check(set(exact_counts.values()) == {1},
          f"serve retraced: {exact_counts}")
    return {"requests": len(texts), "trace_counts": counts,
            "agreement": agreement, "reference_s": ref_s,
            "first_call_s": first_s, "second_call_s": second_s,
            "exact_s": exact_s}


# --- phase 6: Pallas vs dense -----------------------------------------------

def dense_attention(q, k, v, pattern):
    """Plain masked softmax attention in f32 — the reference the kernel is
    held to (independent of ops/attention_pallas.py: it shares only the
    pattern predicate)."""
    from dalle_pytorch_tpu.ops.attention import dense_pattern_mask

    n = q.shape[2]
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    dots = jnp.einsum("bhid,bhjd->bhij", q * q.shape[-1] ** -0.5, k,
                      preferred_element_type=jnp.float32)
    allow = jnp.asarray(dense_pattern_mask(pattern, n, n))[None, None]
    attn = jax.nn.softmax(jnp.where(allow, dots, -1e30), axis=-1)
    return jnp.einsum("bhij,bhjd->bhid", attn, v,
                      preferred_element_type=jnp.float32)


def variant_seed(variant: str) -> int:
    """crc32, not hash(): python string hashes are per-process randomized,
    so a FAIL on the chip would draw other q/k/v on the rerun."""
    return zlib.crc32(variant.encode())


def phase_pallas(size: SmokeSize, platform: str) -> list:
    """The compiled kernel against the dense path: four variants x the tile
    settings, forward and gradients, and the kernel really is in the HLO
    (``tpu_custom_call``) exactly when this runs on a TPU — under the
    interpreter (the CPU rehearsal) it must not be.  Through
    ``flash_pattern_attention``, the wrapper that takes q, k and v apart and
    stacks them into the one array the kernel reads (the model hands it
    ``to_qkv``'s result and never transposes)."""
    from dalle_pytorch_tpu.ops.attention import AttnPattern
    from dalle_pytorch_tpu.ops.attention_pallas import flash_pattern_attention

    text, fmap = size.attn_text, size.attn_fmap
    n = text + fmap * fmap
    b, h, dh = size.attn_shape
    records = []
    for block in size.attn_blocks:
        for variant in VARIANTS:
            pattern = AttnPattern(variant=variant, seq_len=n - 1,
                                  text_len=text, fmap=fmap)
            ks = jax.random.split(jax.random.PRNGKey(variant_seed(variant)),
                                  4)
            q, k, v, tangent = (jax.random.normal(kk, (b, h, n, dh),
                                                  jnp.float32) for kk in ks)

            def loss_pallas(q, k, v):
                out = flash_pattern_attention(q, k, v, pattern,
                                              block_q=block, block_k=block)
                return jnp.sum(out * tangent)

            def loss_dense(q, k, v):
                return jnp.sum(dense_attention(q, k, v, pattern) * tangent)

            with jax.default_matmul_precision("highest"):
                fn_p = jax.jit(jax.value_and_grad(loss_pallas, (0, 1, 2)))
                in_hlo = "tpu_custom_call" in fn_p.lower(q, k, v).as_text()
                (fp, gp), first_s = _timed(
                    lambda: jax.block_until_ready(fn_p(q, k, v)))
                _, second_s = _timed(
                    lambda: jax.block_until_ready(fn_p(q, k, v)))
                fd, gd = jax.jit(
                    jax.value_and_grad(loss_dense, (0, 1, 2)))(q, k, v)
            check(in_hlo == (platform == "tpu"),
                  f"attention[{variant}] b{block}: tpu_custom_call "
                  f"{'present in' if in_hlo else 'missing from'} the HLO "
                  f"on {platform}")
            assert_on_platform((fp, gp), platform, "pallas outputs")
            fwd_rel = abs(float(fp) - float(fd)) / (abs(float(fd)) + 1e-6)
            grad_rel = max(
                float(jnp.max(jnp.abs(a - g)))
                / (float(jnp.max(jnp.abs(g))) + 1e-6)
                for a, g in zip(gp, gd))
            ok = fwd_rel < 2e-3 and grad_rel < 2e-3
            log(f"{'PASS' if ok else 'FAIL'} attention[{variant}] n={n} "
                f"block={block}: fwd rel {fwd_rel:.2e}, max grad rel "
                f"{grad_rel:.2e}, kernel in HLO {in_hlo}, first "
                f"{first_s:.2f}s second {second_s:.3f}s")
            check(ok, f"attention[{variant}] b{block} disagrees with dense")
            records.append({"variant": variant, "block": block,
                            "fwd_rel": fwd_rel, "grad_rel": grad_rel})
    return records


# --- --chips 4: the sharded step --------------------------------------------

def cub_config(**overrides):
    """The CUB-200 model the trainer builds (``presets.cub200_config``)."""
    from dalle_pytorch_tpu.presets import cub200_config

    return dataclasses.replace(cub200_config(), **overrides)


def plan_step(spec: str, devices, cfg, batch: int):
    """The production train step (codes path) under plan ``spec`` over
    ``devices``.  Returns ``(partitioner, optimizer, jitted step, abstract
    args)`` — the abstract args carry each argument's shape AND sharding,
    so the same function serves the chip run (``lower(*abstract).compile()``
    then call with real arrays) and the chip-free AOT compile over a
    described topology (tests/test_tpu_compile.py)."""
    from dalle_pytorch_tpu import DALLE
    from dalle_pytorch_tpu.parallel.plan import ParallelPlan
    from dalle_pytorch_tpu.training import (make_dalle_train_step,
                                            make_optimizer)

    part = ParallelPlan.parse(spec).partitioner(devices=list(devices))
    model, tx = DALLE(cfg), make_optimizer(3e-4)
    one_text = jnp.zeros((1, cfg.text_seq_len), jnp.int32)
    one_codes = jnp.zeros((1, cfg.image_seq_len), jnp.int32)
    p_shapes = jax.eval_shape(
        lambda r: model.init(r, one_text, one_codes)["params"],
        jax.random.PRNGKey(0))
    o_shapes = jax.eval_shape(tx.init, p_shapes)

    def with_shardings(shapes):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, part.param_shardings(shapes))

    def data(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=part.data_sharding)

    abstract = (with_shardings(p_shapes), with_shardings(o_shapes), None,
                data((batch, cfg.text_seq_len), jnp.int32),
                data((batch, cfg.image_seq_len), jnp.int32),
                jax.ShapeDtypeStruct((2,), jnp.uint32,
                                     sharding=part.repl_sharding))
    step = make_dalle_train_step(model, tx, vae=None, donate=False,
                                 partitioner=part)
    return part, tx, step, abstract


COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def collectives_in(hlo_text: str) -> dict:
    return {c: hlo_text.count(f" {c}(") + hlo_text.count(f" {c}-start(")
            for c in COLLECTIVES
            if f" {c}(" in hlo_text or f" {c}-start(" in hlo_text}


def per_device_bytes(tree) -> dict:
    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device] = out.get(shard.device, 0) + shard.data.nbytes
    return out


def phase_sharded_step(size: SmokeSize, cfg, seed: int, platform: str,
                       devices) -> list:
    """One train step under each plan on all of ``devices``, against the
    same step (same params, same batch, same key) on the first alone."""
    from dalle_pytorch_tpu import DALLE

    n_dev = len(devices)
    key = jax.random.PRNGKey(seed)
    batch = size.plan_batch
    text = np.asarray(jax.random.randint(
        key, (batch, cfg.text_seq_len), 0, cfg.num_text_tokens), np.int32)
    codes = np.asarray(jax.random.randint(
        jax.random.fold_in(key, 1), (batch, cfg.image_seq_len), 0,
        cfg.num_image_tokens), np.int32)
    model = DALLE(cfg)
    host_params = jax.device_get(jax.jit(
        lambda r: model.init(r, text[:1], codes[:1])["params"])(key))
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(host_params))
    step_key = np.asarray(jax.random.PRNGKey(seed + 1))

    def run(spec, devs):
        part, tx, step, abstract = plan_step(spec, devs, cfg, batch)
        compiled, compile_s = _timed(
            lambda: step.lower(*abstract).compile())
        params = part.shard_params(host_params)
        opt_state = part.init_opt_state(tx, params)
        text_d, codes_d = part.shard_batch((text, codes))
        (new_params, _, loss), run_s = _timed(
            lambda: jax.block_until_ready(compiled(
                params, opt_state, None, text_d, codes_d,
                part.replicate(step_key))))
        return types.SimpleNamespace(
            part=part, params=params, new_params=new_params,
            loss=float(loss), hlo=compiled.as_text(),
            secs=f"compile {compile_s:.1f}s, run {run_s:.2f}s")

    ref = run("dp", devices[:1])
    check(np.isfinite(ref.loss), f"single-device loss {ref.loss}")
    ref_new = jax.tree.leaves(jax.device_get(ref.new_params))
    log(f"single-device step on {devices[0]}: loss {ref.loss:.5f} "
        f"({ref.secs})")
    records = []
    for spec in size.plan_specs:
        r = run(spec, devices)
        assert_on_platform(r.new_params, platform, f"{spec} params")
        by_dev = per_device_bytes(r.params)
        share = max(by_dev.values()) / total
        # fsdp-sharded kernels and embeddings hold ~all the bytes (norm
        # scales and biases stay replicated), so a device's share is ~1/ways
        ways = r.part.mesh.shape["fsdp"] * r.part.mesh.shape["tp"]
        colls = collectives_in(r.hlo)
        loss_rel = abs(r.loss - ref.loss) / abs(ref.loss)
        moved = max(
            float(np.max(np.abs(np.asarray(a, np.float32)
                                - np.asarray(b, np.float32))))
            for a, b in zip(jax.tree.leaves(jax.device_get(r.new_params)),
                            ref_new))
        log(f"plan {spec}: mesh {dict(r.part.mesh.shape)}, loss "
            f"{r.loss:.5f} (rel {loss_rel:.2e} vs single), max |updated "
            f"param - single| {moved:.2e}, params on {len(by_dev)} devices, "
            f"largest per-device share {share:.3f} of "
            f"{total / 2**20:.1f} MiB, collectives {colls} ({r.secs})")
        # bf16 compute: the sharded step reduces in another order than the
        # single-device one; 2e-2 relative is the bound the repo holds two
        # bf16 paths of this model to, far below any training-visible gap
        check(np.isfinite(r.loss) and loss_rel < 2e-2,
              f"plan {spec} loss {r.loss} vs single-device {ref.loss}")
        # Adam's first step moves every weight by ~lr (3e-4) in the sign of
        # its gradient, so where a gradient is ~0 two correct steps can end
        # 2 lr apart — and no further
        check(moved < 2e-3, f"plan {spec} updated params drifted {moved}")
        check(len(by_dev) == n_dev,
              f"plan {spec}: params on {len(by_dev)} of {n_dev} devices")
        check(share < 1.0 / ways + 0.1,
              f"plan {spec}: a device holds {share:.2f} of the param bytes, "
              f"expected ~1/{ways}")
        check(colls, f"plan {spec}: no collective in the compiled step")
        records.append({"spec": spec, "loss": r.loss, "share": share,
                        "collectives": colls})
    return records


# --- --chips 4: replicas behind a router ------------------------------------

def phase_fleet(size: SmokeSize, cfg, seed: int, platform: str,
                devices) -> dict:
    """One-chip replicas behind a FleetRouter in this one process: each
    replica's params and arena on its own device, every result bit-matched
    against the single-server sampler on the default device."""
    from dalle_pytorch_tpu import DALLE
    from dalle_pytorch_tpu.serve import FleetRouter, Replica

    check(len(devices) == size.fleet_replicas,
          f"{size.fleet_replicas} replicas need as many devices, got "
          f"{len(devices)}")
    key = jax.random.PRNGKey(seed)
    dalle = DALLE(cfg)
    texts = [np.asarray(jax.random.randint(
        jax.random.fold_in(key, i), (cfg.text_seq_len,), 1,
        cfg.num_text_tokens), np.int32) for i in range(size.fleet_requests)]
    variables = jax.jit(lambda r: dalle.init(
        r, jnp.asarray(texts[0])[None],
        jnp.zeros((1, cfg.image_seq_len), jnp.int32)))(key)
    # bit-match needs exact arithmetic on the TPU (see exact_matmuls)
    with exact_matmuls():
        refs, ref_s = _timed(lambda: greedy_references(
            dalle, variables, texts, jax.random.PRNGKey(7)))

        replicas = [Replica(f"r{i}", dalle, variables, size.fleet_slots,
                            warmup_text=texts[0], filter_thres=1.0, device=d)
                    for i, d in enumerate(devices)]
        placement = {}
        for r, want in zip(replicas, devices):
            arena = r.server.arena
            on = {d for leaf in jax.tree.leaves((arena.variables, arena.state))
                  for d in leaf.devices()}
            placement[r.name] = sorted(str(d) for d in on)
            log(f"replica {r.name}: params + arena on {placement[r.name]}")
            check(on == {want} and want.platform == platform,
                  f"replica {r.name} sits on {on}, not on {platform} {want}")
        router = FleetRouter(replicas, heartbeat_timeout_s=60.0).start()
        try:
            _, warm_s = _timed(lambda: router.wait_serving(
                len(replicas), timeout_s=900.0))

            def drive():
                handles = [router.submit(t) for t in texts]
                return handles, [np.asarray(h.result(600.0)) for h in handles]

            (handles, got), serve_s = _timed(drive)
            audit = router.audit()
        finally:
            router.close()
    served = {r.name: sum(h.trail[-1][0] == r.name for h in handles)
              for r in replicas}
    check(all((g == r).all() for g, r in zip(got, refs)),
          "fleet results do not bit-match the single-server sampler: "
          + _mismatch_report(got, refs))
    check(audit["balanced"] and audit["outstanding"] == 0
          and audit["resolved_ok"] == len(texts)
          and audit["replica_deaths"] == 0, f"router audit {audit}")
    return {"placement": placement, "requests": len(got), "audit": audit,
            "served": served, "reference_s": ref_s, "warm_s": warm_s,
            "serve_s": serve_s}


# --- drivers ----------------------------------------------------------------

def native_loader_status() -> str:
    from dalle_pytorch_tpu.data import native

    return ("native (native/libdalle_host.so, built from host_ops.cpp)"
            if native.available() else
            "pure-Python host ops (native library not built or not loaded)")


def describe_environment() -> None:
    import importlib.metadata as md

    import jaxlib

    def version(dist):
        try:
            return md.version(dist)
        except md.PackageNotFoundError:
            return "not installed"

    dev = jax.devices()[0]
    log(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{version('libtpu')}, flax {version('flax')}, optax "
        f"{version('optax')}, orbax-checkpoint "
        f"{version('orbax-checkpoint')}, python "
        f"{sys.version.split()[0]}")
    log(f"device: {dev.platform} / {dev.device_kind} x {len(jax.devices())}")
    placed = "JAX_COMPILATION_CACHE_DIR" in os.environ
    log(f"compile cache: {jax.config.jax_compilation_cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR {'set' if placed else 'unset'})")


def _fmt_s(v) -> str:
    return "n/a" if v is None else f"{v:.3f}s"


def run_one_chip(work: Path, size: SmokeSize, seed: int, platform: str,
                 compiled=lambda phase: None) -> None:
    """``compiled(phase)`` is called after each phase that compiles (main
    passes ``CompileLedger.log_phase``)."""
    log(f"data loader host ops: {native_loader_status()}")
    (data, captions), secs = _timed(lambda: make_dataset(work, size, seed))
    log(f"phase data: {size.n_images} images at {size.image_size}px + "
        f"captions in {secs:.1f}s")

    vae = phase_train_vae(work, size, data)
    log(f"phase vae: first call {vae['first_call_s']:.1f}s (compile "
        f"included), last steps {_fmt_s(vae['last_step_s'])} each "
        f"(run time, not a metric); losses "
        f"{[round(x, 4) for x in vae['losses']]}")
    compiled("vae")

    dalle = phase_train_dalle(work, size, data, vae["ckpt"])
    cfg = dalle["cfg"]
    log(f"phase dalle: dim {cfg.dim} depth {cfg.depth} heads {cfg.heads}x"
        f"{cfg.dim_head} text {cfg.text_seq_len} fmap "
        f"{cfg.image_fmap_size} n {cfg.seq_len} vocab "
        f"{cfg.num_text_tokens}+{cfg.num_image_tokens}; first call "
        f"{dalle['first_call_s']:.1f}s (compile included), last step "
        f"{_fmt_s(dalle['last_step_s'])} (run time, not a metric)")
    log(f"phase dalle: {len(dalle['losses'])} steps, losses "
        f"{[round(x, 4) for x in dalle['losses']]} (ln-uniform "
        f"{dalle['ln_uniform']:.3f}); managed checkpoint step "
        f"{dalle['ckpt_step']} verified and read back")
    compiled("dalle")
    log(f"peak_bytes_in_use after train: {peak_bytes()}")

    gen = phase_generate(work, size, dalle["ckpt"], captions[0], platform)
    log(f"phase generate: {gen['files']} image files for "
        f"{captions[0]!r}; first call {gen['first_call_s']:.1f}s (compile "
        f"included), second call {gen['second_call_s']:.1f}s (run time, "
        f"not a metric); codes in range ({gen['distinct_codes']} "
        f"distinct), decoder output finite in "
        f"[{gen['decoder_range'][0]:.3f}, {gen['decoder_range'][1]:.3f}], "
        f"written files in [0, 1]")
    compiled("generate")

    serve = phase_serve(size, dalle["ckpt"], captions, platform)
    log(f"phase serve: {serve['requests']} greedy requests, as deployed: "
        f"agree with decode_codes on {serve['agreement']:.4f} of positions, "
        f"trace_counts {serve['trace_counts']}; references "
        f"{serve['reference_s']:.1f}s, first drive "
        f"{serve['first_call_s']:.1f}s (compile included), second drive "
        f"{serve['second_call_s']:.1f}s (run time, not a metric)")
    log(f"phase serve: with exact matmuls the {serve['requests']} requests "
        f"bit-match decode_codes ({serve['exact_s']:.1f}s, compile "
        f"included)")
    compiled("serve")
    log(f"peak_bytes_in_use after serve: {peak_bytes()}")

    records = phase_pallas(size, platform)
    log(f"phase pallas: {len(records)} kernel-vs-dense checks PASS")
    compiled("pallas")


def run_four_chips(size: SmokeSize, seed: int, platform: str, devices,
                   compiled=lambda phase: None) -> None:
    records = phase_sharded_step(size, cub_config(), seed, platform, devices)
    log(f"phase sharded-step: {[r['spec'] for r in records]} agree with the "
        "single-device step")
    compiled("sharded-step")
    # serving runs f32 activations over the bf16 KV cache: checkpoints
    # carry no dtype, so this is what load_dalle_checkpoint serves
    fleet = phase_fleet(size, cub_config(dtype=jnp.float32), seed, platform,
                        devices)
    log(f"phase fleet: {fleet['requests']} requests over "
        f"{len(fleet['placement'])} replicas bit-match the single-server "
        f"sampler; served by {fleet['served']}; audit {fleet['audit']}; "
        f"references "
        f"{fleet['reference_s']:.1f}s, warm-up {fleet['warm_s']:.1f}s, "
        f"serve {fleet['serve_s']:.1f}s (run times, not metrics)")
    compiled("fleet")
    for dev in devices:
        log(f"peak_bytes_in_use {dev}: {peak_bytes(dev)}")


def cache_dir_bytes() -> int:
    root = jax.config.jax_compilation_cache_dir
    return sum(p.stat().st_size for p in Path(root).rglob("*")
               if p.is_file()) if root and Path(root).is_dir() else 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the sharded step and the replica "
                             "fleet (needs four chips)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--work", type=Path,
                        default=REPO / ".cache" / "chip_smoke",
                        help="scratch directory (wiped first): data, "
                             "checkpoints, samples, outputs")
    args = parser.parse_args(argv)

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke needs {args.chips} TPU chip(s); jax found "
              f"{device['count']} x {device['platform']} "
              f"({device['kind']})", file=sys.stderr)
        print(json.dumps({"ok": False, "device": device}), flush=True)
        return 2

    from dalle_pytorch_tpu.cli import enable_compilation_cache

    enable_compilation_cache()
    describe_environment()
    compiled = CompileLedger().log_phase
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(FULL, args.seed, "tpu", devices[:4], compiled)
    else:
        # wipe only a directory this script made: --work may name anything
        marker = args.work / ".chip_smoke_work"
        if args.work.exists() and any(args.work.iterdir()):
            if not marker.exists():
                parser.error(f"--work {args.work} is not empty and was not "
                             "made by chip_smoke.py")
            shutil.rmtree(args.work)
        args.work.mkdir(parents=True, exist_ok=True)
        marker.touch()
        run_one_chip(args.work, FULL, args.seed, "tpu", compiled)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s; compile "
        f"cache now holds {cache_dir_bytes() / 2**20:.1f} MiB")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
