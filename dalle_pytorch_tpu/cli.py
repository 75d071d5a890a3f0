"""Shared plumbing for the CLI entry scripts (train_vae / train_dalle /
generate / genrank): tokenizer selection, checkpoint reconstitution, chunked
generation, and multi-host-safe host fetches.

One implementation instead of the reference's per-script copies
(tokenizer selection: ref train_dalle.py:105-112 vs generate.py:59-66;
model loading: ref generate.py:72-87 vs genrank.py:25-44).
"""
from __future__ import annotations

from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import DALLE, DALLEConfig, DiscreteVAE, VAEConfig
from .data.tokenizer import ChineseTokenizer, HugTokenizer, SimpleTokenizer
from .models.dalle import (decode_codes, generate_codes, prefill_codes,
                           tile_prefill)
from .obs import compiles
from .utils.checkpoint import (load_checkpoint, migrate_head_kernels,
                               migrate_qkv_kernels)


#: Where the persistent XLA cache lives when nothing outside placed it.  A
#: fixed path under the checkout: the directory is part of the cache key,
#: so a path that moved between runs (a temporary name, a pid, a home
#: directory the run cannot write) would never hit.
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parent.parent / ".cache" / "xla"


def enable_compilation_cache() -> None:
    """Persistent XLA compilation cache: a cold CUB-width train step or
    decode scan compiles for tens of seconds, so CLI reruns (resume,
    generate sweeps, genrank over checkpoint lists) should pay that once.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set jax has already read it and
    this sets no directory in code; the same early return makes the first
    configuration in a process win, so a tool invoked in-process never
    redirects a cache its host configured.  Otherwise the cache goes to
    ``DEFAULT_COMPILE_CACHE``.  ``JAX_ENABLE_COMPILATION_CACHE=0`` (jax's
    own switch) turns it off.

    Also where the compile log (``obs/compiles.py``) is switched on: every
    entry point and the benchmark's harness come through here before their
    first jit, so trace / lower / compile-or-load / cache events are
    counted from the start of the process."""
    compiles.install()
    if jax.config.jax_compilation_cache_dir:
        return
    # LRU-bound the on-disk cache: the persistent cache never evicts by
    # default, so long-lived dev boxes would accrete stale entries forever.
    # 1 GiB: one pass of the CUB-width executables (chip_smoke.py) alone is
    # more than the 256 MiB this used to be, so a rerun found the trainers'
    # entries evicted by the later phases' and recompiled everything
    jax.config.update("jax_compilation_cache_max_size", 2**30)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))


def select_tokenizer(bpe_path: Optional[str], chinese: bool = False):
    """Tokenizer priority matching the reference (train_dalle.py:105-112):
    explicit BPE file > chinese > CLIP SimpleTokenizer.  The CLIP merges txt
    is data we don't bundle, so the default also needs --bpe_path; json
    selects the HF tokenizer, anything else the CLIP BPE."""
    if bpe_path is not None:
        if str(bpe_path).endswith('.json'):
            return HugTokenizer(bpe_path)
        return SimpleTokenizer(bpe_path)
    if chinese:
        return ChineseTokenizer()
    raise SystemExit(
        '--bpe_path is required: pass the CUB HF-tokenizer json '
        '(cub200_bpe_vsize_7800.json) or a CLIP merges txt '
        '(bpe_simple_vocab_16e6.txt)')


def load_dalle_checkpoint(dalle_path: str | Path, taming: bool = False):
    """Rebuild DALLE + VAE from a checkpoint (ref generate.py:72-87), with
    the same VAE priority: stored custom VAE hparams > pretrained
    (OpenAI dVAE, or VQGAN when `taming`).

    Returns (dalle, cfg, params, vae, vae_params).
    """
    dalle_path = Path(dalle_path)
    assert dalle_path.exists(), 'trained DALL-E must exist'
    ckpt = load_checkpoint(dalle_path)
    dalle_params = dict(ckpt['hparams'])
    dalle_params.pop('vae', None)  # legacy cleanup (ref generate.py:75)
    vae_hparams = ckpt.get('vae_params')

    if vae_hparams is not None:
        vae = DiscreteVAE(VAEConfig.from_dict(dict(vae_hparams)))
        vae_weights = ckpt.get('vae_weights')
        vae_params = (jax.tree.map(jnp.asarray, vae_weights)
                      if vae_weights is not None else None)
    else:
        from .models.pretrained_vae import OpenAIDiscreteVAE, VQGanVAE1024

        vae = VQGanVAE1024() if taming else OpenAIDiscreteVAE()
        vae._require_params()
        vae_params = None

    cfg = DALLEConfig.from_dict(dalle_params)
    dalle = DALLE(cfg)
    weights = migrate_qkv_kernels(ckpt['weights'], dim_head=cfg.dim_head)
    weights = migrate_head_kernels(weights, cfg.total_text_tokens)
    params = jax.tree.map(jnp.asarray, weights)
    return dalle, cfg, params, vae, vae_params


def make_decode_fn(vae, vae_params):
    """Jitted codes -> [b, h, w, 3] float images in [0, 1]."""

    @jax.jit
    def decode(codes):
        if isinstance(vae, DiscreteVAE):
            return vae.apply({'params': vae_params}, codes,
                             method=DiscreteVAE.decode)
        return vae.decode(codes)

    return decode


def iter_generated_chunks(dalle, params, text_tokens: np.ndarray, *,
                          batch_size: int, top_k: float, rng,
                          temperature: float = 1.0,
                          top_p: Optional[float] = None):
    """Sample image codes for [n, text_seq_len] tokens in ``batch_size``
    chunks.  Returns ``(chunks, rng)`` where ``chunks`` yields
    ``(codes [batch_size, image_seq_len] device array, n_valid)`` — codes
    stay on device so downstream consumers (the VAE decode, genrank's fused
    CLIP scorer) can keep the whole pipeline as device arrays.

    **Shared prompt prefill**: when every row is the same prompt (the
    generate/genrank candidate fan-out builds tokens as
    ``np.repeat(prompt, num_images)``), the prompt is prefilled ONCE at
    batch 1 and the resulting KV caches broadcast across the candidate
    batch (``models.dalle.tile_prefill``) — exact, because the prompt
    positions' k/v never depend on the sampled continuation.  Each chunk
    then pays only the decode scan instead of decode + a redundant
    full-sequence prefill forward.  Requests with distinct prompts (the
    pickled-captions eval mode) keep the per-chunk ``generate_codes``
    path, padding the last chunk to hold one compiled shape.
    """
    n = text_tokens.shape[0]
    if n == 0:
        return iter(()), rng
    # one short request compiles at its natural size; padding only pays for
    # itself when it saves a recompile across multiple chunks
    batch_size = min(batch_size, n)
    n_chunks = -(-n // batch_size)
    keys = jax.random.split(rng, n_chunks + 1)
    rng_out, keys = keys[0], keys[1:]
    shared = bool(np.all(np.asarray(text_tokens) == text_tokens[:1]))

    if shared:
        decode_fn = jax.jit(lambda p, fl, c, k: decode_codes(
            dalle, p, fl, c, k, filter_thres=top_k, temperature=temperature,
            top_p=top_p))

        def gen_shared():
            first1, caches1 = jax.jit(
                lambda p, t: prefill_codes(dalle, p, t))(
                    {'params': params},
                    jnp.asarray(text_tokens[:1], jnp.int32))
            first, caches = tile_prefill(first1, caches1, batch_size)
            for i in range(n_chunks):
                codes = decode_fn({'params': params}, first, caches, keys[i])
                yield codes, min(batch_size, n - i * batch_size)

        return gen_shared(), rng_out

    def gen_distinct():
        for i in range(n_chunks):
            chunk = text_tokens[i * batch_size: (i + 1) * batch_size]
            pad = batch_size - chunk.shape[0]
            if pad:
                chunk = np.concatenate([chunk, np.repeat(chunk[-1:], pad, 0)])
            codes = generate_codes(dalle, {'params': params},
                                   jnp.asarray(chunk, jnp.int32), keys[i],
                                   filter_thres=top_k,
                                   temperature=temperature, top_p=top_p)
            yield codes, batch_size - pad

    return gen_distinct(), rng_out


def generate_chunked(dalle, params, decode, text_tokens: np.ndarray, *,
                     batch_size: int, top_k: float, rng,
                     temperature: float = 1.0, top_p: Optional[float] = None,
                     desc: str = 'generating'):
    """Generate images for [n, text_seq_len] tokens in `batch_size` chunks
    (`iter_generated_chunks` semantics: one shared prompt prefill when all
    rows are identical).  Returns (images [n, h, w, 3], rng).
    """
    outs = []
    n = text_tokens.shape[0]
    chunks, rng = iter_generated_chunks(
        dalle, params, text_tokens, batch_size=batch_size, top_k=top_k,
        rng=rng, temperature=temperature, top_p=top_p)
    done = 0
    for codes, n_valid in chunks:
        images = np.asarray(jax.device_get(decode(codes)))
        outs.append(images[:n_valid])
        done += n_valid
        print(f'{desc}: {done}/{n}', flush=True)
    return (np.concatenate(outs) if outs else np.zeros((0,))), rng


def host_fetch(tree):
    """Fetch a (possibly GSPMD-sharded) pytree to host numpy, multi-host
    safe.  Every process must call this together (collective): arrays that
    span non-addressable devices — including arrays replicated over a
    multi-host mesh — are reassembled with a tiled allgather so each
    process ends up holding the FULL global value (root then writes the
    file); only leaves living entirely on this process's devices are plain
    device fetches."""
    if jax.process_count() == 1:
        return jax.device_get(tree)
    from jax.experimental import multihost_utils

    def fetch(leaf):
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            # tiled=True: concatenate the per-process shards back into the
            # logical global array (tiled=False would stack a bogus leading
            # process axis — and rejects global arrays outright)
            return multihost_utils.process_allgather(leaf, tiled=True)
        return jax.device_get(leaf)

    return jax.tree.map(fetch, tree)
