#!/usr/bin/env python
"""Smoke-test converted pretrained checkpoints: one decode/embed per model.

The last stage of `tools/fetch_and_convert.sh`: proves each converted
msgpack actually loads into its wrapper graph and produces finite outputs
of the published shapes (ref runtime use: vae.py:98-170 decodes, genrank.py
:118-135 CLIP-scores).  Writes one PNG per VAE so a human can eyeball the
result the day real weights are converted.

Usage:
    python tools/smoke_decode.py --dir pretrained [--models vqgan,openai,clip]
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def smoke_vqgan(path: Path, outdir: Path):
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models.pretrained_vae import VQGanVAE1024
    from dalle_pytorch_tpu.utils.images import save_image

    vae = VQGanVAE1024(weights_path=str(path))
    rng = np.random.default_rng(0)
    codes = jnp.asarray(rng.integers(0, vae.num_tokens, (1, 256)), jnp.int32)
    img = np.asarray(vae.decode(codes))
    assert img.shape == (1, 256, 256, 3) and np.isfinite(img).all(), img.shape
    save_image(outdir / "vqgan_smoke.png", img[0])
    # round-trip: encode the decode back to codes of the right range
    back = np.asarray(vae.get_codebook_indices(jnp.asarray(img)))
    assert back.shape == (1, 256) and 0 <= back.min() \
        and back.max() < vae.num_tokens
    print(f"vqgan: decode {img.shape} ok -> {outdir / 'vqgan_smoke.png'}")


def smoke_openai(path: Path, outdir: Path):
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models.pretrained_vae import OpenAIDiscreteVAE
    from dalle_pytorch_tpu.utils.images import save_image

    vae = OpenAIDiscreteVAE(weights_path=str(path))
    rng = np.random.default_rng(0)
    codes = jnp.asarray(rng.integers(0, vae.num_tokens, (1, 1024)), jnp.int32)
    img = np.asarray(vae.decode(codes))
    assert img.shape == (1, 256, 256, 3) and np.isfinite(img).all(), img.shape
    save_image(outdir / "openai_smoke.png", img[0])
    back = np.asarray(vae.get_codebook_indices(jnp.asarray(img)))
    assert back.shape == (1, 1024) and 0 <= back.min() \
        and back.max() < vae.num_tokens
    print(f"openai: decode {img.shape} ok -> {outdir / 'openai_smoke.png'}")


def smoke_clip(path: Path, outdir: Path):
    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models.clip_vit import CLIPViT, CLIPViTConfig
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    ckpt = load_checkpoint(path)
    cfg = CLIPViTConfig.from_dict(dict(ckpt["hparams"]))
    model = CLIPViT(cfg)
    params = jax.tree.map(jnp.asarray, ckpt["weights"])
    rng = np.random.default_rng(0)
    image = jnp.asarray(rng.uniform(0, 1, (2, cfg.image_size, cfg.image_size,
                                           3)), jnp.float32)
    text = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, cfg.context_length)),
                       jnp.int32)
    logits_per_text, logits_per_image = model.apply({"params": params},
                                                    text, image)
    lt = np.asarray(logits_per_text)
    assert lt.shape == (2, 2) and np.isfinite(lt).all()
    print(f"clip: text/image logits {lt.shape} ok (ViT-B/32 geometry "
          f"{cfg.vision_width}x{cfg.vision_layers})")


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--dir", required=True,
                        help="directory holding the converted *.msgpack")
    parser.add_argument("--models", default="vqgan,openai,clip")
    args = parser.parse_args(argv)
    d = Path(args.dir)
    outdir = d / "smoke"
    outdir.mkdir(parents=True, exist_ok=True)
    runners = {"vqgan": (d / "vqgan_jax.msgpack", smoke_vqgan),
               "openai": (d / "openai_jax.msgpack", smoke_openai),
               "clip": (d / "clip_jax.msgpack", smoke_clip)}
    for name in args.models.split(","):
        name = name.strip()
        if name not in runners:
            raise SystemExit(f"unknown model '{name}': choose from "
                             f"{', '.join(runners)}")
        path, fn = runners[name]
        if not path.exists():
            raise SystemExit(f"{path} missing — run the convert stage first")
        fn(path, outdir)
    print("smoke ok")


if __name__ == "__main__":
    main()
