#!/usr/bin/env python
"""Produce a CUB-shaped loss trajectory in the reference's log format.

The reference's committed training evidence is `all-logs/cool-frog-21.txt`
(one `epoch iter loss lr` line per step, written at ref train_dalle.py:378;
654 iters/epoch = ~10.5k caption pairs at batch 16): first loss ~7.36,
epoch-99 mean ~4.28.  CUB images cannot ship in this environment, so this
harness trains the same model geometry (cool-frog-21's: dim 256 / depth 8 /
heads 8 / text 80 / VQGAN-1024 codes -> 256 image tokens / batch 16 /
lr from flag) on a SYNTHETIC caption->codes dataset with learnable
conditional structure: each of `--num_pairs` captions deterministically
selects a code template, observed under token noise — so the loss must fall
from the ~7.4 init toward the template entropy, exercising the identical
train step the real run uses (training.make_dalle_train_step, codes path).

Two additions over the bare harness mirror the real training loop:
* ``--lr_plateau`` steps the same host-side ``ReduceLROnPlateau`` that
  train_dalle.py uses (ref train_dalle.py:286-295, :415-416) on each
  epoch-mean loss, and the logged lr column carries the *actual* lr — so a
  multi-epoch run shows the scheduler firing, like the reference's logs.
* ``--ckpt`` (on by default, derived from --out) saves {params, opt state,
  rng, scheduler} after every chunk and resumes from it on restart — a
  lost machine mid-run costs one chunk, not the run.

Usage:
    python tools/loss_curve.py --steps 400 --out all-logs-tpu/synthetic-cub.txt
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def make_synthetic_pairs(rng, num_pairs, text_len, vocab, image_seq,
                         image_vocab, templates=32, noise=0.1):
    """Caption tokens -> noisy code template, with the template derived from
    the caption CONTENT (its first token modulo `templates`) — a
    generalizable conditional rule the transformer can pick up within an
    epoch, so the curve descends through the unconditional floor
    (ln-uniform ~7.19 at this geometry) the way real conditioning does,
    instead of requiring per-pair memorization.  Conditional floor:
    ~(ln V_text + 7*(noise*ln V_img + H(noise)))/8 ~ 2.0."""
    caps = rng.integers(1, vocab, size=(num_pairs, text_len))
    return caps.astype(np.int32), _codes_for(rng, caps[:, 0], image_seq,
                                             image_vocab, templates, noise)


def _codes_for(rng, tmpl_src, image_seq, image_vocab, templates, noise):
    """Template codes selected by the 1-D per-pair template source
    (caption content), observed under noise."""
    tmpl_of_cap = tmpl_src % templates
    templates_codes = rng.integers(0, image_vocab,
                                   size=(templates, image_seq))
    codes = templates_codes[tmpl_of_cap]
    flip = rng.random(codes.shape) < noise
    codes = np.where(flip, rng.integers(0, image_vocab, codes.shape), codes)
    return codes.astype(np.int32)


def make_real_caption_pairs(rng, num_pairs, text_len, image_seq, image_vocab,
                            templates=32, noise=0.1):
    """REAL CUB captions -> synthetic noisy code templates.

    Uses the bundled data artifacts the reference ships
    (`cub_2011_test_captions.pkl`: 30k real bird captions;
    `cub200_bpe_vsize_7800.json`: the CUB BPE vocab — both at the repo
    root, see genrank.py defaults): a deterministic sample of
    ``num_pairs`` captions, tokenized exactly as train_dalle.py would
    (pad 0, truncate at ``text_len``).  The text half of the loss is then
    a REAL language-modeling task with CUB's token statistics; only the
    image codes remain synthetic (no CUB images exist in this
    environment).  The code template hashes the whole caption content, so
    conditioning still has a learnable rule."""
    from dalle_pytorch_tpu.data.bundled import load_captions_pickle
    from dalle_pytorch_tpu.data.tokenizer import HugTokenizer

    df = load_captions_pickle(REPO / "cub_2011_test_captions.pkl")
    tok = HugTokenizer(REPO / "cub200_bpe_vsize_7800.json")
    sel = rng.choice(len(df), size=num_pairs, replace=num_pairs > len(df))
    texts = [str(c) for c in df["caption"].iloc[sel]]
    caps = tok.tokenize(texts, context_length=text_len, truncate_text=True)
    # content hash over the full caption: same caption -> same template
    tmpl_src = (caps.astype(np.int64)
                * (np.arange(caps.shape[1]) + 1)).sum(1) % (2 ** 31)
    return caps, _codes_for(rng, tmpl_src, image_seq, image_vocab,
                            templates, noise)


# default values for sig fields added AFTER a checkpoint was written: a
# stored sig missing such a key is compatible iff the current run uses the
# default (the stored run could only have used it)
_SIG_LATER_DEFAULTS = {"plateau_threshold": 1e-4, "captions": "synthetic",
                       "fresh_noise": False}


def _config_sig(args):
    """Fields that must match for a checkpoint to be resumable."""
    return {k: getattr(args, k) for k in
            ("batch_size", "learning_rate", "num_pairs", "seed", "templates",
             "noise", "lr_plateau", "plateau_factor", "plateau_patience",
             "plateau_threshold", "captions", "fresh_noise")}


def _sig_compatible(stored: dict, current: dict) -> bool:
    return all(
        stored.get(k, _SIG_LATER_DEFAULTS.get(k)) == v
        for k, v in current.items())


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--steps", type=int, default=400)
    parser.add_argument("--batch_size", type=int, default=16)
    parser.add_argument("--learning_rate", type=float, default=3e-4)
    parser.add_argument("--num_pairs", type=int, default=10464,
                        help="654 iters/epoch x batch 16, as cool-frog-21")
    parser.add_argument("--templates", type=int, default=32)
    parser.add_argument("--noise", type=float, default=0.1)
    parser.add_argument("--fresh_noise", action="store_true",
                        help="re-sample the code observation noise on every "
                             "visit (per-step rng) instead of fixing it per "
                             "pair: the noise becomes IRREDUCIBLE, so the "
                             "loss truly stalls at the conditional floor "
                             "and torch-default plateau thresholds (1e-4) "
                             "genuinely fire — the regime of the "
                             "reference's own cool-frog-21 run, whose lr "
                             "column halves 7 times at defaults")
    parser.add_argument("--captions", choices=("synthetic", "real"),
                        default="synthetic",
                        help="'real' trains on the bundled CUB captions "
                             "(cub_2011_test_captions.pkl via the bundled "
                             "BPE): the text loss becomes a real language "
                             "task with CUB token statistics; codes stay "
                             "synthetic (no images in this environment)")
    parser.add_argument("--lr_plateau", action="store_true",
                        help="step ReduceLROnPlateau on each epoch-mean "
                             "loss, as train_dalle.py does (ref :415-416)")
    parser.add_argument("--plateau_factor", type=float, default=0.5)
    parser.add_argument("--plateau_patience", type=int, default=5)
    parser.add_argument("--plateau_threshold", type=float, default=1e-4,
                        help="relative improvement below this counts as a "
                             "bad epoch (torch's default 1e-4 only fires on "
                             "a true stall; raise it to demonstrate firing "
                             "on a converged-but-still-creeping curve)")
    parser.add_argument("--out", type=str,
                        default="all-logs-tpu/synthetic-cub.txt")
    parser.add_argument("--ckpt", type=str, default=None,
                        help="checkpoint path (default: <out>.ckpt); "
                             "'' disables")
    parser.add_argument("--ckpt_every_s", type=float, default=120.0,
                        help="min seconds between checkpoint writes: each "
                             "save fetches the full params+opt state "
                             "(~180 MB at CUB geometry) — an every-chunk "
                             "save could rival the training it protects; "
                             "the final chunk always saves")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chunk", type=int, default=50,
                        help="steps per device dispatch: a lax.scan over "
                             "the chunk's batches turns per-step host "
                             "dispatch into one dispatch per chunk; "
                             "losses are bit-identical to --chunk 1")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from flax import serialization

    from dalle_pytorch_tpu import DALLE, DALLEConfig
    from dalle_pytorch_tpu.cli import enable_compilation_cache
    from dalle_pytorch_tpu.training import (make_dalle_train_step,
                                            make_optimizer,
                                            set_learning_rate)
    from dalle_pytorch_tpu.utils.schedule import ReduceLROnPlateau

    enable_compilation_cache()  # a resumed run must not re-pay the compile

    cfg = DALLEConfig(
        dim=256, num_text_tokens=7800, text_seq_len=80, depth=8, heads=8,
        dim_head=64, attn_types=("full", "axial_row", "axial_col",
                                 "conv_like"),
        num_image_tokens=1024, image_size=256, image_fmap_size=16,
        dtype=jnp.float32)
    model = DALLE(cfg)

    host = np.random.default_rng(args.seed)
    # fresh_noise: build CLEAN codes here and re-noise per step below —
    # same marginal noise rate, but unmemorizable (a new draw every visit)
    ds_noise = 0.0 if args.fresh_noise else args.noise
    if args.captions == "real":
        caps, codes = make_real_caption_pairs(
            host, args.num_pairs, cfg.text_seq_len, cfg.image_seq_len,
            cfg.num_image_tokens, templates=args.templates,
            noise=ds_noise)
    else:
        caps, codes = make_synthetic_pairs(
            host, args.num_pairs, cfg.text_seq_len, cfg.num_text_tokens,
            cfg.image_seq_len, cfg.num_image_tokens,
            templates=args.templates, noise=ds_noise)

    rng = jax.random.PRNGKey(args.seed)
    params = jax.jit(lambda r: model.init(
        r, jnp.asarray(caps[:1]), jnp.asarray(codes[:1]))["params"])(rng)
    tx = make_optimizer(args.learning_rate)
    opt_state = jax.jit(tx.init)(params)
    sched = ReduceLROnPlateau(args.learning_rate, factor=args.plateau_factor,
                              patience=args.plateau_patience,
                              threshold=args.plateau_threshold)

    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    ckpt = Path(args.ckpt) if args.ckpt else (
        None if args.ckpt == "" else out.with_suffix(out.suffix + ".ckpt"))

    # ---- resume ---------------------------------------------------------
    # single-file checkpoint: {params, opt_state, meta-json} in ONE msgpack
    # blob behind ONE os.replace — a crash can only ever leave the previous
    # complete checkpoint, never a params/meta mismatch
    start_step = 0
    epoch_sum, epoch_cnt = 0.0, 0  # running epoch-mean accumulator
    if ckpt is not None and ckpt.exists():
        try:
            state = serialization.from_bytes(
                {"params": params, "opt_state": opt_state, "meta": ""},
                ckpt.read_bytes())
        except (ValueError, KeyError) as e:
            # a checkpoint whose param tree no longer matches this build
            # (e.g. written before a model-layout migration).  Refuse
            # loudly instead of silently restarting: a fresh start would
            # truncate the log this checkpoint was extending.
            raise SystemExit(
                f"checkpoint {ckpt} does not match this build's param "
                f"layout ({e}); delete it to start the run fresh") from None
        meta = json.loads(state["meta"])
        log_lines = (out.read_text().splitlines(keepends=True)
                     if out.exists() else [])
        if not _sig_compatible(meta["sig"], _config_sig(args)):
            print(f"checkpoint {ckpt} config mismatch; starting fresh",
                  flush=True)
        elif len(log_lines) < meta["next_step"]:
            # the log this checkpoint continues is gone/truncated (e.g. a
            # reused --ckpt with a fresh --out): resuming would produce a
            # file silently missing its head
            print(f"log {out} has {len(log_lines)} lines < checkpoint step "
                  f"{meta['next_step']}; starting fresh", flush=True)
        else:
            params, opt_state = state["params"], state["opt_state"]
            rng = jnp.asarray(np.asarray(meta["rng"], dtype=np.uint32))
            sched.load_state_dict(meta["sched"])
            opt_state = set_learning_rate(opt_state, sched.lr)
            start_step = meta["next_step"]
            epoch_sum, epoch_cnt = meta["epoch_sum"], meta["epoch_cnt"]
            # drop any log lines past the checkpoint (died between write
            # and save): keep exactly start_step lines
            out.write_text("".join(log_lines[:start_step]))
            print(f"resumed from {ckpt} at step {start_step} "
                  f"(lr {sched.lr:.2e})", flush=True)

    if start_step == 0 and out.exists():
        out.unlink()

    iters_per_epoch = args.num_pairs // args.batch_size
    chunk = max(1, args.chunk)
    raw_step = make_dalle_train_step(model, tx, jit=False)

    # env-armed on-chip capture (GRAFT_XPROF / GRAFT_XPROF_WINDOW): a
    # measured trace of the loss-parity workload, to set beside
    # PERF_LEDGER.json's predicted rows.  The window snaps
    # to chunk boundaries (on_step fires per chunk, not per step) — use
    # --chunk 1..4 when arming so the capture stays a few steps wide.
    from dalle_pytorch_tpu.obs import prof
    xprof = prof.XprofWindow()

    def drain():
        jax.block_until_ready(params)

    import functools

    @functools.partial(jax.jit, static_argnames="n", donate_argnums=(0, 1, 2))
    def run_chunk(params, opt_state, rng, chunk_caps, chunk_codes, n):
        """lax.scan over the chunk's pre-gathered batches [n, B, ...] —
        one device dispatch per chunk, same step math and rng chain as the
        per-step loop, so losses are bit-identical to --chunk 1."""
        def body(carry, batch):
            params, opt_state, rng = carry
            rng, k = jax.random.split(rng)
            b_caps, b_codes = batch
            params, opt_state, loss = raw_step(params, opt_state, None,
                                               b_caps, b_codes, k)
            return (params, opt_state, rng), loss

        (params, opt_state, rng), losses = jax.lax.scan(
            body, (params, opt_state, rng), (chunk_caps, chunk_codes),
            length=n)
        return params, opt_state, rng, losses

    def batch_indices(step):
        epoch, it = divmod(step, iters_per_epoch)
        order = epoch_orders.setdefault(
            epoch,
            np.random.default_rng(args.seed + epoch).permutation(
                args.num_pairs))
        return epoch, it, order[it * args.batch_size:(it + 1) * args.batch_size]

    last_save = [time.time()]

    def save_ckpt(next_step, final=False):
        if ckpt is None:
            return
        if not final and time.time() - last_save[0] < args.ckpt_every_s:
            return
        last_save[0] = time.time()
        meta = {"sig": _config_sig(args), "next_step": next_step,
                "rng": np.asarray(jax.device_get(rng)).tolist(),
                "sched": sched.state_dict(),
                "epoch_sum": epoch_sum, "epoch_cnt": epoch_cnt}
        tmp = ckpt.with_suffix(".tmp")
        tmp.write_bytes(serialization.to_bytes(
            {"params": jax.device_get(params),
             "opt_state": jax.device_get(opt_state),
             "meta": json.dumps(meta)}))
        os.replace(tmp, ckpt)

    epoch_orders = {}
    t0 = time.time()
    done_before = start_step
    with out.open("a") as f:
        start = start_step
        while start < args.steps:
            # never let a chunk cross an epoch boundary: the plateau step
            # (and its lr change) belongs between epochs, as in the loop it
            # mirrors (train_dalle.py:722-725)
            it0 = start % iters_per_epoch
            n = min(chunk, args.steps - start, iters_per_epoch - it0)
            meta, sels = [], []
            for step in range(start, start + n):
                epoch, it, sel = batch_indices(step)
                meta.append((epoch, it))
                sels.append(sel)
            sel = np.stack(sels)                       # [n, B]
            chunk_codes = codes[sel]
            if args.fresh_noise and args.noise > 0:
                # per-step deterministic noise draw (seed, step): resumes
                # replay the identical observation, so the loss stream is
                # still bit-reproducible across crashes
                for j, step in enumerate(range(start, start + n)):
                    nr = np.random.default_rng((args.seed, 7919, step))
                    flip = nr.random(chunk_codes[j].shape) < args.noise
                    chunk_codes[j] = np.where(
                        flip, nr.integers(0, cfg.num_image_tokens,
                                          chunk_codes[j].shape),
                        chunk_codes[j])
            xprof.on_step(start, sync=drain)
            params, opt_state, rng, losses = run_chunk(
                params, opt_state, rng, jnp.asarray(caps[sel]),
                jnp.asarray(chunk_codes), n)
            host_losses = jax.device_get(losses)  # one transfer per chunk
            for (epoch, it), loss_v in zip(meta, host_losses):
                # the reference's exact line format (ref train_dalle.py:378)
                f.write(f"{epoch} {it} {float(loss_v)} {sched.lr}\n")
            f.flush()
            epoch_sum += float(host_losses.sum())
            epoch_cnt += n
            start += n
            if args.lr_plateau and start % iters_per_epoch == 0:
                epoch_mean = epoch_sum / max(epoch_cnt, 1)
                new_lr = sched.step(epoch_mean)
                opt_state = set_learning_rate(opt_state, new_lr)
                print(f"epoch {start // iters_per_epoch - 1} done: "
                      f"mean loss {epoch_mean:.4f} lr {new_lr:.2e}",
                      flush=True)
                epoch_sum, epoch_cnt = 0.0, 0
            save_ckpt(start, final=start >= args.steps)
            rate = (start - done_before) / (time.time() - t0)
            print(f"step {start - 1}: loss {float(host_losses[-1]):.4f} "
                  f"({rate:.2f} steps/s)", flush=True)
    xprof.close(sync=drain)  # exit-path safety net (window past --steps)
    print(f"wrote {args.steps} lines to {out}")


if __name__ == "__main__":
    main()
