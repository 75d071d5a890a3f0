"""The comparisons that decide ``correct``.  All run outside the timed window.

Generation and serving are held to the plain reference (``reference.py``,
float32, exact matmuls) on logits, never on sampled tokens:

* ``program_logits``: the program's own prompt pass and decode-through-cache
  (``DALLE.prefill`` then ``DALLE.decode_step`` in a scan, what
  ``decode_codes`` and the arena's tick call), teacher-forced on a sampled
  sequence, must give the reference's logits within ``LOGIT_TOL``;
* ``sampled_within_top_k``: every code the timed program really sampled must
  be one the reference's logits admit under the same top-k filter, with the
  same tolerance at the k-th boundary.

Training is held to its loss (finite, starting at the uniform guess of the
geometry, and falling) and, where the mix asks for it and the configuration
has no dropout, to the reference on its first step (``reference_step``): the
step's VAE codes, its loss, and the direction of its parameter update against
the gradient of the reference's loss.  A dropout mask cannot be reproduced
from outside the program, so a configuration with dropout is not held to the
reference in training.  On several chips the replicated parameters must be
the same on every chip after the run (``replicas_agree``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference

#: Largest |program logit - reference logit| allowed, in units of the
#: reference logits' standard deviation over the vocabulary at that position.
#: The program computes in bf16 (8 bits of mantissa: each matmul input is
#: rounded to 1 part in 256) with f32 softmax, norms and head; through 8-12
#: residual layers with LayerScale 0.1 the error measured on the v5e is 0.040
#: to 0.051 std over three cells and eight seeds (PERF.md, Findings PR 22).
#: 0.15 is three times the largest seen: room for other seeds, and an 8-bit
#: float (3 bits of mantissa: 32 x the rounding) or a wrong mask, which moves
#: logits by whole stds, would fail it.
LOGIT_TOL = 0.15

#: Share of sampled codes that must pass the top-k test.  All of them should;
#: two in a thousand allows for a logit that sits within rounding of the
#: k-th.  Codes drawn without the model would pass at the top-k share itself
#: (about 0.2 of the image vocabulary).
TOP_K_SHARE = 0.998


def top_k_count(cfg, filter_thres: float) -> int:
    """The reference sampler's k: derived from the joint vocabulary."""
    total = (cfg.num_text_tokens + cfg.text_seq_len + cfg.num_image_tokens)
    return min(max(int((1 - filter_thres) * total), 1), cfg.num_image_tokens)


def reference_logits(params, cfg, prompts, codes):
    fn = jax.jit(lambda p, t, c: reference.image_logits(p, cfg, t, c))
    return fn(params, jnp.asarray(prompts), jnp.asarray(codes))


def program_logits(dalle, params, prompts, codes):
    """Teacher-forced logits ``[b, image_seq_len, num_image_tokens]`` through
    the program's prefill and cached decode step."""
    from dalle_pytorch_tpu.models.dalle import DALLE

    cfg = dalle.cfg
    n_pre = cfg.text_seq_len + 1

    def run(variables, text, codes):
        first, caches = dalle.apply(variables, text, method=DALLE.prefill)

        def step(carry, code):
            caches, index = carry
            logits, caches = dalle.apply(variables, code, caches, index,
                                         method=DALLE.decode_step)
            return (caches, index + 1), logits

        _, rest = jax.lax.scan(step, (caches, jnp.asarray(n_pre)),
                               codes[:, :-1].T)
        return jnp.concatenate([first[:, None], rest.transpose(1, 0, 2)], 1)

    return jax.jit(run)({"params": params}, jnp.asarray(prompts),
                        jnp.asarray(codes))


def compare(dalle, params, prompts, codes, filter_thres: float) -> dict:
    """Both comparisons on ``[k, text_seq_len]`` prompts and the ``[k,
    image_seq_len]`` codes the timed program sampled for them."""
    cfg = dalle.cfg
    codes = np.asarray(codes)
    in_range = bool(((codes >= 0) & (codes < cfg.num_image_tokens)).all())
    ref = np.asarray(reference_logits(params, cfg, prompts,
                                      np.clip(codes, 0,
                                              cfg.num_image_tokens - 1)))
    got = np.asarray(program_logits(dalle, params, prompts, codes),
                     np.float32)
    std = ref.std(-1, keepdims=True)
    logit_err = float((np.abs(got - ref) / std).max())
    k = top_k_count(cfg, filter_thres)
    kth = np.partition(ref, -k, axis=-1)[..., -k]
    chosen = np.take_along_axis(ref, codes[..., None], -1)[..., 0]
    share = float((chosen >= kth - LOGIT_TOL * std[..., 0]).mean())
    return {"codes_in_range": in_range, "logit_err_std": logit_err,
            "top_k_share": share, "k": k,
            "ok": bool(in_range and np.isfinite(logit_err)
                       and logit_err <= LOGIT_TOL and share >= TOP_K_SHARE)}


def expected_first_loss(cfg) -> float:
    """The loss at uniform logits: text and image cross-entropies weighted
    the way the model weights them (copied from ``chip_smoke.py``)."""
    w = cfg.loss_img_weight
    return float((np.log(cfg.num_text_tokens + cfg.text_seq_len)
                  + w * np.log(cfg.num_image_tokens)) / (w + 1))


def train_losses(cfg, losses) -> dict:
    losses = np.asarray(losses, float)
    first, tail = float(losses[0]), float(losses[-8:].mean())
    want = expected_first_loss(cfg)
    return {"first_loss": first, "last8_mean": tail, "uniform_loss": want,
            "ok": bool(np.isfinite(losses).all() and abs(first - want) <= 1.5
                       and tail < first)}


#: A code the program's dVAE encoder chose must be one the reference's logits
#: put within ``VAE_TOL`` standard deviations (over the 8192 tokens at that
#: position) of their largest; ``VAE_CODES_SHARE`` of the batch's codes must
#: pass.  The program's convolutions run on f32 parameters with the TPU's
#: default one-pass bf16 products, so where the two largest logits nearly
#: tie it may pick the other: the same rounding, tolerance and share as
#: ``LOGIT_TOL`` and ``TOP_K_SHARE`` above.  A code drawn without the
#: encoder would have to hit one of the few logits, of 8192, that close to
#: the largest.
VAE_TOL = LOGIT_TOL
VAE_CODES_SHARE = TOP_K_SHARE

#: Largest |step loss - reference loss| on the first step's batch, from the
#: parameters the step started from and the program's own codes.  bf16
#: rounding moves single logits by up to 0.05 std with either sign, which
#: largely cancels in a mean over 17,664 positions: on the v5e the error was
#: 0 to 1.5e-4 over eight seeds (PERF.md, Findings PR 22).  1e-3 is seven
#: times the largest seen; an 8-bit float rounds 32 times coarser.
STEP_LOSS_TOL = 1e-3

#: Least share, weighted by |reference gradient|, of parameter elements that
#: the first step moved against the sign of the reference's gradient: over
#: all parameters, and within the worst single leaf.  Adam's first update is
#: ``-lr * g / (|g| + eps)``, so only the direction of each element can be
#: compared; where |g| is far above the step's rounding the signs must agree,
#: and elements with next to no gradient carry next to no weight.  On the v5e
#: the share that disagreed was 2.7e-6 to 9.8e-6 over all and at most 7.8e-5
#: in the worst leaf, over eight seeds; the floors allow ten times that.
#: On the CPU twin a step on another batch of images leaves 0.16 disagreeing
#: (0.38 in the worst leaf), other captions 1.4e-3 to 5.5e-3, no caption 9.6e-3.
UPDATE_AGREE_MIN = 1 - 1e-4
UPDATE_LEAF_MIN = 1 - 1e-3


def reference_step(dalle_cfg, vae, vae_cfg, start_params, vae_params, text,
                   images, step_loss, update, micro: int) -> dict:
    """Hold the first train step to the reference.  ``start_params`` are the
    parameters the step started from, ``update`` what it added to them,
    ``step_loss`` the loss it returned for ``text`` and ``images``.  The
    reference's loss and gradient are taken ``micro`` images at a time."""
    images, text = jnp.asarray(images), jnp.asarray(text)
    codes = jax.jit(lambda p, x: vae.apply(
        {"params": p}, x, method=type(vae).get_codebook_indices))(
            vae_params, images)

    @jax.jit
    def codes_near_top(vae_params, images, codes):
        logits = reference.vae_code_logits(vae_params, vae_cfg, images)
        chosen = jnp.take_along_axis(logits, codes[..., None], -1)[..., 0]
        near = chosen >= logits.max(-1) - VAE_TOL * logits.std(-1)
        return near.mean(), (logits.argmax(-1) == codes).mean()

    codes_share, codes_equal = map(float, codes_near_top(vae_params, images,
                                                         codes))

    grad = jax.jit(jax.value_and_grad(
        lambda p, t, c: reference.train_loss(p, dalle_cfg, t, c)))
    # equal parts, so the batch's mean loss and gradient are the parts' means
    # (the signs compared below do not depend on the gradient's scale)
    assert text.shape[0] % micro == 0, (text.shape, micro)
    parts = [grad(start_params, text[i:i + micro], codes[i:i + micro])
             for i in range(0, text.shape[0], micro)]
    ref_loss = float(np.mean([float(loss) for loss, _ in parts]))
    grad_sum = jax.tree.map(lambda *g: sum(g), *[g for _, g in parts])

    @jax.jit
    def agreement(grads, update):
        pairs = [(jnp.abs(g).sum(),
                  jnp.where(jnp.sign(u) == -jnp.sign(g), jnp.abs(g), 0).sum(),
                  (jnp.sign(u) == -jnp.sign(g)).mean())
                 for g, u in zip(jax.tree.leaves(grads),
                                 jax.tree.leaves(update))]
        total, agree, plain = (jnp.stack(x) for x in zip(*pairs))
        return agree.sum() / total.sum(), agree / total, plain

    weighted, by_leaf, plain = agreement(grad_sum, update)
    weighted, by_leaf = float(weighted), np.asarray(by_leaf)
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(grad_sum)[0]]
    worst = int(np.nanargmin(by_leaf))
    loss_err = abs(float(step_loss) - ref_loss)
    return {"vae_codes_share": codes_share, "vae_codes_equal": codes_equal,
            "ref_loss": ref_loss,
            "step_loss": float(step_loss), "loss_err": loss_err,
            "update_agreement": weighted,
            "update_agreement_worst_leaf": [names[worst],
                                            float(by_leaf[worst])],
            "update_sign_share_unweighted": float(np.mean(np.asarray(plain))),
            "ok": bool(codes_share >= VAE_CODES_SHARE
                       and loss_err <= STEP_LOSS_TOL
                       and weighted >= UPDATE_AGREE_MIN
                       and by_leaf[worst] >= UPDATE_LEAF_MIN)}


def replicas_agree(mesh, tree) -> dict:
    """Every fully replicated leaf of ``tree`` must hold the same numbers on
    every device of ``mesh``: a checksum (sum of magnitudes) taken on each
    device from its own copy.  A data-parallel step whose gradients were not
    reduced over the devices leaves them different."""
    from jax.sharding import PartitionSpec as P

    leaves = [x for x in jax.tree.leaves(tree)
              if x.sharding.is_fully_replicated and x.size > 1]
    if mesh.size == 1 or not leaves:
        return {"devices": int(mesh.size), "leaves": len(leaves), "ok": True}

    def local(*copies):
        return jnp.stack([jnp.abs(c.astype(jnp.float32)).sum()
                          for c in copies])[None]

    sums = np.asarray(jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=P(), out_specs=P(mesh.axis_names),
        check_vma=False))(*leaves))
    same = (sums == sums[:1]).all(axis=0)
    return {"devices": int(mesh.size), "leaves": len(leaves),
            "differing_leaves": int((~same).sum()),
            "ok": bool(same.all() and np.isfinite(sums).all())}
