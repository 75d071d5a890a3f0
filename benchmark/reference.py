"""Plain reference of the DALL-E forward pass and training loss, and of the
dVAE encoder, for both configurations.

Straightforward ``jax.numpy`` in float32 with exact matmuls: no cache, no
kernels, no batching tricks, and nothing imported from the program
(``dalle_pytorch_tpu``).  It follows the reference implementation the repo
ports (lucidrains/DALLE-pytorch ``dalle_pytorch.py`` / ``transformer.py`` /
``attention.py``), reading the program's parameter tree by its names:

* tokens: pad id 0 at text position t becomes the unique id
  ``num_text_tokens + t``; ``<bos>`` (id 0) is prepended; text gets a learned
  position embedding, image codes the sum of a row and a column embedding;
* each layer: ``x += s_a * Attn(LN(x))`` then ``x += s_f * GEGLU(LN(x))``
  (LayerScale, pre-norm), attention masked by the layer's pattern
  (``full``, ``axial_row``, ``axial_col``, ``conv_like`` with a 5 x 5
  kernel): a text query sees earlier text; an image query sees all text and
  the earlier image positions its pattern allows;
* head: LayerNorm, then the image-vocabulary kernel at image positions and
  the text-vocabulary kernel at text positions; the training loss is the mean
  cross-entropy of each phase within its own vocabulary, image weighted
  ``loss_img_weight`` to 1 (``dalle_pytorch.py:482-499``);
* dVAE encoder: ``num_layers`` 4 x 4 stride-2 convolutions with ReLU,
  ``num_resnet_blocks`` residual blocks (3 x 3, ReLU, 3 x 3, ReLU, 1 x 1), a
  1 x 1 convolution to ``num_tokens`` logits whose argmax is the code
  (``dalle_pytorch.py`` ``DiscreteVAE.get_codebook_indices``).

Departures from the published code, both the program's own: GELU is the tanh
approximation (``flax.linen.gelu``'s default; torch's is exact), and LayerNorm
uses eps 1e-6 (flax's default; torch's is 1e-5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
EXACT = jax.lax.Precision.HIGHEST


def pattern_mask(variant: str, text_seq_len: int, fmap: int,
                 kernel: int = 5) -> np.ndarray:
    """``[n, n]`` bool, True where query i may attend key j, for
    ``n = text_seq_len + fmap**2`` positions of ``[bos, text, image]`` (the
    last image code is never an input)."""
    n = text_seq_len + fmap * fmap
    t = text_seq_len + 1                      # text positions, bos included
    i, j = np.arange(n)[:, None], np.arange(n)[None, :]
    causal = j <= i
    if variant == "full":
        return causal
    ri, ci = np.divmod(i - t, fmap)
    rj, cj = np.divmod(j - t, fmap)
    if variant == "axial_row":
        img = (rj == ri) & (cj <= ci)
    elif variant == "axial_col":
        img = (cj == ci) & (rj <= ri)
    elif variant == "conv_like":
        half = kernel // 2
        img = (np.abs(rj - ri) <= half) & (np.abs(cj - ci) <= half) & causal
    else:
        raise ValueError(f"no reference for attention variant {variant!r}")
    text_q = causal & (j < t)
    image_q = np.where(j < t, True, img)
    return np.where(i < t, text_q, image_q)


def _layer_norm(x, p, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(np.sqrt(2 / np.pi)
                                   * (x + 0.044715 * x ** 3)))


def _f32(params):
    return jax.tree.map(lambda a: jnp.asarray(a, F32), params)


def _text_labels(cfg, text):
    return jnp.where(text == 0,
                     cfg.num_text_tokens + jnp.arange(cfg.text_seq_len), text)


def hidden(params, cfg, text, codes):
    """``[b, n, dim]`` float32: the transformer's output at the ``n =
    text_seq_len + image_seq_len`` input positions ``[bos, text, codes[:-1]]``
    (teacher forcing).  ``cfg`` needs ``text_seq_len``, ``num_text_tokens``,
    ``image_fmap_size``, ``depth``, ``heads``, ``dim_head``, ``attn_types``.
    """
    p = _f32(params)
    t_len, fmap = cfg.text_seq_len, cfg.image_fmap_size
    n = t_len + fmap * fmap
    variants = tuple(cfg.attn_types or ("full",))

    text = jnp.pad(_text_labels(cfg, text), ((0, 0), (1, 0)))
    tok = (p["text_emb"]["embedding"][text]
           + p["text_pos_emb"]["embedding"][None])
    grid = (p["image_pos_emb"]["row"] + p["image_pos_emb"]["col"]).reshape(
        fmap * fmap, -1)
    img = p["image_emb"]["embedding"][codes] + grid[None]
    x = jnp.concatenate([tok, img], axis=1)[:, :n]

    layers = p["transformer"]
    for d in range(cfg.depth):
        a, f = layers[f"layers_{d}_attn"], layers[f"layers_{d}_ff"]
        h = _layer_norm(x, a["norm"])
        qkv = jnp.einsum("bnd,dkhe->kbhne", h, a["attn"]["to_qkv"]["kernel"],
                         precision=EXACT)
        q, k, v = qkv[0] * cfg.dim_head ** -0.5, qkv[1], qkv[2]
        dots = jnp.einsum("bhie,bhje->bhij", q, k, precision=EXACT)
        allow = pattern_mask(variants[d % len(variants)], t_len, fmap)
        dots = jnp.where(allow[None, None], dots, -jnp.inf)
        out = jnp.einsum("bhij,bhje->bhie", jax.nn.softmax(dots, -1), v,
                         precision=EXACT)
        out = out.transpose(0, 2, 1, 3).reshape(x.shape[0], n, -1)
        out = jnp.dot(out, a["attn"]["to_out"]["kernel"],
                      precision=EXACT) + a["attn"]["to_out"]["bias"]
        x = x + out * a["scale"]

        h = _layer_norm(x, f["norm"])
        h = jnp.dot(h, f["dense_in"]["kernel"],
                    precision=EXACT) + f["dense_in"]["bias"]
        h, gates = jnp.split(h, 2, axis=-1)
        h = jnp.dot(h * _gelu_tanh(gates), f["dense_out"]["kernel"],
                    precision=EXACT) + f["dense_out"]["bias"]
        x = x + h * f["scale"]

    return x


def image_logits(params, cfg, text, codes):
    """``[b, image_seq_len, num_image_tokens]`` float32: at image position p
    the logits of code p given the prompt and codes ``[:p]``."""
    p = _f32(params)
    h = _layer_norm(hidden(params, cfg, text, codes)[:, cfg.text_seq_len:],
                    p["final_norm"])
    head = p["to_logits_dense"]
    return jnp.dot(h, head["image_kernel"],
                   precision=EXACT) + head["image_bias"]


def train_loss(params, cfg, text, codes):
    """The training loss of ``[b, text_seq_len]`` prompts and ``[b,
    image_seq_len]`` codes: next-token cross-entropy, text positions over the
    text vocabulary and image positions over the image vocabulary."""
    p = _f32(params)
    t_len, head = cfg.text_seq_len, p["to_logits_dense"]
    h = _layer_norm(hidden(params, cfg, text, codes), p["final_norm"])

    def nll(h, kernel, bias, labels):
        logits = jnp.dot(h, kernel, precision=EXACT) + bias
        picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return (jax.nn.logsumexp(logits, -1) - picked).mean()

    loss_text = nll(h[:, :t_len], head["text_kernel"], head["text_bias"],
                    _text_labels(cfg, text))
    loss_img = nll(h[:, t_len:], head["image_kernel"], head["image_bias"],
                   codes)
    w = cfg.loss_img_weight
    return (loss_text + w * loss_img) / (w + 1)


def _conv(x, p, stride=1, pad=0):
    out = jax.lax.conv_general_dilated(
        x, p["kernel"], (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=EXACT)
    return out + p["bias"]


def vae_code_logits(vae_params, vae_cfg, images):
    """``[b, fmap * fmap, num_tokens]`` float32: the dVAE encoder's logits
    for ``[b, size, size, 3]`` images in [0, 1]; an image's codes are their
    argmax.  ``vae_cfg`` needs ``num_layers``, ``num_resnet_blocks`` and
    ``normalization`` (means, stds) or None."""
    p = _f32(vae_params)["encoder"]
    x = jnp.asarray(images, F32)
    if vae_cfg.normalization is not None:
        means, stds = vae_cfg.normalization
        x = (x - jnp.asarray(means, F32)) / jnp.asarray(stds, F32)
    for i in range(vae_cfg.num_layers):
        x = jax.nn.relu(_conv(x, p[f"Conv_{i}"], stride=2, pad=1))
    for i in range(vae_cfg.num_resnet_blocks):
        block = p[f"ResBlock_{i}"]
        h = jax.nn.relu(_conv(x, block["Conv_0"], pad=1))
        h = jax.nn.relu(_conv(h, block["Conv_1"], pad=1))
        x = x + _conv(h, block["Conv_2"])
    logits = _conv(x, p[f"Conv_{vae_cfg.num_layers}"])
    return logits.reshape(x.shape[0], -1, logits.shape[-1])
