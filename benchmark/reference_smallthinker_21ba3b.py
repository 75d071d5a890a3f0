"""Plain reference of DALL-E over the SmallThinker family's trunk
(configuration ``smallthinker-21ba3b``): the forward pass, the joint logits,
the training loss, and what each layer's router decided.

Straightforward ``jax.numpy`` in float32 with exact matmuls
(``Precision.HIGHEST``): the whole sequence at once, no cache and no ring (a
mask; queries are taken a block at a time against every key only so that the
``[heads, n, n]`` scores of 4,352 positions fit beside the model), every
expert applied to every token in one dense product and combined with the
routing weights, and nothing imported from the program
(``dalle_pytorch_tpu``).  It reads the program's parameter tree by its names
and upcasts it one layer at a time (each layer is its own jitted call).

The trunk follows
https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json
(``model_type: smallthinker``).  Layer ``i`` with hidden state ``h`` ``[n,
dim]`` and ``kind = mixers[i % len(mixers)]`` (``"attention"``: global, not
rotated, ``sliding_window_layout`` = ``rope_layout`` = 0; ``"window"``:
bounded and rotated, both 1)::

    g      = h @ W_r                        # router logits, from the layer's INPUT
    a      = RMSNorm_1(h)
    q,k,v  = a @ W_q, a @ W_k, a @ W_v      # no bias; heads over kv_heads
    q,k    = RoPE(q, p), RoPE(k, p)         # "window" layers only; pairs (d, d + dh/2)
    s      = q . k / sqrt(dh); key j visible to query p iff j <= p and
             ("attention" or j > p - window)
    h1     = h + softmax(s) v @ W_o         # query head u reads key head u // (heads / kv_heads)
    m      = RMSNorm_2(h1)
    P      = softmax(g) over all experts;  S = the k largest;  c_e = P_e / sum_S P
    y      = sum_{e in S} c_e (relu(m @ W_gate_e) * (m @ W_up_e)) @ W_down_e
    h_out  = h1 + y

then the final RMSNorm and an untied head.  ``RMSNorm(x) = x * rsqrt(mean(x^2)
+ eps) * gain``.

Departures from the published model, all DALL-E's client or this repo's
(``benchmark/configs/smallthinker-21ba3b.json``, ``assumed``):

* the router reads the layer's input before ``RMSNorm_1``: ``config.json``
  has no key for it; the catalog says "router placed before attention" and
  the family's public implementations feed it the un-normed input;
* no secondary experts, no shared expert, no expert or attention bias: the
  config has no key for any;
* the 151,936 rows of the embedding and of the head are DALL-E's joint
  vocabulary: ``num_text_tokens`` text ids, one pad id per text position (pad
  id 0 at position t becomes ``num_text_tokens + t``), then the image codes;
  ``<bos>`` is id 0;
* no learned position embedding (the trunk rotates; upstream DALL-E's
  ``rotary_emb`` zeroes both of its own); RoPE's position is the index in
  the joint sequence ``[bos, text, codes]``;
* logits are masked by phase: a text position may predict text ids only, an
  image position image codes only; the loss is DALL-E's
  ``(loss_text + w loss_img) / (w + 1)``;
* weights are seeded random values, not the checkpoint.

The program's names: ``to_q`` ``[dim, heads, dh]``, ``to_kv`` ``[dim, 2,
kv_heads, dh]`` (k then v), ``to_out`` ``[heads * dh, dim]``; ``moe``:
``w_router`` ``[dim, experts]``, ``w_gate`` / ``w_up`` ``[experts, dim,
width]``, ``w_down`` ``[experts, width, dim]``; ``table/embedding`` and
``head`` ``[vocabulary, dim]``.

**Routing, and what to do where it nearly ties.**  With random weights the
k-th and (k+1)-th router probabilities of some tokens differ by less than the
program's bfloat16 rounding moves them, and there the program may rightly
choose the other expert.  :func:`hidden` therefore reports, per layer and
position, its own chosen experts (``top_idx``) and the relative gap
``(P_(k) - P_(k+1)) / P_(k)`` (``gap``), and can be handed the experts to use
(``routing``: ``[layers, b, n, k]``): it then weights them by its own
probabilities, renormalised over them, and reports how far down its own
ranking the handed set reaches (``reach``: the least ``P_e / P_(k)`` over the
handed experts; 1 where the sets agree).  The caller decides by rule what
reach is legal; no tolerance on logits is widened for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
EXACT = jax.lax.Precision.HIGHEST
#: queries taken at a time against all keys (memory only; every block sees
#: the same keys and mask as the whole sequence would)
Q_BLOCK = 1024


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, F32), tree)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _mm(spec, a, b, low=None):
    """The one matrix product: exact float32, or (``low``, for a tolerance's
    second reading) with both operands first rounded to the float format
    ``low``, each scaled by its largest magnitude into the format's range as
    8-bit inference scales a tensor."""
    if low is not None:
        def rounded(x):
            scale = float(jnp.finfo(low).max) / jnp.maximum(
                jnp.abs(x).max(), 1e-30)
            return (x * scale).astype(low).astype(F32) / scale
        a, b = rounded(a), rounded(b)
    return jnp.einsum(spec, a, b, precision=EXACT)


def rope(x, theta: float):
    """``x`` ``[b, heads, n, dh]`` rotated by position 0..n-1: dimension ``i <
    dh / 2`` pairs with ``i + dh / 2`` and turns by ``p * theta^(-2i / dh)``."""
    n, dh = x.shape[-2], x.shape[-1]
    half = dh // 2
    angle = (jnp.arange(n, dtype=F32)[:, None]
             * theta ** (-2.0 * jnp.arange(half, dtype=F32) / dh))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def visible(n: int, window: int):
    """``[n, n]`` bool: key j visible to query p iff ``j <= p`` and (no
    window or ``j > p - window``)."""
    p, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    return (j <= p) & ((j > p - window) if window else True)


def _attention(p, x, eps, dim_head, window, theta, low=None):
    """``window`` 0: global and unrotated; else bounded and rotated."""
    b, n, _ = x.shape
    h = _rms(x, p["norm"]["scale"], eps)
    a = p["attn"]
    q = _mm("bnd,dhe->bhne", h, a["to_q"]["kernel"], low)
    kv = _mm("bnd,dkge->kbgne", h, a["to_kv"]["kernel"], low)
    heads, groups = q.shape[1], kv.shape[2]
    # each key/value head serves heads / groups query heads, in order
    k = jnp.repeat(kv[0], heads // groups, axis=1)
    v = jnp.repeat(kv[1], heads // groups, axis=1)
    if window:
        q, k = rope(q, theta), rope(k, theta)
    allow = visible(n, window)
    outs = []
    for start in range(0, n, Q_BLOCK):
        rows = slice(start, min(start + Q_BLOCK, n))
        dots = _mm("bhie,bhje->bhij", q[:, :, rows] * dim_head ** -0.5, k,
                   low)
        dots = jnp.where(allow[rows][None, None], dots, -jnp.inf)
        outs.append(_mm("bhij,bhje->bhie", jax.nn.softmax(dots, -1), v, low))
    out = jnp.concatenate(outs, axis=2)
    out = out.transpose(0, 2, 1, 3).reshape(b, n, -1)
    return _mm("...d,de->...e", out, a["to_out"]["kernel"], low)


def _experts(p, x, logits, eps, k, routing, low):
    """The routed feed-forward on the hidden state after attention, with the
    router logits taken from the layer's input.  Returns ``(y, top_idx, gap,
    reach)``."""
    m = _rms(x, p["norm"]["scale"], eps)
    w = p["moe"]
    probs = jax.nn.softmax(logits, -1)                       # [b, n, e]
    ranked, top_idx = jax.lax.top_k(probs, k + 1)
    gap = (ranked[..., k - 1] - ranked[..., k]) / ranked[..., k - 1]
    top_idx = top_idx[..., :k]
    chosen = top_idx if routing is None else routing
    picked = jnp.take_along_axis(probs, chosen, -1)          # [b, n, k]
    reach = picked.min(-1) / ranked[..., k - 1]
    weight = picked / picked.sum(-1, keepdims=True)
    combine = (jax.nn.one_hot(chosen, probs.shape[-1], dtype=F32)
               * weight[..., None]).sum(-2)                  # [b, n, e]
    gate = _mm("bnd,edf->bnef", m, w["w_gate"], low)
    up = _mm("bnd,edf->bnef", m, w["w_up"], low)
    # every expert on every token; an unchosen expert's weight is an exact
    # zero.  The weights multiply before the last product (it is linear), so
    # that no [n, experts, dim] array is made at 4,352 positions.
    y = _mm("bnef,efd->bnd", jax.nn.relu(gate) * up * combine[..., None],
            w["w_down"], low)
    return y, top_idx, gap, reach


@functools.partial(jax.jit, static_argnames=(
    "eps", "dim_head", "window", "theta", "k", "matmul_dtype"))
def _layer(mixer, ff, x, routing, *, eps, dim_head, window, theta, k,
           matmul_dtype):
    """One layer on float32 copies of its own parameters."""
    mixer, ff = _f32(mixer), _f32(ff)
    logits = _mm("...d,de->...e", x, ff["moe"]["w_router"], matmul_dtype)
    x = x + _attention(mixer, x, eps, dim_head, window, theta, matmul_dtype)
    y, top_idx, gap, reach = _experts(ff, x, logits, eps, k, routing,
                                      matmul_dtype)
    return x + y, top_idx, gap, reach


def _text_labels(cfg, text):
    return jnp.where(text == 0,
                     cfg.num_text_tokens + jnp.arange(cfg.text_seq_len), text)


def hidden(params, cfg, text, codes, matmul_dtype=None, routing=None):
    """``(h, routes)``: ``h`` ``[b, n, dim]`` float32 after the final norm,
    at the ``n = text_seq_len + image_seq_len`` input positions ``[bos, text,
    codes[:-1]]`` (teacher forcing); ``routes`` a dict of ``top_idx``
    ``[layers, b, n, k]``, ``gap`` and ``reach`` ``[layers, b, n]`` (module
    docstring).  ``matmul_dtype``: every layer's matrix products, the
    router's among them, on operands rounded to a narrower float (a
    tolerance's second reading).  ``routing``: the experts to use."""
    spec = cfg.trunk
    t_len, fmap = cfg.text_seq_len, cfg.image_fmap_size
    n = t_len + fmap * fmap
    table = _f32(params["table"]["embedding"])
    text = jnp.pad(_text_labels(cfg, text), ((0, 0), (1, 0)))
    split = cfg.num_text_tokens + t_len
    x = jnp.concatenate([table[text], table[codes + split]], axis=1)[:, :n]

    layers = params["transformer"]
    routes = []
    for i in range(cfg.depth):
        kind = spec.mixers[i % len(spec.mixers)]
        x, *route = _layer(
            layers[f"layers_{i}_attn"], layers[f"layers_{i}_ff"], x,
            None if routing is None else routing[i], eps=spec.norm_eps,
            dim_head=cfg.dim_head,
            window=spec.window if kind == "window" else 0,
            theta=float(spec.rope_theta), k=spec.experts_per_token,
            matmul_dtype=matmul_dtype)
        routes.append(route)
    top_idx, gap, reach = (jnp.stack(r) for r in zip(*routes))
    return (_rms(x, _f32(params["final_norm"]["scale"]), spec.norm_eps),
            {"top_idx": top_idx, "gap": gap, "reach": reach})


def _head(params, h, rows=slice(None)):
    return _mm("...d,vd->...v", h, _f32(params["head"][rows]))


def joint_logits(params, cfg, text, codes, **kw):
    """``[b, n, total_tokens]``: the head over every position, then DALL-E's
    phase mask (-inf where the phase forbids the id)."""
    h, _ = hidden(params, cfg, text, codes, **kw)
    logits = _head(params, h)
    split = cfg.num_text_tokens + cfg.text_seq_len
    is_text_pos = jnp.arange(h.shape[1])[:, None] < cfg.text_seq_len
    is_text_id = jnp.arange(logits.shape[-1])[None, :] < split
    return jnp.where(is_text_pos == is_text_id, logits, -jnp.inf)


def image_logits(params, cfg, text, codes, **kw):
    """``(logits, routes)``: ``logits`` ``[b, image_seq_len,
    num_image_tokens]``, at image position p the logits of code p given the
    prompt and codes ``[:p]`` (the head's image rows only, which is the
    phase mask); ``routes`` as :func:`hidden` gives them, over all ``n``
    positions."""
    h, routes = hidden(params, cfg, text, codes, **kw)
    split = cfg.num_text_tokens + cfg.text_seq_len
    return _head(params, h[:, cfg.text_seq_len:], slice(split, None)), routes


def train_loss(params, cfg, text, codes, **kw):
    """DALL-E's loss: next-token cross-entropy, text positions over the text
    ids and image positions over the image codes, image weighted
    ``loss_img_weight`` to 1."""
    logp = jax.nn.log_softmax(joint_logits(params, cfg, text, codes, **kw))
    split = cfg.num_text_tokens + cfg.text_seq_len
    labels = jnp.concatenate([_text_labels(cfg, text), codes + split], 1)
    nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    w = cfg.loss_img_weight
    return (nll[:, :cfg.text_seq_len].mean()
            + w * nll[:, cfg.text_seq_len:].mean()) / (w + 1)
