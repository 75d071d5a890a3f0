"""Plain reference of DALL-E over the Olmo-Hybrid family's trunk
(configuration ``olmo-hybrid-7b``): the forward pass, the joint logits and the
training loss.

Straightforward ``jax.numpy`` in float32 with exact matmuls
(``Precision.HIGHEST``): no cache, no chunks, no batching of positions, the
gated delta rule as one sequential ``lax.scan`` over positions, and nothing
imported from the program (``dalle_pytorch_tpu``).  It reads the program's
parameter tree by its names and upcasts it one layer at a time (each layer is
its own jitted call), so that beside a bfloat16 model of 4.9 GB only one
layer's float32 copy lives.

The trunk, with the numbers of
https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json
(``model_type: olmo_hybrid``); hidden state ``h`` ``[n, dim]``:

* layer ``i``, both kinds, with the norm on each sublayer's OUTPUT and the
  sublayers reading the un-normed stream (the family's reordered norm)::

      h1    = h  + RMSNorm_a(Mixer_i(h))
      h_out = h1 + RMSNorm_f(W_down(silu(W_gate h1) * (W_up h1)))

  ``RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * g``; ``Mixer_i`` is full
  attention where ``mixers[i % len(mixers)]`` says so (``layer_types[i] ==
  "full_attention"``: ``i mod 4 == 3``), else the linear-attention mixer;
* full attention: ``q, k, v = h W_q, h W_k, h W_v``; ``q, k =
  RMSNorm_q(q), RMSNorm_k(k)`` over the whole projection, before the split
  into heads; no rotation (``rope_theta`` null), no bias, scale
  ``dim_head^-0.5``, causal, softmax in float32; out ``= (softmax v) W_o``;
* linear attention (the gated delta rule; ``heads`` heads, state ``S``
  ``[d_k, d_v]`` a head, zero before the first position)::

      q^, k^, v = silu(conv(h W_q)), silu(conv(h W_k)), silu(conv(h W_v))
                           # causal depthwise convolution, no bias
      q_t, k_t  = l2norm(q^_t per head) / sqrt(d_k),  l2norm(k^_t per head)
      beta_t    = 2 sigmoid(h_t W_b)
      g_t       = -exp(A_log) softplus(h_t W_a + dt_bias)
      S         = exp(g_t) S_{t-1}
      S_t       = S + k_t (beta_t (v_t - S^T k_t))^T
      o_t       = S_t^T q_t
      y_t       = RMSNorm_o(o_t) * silu(h_t W_g)_head      # gain [d_v]
      out_t     = concat_heads(y_t) W_o

* the final RMSNorm and an untied head.

Departures from the published model, all DALL-E's client or this repo's
(``benchmark/configs/olmo-hybrid-7b.json``, ``assumed``):

* the 100,352 rows of the embedding and of the head are DALL-E's joint
  vocabulary: ``num_text_tokens`` text ids, one pad id per text position
  (pad id 0 at position t becomes ``num_text_tokens + t``), then the image
  codes; ``<bos>`` is id 0;
* DALL-E's learned text position embedding and axial (row + column) image
  position embedding are added to the token embeddings before the trunk,
  which itself has none;
* logits are masked by phase: a text position may predict text ids only, an
  image position image codes only; the loss is DALL-E's
  ``(loss_text + w loss_img) / (w + 1)``;
* weights are seeded random values, not the checkpoint.

The program's names, under ``transformer``: ``layers_i_gdn/gdn`` holds
``q_proj``, ``k_proj`` ``[dim, heads, d_k]``, ``v_proj``, ``g_proj`` ``[dim,
heads, d_v]``, ``a_proj``, ``b_proj`` ``[dim, heads]``, ``conv_q``, ``conv_k``
``[width, heads, d_k]``, ``conv_v`` ``[width, heads, d_v]`` (the last tap
meets the current position), ``A_log``, ``dt_bias`` ``[heads]``, ``o_norm``
``[d_v]``, ``o_proj`` ``[heads, d_v, dim]``; ``layers_i_attn/attn`` holds
``to_q`` ``[dim, heads, dh]``, ``to_kv`` ``[dim, 2, kv_heads, dh]`` (k then
v), ``q_norm`` ``[heads * dh]``, ``k_norm`` ``[kv_heads * dh]``, ``to_out``
``[heads * dh, dim]``; ``layers_i_mixer_norm``, ``layers_i_ff_norm`` the two
output norms; ``layers_i_ff`` the SwiGLU's ``gate``, ``up``, ``down``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
EXACT = jax.lax.Precision.HIGHEST


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, F32), tree)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _l2(x, eps):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


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


def _mlp(p, x, low):
    gate = _mm("bnd,df->bnf", x, p["gate"]["kernel"], low)
    up = _mm("bnd,df->bnf", x, p["up"]["kernel"], low)
    return _mm("bnf,fd->bnd", jax.nn.silu(gate) * up, p["down"]["kernel"],
               low)


def _attention(p, x, eps, dim_head, low=None):
    b, n, _ = x.shape
    a = p["attn"]
    q = _mm("bnd,dhe->bnhe", x, a["to_q"]["kernel"], low)
    kv = _mm("bnd,dkge->kbnge", x, a["to_kv"]["kernel"], low)
    k, v = kv[0], kv[1]
    heads, groups = q.shape[2], k.shape[2]
    # the norm runs over the whole projection, all heads together
    q = _rms(q.reshape(b, n, -1), a["q_norm"], eps).reshape(q.shape)
    k = _rms(k.reshape(b, n, -1), a["k_norm"], eps).reshape(k.shape)
    # each key/value head serves heads / groups query heads, in order
    k = jnp.repeat(k, heads // groups, axis=2)
    v = jnp.repeat(v, heads // groups, axis=2)
    dots = _mm("bihe,bjhe->bhij", q * dim_head ** -0.5, k, low)
    causal = jnp.tril(jnp.ones((n, n), bool))
    dots = jnp.where(causal[None, None], dots, -jnp.inf)
    out = _mm("bhij,bjhe->bihe", jax.nn.softmax(dots, -1), v, low)
    return _mm("bnf,fd->bnd", out.reshape(b, n, -1), a["to_out"]["kernel"],
               low)


def _conv(u, taps):
    """Causal depthwise convolution of ``u`` ``[b, n, heads, d]`` with
    ``taps`` ``[width, heads, d]``: zeros before position 0, the last tap on
    the current position."""
    width, n = taps.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0), (0, 0)))
    return sum(padded[:, j:j + n] * taps[j] for j in range(width))


def delta_rule(q, k, v, g, beta, state_dtype=F32):
    """The gated delta rule, one position at a time.  ``q``, ``k`` ``[b, n,
    heads, d_k]``, ``v`` ``[b, n, heads, d_v]``, ``g``, ``beta`` ``[b, n,
    heads]``.  ``state_dtype`` is the precision the state is kept in between
    positions (float32; bfloat16 for a limit's second reading).  Returns the
    read-outs ``[b, n, heads, d_v]`` and the state after the last position,
    ``[b, heads, d_k, d_v]`` float32."""
    b, _, h, dk = k.shape

    def step(S, at):
        q_t, k_t, v_t, g_t, beta_t = at
        S = jnp.exp(g_t)[..., None, None] * S.astype(F32)
        read = jnp.einsum("bhde,bhd->bhe", S, k_t, precision=EXACT)
        u_t = beta_t[..., None] * (v_t - read)
        S = (S + k_t[..., :, None] * u_t[..., None, :]).astype(state_dtype)
        o_t = jnp.einsum("bhde,bhd->bhe", S.astype(F32), q_t,
                         precision=EXACT)
        return S, o_t

    S0 = jnp.zeros((b, h, dk, v.shape[-1]), state_dtype)
    S, o = jax.lax.scan(step, S0, tuple(a.swapaxes(0, 1)
                                        for a in (q, k, v, g, beta)))
    return o.swapaxes(0, 1), S.astype(F32)


def _linear_attention(p, x, eps, state_dtype, low):
    b, n, _ = x.shape
    m = p["gdn"]
    proj = lambda name: _mm("bnd,dhe->bnhe", x,  # noqa: E731
                            m[name]["kernel"], low)
    q = jax.nn.silu(_conv(proj("q_proj"), m["conv_q"]))
    k = jax.nn.silu(_conv(proj("k_proj"), m["conv_k"]))
    v = jax.nn.silu(_conv(proj("v_proj"), m["conv_v"]))
    q = _l2(q, eps) * q.shape[-1] ** -0.5
    k = _l2(k, eps)
    beta = 2.0 * jax.nn.sigmoid(_mm("bnd,dh->bnh", x, m["b_proj"]["kernel"],
                                    low))
    g = -jnp.exp(m["A_log"]) * jax.nn.softplus(
        _mm("bnd,dh->bnh", x, m["a_proj"]["kernel"], low) + m["dt_bias"])
    o, _ = delta_rule(q, k, v, g, beta, state_dtype)
    y = _rms(o, m["o_norm"], eps) * jax.nn.silu(proj("g_proj"))
    return _mm("bnhe,hed->bnd", y, m["o_proj"]["kernel"], low)


@functools.partial(jax.jit, static_argnames=(
    "kind", "eps", "dim_head", "state_dtype", "matmul_dtype"))
def _layer(mixer, mixer_norm, mlp, mlp_norm, x, *, kind, eps, dim_head,
           state_dtype, matmul_dtype):
    """One layer on float32 copies of its own parameters."""
    mixer, mixer_norm, mlp, mlp_norm = map(
        _f32, (mixer, mixer_norm, mlp, mlp_norm))
    if kind == "gdn":
        mixed = _linear_attention(mixer, x, eps, state_dtype, matmul_dtype)
    else:
        mixed = _attention(mixer, x, eps, dim_head, matmul_dtype)
    x = x + _rms(mixed, mixer_norm["scale"], eps)
    return x + _rms(_mlp(mlp, x, matmul_dtype), mlp_norm["scale"], eps)


def _text_labels(cfg, text):
    return jnp.where(text == 0,
                     cfg.num_text_tokens + jnp.arange(cfg.text_seq_len), text)


def hidden(params, cfg, text, codes, state_dtype=F32, matmul_dtype=None):
    """``[b, n, dim]`` float32 after the final norm, at the ``n =
    text_seq_len + image_seq_len`` input positions ``[bos, text,
    codes[:-1]]`` (teacher forcing).  The two options exist for the
    tolerance's other readings: the recurrent state kept in a lower
    precision between positions, and every layer's matrix products on
    operands rounded to a narrower float."""
    spec = cfg.trunk
    assert spec.norm_at == "output" and spec.qk_norm and not spec.tied_table
    t_len, fmap = cfg.text_seq_len, cfg.image_fmap_size
    n = t_len + fmap * fmap
    table = params["table"]["embedding"]
    text = jnp.pad(_text_labels(cfg, text), ((0, 0), (1, 0)))
    tok = _f32(table[text]) + _f32(params["text_pos_emb"]["embedding"])[None]
    pos = _f32(params["image_pos_emb"])
    grid = (pos["row"] + pos["col"]).reshape(fmap * fmap, -1)
    split = cfg.num_text_tokens + t_len
    img = _f32(table[codes + split]) + grid[None]
    x = jnp.concatenate([tok, img], axis=1)[:, :n]

    layers = params["transformer"]
    for i in range(cfg.depth):
        kind = spec.mixers[i % len(spec.mixers)]
        name = f"layers_{i}_" + ("gdn" if kind == "gdn" else "attn")
        x = _layer(layers[name], layers[f"layers_{i}_mixer_norm"],
                   layers[f"layers_{i}_ff"], layers[f"layers_{i}_ff_norm"],
                   x, kind=kind, eps=spec.norm_eps, dim_head=cfg.dim_head,
                   state_dtype=state_dtype, matmul_dtype=matmul_dtype)
    return _rms(x, _f32(params["final_norm"]["scale"]), spec.norm_eps)


def joint_logits(params, cfg, text, codes, **kw):
    """``[b, n, total_tokens]``: the untied head over every position, then
    DALL-E's phase mask (-inf where the phase forbids the id)."""
    h = hidden(params, cfg, text, codes, **kw)
    logits = jnp.einsum("bnd,vd->bnv", h, _f32(params["head"]),
                        precision=EXACT)
    split = cfg.num_text_tokens + cfg.text_seq_len
    is_text_pos = jnp.arange(h.shape[1])[:, None] < cfg.text_seq_len
    is_text_id = jnp.arange(logits.shape[-1])[None, :] < split
    return jnp.where(is_text_pos == is_text_id, logits, -jnp.inf)


def image_logits(params, cfg, text, codes, **kw):
    """``[b, image_seq_len, num_image_tokens]``: at image position p the
    logits of code p given the prompt and codes ``[:p]``: the head's image
    rows over the image positions (the other rows and positions are never
    formed: the head is 100,352 rows wide)."""
    split = cfg.num_text_tokens + cfg.text_seq_len
    h = hidden(params, cfg, text, codes, **kw)[:, cfg.text_seq_len:]
    return jnp.einsum("bnd,vd->bnv", h, _f32(params["head"][split:]),
                      precision=EXACT)


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
