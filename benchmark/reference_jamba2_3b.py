"""Plain reference of DALL-E over the Jamba family's trunk (configuration
``jamba2-3b``): the forward pass, the joint logits and the training loss.

Straightforward ``jax.numpy`` in float32 with exact matmuls
(``Precision.HIGHEST``): no cache, no chunking, the selective scan as one
sequential ``lax.scan`` over positions, and nothing imported from the program
(``dalle_pytorch_tpu``).  It reads the program's parameter tree by its names
and upcasts it one layer at a time (each layer is its own jitted call), so
that beside a bfloat16 model of 6 GB only one layer's float32 copy lives.

The trunk follows ``model_type: jamba`` of Hugging Face ``transformers``
(``modeling_jamba.py``) with the numbers of
https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json:

* layer ``i``: ``x += Mixer_i(RMSNorm(x))`` then ``x += MLP(RMSNorm(x))``;
  ``Mixer_i`` is attention where ``mixers[i % len(mixers)]`` says so
  (``i mod 14 == 7``), else Mamba; every MLP dense (``num_experts`` 1);
* ``RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * g``;
* MLP ``W_down(silu(W_gate h) * (W_up h))``;
* attention: ``heads`` query heads over ``kv_heads`` key/value heads, no
  bias, no position encoding, scale ``dim_head^-0.5``, causal;
* Mamba: ``[u, z] = W_in h``; ``u = silu(conv1d_causal_depthwise(u) + b)``;
  ``[dt, B, C] = W_x u``, RMSNorm on each (Jamba's addition to Mamba-1);
  ``delta = softplus(W_dt dt + b_dt)``; ``A = -exp(A_log)``; ``h_t =
  exp(delta_t A) h_{t-1} + (delta_t u_t) B_t^T``; ``y_t = h_t C_t + D u_t``;
  out ``= W_out(y * silu(z))``;
* final RMSNorm; one table tied between the embedding and the head.

Departures from the published model, all DALL-E's client or this repo's
(``benchmark/configs/jamba2-3b.json``, ``assumed``):

* the table's 65,536 rows are DALL-E's joint vocabulary: ``num_text_tokens``
  text ids, one pad id per text position (pad id 0 at position t becomes
  ``num_text_tokens + t``), then the image codes; ``<bos>`` is id 0;
* DALL-E's learned text position embedding and axial (row + column) image
  position embedding are added to the token embeddings before the trunk,
  which itself has none;
* logits are masked by phase: a text position may predict text ids only, an
  image position image codes only; the loss is DALL-E's
  ``(loss_text + w loss_img) / (w + 1)``;
* weights are seeded random values, not the checkpoint.

The program's names: ``in_proj`` kernel ``[dim, 2, d_in]`` (u then z),
``conv_kernel`` ``[width, d_in]`` (the last tap meets the current position),
``x_proj`` ``[d_in, R + 2N]`` (dt, B, C in that order), ``dt_proj`` ``[R,
d_in]``, ``A_log`` ``[d_in, N]``; ``to_q`` ``[dim, heads, dh]``, ``to_kv``
``[dim, 2, kv_heads, dh]`` (k then v), ``to_out`` ``[heads * dh, dim]``.
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


def _dot(x, w, low=None):
    return _mm("...d,de->...e", x, w, low)


def _mlp(p, x, eps, low):
    h = _rms(x, p["norm"]["scale"], eps)
    return _dot(jax.nn.silu(_dot(h, p["gate"]["kernel"], low))
                * _dot(h, p["up"]["kernel"], low), p["down"]["kernel"], low)


def _attention(p, x, eps, dim_head, low=None):
    b, n, _ = x.shape
    h = _rms(x, p["norm"]["scale"], eps)
    a = p["attn"]
    q = _mm("bnd,dhe->bhne", h, a["to_q"]["kernel"], low)
    kv = _mm("bnd,dkge->kbgne", h, a["to_kv"]["kernel"], low)
    heads, groups = q.shape[1], kv.shape[2]
    # each key/value head serves heads / groups query heads, in order
    k = jnp.repeat(kv[0], heads // groups, axis=1)
    v = jnp.repeat(kv[1], heads // groups, axis=1)
    dots = _mm("bhie,bhje->bhij", q * dim_head ** -0.5, k, low)
    causal = jnp.tril(jnp.ones((n, n), bool))
    dots = jnp.where(causal[None, None], dots, -jnp.inf)
    out = _mm("bhij,bhje->bhie", jax.nn.softmax(dots, -1), v, low)
    out = out.transpose(0, 2, 1, 3).reshape(b, n, -1)
    return _dot(out, a["to_out"]["kernel"], low)


def _mamba(p, x, eps, state_dtype, norm_dbc, low):
    b, n, _ = x.shape
    m = p["ssm"]
    h = _rms(x, p["norm"]["scale"], eps)
    uz = _mm("bnd,dkc->kbnc", h, m["in_proj"]["kernel"], low)
    u, z = uz[0], uz[1]
    width = m["conv_kernel"].shape[0]
    padded = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    u = sum(padded[:, k:k + n] * m["conv_kernel"][k] for k in range(width))
    u = jax.nn.silu(u + m["conv_bias"])
    N = m["A_log"].shape[1]
    R = m["dt_proj"]["kernel"].shape[0]
    dbc = _dot(u, m["x_proj"]["kernel"], low)
    dt, B, C = dbc[..., :R], dbc[..., R:R + N], dbc[..., R + N:]
    if norm_dbc:
        dt = _rms(dt, m["dt_norm"], eps)
        B = _rms(B, m["b_norm"], eps)
        C = _rms(C, m["c_norm"], eps)
    delta = jax.nn.softplus(_dot(dt, m["dt_proj"]["kernel"], low)
                            + m["dt_bias"])
    A = -jnp.exp(m["A_log"])                                # [d_in, N]

    def step(state, at):
        u_t, delta_t, B_t, C_t = at                         # [b, d_in], [b, N]
        decay = jnp.exp(delta_t[..., None] * A).astype(state_dtype)
        inp = ((delta_t * u_t)[..., None]
               * B_t[:, None, :]).astype(state_dtype)
        state = decay * state + inp                         # [b, d_in, N]
        y_t = jnp.einsum("bdn,bn->bd", state.astype(F32), C_t,
                         precision=EXACT)
        return state, y_t

    state0 = jnp.zeros((b, u.shape[-1], N), state_dtype)
    _, y = jax.lax.scan(step, state0,
                        tuple(a.swapaxes(0, 1) for a in (u, delta, B, C)))
    y = y.swapaxes(0, 1) + m["D"] * u
    return _dot(y * jax.nn.silu(z), m["out_proj"]["kernel"], low)


@functools.partial(jax.jit, static_argnames=(
    "kind", "eps", "dim_head", "state_dtype", "norm_dbc", "matmul_dtype"))
def _layer(mixer, mlp, x, *, kind, eps, dim_head, state_dtype, norm_dbc,
           matmul_dtype):
    """One layer on float32 copies of its own parameters."""
    mixer, mlp = _f32(mixer), _f32(mlp)
    if kind == "mamba":
        x = x + _mamba(mixer, x, eps, state_dtype, norm_dbc, matmul_dtype)
    else:
        x = x + _attention(mixer, x, eps, dim_head, matmul_dtype)
    return x + _mlp(mlp, x, eps, matmul_dtype)


def _text_labels(cfg, text):
    return jnp.where(text == 0,
                     cfg.num_text_tokens + jnp.arange(cfg.text_seq_len), text)


def hidden(params, cfg, text, codes, state_dtype=F32, norm_dbc=True,
           matmul_dtype=None):
    """``[b, n, dim]`` float32 after the final norm, at the ``n =
    text_seq_len + image_seq_len`` input positions ``[bos, text,
    codes[:-1]]`` (teacher forcing).  The three options exist for the
    tolerances' second readings: the recurrent state held in a lower
    precision, Jamba's norms on dt, B and C left out, and every layer's
    matrix products on operands rounded to a narrower float."""
    spec = cfg.trunk
    t_len, fmap = cfg.text_seq_len, cfg.image_fmap_size
    n = t_len + fmap * fmap
    table = _f32(params["table"]["embedding"])
    text = jnp.pad(_text_labels(cfg, text), ((0, 0), (1, 0)))
    tok = table[text] + _f32(params["text_pos_emb"]["embedding"])[None]
    pos = _f32(params["image_pos_emb"])
    grid = (pos["row"] + pos["col"]).reshape(fmap * fmap, -1)
    split = cfg.num_text_tokens + t_len
    img = table[codes + split] + grid[None]
    x = jnp.concatenate([tok, img], axis=1)[:, :n]

    layers = params["transformer"]
    for i in range(cfg.depth):
        kind = spec.mixers[i % len(spec.mixers)]
        name = f"layers_{i}_" + ("ssm" if kind == "mamba" else "attn")
        x = _layer(layers[name], layers[f"layers_{i}_ff"], x, kind=kind,
                   eps=spec.norm_eps, dim_head=cfg.dim_head,
                   state_dtype=state_dtype, norm_dbc=norm_dbc,
                   matmul_dtype=matmul_dtype)
    return _rms(x, _f32(params["final_norm"]["scale"]), spec.norm_eps)


def joint_logits(params, cfg, text, codes, **kw):
    """``[b, n, total_tokens]``: the tied head over every position, then
    DALL-E's phase mask (-inf where the phase forbids the id)."""
    h = hidden(params, cfg, text, codes, **kw)
    logits = _dot(h, _f32(params["table"]["embedding"]).T)
    split = cfg.num_text_tokens + cfg.text_seq_len
    is_text_pos = jnp.arange(h.shape[1])[:, None] < cfg.text_seq_len
    is_text_id = jnp.arange(logits.shape[-1])[None, :] < split
    return jnp.where(is_text_pos == is_text_id, logits, -jnp.inf)


def image_logits(params, cfg, text, codes, **kw):
    """``[b, image_seq_len, num_image_tokens]``: at image position p the
    logits of code p given the prompt and codes ``[:p]``."""
    split = cfg.num_text_tokens + cfg.text_seq_len
    return joint_logits(params, cfg, text, codes,
                        **kw)[:, cfg.text_seq_len:, split:]


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
