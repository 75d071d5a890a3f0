"""Plain reference of DALL-E over GLM-4.7-Flash's trunk (configuration
``glm-4.7-flash``): the forward pass in the PUBLISHED form, the joint logits,
the training loss, what each layer's cache would hold and what each router
decided.

Straightforward ``jax.numpy`` in float32 with exact matmuls
(``Precision.HIGHEST``): the whole sequence at once; every position's
``k_nope`` and ``v`` decompressed from the latent through ``W_kvb``; no
cache, no absorption, no batching (queries are taken a block at a time against
every key only so that the ``[heads, n, n]`` scores of 4,352 positions fit
beside the model); its own rotation and its own router; a Python loop over the
experts held; nothing imported from the program (``dalle_pytorch_tpu``).  It
reads the program's parameter tree by its names and upcasts it one layer at a
time (each layer is its own jitted call).

The trunk follows
https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json
(``model_type: glm4_moe_lite``).  Layer ``i`` with hidden state ``x`` ``[n,
dim]``, position ``t``, head ``j``::

    h        = RMSNorm_1(x)
    c_q      = RMSNorm_q(h @ W_qa)                      # q_lora_rank
    [q_nope_j | q_rope_j] = c_q @ W_qb                  # qk_nope | qk_rope
    [c_raw | k_raw]       = h @ W_kva                   # kv_lora_rank | qk_rope
    c        = RMSNorm_kv(c_raw)
    k_rope   = RoPE(k_raw, t);  q_rope_j = RoPE(q_rope_j, t)   # pairs (d, d + 32)
    [k_nope_j | v_j]      = c @ W_kvb                   # qk_nope | v_head_dim
    s_j(t,u) = (q_nope_j(t).k_nope_j(u) + q_rope_j(t).k_rope(u)) / sqrt(qk_nope + qk_rope),  u <= t
    x1       = x + concat_j(softmax_u(s_j) v_j) @ W_o
    m        = RMSNorm_2(x1)
    i <  first_k_dense_replace:   y = (silu(m @ W_gate) * (m @ W_up)) @ W_down
    i >= first_k_dense_replace:
      sc     = sigmoid(m @ W_r)                         # all n_routed (published) experts
      S      = the k largest of sc + b                  # b: e_score_correction_bias
      w_e    = routed_scaling_factor * sc_e / (sum_S sc + 1e-20)
      y      = sum_{e in S, e held} w_e (silu(m @ W_gate_e) * (m @ W_up_e)) @ W_down_e
               + (silu(m @ W_gate_s) * (m @ W_up_s)) @ W_down_s
    x_out    = x1 + y

then the final RMSNorm and an untied head.  ``RMSNorm(x) = x * rsqrt(mean(x^2)
+ eps) * gain``.

**The share.**  ``experts_first`` and ``experts_held`` (default: the
configuration's) say which experts' banks the parameters hold: bank ``j`` is
expert ``experts_first + j``.  The router scores all experts; what a chosen
expert that is not held would have added is left out.  With every bank held
the layer is the uncut one.

Departures from the published model, all DALL-E's client or this repo's
(``benchmark/configs/glm-4.7-flash.json``, ``assumed``): rotate-half pairing
of the rotary dimensions; the joint vocabulary (text ids, one pad id a text
position, image codes; ``<bos>`` is id 0) and DALL-E's phase mask and loss; no
learned position embedding, RoPE's position the index in ``[bos, text,
codes]``; a seeded selection bias and seeded weights; the next-token-
prediction layer is not held.

The program's names: ``layers_i_attn/mla``: ``w_qa`` ``[dim, q_rank]``,
``q_norm``, ``w_qb`` ``[q_rank, heads, nope + rope]``, ``w_kva`` ``[dim,
kv_rank + rope]``, ``kv_norm``, ``w_kvb`` ``[kv_rank, heads, nope + value]``,
``w_o`` ``[heads, value, dim]``; ``layers_0_ff``: ``gate`` / ``up`` / ``down``
kernels; ``layers_i_ff/moe``: ``w_router`` ``[dim, experts]``,
``router_bias``, ``w_gate`` / ``w_up`` ``[held, dim, width]``, ``w_down``
``[held, width, dim]``, ``shared_gate`` / ``shared_up`` / ``shared_down``;
``table/embedding`` and ``head`` ``[vocabulary, dim]``.

**Routing, and what to do where it nearly ties.**  As
``reference_smallthinker_21ba3b``: :func:`hidden` reports, per routed layer
and position, its own chosen experts (``top_idx``) and can be handed the
experts to use (``routing``: ``[routed layers, b, n, k]``): it then weights
them by its own scores and reports how far down its own ranking of ``sc + b``
the handed set reaches (``reach``: the least ``(sc + b)_e / (sc + b)_(k)``
over the handed experts; 1 where the sets agree).

**Faults to plant** (``fault``; the benchmark's controls, each of which the
comparison must refuse): ``"unnormed_latent"`` (``c = c_raw``),
``"unrotated_key"`` (``k_rope = k_raw``), ``"bias_in_weights"`` (``w_e`` from
``sc + b``), ``"no_shared_expert"``, ``"other_experts"`` (the banks taken for
experts ``experts_first + experts_held`` onwards).
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
FAULTS = ("unnormed_latent", "unrotated_key", "bias_in_weights",
          "no_shared_expert", "other_experts")


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
    """``x`` ``[..., n, d]`` rotated by position 0..n-1 over all ``d``
    dimensions: dimension ``i < d / 2`` pairs with ``i + d / 2`` and turns by
    ``p * theta^(-2i / d)``."""
    n, d = x.shape[-2], x.shape[-1]
    half = d // 2
    angle = (jnp.arange(n, dtype=F32)[:, None]
             * theta ** (-2.0 * jnp.arange(half, dtype=F32) / d))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _attention(p, x, *, eps, nope, theta, low, fault):
    """Latent attention in the published form.  Returns ``(out, c, k_rope)``
    with ``c`` ``[b, n, kv_rank]`` and ``k_rope`` ``[b, n, rope]`` what a
    decode cache would hold of the sequence."""
    b, n, _ = x.shape
    h = _rms(x, p["norm"]["scale"], eps)
    a = p["mla"]
    rank = a["kv_norm"].shape[0]
    c_q = _rms(_mm("bnd,dr->bnr", h, a["w_qa"], low), a["q_norm"], eps)
    q = _mm("bnr,rhe->bhne", c_q, a["w_qb"], low)
    ckv = _mm("bnd,dr->bnr", h, a["w_kva"], low)
    c_raw, k_raw = ckv[..., :rank], ckv[..., rank:]
    c = c_raw if fault == "unnormed_latent" else _rms(c_raw, a["kv_norm"],
                                                      eps)
    k_rope = k_raw if fault == "unrotated_key" else rope(k_raw, theta)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], theta)
    kv = _mm("bnc,che->bhne", c, a["w_kvb"], low)       # decompressed
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = q.shape[-1] ** -0.5
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    outs = []
    for start in range(0, n, Q_BLOCK):
        rows = slice(start, min(start + Q_BLOCK, n))
        dots = (_mm("bhie,bhje->bhij", q_nope[:, :, rows], k_nope, low)
                + _mm("bhie,bje->bhij", q_rope[:, :, rows], k_rope, low)
                ) * scale
        dots = jnp.where(causal[rows][None, None], dots, -jnp.inf)
        outs.append(_mm("bhij,bhje->bhie", jax.nn.softmax(dots, -1), v, low))
    o = jnp.concatenate(outs, axis=2)                   # [b, h, n, value]
    return _mm("bhnv,hvd->bnd", o, a["w_o"], low), c, k_rope


def _swiglu(m, gate, up, down, low):
    return _mm("...f,fd->...d",
               jax.nn.silu(_mm("...d,df->...f", m, gate, low))
               * _mm("...d,df->...f", m, up, low), down, low)


def _experts(p, x, *, eps, k, scale, first, routing, low, fault):
    """The routed feed-forward on the hidden state after attention.  Returns
    ``(y, top_idx, gap, reach, weight)``, ``weight`` ``[b, n, k]`` the
    weights of the experts used, in their order."""
    m = _rms(x, p["norm"]["scale"], eps)
    w = p["moe"]
    held = w["w_gate"].shape[0]
    if fault == "other_experts":
        first = first + held
    sc = jax.nn.sigmoid(_mm("bnd,de->bne", m, w["w_router"], low))
    sel = sc + w["router_bias"]
    ranked, top_idx = jax.lax.top_k(sel, k + 1)
    gap = (ranked[..., k - 1] - ranked[..., k]) / ranked[..., k - 1]
    top_idx = top_idx[..., :k]
    chosen = top_idx if routing is None else routing
    reach = jnp.take_along_axis(sel, chosen, -1).min(-1) / ranked[..., k - 1]
    picked = jnp.take_along_axis(
        sel if fault == "bias_in_weights" else sc, chosen, -1)   # [b, n, k]
    weight = scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    y = jnp.zeros_like(x)
    for j in range(held):                  # one held expert at a time
        w_e = jnp.where(chosen == first + j, weight, 0.0).sum(-1)  # [b, n]
        y = y + w_e[..., None] * _swiglu(m, w["w_gate"][j], w["w_up"][j],
                                         w["w_down"][j], low)
    if fault != "no_shared_expert":
        y = y + _swiglu(m, w["shared_gate"], w["shared_up"],
                        w["shared_down"], low)
    return y, top_idx, gap, reach, weight


@functools.partial(jax.jit, static_argnames=(
    "eps", "nope", "theta", "k", "scale", "first", "matmul_dtype", "fault"))
def _layer(mixer, ff, x, routing, *, eps, nope, theta, k, scale, first,
           matmul_dtype, fault):
    """One layer on float32 copies of its own parameters; ``ff`` with a
    ``moe`` entry is a routed layer, else the dense SwiGLU."""
    mixer, ff = _f32(mixer), _f32(ff)
    out, c, k_rope = _attention(mixer, x, eps=eps, nope=nope, theta=theta,
                                low=matmul_dtype, fault=fault)
    x = x + out
    if "moe" not in ff:
        m = _rms(x, ff["norm"]["scale"], eps)
        y = _swiglu(m, ff["gate"]["kernel"], ff["up"]["kernel"],
                    ff["down"]["kernel"], matmul_dtype)
        return x + y, c, k_rope, None
    y, *route = _experts(ff, x, eps=eps, k=k, scale=scale, first=first,
                         routing=routing, low=matmul_dtype, fault=fault)
    return x + y, c, k_rope, route


def _text_labels(cfg, text):
    return jnp.where(text == 0,
                     cfg.num_text_tokens + jnp.arange(cfg.text_seq_len), text)


def hidden(params, cfg, text, codes, matmul_dtype=None, routing=None,
           fault=None, experts_first=None, depth=None):
    """``(h, extras)``: ``h`` ``[b, n, dim]`` float32 after the final norm,
    at the ``n = text_seq_len + image_seq_len`` input positions ``[bos, text,
    codes[:-1]]`` (teacher forcing); ``extras`` a dict of ``top_idx``
    ``[routed layers, b, n, k]``, ``gap`` and ``reach`` ``[routed layers, b,
    n]`` (module docstring), ``weight`` ``[routed layers, b, n, k]`` (the
    weights of the experts used, in the order handed) and ``latent``, per layer the ``(c, k_rope)`` a
    decode cache would hold.  ``matmul_dtype``: every layer's matrix
    products on operands rounded to a narrower float (a tolerance's second
    reading).  ``routing``: the experts to use.  ``fault``: one of
    :data:`FAULTS`.  ``experts_first``: the first expert the banks hold
    (default: the configuration's).  ``depth``: stop after that many layers
    (what the first layers cache and route does not depend on the rest)."""
    assert fault is None or fault in FAULTS, fault
    spec = cfg.trunk
    t_len, fmap = cfg.text_seq_len, cfg.image_fmap_size
    n = t_len + fmap * fmap
    table = _f32(params["table"]["embedding"])
    text = jnp.pad(_text_labels(cfg, text), ((0, 0), (1, 0)))
    split = cfg.num_text_tokens + t_len
    x = jnp.concatenate([table[text], table[codes + split]], axis=1)[:, :n]

    layers = params["transformer"]
    routes, latent = [], []
    for i in range(cfg.depth if depth is None else depth):
        routed = i - spec.dense_layers
        x, c, k_rope, route = _layer(
            layers[f"layers_{i}_attn"], layers[f"layers_{i}_ff"], x,
            None if routing is None or routed < 0 else routing[routed],
            eps=spec.norm_eps, nope=spec.nope_dim,
            theta=float(spec.rope_theta), k=spec.experts_per_token,
            scale=float(spec.route_scale),
            first=int(spec.experts_first if experts_first is None
                      else experts_first),
            matmul_dtype=matmul_dtype, fault=fault)
        latent.append((c, k_rope))
        if route is not None:
            routes.append(route)
    top_idx, gap, reach, weight = (
        (jnp.stack(r) for r in zip(*routes)) if routes else (None,) * 4)
    return (_rms(x, _f32(params["final_norm"]["scale"]), spec.norm_eps),
            {"top_idx": top_idx, "gap": gap, "reach": reach,
             "weight": weight, "latent": latent})


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
    """``(logits, extras)``: ``logits`` ``[b, image_seq_len,
    num_image_tokens]``, at image position p the logits of code p given the
    prompt and codes ``[:p]`` (the head's image rows only, which is the
    phase mask); ``extras`` as :func:`hidden` gives them, over all ``n``
    positions."""
    h, extras = hidden(params, cfg, text, codes, **kw)
    split = cfg.num_text_tokens + cfg.text_seq_len
    return _head(params, h[:, cfg.text_seq_len:], slice(split, None)), extras


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
