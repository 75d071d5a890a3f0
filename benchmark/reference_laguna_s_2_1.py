"""Plain reference of DALL-E over Laguna-S-2.1's trunk (configuration
``laguna-s-2.1``): the forward pass, the joint logits, the training loss, what
each layer's cache would hold and what each router decided.

Straightforward ``jax.numpy`` in float32 with exact matmuls
(``Precision.HIGHEST``): the whole sequence at once, no cache and no ring (a
mask; queries are taken a block at a time against every key only so that the
``[72, n, n]`` scores of 4,352 positions fit beside the model), its own
rotation tables, gate and router, a Python loop over the experts held, and
nothing imported from the program (``dalle_pytorch_tpu``).  It reads the
program's parameter tree by its names and upcasts it one layer at a time (each
layer is its own jitted call).

The trunk follows
https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json
(``model_type: laguna``).  Layer ``i`` with hidden state ``x`` ``[n, 3072]``,
position ``t``, query head ``j``, key head ``g(j) = floor(j / (H_i / 8))``::

    kind_i   = global if i mod 4 == 0 else window         # layer_types
    H_i      = 48 if global else 72                       # num_attention_heads_per_layer
    h        = RMSNorm_1(x)                                # eps 1e-6, f32 gain
    q_j, k_g, v_g = h W_q, h W_k, h W_v                    # no bias, no q/k norm
    global:  q_j, k_g <- YaRN-RoPE on dims 0..63 (rotate-half within them:
               d with d + 32), dims 64..127 untouched
               inv_freq_d = lerp(1/(128 5e5^(2d/64)), 1/5e5^(2d/64), 1 - ramp_d)
               ramp_d = clip((d - 9)/(18 - 9), 0, 1)      # floor 9.04, ceil 17.49
               cos, sin x attention_factor 1.4852030263919618
    window:  q_j, k_g <- RoPE theta 1e4 over all 128 dims (rotate-half)
    s_j(t,u) = q_j(t).k_g(u) / sqrt(128),  u <= t, and (window) u > t - 512
    a_j      = sigmoid(h W_gate)_j . sum_u softmax_u(s_j) v_g(u)   # one scalar a head
    x1       = x + concat_j(a_j) W_o
    m        = RMSNorm_2(x1)
    i == 0:  y = (silu(m W_g) * m W_u) W_d                 # 12,288 (mlp_only_layers [0])
    i >= 1:  p = softmax(m W_r) (f32, all 256);  S = top-10 of p;  w_e = 2.5 p_e / sum_S p
             y = sum_{e in S, e held} w_e (silu(m W_g,e) * m W_u,e) W_d,e
                 + (silu(m W_g,s) * m W_u,s) W_d,s         # 1,024 wide each
    x_out    = x1 + y

then the final RMSNorm and an untied head.  ``RMSNorm(x) = x * rsqrt(mean(x^2)
+ eps) * gain``.

**The share.**  ``experts_first`` and ``experts_held`` (default: the
configuration's) say which experts' banks the parameters hold: bank ``j`` is
expert ``experts_first + j``.  The router scores all experts; what a chosen
expert that is not held would have added is left out.  With every bank held
the layer is the uncut one.

Departures from the published model, all DALL-E's client or this repo's
(``benchmark/configs/laguna-s-2.1.json``, ``assumed``): the gate's form
(head-wise, from the normed sublayer input), the partial rotation's
convention (the leading dimensions), the shared expert without a gate; the
joint vocabulary (text ids, one pad id a text position, image codes;
``<bos>`` is id 0) and DALL-E's phase mask and loss; no learned position
embedding, RoPE's position the index in ``[bos, text, codes]``; seeded
weights.

The program's names: ``layers_i_attn/attn``: ``to_q`` ``[dim, heads, dh]``,
``to_kv`` ``[dim, 2, kv_heads, dh]`` (k then v), ``to_gate`` ``[dim,
heads]``, ``to_out`` ``[heads * dh, dim]``; ``layers_0_ff``: ``gate`` /
``up`` / ``down`` kernels; ``layers_i_ff/moe``: ``w_router`` ``[dim,
experts]``, ``w_gate`` / ``w_up`` ``[held, dim, width]``, ``w_down`` ``[held,
width, dim]``, ``shared_gate`` / ``shared_up`` / ``shared_down``;
``table/embedding`` and ``head`` ``[vocabulary, dim]``.

**Routing, and what to do where it nearly ties.**  As
``reference_smallthinker_21ba3b``: :func:`hidden` reports, per routed layer
and position, its own chosen experts (``top_idx``) and can be handed the
experts to use (``routing``: ``[routed layers, b, n, k]``): it then weights
them by its own probabilities and reports how far down its own ranking they
reach (``reach``: the least ``p_e / p_(k)`` over the handed experts; 1 where
the sets agree).

**Faults to plant** (``fault``; the benchmark's controls, each of which the
comparison must refuse): ``"plain_rope"`` (global layers by RoPE theta 5e5
without YaRN's table or its attention factor), ``"full_rotation"`` (global
layers turned over all 128 dimensions), ``"no_gate"``, ``"unbounded_window"``,
``"no_scale"`` (``w_e = p_e / sum_S p``), ``"no_shared_expert"``,
``"other_experts"`` (the banks taken for experts ``experts_first +
experts_held`` onwards).  What a fault changes enters each layer as a traced
value (``_knobs``), so that a fault costs no compile of its own but for
``"full_rotation"``, whose shapes differ.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
EXACT = jax.lax.Precision.HIGHEST
#: queries taken at a time against all keys (memory only; every block sees
#: the same keys and mask as the whole sequence would)
Q_BLOCK = 512
FAULTS = ("plain_rope", "full_rotation", "no_gate", "unbounded_window",
          "no_scale", "no_shared_expert", "other_experts")


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


#: the config's ``beta_fast`` and ``beta_slow``: a frequency that turns more
#: than 32 times over the original length is kept, one that turns less than
#: once is divided by the factor
BETA_FAST, BETA_SLOW = 32.0, 1.0


def attention_factor(factor: float) -> float:
    """The config's ``attention_factor``: ``0.1 ln(factor) + 1`` (1.4852 at
    128)."""
    return 0.1 * math.log(factor) + 1.0


def yarn_ramp(theta: float, rot: int, original: int,
              beta_fast: float = BETA_FAST, beta_slow: float = BETA_SLOW):
    """``(low, high)``: the pair a dimension ``beta`` turns make over
    ``original`` positions, rounded outwards and kept in range (9.04 -> 9,
    17.49 -> 18 at theta 5e5, 64 dimensions, 8,192, 32 and 1)."""
    def pair(turns):
        return rot * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    return (max(math.floor(pair(beta_fast)), 0),
            min(math.ceil(pair(beta_slow)), rot - 1))


def frequencies(theta: float, rot: int, yarn=None):
    """``[rot / 2]`` float32: ``theta^(-2d / rot)``, or with ``yarn`` a dict
    (``factor``, ``original``) the module docstring's ``inv_freq_d``."""
    d = jnp.arange(rot // 2, dtype=F32)
    base = 1.0 / theta ** (2.0 * d / rot)
    if yarn is None:
        return base
    low, high = yarn_ramp(theta, rot, yarn["original"])
    ramp = jnp.clip((d - low) / max(high - low, 1e-3), 0.0, 1.0)
    interpolated = base / yarn["factor"]
    return interpolated + (base - interpolated) * (1.0 - ramp)


def rope(x, freq, factor):
    """``x`` ``[..., n, d]`` turned by position 0..n-1 over its first ``2
    len(freq)`` dimensions (rotate-half within them), the rest as they are;
    cosines and sines times ``factor``."""
    n, half = x.shape[-2], freq.shape[0]
    angle = jnp.arange(n, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    lo, hi, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin, rest],
                           -1)


def _attention(p, x, knobs, *, eps, low):
    """Grouped attention of either kind: ``knobs`` holds the rotation
    (``freq``, ``factor``), the window (``window``: keys ``u > t - window``;
    ``n`` or more is global) and ``gate`` (1: the gate, 0: left out).
    Returns ``(out, k, v)`` with ``k`` (rotated) and ``v`` ``[b, kv heads,
    n, dh]``, what a decode cache would hold of the sequence."""
    b, n, _ = x.shape
    h = _rms(x, p["norm"]["scale"], eps)
    a = p["attn"]
    q = _mm("bnd,dhe->bhne", h, a["to_q"]["kernel"], low)
    kv = _mm("bnd,dkge->kbgne", h, a["to_kv"]["kernel"], low)
    q = rope(q, knobs["freq"], knobs["factor"])
    k, v = rope(kv[0], knobs["freq"], knobs["factor"]), kv[1]
    heads, groups = q.shape[1], k.shape[1]
    # query head j reads key head j // (heads / groups)
    k_all = jnp.repeat(k, heads // groups, axis=1)
    v_all = jnp.repeat(v, heads // groups, axis=1)
    scale = q.shape[-1] ** -0.5
    t, u = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    visible = (u <= t) & (u > t - knobs["window"])
    outs = []
    for start in range(0, n, Q_BLOCK):
        rows = slice(start, min(start + Q_BLOCK, n))
        dots = _mm("bhie,bhje->bhij", q[:, :, rows], k_all, low) * scale
        dots = jnp.where(visible[rows][None, None], dots, -jnp.inf)
        outs.append(_mm("bhij,bhje->bhie", jax.nn.softmax(dots, -1), v_all,
                        low))
    o = jnp.concatenate(outs, axis=2)                   # [b, heads, n, dh]
    gate = jax.nn.sigmoid(_mm("bnd,dh->bhn", h, a["to_gate"]["kernel"], low))
    gate = knobs["gate"] * gate + (1.0 - knobs["gate"])
    o = o * gate[..., None]
    o = o.transpose(0, 2, 1, 3).reshape(b, n, -1)
    return _mm("bnf,fd->bnd", o, a["to_out"]["kernel"], low), k, v


def _swiglu(m, gate, up, down, low):
    return _mm("...f,fd->...d",
               jax.nn.silu(_mm("...d,df->...f", m, gate, low))
               * _mm("...d,df->...f", m, up, low), down, low)


def _experts(p, x, routing, knobs, *, eps, k, low):
    """The routed feed-forward on the hidden state after attention; the
    knobs ``scale``, ``shared`` (1: the shared expert, 0: left out) and
    ``first`` (the expert bank 0 holds).  Returns ``(y, top_idx, gap,
    reach, weight)``, ``weight`` ``[b, n, k]`` the weights of the experts
    used, in their order."""
    m = _rms(x, p["norm"]["scale"], eps)
    w = p["moe"]
    held = w["w_gate"].shape[0]
    probs = jax.nn.softmax(_mm("bnd,de->bne", m, w["w_router"], low), -1)
    ranked, top_idx = jax.lax.top_k(probs, k + 1)
    gap = (ranked[..., k - 1] - ranked[..., k]) / ranked[..., k - 1]
    top_idx = top_idx[..., :k]
    chosen = top_idx if routing is None else routing
    picked = jnp.take_along_axis(probs, chosen, -1)            # [b, n, k]
    reach = picked.min(-1) / ranked[..., k - 1]
    weight = knobs["scale"] * picked / picked.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for j in range(held):                  # one held expert at a time
        w_e = jnp.where(chosen == knobs["first"] + j, weight, 0.0).sum(-1)
        y = y + w_e[..., None] * _swiglu(m, w["w_gate"][j], w["w_up"][j],
                                         w["w_down"][j], low)
    y = y + knobs["shared"] * _swiglu(m, w["shared_gate"], w["shared_up"],
                                      w["shared_down"], low)
    return y, top_idx, gap, reach, weight


@functools.partial(jax.jit, static_argnames=("eps", "k", "matmul_dtype"))
def _layer(mixer, ff, x, routing, knobs, *, eps, k, matmul_dtype):
    """One layer on float32 copies of its own parameters; ``ff`` with a
    ``moe`` entry is a routed layer, else the dense SwiGLU."""
    mixer, ff = _f32(mixer), _f32(ff)
    out, keys, values = _attention(mixer, x, knobs, eps=eps,
                                   low=matmul_dtype)
    x = x + out
    if "moe" not in ff:
        m = _rms(x, ff["norm"]["scale"], eps)
        y = _swiglu(m, ff["gate"]["kernel"], ff["up"]["kernel"],
                    ff["down"]["kernel"], matmul_dtype)
        return x + y, keys, values, None
    y, *route = _experts(ff, x, routing, knobs, eps=eps, k=k,
                         low=matmul_dtype)
    return x + y, keys, values, route


def layer_kind(cfg, i: int) -> str:
    """``"global"`` iff ``i mod 4 == 0`` (the configuration's mixer period)."""
    mixers = cfg.trunk.mixers
    return "window" if mixers[i % len(mixers)] == "window" else "global"


def knobs(cfg, i: int, n: int, fault=None, experts_first=None) -> dict:
    """What layer ``i`` is told besides its weights: its rotation, window,
    gate, route scale, shared expert and first held expert, with ``fault``
    planted where it applies."""
    t = cfg.trunk
    dh = cfg.dim_head
    if layer_kind(cfg, i) == "global":
        rot = dh if fault == "full_rotation" else int(
            dh * t.global_rope_fraction)
        yarn = None if fault == "plain_rope" or not t.yarn_factor else dict(
            factor=t.yarn_factor, original=t.yarn_original_len)
        freq = frequencies(float(t.global_rope_theta), rot, yarn)
        factor = attention_factor(t.yarn_factor) if yarn is not None else 1.0
        window = n
    else:
        freq, factor = frequencies(float(t.rope_theta), dh), 1.0
        window = n if fault == "unbounded_window" else t.window
    first = t.experts_first if experts_first is None else experts_first
    if fault == "other_experts":
        first = first + t.held_experts
    return {"freq": freq, "factor": jnp.asarray(factor, F32),
            "window": jnp.asarray(window, jnp.int32),
            "gate": jnp.asarray(0.0 if fault == "no_gate" else 1.0, F32),
            "scale": jnp.asarray(1.0 if fault == "no_scale"
                                 else t.route_scale, F32),
            "shared": jnp.asarray(0.0 if fault == "no_shared_expert"
                                  else 1.0, F32),
            "first": jnp.asarray(first, jnp.int32)}


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
    weights of the experts used, in the order handed) and ``kv``, per layer
    the rotated keys and the values ``[b, kv heads, n, dh]`` a decode cache
    would hold.  ``matmul_dtype``: every layer's matrix products on operands
    rounded to a narrower float (a tolerance's second reading).
    ``routing``: the experts to use.  ``fault``: one of :data:`FAULTS`.
    ``experts_first``: the first expert the banks hold (default: the
    configuration's).  ``depth``: stop after that many layers (what the
    first layers cache and route does not depend on the rest)."""
    assert fault is None or fault in FAULTS, fault
    spec = cfg.trunk
    t_len, fmap = cfg.text_seq_len, cfg.image_fmap_size
    n = t_len + fmap * fmap
    table = _f32(params["table"]["embedding"])
    text = jnp.pad(_text_labels(cfg, text), ((0, 0), (1, 0)))
    split = cfg.num_text_tokens + t_len
    x = jnp.concatenate([table[text], table[codes + split]], axis=1)[:, :n]

    layers = params["transformer"]
    routes, kv = [], []
    for i in range(cfg.depth if depth is None else depth):
        routed = i - spec.dense_layers
        x, keys, values, route = _layer(
            layers[f"layers_{i}_attn"], layers[f"layers_{i}_ff"], x,
            None if routing is None or routed < 0 else routing[routed],
            knobs(cfg, i, n, fault, experts_first), eps=spec.norm_eps,
            k=spec.experts_per_token, matmul_dtype=matmul_dtype)
        kv.append((keys, values))
        if route is not None:
            routes.append(route)
    top_idx, gap, reach, weight = (
        (jnp.stack(r) for r in zip(*routes)) if routes else (None,) * 4)
    return (_rms(x, _f32(params["final_norm"]["scale"]), spec.norm_eps),
            {"top_idx": top_idx, "gap": gap, "reach": reach,
             "weight": weight, "kv": kv})


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
