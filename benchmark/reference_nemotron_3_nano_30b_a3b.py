"""Plain reference of DALL-E over the Nemotron-3-Nano-30B-A3B trunk
(configuration ``nemotron-3-nano-30b-a3b``): the forward pass, the joint
logits, the training loss, each Mamba-2 layer's state after the last position
and what each router decided.

Straightforward ``jax.numpy`` in float32 with exact matmuls
(``Precision.HIGHEST``, under ``jax.default_matmul_precision("highest")``
besides): the whole sequence at once; Mamba-2 as its
per-position recurrence, one sequential ``lax.scan`` over positions (not the
chunked form the program runs, so that the two are independent); no cache, no
batching; a Python loop over the experts held; nothing imported from the
program (``dalle_pytorch_tpu``).  It reads the program's parameter tree by its
names and upcasts it one layer at a time (each layer is its own jitted call).

The trunk follows ``model_type: nemotron_h`` (``modeling_nemotron_h.py``)
with the numbers of
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json.
Every layer is ONE sublayer, ``x += Sublayer(RMSNorm(x))``, as
``hybrid_override_pattern`` says ("M", "E" or "*"); then the final RMSNorm
and an untied head.  With ``m = RMSNorm(x)``, ``H`` heads of ``P``
channels, ``G`` groups, state size ``N``, ``d_in = H P``::

    M:  [z | xBC | dt] = m @ W_in                   # d_in | d_in + 2GN | H
        xBC     = silu(causal_conv(xBC) + b_conv)   # 4 taps, depthwise
        [x | B | C] = xBC                           # [H, P] | [G, N] | [G, N]
        delta   = softplus(dt + b_dt);  A = -exp(A_log)   # per head
        h_t     = exp(delta_t A) h_{t-1} + delta_t x_t B_{g(h),t}^T
        y_t     = h_t C_{g(h),t} + D x_t            # g(h) = h // (H / G)
        out     = GroupRMSNorm_{d_in / G}(y silu(z)) g @ W_out
    E:  sc      = sigmoid(m @ W_r)                  # all n_routed experts
        S       = the k largest of sc + b           # b: the selection bias
        w_e     = routed_scaling_factor sc_e / (sum_S sc + 1e-20)
        out     = sum_{e in S, e held} w_e relu(m @ W_up_e)^2 @ W_down_e
                  + relu(m @ W_up_s)^2 @ W_down_s   # the shared expert
    *:  grouped-query attention, ``heads`` queries over ``kv_heads`` keys
        and values, no bias, no rotation, scale dim_head^-0.5, causal

``RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * gain``; ``GroupRMSNorm`` the same
over each group of ``d_in / G`` channels.

**The share.**  ``experts_first`` and ``experts_held`` (default: the
configuration's) say which experts' banks the parameters hold: bank ``j`` is
expert ``experts_first + j``.  The router scores all experts; what a chosen
expert that is not held would have added is left out.  With every bank held
the layer is the uncut one.

**Routing** (as ``reference_glm_4_7_flash``): :func:`hidden` reports each
expert layer's own choices (``top_idx``) and can be handed the experts to
use (``routing``, ``[expert layers, b, n, k]``): it then weights them by its
own scores and reports how far down its own ranking of ``sc + b`` the handed
set reaches (``reach``: the least ``(sc + b)_e / (sc + b)_(k)`` over the
handed experts; 1 where the sets agree).

Departures from ``modeling_nemotron_h.py``, all DALL-E's client or this
repo's (``benchmark/configs/nemotron-3-nano-30b-a3b.json``, ``assumed``):
the joint vocabulary (text ids, one pad id a text position, image codes;
``<bos>`` is id 0), DALL-E's learned text and axial image position
embeddings added before the trunk, its phase mask and loss; the group norm's
product with its gain taken in float32 (the published one rounds the normed
value to the activation dtype first); the router's product in float32 here
(the program's takes bfloat16 operands with float32 sums); seeded weights,
``b_conv``, ``D``, the norm's gain and the selection bias drawn rather than
loaded; layers 9-51 and experts 16-127 not held.

The program's names: ``layers_i_ssd/ssd``: ``in_proj/kernel`` ``[dim, d_in
+ d_in + 2GN + H]``, ``conv_kernel`` ``[4, d_in + 2GN]`` (the last tap meets
the current position), ``conv_bias``, ``dt_bias``, ``A_log``, ``D`` ``[H]``,
``norm_gain`` ``[d_in]``, ``out_proj/kernel`` ``[d_in, dim]``;
``layers_i_ff/moe``: ``w_router`` ``[dim, experts]``, ``router_bias``,
``w_up`` ``[held, dim, width]``, ``w_down`` ``[held, width, dim]``,
``shared_up`` / ``shared_down``; ``layers_i_attn/attn``: ``to_q`` ``[dim,
heads, dh]``, ``to_kv`` ``[dim, 2, kv_heads, dh]``, ``to_out`` ``[heads dh,
dim]``; each layer's ``norm/scale``; ``table/embedding`` and ``head``
``[vocabulary, dim]``.

**Faults to plant** (``fault``; the controls, each of which a comparison
must refuse): ``"group_mod"`` (head ``h`` reads group ``h % G``),
``"whole_norm"`` (the gated norm over all ``d_in`` channels),
``"relu"`` (relu for relu^2), ``"no_route_scale"``, ``"bias_in_weights"``
(``w_e`` from ``sc + b``), ``"no_shared_expert"``, ``"no_conv_bias"``,
``"other_experts"`` (the banks taken for experts ``experts_first +
experts_held`` onwards); and ``state_dtype`` (the state rounded to a
narrower float after every update).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
EXACT = jax.lax.Precision.HIGHEST
FAULTS = ("group_mod", "whole_norm", "relu", "no_route_scale",
          "bias_in_weights", "no_shared_expert", "no_conv_bias",
          "other_experts")
#: the layers each fault acts in (the others are compiled without it)
_FAULTS_OF = {"mamba2": ("group_mod", "whole_norm", "no_conv_bias"),
              "none": ("relu", "no_route_scale", "bias_in_weights",
                       "no_shared_expert", "other_experts")}


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


def rule(x, B, C, delta, A, state_dtype=F32, group_of=None):
    """Mamba-2's recurrence, one position at a time: ``x`` ``[b, n, H, P]``,
    ``B`` and ``C`` ``[b, n, G, N]``, ``delta`` ``[b, n, H]``, ``A`` ``[H]``.
    ``group_of`` ``[H]``: the group each head reads (default ``h // (H /
    G)``).  The state starts at zero and is held in ``state_dtype`` (rounded
    after every update).  Returns ``(y [b, n, H, P] without D, h [b, H, P,
    N] after the last position)``, both float32."""
    b, n, H, P = x.shape
    G, N = B.shape[2:]
    if group_of is None:
        group_of = jnp.arange(H) // (H // G)
    x, B, C, delta = (jnp.asarray(a, F32) for a in (x, B, C, delta))
    B, C = B[:, :, group_of], C[:, :, group_of]         # [b, n, H, N]

    def step(h, at):
        x_t, B_t, C_t, d_t = at
        h = (jnp.exp(d_t * A)[..., None, None] * h.astype(F32)
             + (d_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        h = h.astype(state_dtype)
        y_t = jnp.einsum("bhpn,bhn->bhp", h.astype(F32), C_t,
                         precision=EXACT)
        return h, y_t

    h0 = jnp.zeros((b, H, P, N), state_dtype)
    h, y = jax.lax.scan(step, h0, tuple(jnp.swapaxes(a, 0, 1)
                                        for a in (x, B, C, delta)))
    return jnp.swapaxes(y, 0, 1), h.astype(F32)


def _mamba2(p, x, *, eps, groups, state, state_dtype, low, fault):
    """The Mamba-2 sublayer.  Returns ``(out, h)``, ``h`` the state after
    the last position ``[b, H, P, N]``."""
    b, n, _ = x.shape
    m = _rms(x, p["norm"]["scale"], eps)
    s = p["ssd"]
    H = s["A_log"].shape[0]
    d_in = s["norm_gain"].shape[0]
    P, GN = d_in // H, groups * state
    zxbcdt = _mm("bnd,de->bne", m, s["in_proj"]["kernel"], low)
    z, xbc, dt = (zxbcdt[..., :d_in], zxbcdt[..., d_in:2 * d_in + 2 * GN],
                  zxbcdt[..., 2 * d_in + 2 * GN:])
    width = s["conv_kernel"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    xbc = sum(padded[:, k:k + n] * s["conv_kernel"][k] for k in range(width))
    if fault != "no_conv_bias":
        xbc = xbc + s["conv_bias"]
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :d_in].reshape(b, n, H, P)
    B = xbc[..., d_in:d_in + GN].reshape(b, n, groups, state)
    C = xbc[..., d_in + GN:].reshape(b, n, groups, state)
    delta = jax.nn.softplus(dt + s["dt_bias"])
    group_of = (jnp.arange(H) % groups if fault == "group_mod" else None)
    y, h = rule(xs, B, C, delta, -jnp.exp(s["A_log"]), state_dtype,
                group_of)
    y = (y + s["D"][:, None] * xs).reshape(b, n, d_in) * jax.nn.silu(z)
    if fault == "whole_norm":
        y = _rms(y, 1.0, eps)
    else:
        y = _rms(y.reshape(b, n, groups, d_in // groups), 1.0,
                 eps).reshape(b, n, d_in)
    return _mm("bnd,de->bne", y * s["norm_gain"], s["out_proj"]["kernel"],
               low), h


def _relu2(x, fault):
    return jax.nn.relu(x) if fault == "relu" else jnp.square(jax.nn.relu(x))


def _experts(p, x, *, eps, k, scale, first, routing, low, fault):
    """The expert sublayer.  Returns ``(out, top_idx, reach, weight)``,
    ``weight`` ``[b, n, k]`` the weights of the experts used, in their
    order."""
    m = _rms(x, p["norm"]["scale"], eps)
    w = p["moe"]
    held = w["w_up"].shape[0]
    if fault == "other_experts":
        first = first + held
    sc = jax.nn.sigmoid(_mm("bnd,de->bne", m, w["w_router"], low))
    sel = sc + w["router_bias"]
    ranked, top_idx = jax.lax.top_k(sel, k)
    chosen = top_idx if routing is None else routing
    reach = jnp.take_along_axis(sel, chosen, -1).min(-1) / ranked[..., -1]
    picked = jnp.take_along_axis(
        sel if fault == "bias_in_weights" else sc, chosen, -1)   # [b, n, k]
    weight = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    if fault != "no_route_scale":
        weight = scale * weight
    y = jnp.zeros_like(x)
    for j in range(held):                  # one held expert at a time
        w_e = jnp.where(chosen == first + j, weight, 0.0).sum(-1)  # [b, n]
        hid = _relu2(_mm("bnd,df->bnf", m, w["w_up"][j], low), fault)
        y = y + w_e[..., None] * _mm("bnf,fd->bnd", hid, w["w_down"][j], low)
    if fault != "no_shared_expert":
        hid = _relu2(_mm("bnd,df->bnf", m, w["shared_up"], low), fault)
        y = y + _mm("bnf,fd->bnd", hid, w["shared_down"], low)
    return y, top_idx, reach, weight


def _attention(p, x, *, eps, dim_head, low):
    b, n, _ = x.shape
    m = _rms(x, p["norm"]["scale"], eps)
    a = p["attn"]
    q = _mm("bnd,dhe->bhne", m, a["to_q"]["kernel"], low)
    kv = _mm("bnd,dkge->kbgne", m, a["to_kv"]["kernel"], low)
    heads, groups = q.shape[1], kv.shape[2]
    # each key/value head serves heads / groups query heads, in order
    key = jnp.repeat(kv[0], heads // groups, axis=1)
    value = jnp.repeat(kv[1], heads // groups, axis=1)
    dots = _mm("bhie,bhje->bhij", q * dim_head ** -0.5, key, low)
    causal = jnp.tril(jnp.ones((n, n), bool))
    dots = jnp.where(causal[None, None], dots, -jnp.inf)
    out = _mm("bhij,bhje->bhie", jax.nn.softmax(dots, -1), value, low)
    out = out.transpose(0, 2, 1, 3).reshape(b, n, -1)
    return _mm("bne,ed->bnd", out, a["to_out"]["kernel"], low)


@functools.partial(jax.jit, static_argnames=(
    "kind", "eps", "dim_head", "groups", "state", "k", "scale", "first",
    "state_dtype", "matmul_dtype", "fault"))
def _layer(p, x, routing, *, kind, eps, dim_head, groups, state, k, scale,
           first, state_dtype, matmul_dtype, fault):
    """One layer on float32 copies of its own parameters: ``(x', extra)``,
    ``extra`` the Mamba-2 state, the routing record, or None."""
    p = _f32(p)
    if kind == "mamba2":
        out, h = _mamba2(p, x, eps=eps, groups=groups, state=state,
                         state_dtype=state_dtype, low=matmul_dtype,
                         fault=fault)
        return x + out, h
    if kind == "none":
        out, *route = _experts(p, x, eps=eps, k=k, scale=scale, first=first,
                               routing=routing, low=matmul_dtype, fault=fault)
        return x + out, route
    return x + _attention(p, x, eps=eps, dim_head=dim_head,
                          low=matmul_dtype), None


def _text_labels(cfg, text):
    return jnp.where(text == 0,
                     cfg.num_text_tokens + jnp.arange(cfg.text_seq_len), text)


def hidden(params, cfg, text, codes, matmul_dtype=None, routing=None,
           fault=None, state_dtype=F32, experts_first=None):
    """``(h, extras)``: ``h`` ``[b, n, dim]`` float32 after the final norm,
    at the ``n = text_seq_len + image_seq_len`` input positions ``[bos, text,
    codes[:-1]]`` (teacher forcing); ``extras`` a dict of ``states``
    (``[Mamba-2 layers, b, H, P, N]``: each layer's state after the last
    position), ``top_idx`` ``[expert layers, b, n, k]``, ``reach``
    ``[expert layers, b, n]`` (module docstring) and ``weight`` ``[expert
    layers, b, n, k]`` (the weights of the experts used, in the order
    handed).  ``matmul_dtype``: every
    layer's matrix products on operands rounded to a narrower float (a
    tolerance's second reading).  ``routing``: the experts to use.
    ``fault``: one of :data:`FAULTS`.  ``state_dtype``: the Mamba-2 state's
    precision.  ``experts_first``: the first expert the banks hold (default:
    the configuration's)."""
    assert fault is None or fault in FAULTS, fault
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
    with jax.default_matmul_precision("highest"):
        x, states, routes = _layers(layers, cfg, x, routing, matmul_dtype,
                                    fault, state_dtype, experts_first)
    top_idx, reach, weight = ((jnp.stack(r) for r in zip(*routes))
                              if routes else (None,) * 3)
    return (_rms(x, _f32(params["final_norm"]["scale"]), spec.norm_eps),
            {"states": jnp.stack(states) if states else None,
             "top_idx": top_idx, "reach": reach, "weight": weight})


def _layers(layers, cfg, x, routing, matmul_dtype, fault, state_dtype,
            experts_first):
    """Every layer in turn: ``(x, states, routes)``."""
    spec = cfg.trunk
    states, routes = [], []
    for i in range(cfg.depth):
        kind = spec.mixers[i % len(spec.mixers)]
        name = {"mamba2": "ssd", "none": "ff"}.get(kind, "attn")
        x, extra = _layer(
            layers[f"layers_{i}_{name}"], x,
            None if routing is None or kind != "none"
            else routing[len(routes)],
            kind=kind, eps=spec.norm_eps, dim_head=cfg.dim_head,
            groups=spec.ssd_groups, state=spec.ssm_state,
            k=spec.experts_per_token, scale=float(spec.route_scale),
            first=int(spec.experts_first if experts_first is None
                      else experts_first),
            state_dtype=state_dtype, matmul_dtype=matmul_dtype,
            fault=fault if fault in _FAULTS_OF.get(kind, ()) else None)
        if kind == "mamba2":
            states.append(extra)
        elif kind == "none":
            routes.append(extra)
    return x, states, routes


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
    phase mask); ``extras`` as :func:`hidden` gives them."""
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
