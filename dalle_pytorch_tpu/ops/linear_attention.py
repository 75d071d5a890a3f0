"""Linear attention with a matrix-valued state: the gated delta rule and the
mixer layer built on it.

A head keeps a state ``S`` ``[d_k, d_v]`` (float32, zero before the first
position) and at position t, with a unit-length key ``k_t``, a query ``q_t``,
a value ``v_t``, a log-decay ``g_t <= 0`` and a write strength ``beta_t`` in
(0, 2)::

    S   = exp(g_t) * S_{t-1}
    u_t = beta_t * (v_t - S^T k_t)          # what the key should read, less
    S_t = S + k_t u_t^T                     #   what it reads: the delta rule
    o_t = S_t^T q_t

in the two forms a decoder needs: one position against a carried state
(``gated_delta_step``; the decode tick) and over a sequence, ``chunk``
positions at a time (``gated_delta_rule``; differentiable, used by the
forward pass, the prefill and training).  The state, the decays and every
sum are float32 whatever the activation dtype, and the sequence form's
matrix products run at ``Precision.HIGHEST`` (on a TPU a float32 product
otherwise rounds its operands to bfloat16, the state among them).

Layout: a carried state is ``[b, heads / f, d_k, f * d_v]``: ``f`` heads'
``d_v`` columns side by side on the minor axis (:func:`state_fold`: 2 at
``d_v`` 192, 384 = 3 x 128 lanes, and ``d_k`` 96 = 12 x 8 sublanes), so that
the TPU's tiled layout pads nothing.  A head a matrix, ``[b, heads, d_k,
d_v]``, puts 192 on the lanes and pads it to 256: a third more bytes on the
one tensor the tick is built around (0.83 ms a layer a tick at 64 rows
against 0.64; every head side by side, ``[b, d_k, heads * d_v]``, pads
nothing either, but the compiler then writes the keys and queries out once
per lane, 2.86 ms: PERF.md, Findings).

Which form of the step runs where: where :func:`one_pass_step` admits the
state (float32, ``d_k`` whole sublane tiles, ``f * d_v`` whole lane tiles)
and the program is lowered for a TPU, one Pallas kernel
(ops/linear_attention_pallas.py, kept beside the compile cache,
ops/kept.py) reads each block of the state once and writes it once, in
place; everywhere else (the CPU, a narrow or bfloat16 state) the plain
``jnp`` step, elementwise on the carried layout, a head's ``k`` and ``q``
spread over its own lanes by a select, which XLA runs as two passes: one for
both read-outs (``S^T k`` and ``S^T q``; the written state's read-out is
``S_t^T q = S^T q + u (k . q)``), one to write.  The sequence form is plain
``jnp``/``lax`` everywhere.

``GatedDeltaMixer`` is the layer (Gated DeltaNet: Yang, Kautz, Hatamizadeh
2024) as the Olmo-Hybrid family's ``linear_*`` keys size it.  Its scopes,
none nested in another: ``gdn-proj`` (the wide projections and the two
per-head ones), ``gdn-conv`` (the convolution and its activation),
``gdn-state`` (the l2 norms, ``beta``, the decay, the update, the read-out
and the gated norm).
"""
from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..obs import prof
from . import kept
from .ssm import (F32, causal_conv, causal_conv_step, dt_bias_init,
                  fan_in_normal, rms_norm)

EXACT = jax.lax.Precision.HIGHEST

#: minor dimension of the TPU's tiled layouts (a vector register's lanes)
LANES = 128

#: sublanes of a float32 tile
SUBLANES = 8

#: positions a chunk of the sequence form solves at once: the triangular
#: system is ``[chunk, chunk]`` a head and the work inside a chunk is matrix
#: products; the state is carried from chunk to chunk by a ``lax.scan``.
DELTA_CHUNK = 64


def l2_norm(x, eps: float):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def state_fold(heads: int, value_dim: int) -> int:
    """How many heads share the minor axis of a carried state: the fewest
    whose ``d_v`` columns fill whole lane tiles, or 1 (a head a matrix,
    padded to the lanes) where the head count has no such divisor."""
    fold = LANES // math.gcd(value_dim, LANES)
    return fold if heads % fold == 0 else 1


def fold_state(S):
    """``[b, heads, d_k, d_v]`` -> the carried ``[b, heads / f, d_k, f *
    d_v]``."""
    b, h, dk, dv = S.shape
    f = state_fold(h, dv)
    return S.reshape(b, h // f, f, dk, dv).transpose(0, 1, 3, 2, 4).reshape(
        b, h // f, dk, f * dv)


def unfold_state(S, heads: int):
    """The carried ``[b, heads / f, d_k, f * d_v]`` -> ``[b, heads, d_k,
    d_v]``."""
    b, groups, dk, lanes = S.shape
    f = heads // groups
    return S.reshape(b, groups, dk, f, lanes // f).transpose(
        0, 1, 3, 2, 4).reshape(b, heads, dk, lanes // f)


def one_pass_step(heads: int, key_dim: int, value_dim: int, dtype) -> bool:
    """Whether the decode tick's update of a carried state takes one pass
    over it (the Pallas kernel, where the program is lowered for a TPU),
    from static shapes and dtypes alone: a float32 state, ``d_k`` in whole
    sublane tiles and the carried minor axis, ``f * d_v``
    (:func:`state_fold`), in whole lane tiles.  A bfloat16 state, a narrow
    twin or a head count that folds nothing keep the ``jnp`` step."""
    return (jnp.dtype(dtype) == jnp.float32 and key_dim % SUBLANES == 0
            and state_fold(heads, value_dim) * value_dim % LANES == 0)


def gated_delta_step(S, q, k, v, g, beta):
    """One position of the rule.  ``S`` ``[b, heads / f, d_k, f * d_v]``
    float32 (:func:`fold_state`), ``q`` and ``k`` ``[b, heads, d_k]``, ``v``
    ``[b, heads, d_v]``, ``g`` and ``beta`` ``[b, heads]``.  Returns ``(o
    [b, heads, d_v] float32, S')``: in one pass over ``S`` where
    :func:`one_pass_step` holds and the program is lowered for a TPU
    (``jax.lax.platform_dependent``), else by :func:`_plain_step`."""
    if not one_pass_step(k.shape[1], k.shape[2], v.shape[-1], S.dtype):
        return _plain_step(S, q, k, v, g, beta)

    def kernel(*args):
        return kept.kernel("linear_attention_pallas", "delta_step", *args,
                           salt=(SUBLANES,))

    return jax.lax.platform_dependent(S, q, k, v, g, beta, tpu=kernel,
                                      default=_plain_step)


def _plain_step(S, q, k, v, g, beta):
    """:func:`gated_delta_step` in plain ``jnp``, elementwise on the carried
    layout (module docstring)."""
    b, h, _ = k.shape
    dv = v.shape[-1]
    groups = S.shape[1]
    f = h // groups
    q, k, v = q.astype(F32), k.astype(F32), v.astype(F32)
    lane_head = jnp.arange(f * dv) // dv        # whose lanes: [f d_v]

    def lanes(a):
        """``[b, heads, ...]`` -> ``[b, heads / f, ..., f d_v]``: each
        head's value over its own lanes (selects, which fuse into the pass
        over the state; a reshape of a broadcast would be written out)."""
        a = a.reshape((b, groups, f) + a.shape[2:])
        out = a[:, :, 0][..., None]
        for i in range(1, f):
            out = jnp.where(lane_head == i, a[:, :, i][..., None], out)
        return out

    k_l, q_l = lanes(k), lanes(q)                       # [b, h/f, d_k, f d_v]
    alpha = lanes(jnp.exp(g.astype(F32)))               # [b, h/f, f d_v]
    read_k = alpha * jnp.sum(S * k_l, axis=2)           # (alpha S)^T k
    read_q = alpha * jnp.sum(S * q_l, axis=2)
    u = lanes(beta.astype(F32)) * (v.reshape(b, groups, f * dv) - read_k)
    o = read_q + u * lanes(jnp.sum(k * q, -1))
    S = alpha[:, :, None] * S + k_l * u[:, :, None]
    return o.reshape(b, h, dv), S


def gated_delta_rule(q, k, v, g, beta, S0=None, chunk: int = DELTA_CHUNK):
    """The rule over a sequence, ``chunk`` positions at a time.

    ``q`` and ``k`` ``[b, n, heads, d_k]``, ``v`` ``[b, n, heads, d_v]``,
    ``g`` and ``beta`` ``[b, n, heads]``, ``S0`` the carried ``[b, heads /
    f, d_k, f * d_v]`` (zeros when None).  Inside a chunk, with ``G_t`` the running sum of
    ``g`` and ``D[t, i] = exp(G_t - G_i)`` for ``i <= t``, the writes ``U``
    solve the unit lower-triangular system (the WY / UT transform) ::

        (I + tril(beta_t D[t, i] k_t . k_i, -1)) U
            = beta V - (beta exp(G) K) S

    and then ``O = exp(G) Q S + (D * Q K^T) U``, ``S' = exp(G_c) S + (K
    exp(G_c - G))^T U``: matrix products and one triangular solve a chunk,
    every decay a ratio ``<= 1``.  A sequence that the chunk does not divide
    is padded with ``g = 0``, ``beta = 0`` (decay 1, nothing written), which
    leaves the state as it was.  Returns ``(o [b, n, heads, d_v] float32,
    S_n)`` in the carried layout."""
    b, n, h, dk = k.shape
    dv = v.shape[-1]
    S = (jnp.zeros((b, h, dk, dv), F32) if S0 is None
         else unfold_state(S0.astype(F32), h))
    chunk = min(chunk, n)
    pad = -n % chunk

    def chunks(x):  # [b, n, heads, ...] -> [n / chunk, b, heads, chunk, ...]
        x = jnp.pad(x.astype(F32),
                    ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, (n + pad) // chunk, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

    def mm(spec, x, y):
        return jnp.einsum(spec, x, y, precision=EXACT,
                          preferred_element_type=F32)

    def one_chunk(S, part):
        q, k, v, g, beta = part       # [b, h, c, d], [b, h, c]
        G = jnp.cumsum(g, axis=-1)
        diff = G[..., :, None] - G[..., None, :]            # [b, h, t, i]
        decay = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
        gamma = jnp.exp(G)[..., None]                       # [b, h, c, 1]
        system = jnp.where(strict, beta[..., None] * decay
                           * mm("bhtd,bhid->bhti", k, k), 0.0)
        rhs = jnp.concatenate([beta[..., None] * v,
                               beta[..., None] * gamma * k], axis=-1)
        solved = jax.lax.linalg.triangular_solve(
            system, rhs, left_side=True, lower=True, unit_diagonal=True)
        U = solved[..., :dv] - mm("bhtd,bhde->bhte", solved[..., dv:], S)
        o = (gamma * mm("bhtd,bhde->bhte", q, S)
             + mm("bhti,bhie->bhte", decay * mm("bhtd,bhid->bhti", q, k), U))
        tail = jnp.exp(G[..., -1:] - G)[..., None]          # exp(G_c - G_i)
        S = gamma[..., -1:, :] * S + mm("bhid,bhie->bhde", k * tail, U)
        return S, o

    S, o = jax.lax.scan(one_chunk, S, tuple(map(chunks, (q, k, v, g, beta))))
    # [n / chunk, b, heads, chunk, d_v] -> [b, n, heads, d_v]
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n + pad, dv)[:, :, :n]
    return o.transpose(0, 2, 1, 3), fold_state(S)


def _decay_rate_init(key, shape, dtype=F32):
    """``A_log`` such that ``exp(A_log)`` is uniform in (0, 16]."""
    return jnp.log(16.0 * (1.0 - jax.random.uniform(key, shape, F32))
                   ).astype(dtype)


class GatedDeltaMixer(nn.Module):
    """The gated-delta-rule mixer: ``q, k, v = silu(conv(W_q x)),
    silu(conv(W_k x)), silu(conv(W_v x))`` (causal depthwise convolutions, no
    bias); ``q, k`` l2-normed a head, ``q`` scaled by ``d_k^-0.5``; ``beta =
    2 sigmoid(W_b x)``; ``g = -exp(A_log) softplus(W_a x + dt_bias)``; the
    gated delta rule; ``y = RMSNorm(o) silu(W_g x)`` with one gain of ``d_v``
    shared by the heads; out ``= W_o y``.

    Matrices and convolution kernels are stored in ``param_dtype``;
    ``A_log``, ``dt_bias`` and the norm's gain in float32.  The three
    convolutions run as one over the ``heads * (2 d_k + d_v)`` concatenated
    channels (q, then k, then v), so the decode state of a layer is two
    leaves, as a Mamba layer's: ``(window [b, conv - 1, heads * (2 d_k +
    d_v)] in the activation dtype, S [b, heads / f, d_k, f * d_v] float32)``
    (:func:`fold_state`)."""

    dim: int
    heads: int
    key_dim: int
    value_dim: int
    conv: int = 4
    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def setup(self):
        h, dk, dv = self.heads, self.key_dim, self.value_dim

        def dense(features, name):
            # [dim, heads, d]: tensor parallelism splits the heads axis
            return nn.DenseGeneral(
                features, use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=fan_in_normal(self.dim), name=name)

        def taps(width, name):
            return self.param(name, fan_in_normal(self.conv),
                              (self.conv, h, width), self.param_dtype)

        self.q_proj = dense((h, dk), "q_proj")
        self.k_proj = dense((h, dk), "k_proj")
        self.v_proj = dense((h, dv), "v_proj")
        self.g_proj = dense((h, dv), "g_proj")
        self.a_proj = dense(h, "a_proj")
        self.b_proj = dense(h, "b_proj")
        self.conv_q = taps(dk, "conv_q")
        self.conv_k = taps(dk, "conv_k")
        self.conv_v = taps(dv, "conv_v")
        self.A_log = self.param("A_log", _decay_rate_init, (h,), F32)
        self.dt_bias = self.param("dt_bias", dt_bias_init, (h,), F32)
        self.o_norm = self.param("o_norm", nn.initializers.ones, (dv,), F32)
        self.o_proj = nn.DenseGeneral(
            self.dim, axis=(-2, -1), use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=fan_in_normal(h * dv), name="o_proj")

    def _projected(self, x):
        """``(qkv [..., channels] float32, gate [..., heads, d_v], a, b
        [..., heads])`` of the block's input: the convolution's channels
        (q, k, v side by side), the output gate and the two per-head
        projections."""
        flat = lambda a: a.reshape(a.shape[:-2] + (-1,))  # noqa: E731
        qkv = jnp.concatenate([flat(self.q_proj(x)), flat(self.k_proj(x)),
                               flat(self.v_proj(x))], axis=-1)
        return qkv.astype(F32), self.g_proj(x), self.a_proj(x), self.b_proj(x)

    def _taps(self):
        """The one depthwise kernel ``[conv, channels]``, float32."""
        flat = lambda a: a.reshape(self.conv, -1)  # noqa: E731
        return jnp.concatenate([flat(self.conv_q), flat(self.conv_k),
                                flat(self.conv_v)], axis=-1).astype(F32)

    def _rule_inputs(self, qkv, a, b):
        """``(q, k, v, g, beta)`` of the activated convolution outputs
        ``qkv`` ``[..., channels]`` and the per-head projections."""
        h, dk, dv = self.heads, self.key_dim, self.value_dim
        heads = lambda a, d: a.reshape(a.shape[:-1] + (h, d))  # noqa: E731
        q = l2_norm(heads(qkv[..., :h * dk], dk), self.eps) * dk ** -0.5
        k = l2_norm(heads(qkv[..., h * dk:2 * h * dk], dk), self.eps)
        v = heads(qkv[..., 2 * h * dk:], dv)
        beta = 2.0 * jax.nn.sigmoid(b.astype(F32))
        g = -jnp.exp(self.A_log) * jax.nn.softplus(a.astype(F32)
                                                   + self.dt_bias)
        # what the rule is given, for the benchmark's comparison of the
        # state it leaves (a no-op unless "intermediates" is mutable)
        self.sow("intermediates", "rule_inputs", (q, k, v, g, beta))
        return q, k, v, g, beta

    def _gated_out(self, o, gate):
        """``W_o(RMSNorm(o) silu(gate))`` of the rule's read-out ``o``
        ``[..., heads, d_v]``."""
        with prof.scope("gdn-state"):
            y = rms_norm(o, self.o_norm, self.eps) * jax.nn.silu(
                gate.astype(F32))
        with prof.scope("gdn-proj"):
            return self.o_proj(y.astype(self.dtype))

    def __call__(self, x, return_state: bool = False):
        """``x`` ``[b, n, dim]`` from a zero state.  With ``return_state``
        also the decode state after the last position."""
        with prof.scope("gdn-proj"):
            qkv, gate, a, b = self._projected(x)
        with prof.scope("gdn-conv"):
            qkv, window = causal_conv(qkv, self._taps(), 0.0)
            qkv = jax.nn.silu(qkv)
        with prof.scope("gdn-state"):
            o, S = gated_delta_rule(*self._rule_inputs(qkv, a, b))
        out = self._gated_out(o, gate)
        return (out, (window.astype(self.dtype), S)) if return_state else out

    def decode_step(self, x, window, S):
        """``x`` ``[b, 1, dim]`` against the carried ``(window, S)``.
        Returns ``(out [b, 1, dim], window', S')``."""
        with prof.scope("gdn-proj"):
            qkv, gate, a, b = self._projected(x[:, 0])
        with prof.scope("gdn-conv"):
            qkv, window = causal_conv_step(qkv, self._taps(), 0.0, window)
            qkv = jax.nn.silu(qkv)
        with prof.scope("gdn-state"):
            q, k, v, g, beta = self._rule_inputs(qkv, a, b)
            o, S = gated_delta_step(S, q, k, v, g, beta)
        return self._gated_out(o, gate)[:, None], window, S

    def init_state(self, batch: int):
        """A zero decode state for ``batch`` rows."""
        h, dk, dv = self.heads, self.key_dim, self.value_dim
        f = state_fold(h, dv)
        return (jnp.zeros((batch, self.conv - 1, h * (2 * dk + dv)),
                          self.dtype),
                jnp.zeros((batch, h // f, dk, f * dv), F32))
