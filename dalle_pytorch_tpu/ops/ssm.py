"""State-space (Mamba-1 and Mamba-2) operators and the mixer layers built on
them.

Three operators, each in the two forms a decoder needs:

* the causal depthwise convolution: over a sequence (``causal_conv``) and one
  position against a rolling window of the last ``width - 1`` inputs
  (``causal_conv_step``);
* the selective scan ``h_t = exp(delta_t A) h_{t-1} + (delta_t u_t) B_t^T``,
  ``y_t = h_t C_t``: over a sequence in chunks, the state carried from chunk
  to chunk (``selective_scan``; differentiable, used by the forward pass,
  the prefill and training), and one step against a carried state
  (``selective_scan_step``; the decode tick);
* Mamba-2's recurrence with one scalar decay a head, ``h_t = exp(delta_t
  A) h_{t-1} + delta_t x_t B_t^T``, ``y_t = h_t C_t``, each head's ``[P,
  N]`` state reading the ``B`` and ``C`` of its group: over a sequence in
  the chunked matrix form of state-space duality (``ssd``), and one step
  (``ssd_step``).

Plain ``jnp``/``lax``: no kernel here.  The state, ``delta``, ``A`` and the
exponentials are float32 whatever the activation dtype.

Layout: the channel axis ``d_in`` is minor in every carried leaf of a
Mamba-1 layer (the state is ``[b, N, d_in]``, the window ``[b, width - 1,
d_in]``), so that the row-major layout fills the TPU's 128 lanes with
channels and nothing is padded: ``N`` is 16 and the window 3 deep.  A
Mamba-2 layer's state is ``[b, H, P, N]`` with ``N`` minor (128 at
``nemotron-3-nano-30b-a3b``: the lanes are filled).

``MambaMixer`` is the layer of the Jamba family (Mamba-1 with an RMSNorm on
each of ``dt``, ``B`` and ``C``).  Its scopes, none nested in another:
``ssm-proj`` (the two wide projections), ``ssm-conv`` (the convolution and
its activation), ``ssm-scan`` (the recurrent update: the two small
projections, the norms, ``delta``, the scan, the read-out and the gate).
``Mamba2Mixer`` is Nemotron-H's Mamba-2 layer; its scopes are ``ssd-proj``
(the projections in and out), ``ssd-conv`` (the convolution of ``[x, B,
C]`` and its activation) and ``ssd-state`` (``delta``, the decays, the
state's update and read-out, ``D``, the gate and the grouped norm).
"""
from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..obs import prof

F32 = jnp.float32

#: positions a chunk of the sequence form scans at once: the associative scan
#: holds ``[b, chunk, N, d_in]`` float32 decays and inputs, 2 x 21 MB a row at
#: d_in 5120, so the prompt pass of one row stays small.
SCAN_CHUNK = 64

#: the highest precision, for the chunked form's products of float32
#: operands: at the default a TPU multiplies them in one bfloat16 pass
EXACT = jax.lax.Precision.HIGHEST


def rms_norm(x, gain, eps: float):
    """``x * rsqrt(mean(x^2) + eps) * gain`` in float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def causal_conv(u, kernel, bias, window=None):
    """Causal depthwise convolution over a sequence.

    ``u`` ``[b, n, d_in]``, ``kernel`` ``[width, d_in]`` (tap ``width - 1``
    meets the current position), ``bias`` ``[d_in]``; ``window`` ``[b,
    width - 1, d_in]`` holds the inputs before position 0 (zeros when None).
    Returns ``(out [b, n, d_in], window')`` with ``window'`` the last
    ``width - 1`` inputs."""
    width = kernel.shape[0]
    if window is None:
        window = jnp.zeros((u.shape[0], width - 1, u.shape[2]), u.dtype)
    full = jnp.concatenate([window.astype(u.dtype), u], axis=1)
    n = u.shape[1]
    out = sum(full[:, k:k + n] * kernel[k] for k in range(width)) + bias
    return out, full[:, n:]


def causal_conv_step(u, kernel, bias, window):
    """One position of :func:`causal_conv`: ``u`` ``[b, d_in]`` against the
    window of the ``width - 1`` inputs before it.  Returns ``(out [b, d_in],
    window')``."""
    full = jnp.concatenate([window, u[:, None].astype(window.dtype)], axis=1)
    out = jnp.sum(full.astype(u.dtype) * kernel, axis=1) + bias
    return out, full[:, 1:]


def selective_scan_step(h, u, delta, A, B, C):
    """One step of the recurrence.  ``h`` ``[b, N, d_in]`` f32, ``u`` and
    ``delta`` ``[b, d_in]``, ``A`` ``[N, d_in]``, ``B`` and ``C`` ``[b, N]``.
    Returns ``(y [b, d_in] f32, h')``."""
    u, delta = u.astype(F32), delta.astype(F32)
    B, C = B.astype(F32), C.astype(F32)
    h = (jnp.exp(delta[:, None] * A) * h
         + (delta * u)[:, None] * B[:, :, None])
    return jnp.sum(h * C[:, :, None], axis=1), h


def selective_scan(u, delta, A, B, C, h0=None, chunk: int = SCAN_CHUNK):
    """The recurrence over a sequence, ``chunk`` positions at a time.

    ``u`` and ``delta`` ``[b, n, d_in]``, ``A`` ``[N, d_in]``, ``B`` and
    ``C`` ``[b, n, N]``, ``h0`` ``[b, N, d_in]`` (zeros when None).  Inside a
    chunk the linear recurrence is an associative scan over ``(decay,
    input)`` pairs; a ``lax.scan`` carries the state from chunk to chunk.  A
    sequence that the chunk does not divide is padded with ``delta = 0``
    (decay 1, input 0), which leaves the state as it was.  Returns ``(y
    [b, n, d_in] f32, h_n)``."""
    b, n, d_in = u.shape
    N = A.shape[0]
    u, delta = u.astype(F32), delta.astype(F32)
    B, C = B.astype(F32), C.astype(F32)
    if h0 is None:
        h0 = jnp.zeros((b, N, d_in), F32)
    chunk = min(chunk, n)
    pad = -n % chunk

    def chunks(x):  # [b, n, ...] -> [n / chunk, b, chunk, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, (n + pad) // chunk, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 1, 0)

    def combine(left, right):
        (a1, x1), (a2, x2) = left, right
        return a1 * a2, a2 * x1 + x2

    def one_chunk(h, part):
        u, delta, B, C = part
        decay = jnp.exp(delta[:, :, None] * A)              # [b, c, N, d_in]
        inp = (delta * u)[:, :, None] * B[..., None]
        decay, inp = jax.lax.associative_scan(combine, (decay, inp), axis=1)
        hs = inp + decay * h[:, None]
        return hs[:, -1], jnp.sum(hs * C[..., None], axis=2)

    h, y = jax.lax.scan(one_chunk, h0,
                        (chunks(u), chunks(delta), chunks(B), chunks(C)))
    return jnp.moveaxis(y, 0, 1).reshape(b, n + pad, d_in)[:, :n], h


def ssd_step(h, x, delta, A, B, C):
    """One step of Mamba-2's recurrence.  ``h`` ``[b, H, P, N]`` float32,
    ``x`` ``[b, H, P]``, ``delta`` ``[b, H]``, ``A`` ``[H]``, ``B`` and ``C``
    ``[b, G, N]``; head ``h`` reads group ``h // (H / G)``.  Returns ``(y
    [b, H, P] f32, h')``."""
    b, H, P, N = h.shape
    G = B.shape[1]
    x, delta = x.astype(F32), delta.astype(F32)
    B, C = B.astype(F32)[:, :, None, None], C.astype(F32)[:, :, None, None]
    h = h.reshape(b, G, H // G, P, N)
    decay = jnp.exp(delta * A).reshape(b, G, H // G, 1, 1)
    dx = (delta[..., None] * x).reshape(b, G, H // G, P, 1)
    h = decay * h + dx * B
    return jnp.sum(h * C, axis=-1).reshape(b, H, P), h.reshape(b, H, P, N)


def ssd(x, delta, A, B, C, h0=None, chunk: int = 128):
    """Mamba-2's recurrence over a sequence in the chunked matrix form of
    state-space duality.

    ``x`` ``[b, n, H, P]``, ``delta`` ``[b, n, H]``, ``A`` ``[H]``, ``B`` and
    ``C`` ``[b, n, G, N]``, ``h0`` ``[b, H, P, N]`` (zeros when None).
    Inside a chunk of ``chunk`` positions, with ``a = delta A`` and its
    cumulative sum ``s`` over the chunk, ``y_t = sum_{u <= t} exp(s_t - s_u)
    (C_t . B_u) delta_u x_u``: the masked decay matrix times ``C B^T``, then
    a product with ``delta x``; each chunk's own contribution to the state is
    ``sum_u exp(s_last - s_u) delta_u x_u B_u^T``, and a ``lax.scan`` carries
    the state from chunk to chunk, adding ``exp(s_t) C_t . h`` to each
    position.  A sequence that the chunk does not divide is padded with
    ``delta = 0`` (decay 1, input 0), which leaves the state as it was.
    Float32 throughout, products at the highest precision.  Returns ``(y [b,
    n, H, P] f32, h_n)``."""
    b, n, H, P = x.shape
    G, N = B.shape[2:]
    R = H // G
    x, delta = x.astype(F32), delta.astype(F32)
    B, C = B.astype(F32), C.astype(F32)
    if h0 is None:
        h0 = jnp.zeros((b, H, P, N), F32)
    L = min(chunk, n)
    pad = -n % L
    c = (n + pad) // L

    def chunks(a):  # [b, n, ...] -> [b, c, L, ...]
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return a.reshape((b, c, L) + a.shape[2:])

    x = chunks(x).reshape(b, c, L, G, R, P)
    delta = chunks(delta).reshape(b, c, L, G, R)
    B, C = chunks(B), chunks(C)                         # [b, c, L, G, N]
    s = jnp.cumsum(delta * A.reshape(G, R), axis=2)     # [b, c, L, G, R]
    causal = jnp.tril(jnp.ones((L, L), bool))[:, :, None, None]
    seg = s[:, :, :, None] - s[:, :, None, :]           # [b, c, t, u, G, R]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bctgn,bcugn->bctug", C, B, precision=EXACT,
                    preferred_element_type=F32)
    w = cb[..., None] * decay * delta[:, :, None]       # [b, c, t, u, G, R]
    y = jnp.einsum("bctugr,bcugrp->bctgrp", w, x, precision=EXACT,
                   preferred_element_type=F32)
    to_end = jnp.exp(s[:, :, -1:] - s) * delta          # [b, c, L, G, R]
    own = jnp.einsum("bcugr,bcugrp,bcugn->bcgrpn", to_end, x, B,
                     precision=EXACT, preferred_element_type=F32)

    def one_chunk(h, part):
        own, s, C = part
        y = jnp.einsum("btgn,bgrpn->btgrp", C, h, precision=EXACT,
                       preferred_element_type=F32) * jnp.exp(s)[..., None]
        return jnp.exp(s[:, -1])[..., None, None] * h + own, y

    h, y_in = jax.lax.scan(
        one_chunk, h0.reshape(b, G, R, P, N),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(s, 1, 0),
         jnp.moveaxis(C, 1, 0)))
    y = y + jnp.moveaxis(y_in, 0, 1)
    return y.reshape(b, c * L, H, P)[:, :n], h.reshape(b, H, P, N)


def normal_init(std: float):
    """Normal initialiser drawn in float32 and stored in the parameter's
    dtype, leaf by leaf (no float32 copy of the tree)."""
    def init(key, shape, dtype=F32):
        return (std * jax.random.normal(key, shape, F32)).astype(dtype)
    return init


def fan_in_normal(fan_in: int):
    return normal_init(fan_in ** -0.5)


def dt_bias_init(key, shape, dtype=F32):
    """``b_dt`` such that ``softplus(b_dt)`` is log-uniform in [1e-3, 1e-1]
    (Mamba's initialisation)."""
    lo, hi = jnp.log(1e-3), jnp.log(1e-1)
    dt = jnp.exp(jax.random.uniform(key, shape, F32) * (hi - lo) + lo)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class MambaMixer(nn.Module):
    """The Jamba family's Mamba-1 mixer: ``[u, z] = W_in x``; ``u =
    silu(conv(u) + b)``; ``[dt, B, C] = W_x u``, each RMS-normed; ``delta =
    softplus(W_dt dt + b_dt)``; the selective scan with ``A = -exp(A_log)``;
    ``y = (scan + D u) silu(z)``; out ``= W_out y``.

    Matrices are stored in ``param_dtype``; ``A_log``, ``D``, ``b_dt`` and
    the norm gains in float32.  The decode state of a layer is ``(window [b,
    conv - 1, d_in] in the activation dtype, h [b, N, d_in] float32)``."""

    dim: int
    expand: int = 2
    state: int = 16
    conv: int = 4
    dt_rank: int = 160
    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @property
    def d_in(self) -> int:
        return self.expand * self.dim

    def setup(self):
        d_in, N, R = self.d_in, self.state, self.dt_rank

        def dense(features, fan_in, name, **kw):
            return nn.DenseGeneral(
                features, use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=fan_in_normal(fan_in), name=name, **kw)

        # [dim, 2, d_in]: the u/z split is an index of an unsharded axis
        self.in_proj = dense((2, d_in), self.dim, "in_proj")
        self.conv_kernel = self.param(
            "conv_kernel", fan_in_normal(self.conv), (self.conv, d_in),
            self.param_dtype)
        self.conv_bias = self.param("conv_bias", nn.initializers.zeros,
                                    (d_in,), self.param_dtype)
        self.x_proj = dense(R + 2 * N, d_in, "x_proj")
        self.dt_norm = self.param("dt_norm", nn.initializers.ones, (R,), F32)
        self.b_norm = self.param("b_norm", nn.initializers.ones, (N,), F32)
        self.c_norm = self.param("c_norm", nn.initializers.ones, (N,), F32)
        self.dt_proj = dense(d_in, R, "dt_proj")
        self.dt_bias = self.param("dt_bias", dt_bias_init, (d_in,), F32)
        self.A_log = self.param(
            "A_log", lambda key, shape: jnp.broadcast_to(
                jnp.log(jnp.arange(1, shape[1] + 1, dtype=F32)), shape),
            (d_in, N))
        self.D = self.param("D", nn.initializers.ones, (d_in,), F32)
        self.out_proj = dense(self.dim, d_in, "out_proj")

    def _scan_inputs(self, u):
        """``(delta, B, C)`` of conv outputs ``u`` ``[..., d_in]``: the
        low-rank projection, the three norms, the step size."""
        N, R = self.state, self.dt_rank
        dbc = self.x_proj(u)
        dt = rms_norm(dbc[..., :R], self.dt_norm, self.eps)
        B = rms_norm(dbc[..., R:R + N], self.b_norm, self.eps)
        C = rms_norm(dbc[..., R + N:], self.c_norm, self.eps)
        delta = jax.nn.softplus(
            self.dt_proj(dt.astype(self.dtype)).astype(F32) + self.dt_bias)
        return delta, B, C

    def _A(self):
        return -jnp.exp(self.A_log).T                       # [N, d_in]

    def _gated_out(self, y, u, z):
        """``W_out((y + D u) silu(z))`` of the scan's read-out ``y``."""
        with prof.scope("ssm-scan"):
            y = (y + self.D * u.astype(F32)) * jax.nn.silu(z.astype(F32))
        with prof.scope("ssm-proj"):
            return self.out_proj(y.astype(self.dtype))

    def __call__(self, x, return_state: bool = False):
        """``x`` ``[b, n, dim]`` from a zero state.  With ``return_state``
        also the decode state after the last position."""
        with prof.scope("ssm-proj"):
            uz = self.in_proj(x)                            # [b, n, 2, d_in]
            u, z = uz[:, :, 0], uz[:, :, 1]
        with prof.scope("ssm-conv"):
            u, window = causal_conv(u, self.conv_kernel.astype(self.dtype),
                                    self.conv_bias.astype(self.dtype))
            u = jax.nn.silu(u)
        with prof.scope("ssm-scan"):
            delta, B, C = self._scan_inputs(u)
            y, h = selective_scan(u, delta, self._A(), B, C)
        out = self._gated_out(y, u, z)
        return (out, (window, h)) if return_state else out

    def decode_step(self, x, window, h):
        """``x`` ``[b, 1, dim]`` against the carried ``(window, h)``.
        Returns ``(out [b, 1, dim], window', h')``."""
        with prof.scope("ssm-proj"):
            uz = self.in_proj(x[:, 0])                      # [b, 2, d_in]
            u, z = uz[:, 0], uz[:, 1]
        with prof.scope("ssm-conv"):
            u, window = causal_conv_step(
                u, self.conv_kernel.astype(self.dtype),
                self.conv_bias.astype(self.dtype), window)
            u = jax.nn.silu(u)
        with prof.scope("ssm-scan"):
            delta, B, C = self._scan_inputs(u)
            y, h = selective_scan_step(h, u, delta, self._A(), B, C)
        return self._gated_out(y, u, z)[:, None], window, h

    def init_state(self, batch: int):
        """A zero decode state for ``batch`` rows."""
        return (jnp.zeros((batch, self.conv - 1, self.d_in), self.dtype),
                jnp.zeros((batch, self.state, self.d_in), F32))


def uniform_init(lo: float, hi: float):
    """Uniform initialiser on ``[lo, hi)``, drawn in float32."""
    def init(key, shape, dtype=F32):
        return jax.random.uniform(key, shape, F32, lo, hi).astype(dtype)
    return init


class Mamba2Mixer(nn.Module):
    """Nemotron-H's Mamba-2 mixer: ``[z | xBC | dt] = W_in m``; ``xBC =
    silu(conv(xBC) + b_conv)``, split into ``x [H, P]``, ``B [G, N]`` and
    ``C [G, N]``; ``delta = softplus(dt + b_dt)``; the recurrence of
    :func:`ssd` with ``A = -exp(A_log)``, one decay a head, head ``h``
    reading group ``h // (H / G)``; ``y = GroupRMSNorm(((h C) + D x)
    silu(z)) g`` over groups of ``H P / G`` channels; out ``= W_out y``.

    Matrices and the convolution's taps are stored in ``param_dtype``;
    ``A_log``, ``D``, ``b_dt``, ``b_conv``'s draw and the norm's gain in
    float32.  ``b_conv``, ``D`` and the gain are drawn from the seed rather
    than set to 0 or 1, so that a seeded model's output depends on each.
    The decode state of a layer is ``(window [b, conv - 1, H P + 2 G N] in
    the activation dtype, h [b, H, P, N] float32)``.

    What the recurrence is given at every position, ``(x, B, C, delta)``,
    is sown as ``intermediates/rule_inputs`` (a no-op unless that collection
    is mutable), so that a check can run another implementation of the rule
    over exactly those inputs."""

    dim: int
    heads: int
    head_dim: int
    groups: int
    state: int = 128
    conv: int = 4
    chunk: int = 128
    eps: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @property
    def d_in(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_in + 2 * self.groups * self.state

    def setup(self):
        H, d_in, width = self.heads, self.d_in, self.conv

        def dense(features, fan_in, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype,
                            kernel_init=fan_in_normal(fan_in), name=name)

        self.in_proj = dense(d_in + self.conv_dim + H, self.dim, "in_proj")
        self.conv_kernel = self.param(
            "conv_kernel", fan_in_normal(width), (width, self.conv_dim),
            self.param_dtype)
        # torch's Conv1d default for a bias of fan-in ``width``
        self.conv_bias = self.param(
            "conv_bias", uniform_init(-width ** -0.5, width ** -0.5),
            (self.conv_dim,), self.param_dtype)
        self.dt_bias = self.param("dt_bias", dt_bias_init, (H,), F32)
        self.A_log = self.param(
            "A_log", lambda key, shape: jnp.log(
                jax.random.uniform(key, shape, F32, 1.0, 16.0)), (H,))
        self.D = self.param("D", uniform_init(0.5, 1.5), (H,), F32)
        self.norm_gain = self.param("norm_gain", uniform_init(0.5, 1.5),
                                    (d_in,), F32)
        self.out_proj = dense(self.dim, d_in, "out_proj")

    def _split(self, zxbcdt):
        """``(z, xBC, dt)`` of the projection's output ``[..., d_in +
        conv_dim + H]``."""
        d_in, conv_dim = self.d_in, self.conv_dim
        return (zxbcdt[..., :d_in], zxbcdt[..., d_in:d_in + conv_dim],
                zxbcdt[..., d_in + conv_dim:])

    def _rule_inputs(self, xbc, dt):
        """``(x [..., H, P], B [..., G, N], C [..., G, N], delta [..., H])``
        of the convolution's output and the projected ``dt``; sown."""
        d_in, GN = self.d_in, self.groups * self.state
        lead = xbc.shape[:-1]
        x = xbc[..., :d_in].reshape(lead + (self.heads, self.head_dim))
        B = xbc[..., d_in:d_in + GN].reshape(lead + (self.groups, self.state))
        C = xbc[..., d_in + GN:].reshape(lead + (self.groups, self.state))
        delta = jax.nn.softplus(dt.astype(F32) + self.dt_bias)
        self.sow("intermediates", "rule_inputs", (x, B, C, delta))
        return x, B, C, delta

    def _A(self):
        return -jnp.exp(self.A_log)

    def _gated_out(self, y, x, z):
        """``W_out(GroupRMSNorm((y + D x) silu(z)) g)`` of the read-out ``y``
        ``[..., H, P]``."""
        lead = y.shape[:-2]
        with prof.scope("ssd-state"):
            y = y + self.D[:, None] * x.astype(F32)
            y = y.reshape(lead + (self.d_in,)) * jax.nn.silu(z.astype(F32))
            y = y.reshape(lead + (self.groups, self.d_in // self.groups))
            y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                                  + self.eps)
            y = y.reshape(lead + (self.d_in,)) * self.norm_gain
        with prof.scope("ssd-proj"):
            return self.out_proj(y.astype(self.dtype))

    def __call__(self, m, return_state: bool = False):
        """``m`` ``[b, n, dim]`` (the normed hidden state) from a zero
        state.  With ``return_state`` also the decode state after the last
        position."""
        with prof.scope("ssd-proj"):
            z, xbc, dt = self._split(self.in_proj(m))
        with prof.scope("ssd-conv"):
            xbc, window = causal_conv(xbc, self.conv_kernel.astype(self.dtype),
                                      self.conv_bias.astype(self.dtype))
            xbc = jax.nn.silu(xbc)
        with prof.scope("ssd-state"):
            x, B, C, delta = self._rule_inputs(xbc, dt)
            y, h = ssd(x, delta, self._A(), B, C, chunk=self.chunk)
        out = self._gated_out(y, x, z)
        return (out, (window, h)) if return_state else out

    def decode_step(self, m, window, h):
        """``m`` ``[b, 1, dim]`` against the carried ``(window, h)``.
        Returns ``(out [b, 1, dim], window', h')``."""
        with prof.scope("ssd-proj"):
            z, xbc, dt = self._split(self.in_proj(m[:, 0]))
        with prof.scope("ssd-conv"):
            xbc, window = causal_conv_step(
                xbc, self.conv_kernel.astype(self.dtype),
                self.conv_bias.astype(self.dtype), window)
            xbc = jax.nn.silu(xbc)
        with prof.scope("ssd-state"):
            x, B, C, delta = self._rule_inputs(xbc, dt)
            y, h = ssd_step(h, x, delta, self._A(), B, C)
        return self._gated_out(y, x, z)[:, None], window, h

    def init_state(self, batch: int):
        """A zero decode state for ``batch`` rows."""
        return (jnp.zeros((batch, self.conv - 1, self.conv_dim), self.dtype),
                jnp.zeros((batch, self.heads, self.head_dim, self.state), F32))
