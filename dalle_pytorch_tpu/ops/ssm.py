"""State-space (Mamba-1) operators and the mixer layer built on them.

Two operators, each in the two forms a decoder needs:

* the causal depthwise convolution: over a sequence (``causal_conv``) and one
  position against a rolling window of the last ``width - 1`` inputs
  (``causal_conv_step``);
* the selective scan ``h_t = exp(delta_t A) h_{t-1} + (delta_t u_t) B_t^T``,
  ``y_t = h_t C_t``: over a sequence in chunks, the state carried from chunk
  to chunk (``selective_scan``; differentiable, used by the forward pass,
  the prefill and training), and one step against a carried state
  (``selective_scan_step``; the decode tick).

Plain ``jnp``/``lax``: no kernel here.  The state, ``delta``, ``A`` and the
exponentials are float32 whatever the activation dtype.

Layout: the channel axis ``d_in`` is minor in every carried leaf (the state
is ``[b, N, d_in]``, the window ``[b, width - 1, d_in]``), so that the
row-major layout fills the TPU's 128 lanes with channels and nothing is
padded: ``N`` is 16 and the window 3 deep.

``MambaMixer`` is the layer of the Jamba family (Mamba-1 with an RMSNorm on
each of ``dt``, ``B`` and ``C``).  Its scopes, none nested in another:
``ssm-proj`` (the two wide projections), ``ssm-conv`` (the convolution and
its activation), ``ssm-scan`` (the recurrent update: the two small
projections, the norms, ``delta``, the scan, the read-out and the gate).
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
