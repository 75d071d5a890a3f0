"""Pallas TPU flash attention with block-sparse pattern skipping.

This is the framework's flagship custom kernel, replacing the reference's
DeepSpeed ``SparseSelfAttention`` CUDA/Triton block-sparse kernel
(`/root/reference/dalle_pytorch/attention.py:284-342`) — and, beyond parity,
accelerating *every* attention variant (full / axial_row / axial_col /
conv_like / sparse), since they are all boolean patterns over absolute
positions (see ``ops/attention.py``).

Design (TPU-first):
* **flash**: online-softmax accumulation over key blocks — the [n, n]
  attention matrix is never materialized in HBM.  At the reference's CUB
  geometry (b16 h8 n1104) the dense f32 scores alone are ~624 MB/step of
  HBM traffic; this kernel keeps them in VMEM tiles.
* **block-sparse skipping**: a static block summary (0 = skip, >0 = compute)
  derived from the pattern predicate lets the kernel skip disallowed key
  blocks entirely — axial patterns touch O(n·sqrt(n)) instead of O(n^2)
  score entries, matching the asymptotics DeepSpeed's kernel gave the
  reference.
* **keys/values stay VMEM-resident** per (batch*head) program: at n≈1104,
  dh=64 they fit comfortably (~0.6 MB), so the inner loop does no HBM
  traffic at all.
* full custom VJP: flash backward (dq then dk/dv) with the same block
  skipping, using the saved logsumexp rows.

All shapes are padded to block multiples with masked-off (never-attended)
positions; softmax runs in f32 regardless of input dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import AttnPattern, dense_pattern_mask

NEG_INF = -1e30  # finite mask value: keeps (s - lse) well-defined everywhere


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.lru_cache(maxsize=64)
def _pattern_blocks(pattern: AttnPattern, n: int, n_pad: int,
                    block_q: int, block_k: int):
    """Static (trace-time) mask + block summary for a pattern at length n.

    Returns (mask [n_pad, n_pad] bool, bsum [NQ, NK] int32) where
    bsum[qb, kb] = 0 if no (i, j) in the block may attend, else 1.
    """
    mask = np.zeros((n_pad, n_pad), dtype=bool)
    mask[:n, :n] = dense_pattern_mask(pattern, n, n)
    nq, nk = n_pad // block_q, n_pad // block_k
    bsum = np.zeros((nq, nk), dtype=np.int32)
    for qb in range(nq):
        for kb in range(nk):
            blk = mask[qb * block_q:(qb + 1) * block_q,
                       kb * block_k:(kb + 1) * block_k]
            bsum[qb, kb] = 1 if blk.any() else 0
    return mask, bsum


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(bsum_ref, q_ref, k_ref, v_ref, mask_ref, bias_ref,
                o_ref, lse_ref, *, scale: float, block_k: int, nk: int):
    qb = pl.program_id(1)
    q = q_ref[0]  # [bq, dh], input dtype (MXU takes bf16 with f32 accum)
    bq = q.shape[0]

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, q.shape[1]), jnp.float32)

    def body(kb, carry):
        def compute(carry):
            m, l, acc = carry
            start = pl.multiple_of(kb * block_k, block_k)
            k_blk = k_ref[0, pl.ds(start, block_k), :]
            v_blk = v_ref[0, pl.ds(start, block_k), :]
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [bq, bk]
            s = s + bias_ref[0, 0, pl.ds(start, block_k)][None, :]
            mblk = mask_ref[:, pl.ds(start, block_k)]
            s = jnp.where(mblk, s, NEG_INF)

            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            # rows with every key masked have s == m_new == NEG_INF, where
            # exp(s - m_new) = 1 would leak uniform attention onto
            # disallowed keys — force those terms to 0 (l then stays 0 and
            # the lse=+inf guard below takes over)
            p = jnp.where(s <= NEG_INF * 0.5, 0.0, p)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=1, keepdims=True)
            acc_new = acc * alpha + jax.lax.dot_general(
                p, v_blk.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        return jax.lax.cond(bsum_ref[qb, kb] > 0, compute, lambda c: c, carry)

    m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    # rows with no attendable key (padding): lse = +inf so bwd's
    # exp(s - lse) is exactly 0
    lse = jnp.where(l == 0.0, jnp.inf, m + jnp.log(l_safe))
    lse_ref[0, 0, :] = lse[:, 0]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(bsum_ref, q_ref, k_ref, v_ref, mask_ref, bias_ref,
                   do_ref, lse_ref, delta_ref, dq_ref,
                   *, scale: float, block_k: int, nk: int):
    qb = pl.program_id(1)
    q = q_ref[0]
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0, :][:, None]      # [bq, 1]
    delta = delta_ref[0, 0, :][:, None]  # [bq, 1]
    dq0 = jnp.zeros(q.shape, jnp.float32)

    def body(kb, dq):
        def compute(dq):
            start = pl.multiple_of(kb * block_k, block_k)
            k_blk = k_ref[0, pl.ds(start, block_k), :]
            v_blk = v_ref[0, pl.ds(start, block_k), :]
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = s + bias_ref[0, 0, pl.ds(start, block_k)][None, :]
            mblk = mask_ref[:, pl.ds(start, block_k)]
            s = jnp.where(mblk, s, NEG_INF)
            p = jnp.exp(s - lse)                      # [bq, bk]
            dp = jax.lax.dot_general(
                do, v_blk.astype(jnp.float32), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # [bq, bk]
            ds = p * (dp - delta)
            return dq + jax.lax.dot_general(
                ds, k_blk.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

        return jax.lax.cond(bsum_ref[qb, kb] > 0, compute, lambda d: d, dq)

    dq = jax.lax.fori_loop(0, nk, body, dq0)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(bsum_ref, q_ref, k_ref, v_ref, mask_ref, bias_ref,
                    do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    *, scale: float, block_q: int, nq: int):
    kb = pl.program_id(1)
    k_blk = k_ref[0]   # [bk, dh]
    v_blk = v_ref[0]
    bias = bias_ref[0, 0, :][None, :]  # [1, bk] — bias over this key block
    dk0 = jnp.zeros(k_blk.shape, jnp.float32)
    dv0 = jnp.zeros(v_blk.shape, jnp.float32)

    def body(qb, carry):
        def compute(carry):
            dk, dv = carry
            start = pl.multiple_of(qb * block_q, block_q)
            q = q_ref[0, pl.ds(start, block_q), :]
            do = do_ref[0, pl.ds(start, block_q), :].astype(jnp.float32)
            lse = lse_ref[0, 0, pl.ds(start, block_q)][:, None]
            delta = delta_ref[0, 0, pl.ds(start, block_q)][:, None]
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [bq, bk]
            s = s + bias
            mblk = mask_ref[pl.ds(start, block_q), :]
            s = jnp.where(mblk, s, NEG_INF)
            p = jnp.exp(s - lse)
            dv_new = dv + jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [bk, dh]
            dp = jax.lax.dot_general(
                do, v_blk.astype(jnp.float32), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [bq, bk]
            ds = p * (dp - delta)
            dk_new = dk + jax.lax.dot_general(
                ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [bk, dh]
            return dk_new, dv_new

        return jax.lax.cond(bsum_ref[qb, kb] > 0, compute, lambda c: c, carry)

    dk, dv = jax.lax.fori_loop(0, nq, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------


# Every (batch*head, q-or-k-block) program in the three kernels below
# writes its own disjoint output block exactly once (accumulation happens
# only inside the per-program fori_loop), so both grid axes are parallel —
# this lets Mosaic pipeline/reorder programs freely (megacore splits on
# v4/v5p; no-op on single-tensorcore chips).
_PARALLEL_GRID = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"))


def _smem_spec(shape):
    return pl.BlockSpec(shape, lambda ib, iq: (0, 0), memory_space=pltpu.SMEM)


def _call_fwd(q, k, v, mask, bsum, bias, *, scale, block_q, block_k,
              interpret):
    bh, n_pad, dh = q.shape
    nq, nk = bsum.shape
    heads_bias = bias.shape[0]  # bias is [b, 1, n_pad]; bh = b * h
    h = bh // heads_bias

    kernel = functools.partial(_fwd_kernel, scale=scale, block_k=block_k,
                               nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq),
        in_specs=[
            _smem_spec((nq, nk)),
            pl.BlockSpec((1, block_q, dh), lambda ib, iq: (ib, iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_pad, dh), lambda ib, iq: (ib, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_pad, dh), lambda ib, iq: (ib, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_q, n_pad), lambda ib, iq: (iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, n_pad), lambda ib, iq: (jax.lax.div(ib, h), 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dh), lambda ib, iq: (ib, iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda ib, iq: (ib, 0, iq),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n_pad, dh), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, n_pad), jnp.float32),
        ],
        compiler_params=_PARALLEL_GRID,
        interpret=interpret,
    )(bsum, q, k, v, mask, bias)


def _call_bwd(q, k, v, mask, bsum, bias, do, lse, delta, *, scale, block_q,
              block_k, interpret):
    bh, n_pad, dh = q.shape
    nq, nk = bsum.shape
    h = bh // bias.shape[0]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_k=block_k, nk=nk),
        grid=(bh, nq),
        in_specs=[
            _smem_spec((nq, nk)),
            pl.BlockSpec((1, block_q, dh), lambda ib, iq: (ib, iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_pad, dh), lambda ib, iq: (ib, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_pad, dh), lambda ib, iq: (ib, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_q, n_pad), lambda ib, iq: (iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, n_pad), lambda ib, iq: (jax.lax.div(ib, h), 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, dh), lambda ib, iq: (ib, iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda ib, iq: (ib, 0, iq),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda ib, iq: (ib, 0, iq),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda ib, iq: (ib, iq, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bh, n_pad, dh), q.dtype),
        compiler_params=_PARALLEL_GRID,
        interpret=interpret,
    )(bsum, q, k, v, mask, bias, do, lse, delta)

    def kv_spec(_):
        return pl.BlockSpec((1, block_k, dh), lambda ib, ik: (ib, ik, 0),
                            memory_space=pltpu.VMEM)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, block_q=block_q,
                          nq=nq),
        grid=(bh, nk),
        in_specs=[
            _smem_spec((nq, nk)),
            pl.BlockSpec((1, n_pad, dh), lambda ib, ik: (ib, 0, 0),
                         memory_space=pltpu.VMEM),
            kv_spec(None),
            kv_spec(None),
            pl.BlockSpec((n_pad, block_k), lambda ib, ik: (0, ik),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k), lambda ib, ik: (jax.lax.div(ib, h), 0, ik),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_pad, dh), lambda ib, ik: (ib, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, n_pad), lambda ib, ik: (ib, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, n_pad), lambda ib, ik: (ib, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[kv_spec(None), kv_spec(None)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n_pad, dh), q.dtype),
            jax.ShapeDtypeStruct((bh, n_pad, dh), q.dtype),
        ],
        compiler_params=_PARALLEL_GRID,
        interpret=interpret,
    )(bsum, q, k, v, mask, bias, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp public op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _flash_attention(pattern: AttnPattern, block_q: int, block_k: int,
                     interpret: bool, q, k, v, bias):
    out, _ = _flash_fwd(pattern, block_q, block_k, interpret, q, k, v, bias)
    return out


def _padded_len(n: int, block_q: int, block_k: int) -> int:
    """The kernel's actual padded sequence length — shared with the VMEM
    guard so its estimate can never diverge from what _prepare allocates."""
    n_pad = _round_up(n, max(block_q, block_k))
    n_pad = _round_up(n_pad, block_q)
    return _round_up(n_pad, block_k)


def _prepare(pattern, block_q, block_k, q, bias):
    b, h, n, dh = q.shape
    n_pad = _padded_len(n, block_q, block_k)
    mask_np, bsum_np = _pattern_blocks(pattern, n, n_pad, block_q, block_k)
    mask = jnp.asarray(mask_np)
    bsum = jnp.asarray(bsum_np)
    if bias is None:
        bias_p = jnp.zeros((b, 1, n_pad), jnp.float32)
    else:
        bias_p = jnp.pad(bias.astype(jnp.float32),
                         ((0, 0), (0, n_pad - n)))[:, None, :]
    return n_pad, mask, bsum, bias_p


def _flash_fwd(pattern, block_q, block_k, interpret, q, k, v, bias):
    b, h, n, dh = q.shape
    scale = dh ** -0.5
    n_pad, mask, bsum, bias_p = _prepare(pattern, block_q, block_k, q, bias)

    def flat_pad(t):
        t = t.reshape(b * h, n, dh)
        return jnp.pad(t, ((0, 0), (0, n_pad - n), (0, 0)))

    qf, kf, vf = flat_pad(q), flat_pad(k), flat_pad(v)
    o, lse = _call_fwd(qf, kf, vf, mask, bsum, bias_p, scale=scale,
                       block_q=block_q, block_k=block_k, interpret=interpret)
    out = o[:, :n, :].reshape(b, h, n, dh)
    return out, (qf, kf, vf, bias_p, o, lse)


def _flash_bwd(pattern, block_q, block_k, interpret, residuals, g):
    qf, kf, vf, bias_p, o, lse = residuals
    bh, n_pad, dh = qf.shape
    b = bias_p.shape[0]
    h = bh // b
    n = g.shape[2]
    scale = dh ** -0.5
    mask_np, bsum_np = _pattern_blocks(pattern, n, n_pad, block_q, block_k)
    mask, bsum = jnp.asarray(mask_np), jnp.asarray(bsum_np)

    do = jnp.pad(g.reshape(bh, n, dh), ((0, 0), (0, n_pad - n), (0, 0)))
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]  # [bh, 1, n_pad]

    dq, dk, dv = _call_bwd(qf, kf, vf, mask, bsum, bias_p, do, lse, delta,
                           scale=scale, block_q=block_q, block_k=block_k,
                           interpret=interpret)

    def unflat(t):
        return t[:, :n, :].reshape(b, h, n, dh)

    dbias = jnp.zeros((b, n), jnp.float32)  # pad bias is non-trainable
    return unflat(dq), unflat(dk), unflat(dv), dbias


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


# Per-core VMEM is ~16 MB on current TPUs; the kernel keeps each program's
# full-sequence K/V (plus the padded [n_pad, n_pad] bool mask tile rows)
# VMEM-resident, which is the right call at the CUB geometry (n=1104:
# ~0.6 MB K/V) but stops scaling with n.  Budget conservatively at half of
# VMEM so q/o/acc tiles, the mask and double-buffering still fit.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024


def _vmem_resident_bytes(n_pad: int, dh: int, itemsize: int,
                         block_q: int) -> int:
    # K + V [n_pad, dh] + mask rows [block_q, n_pad] (bool) per program
    return 2 * n_pad * dh * itemsize + block_q * n_pad


def flash_pattern_attention(q, k, v, pattern: AttnPattern,
                            key_pad_bias: Optional[jax.Array] = None, *,
                            block_q: int = 128, block_k: int = 128,
                            interpret: bool = False) -> jax.Array:
    """Block-sparse flash attention for any `AttnPattern`.

    q/k/v: [b, heads, n, dim_head]; `key_pad_bias` is an optional additive
    f32 [b, n] key bias (0 keep / -1e30 drop) carrying the per-sample key
    padding mask.  Returns [b, heads, n, dim_head] in q's dtype.

    Raises ValueError when the sequence is long enough that the
    VMEM-resident K/V design would overflow the per-core budget — callers
    should fall back to the dense-masked XLA path (or sequence parallelism,
    parallel/ring.py) instead of letting Mosaic fail opaquely mid-compile.
    The guard only applies to real TPU compilation; the interpreter
    (CPU/GPU correctness runs) has no VMEM limit.
    """
    b, _, n, dh = q.shape
    if (block_q % 128 or block_k % 128) and not interpret:
        # Mosaic requires the last block dim be a multiple of the 128-lane
        # width (the lse output [b, h, n] blocks the q axis in its last
        # dim; k blocks stream through the same lanes) — sub-128 tiles
        # fail deep inside lowering, so reject them at the API edge.
        # Seen on the chip: perf_ab pallas-b64, manual session 2026-08-02.
        raise ValueError(
            f"block_q/block_k must be multiples of the TPU lane width 128 "
            f"(got {block_q}/{block_k})")
    n_pad = _padded_len(n, block_q, block_k)
    resident = _vmem_resident_bytes(n_pad, dh, q.dtype.itemsize, block_q)
    if resident > VMEM_BUDGET_BYTES and not interpret:
        raise ValueError(
            f"flash_pattern_attention keeps full-sequence K/V VMEM-resident: "
            f"n={n} (padded {n_pad}), dh={dh} needs ~{resident / 1e6:.1f} MB "
            f"of the ~{VMEM_BUDGET_BYTES / 1e6:.0f} MB budget. Use the dense "
            "path (use_pallas=False) or sequence parallelism (ring_axis) "
            "for sequences this long.")
    if key_pad_bias is None:
        key_pad_bias = jnp.zeros((b, n), jnp.float32)
    return _flash_attention(pattern, block_q, block_k, interpret,
                            q, k, v, key_pad_bias)
