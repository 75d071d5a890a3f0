"""Small shared helpers.

TPU-native analog of the helper block in the reference
(`/root/reference/dalle_pytorch/dalle_pytorch.py:13-50`), re-expressed for a
functional JAX codebase: no in-place ops, no `.training` flags, explicit RNG.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def env_flag(name: str, default: bool = False) -> bool:
    """Boolean environment flag with OFF-able semantics: unset -> default;
    ``"0"``, ``"false"``, ``"no"``, ``"off"`` and the empty string (any
    case) -> False; anything else -> True.

    ``bool(os.environ.get(X))`` treats ``X=0`` as ON — an operator
    disabling a flag with 0 would silently enable it (the BENCH_PALLAS /
    GRAFT_DRYRUN_FULL footgun of review round 5).  All boolean env knobs
    parse through here.
    """
    import os

    val = os.environ.get(name)
    if val is None:
        return default
    return val.strip().lower() not in ("", "0", "false", "no", "off")


def atomic_write_bytes(path, data: bytes) -> None:
    """Write ``data`` whole-or-not-at-all: temp file + fsync +
    ``os.replace`` (the I1 discipline of DESIGN.md §8 — a reader can see
    the old file or the new file, never a torn one).  Durable-state writes
    outside ``utils/`` must route through here or the checkpoint helpers
    (graftlint CKPT001)."""
    import os
    import tempfile
    from pathlib import Path

    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=str(path.parent),
                               prefix=f".{path.name}-")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def atomic_write_json(path, obj, indent: int = 1) -> None:
    """:func:`atomic_write_bytes` of a JSON document."""
    import json

    atomic_write_bytes(path, json.dumps(obj, indent=indent).encode())


def exists(val):
    return val is not None


def default(val, d):
    if val is not None:
        return val
    return d() if callable(d) else d


def cast_tuple(val, depth: int = 1):
    if isinstance(val, list):
        val = tuple(val)
    return val if isinstance(val, tuple) else (val,) * depth


def max_neg_value(dtype) -> float:
    """Most-negative finite value for a dtype (ref dalle_pytorch.py:483)."""
    return -jnp.finfo(dtype).max


def masked_mean(t: jax.Array, mask: jax.Array, axis: int = 1) -> jax.Array:
    """Mean over `axis` counting only positions where `mask` is True.

    Ref `dalle_pytorch.py:29-31` (CLIP text pooling).
    """
    mask = mask[..., None]
    t = jnp.where(mask, t, 0.0)
    return t.sum(axis=axis) / mask.sum(axis=axis)


def l2norm(t: jax.Array, axis: int = -1, eps: float = 1e-12) -> jax.Array:
    return t / jnp.maximum(jnp.linalg.norm(t, axis=axis, keepdims=True), eps)


#: counting passes of :func:`kth_largest`: one per bit of an f32's key
TOP_K_PASSES = 32


def top_k_count(num_logits: int, thres: float,
                k_vocab: Optional[int] = None) -> int:
    """How many of ``num_logits`` logits :func:`top_k_filter` keeps: the
    reference's ``max(int((1 - thres) * V), 1)`` (`dalle_pytorch.py:44-50`),
    ``V`` being ``k_vocab`` where the caller's logits are a slice of a wider
    vocabulary, and never more than there are logits."""
    vocab = k_vocab if k_vocab is not None else num_logits
    return min(max(int((1 - thres) * vocab), 1), num_logits)


def kth_largest(x: jax.Array, k: int) -> jax.Array:
    """The ``k``-th largest value along the last axis, ``[..., 1]``: what
    ``jax.lax.top_k(x, k)[0][..., -1:]`` returns, found by exact selection
    and not by ordering the row (XLA:TPU lowers ``top_k`` at a k of 20-80%
    of the row to a full sort; only this one number leaves it).

    Each value is mapped to its order-preserving 32-bit key (the f32's bits,
    with the 31 low bits of a negative flipped: signed integer order is then
    float order; bf16/f16 widen to f32 exactly and monotonically), and the
    answer's key is settled from the top bit down, one counting pass a bit:
    a bit is set if at least ``k`` keys are still >= the candidate.  32
    passes of compare-and-count over a row that stays resident, whatever k.
    The keys are compared signed, not as the u32 with the sign bit flipped:
    the chip emulates an unsigned compare, and a tick of matmul, cut-off and
    filter at 128 x 8192 read 0.077 ms in the unsigned form against 0.033
    in this one and 0.475 with ``lax.top_k`` (PERF.md, PR 31); a
    ``fori_loop`` read faster than the same passes unrolled, and traces
    once.

    Ties, duplicated boundary values and infinities are exact.  A zero's
    sign is the key's (-0 orders below +0, as in ``lax.top_k``); callers
    compare with ``<``, which does not see it.  A NaN orders by its bits as
    it does in ``lax.top_k``, a positive one above +inf and a negative one
    below -inf; logits that reach the sampler are finite."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    low = jnp.int32(0x7FFFFFFF)
    keys = jnp.where(bits < 0, bits ^ low, bits)

    def settle_bit(i, best):
        # bit 31 first: INT_MIN ^ INT_MIN is 0, the least non-negative key
        cand = best ^ (jnp.int32(1) << (TOP_K_PASSES - 1 - i))
        reached = jnp.sum(keys >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(reached >= k, cand, best)

    least = jnp.full(x.shape[:-1], jnp.iinfo(jnp.int32).min, jnp.int32)
    kth = jax.lax.fori_loop(0, TOP_K_PASSES, settle_bit, least)
    kth = jnp.where(kth < 0, kth ^ low, kth)
    return jax.lax.bitcast_convert_type(kth, jnp.float32).astype(
        x.dtype)[..., None]


def top_k_filter(logits: jax.Array, thres: float = 0.5,
                 k_vocab: Optional[int] = None) -> jax.Array:
    """Keep the top `max(int((1-thres)*V), 1)` logits, set the rest to -inf.

    Exact semantics of the reference sampler filter
    (`dalle_pytorch.py:44-50`): k is derived from the vocab size, not given
    directly. Static `k` keeps this jit-friendly.

    `k_vocab` overrides the vocab size V used to derive k: the decode path
    hands in image-vocab-only logits (the text half of the joint vocab is
    structurally -inf there and is never materialized), but the reference
    derives k from the FULL joint vocab — since its -inf text entries can
    never win a top-k slot anyway, deriving k from the full size over the
    sliced logits selects the identical candidate set.

    The cut-off is :func:`kth_largest`'s: bit for bit the output a
    ``lax.top_k`` cut-off gives on every input without a NaN (a NaN at the
    cut-off cuts nothing, there as here).
    """
    k = top_k_count(logits.shape[-1], thres, k_vocab)
    return jnp.where(logits < kth_largest(logits, k), -jnp.inf, logits)


def top_p_filter(logits: jax.Array, p: float) -> jax.Array:
    """Nucleus filtering (beyond the reference, which only has top-k): keep
    the smallest set of tokens whose softmax mass reaches ``p``, set the
    rest to -inf.  The highest-probability token always survives.  Static
    shapes throughout — jit/scan friendly."""
    assert 0.0 < p <= 1.0, f"top_p must be in (0, 1], got {p}"
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # token i survives if the mass BEFORE it is < p (so the first token that
    # crosses p is still included)
    keep = (cum - probs) < p
    # threshold = smallest surviving logit; everything below is cut
    cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1,
                     keepdims=True)
    return jnp.where(logits < cutoff, -jnp.inf, logits)
