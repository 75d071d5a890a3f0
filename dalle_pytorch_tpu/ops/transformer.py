"""Transformer stack: LayerScale / PreNorm / GEGLU-FF blocks + executors.

Capability parity with `/root/reference/dalle_pytorch/transformer.py`:
* LayerScale with depth-staged init (0.1 / 1e-5 / 1e-6 for layer index <=18 /
  <=24 / >24; ref :28-42);
* PreNorm + GEGLU feed-forward, mult=4 (ref :44-69);
* per-layer attention type cycled from ``attn_types`` (ref :93-109);
* executor choice: sequential residual stack or reversible two-stream
  (ref :116-120), with the kwarg router semantics that only attention layers
  receive ``mask`` (ref :117-118).

TPU-native deltas: optional `jax.checkpoint` rematerialization per block
(the standard XLA memory-saving move), a true O(1)-activation reversible
executor built on `jax.custom_vjp` (ops/reversible.py) replacing torch's
autograd.Function + RNG replay, and a KV-cache `decode_step` used by the
jitted sampler.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..obs import prof, telemetry
from ..utils.helpers import cast_tuple, default
from .attention import YaRN, AttnPattern, MultiHeadAttention
from .latent_attention import LatentAttention
from .linear_attention import GatedDeltaMixer
from .reversible import reversible_sequence, reversible_sequence_naive
from .ssm import Mamba2Mixer, MambaMixer, fan_in_normal, rms_norm

#: a layer without a mixer: its feed-forward alone (``TrunkSpec.sublayers``)
NO_MIXER = "none"
MIXERS = ("attention", "gdn", "mamba", "mamba2", "mla", "rotated", "window",
          NO_MIXER)
#: the mixers whose decode state is a recurrent state (two leaves with the
#: rows on axis 0 and no position axis), not keys and values
RECURRENT_MIXERS = ("gdn", "mamba", "mamba2")
#: the mixers that rotate their queries and keys by position
ROTARY_MIXERS = ("mla", "rotated", "window")
FFS = ("swiglu", "moe_reglu", "moe_swiglu_shared")
#: a shared-expert layer's experts: gated SiLU, or relu(W_up m)^2 with no
#: gate bank (ops/moe.py::ExpertsSwiGLUShared)
EXPERT_ACTS = ("swiglu", "relu2")
#: the feed-forwards that route tokens to experts
ROUTED_FFS = ("moe_reglu", "moe_swiglu_shared")
#: how a routed layer scores its experts (ops/moe.py::route)
SCORINGS = ("softmax", "sigmoid")
NORM_AT = ("input", "output")


@dataclasses.dataclass(frozen=True)
class TrunkSpec:
    """Per-layer block spec of a trunk that is not the 2021 DALL-E block:
    layer ``i`` is ``x += Mixer_i(Norm(x)); x += FF(Norm(x))`` (``norm_at``
    "input") or ``x += Norm(Mixer_i(x)); x += Norm(FF(x))`` ("output": the
    sublayers read the un-normed stream) with no LayerScale, bias or
    dropout, its mixer ``mixers[i % len(mixers)]``.  A ``"none"`` mixer
    leaves the layer its feed-forward alone; ``sublayers`` 1 makes every
    layer ONE sublayer (Nemotron-H's ``hybrid_override_pattern``): a layer
    with a mixer has no feed-forward, a ``"none"`` layer is its feed-forward
    (:meth:`ff_kind`), and such a layer holds no decode state at all
    (:func:`is_stateless`).  Absent
    (``DALLEConfig.trunk`` None) the stack is LayerScale(PreNorm(
    attention)) + LayerScale(PreNorm(GEGLU x4)) as before.

    Every field is a model field (it changes the parameter tree or the
    mathematics).  Mixers: ``"attention"`` is global grouped-query attention
    without position encoding, ``"window"`` the same attention rotated
    (``rope_theta`` over every dimension of a head, ops/attention.py::
    apply_rope) and bounded to the last ``window`` keys, with
    ``window_heads`` query heads (0: ``DALLEConfig.heads``, which every
    other attention layer has), ``"rotated"`` global attention rotated by a
    rotation of its own: ``global_rope_theta`` over the leading
    ``global_rope_fraction`` of a head's dimensions, with YaRN's
    frequencies and attention factor where ``yarn_factor`` is set (over
    ``yarn_original_len`` positions: :meth:`yarn`), ``"mamba"`` Mamba-1 with normed ``dt``/``B``/``C``,
    ``"mamba2"`` Mamba-2 (ops/ssm.py::Mamba2Mixer): ``ssd_heads`` heads of
    ``ssd_head_dim`` channels, each a ``[ssd_head_dim, ssm_state]`` state
    with one decay, ``B`` and ``C`` shared by ``ssd_groups`` groups of
    heads, an ``ssm_conv``-tap convolution, the sequence in chunks of
    ``ssd_chunk``,
    ``"gdn"`` gated-delta-rule linear attention (ops/linear_attention.py):
    ``DALLEConfig.heads`` heads of ``lin_key_dim`` x ``lin_value_dim`` state
    behind ``lin_conv``-tap convolutions; ``"mla"`` multi-head latent
    attention (ops/latent_attention.py): global, rotated (``rope_theta``)
    over ``rope_dim`` of a head's ``nope_dim + rope_dim`` query/key
    dimensions, queries through a normed ``q_rank`` bottleneck, keys and
    values of ``value_dim`` from one normed ``kv_rank`` latent a position,
    which with one shared rotary key is all its decode cache holds.
    ``qk_norm``: attention layers RMS-norm their projected queries and keys
    over the projection's whole width, before the split into heads.
    ``head_gate``: every attention layer multiplies each head's attended
    values by a sigmoid of the sublayer's normed input, one scalar a head
    (ops/attention.py::MultiHeadAttention.head_gate).
    Feed-forward: ``"swiglu"`` a dense gated SiLU of width ``ff_dim``;
    ``"moe_reglu"`` ``experts`` routed ReGLU experts of width ``expert_dim``,
    ``experts_per_token`` a token, dropless (ops/moe.py::ExpertsReGLU), whose
    router reads the layer's INPUT (before the norm and the mixer, so the
    two halves of a layer are no longer independent);
    ``"moe_swiglu_shared"`` routed SwiGLU experts of width ``expert_dim``
    whose router reads the sublayer's NORMED input, its weights renormalised
    over the chosen and scaled by ``route_scale``, beside ``shared_experts``
    experts that every token takes
    (ops/moe.py::ExpertsSwiGLUShared), each expert ``expert_act``
    ("swiglu", or "relu2": ``W_down relu(W_up m)^2`` with no gate bank) and
    the shared ones ``shared_dim`` wide (0: ``shared_experts x
    expert_dim``); ``experts`` stays the router's width
    and ``experts_held`` (0: all) banks exist here, experts ``experts_first``
    onwards: the share of a deployment that splits each layer's experts over
    devices.  ``scoring`` says how a routed layer scores its experts
    (ops/moe.py::route): ``"softmax"`` (``"moe_reglu"`` knows no other), or
    ``"sigmoid"`` with a selection bias in the choice alone; unstated, as in
    a configuration written before the field, it is what the family of the
    ``ff`` had then (``"sigmoid"`` for ``"moe_swiglu_shared"``).  The first
    ``dense_layers`` layers take the dense ``"swiglu"``
    of ``ff_dim`` whatever ``ff`` says (:meth:`ff_kind`).  ``tied_table``:
    one table for the embedding and the head, or (False) a table and a
    separate ``head`` (``models/dalle.py``).  A rotary trunk takes no
    position embedding from DALL-E's client.  Built from a plain dict (a
    checkpoint's hparams, a benchmark configuration)."""

    mixers: Tuple[str, ...]
    ff_dim: int = 0
    kv_heads: int = 1
    norm: str = "rms"
    norm_eps: float = 1e-6
    ff: str = "swiglu"
    ssm_expand: int = 2
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_dt_rank: int = 160
    param_dtype: str = "bfloat16"     # matrices and the table; gains stay f32
    window: int = 0
    rope_theta: float = 10000.0
    experts: int = 0
    experts_per_token: int = 0
    expert_dim: int = 0
    tied_table: bool = True
    norm_at: str = "input"
    qk_norm: bool = False
    lin_key_dim: int = 0
    lin_value_dim: int = 0
    lin_conv: int = 4
    q_rank: int = 0
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    value_dim: int = 0
    dense_layers: int = 0
    experts_held: int = 0
    experts_first: int = 0
    shared_experts: int = 0
    route_scale: float = 1.0
    scoring: str = ""
    window_heads: int = 0
    global_rope_theta: float = 0.0
    global_rope_fraction: float = 1.0
    yarn_factor: float = 0.0
    yarn_original_len: int = 0
    head_gate: bool = False
    sublayers: int = 2
    ssd_heads: int = 0
    ssd_head_dim: int = 0
    ssd_groups: int = 0
    ssd_chunk: int = 128
    expert_act: str = "swiglu"
    shared_dim: int = 0

    def __post_init__(self):
        object.__setattr__(self, "mixers", tuple(self.mixers))
        if not self.scoring:
            object.__setattr__(self, "scoring", (
                "sigmoid" if self.ff == "moe_swiglu_shared" else "softmax"))
        assert self.mixers and set(self.mixers) <= set(MIXERS), (
            f"trunk mixers {self.mixers} outside {MIXERS}")
        assert self.norm == "rms" and self.ff in FFS, (
            f"trunk norm {self.norm!r} / ff {self.ff!r}: only 'rms' and "
            f"{FFS} blocks exist")
        assert self.param_dtype in ("bfloat16", "float32"), self.param_dtype
        assert ("window" in self.mixers) == (self.window > 0), (
            f"'window' layers need a window and a window needs them: "
            f"{self.mixers}, window {self.window}")
        assert ("gdn" in self.mixers) == (
            self.lin_key_dim > 0 and self.lin_value_dim > 0), (
            f"'gdn' layers need lin_key_dim and lin_value_dim, which need "
            f"them: {self.mixers}, {self.lin_key_dim}, {self.lin_value_dim}")
        latent = (self.q_rank, self.kv_rank, self.nope_dim, self.rope_dim,
                  self.value_dim)
        assert (all(d > 0 for d in latent) if "mla" in self.mixers
                else not any(latent)), (
            f"'mla' layers need q_rank, kv_rank, nope_dim, rope_dim and "
            f"value_dim, which need them: {self.mixers}, {latent}")
        assert self.window_heads >= 0 and (
            not self.window_heads or "window" in self.mixers), (
            f"window_heads belong to 'window' layers: {self.mixers}")
        assert ("rotated" in self.mixers) == (self.global_rope_theta > 0), (
            f"'rotated' layers need global_rope_theta and it needs them: "
            f"{self.mixers}, {self.global_rope_theta}")
        assert 0 < self.global_rope_fraction <= 1, self.global_rope_fraction
        assert not self.yarn_factor or "rotated" in self.mixers, (
            "YaRN scales a 'rotated' layer's rotation")
        assert self.norm_at in NORM_AT, self.norm_at
        assert self.norm_at == "input" or (
            not {"mamba", "mamba2", "mla", NO_MIXER} & set(self.mixers)
            and self.ff == "swiglu" and self.sublayers == 2), (
            "norm_at = 'output' closes attention, 'gdn' and swiglu "
            f"sublayers only: {self.mixers}, ff {self.ff!r}")
        assert self.sublayers in (1, 2), self.sublayers
        ssd = (self.ssd_heads, self.ssd_head_dim, self.ssd_groups)
        assert (all(d > 0 for d in ssd) if "mamba2" in self.mixers
                else not any(ssd)), (
            f"'mamba2' layers need ssd_heads, ssd_head_dim and ssd_groups, "
            f"which need them: {self.mixers}, {ssd}")
        assert not self.ssd_groups or self.ssd_heads % self.ssd_groups == 0, (
            f"{self.ssd_heads} heads in {self.ssd_groups} groups")
        assert self.expert_act in EXPERT_ACTS, self.expert_act
        shared = self.ff == "moe_swiglu_shared"
        assert shared or not (self.dense_layers or self.experts_held
                              or self.experts_first or self.shared_experts
                              or self.route_scale != 1.0
                              or self.scoring == "sigmoid"
                              or self.expert_act != "swiglu"
                              or self.shared_dim), (
            "dense_layers, experts_held, experts_first, shared_experts, "
            "route_scale, sigmoid scoring, expert_act and shared_dim belong "
            f"to ff = 'moe_swiglu_shared', not {self.ff!r}")
        assert self.scoring in SCORINGS, self.scoring
        if not self.routed or self.dense_layers:
            assert self.ff_dim > 0, "a swiglu feed-forward needs ff_dim"
        if self.routed:
            assert (0 < self.experts_per_token <= self.experts
                    and self.expert_dim > 0), (
                f"{self.ff} needs experts >= experts_per_token > 0 and an "
                f"expert_dim: {self.experts}, {self.experts_per_token}, "
                f"{self.expert_dim}")
            assert (self.experts_first >= 0 and self.experts_first
                    + self.held_experts <= self.experts), (
                f"experts held {self.experts_first}.."
                f"{self.experts_first + self.held_experts} outside the "
                f"router's {self.experts}")

    def mixer(self, layer: int) -> str:
        return self.mixers[layer % len(self.mixers)]

    def ff_kind(self, layer: int) -> Optional[str]:
        """Layer ``layer``'s feed-forward: the leading ``dense_layers`` are
        dense SwiGLUs, the rest ``ff``; None where the layer is its mixer
        alone (``sublayers`` 1)."""
        if self.sublayers == 1 and self.mixer(layer) != NO_MIXER:
            return None
        return "swiglu" if layer < self.dense_layers else self.ff

    def routed_layers(self, depth: int) -> int:
        """How many of ``depth`` layers route their tokens to experts."""
        return sum(is_routed(self.ff_kind(i)) for i in range(depth))

    @property
    def expert_matrices(self) -> int:
        """Weight matrices an expert of a routed layer holds."""
        return 2 if self.expert_act == "relu2" else 3

    @property
    def rotary(self) -> bool:
        """Some layer rotates its queries and keys."""
        return any(is_rotary(kind) for kind in self.mixers)

    @property
    def routed(self) -> bool:
        """Some layer routes its tokens to experts."""
        return is_routed(self.ff)

    @property
    def held_experts(self) -> int:
        """Expert banks a routed layer holds here (all, unless told)."""
        return self.experts_held or self.experts

    @property
    def yarn(self) -> Optional[YaRN]:
        """A "rotated" layer's YaRN scaling, or None (a plain rotation)."""
        if not self.yarn_factor:
            return None
        return YaRN(self.yarn_factor, self.yarn_original_len)


def is_rotary(kind: str) -> bool:
    """A layer of this mixer kind rotates its queries and keys itself."""
    return kind in ROTARY_MIXERS


def is_routed(ff: str) -> bool:
    """A feed-forward of this kind routes its tokens to experts."""
    return ff in ROUTED_FFS


def layer_mixers(trunk: Optional[TrunkSpec], depth: int) -> Tuple[str, ...]:
    """Each layer's mixer kind, which is also the kind of its decode state:
    ``(k, v)`` over every position for "attention", ``(k, v)`` over a ring
    of the window's length for "window", ``(window, h)`` for "mamba" and
    "mamba2", ``(window, S)`` for "gdn" (:func:`is_recurrent` tells the
    last three from the others), ``(c, k_rope)`` over every position for
    "mla" (:func:`is_latent`: no head axis, the positions on axis 1), none
    for "none" (:func:`is_stateless`)."""
    if trunk is None:
        return ("attention",) * depth
    return tuple(trunk.mixer(i) for i in range(depth))


def is_stateless(kind: str) -> bool:
    """A layer of this mixer kind has no mixer, and so no decode state: no
    leaf at all in the per-layer caches (an entry of None there)."""
    return kind == NO_MIXER


def is_recurrent(kind: str) -> bool:
    """A layer of this mixer kind carries a recurrent state through decode
    (``(window, state)``: rows on axis 0, no position axis, replaced whole
    at every step), not a cache of keys and values."""
    return kind in RECURRENT_MIXERS


def caches_positions(kind: str) -> bool:
    """A layer of this mixer kind caches something a position (keys and
    values, a ring of them, a latent): neither a recurrent state nor
    nothing."""
    return not (is_recurrent(kind) or is_stateless(kind))


def is_latent(kind: str) -> bool:
    """A layer of this mixer kind caches one normed latent and one rotated
    key a position (``(c [rows, slots, kv_rank], k_rope [rows, slots,
    rope_dim])``: ops/latent_attention.py), not keys and values a head."""
    return kind == "mla"


def cache_position_axis(kind: str) -> int:
    """The position axis of a non-recurrent layer's cache arrays."""
    return 1 if is_latent(kind) else 2


def layer_cache_lens(trunk: Optional[TrunkSpec], depth: int,
                     seq_len: int) -> Tuple[int, ...]:
    """Slots of each layer's key/value cache: ``seq_len``, or ``min(window,
    seq_len)`` for a "window" layer (a ring: position p in slot ``p mod
    window``); 0 for a layer that keeps no keys."""
    return tuple(0 if not caches_positions(kind) else
                 min(trunk.window, seq_len) if kind == "window" else seq_len
                 for kind in layer_mixers(trunk, depth))


def layerscale_init(layer_index: int) -> float:
    """ref transformer.py:28-42 (arg is 1-based layer index)."""
    if layer_index <= 18:
        return 0.1
    if layer_index <= 24:
        return 1e-5
    return 1e-6


class AttnBlock(nn.Module):
    """LayerScale(PreNorm(attention)) (ref transformer.py:111-113)."""

    pattern: AttnPattern
    dim: int
    layer_index: int
    heads: int = 8
    dim_head: int = 64
    dropout: float = 0.0
    ring_axis: Optional[str] = None
    sp_impl: str = "ring"
    aligned_span_decode: bool = True
    dtype: Any = jnp.float32

    def setup(self):
        self.norm = nn.LayerNorm(dtype=jnp.float32, name="norm")
        self.attn = MultiHeadAttention(
            pattern=self.pattern, dim=self.dim, heads=self.heads,
            dim_head=self.dim_head, dropout=self.dropout,
            ring_axis=self.ring_axis,
            sp_impl=self.sp_impl,
            aligned_span_decode=self.aligned_span_decode, dtype=self.dtype,
            name="attn",
        )
        self.scale = self.param(
            "scale",
            lambda key, shape: jnp.full(shape, layerscale_init(self.layer_index)),
            (1, 1, self.dim),
        )

    def __call__(self, x, mask=None, deterministic: bool = True,
                 return_kv: bool = False):
        with prof.scope("attn-qkv"):
            normed = self.norm(x).astype(x.dtype)
        out = self.attn(normed, mask=mask,
                        deterministic=deterministic, return_kv=return_kv)
        if return_kv:
            h, kv = out
            with prof.scope("attn-out"):
                return h * self.scale.astype(h.dtype), kv
        with prof.scope("attn-out"):
            return out * self.scale.astype(out.dtype)

    def decode_step(self, x, cache_k, cache_v, index, mask=None,
                    write_pos=None, qw=None):
        with prof.scope("attn-qkv"):
            normed = self.norm(x).astype(x.dtype)
        h, ck, cv = self.attn.decode_step(
            normed, cache_k, cache_v, index, mask=mask,
            write_pos=write_pos, qw=qw
        )
        with prof.scope("attn-out"):
            return h * self.scale.astype(h.dtype), ck, cv


class FFBlock(nn.Module):
    """LayerScale(PreNorm(GEGLU feed-forward)) (ref transformer.py:53-69)."""

    dim: int
    layer_index: int
    mult: int = 4
    dropout: float = 0.0
    dtype: Any = jnp.float32

    def setup(self):
        inner = int(self.dim * self.mult)
        self.norm = nn.LayerNorm(dtype=jnp.float32, name="norm")
        self.dense_in = nn.Dense(inner * 2, dtype=self.dtype, name="dense_in")
        self.dense_out = nn.Dense(self.dim, dtype=self.dtype, name="dense_out")
        self.drop = nn.Dropout(self.dropout)
        self.scale = self.param(
            "scale",
            lambda key, shape: jnp.full(shape, layerscale_init(self.layer_index)),
            (1, 1, self.dim),
        )

    def __call__(self, x, deterministic: bool = True, qw=None):
        """``qw`` (decode path only, ``weights_int8``): this layer's
        session-quantized kernels ``{"ff_in": (int8, scale, bias),
        "ff_out": ...}`` — the GEGLU runs with int8 multiplicands and f32
        accumulation instead of touching the f32 params."""
        from .quant import qdense

        with prof.scope("ff"):
            normed = self.norm(x).astype(x.dtype)
            if qw is not None:
                h = qdense(normed, *qw["ff_in"]).astype(x.dtype)
            else:
                h = self.dense_in(normed)
            h, gates = jnp.split(h, 2, axis=-1)
            h = h * nn.gelu(gates)
            h = self.drop(h, deterministic=deterministic)
            if qw is not None:
                h = qdense(h, *qw["ff_out"]).astype(x.dtype)
            else:
                h = self.dense_out(h)
            return h * self.scale.astype(h.dtype)


class RMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * scale`` in float32."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        return rms_norm(x, scale, self.eps)


class TrunkAttnBlock(nn.Module):
    """PreNorm(attention) of a :class:`TrunkSpec` trunk: RMSNorm, ``heads``
    queries over ``kv_heads`` keys and values, no bias, no LayerScale.  Same
    calls as :class:`AttnBlock`.  Without ``prenorm`` the attention reads
    its input as it comes (the trunk norms the output:
    :meth:`Transformer._residual`)."""

    pattern: AttnPattern
    dim: int
    heads: int
    dim_head: int
    kv_heads: int
    eps: float = 1e-6
    # a rotary layer's rotation (MultiHeadAttention's fields; a "window"
    # layer's window is the pattern's); prenorm: the block norms its own
    # input; qk_norm and head_gate: TrunkSpec's
    rope_theta: Optional[float] = None
    rope_dim: Optional[int] = None
    rope_yarn: Optional[YaRN] = None
    prenorm: bool = True
    qk_norm: bool = False
    head_gate: bool = False
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def setup(self):
        if self.prenorm:
            self.norm = RMSNorm(self.eps, name="norm")
        self.attn = MultiHeadAttention(
            pattern=self.pattern, dim=self.dim, heads=self.heads,
            dim_head=self.dim_head, kv_heads=self.kv_heads, use_bias=False,
            rope_theta=self.rope_theta, rope_dim=self.rope_dim,
            rope_yarn=self.rope_yarn, qk_norm=self.qk_norm,
            head_gate=self.head_gate, norm_eps=self.eps, dtype=self.dtype,
            param_dtype=self.param_dtype, name="attn")

    def _normed(self, x):
        if not self.prenorm:
            return x
        with prof.scope("attn-qkv"):
            return self.norm(x).astype(x.dtype)

    def __call__(self, x, mask=None, deterministic: bool = True,
                 return_kv: bool = False):
        return self.attn(self._normed(x), mask=mask, return_kv=return_kv)

    def decode_step(self, x, cache_k, cache_v, index, mask=None,
                    write_pos=None, qw=None):
        return self.attn.decode_step(self._normed(x), cache_k, cache_v,
                                     index, mask=mask, write_pos=write_pos)


class TrunkSSMBlock(nn.Module):
    """PreNorm(state-space mixer) of a :class:`TrunkSpec` trunk: ``kind``
    ``"mamba"`` a Mamba mixer (``ssm``, scope ``ssm-proj``), ``"mamba2"`` a
    Mamba-2 mixer (``ssd``, scope ``ssd-proj``; ops/ssm.py).  Its decode
    state ``(window, h)`` rides where an attention layer's ``(k, v)`` does;
    it has no position axis, so ``index``, ``mask`` and ``write_pos`` mean
    nothing to it."""

    dim: int
    spec: TrunkSpec
    kind: str = "mamba"
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def setup(self):
        spec = self.spec
        kw = dict(dim=self.dim, state=spec.ssm_state, conv=spec.ssm_conv,
                  eps=spec.norm_eps, dtype=self.dtype,
                  param_dtype=self.param_dtype)
        self.norm = RMSNorm(spec.norm_eps, name="norm")
        if self.kind == "mamba2":
            self.mixer = Mamba2Mixer(
                heads=spec.ssd_heads, head_dim=spec.ssd_head_dim,
                groups=spec.ssd_groups, chunk=spec.ssd_chunk, name="ssd",
                **kw)
        else:
            self.mixer = MambaMixer(expand=spec.ssm_expand,
                                    dt_rank=spec.ssm_dt_rank, name="ssm", **kw)

    def _normed(self, x):
        with prof.scope("ssd-proj" if self.kind == "mamba2" else "ssm-proj"):
            return self.norm(x).astype(x.dtype)

    def __call__(self, x, mask=None, deterministic: bool = True,
                 return_kv: bool = False):
        return self.mixer(self._normed(x), return_state=return_kv)

    def decode_step(self, x, window, h, index, mask=None, write_pos=None,
                    qw=None):
        return self.mixer.decode_step(self._normed(x), window, h)

    def init_state(self, batch: int):
        return self.mixer.init_state(batch)


class TrunkLinearBlock(nn.Module):
    """PreNorm(gated-delta-rule mixer) of a :class:`TrunkSpec` trunk
    (ops/linear_attention.py), or the mixer on its input as it comes where
    the trunk norms the output.  Its decode state ``(window, S)`` rides
    where a state-space layer's ``(window, h)`` does, and takes the same
    calls as :class:`TrunkSSMBlock`."""

    dim: int
    heads: int
    spec: TrunkSpec
    prenorm: bool = True
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def setup(self):
        if self.prenorm:
            self.norm = RMSNorm(self.spec.norm_eps, name="norm")
        self.gdn = GatedDeltaMixer(
            dim=self.dim, heads=self.heads, key_dim=self.spec.lin_key_dim,
            value_dim=self.spec.lin_value_dim, conv=self.spec.lin_conv,
            eps=self.spec.norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, name="gdn")

    def _normed(self, x):
        if not self.prenorm:
            return x
        with prof.scope("gdn-proj"):
            return self.norm(x).astype(x.dtype)

    def __call__(self, x, mask=None, deterministic: bool = True,
                 return_kv: bool = False):
        return self.gdn(self._normed(x), return_state=return_kv)

    def decode_step(self, x, window, S, index, mask=None, write_pos=None,
                    qw=None):
        return self.gdn.decode_step(self._normed(x), window, S)

    def init_state(self, batch: int):
        return self.gdn.init_state(batch)


class TrunkLatentBlock(nn.Module):
    """PreNorm(latent attention) of a :class:`TrunkSpec` trunk
    (ops/latent_attention.py).  Its decode state ``(c, k_rope)`` rides where
    an attention layer's ``(k, v)`` does and takes the same calls as
    :class:`TrunkAttnBlock`; the arrays have no head axis (:func:`is_latent`)."""

    pattern: AttnPattern
    dim: int
    heads: int
    spec: TrunkSpec
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def setup(self):
        spec = self.spec
        self.norm = RMSNorm(spec.norm_eps, name="norm")
        self.mla = LatentAttention(
            pattern=self.pattern, dim=self.dim, heads=self.heads,
            q_rank=spec.q_rank, kv_rank=spec.kv_rank, nope_dim=spec.nope_dim,
            rope_dim=spec.rope_dim, value_dim=spec.value_dim,
            rope_theta=spec.rope_theta, eps=spec.norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, name="mla")

    def _normed(self, x):
        with prof.scope("mla-proj"):
            return self.norm(x).astype(x.dtype)

    def __call__(self, x, mask=None, deterministic: bool = True,
                 return_kv: bool = False):
        return self.mla(self._normed(x), mask=mask, return_kv=return_kv)

    def decode_step(self, x, cache_c, cache_kr, index, mask=None,
                    write_pos=None, qw=None):
        return self.mla.decode_step(self._normed(x), cache_c, cache_kr,
                                    index, mask=mask, write_pos=write_pos)


class SwiGLUBlock(nn.Module):
    """PreNorm(``W_down(silu(W_gate h) * (W_up h))``) of a
    :class:`TrunkSpec` trunk: RMSNorm, no bias, no LayerScale; without
    ``prenorm`` on its input as it comes (the trunk norms the output)."""

    dim: int
    ff_dim: int
    eps: float = 1e-6
    prenorm: bool = True
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def setup(self):
        def dense(features, fan_in, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype,
                            kernel_init=fan_in_normal(fan_in), name=name)

        if self.prenorm:
            self.norm = RMSNorm(self.eps, name="norm")
        self.gate = dense(self.ff_dim, self.dim, "gate")
        self.up = dense(self.ff_dim, self.dim, "up")
        self.down = dense(self.dim, self.ff_dim, "down")

    def __call__(self, x, deterministic: bool = True):
        with prof.scope("ff"):
            h = self.norm(x).astype(x.dtype) if self.prenorm else x
            return self.down(jax.nn.silu(self.gate(h)) * self.up(h))


class TrunkMoEBlock(nn.Module):
    """PreNorm(routed ReGLU experts) of a :class:`TrunkSpec` trunk
    (ops/moe.py::ExpertsReGLU): RMSNorm, no bias, no LayerScale, no
    auxiliary loss.  The router's logits come from the layer's input
    (:meth:`router_logits`, called before the mixer) and are handed back
    past it, so the call takes them beside the hidden state."""

    dim: int
    spec: TrunkSpec
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def setup(self):
        from .moe import ExpertsReGLU

        self.norm = RMSNorm(self.spec.norm_eps, name="norm")
        self.moe = ExpertsReGLU(
            dim=self.dim, experts=self.spec.experts,
            k=self.spec.experts_per_token, expert_dim=self.spec.expert_dim,
            dtype=self.dtype, param_dtype=self.param_dtype, name="moe")

    def router_logits(self, x):
        return self.moe.router_logits(x)

    def __call__(self, x, router_logits):
        with prof.scope("moe-route"):
            normed = self.norm(x).astype(x.dtype)
        return self.moe(normed, router_logits)


class TrunkSharedMoEBlock(nn.Module):
    """PreNorm(routed SwiGLU experts + shared experts) of a
    :class:`TrunkSpec` trunk (ops/moe.py::ExpertsSwiGLUShared), scored as
    the spec states, on the experts the spec says are held here.  The router reads the sublayer's
    normed input, so the block takes the hidden state alone, as
    :class:`SwiGLUBlock` does."""

    dim: int
    spec: TrunkSpec
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def setup(self):
        from .moe import ExpertsSwiGLUShared

        spec = self.spec
        self.norm = RMSNorm(spec.norm_eps, name="norm")
        self.moe = ExpertsSwiGLUShared(
            dim=self.dim, experts=spec.experts, k=spec.experts_per_token,
            expert_dim=spec.expert_dim, held=spec.held_experts,
            first=spec.experts_first, shared=spec.shared_experts,
            shared_dim=spec.shared_dim, act=spec.expert_act,
            scoring=spec.scoring, scale=spec.route_scale, dtype=self.dtype,
            param_dtype=self.param_dtype, name="moe")

    def __call__(self, x, deterministic: bool = True):
        with prof.scope("moe-route"):
            normed = self.norm(x).astype(x.dtype)
        return self.moe(normed)


class MoEFFBlock(nn.Module):
    """LayerScale(PreNorm(MoE feed-forward)) — the FFBlock with its GEGLU
    swapped for a top-k routed expert mixture (ops/moe.py).  The switch
    load-balance loss is sown into the ``losses`` collection as
    ``moe_aux``; training loops read it via ``mutable=['losses']``."""

    dim: int
    layer_index: int
    num_experts: int = 8
    top_k: int = 2
    mult: int = 4
    dropout: float = 0.0
    dispatch: str = "dense"
    capacity_factor: float = 1.25
    capacity_group: int = 1024
    dtype: Any = jnp.float32

    def setup(self):
        from .moe import MoEFeedForward

        self.norm = nn.LayerNorm(dtype=jnp.float32, name="norm")
        self.moe = MoEFeedForward(
            dim=self.dim, num_experts=self.num_experts, top_k=self.top_k,
            mult=self.mult, dropout=self.dropout, dispatch=self.dispatch,
            capacity_factor=self.capacity_factor,
            capacity_group=self.capacity_group, dtype=self.dtype,
            name="moe")
        self.scale = self.param(
            "scale",
            lambda key, shape: jnp.full(shape, layerscale_init(self.layer_index)),
            (1, 1, self.dim),
        )

    def __call__(self, x, deterministic: bool = True):
        with prof.scope("ff"):
            h, aux = self.moe(self.norm(x).astype(x.dtype),
                              deterministic=deterministic)
            self.sow("losses", "moe_aux", aux)
            return h * self.scale.astype(h.dtype)


class Transformer(nn.Module):
    """Depth x (attn, ff) residual stack with cycled attention variants
    (ref transformer.py:71-123); with a ``trunk`` (:class:`TrunkSpec`),
    depth x (mixer, feed-forward) with each layer's mixer global, windowed
    or latent attention, Mamba-1, Mamba-2 or gated-delta-rule linear
    attention and its feed-forward a SwiGLU or routed experts (of either
    kind, after the spec's leading dense layers), the norm on each
    sublayer's input or on its output; a layer may be one of the two alone
    (``TrunkSpec.sublayers``), and the block it lacks is None in
    ``attn_blocks`` / ``ff_blocks``."""

    dim: int
    depth: int
    seq_len: int
    causal: bool = True
    heads: int = 8
    dim_head: int = 64
    ff_mult: int = 4
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    attn_types: Optional[Tuple[str, ...]] = None
    image_fmap_size: Optional[int] = None
    text_len: Optional[int] = None     # text positions incl <bos>
    reversible: bool = False
    reversible_naive: bool = False  # test hook: plain-autodiff two-stream
    use_remat: bool = False
    ring_axis: Optional[str] = None  # sequence-parallel axis (inside shard_map)
    sp_impl: str = "ring"            # 'ring' | 'ulysses' (all-to-all)
    aligned_span_decode: bool = True  # serve-path circular reads as spans
    ff_experts: int = 0        # >1: MoE feed-forward with this many experts
    ff_expert_top_k: int = 2
    ff_expert_dispatch: str = "dense"        # 'dense' | 'capacity'
    ff_expert_capacity_factor: float = 1.25
    ff_expert_capacity_group: int = 1024
    sparse_layout_seed: int = 0
    trunk: Optional[TrunkSpec] = None  # per-layer block spec; None = above
    dtype: Any = jnp.float32

    @property
    def mixers(self) -> Tuple[str, ...]:
        return layer_mixers(self.trunk, self.depth)

    @property
    def cache_lens(self) -> Tuple[int, ...]:
        return layer_cache_lens(self.trunk, self.depth, self.seq_len)

    def setup(self):
        attn_types = cast_tuple(default(self.attn_types, ("full",)))
        fmap = default(self.image_fmap_size, 0)
        text_len = default(
            self.text_len,
            self.seq_len + 1 - fmap * fmap if fmap else self.seq_len + 1,
        )
        attn_blocks = []
        ff_blocks = []
        mixer_norms, ff_norms = [], []
        for ind in range(self.depth):
            variant = attn_types[ind % len(attn_types)]
            pattern = AttnPattern(
                variant=variant, seq_len=self.seq_len, text_len=text_len,
                fmap=fmap, causal=self.causal,
                layout_seed=self.sparse_layout_seed + ind,
            )
            if self.trunk is not None:
                spec = self.trunk
                kw = dict(dim=self.dim, dtype=self.dtype,
                          param_dtype=jnp.dtype(spec.param_dtype))
                kind = spec.mixer(ind)
                prenorm = spec.norm_at == "input"
                if not prenorm:
                    mixer_norms.append(RMSNorm(
                        spec.norm_eps, name=f"layers_{ind}_mixer_norm"))
                    ff_norms.append(RMSNorm(
                        spec.norm_eps, name=f"layers_{ind}_ff_norm"))
                if is_stateless(kind):
                    attn_blocks.append(None)
                elif kind in ("mamba", "mamba2"):
                    attn_blocks.append(TrunkSSMBlock(
                        spec=spec, kind=kind, name=f"layers_{ind}_"
                        + ("ssd" if kind == "mamba2" else "ssm"), **kw))
                elif kind == "gdn":
                    attn_blocks.append(TrunkLinearBlock(
                        heads=self.heads, spec=spec, prenorm=prenorm,
                        name=f"layers_{ind}_gdn", **kw))
                elif is_latent(kind):
                    attn_blocks.append(TrunkLatentBlock(
                        pattern=pattern, heads=self.heads, spec=spec,
                        name=f"layers_{ind}_attn", **kw))
                else:
                    windowed = kind == "window"
                    rotated = kind == "rotated"
                    attn_blocks.append(TrunkAttnBlock(
                        pattern=dataclasses.replace(
                            pattern, window=spec.window) if windowed
                        else pattern,
                        heads=spec.window_heads or self.heads if windowed
                        else self.heads,
                        dim_head=self.dim_head, kv_heads=spec.kv_heads,
                        eps=spec.norm_eps,
                        rope_theta=(spec.rope_theta if windowed else
                                    spec.global_rope_theta if rotated
                                    else None),
                        rope_dim=int(self.dim_head
                                     * spec.global_rope_fraction)
                        if rotated and spec.global_rope_fraction < 1
                        else None,
                        rope_yarn=spec.yarn if rotated else None,
                        prenorm=prenorm, qk_norm=spec.qk_norm,
                        head_gate=spec.head_gate,
                        name=f"layers_{ind}_attn", **kw))
                ff_kind = spec.ff_kind(ind)
                if ff_kind is None:
                    ff_blocks.append(None)
                elif ff_kind == "moe_reglu":
                    ff_blocks.append(TrunkMoEBlock(
                        spec=spec, name=f"layers_{ind}_ff", **kw))
                elif ff_kind == "moe_swiglu_shared":
                    ff_blocks.append(TrunkSharedMoEBlock(
                        spec=spec, name=f"layers_{ind}_ff", **kw))
                else:
                    ff_blocks.append(SwiGLUBlock(
                        ff_dim=spec.ff_dim, eps=spec.norm_eps,
                        prenorm=prenorm, name=f"layers_{ind}_ff", **kw))
                continue
            attn_blocks.append(AttnBlock(
                pattern=pattern, dim=self.dim, layer_index=ind + 1,
                heads=self.heads, dim_head=self.dim_head,
                dropout=self.attn_dropout,
                ring_axis=self.ring_axis, sp_impl=self.sp_impl,
                aligned_span_decode=self.aligned_span_decode,
                dtype=self.dtype,
                name=f"layers_{ind}_attn",
            ))
            if self.ff_experts > 1:
                ff_blocks.append(MoEFFBlock(
                    dim=self.dim, layer_index=ind + 1,
                    num_experts=self.ff_experts, top_k=self.ff_expert_top_k,
                    mult=self.ff_mult, dropout=self.ff_dropout,
                    dispatch=self.ff_expert_dispatch,
                    capacity_factor=self.ff_expert_capacity_factor,
                    capacity_group=self.ff_expert_capacity_group,
                    dtype=self.dtype, name=f"layers_{ind}_ff",
                ))
            else:
                ff_blocks.append(FFBlock(
                    dim=self.dim, layer_index=ind + 1, mult=self.ff_mult,
                    dropout=self.ff_dropout, dtype=self.dtype,
                    name=f"layers_{ind}_ff",
                ))
        self.attn_blocks = attn_blocks
        self.ff_blocks = ff_blocks
        # a trunk that norms each sublayer's OUTPUT: one gain per sublayer,
        # owned here and applied in :meth:`_residual` alone
        self.mixer_norms = mixer_norms
        self.ff_norms = ff_norms

    def _residual(self, x, ind: int, h, ff: bool = False):
        """The stream after layer ``ind``'s mixer (or, ``ff``, feed-forward)
        output ``h``: ``x + h``, or ``x + Norm(h)`` where the trunk norms
        outputs, under the scope of the sublayer the norm closes."""
        norms = self.ff_norms if ff else self.mixer_norms
        if not norms:
            return x + h
        scope = ("ff" if ff else
                 "gdn-proj" if self.mixers[ind] == "gdn" else "attn-out")
        with prof.scope(scope):
            return x + norms[ind](h).astype(x.dtype)

    def _block_apply(self, x, ind: int, mask, deterministic: bool):
        """One (attn, ff) residual block — a method so lifted transforms
        (nn.remat) can thread params AND mutable collections (MoE's sown
        aux losses) through it; a raw jax.checkpoint closure would leak
        tracers out of any sown value."""
        routed = self._router_logits(ind, x)
        if self.attn_blocks[ind] is not None:
            x = self._residual(x, ind, self.attn_blocks[ind](
                x, mask=mask, deterministic=deterministic))
        if self.ff_blocks[ind] is None:
            return x
        return self._residual(
            x, ind, self._ff(ind, x, routed, deterministic=deterministic),
            ff=True)

    def _router_logits(self, ind: int, x):
        """A routed layer's router logits, read from the layer's input
        before its mixer runs; None for every other layer."""
        ff = self.ff_blocks[ind]
        return ff.router_logits(x) if isinstance(ff, TrunkMoEBlock) else None

    def _ff(self, ind: int, x, routed, qw=None, deterministic: bool = True):
        """Layer ``ind``'s feed-forward half on the hidden state after its
        mixer, with the logits :meth:`_router_logits` took before it."""
        ff = self.ff_blocks[ind]
        if routed is not None:
            return ff(x, routed)
        if qw is not None:
            return ff(x, qw=qw)
        return ff(x, deterministic=deterministic)

    def __call__(self, x, mask=None, deterministic: bool = True,
                 return_kv: bool = False):
        if self.reversible and not self.is_initializing():
            return self._reversible_call(x, mask, deterministic, return_kv)

        if (self.trunk is not None and self.trunk.routed
                and not self.is_initializing()):
            telemetry.emit("moe", "route", tokens=x.shape[0] * x.shape[1],
                           experts=self.trunk.experts,
                           k=self.trunk.experts_per_token,
                           layers=self.trunk.routed_layers(self.depth),
                           scoring=self.trunk.scoring,
                           experts_held=self.trunk.held_experts,
                           shared_experts=self.trunk.shared_experts)
        use_remat = (self.use_remat and not self.is_initializing()
                     and not return_kv)
        remat_block = nn.remat(
            Transformer._block_apply, static_argnums=(2, 4)) if use_remat else None

        kvs = []
        for ind in range(self.depth):
            if return_kv:
                routed = self._router_logits(ind, x)
                kv = None
                if self.attn_blocks[ind] is not None:
                    h, kv = self.attn_blocks[ind](
                        x, mask=mask, deterministic=deterministic,
                        return_kv=True)
                    x = self._residual(x, ind, h)
                kvs.append(kv)
                if self.ff_blocks[ind] is not None:
                    x = self._residual(
                        x, ind, self._ff(ind, x, routed,
                                         deterministic=deterministic),
                        ff=True)
            elif use_remat:
                x = remat_block(self, x, ind, mask, deterministic)
            else:
                x = self._block_apply(x, ind, mask, deterministic)
        if return_kv:
            return x, kvs
        return x

    def _reversible_call(self, x, mask, deterministic, return_kv: bool = False):
        """Two-stream reversible executor (ref reversible.py:143-157):
        duplicate the channels, run y1 = x1 + f(x2); y2 = x2 + g(y1), output
        the mean of both streams.  O(1) activation memory via custom_vjp."""
        # custom_vjp functions cannot close over traced values, so a (traced)
        # padding mask rides inside the differentiable f-params pytree as a
        # float leaf (where() grads wrt the condition are zero; the cotangent
        # is computed and discarded).
        mask_f = None if mask is None else mask.astype(jnp.float32)
        f_fns, f_params, g_fns, g_params = [], [], [], []
        for attn, ff in zip(self.attn_blocks, self.ff_blocks):
            unbound_attn, attn_vars = attn.unbind()
            unbound_ff, ff_vars = ff.unbind()

            def f_fn(p, h, m=unbound_attn):
                key_mask = None if p.get("mask") is None else p["mask"] > 0.5
                return m.apply({"params": p["params"]}, h, mask=key_mask,
                               deterministic=deterministic)

            def g_fn(p, h, m=unbound_ff):
                return m.apply({"params": p}, h, deterministic=deterministic)

            f_fns.append(f_fn)
            f_params.append({"params": attn_vars["params"], "mask": mask_f})
            g_fns.append(g_fn)
            g_params.append(ff_vars["params"])

        assert deterministic or (self.attn_dropout == 0 and self.ff_dropout == 0), (
            "the reversible executor requires deterministic blocks (no dropout); "
            "the reference replays RNG state instead (reversible.py:20-50)"
        )
        assert self.ff_experts <= 1, (
            "the reversible executor's custom_vjp cannot thread the MoE "
            "load-balance aux losses; sowing would silently no-op"
        )
        if return_kv:
            # prefill path (no grads): run the two-stream loop inline so each
            # attention's k/v can be captured for the KV cache.
            x1 = x2 = x
            kvs = []
            for attn, ff in zip(self.attn_blocks, self.ff_blocks):
                h, kv = attn(x2, mask=mask, deterministic=deterministic,
                             return_kv=True)
                kvs.append(kv)
                x1 = x1 + h
                x2 = x2 + ff(x1, deterministic=deterministic)
            return (x1 + x2) / 2, kvs
        executor = (reversible_sequence_naive if self.reversible_naive
                    else reversible_sequence)
        y1, y2 = executor(
            tuple(f_fns), tuple(g_fns), tuple(f_params), tuple(g_params), x, x
        )
        return (y1 + y2) / 2

    def decode_init_cache(self, batch: int, dtype=None):
        """Zeroed decode state, one pair per layer: ``(k, v)`` ``[b, kv
        heads, slots, dh]`` for an attention layer, ``slots`` its own
        (:attr:`cache_lens`: ``seq_len``, or a ring of the window's length),
        ``(c [b, slots, kv_rank], k_rope [b, slots, rope_dim])`` for a latent
        one, the block's own ``(window, state)`` for a recurrent one
        (ops/ssm.py, ops/linear_attention.py), None for a layer without a
        mixer."""
        dtype = dtype or self.dtype
        kv_heads = self.heads if self.trunk is None else self.trunk.kv_heads

        def pair(slots):
            shape = (batch, kv_heads, slots, self.dim_head)
            return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)

        return [
            None if is_stateless(kind) else
            blk.init_state(batch) if is_recurrent(kind) else
            blk.mla.init_cache(batch, slots, dtype) if is_latent(kind) else
            pair(slots)
            for blk, kind, slots in zip(self.attn_blocks, self.mixers,
                                        self.cache_lens)
        ]

    def lane_dense_caches(self, caches, masked: bool = False):
        """Per-layer caches as ``decode_codes``' scan should carry them
        (MultiHeadAttention.lane_dense_cache): :meth:`decode_step` takes
        either layout, told by the shape.  A recurrent state passes as it
        is, and a stateless layer's None; a latent pair has no head to
        fold, and its layer says whether the two ride as one array
        (LatentAttention.lane_dense_cache: not where the call is ``masked``,
        has a key-padding mask)."""
        return [cache if not caches_positions(kind) else
                blk.mla.lane_dense_cache(*cache, masked) if is_latent(kind)
                else tuple(map(blk.attn.lane_dense_cache, cache))
                for blk, kind, cache in zip(self.attn_blocks, self.mixers,
                                            caches)]

    def arena_forms(self, dtype):
        """Per layer, the form the serving arena stores its caches of
        ``dtype`` in (MultiHeadAttention.arena_form); None for a layer that
        carries a recurrent state or none."""
        return [None if not caches_positions(kind) else
                (blk.mla if is_latent(kind) else blk.attn).arena_form(dtype)
                for blk, kind in zip(self.attn_blocks, self.mixers)]

    def dense_read_bounds(self, dtype, masked: bool = False):
        """Per layer, the prefixes its decode step's dense read of a cache of
        ``dtype`` ends at (MultiHeadAttention.dense_read_bounds, the buckets
        a ``lax.switch`` chooses among; LatentAttention.dense_read_bounds,
        those or, without a key-padding mask, the ends of its one-pass
        read's blocks); None for a layer that reads slices, carries a
        recurrent state or carries none."""
        return [None if not caches_positions(kind) else
                blk.mla.dense_read_bounds(dtype, masked) if is_latent(kind)
                else blk.attn.dense_read_bounds()
                for blk, kind in zip(self.attn_blocks, self.mixers)]

    def decode_step(self, x, caches, index, mask=None, write_pos=None,
                    qweights=None):
        """Single-token pass: x [b, 1, dim], the per-layer decode state
        (``(k, v)`` caches, or ``(window, state)`` for a recurrent layer of
        a ``trunk``: :attr:`mixers` says which), traced absolute position
        `index`.  Returns (out, new_caches).

        ``write_pos`` enables the phase-aligned serving mode (``index``
        may be per-row, caches rotated, one shared physical write column —
        see MultiHeadAttention.decode_step).  ``qweights`` is the
        per-layer list of session-quantized int8 kernels
        (models/dalle.py::quantize_decode_weights) consumed by the
        attention projections and the FF blocks under ``weights_int8``.

        Mirrors the executor the model trains with: residual stack, or the
        reversible two-stream recurrence (whose attention reads the x2
        stream — caches must match what training computed)."""
        qws = qweights if qweights is not None else [None] * self.depth
        new_caches = []
        if self.reversible:
            x1 = x2 = x
            for attn, ff, (ck, cv), qw in zip(self.attn_blocks,
                                              self.ff_blocks, caches, qws):
                h, ck, cv = attn.decode_step(x2, ck, cv, index, mask=mask,
                                             write_pos=write_pos, qw=qw)
                x1 = x1 + h
                # MoE FF blocks take no qw (weights_int8 asserts them away)
                x2 = x2 + (ff(x1, qw=qw) if qw is not None else ff(x1))
                new_caches.append((ck, cv))
            return (x1 + x2) / 2, new_caches
        for ind, (attn, cache, qw) in enumerate(zip(self.attn_blocks, caches,
                                                    qws)):
            routed = self._router_logits(ind, x)
            if attn is not None:
                h, *cache = attn.decode_step(x, *cache, index, mask=mask,
                                             write_pos=write_pos, qw=qw)
                cache = tuple(cache)
                x = self._residual(x, ind, h)
            if self.ff_blocks[ind] is not None:
                x = self._residual(x, ind, self._ff(ind, x, routed, qw=qw),
                                   ff=True)
            new_caches.append(cache)
        return x, new_caches
