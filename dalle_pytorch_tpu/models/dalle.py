"""DALLE — joint text+image autoregressive transformer, TPU-native.

Capability parity with the reference `DALLE`
(`/root/reference/dalle_pytorch/dalle_pytorch.py:289-500`).  Behavioral
invariants preserved (SURVEY.md §7 checklist):

* unique padding token per text position: pad id 0 at position t is remapped
  to ``num_text_tokens + t`` where ``num_text_tokens`` was already extended
  by ``text_seq_len`` (ref :315, :440-441);
* ``<bos>`` = token 0 prepended, text pos-emb over ``text_seq_len + 1``
  (ref :320, :445);
* axial image positional embedding: summed row + column embeddings over the
  ``fmap x fmap`` raster (ref :321, external ``axial_positional_embedding``);
* logits mask forcing text positions -> text vocab, image positions -> image
  vocab (ref :356-367, :480-484); last-token drop when the sequence
  overflows (ref :473-475);
* loss = ``(loss_text + loss_img_weight * loss_img) / (loss_img_weight + 1)``
  (ref :499).

TPU-native redesign:
* the VAE is *not* a submodule: token codes are produced by the (frozen) VAE
  apply outside this module and passed in — keeping DALLE a pure function of
  (params, text, image_codes) so pjit shards it cleanly;
* generation is a jit-compiled prefill + ``lax.scan`` decode loop *with a KV
  cache* — output-equivalent to the reference's full-forward-per-token
  sampler (ref :400-415) but O(n) instead of O(n^2) per token.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics, prof, telemetry
from ..ops.attention import LANES, record_kernel_choices
from ..ops.ssm import fan_in_normal, normal_init
from ..ops.transformer import (RMSNorm, Transformer, TrunkSpec,
                                cache_position_axis, caches_positions,
                                is_latent, is_stateless, layer_cache_lens,
                                layer_mixers)
from ..utils.helpers import (TOP_K_PASSES, max_neg_value, top_k_count,
                             top_k_filter, top_p_filter)


@dataclasses.dataclass(frozen=True)
class DALLEConfig:
    """Ctor-level hyperparameters (mirrors ref DALLE kwargs, dalle_pytorch.py
    :289-306) + the VAE-derived geometry the reference reads off its vae
    submodule (:310-313)."""

    dim: int
    num_text_tokens: int = 10000       # as passed in, before per-position pads
    text_seq_len: int = 256
    depth: int = 8
    heads: int = 8
    dim_head: int = 64
    reversible: bool = False
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    sparse_attn: bool = False
    attn_types: Optional[Tuple[str, ...]] = None
    loss_img_weight: int = 7
    # VAE-derived geometry (ref :310-313)
    num_image_tokens: int = 512
    image_size: int = 256
    image_fmap_size: int = 32
    # TPU-native extras
    use_remat: bool = False
    # MoE feed-forward (model hyperparameters — they change the param tree)
    ff_experts: int = 0        # >1: MoE FF with this many experts
    ff_expert_top_k: int = 2
    ff_aux_weight: float = 0.01  # load-balance aux loss weight in training
    # dispatch mode is execution strategy over the SAME params: 'dense'
    # (every expert sees every token, exact) or 'capacity' (GShard-style
    # fixed slots, FLOPs ∝ top_k·capacity_factor instead of num_experts).
    # Plan fields (below): excluded from checkpoints, CLI-selectable per run
    ff_expert_dispatch: str = "dense"
    ff_expert_capacity_factor: float = 1.25
    # Sequence-parallel execution plan (NOT model hyperparameters: the param
    # tree and the function are identical to the dense model; these only
    # select manual collectives inside a shard_map.  Excluded from to_dict
    # so checkpoints stay topology-free.)
    ring_axis: Optional[str] = None  # mesh axis name, e.g. "sp"
    sp_impl: str = "ring"            # 'ring' | 'ulysses'
    sp_size: int = 1                 # ways of the sp axis (static shard count)
    # Decode-time KV-cache STORAGE dtype: True keeps the caches in bf16 even
    # when activations are f32 (checkpoint-loaded eval models default to
    # f32).  The decode loop is measured HBM-bound on cache traffic
    # (PERF.md: sliced-KV 2.16x), so halving every cache byte is a direct
    # cut to its dominant stream; attention still *accumulates* in f32
    # (ops/attention.py::decode_step computes all q·k dots with
    # preferred_element_type=f32 and softmaxes in f32), so only the stored
    # k/v values round through bf16.  False is the control no cell has
    # judged yet.  No-op when dtype is already bf16.
    kv_cache_bf16: bool = True
    # Int8 cache storage (takes precedence over kv_cache_bf16): the caches
    # become (int8 values, f32 per-head scale) pairs — ops/quant.py layout
    # — halving the dominant decode byte stream AGAIN over bf16.  Scales
    # are computed once at prefill write time (per slot in the serve
    # arena); decode writes saturate under the frozen scale; every dot
    # keeps the int8 tensor as a multiplicand with f32 accumulation
    # (contract_check C2/C3 pin the no-dequant-hoist property).  OFF by
    # default until the queued `gen_int8_ab` wall-clock A/B lands.
    kv_cache_int8: bool = False
    # Int8 decode-path weights: attn/ff projection kernels + the image-
    # phase logits head are quantized ONCE per generate/serve session to
    # int8 with per-output-channel f32 scales (quantize_decode_weights)
    # and the decode program consumes ONLY the quantized copies (jit
    # prunes the unused f32 originals from its arguments) — halving the
    # weight stream that dominates small-batch decode.  Training, prefill
    # and the forward pass are untouched.
    weights_int8: bool = False
    # Serve-path sliced reads through the cache rotation as circular
    # dynamic_slice spans (<=2 per row) instead of a per-key gather —
    # bit-identical (ops/attention.py::_decode_step_aligned); False is
    # the A/B control.
    aligned_span_decode: bool = True
    # Per-layer block spec of another trunk under DALL-E's client (prompt
    # layout, phase mask, sampler, loss): ops/transformer.py::TrunkSpec, or
    # the plain dict it is built from (a checkpoint's hparams, a benchmark
    # configuration).  A model hyperparameter: it changes the parameter
    # tree (RMSNorm / Mamba / grouped-query / SwiGLU or routed-expert
    # layers, and ONE table of ``total_tokens`` rows, tied between the
    # embedding and the head or with a separate ``head``, in place of
    # text_emb / image_emb / to_logits_dense; no position embeddings over a
    # rotary trunk).  None is the 2021 DALL-E block, its parameter names and
    # its programs.
    trunk: Optional[TrunkSpec] = None
    dtype: Any = jnp.float32

    # execution-plan fields stripped from checkpoint hparams (like dtype):
    # they select how the same params are computed, not what the model is
    _PLAN_FIELDS = ("ring_axis", "sp_impl", "sp_size",
                    "ff_expert_dispatch", "ff_expert_capacity_factor",
                    "kv_cache_bf16", "kv_cache_int8", "weights_int8",
                    "aligned_span_decode")
    # execution switches that no longer exist; checkpoints written while
    # they did carry them in their hparams (from_dict drops them)
    _RETIRED_FIELDS = ("use_pallas", "pallas_block_q", "pallas_block_k",
                       "logits_bf16", "onehot_embed", "head_phase_sliced",
                       "sliced_kv_decode")

    def __post_init__(self):
        if isinstance(self.trunk, dict):
            object.__setattr__(self, "trunk", TrunkSpec(**self.trunk))
        if self.trunk is not None:
            # what a trunk's layers have no form for yet: a recurrent state
            # (a state-space vector or a linear-attention matrix a head) or
            # a ring of keys cannot be recomputed backwards (reversible); the
            # trunk's blocks have no int8 kernels (weights_int8: expert banks
            # least of all) and its grouped, rotated, ring or latent caches
            # no int8 layout (kv_cache_int8)
            for field in ("reversible", "weights_int8", "kv_cache_int8",
                          "sparse_attn"):
                assert not getattr(self, field), (
                    f"{field} is not supported over a TrunkSpec trunk (its "
                    "layers carry a recurrent state-space or linear-"
                    "attention state, grouped keys, a ring of them or a "
                    "latent ('mla') in place of them, and routed experts "
                    "('moe_reglu', 'moe_swiglu_shared'); none has that form)")
            assert self.ring_axis is None and self.ff_experts <= 1, (
                "a TrunkSpec trunk runs unsharded in sequence (no ring or "
                "Ulysses form of a windowed, grouped or latent layer) and "
                "routes through its own ff ('moe_reglu', "
                "'moe_swiglu_shared'), not ff_experts")
            assert self.attn_dropout == 0 and self.ff_dropout == 0, (
                "a TrunkSpec trunk has no dropout")
            for heads in (self.heads, self.trunk.window_heads or self.heads):
                assert heads % self.trunk.kv_heads == 0, (
                    heads, self.trunk.kv_heads)
        assert not (self.weights_int8 and self.ff_experts > 1), (
            "weights_int8 quantizes the dense GEGLU kernels; MoE expert "
            "kernels are not supported on the quantized decode path")

    @property
    def image_seq_len(self) -> int:
        return self.image_fmap_size ** 2

    @property
    def total_text_tokens(self) -> int:
        """num_text_tokens + one unique pad id per text position (ref :315)."""
        return self.num_text_tokens + self.text_seq_len

    @property
    def seq_len(self) -> int:
        return self.text_seq_len + self.image_seq_len

    @property
    def total_tokens(self) -> int:
        return self.total_text_tokens + self.num_image_tokens

    @property
    def kv_heads(self) -> int:
        """Key/value heads an attention layer's cache holds."""
        return self.heads if self.trunk is None else self.trunk.kv_heads

    @property
    def mixers(self) -> Tuple[str, ...]:
        """Each layer's mixer, and so the kind of its decode state:
        "attention" carries ``(k, v)`` over every position, "window" over a
        ring of the window's length, "mamba" and "mamba2" ``(window, h)``,
        "gdn" ``(window, S)`` (ops/transformer.py::is_recurrent), "mla" one
        latent and one rotated key a position (``is_latent``), "none"
        nothing (``is_stateless``: the layer is its feed-forward alone)."""
        return layer_mixers(self.trunk, self.depth)

    @property
    def cache_lens(self) -> Tuple[int, ...]:
        """Slots of each layer's key/value cache (0: it keeps none)."""
        return layer_cache_lens(self.trunk, self.depth, self.seq_len)

    @property
    def rotary(self) -> bool:
        """The trunk rotates queries and keys itself, so the client adds no
        learned position embedding (upstream: ``rotary_emb`` makes
        ``text_pos_emb`` / ``image_pos_emb`` ``always(0)``)."""
        return self.trunk is not None and self.trunk.rotary

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.pop("dtype")
        for f in self._PLAN_FIELDS:  # run topology, not model identity
            d.pop(f)
        if d.get("attn_types") is not None:
            d["attn_types"] = list(d["attn_types"])
        if d.get("trunk") is not None:
            d["trunk"]["mixers"] = list(d["trunk"]["mixers"])
        return d

    @classmethod
    def from_dict(cls, d: dict, **overrides) -> "DALLEConfig":
        d = {k: v for k, v in d.items()  # tolerate old ckpts carrying them
             if k not in cls._PLAN_FIELDS + cls._RETIRED_FIELDS}
        if d.get("attn_types") is not None:
            d["attn_types"] = tuple(d["attn_types"])
        d.update(overrides)
        return cls(**d)

    @classmethod
    def from_vae(cls, vae_cfg, **kwargs) -> "DALLEConfig":
        return cls(
            num_image_tokens=vae_cfg.num_tokens,
            image_size=vae_cfg.image_size,
            image_fmap_size=vae_cfg.image_size // (2 ** vae_cfg.num_layers),
            **kwargs,
        )


class PhaseLogits(nn.Module):
    """The joint-vocab logits head, stored as one kernel PER VOCAB PHASE.

    The reference keeps a single ``nn.Linear(total_tokens)`` and masks the
    wrong-phase half to -inf afterwards (dalle_pytorch.py:482-484); here
    the text-vocab and image-vocab column blocks are separate parameters.
    Two wins over a single [dim, total] kernel with interior slicing:

    * **Phase fast paths with no slice op**: ``image_only`` multiplies only
      the image kernel (every sampled position is an image position, so the
      decode path never computes text logits), ``text_only`` mirrors it.
      A per-phase matmul is bit-identical to slicing the full product —
      each output column is an independent dot-row.
    * **Tensor parallelism**: each phase kernel is tp-sharded on ITS OWN
      vocab dim, so the phase boundary is a parameter boundary, never an
      interior slice.  A slice at ``total_text`` (7880 at CUB geometry)
      inside a single tp-sharded kernel can't align with the equal-width
      shard boundaries GSPMD requires, forcing a per-step reshard.

    Joint-vocab callers get ``concat(text, image)`` — XLA folds a
    downstream phase slice of that concat back to the operand, so the
    full-logits path costs the same as before.

    Legacy single-kernel checkpoints are upgraded by
    ``utils.checkpoint.migrate_head_kernels`` (an exact column split).
    """

    total_text: int
    total: int

    @nn.compact
    def __call__(self, x, image_only: bool = False, text_only: bool = False):
        assert not (image_only and text_only)
        num_image = self.total - self.total_text
        # Both phase kernels are created on EVERY call path: a module
        # initialized through a phase-only caller (e.g. prefill's
        # image_only head) must still own the full param tree, or a later
        # full-checkpoint load would find half the head missing.  Unused
        # kernels cost nothing — XLA dead-code-eliminates the untouched
        # matmul inputs from the compiled program.
        phases = {
            "text": (self.param("text_kernel", nn.initializers.lecun_normal(),
                                (x.shape[-1], self.total_text), jnp.float32),
                     self.param("text_bias", nn.initializers.zeros,
                                (self.total_text,), jnp.float32)),
            "image": (self.param("image_kernel",
                                 nn.initializers.lecun_normal(),
                                 (x.shape[-1], num_image), jnp.float32),
                      self.param("image_bias", nn.initializers.zeros,
                                 (num_image,), jnp.float32)),
        }
        wanted = []
        if not image_only:  # text phase wanted
            wanted.append("text")
        if not text_only:   # image phase wanted
            wanted.append("image")
        outs = []
        for phase in wanted:
            kernel, bias = phases[phase]
            outs.append(x @ kernel + bias)
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)


class AxialPositionalEmbedding(nn.Module):
    """Summed per-row + per-column embeddings over the image raster
    (replaces the external ``axial_positional_embedding`` package the
    reference uses at dalle_pytorch.py:6, :321)."""

    dim: int
    fmap: int
    std: float = 1.0

    @nn.compact
    def __call__(self, n: int):
        row = self.param("row", nn.initializers.normal(self.std), (self.fmap, 1, self.dim))
        col = self.param("col", nn.initializers.normal(self.std), (1, self.fmap, self.dim))
        grid = (row + col).reshape(self.fmap * self.fmap, self.dim)
        return grid[:n]


#: init std of a TrunkSpec model's tied table and position embeddings
TABLE_STD = 0.02


def transformer_kwargs(cfg: DALLEConfig) -> dict:
    """The Transformer construction kwargs DALLE uses — exposed so the
    pipeline-parallel trainer can build the identical stage module
    (parallel/pipeline.py) without duplicating this mapping."""
    attn_types = cfg.attn_types
    if attn_types is None:
        # the reference's `sparse_attn` flag selected DeepSpeed's kernel
        # upstream (attention.py:284-342); here it selects the
        # block-sparse pattern for every layer.
        attn_types = ("sparse",) if cfg.sparse_attn else ("full",)
    return dict(
        dim=cfg.dim, depth=cfg.depth, seq_len=cfg.seq_len, causal=True,
        heads=cfg.heads, dim_head=cfg.dim_head,
        attn_dropout=cfg.attn_dropout, ff_dropout=cfg.ff_dropout,
        attn_types=tuple(attn_types), image_fmap_size=cfg.image_fmap_size,
        text_len=cfg.text_seq_len + 1, reversible=cfg.reversible,
        use_remat=cfg.use_remat,
        ring_axis=cfg.ring_axis, sp_impl=cfg.sp_impl,
        aligned_span_decode=cfg.aligned_span_decode,
        ff_experts=cfg.ff_experts, ff_expert_top_k=cfg.ff_expert_top_k,
        ff_expert_dispatch=cfg.ff_expert_dispatch,
        ff_expert_capacity_factor=cfg.ff_expert_capacity_factor,
        trunk=cfg.trunk, dtype=cfg.dtype)


class DALLE(nn.Module):
    cfg: DALLEConfig

    def setup(self):
        cfg = self.cfg
        if cfg.trunk is not None:
            # one table over the joint vocabulary [text ids | per-position
            # pad ids | image codes], embedding and head tied, or a separate
            # head beside it (stored [vocab, dim] like the table, so that a
            # phase's logits read whole rows); the learned position
            # embeddings are DALL-E's, outside the trunk, at the table's
            # scale, and absent over a trunk that rotates
            param_dtype = jnp.dtype(cfg.trunk.param_dtype)
            self.table = nn.Embed(
                cfg.total_tokens, cfg.dim, param_dtype=param_dtype,
                embedding_init=normal_init(TABLE_STD), name="table")
            if not cfg.trunk.tied_table:
                self.head = self.param(
                    "head", fan_in_normal(cfg.dim),
                    (cfg.total_tokens, cfg.dim), param_dtype)
            if not cfg.rotary:
                self.text_pos_emb = nn.Embed(
                    cfg.text_seq_len + 1, cfg.dim,
                    embedding_init=nn.initializers.normal(TABLE_STD),
                    name="text_pos_emb")
                self.image_pos_emb = AxialPositionalEmbedding(
                    cfg.dim, cfg.image_fmap_size, std=TABLE_STD,
                    name="image_pos_emb")
            self.transformer = Transformer(name="transformer",
                                           **transformer_kwargs(cfg))
            self.final_norm = RMSNorm(cfg.trunk.norm_eps, name="final_norm")
            return
        self.text_emb = nn.Embed(cfg.total_text_tokens, cfg.dim,
                                 embedding_init=nn.initializers.normal(1.0),
                                 name="text_emb")
        self.image_emb = nn.Embed(cfg.num_image_tokens, cfg.dim,
                                  embedding_init=nn.initializers.normal(1.0),
                                  name="image_emb")
        self.text_pos_emb = nn.Embed(cfg.text_seq_len + 1, cfg.dim,
                                     embedding_init=nn.initializers.normal(1.0),
                                     name="text_pos_emb")
        self.image_pos_emb = AxialPositionalEmbedding(
            cfg.dim, cfg.image_fmap_size, name="image_pos_emb")
        self.transformer = Transformer(name="transformer",
                                       **transformer_kwargs(cfg))
        self.final_norm = nn.LayerNorm(dtype=jnp.float32, name="final_norm")
        self.to_logits_dense = PhaseLogits(cfg.total_text_tokens,
                                           cfg.total_tokens,
                                           name="to_logits_dense")

    # --- embedding helpers ---

    def _remap_pad_tokens(self, text):
        """Pad id 0 at text position t -> unique id num_text_tokens + t
        (ref :315, :440-441)."""
        cfg = self.cfg
        text_range = jnp.arange(cfg.text_seq_len) + (
            cfg.total_text_tokens - cfg.text_seq_len)
        return jnp.where(text == 0, text_range, text)

    def _embed_text(self, text):
        """Unique-pad remap + <bos> + token/pos embeddings (ref :440-448)."""
        cfg = self.cfg
        assert text.shape[-1] == cfg.text_seq_len, (
            f"text length {text.shape[-1]} != text_seq_len {cfg.text_seq_len}"
        )
        text = jnp.pad(self._remap_pad_tokens(text), ((0, 0), (1, 0)))  # <bos> id 0
        tokens = self._lookup_ids(text, False)
        if not cfg.rotary:
            tokens = tokens + self.text_pos_emb(jnp.arange(text.shape[1]))
        return tokens.astype(cfg.dtype)

    def _lookup_ids(self, ids, image: bool):
        """Token embeddings of text ids or image codes: each phase's own
        table, or its rows of the tied table (image codes after the text
        vocabulary, as the joint logits order them)."""
        cfg = self.cfg
        if cfg.trunk is not None:
            return self.table(ids + cfg.total_text_tokens if image else ids)
        return (self.image_emb if image else self.text_emb)(ids)

    def _embed_image_codes(self, codes):
        emb = self._lookup_ids(codes, True)
        if not self.cfg.rotary:
            emb = emb + self.image_pos_emb(codes.shape[1])
        return emb.astype(self.cfg.dtype)

    @staticmethod
    def _pad_mask_for_bos(mask):
        """Text key-pad mask [b, text_seq_len] -> [b, text_seq_len+1]: after
        <bos> is prepended, mask bit t governs key position t+1; <bos> itself
        is always attendable.  (The reference accepts a mask but drops it in
        forward — `out = self.transformer(tokens)` at dalle_pytorch.py:477;
        we keep the parameter and make it actually correct.)"""
        if mask is None:
            return None
        return jnp.pad(mask, ((0, 0), (1, 0)), constant_values=True)

    def _logits_mask(self, n: int):
        """[n, total_tokens] — True where the logit must be suppressed
        (ref :356-367)."""
        cfg = self.cfg
        seq_range = jnp.arange(n)[:, None]
        logits_range = jnp.arange(cfg.total_tokens)[None, :]
        return (
            ((seq_range >= cfg.text_seq_len) & (logits_range < cfg.total_text_tokens))
            | ((seq_range < cfg.text_seq_len) & (logits_range >= cfg.total_text_tokens))
        )

    # --- main forward (ref :428-500) ---

    def embed_sequence(self, text, image_codes=None):
        """[bos+text | image] token embeddings, truncated to seq_len (ref
        :440-475) — the input to the transformer stack.  Exposed as a
        method so the pipeline-parallel trainer can run embeddings outside
        the pipelined stack (training.py::make_dalle_pp_train_step)."""
        cfg = self.cfg
        with prof.scope("embed"):
            tokens = self._embed_text(text)
            if image_codes is not None and image_codes.shape[1] > 0:
                image_emb = self._embed_image_codes(image_codes)
                tokens = jnp.concatenate([tokens, image_emb], axis=1)
            # drop the final token when the sequence overflows (ref :473-475)
            if tokens.shape[1] > cfg.seq_len:
                tokens = tokens[:, : cfg.seq_len]
            return tokens

    def _head(self, out, image_only: bool = False, text_only: bool = False,
              qhead=None):
        """final-norm (f32) + logits head — shared by the dense loss, the
        sp loss, the inference forward and the prefill/decode paths.
        ``qhead`` (decode only, ``weights_int8``) is the session-quantized
        image-phase kernel ``(int8, scale, bias)``: the head matmul then
        runs the int8 kernel as a direct multiplicand (f32 accumulation),
        bypassing — and letting jit prune — the f32 PhaseLogits params."""
        with prof.scope("logits-head"):
            h = self.final_norm(out.astype(jnp.float32))
            if qhead is not None:
                assert image_only, "quantized head is the decode (image) phase"
                from ..ops.quant import qdense
                return qdense(h, *qhead)  # f32 logits
            if self.cfg.trunk is not None:
                # the wanted phase's rows (DALL-E's phase mask) of the tied
                # table or of the separate head, multiplicands in their
                # dtype, f32 logits
                split = self.cfg.total_text_tokens
                rows = (self.table.embedding if self.cfg.trunk.tied_table
                        else self.head)
                rows = (rows[split:] if image_only else
                        rows[:split] if text_only else rows)
                return jnp.einsum("...d,vd->...v", h.astype(rows.dtype),
                                  rows, preferred_element_type=jnp.float32)
            return self.to_logits_dense(h, image_only=image_only,
                                        text_only=text_only)

    @staticmethod
    def _phase_nll(phase_logits, labels):
        """Per-position negative log-likelihood within one vocab phase."""
        lse = jax.nn.logsumexp(phase_logits, axis=-1)
        ll = jnp.take_along_axis(
            phase_logits, labels[:, :, None], axis=-1)[..., 0]
        return lse - ll

    def loss_from_hidden(self, out, text, image_codes):
        """final-norm + logits head + phase-sliced CE over full-sequence
        transformer output ``out`` [b, n, d] (the second half of the dense
        training forward; also the pipeline trainer's exit path)."""
        cfg = self.cfg
        # Phase-sliced cross-entropy AND head: text positions multiply only
        # the text-vocab kernel columns, image positions only the image-vocab
        # columns, and each phase normalizes within its own vocab.  Identical
        # to the reference's full-head + masked-logits softmax (ref :482-499
        # — masked entries are -inf and vanish from the logsumexp; and a
        # column-sliced dot is bit-identical to slicing the full product)
        # but never materializes the [b, n, total_tokens] logits/logprobs/
        # mask tensors, and skips the cross-phase half of the head matmul:
        # at the CUB geometry that is ~2 x 1.1 GB less HBM traffic and ~9%
        # fewer step FLOPs (utils/profiling.py::dalle_train_flops counts
        # this sliced head).
        T = cfg.text_seq_len
        # labels: next-token over [text[1:], image codes] (ref :489-499)
        text_logits = self._head(out[:, :T], text_only=True)
        img_logits = self._head(out[:, T:], image_only=True)
        with prof.scope("logits-head"):
            loss_text = self._phase_nll(text_logits,
                                        self._remap_pad_tokens(text)).mean()
            loss_img = self._phase_nll(img_logits, image_codes).mean()
            return (loss_text + cfg.loss_img_weight * loss_img) / (cfg.loss_img_weight + 1)

    def _sp_loss(self, text, image_codes, deterministic: bool):
        """Sequence-parallel training loss — runs INSIDE a shard_map over
        ``cfg.ring_axis`` (training.py::make_dalle_sp_train_step).

        Embeddings are computed on the full sequence (cheap: gathers + adds)
        and the local shard sliced off; the transformer — where the FLOPs
        are — sees only ``seq_len / sp_size`` positions per device, with
        ring/Ulysses collectives making attention exact.  The phase CE is
        computed per local position against its *global* phase and label,
        then psum'd, reproducing the dense loss exactly.
        """
        cfg = self.cfg
        S = cfg.sp_size
        tokens = self.embed_sequence(text, image_codes)
        n = tokens.shape[1]
        assert n % S == 0, f"seq_len {n} not divisible by sp_size {S}"
        L = n // S
        idx = jax.lax.axis_index(cfg.ring_axis)
        x = jax.lax.dynamic_slice_in_dim(tokens, idx * L, L, axis=1)

        out = self.transformer(x, deterministic=deterministic)
        logits = self._head(out)               # [b, L, total_tokens]

        T, V_text = cfg.text_seq_len, cfg.total_text_tokens
        pos = idx * L + jnp.arange(L)          # global positions of my shard
        is_text = pos < T
        text_labels = self._remap_pad_tokens(text)
        lab_t = jnp.take(text_labels, jnp.clip(pos, 0, T - 1), axis=1)
        lab_i = jnp.take(image_codes,
                         jnp.clip(pos - T, 0, image_codes.shape[1] - 1), axis=1)

        def phase_ce_sum(phase_logits, labels, sel):
            return jnp.where(sel[None, :],
                             self._phase_nll(phase_logits, labels), 0.0).sum()

        b = text.shape[0]
        with prof.scope("logits-head"):
            sum_t = jax.lax.psum(
                phase_ce_sum(logits[..., :V_text], lab_t, is_text),
                cfg.ring_axis)
            sum_i = jax.lax.psum(
                phase_ce_sum(logits[..., V_text:], lab_i, ~is_text),
                cfg.ring_axis)
            loss_text = sum_t / (b * T)
            loss_img = sum_i / (b * cfg.image_seq_len)
            return (loss_text + cfg.loss_img_weight * loss_img) / (cfg.loss_img_weight + 1)

    def __call__(self, text, image_codes=None, mask=None, return_loss: bool = False,
                 deterministic: bool = True):
        cfg = self.cfg
        if return_loss and cfg.ring_axis is not None and cfg.sp_size > 1 \
                and not self.is_initializing():
            assert image_codes is not None, (
                "when training, image codes must be supplied")
            assert mask is None, (
                "sequence-parallel training does not take a key padding mask")
            return self._sp_loss(text, image_codes, deterministic)

        tokens = self.embed_sequence(text, image_codes)
        n = tokens.shape[1]

        # what each attention layer of this trace runs, said once
        # (ops/attention.py::record_kernel_choices); not for the shape pass
        with (contextlib.nullcontext() if self.is_initializing()
              else record_kernel_choices("dalle")):
            out = self.transformer(tokens, mask=self._pad_mask_for_bos(mask),
                                   deterministic=deterministic)

        if not return_loss:
            logits = self._head(out)
            return jnp.where(self._logits_mask(n)[None],
                             max_neg_value(logits.dtype), logits)

        assert image_codes is not None, "when training, image codes must be supplied"
        return self.loss_from_hidden(out, text, image_codes)

    # --- generation (prefill + decode; ref generate_images :370-426) ---

    def prefill(self, text, prime_codes=None, mask=None):
        """Run the forward over [bos+text (+ primed image codes)], padded to
        the full static seq_len, returning (last-position image-phase
        logits [b, num_image_tokens], caches)."""
        cfg = self.cfg
        with prof.scope("embed"):
            tokens = self._embed_text(text)
            n_pre = tokens.shape[1]
            if prime_codes is not None and prime_codes.shape[1] > 0:
                tokens = jnp.concatenate(
                    [tokens, self._embed_image_codes(prime_codes)], axis=1)
                n_pre = tokens.shape[1]
            pad = cfg.seq_len - tokens.shape[1]
            assert pad >= 0, ("priming must leave at least one image token "
                              "to sample")
            if cfg.trunk is None:
                tokens = jnp.pad(tokens, ((0, 0), (0, pad), (0, 0)))

        out, kvs = self.transformer(tokens, mask=self._pad_mask_for_bos(mask),
                                    return_kv=True)
        if cfg.trunk is not None:
            # the prompt's positions only: a recurrent layer's state is the
            # one after the last of them (a layer without a mixer has none),
            # and an attention layer's keys and
            # values (a latent layer's latent and rotated key) are padded
            # out to the cache's static length along their position axis; a
            # window layer's cache is a ring (position p in slot p mod
            # slots), so a prompt longer than it leaves its last ``slots``
            # positions, rolled to their slots
            def stored(a, slots, axis):
                a = a.astype(jnp.bfloat16) if cfg.kv_cache_bf16 else a
                if n_pre <= slots:
                    pad = [(0, 0)] * a.ndim
                    pad[axis] = (0, slots - n_pre)
                    return jnp.pad(a, pad)
                return jnp.roll(
                    jax.lax.slice_in_dim(a, n_pre - slots, n_pre, axis=axis),
                    (n_pre - slots) % slots, axis=axis)

            with prof.scope("attn-cache"):
                kvs = [kv if not caches_positions(kind) else
                       tuple(stored(a, slots, cache_position_axis(kind))
                             for a in kv)
                       for kind, slots, kv in zip(cfg.mixers, cfg.cache_lens,
                                                  kvs)]
        elif cfg.kv_cache_int8:
            # int8 cache storage: per-head symmetric scales computed HERE,
            # at prefill write time — the one place the whole sequence is
            # in hand — then frozen for the decode writes (ops/quant.py
            # scale-layout contract).  Takes precedence over kv_cache_bf16.
            from ..ops.quant import quantize_per_head
            with prof.scope("attn-cache"):
                kvs = [(quantize_per_head(k), quantize_per_head(v))
                       for k, v in kvs]
        elif cfg.kv_cache_bf16:
            # cache STORAGE dtype only: the decode step re-reads these
            # through f32-accumulating dots (ops/attention.py::decode_step),
            # so this is a pure byte cut on the HBM-bound decode loop
            with prof.scope("attn-cache"):
                kvs = [(k.astype(jnp.bfloat16), v.astype(jnp.bfloat16))
                       for k, v in kvs]
        last = out[:, n_pre - 1 : n_pre]
        logits = self._head(last, image_only=True)
        return logits[:, 0], kvs

    def decode_init_state(self, batch: int):
        """A zero decode state for ``batch`` rows, one pair per layer
        (ops/transformer.py::Transformer.decode_init_cache) in the prefill's
        storage dtypes."""
        cfg = self.cfg
        return self.transformer.decode_init_cache(
            batch, jnp.bfloat16 if cfg.kv_cache_bf16 else cfg.dtype)

    def lane_dense_caches(self, caches, masked: bool = False):
        """The prefill's caches as ``decode_codes``' scan carries them
        (ops/transformer.py::Transformer.lane_dense_caches)."""
        return self.transformer.lane_dense_caches(caches, masked)

    def arena_forms(self, dtype):
        """Per layer, the form the serving arena stores its caches in
        (ops/transformer.py::Transformer.arena_forms)."""
        return self.transformer.arena_forms(dtype)

    def dense_read_bounds(self, masked: bool = False):
        """Per layer, the prefixes the decode step's dense cache read ends
        at (ops/transformer.py::Transformer.dense_read_bounds), the caches
        in the prefill's storage dtype; ``masked``: of a call with a
        key-padding mask."""
        cfg = self.cfg
        return self.transformer.dense_read_bounds(
            jnp.bfloat16 if cfg.kv_cache_bf16 else cfg.dtype, masked)

    def decode_step(self, code, caches, index, mask=None, write_pos=None,
                    qweights=None):
        """One sampled image code in, next-position logits out.

        `code` [b] is the image-vocab token at *input* position `index`
        (traced); returns ([b, num_image_tokens] image-phase logits, new
        caches) — text logits would be -inf here (ref mask :482-484) and
        are never computed.

        With ``write_pos`` (the serving arena's phase-aligned mode, see
        ops/attention.py), ``index`` may be a per-row [b] vector — every
        row decodes at its own depth against rotated caches that all
        write the same physical column.

        ``qweights`` (``weights_int8``) is the session-quantized weight
        tree from :func:`quantize_decode_weights`; the attention/FF
        projections and the image head then run int8 multiplicands with
        f32 accumulation instead of streaming the f32 params."""
        cfg = self.cfg
        with prof.scope("decode-step"):
            with prof.scope("embed"):
                emb = self._lookup_ids(code[:, None], True)
                img_index = index - (cfg.text_seq_len + 1)
                if cfg.rotary:
                    pass    # the trunk's own layers take the position
                elif jnp.ndim(index) > 0:
                    # per-row positions: gather each row's pos-emb (clipped
                    # like dynamic_slice clamps — idle serve slots park out
                    # of range)
                    pos_grid = self.image_pos_emb(cfg.image_seq_len)
                    rows = jnp.clip(img_index, 0, cfg.image_seq_len - 1)
                    emb = emb + jnp.take(pos_grid, rows, axis=0)[:, None]
                else:
                    pos_grid = self.image_pos_emb(cfg.image_seq_len)
                    emb = emb + jax.lax.dynamic_slice_in_dim(
                        pos_grid, img_index, 1, axis=0)[None]
                x = emb.astype(cfg.dtype)
            out, caches = self.transformer.decode_step(
                x, caches, index, mask=self._pad_mask_for_bos(mask),
                write_pos=write_pos,
                qweights=None if qweights is None else qweights["layers"])
            logits = self._head(out, image_only=True,
                                qhead=None if qweights is None
                                else qweights["head"])
            return logits[:, 0], caches


def quantize_decode_weights(params, cfg: DALLEConfig):
    """One-shot int8 quantization of every decode-path weight matrix —
    the ``weights_int8`` half of the quantized-serving recipe.

    Run ONCE per generate/serve session (the serve arena does it at
    construction; ``decode_codes`` does it per jitted call, where XLA
    hoists it out of the decode scan): returns the quantized-weight tree
    ``DALLE.decode_step`` consumes — per layer ``{"qkv": (int8 [dim, 3,
    h, dh], f32 scale), "out"/"ff_in"/"ff_out": (int8, scale, f32
    bias)}`` plus ``"head"`` for the image-phase logits kernel.  Scales
    are per-output-channel (ops/quant.py::quantize_weight, reduced over
    the input dim), so every output column keeps its own dynamic range —
    the LLM.int8() weight layout.  The f32 originals stay in ``params``
    untouched (checkpoints, training and prefill never see int8); the
    compiled decode/tick programs simply stop referencing them, so jit's
    unused-argument pruning removes them from the weight stream."""
    from ..ops.quant import quantize_weight

    assert cfg.ff_experts <= 1, (
        "weights_int8 does not cover MoE expert kernels")
    if "params" in params:  # accept the full variables dict too
        params = params["params"]
    t = params["transformer"]
    layers = []
    for i in range(cfg.depth):
        attn = t[f"layers_{i}_attn"]["attn"]
        ff = t[f"layers_{i}_ff"]
        layers.append({
            "qkv": quantize_weight(attn["to_qkv"]["kernel"]),
            "out": (*quantize_weight(attn["to_out"]["kernel"]),
                    attn["to_out"]["bias"]),
            "ff_in": (*quantize_weight(ff["dense_in"]["kernel"]),
                      ff["dense_in"]["bias"]),
            "ff_out": (*quantize_weight(ff["dense_out"]["kernel"]),
                       ff["dense_out"]["bias"]),
        })
    head = params["to_logits_dense"]
    return {"layers": layers,
            "head": (*quantize_weight(head["image_kernel"]),
                     head["image_bias"])}


def sample_image_code(logits, key, *, k_vocab: int,
                      filter_thres: float = 0.5, temperature=1.0,
                      top_p: Optional[float] = None) -> jax.Array:
    """Sample image codes from image-phase logits ``[..., num_image_tokens]``.

    THE sampling semantics of this repo, shared by ``decode_codes`` and the
    serving tick (``serve/engine.py``) so the two paths cannot drift:
    logits are image-vocab-only, ``k`` still derives from the full joint
    vocab (reference semantics — its text entries were -inf and could never
    win a slot), and the sampled index IS the image code (the reference's
    ``- num_text_tokens`` offset is pre-applied by slicing).  Temperature
    scales BEFORE the filters: top-k is invariant to the monotone rescale
    (so reference top-k semantics are untouched) but the nucleus must be
    the p-mass set of the distribution actually sampled.  ``temperature``
    may be a traced scalar/array (the serve path carries it per request),
    ``filter_thres``/``top_p`` stay static (``top_k_filter`` derives a
    static k).  Its own ``sample`` scope.  The top-k cut-off is found by
    exact selection (``utils/helpers.py::kth_largest``: 32 counting passes
    over keys, no ordering; as a sort it was a quarter of a decode tick's
    device time at CUB width, PERF.md PR 31), a static choice, so it is
    counted per trace: every traced sampler emits one ``sample.top_k``
    record (rows as traced, so 1 under a ``vmap``) and sets two gauges.  A
    ``decode_codes`` program holds two samplers, the first code's and the
    scan body's."""
    k = top_k_count(logits.shape[-1], filter_thres, k_vocab)
    telemetry.emit("sample", "top_k", rows=logits.size // logits.shape[-1],
                   vocab=logits.shape[-1], k=k, passes=TOP_K_PASSES,
                   method="select")
    reg = metrics.active()
    if reg is not None:
        reg.gauge("graft_sample_topk_k",
                  "logits the last traced sampler's top-k filter keeps"
                  ).set(k)
        reg.gauge("graft_sample_topk_passes",
                  "counting passes of the last traced sampler's k-th-largest "
                  "selection").set(TOP_K_PASSES)
    with prof.scope("sample"):
        # a static temperature of exactly 1 emits no divide (x / 1 is x bit
        # for bit, and XLA drops it anyway)
        if not (isinstance(temperature, (int, float)) and temperature == 1):
            logits = logits / temperature
        filtered = top_k_filter(logits, thres=filter_thres, k_vocab=k_vocab)
        if top_p is not None:
            filtered = top_p_filter(filtered, top_p)
        return jax.random.categorical(key, filtered,
                                      axis=-1).astype(jnp.int32)


def prefill_codes(dalle: DALLE, params, text, *, prime_codes=None,
                  mask=None):
    """The prompt half of the sampler: run the full forward over
    [bos+text (+prime)] once, returning ``(first_logits [b, num_image_
    tokens], caches)`` — the state ``decode_codes`` continues from.

    Split out of ``generate_codes`` so callers sampling MANY candidates of
    the SAME prompt (cli.generate_chunked, genrank) can pay this forward
    once per unique prompt and ``tile_prefill`` the result across the
    candidate batch, instead of re-running the prefill transformer for
    every batch-size chunk."""
    return dalle.apply(params, text, prime_codes, mask, method=DALLE.prefill)


def broadcast_prefill(first_logits, caches, reps: int):
    """Tile a prefill state across ``reps`` batch rows — THE shared
    broadcast primitive behind every prompt-reuse path (``tile_prefill``
    for same-prompt candidate batches, ``serve/prefix.py`` for radix
    prefix-cache re-admissions), so the rotation/tiling logic lives in
    exactly one place."""
    if reps == 1:
        return first_logits, caches
    rep = lambda a: jnp.repeat(a, reps, axis=0)  # noqa: E731
    # tree_map, not tuple unpacking: int8 cache entries are (values,
    # scale) pairs and the per-head scale planes tile on the same axis
    return rep(first_logits), jax.tree.map(rep, caches)


def tile_prefill(first_logits, caches, reps: int):
    """Broadcast a batch-1 prefill state across ``reps`` candidates.

    Every candidate of one prompt shares an identical prefill (the prompt
    positions' k/v never depend on the sampled continuation), so tiling the
    cached state is exact — one HBM write of the caches instead of ``reps``
    prefill forwards.  The per-candidate divergence comes entirely from the
    decode loop's rng."""
    assert first_logits.shape[0] == 1, (
        "tile_prefill broadcasts a single-prompt (batch-1) prefill; "
        f"expected first_logits batch shape (1, ...), got shape "
        f"{tuple(first_logits.shape)}")
    return broadcast_prefill(first_logits, caches, reps)


def _kv_reach(dalle: DALLE, params, caches, n_pre: int,
              masked: bool = False) -> dict:
    """What a ``decode_codes`` call's bounded cache reads come to
    (ops/attention.py::MultiHeadAttention._masked_read; a latent layer's
    one-pass kernel or two-pass read, ops/latent_attention.py), from static
    shapes: the layers whose dense read ends at one of several prefixes (a
    ``lax.switch``'s buckets, or the ends of the kernel's blocks) and those
    that read as before (slices, or a cache of one bucket), the prefixes
    over all layers, and ``read_share``: over the dense-read layers,
    weighted by their caches' bytes, the mean over the call's ticks
    (positions ``n_pre`` to the last) of slots read over slots held; 1.0
    where no layer reads densely."""
    cfg = dalle.cfg
    bounds = dalle.apply(params, masked, method=DALLE.dense_read_bounds)
    ticks = np.arange(n_pre, cfg.seq_len)
    read = held = 0.0
    for layer, cache in zip(bounds, caches):
        if layer is None:
            continue
        slots = layer[-1]
        filled = np.minimum(ticks + 1, slots)   # a ring fills, then wraps
        chosen = np.asarray(layer)[np.searchsorted(layer, filled)]
        nbytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(cache))
        read += nbytes * (chosen.mean() / slots if ticks.size else 1.0)
        held += nbytes
    bounded = [layer for layer in bounds if layer and len(layer) > 1]
    return {"bounded_layers": len(bounded),
            "unbounded_layers": sum(map(caches_positions, cfg.mixers))
            - len(bounded),
            "buckets": sum(map(len, bounded)),
            "read_share": read / held if held else 1.0}


def _lane_dense_caches(dalle: DALLE, params, caches, n_pre: int,
                       masked: bool = False):
    """``caches`` with the dense-read layers' entries head-folded wherever
    XLA:TPU would pad the plain layout to the lanes
    (ops/attention.py::kv_fold_factor): one relayout a call, so that every
    tick of the scan reads each cache byte once.  Recurrent entries pass as
    they are; a latent pair rides as one array where its read takes one
    pass (ops/latent_attention.py::fold_latent; not where the call is
    ``masked``).  A static choice, so its counters are per trace: a
    ``decode.kv_layout`` record and two gauges say how many attention
    layers' caches were folded and how many kept plain, a
    ``decode.state_layout`` record and four gauges how many layers carry
    keys and values, how many a state-space state (Mamba-1 or Mamba-2),
    how many none at all (``stateless_layers``, only where there are any:
    layers of one sublayer without a mixer), how many a
    linear-attention state (with the shape a row of it is carried in), how
    many a latent pair (``latent_layers``, with the bytes a position holds
    and the bytes its stored form walks, gauge
    ``graft_decode_latent_layers``), and
    the bytes of decode state one row holds; over a routed trunk a ``decode.moe_layout`` record
    and three gauges besides: the expert layers, the window layers, and the
    key/value slots one row holds over all layers (a window layer holds its
    ring, not ``seq_len``); and a ``decode.kv_reach`` record (:func:`_kv_reach`)
    with gauges ``graft_decode_kv_bounded_layers`` / ``_kv_read_share``: how
    many layers' dense reads the position bounds, and the share of their
    slots a tick of this call (``n_pre`` positions prefilled) reads."""
    from ..ops.linear_attention import one_pass_step
    from ..ops.quant import cache_values

    cfg = dalle.cfg
    with prof.scope("attn-cache"):
        folded = dalle.apply(params, caches, masked,
                             method=DALLE.lane_dense_caches)
    latent = [i for i, kind in enumerate(cfg.mixers) if is_latent(kind)]
    attn = [i for i, kind in enumerate(cfg.mixers)
            if caches_positions(kind) and not is_latent(kind)]
    linear = [i for i, kind in enumerate(cfg.mixers) if kind == "gdn"]
    stateless = sum(map(is_stateless, cfg.mixers))
    dense = sum(cache_values(folded[i][0]).shape
                != cache_values(caches[i][0]).shape for i in attn)
    rows = int(jax.tree.leaves(caches)[0].shape[0])
    records = {
        "kv_layout": {"kv_lane_dense_layers": dense,
                      "kv_plain_layers": len(attn) - dense},
        "state_layout": {
            "ssm_layers": sum(kind in ("mamba", "mamba2")
                              for kind in cfg.mixers),
            "kv_layers": len(attn), "linear_layers": len(linear),
            # only where there are any: other models' records stay as they
            # were
            **({"stateless_layers": stateless} if stateless else {}),
            "state_bytes_per_row": sum(
                a.size * a.dtype.itemsize
                for a in jax.tree.leaves(caches)) // rows}}
    if latent:
        # a latent layer holds one pair a position and no head axis: what a
        # position costs as the mathematics counts it, and as the scan
        # carries the arrays row-major (each minor dimension padded to the
        # lanes: 64 rotary values alone take a whole tile row; two
        # positions folded into one row of the layer's one array fill
        # theirs, LatentAttention.lane_dense_cache; the unfolded pair the
        # v5e's compiler lays out itself inside the scan, the positions on
        # the lanes, PERF.md PR 38)
        slots = caches[latent[0]][0].shape[1]
        records["kv_layout"]["kv_latent_layers"] = len(latent)
        records["state_layout"].update(
            latent_layers=len(latent),
            latent_bytes_per_position=sum(
                a.shape[-1] * a.dtype.itemsize for a in caches[latent[0]]),
            latent_bytes_walked_per_position=sum(
                -(-a.shape[-1] // LANES) * LANES * a.dtype.itemsize
                * a.shape[1] // slots
                for a in jax.tree.leaves(folded[latent[0]])))
    if linear:
        # how many linear layers' states the tick updates in one pass
        # (ops/linear_attention.py::one_pass_step)
        records["state_layout"]["linear_one_pass_layers"] = sum(
            one_pass_step(cfg.heads, caches[i][1].shape[2],
                          cfg.trunk.lin_value_dim, caches[i][1].dtype)
            for i in linear)
    # the shape one row of a linear-attention state is carried in: said in
    # the state_layout record, no gauge
    state_shape = ({"linear_state_shape": list(caches[linear[0]][1].shape[1:])}
                   if linear else {})
    reg = metrics.active()
    for record, counts in records.items():
        telemetry.emit("decode", record, rows=rows, **counts,
                       **(state_shape if record == "state_layout" else {}))
        if reg is not None:
            for name, value in counts.items():
                reg.gauge(f"graft_decode_{name}",
                          f"decode_codes' last trace ({record})").set(value)
    reach = _kv_reach(dalle, params, caches, n_pre, masked)
    telemetry.emit("decode", "kv_reach", rows=rows, **reach)
    if reg is not None:
        for name in ("bounded_layers", "read_share"):
            reg.gauge(f"graft_decode_kv_{name}",
                      "decode_codes' last trace (kv_reach)").set(reach[name])
    if cfg.trunk is not None and cfg.trunk.routed:
        t = cfg.trunk
        counts = {"moe_layers": t.routed_layers(cfg.depth),
                  "window_layers": cfg.mixers.count("window"),
                  "kv_slots_per_row": sum(cfg.cache_lens)}
        telemetry.emit(
            "decode", "moe_layout", rows=rows, layers=counts["moe_layers"],
            experts=t.experts, experts_per_token=t.experts_per_token,
            expert_bytes_per_layer=t.expert_matrices * t.held_experts
            * cfg.dim * t.expert_dim * jnp.dtype(t.param_dtype).itemsize,
            window_layers=counts["window_layers"],
            kv_slots_per_row=counts["kv_slots_per_row"],
            scoring=t.scoring, experts_held=t.held_experts,
            shared_experts=t.shared_experts)
        if reg is not None:
            for name, value in counts.items():
                reg.gauge(f"graft_decode_{name}",
                          "decode_codes' last trace (moe_layout)").set(value)
    return folded


def decode_codes(dalle: DALLE, params, first_logits, caches, rng, *,
                 n_prime: int = 0, prime_codes=None,
                 filter_thres: float = 0.5, temperature: float = 1.0,
                 top_p: Optional[float] = None, mask=None,
                 return_caches: bool = False):
    """The sampling half: `lax.scan` KV-cache decode from a prefill state
    (``prefill_codes`` or a ``tile_prefill`` broadcast of one).  Sampling
    semantics match the reference exactly (top_k filter with
    ``k = max(int((1-thres)*vocab), 1)``, temperature softmax, categorical
    draw, image-vocab offset subtraction; ref dalle_pytorch.py:400-415).
    ``top_p`` additionally applies nucleus filtering after top-k (a knob
    the reference lacks).  ``return_caches``: return ``(codes, caches)``,
    the scan's carried decode state after its last step beside the codes.
    """
    cfg = dalle.cfg
    n_pre = cfg.text_seq_len + 1 + n_prime

    def sample(logits, key):
        return sample_image_code(logits, key, k_vocab=cfg.total_tokens,
                                 filter_thres=filter_thres,
                                 temperature=temperature, top_p=top_p)

    def step(carry, key):
        code, caches, index = carry
        logits, caches = dalle.apply(
            params, code, caches, index, mask, None, qweights,
            method=DALLE.decode_step)
        next_code = sample(logits, key)
        return (next_code, caches, index + 1), next_code

    with prof.scope("decode-step"):
        # weights_int8: quantize once per call — a scan constant, so XLA
        # hoists it and the decode loop streams only the int8 copies
        qweights = (quantize_decode_weights(params, cfg)
                    if cfg.weights_int8 else None)
        rng, key0 = jax.random.split(rng)
        first_code = sample(first_logits, key0)
        caches = _lane_dense_caches(dalle, params, caches, n_pre,
                                    masked=mask is not None)

        num_steps = cfg.seq_len - n_pre  # remaining image positions
        keys = (jax.random.split(rng, num_steps) if num_steps > 0
                else jnp.zeros((0, 2), jnp.uint32))
        (_, caches, _), rest = jax.lax.scan(
            step, (first_code, caches, jnp.asarray(n_pre)), keys)
        rest = rest.transpose(1, 0)  # [b, num_steps]

        parts = [first_code[:, None], rest]
        if prime_codes is not None and n_prime > 0:
            parts.insert(0, prime_codes)
        codes = jnp.concatenate(parts, axis=1)
        return (codes, caches) if return_caches else codes


def generate_codes(dalle: DALLE, params, text, rng, *, prime_codes=None,
                   filter_thres: float = 0.5, temperature: float = 1.0,
                   top_p: Optional[float] = None, mask=None) -> jax.Array:
    """Sample a full image token sequence [b, image_seq_len].

    Pure jittable function: ``prefill_codes`` once, then the
    ``decode_codes`` scan — the one-shot composition of the split halves
    (callers amortizing one prompt across many candidates use the halves
    directly; see ``tile_prefill``)."""
    n_prime = 0 if prime_codes is None else prime_codes.shape[1]
    first_logits, caches = prefill_codes(dalle, params, text,
                                         prime_codes=prime_codes, mask=mask)
    return decode_codes(dalle, params, first_logits, caches, rng,
                        n_prime=n_prime, prime_codes=prime_codes,
                        filter_thres=filter_thres, temperature=temperature,
                        top_p=top_p, mask=mask)
