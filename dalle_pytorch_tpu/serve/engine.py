"""SlotArena: the fixed-shape KV-cache arena behind continuous batching.

The static-batch sampler (``models/dalle.py::decode_codes``) turns one
batch of one prompt into image codes at full device efficiency — but a
*service* sees requests arriving at arbitrary times, and re-batching them
into aligned cohorts leaves decode slots idle while stragglers finish (the
head-of-line blocking the Orca iteration-level-scheduling paper measures).
This module is the device half of the fix:

* **One arena, N slots, every shape static.**  The KV caches live in
  per-layer arrays allocated once, the slot axis first.  A request
  occupies one slot; its per-slot decode position is a *traced*
  ``int32``, so slots at different depths of their decode share one
  compiled program.
* **The caches are stored in the form the tick reads.**  Between the
  programs (``fresh_state``'s output, the donated input and output of
  ``jit_serve_admit`` and ``jit_serve_tick``) a layer's arrays have the
  form its own ``MultiHeadAttention.arena_form`` gives
  (ops/quant.py::CacheForm), decided from heads, ``dim_head``, the cache's
  dtype, the pattern and the mixer: where the static scan's predicate
  folds (bf16 or int8, ``dim_head`` 64, an even head count) ``[num_slots,
  heads / fold, seq_len, fold * dim_head]`` for a ``full`` layer, whose
  tick reads the whole array, and ``[num_slots, seq_len, heads / fold,
  fold * dim_head]`` for a layer that reads spans and gathers along the
  positions; else (a 4-byte cache, ``dim_head`` 128 or 96, grouped keys,
  a ring) the plain ``[num_slots, kv heads, slots, dim_head]``.  The
  lanes are filled and the slot axis is major, so the chip keeps the
  array in the order it was given: the tick holds no copy of a whole
  cache, and an install writes one slot's rows, one run of memory.
  (Stored plain at ``dim_head`` 64 and 128 slots the chip put the SLOTS
  on the lanes: every tick copied all sixteen arrays in and out, 27 of
  its 33 ms, and an install rewrote a lane of every tile, 36 ms; PERF.md,
  Findings PR 37.)  Per built arena, where a telemetry stream or a metrics
  registry is open: a ``serve.arena_layout`` record (:meth:`SlotArena.
  layout`) and gauges ``graft_serve_arena_folded_layers`` /
  ``graft_serve_tick_relayout_bytes``.
* **Admission is a ``dynamic_update_slice``, never a retrace.**  A new
  request is prefilled at batch 1 (one compiled prefill shape), then its
  caches are brought into the stored form and written into a free slot by
  the jitted :meth:`SlotArena.admit` — the slot id is traced, so admitting
  into slot 0 and slot 17 is the same executable.  Retiring a finished
  request is pure host bookkeeping
  (the slot is marked free; its stale cache bytes are overwritten by the
  next admit and are unreachable meanwhile — decode attention masks keys
  beyond the slot's position).
* **One jitted tick decodes every occupied slot.**  :meth:`SlotArena.tick`
  runs the batched ``DALLE.decode_step`` with a per-slot position vector
  and a per-slot active mask: occupied slots advance one token, free
  slots burn a masked lane (fixed shapes are the point — the mask changes
  per tick as requests come and go, but it is a *traced* input, so
  occupancy changes never recompile).  graftspmd S3 gates exactly this
  (``tools/spmd_check.py`` serve-tick harness): N simulated admit/retire
  cycles across differing occupancies must leave ``_cache_size == 1`` on
  every jitted entry point.
* **Phase-aligned (circular) slot caches.**  Slots sit at different
  depths, but a per-slot cache-write position would lower to an XLA
  scatter — which copies the whole arena on backends that don't alias it
  (measured ~2x the whole decode step on CPU).  Instead each slot's cache
  is stored ROTATED by ``(clock - index) mod seq_len`` (established once
  at admit by rolling the prefilled caches), so at every tick ALL slots
  write their new k/v at the same physical column — the arena clock mod
  seq_len — one plain in-place ``dynamic_update_slice``.  Attention masks
  translate physical -> logical per slot (``ops/attention.py::
  MultiHeadAttention._decode_step_aligned``), which also hides the
  previous resident's stale keys.
* **Rings beside the rotated caches.**  A sliding-window layer's slot
  holds ``min(window, seq_len)`` keys (``DALLEConfig.cache_lens``), position
  p in slot ``p mod ring``: tied to the row's own positions, not to the
  arena clock, so it is written per row and read whole
  (``MultiHeadAttention._decode_step_ring``); ``admit`` installs it unrolled.
* **Latent slots.**  A latent-attention layer (``"mla"``,
  ops/latent_attention.py) keeps ``(c, k_rope)`` per slot, ``[num_slots,
  seq_len, kv_rank]`` and ``[num_slots, seq_len, rope_dim]``: no head axis
  to fold, the positions on axis 1 (``LatentAttention.arena_form``: one more
  answer of the one rule).  It is rotated, installed and written at the
  shared column like a key/value cache.
* **Recurrent entries beside the caches.**  A state-space layer
  (``DALLEConfig.trunk``) keeps ``(window, h)`` per slot, ``[num_slots,
  ...]`` with no position axis: nothing to rotate and nothing a mask could
  hide.  ``admit`` writes the prefilled state whole; ``tick`` advances it
  only where ``active``, so an idle or finished slot's state stands still.
  A layer without a mixer (``TrunkSpec.sublayers`` 1) holds nothing: its
  entry is None, and ``admit`` and ``tick`` pass it by.

Sampling reuses ``models.dalle.sample_image_code`` — the serve path and
``decode_codes`` share one sampler, so semantics cannot drift; temperature
rides per-slot as a traced array (a per-request knob), while
``filter_thres``/``top_p`` are server-static (they derive static shapes).

The host-side queueing/SLO policy lives in ``serve/scheduler.py``; this
module knows nothing about requests, only slots.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import re
from typing import Optional

import jax
import jax.numpy as jnp

from ..models.dalle import (DALLE, prefill_codes, quantize_decode_weights,
                            sample_image_code)
from ..obs import metrics, prof, telemetry
from ..ops.quant import cache_values, split_cache
from ..ops.transformer import is_latent, is_recurrent, is_stateless


#: ``%name = bf16[128,4,1104,128]{3,2,1,0:T(8,128)(2,1)} copy(`` in a compiled
#: program's text: the bits of an element and the dimensions of the result
_RELAYOUT = re.compile(
    r"= [a-z]+(\d+)\[([\d,]+)\](?:\{[^}]*\})? (?:copy|transpose)\(")


def relayout_bytes(hlo_text: str, elements) -> int:
    """Bytes of the ``copy`` and ``transpose`` results in a compiled
    program's text that are as large as one of the arrays of ``elements``
    elements: what the program spends bringing whole caches into another
    order than the one they are stored in.  0 is the goal."""
    total = 0
    for bits, dims in _RELAYOUT.findall(hlo_text):
        count = math.prod(map(int, dims.split(",")))
        if count in elements:
            total += count * int(bits) // 8
    return total


@dataclasses.dataclass(frozen=True)
class ArenaGeometry:
    """Static facts of one arena build (host-side mirrors of the traced
    state the scheduler needs for progress accounting)."""

    num_slots: int
    n_pre: int            # absolute input position of the first decode step
    image_seq_len: int    # codes produced per request
    seq_len: int


class SlotArena:
    """Device state + the three jitted entry points of the serving engine.

    ``variables`` is the flax variables dict (``{"params": ...}``) the
    generation primitives take.  All three entry points donate the arena
    state, so the caches update in place; callers must always thread the
    *returned* state (the donated input buffers are dead)."""

    def __init__(self, dalle: DALLE, variables, num_slots: int, *,
                 filter_thres: float = 0.9,
                 top_p: Optional[float] = None,
                 device: Optional[jax.Device] = None):
        cfg = dalle.cfg
        self.dalle = dalle
        # ``device`` pins this arena to one chip: the params and the arena
        # state are committed there, and every entry point then runs where
        # its committed arguments live.  N arenas in one process (a fleet
        # of one-chip replicas) each name their own device; None keeps
        # jax's default device, the single-server deployment.
        if device is not None:
            variables = jax.device_put(variables, device)
        self.variables = variables
        self.geometry = ArenaGeometry(
            num_slots=num_slots, n_pre=cfg.text_seq_len + 1,
            image_seq_len=cfg.image_seq_len, seq_len=cfg.seq_len)
        # cache STORAGE layout matches what prefill returns (models/dalle.py
        # quantizes under kv_cache_int8, casts to bf16 under kv_cache_bf16)
        # — admit's astype is then a no-op and the arena carries the same
        # byte-cut the static sampler measured.  Int8 arenas ride PER-SLOT
        # per-head f32 scale planes [S, heads, 1, 1] next to the int8
        # values; scale-plane init is ones, not zeros — a never-admitted
        # slot's masked lane still divides by its scale in the tick's
        # saturating re-quantize, and 0/0 would poison it with NaNs.
        self._cache_dtype = (jnp.int8 if cfg.kv_cache_int8
                             else jnp.bfloat16 if cfg.kv_cache_bf16
                             else cfg.dtype)
        S = num_slots
        recurrent = [is_recurrent(kind) for kind in cfg.mixers]
        # a latent layer's slot holds ``(c, k_rope)`` with no head axis, the
        # positions on axis 1: allocated by the model itself like a
        # recurrent state, rotated and written like a cache
        latent = [is_latent(kind) for kind in cfg.mixers]
        # a window layer's slot holds a ring of its own length, position p
        # in slot p mod ring whatever the arena's clock: rows at different
        # depths cannot share a write column there, so such a layer takes
        # ops/attention.py's per-row ring step and no rotation
        ring = [kind == "window" for kind in cfg.mixers]
        # a layer without a mixer holds no decode state: None in the arena
        stateless = [is_stateless(kind) for kind in cfg.mixers]
        # the form each layer's caches are STORED in between the programs
        # (ops/quant.py::CacheForm; None for a recurrent layer), asked of the
        # layers themselves: the tick reads and writes the arrays where
        # they lie, an install writes one slot's rows
        self._forms = forms = dalle.apply(variables, self._cache_dtype,
                                          method=DALLE.arena_forms)

        def fresh_entry(slots, form):
            values = jnp.zeros(
                form.shape(S, cfg.kv_heads, slots, cfg.dim_head),
                self._cache_dtype)
            if not cfg.kv_cache_int8:
                return values
            return (values, jnp.ones((S, cfg.heads, 1, 1), jnp.float32))

        # weights_int8: the per-session one-shot quantization — computed
        # here, once per arena, and passed to every tick as an argument
        # (the tick's compiled program then consumes ONLY the int8 copies;
        # jit prunes the unused f32 kernels from its argument list)
        self._qweights = (jax.jit(
            lambda v: quantize_decode_weights(v, cfg))(variables)
            if cfg.weights_int8 else None)

        def fresh_state():
            # a recurrent layer's zero state comes from the model itself
            # (shapes and dtypes of ops/ssm.py and ops/linear_attention.py),
            # an attention layer's cache
            # from the geometry above
            zero = (dalle.apply(variables, S, method=DALLE.decode_init_state)
                    if any(recurrent) or any(latent) else [None] * cfg.depth)
            return dict(
                caches=[entry if rec or lat or none
                        else (fresh_entry(slots, form),
                              fresh_entry(slots, form))
                        for rec, lat, none, entry, slots, form in zip(
                            recurrent, latent, stateless, zero,
                            cfg.cache_lens, forms)],
                code=jnp.zeros((S,), jnp.int32),
                index=jnp.zeros((S,), jnp.int32),
                pos=jnp.zeros((S,), jnp.int32),
                # per-slot PRE-SPLIT key stream, one key per decoded code
                # (decode_codes splits all its scan keys up front for the
                # same reason: a threefry split inside the hot loop costs
                # more than the toy-model decode step on CPU).  admit pays
                # one vectorized split; the tick only gathers.
                keys=jnp.zeros((S, cfg.image_seq_len, 2), jnp.uint32),
                # temp divides logits — a zero in a never-admitted slot
                # would poison that (masked) lane's sampler with inf/nan
                temp=jnp.ones((S,), jnp.float32),
                out=jnp.zeros((S, cfg.image_seq_len), jnp.int32),
            )

        self.state = jax.jit(
            fresh_state,
            out_shardings=(jax.sharding.SingleDeviceSharding(device)
                           if device is not None else None))()
        n_pre = self.geometry.n_pre
        k_vocab = cfg.total_tokens

        def sample_one(logits, key, temp):
            # [V] logits, [2] key, scalar temp -> scalar code; vmapped over
            # the slot axis so each slot draws from its own request key
            return sample_image_code(
                logits, key, k_vocab=k_vocab, filter_thres=filter_thres,
                temperature=temp, top_p=top_p)

        # named for a profiler trace: the programs read jit_serve_prefill,
        # jit_serve_admit and jit_serve_tick there, keys no other `prefill`
        # or `tick` in the process collides with
        def serve_prefill(variables, text):
            return prefill_codes(dalle, variables, text)

        def serve_admit(state, slot, first_logits, caches1, key, temp,
                        write_pos):
            """Install a batch-1 prefill into (traced) ``slot``: one
            dynamic_update_slice per cache array, plus the request's first
            sampled code — mirrors decode_codes' pre-scan sampling.

            ``write_pos`` is the physical column the NEXT tick writes (the
            arena clock mod seq_len): the prefill caches are rolled so the
            slot's logical position ``n_pre`` lands exactly there —
            establishing the rotation every later tick relies on to keep
            its cache write one shared-column dynamic_update_slice."""
            rot = jnp.remainder(write_pos - jnp.int32(n_pre),
                                jnp.int32(self.geometry.seq_len))

            def install(form, lat, arena_entry, new_entry):
                """Bring the prefilled values into the stored ``form``, roll
                them into the slot's rotation and write them: one DUS of the
                slot's own rows, one run of memory with the slot axis major.
                Int8 entries also carry the slot's per-head scale plane
                across — scales are write-position-invariant, so only the
                values roll.  A latent array (``lat``: no head axis) is
                prefilled in the form it is stored in."""
                vals, scale = split_cache(arena_entry)
                new_vals, new_scale = split_cache(new_entry)
                new_vals = new_vals.astype(vals.dtype)
                if not lat:
                    new_vals = form.store(new_vals)
                vals = jax.lax.dynamic_update_slice(
                    vals, jnp.roll(new_vals, rot, axis=form.position_axis),
                    (slot,) + (0,) * (vals.ndim - 1))
                if scale is None:
                    return vals
                return (vals, jax.lax.dynamic_update_slice(
                    scale, new_scale, (slot, 0, 0, 0)))

            def install_whole(arena_entry, new_entry):
                """A recurrent entry has no position axis, and a window
                layer's ring is prefilled in its own slot order: the slot's
                row is replaced whole."""
                return jax.lax.dynamic_update_slice(
                    arena_entry, new_entry.astype(arena_entry.dtype),
                    (slot,) + (0,) * (arena_entry.ndim - 1))

            caches = [None if none else
                      tuple(map(install_whole if rec or rng
                                else functools.partial(install, form, lat),
                                old, new))
                      for rec, rng, lat, none, form, old, new in zip(
                          recurrent, ring, latent, stateless, forms,
                          state["caches"], caches1)]
            ks = jax.random.split(key, self.geometry.image_seq_len)
            code0 = sample_one(first_logits[0], ks[0], temp)

            def set1(arr, val, dtype=None):
                return jax.lax.dynamic_update_slice(
                    arr, jnp.asarray(val, dtype or arr.dtype)[None], (slot,))

            out_row = jnp.zeros((self.geometry.image_seq_len,), jnp.int32
                                ).at[0].set(code0)
            return dict(
                caches=caches,
                code=set1(state["code"], code0),
                index=set1(state["index"], jnp.int32(n_pre)),
                pos=set1(state["pos"], jnp.int32(1)),
                keys=jax.lax.dynamic_update_slice(
                    state["keys"], ks[None], (slot, 0, 0)),
                temp=set1(state["temp"], temp),
                out=jax.lax.dynamic_update_slice(
                    state["out"], out_row[None], (slot, 0)),
            )

        def serve_tick(variables, state, active, write_pos, qweights):
            """One decode step over every slot (phase-aligned batched
            ``DALLE.decode_step``: per-slot logical ``index`` vector, one
            shared physical write column).  ``active`` [S] bool masks
            which slots advance; masked lanes still compute (fixed shape)
            but their code/pos/index/out are held, and their junk cache
            write lands in the shared column — overwritten by the next
            admit, unreachable before it (the aligned mask only reaches
            logical positions a resident actually wrote).  ``qweights``
            (weights_int8) rides as a real argument so the executable's
            weight stream is the int8 copies, never a baked-in constant."""
            with prof.scope("serve-tick"):
                logits, caches = dalle.apply(
                    variables, state["code"], state["caches"], state["index"],
                    None, write_pos, qweights, method=DALLE.decode_step)
                # per-slot key for THIS position, gathered from the pre-split
                # stream (no threefry in the tick)
                sub = jax.vmap(
                    lambda ks, p: jax.lax.dynamic_slice(
                        ks, (p, 0), (1, 2))[0])(state["keys"], state["pos"])
                sampled = jax.vmap(sample_one)(logits, sub, state["temp"])

                # a recurrent state has no mask to hide a junk update behind:
                # it moves only where the slot is active
                caches = [tuple(jnp.where(
                              active.reshape((-1,) + (1,) * (new.ndim - 1)),
                              new, old) for new, old in zip(entry, before))
                          if rec else entry
                          for rec, entry, before in zip(
                              recurrent, caches, state["caches"])]
                adv = active.astype(jnp.int32)
                written = jax.vmap(
                    lambda row, p, val: jax.lax.dynamic_update_slice(
                        row, val[None], (p,)))(state["out"], state["pos"],
                                               sampled)
                return dict(
                    caches=caches,
                    code=jnp.where(active, sampled, state["code"]),
                    index=state["index"] + adv,
                    pos=state["pos"] + adv,
                    keys=state["keys"],
                    temp=state["temp"],
                    out=jnp.where(active[:, None], written, state["out"]),
                )

        self._prefill = jax.jit(serve_prefill)
        self._admit = jax.jit(serve_admit, donate_argnums=(0,))
        self._tick = jax.jit(serve_tick, donate_argnums=(1,))
        # a static choice, so its counters are per built arena; they cost a
        # compile of the tick, paid only where someone listens
        reg = metrics.active()
        # graftlint: disable=SRV001 (telemetry.get() returns the open stream or None; it waits for nothing)
        if telemetry.get() is not None or reg is not None:
            layout = self.layout()
            telemetry.emit("serve", "arena_layout", **layout)
            if reg is not None:
                said = "the last built arena (serve.arena_layout)"
                reg.gauge("graft_serve_arena_folded_layers", said).set(
                    layout["folded_layers"])
                reg.gauge("graft_serve_tick_relayout_bytes", said).set(
                    layout["tick_relayout_bytes"])

    # --- public API (scheduler-facing) ------------------------------------

    def prefill(self, text):
        """Batch-1 prompt prefill: ``text`` [1, text_seq_len] int32 ->
        (first_logits, caches) device state for :meth:`admit`.  One
        compiled shape for every request."""
        return self._prefill(self.variables, text)

    def admit(self, slot: int, first_logits, caches1, key, temperature,
              clock: int):
        """Write a prefilled request into ``slot`` (traced — no retrace
        across slots) and sample its first code.  ``clock`` is the arena
        tick counter the NEXT tick will run at — it fixes the slot's
        cache rotation.  Mutates ``self.state`` (donated)."""
        self.state = self._admit(
            self.state, jnp.int32(slot), first_logits, caches1,
            jnp.asarray(key, jnp.uint32),
            jnp.float32(temperature),
            jnp.int32(clock % self.geometry.seq_len))

    def tick(self, active_mask, clock: int):
        """Advance every slot where ``active_mask`` [num_slots] bool is
        set by one decoded token; ``clock`` is the arena tick counter
        (all running slots write physical column ``clock % seq_len``).
        Mutates ``self.state`` (donated)."""
        self.state = self._tick(self.variables, self.state,
                                jnp.asarray(active_mask),
                                jnp.int32(clock % self.geometry.seq_len),
                                self._qweights)

    def take_codes(self, slot: int):
        """One slot's decoded codes [image_seq_len] as a device array of
        its own, dispatched now: whatever is admitted into the slot
        afterwards does not touch it, and ``jax.device_get`` of it is the
        retirement read (it waits for every tick dispatched so far)."""
        return self.state["out"][slot]

    def _lower_decode(self):
        """The tick, lowered from the state's own shapes."""
        active = jax.ShapeDtypeStruct((self.geometry.num_slots,), jnp.bool_)
        return self._tick.lower(self.variables, self.state, active,
                                jax.ShapeDtypeStruct((), jnp.int32),
                                self._qweights)

    def programs(self) -> dict:
        """The arena's compiled programs under the names a profiler trace
        gives them (``jit_serve_prefill``, ``jit_serve_admit`` and
        ``jit_serve_tick``), lowered from the state's own shapes: what a
        reader of a device trace needs to map ops to ``graftprof:`` scopes
        (``as_text``) or to ask the compiler's memory plan.  Runs nothing
        and leaves :meth:`trace_counts` as it was."""
        def shape(*dims, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(dims, dtype)

        prefill = self._prefill.lower(
            self.variables, shape(1, self.dalle.cfg.text_seq_len))
        first_logits, caches1 = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
            prefill.out_info)
        admit = self._admit.lower(
            self.state, shape(), first_logits, caches1,
            shape(2, dtype=jnp.uint32), shape(dtype=jnp.float32), shape())
        return {f"jit_{fn.__name__}": lowered.compile()
                for fn, lowered in ((self._prefill, prefill),
                                    (self._admit, admit),
                                    (self._tick, self._lower_decode()))}

    def layout(self) -> dict:
        """The ``serve.arena_layout`` record: how this arena stores its
        decode state between its programs, and what the stored form costs
        the tick.  ``folded_layers`` / ``plain_layers``: rotated key/value
        caches stored head-folded (ops/attention.py::MultiHeadAttention.
        arena_form) / as ``[slots, kv heads, n, dim_head]``;
        ``ring_layers`` / ``latent_layers`` / ``recurrent_layers``:
        sliding-window rings / latent pairs (one latent and one rotated key
        a position, no head axis) / recurrent entries, and
        ``stateless_layers`` (only where there are any) the layers that hold
        nothing; ``install_bytes_per_slot``: what an admission
        writes of the caches, one slot's rows; ``tick_relayout_bytes``
        (:func:`relayout_bytes`): cache-sized ``copy`` / ``transpose``
        results in the COMPILED tick, compiled here for the backend the
        arena runs on."""
        forms = [form for form in self._forms if form is not None]
        rings = self.dalle.cfg.mixers.count("window")
        mixers = self.dalle.cfg.mixers
        latent = sum(map(is_latent, mixers))
        stateless = sum(map(is_stateless, mixers))
        folded = sum(form.fold > 1 for form in forms)
        sizes = {cache_values(entry).size
                 for form, pair in zip(self._forms, self.state["caches"])
                 if form is not None for entry in pair}
        return {
            "slots": self.geometry.num_slots,
            "folded_layers": folded,
            "plain_layers": len(forms) - folded - rings - latent,
            "ring_layers": rings,
            # only where there are any: other models' records stay as they were
            **({"latent_layers": latent} if latent else {}),
            "recurrent_layers": sum(map(is_recurrent, mixers)),
            **({"stateless_layers": stateless} if stateless else {}),
            "install_bytes_per_slot": sum(
                leaf.nbytes for leaf in jax.tree.leaves(self.state["caches"])
            ) // self.geometry.num_slots,
            "tick_relayout_bytes": relayout_bytes(
                self._lower_decode().compile().as_text(), sizes),
        }

    def trace_counts(self) -> dict:
        """Executable-cache population per jitted entry point — the
        no-recompile sentinel the S3 serve gate and tests assert on.  A
        healthy server holds every count at 1 forever, whatever the
        admit/retire pattern."""
        return {name: int(fn._cache_size())
                for name, fn in (("prefill", self._prefill),
                                 ("admit", self._admit), ("tick", self._tick))}
