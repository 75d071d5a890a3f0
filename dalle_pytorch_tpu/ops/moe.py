"""Mixture-of-Experts feed-forward with expert parallelism.

The reference has no MoE (its FF is a single GEGLU block,
`/root/reference/dalle_pytorch/transformer.py:53-69`); this is scaling
headroom alongside the framework's other mesh axes (dp/fsdp/tp in mesh.py,
sp in ring.py/ulysses.py, pp in pipeline.py): widen FF *capacity* (params)
by ``num_experts`` while the top-k router keeps each token's output a
mixture of k experts.

TPU-native design choices:
* **two dispatch modes**, both static-shaped and einsum-only (no
  scatter/gather, no dynamic shapes — everything is MXU matmuls that GSPMD
  shards cleanly):
  - ``dispatch='dense'``: every expert sees every token; the combine
    matrix zeroes the non-routed outputs.  FF *FLOPs* scale with
    ``num_experts`` — simplest and exact, right at small expert counts.
  - ``dispatch='capacity'``: GShard/Switch-style fixed expert capacity
    within token *groups* of ``capacity_group`` tokens: per group,
    ``C = ceil(top_k · g / e · capacity_factor)`` slots per expert.
    One-hot dispatch/combine tensors [G, g, e, C] route each token to a
    slot (position-in-expert via cumsum, no sort); tokens over a group's
    capacity are DROPPED for that expert (their residual passes
    through).  Grouping keeps dispatch memory and FLOPs linear in token
    count (≈ T·k·cf·g dispatch-matmul elements) — ungrouped [T, e, C]
    dispatch would be quadratic in T.  Expert FF FLOPs scale with
    ``top_k · capacity_factor`` instead of ``num_experts``.
* **expert parallelism by sharding annotation** — expert-stacked kernels
  carry a leading ``num_experts`` axis; `Partitioner`-style regex rules or
  an explicit `with_sharding_constraint` put that axis on an ``ep`` mesh
  axis and XLA inserts the all-to-alls.  The module itself stays a pure
  function — same philosophy as the rest of the framework (the reference's
  NCCL machinery became shardings, SURVEY.md §2.3).
* **router in f32**, switch-style load-balance auxiliary loss (mean
  fraction x mean probability per expert), returned separately so callers
  weight it.

Three layers share one routing rule (:func:`route`): :class:`MoEFeedForward`
above, the 2021 block's GEGLU experts; :class:`ExpertsReGLU`, the dropless
bias-free ReGLU experts of a ``TrunkSpec`` trunk
(ops/transformer.py::TrunkMoEBlock), whose router logits come from the
caller; and :class:`ExpertsSwiGLUShared`, SwiGLU experts (or relu^2
experts without a gate bank) under a softmax or a sigmoid router (the
latter with a selection bias), their weights scaled, beside a shared
expert, on the experts this device holds
(ops/transformer.py::TrunkSharedMoEBlock).
"""
from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..obs import prof


SCORINGS = ("softmax", "sigmoid")


def route(logits, k: int, scoring: str = "softmax", bias=None,
          scale: float = 1.0):
    """The one routing rule: float32 scores over ALL experts, the ``k``
    largest, renormalised over the chosen.

    ``logits`` ``[..., e]`` -> ``(scores [..., e], top_idx [..., k], combine
    [..., e])``, all float32 but the indices: ``combine`` holds each chosen
    expert's weight at its own column and exact zeros elsewhere.
    ``jax.lax.top_k`` breaks exact ties towards the lower index.

    ``scoring`` "softmax": the scores are the softmax's probabilities and a
    row of ``combine`` sums to ``scale``.  "sigmoid" (``noaux_tc``): each
    score is its own logit's sigmoid; ``bias`` ``[e]`` is added for the CHOICE
    alone (the ``k`` largest of ``score + bias``) and never enters a weight,
    which is ``scale * score_e / (sum of the chosen scores + 1e-20)``."""
    assert scoring in SCORINGS, scoring
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        e = scores.shape[-1]
        _, top_idx = jax.lax.top_k(
            scores if bias is None else scores + bias.astype(jnp.float32), k)
        onehot = jax.nn.one_hot(top_idx, e, dtype=scores.dtype)
        chosen = onehot.sum(axis=-2)                         # [..., e] 0 / 1
        combine = scores * chosen
        combine = scale * combine / (
            combine.sum(axis=-1, keepdims=True) + 1e-20)
        return scores, top_idx, combine
    assert bias is None, "a selection bias belongs to sigmoid scoring"
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    e = probs.shape[-1]
    top_p, top_idx = jax.lax.top_k(probs, k)
    onehot = jax.nn.one_hot(top_idx, e, dtype=probs.dtype)  # [..., k, e]
    combine = (top_p[..., None] * onehot).sum(axis=-2)      # [..., e]
    combine = combine / jnp.clip(
        combine.sum(axis=-1, keepdims=True), 1e-9)
    if scale != 1.0:
        combine = scale * combine
    return probs, top_idx, combine


def relu2(x):
    """``relu(x)^2``: Nemotron-H's expert nonlinearity, which has no gate."""
    return jnp.square(jax.nn.relu(x))


def _bank_products(x, combine, w_gate, w_up, w_down, act, dtype):
    """Every bank's expert on every token ``x`` ``[t, d]``, the ``combine``
    weights' exact zeros cancelling the unchosen before one contraction over
    experts x width into the model width: float32 ``[t, d]``.  ``combine``
    ``[t, e]`` has one column a bank; ``act`` is the gate's nonlinearity,
    or, where ``w_gate`` is None, the nonlinearity of ``x W_up`` alone."""
    with prof.scope("moe-experts"):
        if w_gate is None:
            # graftlint: disable=DOT001 (uniform: x and the banks are both cast to dtype)
            hidden = act(jnp.einsum("td,edf->tef", x, w_up))
        else:
            # graftlint: disable=DOT001 (uniform: x and the banks are both cast to dtype)
            gate = jnp.einsum("td,edf->tef", x, w_gate)
            # graftlint: disable=DOT001 (uniform: x and the banks are both cast to dtype)
            up = jnp.einsum("td,edf->tef", x, w_up)
            hidden = act(gate) * up
    with prof.scope("moe-route"):
        hidden = (hidden.astype(jnp.float32)
                  * combine[:, :, None]).astype(dtype)
    with prof.scope("moe-experts"):
        return jnp.einsum("tef,efd->td", hidden, w_down,
                          preferred_element_type=jnp.float32)


class ExpertsReGLU(nn.Module):
    """Dropless top-k mixture of ReGLU experts without bias:
    ``y = sum_{e in S} c_e (relu(m W_gate_e) * (m W_up_e)) W_down_e`` with
    ``S`` and ``c`` from :func:`route` over router logits the CALLER hands
    in (``router_logits``: a trunk's router reads its layer's input, before
    the mixer, ops/transformer.py::TrunkMoEBlock).  Every token gets all
    ``k`` of its experts whatever the imbalance.

    One form for a tick's rows and a sequence's tokens: every expert on every
    token, the ``combine`` weights' exact zeros cancelling the unchosen before
    one contraction over experts x width into the model width.  Settled on
    the chip (PERF.md, Findings PR 32; ms a layer at dim 2560, 64 experts of
    768, bf16): 1.01 at a tick's 64 rows, where the 755 MB of banks are read
    whole either way (750 GB/s), and 8.2 at a prompt's 2,049; tokens sorted
    by expert into ``jax.lax.ragged_dot`` read 4.01 and 12.0, because XLA:TPU
    lowers it to every expert on all ``tokens x k`` sorted rows under a mask.
    A grouped product that skips the unchosen needs a kernel of its own
    (ROADMAP R1).  Scopes: ``moe-route`` (router product, softmax, top-k,
    renormalise, combine) and ``moe-experts`` (the three products and the
    gate)."""

    dim: int
    experts: int
    k: int
    expert_dim: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def setup(self):
        from .ssm import fan_in_normal

        e, d, f = self.experts, self.dim, self.expert_dim
        bank = dict(dtype=self.param_dtype)
        self.w_router = self.param("w_router", fan_in_normal(d), (d, e),
                                   **bank)
        self.w_gate = self.param("w_gate", fan_in_normal(d), (e, d, f),
                                 **bank)
        self.w_up = self.param("w_up", fan_in_normal(d), (e, d, f), **bank)
        self.w_down = self.param("w_down", fan_in_normal(f), (e, f, d),
                                 **bank)

    def router_logits(self, x):
        """``x @ W_r`` with float32 sums: ``[..., e]`` float32."""
        with prof.scope("moe-route"):
            kernel = self.w_router.astype(self.dtype)
            return jnp.einsum("...d,de->...e", x.astype(self.dtype), kernel,
                              preferred_element_type=jnp.float32)

    def __call__(self, m, router_logits):
        """``m`` ``[b, n, dim]`` (the normed hidden state), ``router_logits``
        ``[b, n, e]`` -> ``[b, n, dim]`` in ``m``'s dtype."""
        b, n, d = m.shape
        tokens = b * n
        with prof.scope("moe-route"):
            _, top_idx, combine = route(router_logits.reshape(tokens, -1),
                                        self.k)
            # what this layer's routing chose, for tests and the benchmark's
            # comparison (a no-op unless "intermediates" is mutable)
            self.sow("intermediates", "top_idx", top_idx.reshape(b, n, -1))
        x = m.reshape(tokens, d).astype(self.dtype)
        w_gate, w_up, w_down = (self.w_gate.astype(self.dtype),
                                self.w_up.astype(self.dtype),
                                self.w_down.astype(self.dtype))
        y = _bank_products(x, combine, w_gate, w_up, w_down, jax.nn.relu,
                           self.dtype)
        return y.reshape(b, n, d).astype(m.dtype)


class ExpertsSwiGLUShared(nn.Module):
    """Dropless mixture of SwiGLU experts beside shared experts that every
    token takes, on the experts THIS device holds (ops/transformer.py::
    TrunkSharedMoEBlock).  With ``m`` the normed input of the sublayer,
    ``scoring`` "sigmoid" (GLM-4.7-Flash's ``noaux_tc`` layer)::

        sc     = sigmoid(m W_r)                     # float32, all ``experts``
        chosen = the k largest of sc + b            # b: selection bias only
        w_e    = scale * sc_e / (sum_chosen sc + 1e-20)
        y      = sum_{held e} w_e W_down_e(silu(W_gate_e m) * (W_up_e m))
                 + W_down_s(silu(W_gate_s m) * (W_up_s m))

    and "softmax" (Laguna's): ``p = softmax(m W_r)``, the k largest of ``p``,
    ``w_e = scale * p_e / sum_chosen p``; no selection bias, and no
    ``router_bias`` leaf.  ``act`` "relu2" (Nemotron-H's experts) makes every
    expert, shared or routed, ``W_down relu(W_up m)^2``: no gate bank, no
    ``w_gate`` or ``shared_gate`` leaf.

    ``experts`` stays the router's width; ``held`` banks exist here, experts
    ``first .. first + held - 1`` (the share of a deployment that splits the
    experts over devices, as expert parallelism does): the layer routes over
    all ``experts``, multiplies its own banks, and what the others would have
    added is left out.  On one device there is no exchange; ``held =
    experts`` is the whole layer.  The ``shared`` shared experts are one
    expert of width ``shared_dim``, or (0) ``shared x expert_dim``.
    Scopes: ``moe-route`` (router product, scores, top-k, weights) and
    ``moe-experts`` (the banks' products and the shared expert's)."""

    dim: int
    experts: int
    k: int
    expert_dim: int
    held: int
    first: int = 0
    shared: int = 1
    shared_dim: int = 0
    act: str = "swiglu"
    scoring: str = "sigmoid"
    scale: float = 1.0
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def setup(self):
        from .ssm import fan_in_normal

        e, d, f = self.held, self.dim, self.expert_dim
        assert 0 <= self.first and self.first + e <= self.experts, (
            self.first, e, self.experts)
        bank = dict(dtype=self.param_dtype)
        self.w_router = self.param("w_router", fan_in_normal(d),
                                   (d, self.experts), **bank)
        # enters the choice and never a weight; drawn, not zero, so that a
        # seeded model's choices depend on it
        if self.scoring == "sigmoid":
            self.router_bias = self.param(
                "router_bias",
                lambda key, shape: jax.random.uniform(key, shape, jnp.float32,
                                                      -0.1, 0.1),
                (self.experts,))
        assert self.act in ("swiglu", "relu2"), self.act
        if self.gated:
            self.w_gate = self.param("w_gate", fan_in_normal(d), (e, d, f),
                                     **bank)
        self.w_up = self.param("w_up", fan_in_normal(d), (e, d, f), **bank)
        self.w_down = self.param("w_down", fan_in_normal(f), (e, f, d),
                                 **bank)
        fs = self.shared_dim or self.shared * f
        if self.gated:
            self.shared_gate = self.param("shared_gate", fan_in_normal(d),
                                          (d, fs), **bank)
        self.shared_up = self.param("shared_up", fan_in_normal(d), (d, fs),
                                    **bank)
        self.shared_down = self.param("shared_down", fan_in_normal(fs),
                                      (fs, d), **bank)

    @property
    def gated(self) -> bool:
        """Every expert has a gate bank (SwiGLU), not relu^2 alone."""
        return self.act == "swiglu"

    def __call__(self, m):
        """``m`` ``[b, n, dim]`` (the normed hidden state) -> ``[b, n, dim]``
        in ``m``'s dtype."""
        b, n, d = m.shape
        tokens = b * n
        x = m.reshape(tokens, d).astype(self.dtype)
        with prof.scope("moe-route"):
            logits = jnp.einsum("td,de->te", x,
                                self.w_router.astype(self.dtype),
                                preferred_element_type=jnp.float32)
            _, top_idx, combine = route(
                logits, self.k, self.scoring,
                self.router_bias if self.scoring == "sigmoid" else None,
                self.scale)
            # what this layer's routing chose and how it weighted its
            # choices, for tests and the benchmark's comparison (a no-op
            # unless "intermediates" is mutable)
            self.sow("intermediates", "top_idx", top_idx.reshape(b, n, -1))
            self.sow("intermediates", "top_weight", jnp.take_along_axis(
                combine, top_idx, axis=-1).reshape(b, n, -1))
            combine = combine[:, self.first:self.first + self.held]
        gated = self.gated
        y = _bank_products(x, combine,
                           self.w_gate.astype(self.dtype) if gated else None,
                           self.w_up.astype(self.dtype),
                           self.w_down.astype(self.dtype),
                           jax.nn.silu if gated else relu2, self.dtype)
        with prof.scope("moe-experts"):
            if gated:
                # graftlint: disable=DOT001 (uniform: x and the kernel are both cast to self.dtype)
                gate = jnp.dot(x, self.shared_gate.astype(self.dtype))
                # graftlint: disable=DOT001 (uniform: x and the kernel are both cast to self.dtype)
                up = jnp.dot(x, self.shared_up.astype(self.dtype))
                hidden = jax.nn.silu(gate) * up
            else:
                # graftlint: disable=DOT001 (uniform: x and the kernel are both cast to self.dtype)
                hidden = relu2(jnp.dot(x, self.shared_up.astype(self.dtype)))
            y = y + jnp.dot(hidden, self.shared_down.astype(self.dtype),
                            preferred_element_type=jnp.float32)
        return y.reshape(b, n, d).astype(m.dtype)


class MoEFeedForward(nn.Module):
    """Top-k routed GEGLU expert FF: drop-in for FFBlock's inner compute.

    Output = sum over selected experts of gate * expert_ff(x); with
    ``num_experts=1`` this reduces exactly to a single GEGLU FF (up to the
    router's constant gate of 1.0).
    """

    dim: int
    num_experts: int = 8
    top_k: int = 2
    mult: int = 4
    dropout: float = 0.0   # on the expert inner activations (FFBlock parity)
    dispatch: str = "dense"        # 'dense' | 'capacity'
    capacity_factor: float = 1.25  # only used by 'capacity' dispatch
    capacity_group: int = 1024     # tokens per dispatch group ('capacity')
    dtype: Any = jnp.float32

    def _expert_geglu(self, deterministic):
        """Returns the stacked-expert GEGLU: input flows through per-expert
        kernels with the expert axis named 'e' in the caller's einsum
        specs."""
        e, d = self.num_experts, self.dim
        inner = int(d * self.mult)
        w_in = self.param(
            "w_in", nn.initializers.lecun_normal(batch_axis=(0,)),
            (e, d, inner * 2)).astype(self.dtype)
        b_in = self.param("b_in", nn.initializers.zeros,
                          (e, inner * 2)).astype(self.dtype)
        w_out = self.param(
            "w_out", nn.initializers.lecun_normal(batch_axis=(0,)),
            (e, inner, d)).astype(self.dtype)
        b_out = self.param("b_out", nn.initializers.zeros,
                           (e, d)).astype(self.dtype)

        def ff(h, in_spec, out_spec, expert_leading=False):
            # biases align on (e, last): in the [e, C, ...] layout the
            # expert axis leads, so give them a slot axis to broadcast over
            bi = b_in[:, None] if expert_leading else b_in
            bo = b_out[:, None] if expert_leading else b_out
            # graftlint: disable=DOT001 (uniform: h and w_in are both cast to self.dtype)
            h = jnp.einsum(in_spec, h, w_in) + bi
            h, gates = jnp.split(h, 2, axis=-1)
            h = h * nn.gelu(gates)
            # dropout on the inner activation, matching FFBlock's placement
            # (between the GEGLU gate and the output projection)
            h = nn.Dropout(self.dropout)(h, deterministic=deterministic)
            # graftlint: disable=DOT001 (uniform: h and w_out are both cast to self.dtype)
            return jnp.einsum(out_spec, h, w_out) + bo

        return ff

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        """x: [b, n, dim] -> (y: [b, n, dim], aux_loss: scalar f32)."""
        e = self.num_experts
        k = min(self.top_k, e)
        assert self.dispatch in ("dense", "capacity"), (
            f"unknown MoE dispatch {self.dispatch!r}")

        # --- router (f32 for a stable softmax) ---
        router = nn.Dense(e, dtype=jnp.float32, name="router")
        logits = router(x.astype(jnp.float32))  # [b, n, e]
        probs, top_idx, combine = route(logits, k)

        # --- switch-style load-balance loss (f32) ---
        # fraction of tokens whose top-1 lands on each expert x mean prob
        top1 = jax.nn.one_hot(jnp.argmax(probs, axis=-1), e, dtype=jnp.float32)
        aux = (top1.mean(axis=(0, 1)) * probs.mean(axis=(0, 1))).sum() * e

        ff = self._expert_geglu(deterministic)
        xc = x.astype(self.dtype)

        if self.dispatch == "dense":
            # every expert sees every token; combine zeroes the non-routed
            y = ff(xc, "bnd,edi->bnei", "bnei,eid->bned")  # [b, n, e, d]
            # graftlint: disable=DOT001 (uniform: combine is cast to y's self.dtype)
            y = jnp.einsum("bned,bne->bnd", y, combine.astype(self.dtype))
            return y.astype(x.dtype), aux.astype(jnp.float32)

        # --- capacity dispatch (GShard/Switch): per-group C slots/expert ---
        b, n, d = x.shape
        T = b * n
        g = min(self.capacity_group, T)
        G = -(-T // g)  # ceil
        Tp = G * g
        C = max(1, int(-(-k * g * self.capacity_factor // e)))  # ceil

        def pad(arr):
            return jnp.pad(arr, ((0, Tp - T),) + ((0, 0),) * (arr.ndim - 1))

        flat_gate = pad(combine.reshape(T, e)).reshape(G, g, e)
        flat_idx = pad(top_idx.reshape(T, k)).reshape(G, g, k)
        xf = pad(xc.reshape(T, d)).reshape(G, g, d)
        # padding tokens must not consume capacity slots
        valid = pad(jnp.ones((T, 1), jnp.int32)).reshape(G, g, 1)

        # slot assignment: per routing priority j, position-in-expert via a
        # cumulative count over token order within the group (no sort,
        # static shapes); one_hot(pos, C) is all-zero past capacity, which
        # is exactly the drop
        counts = jnp.zeros((G, e), jnp.int32)
        dispatch = jnp.zeros((G, g, e, C), self.dtype)
        for j in range(k):
            oh = jax.nn.one_hot(flat_idx[..., j], e, dtype=jnp.int32) * valid
            pos = jnp.cumsum(oh, axis=1) - oh + counts[:, None]   # [G, g, e]
            pos_tok = (pos * oh).sum(-1)                          # [G, g]
            slot = jax.nn.one_hot(pos_tok, C, dtype=self.dtype)   # [G, g, C]
            dispatch = dispatch + (oh.astype(self.dtype)[..., None]
                                   * slot[:, :, None, :])
            counts = counts + oh.sum(axis=1)

        combine_slots = dispatch * flat_gate.astype(self.dtype)[..., None]
        # graftlint: disable=DOT001 (uniform: dispatch is built in self.dtype, xf cast to it)
        expert_in = jnp.einsum("gtec,gtd->gecd", dispatch, xf)  # [G, e, C, d]
        y = ff(expert_in, "gecd,edi->geci", "geci,eid->gecd",
               expert_leading=True)                             # [G, e, C, d]
        # graftlint: disable=DOT001 (uniform: combine_slots and y are both self.dtype)
        out = jnp.einsum("gtec,gecd->gtd", combine_slots, y)    # dropped -> 0
        out = out.reshape(Tp, d)[:T]
        return out.reshape(b, n, d).astype(x.dtype), aux.astype(jnp.float32)


def ep_shard_moe_params(params: dict, mesh, ep_axis: str = "ep"):
    """NamedSharding tree putting every MoE expert-stacked leaf's leading
    axis on ``ep_axis`` and replicating everything else.  Feed to
    `jax.device_put` / `jit(..., in_shardings=...)`."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def spec_for(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if any(n in ("w_in", "b_in", "w_out", "b_out") for n in names):
            return NamedSharding(mesh, P(ep_axis))  # graftlint: disable=PLAN001 (expert banks shard over the ep axis by POSITION (leading expert dim), which a path-regex rule table cannot express)
        return NamedSharding(mesh, P())  # graftlint: disable=PLAN001 (router/norm leaves replicate on the ep mesh — the ep plan owns its inner axis, outside PARTITION_RULES by design)

    return jax.tree_util.tree_map_with_path(spec_for, params)
