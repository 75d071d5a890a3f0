"""DALL-E over a routed, windowed ``TrunkSpec`` trunk (PERF.md, Findings PR
32): dropless top-k ReGLU experts whose router reads the layer's input, one
global unrotated attention layer to three rotated sliding-window layers over
grouped keys, an untied head, no client position embedding.

Tiny widths, seeded weights, float32 parameters, on the CPU.  The program is
held to ``benchmark/reference_smallthinker_21ba3b.py`` (which imports nothing
from it): the forward pass and its routing, prefill + ``decode_step`` through
the ring caches, primed ``decode_codes``, RoPE, the window's mask in its three
forms, the expert layer's two shapes, the loss and its gradients; then the
shared routing rule, the arena, the refusing asserts, the counters, and the
other configurations' programs, which must not have moved.
"""
from __future__ import annotations

import dataclasses
import hashlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from benchmark import reference_smallthinker_21ba3b as reference  # noqa: E402
from dalle_pytorch_tpu import DALLE, DALLEConfig  # noqa: E402
from dalle_pytorch_tpu.models.dalle import (  # noqa: E402
    decode_codes, generate_codes, prefill_codes, tile_prefill)
from dalle_pytorch_tpu.obs import metrics, prof, telemetry  # noqa: E402
from dalle_pytorch_tpu.obs.report import build_report, render_text  # noqa: E402
from dalle_pytorch_tpu.ops import moe  # noqa: E402
from dalle_pytorch_tpu.ops.attention import (  # noqa: E402
    AttnPattern, _allowed, apply_rope, dense_pattern_mask, flash_tiles,
    pattern_mask_row, ring_positions)
from dalle_pytorch_tpu.ops.quant import cache_write_rows  # noqa: E402
from dalle_pytorch_tpu.ops.transformer import (  # noqa: E402
    TrunkSpec, layer_cache_lens)

TRUNK = dict(mixers=["attention", "window", "window", "window"], kv_heads=2,
             window=8, rope_theta=1.5e6, ff="moe_reglu", experts=8,
             experts_per_token=3, expert_dim=24, tied_table=False,
             param_dtype="float32")
GEOMETRY = dict(dim=32, depth=4, heads=4, dim_head=8, num_text_tokens=50,
                text_seq_len=8, num_image_tokens=32, image_size=32,
                image_fmap_size=4)
#: one more shape: a window (5) that divides nothing, a prompt (5 positions)
#: no longer than it, one key head, two whole periods and a half
OTHER = dict(geometry=dict(GEOMETRY, depth=6, text_seq_len=4, heads=2,
                           dim_head=16),
             trunk=dict(TRUNK, kv_heads=1, window=5, experts=6,
                        experts_per_token=2, expert_dim=16))

#: Largest |program - reference| in units of the reference logits' standard
#: deviation, float32 on both sides: they differ in the order of sums only
#: (5e-6 measured).  1e-3 is two hundred times that; a window off by one
#: position reads 0.3, a router fed the normed input 1.4.
LOGIT_TOL = 1e-3


def _model(geometry=GEOMETRY, trunk=TRUNK, seed=0, **overrides):
    cfg = DALLEConfig(**{**geometry, "trunk": trunk, "kv_cache_bf16": False,
                         **overrides})
    dalle = DALLE(cfg)
    rng = np.random.default_rng(seed)
    t = cfg.text_seq_len
    text = jnp.asarray(rng.integers(1, 50, (2, t)), jnp.int32
                       ).at[:, t - 3:].set(0)
    codes = jnp.asarray(rng.integers(0, 32, (2, cfg.image_seq_len)),
                        jnp.int32)
    variables = dalle.init(jax.random.PRNGKey(seed), text, codes)
    # move every leaf off its initial value (gains 1), so that each matters
    variables = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(
            jax.random.PRNGKey(a.size), a.shape, a.dtype), variables)
    return cfg, dalle, variables, text, codes


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module", params=["tiny", "other"])
def either(request, model):
    if request.param == "tiny":
        return model
    return _model(OTHER["geometry"], OTHER["trunk"], seed=1)


def _err_std(got, ref):
    return float((jnp.abs(got - ref) / ref.std(-1, keepdims=True)).max())


def _chosen(cfg, state):
    """``[layers, b, n, k]`` from what the expert layers sowed."""
    layers = state["intermediates"]["transformer"]
    return jnp.stack([layers[f"layers_{i}_ff"]["moe"]["top_idx"][0]
                      for i in range(cfg.depth)])


def _teacher_forced(dalle, variables, text, codes, n_prime=0):
    """Image logits from position ``n_prime`` on through ``DALLE.prefill``
    (text and ``n_prime`` prime codes) and ``DALLE.decode_step``."""
    cfg = dalle.cfg
    first, caches = dalle.apply(variables, text, codes[:, :n_prime],
                                method=DALLE.prefill)
    outs = [first]
    for t in range(n_prime, cfg.image_seq_len - 1):
        logits, caches = dalle.apply(
            variables, codes[:, t], caches,
            jnp.asarray(cfg.text_seq_len + 1 + t), method=DALLE.decode_step)
        outs.append(logits)
    return jnp.stack(outs, axis=1), caches


# --- the model against the reference ----------------------------------------------

def test_forward_logits_mask_and_routing_match_the_reference(either):
    cfg, dalle, variables, text, codes = either
    got, state = dalle.apply(variables, text, codes,
                             mutable=["intermediates"])
    want = np.asarray(reference.joint_logits(variables["params"], cfg, text,
                                             codes))
    allowed = np.isfinite(want)
    np.testing.assert_array_equal(allowed, np.asarray(got) > -1e30)
    ref = jnp.where(allowed, want, 0.0)
    assert _err_std(jnp.where(allowed, got, 0.0), ref) <= LOGIT_TOL
    _, routes = reference.hidden(variables["params"], cfg, text, codes)
    assert float(routes["gap"].min()) > 1e-4      # no tie at these seeds
    np.testing.assert_array_equal(jnp.sort(_chosen(cfg, state), -1),
                                  jnp.sort(routes["top_idx"], -1))
    np.testing.assert_array_equal(routes["reach"], 1.0)


@pytest.mark.parametrize("n_prime", [0, 5, 9])
def test_prefill_and_decode_through_the_ring_match_the_reference(either,
                                                                 n_prime):
    """The prompt shorter than the window (``OTHER``, unprimed: 5 positions
    for 5 slots), as long, and longer (the tiny twin: 9, 14 and 18 positions
    for 8 slots, so that ``prefill`` rolls what it keeps); the walk then goes
    past every wrap left before the last position."""
    cfg, dalle, variables, text, codes = either
    got, caches = _teacher_forced(dalle, variables, text, codes, n_prime)
    want, _ = reference.image_logits(variables["params"], cfg, text, codes)
    assert got.shape == want[:, n_prime:].shape
    assert _err_std(got, want[:, n_prime:]) <= LOGIT_TOL
    slots = [entry[0].shape[2] for entry in caches]
    assert slots == list(cfg.cache_lens) == [
        cfg.seq_len if kind == "attention" else cfg.trunk.window
        for kind in cfg.mixers]
    assert cfg.seq_len > 2 * cfg.trunk.window     # the ring really wrapped


def test_a_shorter_window_or_other_experts_fail_the_tolerance(model):
    """Two departures the comparison must catch: a window one key shorter
    moves logits past the tolerance, and experts that the reference ranks
    far below its own reach far below 1 (the rule the benchmark's driver
    applies to the program's choices)."""
    cfg, dalle, variables, text, codes = model
    params = variables["params"]
    want, routes = reference.image_logits(params, cfg, text, codes)
    shorter = dataclasses.replace(
        cfg, trunk=dataclasses.replace(cfg.trunk, window=cfg.trunk.window - 1))
    off, _ = reference.image_logits(params, shorter, text, codes)
    assert _err_std(off, want) > 0.05 > LOGIT_TOL
    other = (routes["top_idx"] + 1) % cfg.trunk.experts
    moved, info = reference.image_logits(params, cfg, text, codes,
                                         routing=other)
    assert _err_std(moved, want) > 0.05
    assert float(info["reach"].min()) < 0.9
    # handed its own choices, the reference gives its own logits
    same, info = reference.image_logits(params, cfg, text, codes,
                                        routing=routes["top_idx"])
    np.testing.assert_allclose(same, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(info["reach"], 1.0)


@pytest.mark.parametrize("n_prime", [0, 7])
def test_decode_codes_with_prime_codes_matches_a_stepwise_oracle(model,
                                                                 n_prime):
    """Greedy ``generate_codes(prime_codes=...)`` over the ring carry against
    re-running the whole forward pass for every token (no cache)."""
    cfg, dalle, variables, text, codes = model
    prime = codes[:, :n_prime]
    got = np.asarray(jax.jit(lambda v, t, p: generate_codes(
        dalle, v, t, jax.random.PRNGKey(3), prime_codes=p, filter_thres=1.0))(
            variables, text, prime))
    split = cfg.total_text_tokens
    out = prime
    for t in range(n_prime, cfg.image_seq_len):
        padded = jnp.pad(out, ((0, 0), (0, cfg.image_seq_len - t)))
        logits = dalle.apply(variables, text, padded)
        nxt = logits[:, cfg.text_seq_len + t, split:].argmax(-1)
        out = jnp.concatenate([out, nxt[:, None].astype(jnp.int32)], 1)
    np.testing.assert_array_equal(got, out)
    np.testing.assert_array_equal(got[:, :n_prime], prime)


def test_loss_and_gradients_match_the_reference(model):
    cfg, dalle, variables, text, codes = model
    params = variables["params"]
    loss, grads = jax.value_and_grad(lambda p: dalle.apply(
        {"params": p}, text, codes, return_loss=True))(params)
    want_loss, want = jax.value_and_grad(
        lambda p: reference.train_loss(p, cfg, text, codes))(params)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    worst = jax.tree.map(lambda g, w: float(
        jnp.abs(g - w).max() / (jnp.abs(w).max() + 1e-12)), grads, want)
    assert max(jax.tree.leaves(worst)) <= 1e-4, worst
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
    # every bank and the router learn: the layer is differentiable through
    # its routing weights
    ff = grads["transformer"]["layers_1_ff"]["moe"]
    assert all(float(jnp.abs(ff[k]).max()) > 0
               for k in ("w_router", "w_gate", "w_up", "w_down"))


def test_train_step_trains_the_trunk(model):
    from dalle_pytorch_tpu.training import (make_dalle_train_step,
                                            make_optimizer)

    _, dalle, variables, text, codes = model
    tx = make_optimizer(3e-3)
    params = jax.tree.map(jnp.copy, variables["params"])
    opt_state = tx.init(params)
    step = make_dalle_train_step(dalle, tx, donate=False)
    losses = []
    for i in range(12):
        params, opt_state, loss = step(params, opt_state, None, text, codes,
                                       jax.random.PRNGKey(i))
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < 0.8 * losses[0], losses


# --- rotation and the window --------------------------------------------------------

@pytest.mark.parametrize("dh,theta", [(8, 1.5e6), (128, 1.5e6), (16, 1e4)])
def test_rope_matches_a_complex_number_oracle(dh, theta):
    """Dimension i < dh / 2 and i + dh / 2 are the real and imaginary part of
    one complex number, turned by ``exp(1j p theta^(-2i / dh))``; float64."""
    r = np.random.default_rng(0)
    x = r.normal(size=(2, 3, 7, dh))
    pos = np.array([0, 1, 2, 5, 100, 4351, 16383])
    half = dh // 2
    z = (x[..., :half] + 1j * x[..., half:]) * np.exp(
        1j * pos[:, None] * theta ** (-2.0 * np.arange(half) / dh))
    want = np.concatenate([z.real, z.imag], -1)
    got = apply_rope(jnp.asarray(x, jnp.float32), jnp.asarray(pos), theta)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    # per-row positions, as the arena's tick hands them
    rows = np.stack([pos, pos[::-1]])
    both = apply_rope(jnp.asarray(x, jnp.float32), jnp.asarray(rows), theta)
    np.testing.assert_allclose(both[0], got[0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        both[1], apply_rope(jnp.asarray(x[1:], jnp.float32),
                            jnp.asarray(pos[::-1]), theta)[0],
        rtol=1e-6, atol=1e-6)
    # q.k after rotation depends on the distance alone
    q, k = (jnp.asarray(r.normal(size=(1, 1, 1, dh)), jnp.float32)
            for _ in range(2))

    def dot(pq, pk):
        return float((apply_rope(q, jnp.asarray([pq]), theta)
                      * apply_rope(k, jnp.asarray([pk]), theta)).sum())

    assert dot(9, 4) == pytest.approx(dot(105, 100), rel=1e-3, abs=1e-4)


def test_the_global_layer_is_untouched_by_position(model):
    """A global layer has no position encoding at all: through one such
    layer (causal, so deeper stacks would see the order through the
    positions in between) the last position's logits do not change when
    earlier tokens swap places; through one window layer they do."""
    base = dict(TRUNK, window=0, mixers=["attention"])
    rotated = dict(TRUNK, window=24, mixers=["window"])
    for trunk, moves in ((base, False), (rotated, True)):
        cfg, dalle, variables, text, codes = _model(
            geometry=dict(GEOMETRY, depth=1), trunk=trunk)
        assert cfg.rotary == moves
        assert ("text_pos_emb" in variables["params"]) == (not moves)
        swapped = codes.at[:, 2].set(codes[:, 5]).at[:, 5].set(codes[:, 2])
        a = dalle.apply(variables, text, codes)[:, -1]
        b = dalle.apply(variables, text, swapped)[:, -1]
        if not moves:
            # the learned client embeddings are the only position signal
            # left: zero them
            zeroed = jax.tree_util.tree_map_with_path(
                lambda path, leaf: leaf * 0 if "pos_emb" in
                jax.tree_util.keystr(path) else leaf, variables)
            a = dalle.apply(zeroed, text, codes)[:, -1]
            b = dalle.apply(zeroed, text, swapped)[:, -1]
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
        else:
            assert float(jnp.abs(a - b).max()) > 1e-3


@pytest.mark.parametrize("n,window", [(24, 8), (24, 5), (12, 40), (9, 1)])
def test_window_mask_is_one_predicate_in_three_forms(n, window):
    """Key j visible to query i iff ``i - window < j <= i``: as written, as
    ``_allowed`` over a grid, as the dense training mask, as the traced
    decode row, and as the ring's slot positions give it."""
    pattern = AttnPattern("full", seq_len=n, text_len=4, fmap=0,
                          window=window)
    i, j = np.arange(n)[:, None], np.arange(n)[None, :]
    want = (j <= i) & (j > i - window)
    np.testing.assert_array_equal(_allowed(pattern, i, j, np), want)
    np.testing.assert_array_equal(dense_pattern_mask(pattern, n, n), want)
    np.testing.assert_array_equal(reference.visible(n, window), want)
    slots = pattern.cache_len
    assert slots == min(window, n)
    for q in range(n):
        np.testing.assert_array_equal(
            pattern_mask_row(pattern, jnp.asarray(q), n), want[q])
        held = np.asarray(ring_positions(jnp.asarray(q), slots))
        assert sorted(p for p in held if p >= 0) == list(
            range(max(0, q - slots + 1), q + 1))
        assert all(p % slots == s for s, p in enumerate(held) if p >= 0)
        seen = np.asarray(_allowed(pattern, q, held, np) & (held >= 0))
        assert set(held[seen]) == set(np.flatnonzero(want[q]))
    # unbounded: the pattern and its cache are what they were; the repr,
    # which keys the kept kernels, tells a window from none
    plain = AttnPattern("full", seq_len=n, text_len=4, fmap=0)
    assert plain.window == 0 and plain.cache_len == n
    assert f"window={window}" in repr(pattern) != repr(plain)
    with pytest.raises(AssertionError):
        AttnPattern("axial_row", seq_len=n, text_len=4, fmap=2, window=4)
    assert flash_tiles(1024, 4, 128, jnp.bfloat16, dataclasses.replace(
        pattern, seq_len=1024, window=512)) is None


# --- the expert layer ----------------------------------------------------------------

def _experts(tokens=10, e=8, k=3, dim=16, width=12, seed=0):
    layer = moe.ExpertsReGLU(dim=dim, experts=e, k=k, expert_dim=width)
    r = np.random.default_rng(seed)
    m = jnp.asarray(r.normal(size=(1, tokens, dim)), jnp.float32)
    logits = jnp.asarray(r.normal(size=(1, tokens, e)), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(seed), m, logits)
    return layer, variables, m, logits


def _every_expert_every_token(params, m, logits, k):
    """The layer as a plain loop: each token through each of its k experts,
    weighted by the renormalised softmax (numpy, float64)."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    m, logits = np.asarray(m, np.float64), np.asarray(logits, np.float64)
    out = np.zeros_like(m)
    for b in range(m.shape[0]):
        for t in range(m.shape[1]):
            probs = np.exp(logits[b, t] - logits[b, t].max())
            probs /= probs.sum()
            chosen = np.argsort(-probs, kind="stable")[:k]
            for e in chosen:
                act = (np.maximum(m[b, t] @ p["w_gate"][e], 0)
                       * (m[b, t] @ p["w_up"][e]))
                out[b, t] += probs[e] / probs[chosen].sum() * (
                    act @ p["w_down"][e])
    return out


def test_expert_layer_equals_every_expert_on_every_token():
    layer, variables, m, logits = _experts()
    got = layer.apply(variables, m, logits)
    want = _every_expert_every_token(variables["params"], m, logits, 3)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_expert_layer_is_dropless_when_every_token_chooses_one_expert():
    """Every token's largest logit on expert 2 (a capacity dispatch at factor
    1.25 would drop most of them): all tokens still get all three of their
    experts, with their weights."""
    layer, variables, m, logits = _experts(tokens=40)
    forced = logits.at[..., 2].set(50.0)
    got, state = layer.apply(variables, m, forced, mutable=["intermediates"])
    chosen = state["intermediates"]["top_idx"][0]
    assert chosen.shape == (1, 40, 3) and bool((chosen[..., 0] == 2).all())
    want = _every_expert_every_token(variables["params"], m, forced, 3)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    _, _, combine = moe.route(forced, 3)
    np.testing.assert_allclose(combine.sum(-1), 1.0, rtol=1e-6)
    assert int((combine > 0).sum(-1).min()) == 3       # none dropped


def test_sequence_form_and_tick_form_of_the_expert_layer_agree():
    """A sequence in one call against its rows one tick at a time, and a
    prompt-sized call against the plain loop (one form serves both)."""
    layer, variables, m, logits = _experts(tokens=12)
    whole = layer.apply(variables, m, logits)
    for t in range(12):
        tick = layer.apply(variables, m[:, t:t + 1], logits[:, t:t + 1])
        np.testing.assert_allclose(tick[:, 0], whole[:, t], rtol=1e-5,
                                   atol=1e-6)
    layer, variables, m, logits = _experts(tokens=263, seed=1)
    got = layer.apply(variables, m, logits)
    want = _every_expert_every_token(variables["params"], m, logits, 3)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def _former_moe_outputs(params, x, e, k):
    """``MoEFeedForward``'s dense dispatch as it was written before the
    routing rule was shared (PR 32's parent), inlined."""
    import flax.linen as nn

    logits = x.astype(jnp.float32) @ params["router"]["kernel"] + params[
        "router"]["bias"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, k)
    onehot = jax.nn.one_hot(top_idx, e, dtype=probs.dtype)
    combine = (top_p[..., None] * onehot).sum(axis=-2)
    combine = combine / jnp.clip(combine.sum(axis=-1, keepdims=True), 1e-9)
    top1 = jax.nn.one_hot(jnp.argmax(probs, axis=-1), e, dtype=jnp.float32)
    aux = (top1.mean(axis=(0, 1)) * probs.mean(axis=(0, 1))).sum() * e
    h = jnp.einsum("bnd,edi->bnei", x, params["w_in"]) + params["b_in"]
    h, gates = jnp.split(h, 2, axis=-1)
    h = h * nn.gelu(gates)
    y = jnp.einsum("bnei,eid->bned", h, params["w_out"]) + params["b_out"]
    return jnp.einsum("bned,bne->bnd", y, combine), aux


@pytest.mark.parametrize("e,k", [(4, 2), (8, 3), (5, 5)])
def test_shared_routing_leaves_the_old_layer_bit_for_bit(e, k):
    layer = moe.MoEFeedForward(dim=16, num_experts=e, top_k=k, mult=2)
    x = jax.random.normal(jax.random.PRNGKey(e), (2, 9, 16))
    variables = layer.init(jax.random.PRNGKey(k), x)
    got, aux = layer.apply(variables, x)
    want, want_aux = _former_moe_outputs(variables["params"], x, e, k)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(aux, want_aux)
    cap, _ = moe.MoEFeedForward(dim=16, num_experts=e, top_k=k, mult=2,
                                dispatch="capacity",
                                capacity_factor=float(e)).apply(variables, x)
    np.testing.assert_allclose(cap, want, rtol=1e-5, atol=1e-6)


# --- the configuration field -----------------------------------------------------------

def test_spec_round_trips_and_names_each_layers_state():
    cfg = DALLEConfig(**GEOMETRY, trunk=dict(TRUNK))
    assert isinstance(cfg.trunk, TrunkSpec) and cfg.trunk.routed
    assert cfg.mixers == ("attention", "window", "window", "window")
    assert cfg.cache_lens == (24, 8, 8, 8) == layer_cache_lens(
        cfg.trunk, 4, 24)
    assert cfg.kv_heads == 2 and cfg.rotary
    import json
    assert DALLEConfig.from_dict(json.loads(json.dumps(cfg.to_dict()))) == cfg
    # the fields a trunk from before PR 32 lacks default to what it was
    old = TrunkSpec(mixers=("mamba", "attention"), ff_dim=96)
    assert (old.window, old.ff, old.tied_table, old.rotary, old.routed) == (
        0, "swiglu", True, False, False)


@pytest.mark.parametrize("bad", [
    dict(window=0), dict(mixers=["attention"], window=8),
    dict(experts_per_token=9), dict(expert_dim=0), dict(experts=2),
    dict(ff="swiglu")])
def test_trunk_spec_refuses_what_it_cannot_build(bad):
    with pytest.raises(AssertionError):
        TrunkSpec(**{**TRUNK, **bad})


@pytest.mark.parametrize("field,value", [
    ("reversible", True), ("weights_int8", True),
    ("kv_cache_int8", True), ("ring_axis", "sp"), ("ff_experts", 4)])
def test_paths_without_a_form_for_this_trunk_refuse(field, value):
    with pytest.raises(AssertionError):
        DALLEConfig(**GEOMETRY, trunk=dict(TRUNK), **{field: value})


def test_the_pipeline_step_refuses_the_trunk():
    from dalle_pytorch_tpu.training import make_dalle_pp_train_step

    dalle = DALLE(DALLEConfig(**GEOMETRY, trunk=dict(TRUNK)))
    with pytest.raises(AssertionError, match="differ in kind"):
        make_dalle_pp_train_step(dalle, None, None, None, num_microbatches=1)


def test_parameter_tree_has_a_head_banks_and_no_position_embedding():
    cfg = DALLEConfig(**GEOMETRY, dtype=jnp.bfloat16,
                      trunk={**TRUNK, "param_dtype": "bfloat16"})
    text = jnp.zeros((1, cfg.text_seq_len), jnp.int32)
    codes = jnp.zeros((1, cfg.image_seq_len), jnp.int32)
    params = jax.eval_shape(DALLE(cfg).init, jax.random.PRNGKey(0), text,
                            codes)["params"]
    assert set(params) == {"table", "head", "transformer", "final_norm"}
    assert params["head"].shape == params["table"]["embedding"].shape == (
        cfg.total_tokens, cfg.dim)
    ff = params["transformer"]["layers_2_ff"]
    assert {k: v.shape for k, v in ff["moe"].items()} == {
        "w_router": (32, 8), "w_gate": (8, 32, 24), "w_up": (8, 32, 24),
        "w_down": (8, 24, 32)}
    flat = {jax.tree_util.keystr(path): leaf.dtype for path, leaf in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    for name, dtype in flat.items():
        assert dtype == (jnp.float32 if "norm" in name else jnp.bfloat16), (
            name, dtype)


def test_the_model_is_reachable_by_name_and_matches_the_benchmarks_file():
    import json

    from dalle_pytorch_tpu import presets

    cfg = presets.preset_config("smallthinker-21ba3b")
    assert cfg.mixers == ("attention", "window", "window", "window")
    assert cfg.total_tokens == 151936 and cfg.seq_len == 4352
    assert cfg.cache_lens == (4352, 4096, 4096, 4096)
    assert presets.check_param_band("smallthinker-21ba3b")
    tiny = presets.preset_config("smallthinker-tiny")
    assert tiny.trunk.window < tiny.text_seq_len + 1 < tiny.seq_len
    bench = json.loads((REPO / "benchmark/configs/smallthinker-21ba3b.json"
                        ).read_text())
    # the benchmark's file dates from PR 32: every field it names, and the
    # fields added since at the values that leave this trunk as it was
    trunk = cfg.to_dict()["trunk"]
    assert {k: trunk[k] for k in bench["dalle"]["trunk"]} == bench["dalle"][
        "trunk"]
    assert TrunkSpec(**bench["dalle"]["trunk"]) == cfg.trunk
    for key in ("dim", "depth", "heads", "dim_head", "text_seq_len",
                "num_text_tokens"):
        assert getattr(cfg, key) == bench["dalle"][key], key


def test_every_new_leaf_meets_a_sharding_rule_and_dp_replicates_the_banks(
        model):
    import re

    from dalle_pytorch_tpu.parallel.plan import PARTITION_RULES, ParallelPlan

    _, _, variables, _, _ = model
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            variables["params"])[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if leaf.ndim < 2:
            continue
        pat, spec = next((pat, spec) for pat, spec in PARTITION_RULES
                         if re.match(pat, name))
        assert len(spec) == leaf.ndim, (name, spec, leaf.shape)
        assert pat != r".*/kernel$" or "attn" in name, (name, pat)
    part = ParallelPlan("dp").partitioner(devices=jax.devices()[:4])
    shardings = part.param_shardings(variables["params"])
    bank = shardings["transformer"]["layers_1_ff"]["moe"]["w_gate"]
    assert bank.is_fully_replicated


# --- the carry: tile_prefill, decode_codes, the arena ---------------------------------

def test_prefill_writes_the_last_window_of_a_long_prompt_into_the_ring(model):
    cfg, dalle, variables, text, codes = model
    n_prime = 9                                  # 18 positions for 8 slots
    first, caches = prefill_codes(dalle, variables, text[:1],
                                  prime_codes=codes[:1, :n_prime])
    assert [e[0].shape for e in caches] == [
        (1, 2, 24, 8), (1, 2, 8, 8), (1, 2, 8, 8), (1, 2, 8, 8)]
    # the ring holds positions 10..17, each in slot p mod 8, as a walk of
    # decode steps from the unprimed prompt leaves them
    _, short = prefill_codes(dalle, variables, text[:1])
    for t in range(n_prime):
        _, short = dalle.apply(variables, codes[:1, t], short,
                               jnp.asarray(9 + t), method=DALLE.decode_step)
    for ring, stepped in zip(caches[1:], short[1:]):
        np.testing.assert_allclose(ring[0], stepped[0], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(ring[1], stepped[1], rtol=1e-5, atol=1e-6)
    tiled_first, tiled = tile_prefill(first, caches, 3)
    assert [e[0].shape[0] for e in tiled] == [3] * 4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_rings_per_row_write_touches_one_column_of_each_row(dtype):
    """``cache_write_rows``, the arena's ring write: row r's new keys land in
    column ``columns[r]`` and nowhere else, every other column and every
    other row keep what they held, in the ring's storage dtype."""
    rows, heads, slots, dh = 3, 2, 8, 4
    ring = jax.random.normal(jax.random.PRNGKey(0),
                             (rows, heads, slots, dh)).astype(dtype)
    new = jax.random.normal(jax.random.PRNGKey(1), (rows, heads, 1, dh))
    columns = jnp.asarray([5, 0, 5], jnp.int32)
    written = cache_write_rows(ring, new, columns)
    assert written.dtype == dtype
    out = np.asarray(written.astype(jnp.float32))
    want = np.asarray(ring.astype(jnp.float32)).copy()
    for r, c in enumerate(np.asarray(columns)):
        want[r, :, c] = np.asarray(new[r, :, 0].astype(dtype).astype(
            jnp.float32))
    np.testing.assert_array_equal(out, want)
    # each row's untouched columns, bit for bit
    for r, c in enumerate(np.asarray(columns)):
        keep = np.arange(slots) != c
        np.testing.assert_array_equal(
            out[r][:, keep], np.asarray(ring[r].astype(jnp.float32))[:, keep])


@pytest.fixture(scope="module")
def served(model):
    from dalle_pytorch_tpu.serve import GenerationServer

    cfg, dalle, variables, _, _ = model
    texts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(i), (cfg.text_seq_len,), 1, 50), np.int32)
        for i in range(4)]
    prefill = jax.jit(lambda p, t: prefill_codes(dalle, p, t))

    def static(i):
        first, caches = prefill(variables, jnp.asarray(texts[i])[None])
        return np.asarray(decode_codes(dalle, variables, first, caches,
                                       jax.random.PRNGKey(7),
                                       filter_thres=1.0))[0]

    def server(num_slots, **kw):
        return GenerationServer(dalle, variables, num_slots=num_slots,
                                filter_thres=1.0, **kw)

    return texts, [static(i) for i in range(4)], server


def test_arena_matches_static_decode_code_for_code(served):
    """Admit, tick with an inactive slot, admit mid-flight at another depth
    (the rows' rings then sit at different phases), retire, re-admit into
    the freed slot: every request's codes are the static sampler's, and each
    entry point compiled once."""
    texts, refs, server = served
    srv = server(2)
    h0 = srv.submit(texts[0])
    for _ in range(5):
        srv.step()
    h1 = srv.submit(texts[1])
    for _ in range(3):
        srv.step()
    h2, h3 = srv.submit(texts[2]), srv.submit(texts[3])
    srv.run_until_idle(max_ticks=400)
    for h, ref in zip((h0, h1, h2, h3), refs):
        np.testing.assert_array_equal(h.result(0), ref)
    assert srv.trace_counts() == {"prefill": 1, "admit": 1, "tick": 1}


def test_arena_holds_each_layers_own_slots_and_its_logits_are_the_static_paths(
        model):
    from dalle_pytorch_tpu.serve.engine import SlotArena

    cfg, dalle, variables, text, codes = model
    arena = SlotArena(dalle, variables, 3, filter_thres=1.0)
    assert [tuple(a.shape for a in e) for e in arena.state["caches"]] == [
        ((3, 2, 24, 8),) * 2] + [((3, 2, 8, 8),) * 2] * 3
    # one slot admitted at clock 5: its tick's logits against the static
    # path's decode_step from the same prefill
    first, caches = arena.prefill(text[:1])
    arena.admit(1, first, caches, jax.random.PRNGKey(0), 1.0, clock=5)
    code = arena.state["code"][1]
    want, _ = dalle.apply(variables, code[None], caches,
                          jnp.asarray(cfg.text_seq_len + 1),
                          method=DALLE.decode_step)
    got, _ = dalle.apply(
        variables, arena.state["code"], arena.state["caches"],
        arena.state["index"], None, jnp.int32(5), None,
        method=DALLE.decode_step)
    assert _err_std(got[1:2], want) <= LOGIT_TOL


# --- spans and counters ------------------------------------------------------------------

def test_expert_scopes_are_siblings_of_the_attention_scopes(model):
    """``moe-route`` and ``moe-experts`` are in the scope table, a decode
    step's equations sit under them, and neither nests in ``ff``, in an
    attention scope or in the other."""
    import re

    cfg, dalle, variables, text, codes = model
    assert {"moe-route", "moe-experts"} <= set(prof.SCOPES)
    first, caches = prefill_codes(dalle, variables, text)
    jaxpr = jax.make_jaxpr(lambda v, c, s: dalle.apply(
        v, c, s, jnp.asarray(cfg.text_seq_len + 1),
        method=DALLE.decode_step))(variables, codes[:, 0], caches)
    stacks = {str(eqn.source_info.name_stack) for eqn in jaxpr.jaxpr.eqns}
    chains = {tuple(re.findall(r"graftprof:([a-z0-9_-]+)", s))
              for s in stacks}
    inner = {c[-1] for c in chains if c}
    assert {"moe-route", "moe-experts", "attn-scores", "attn-cache",
            "attn-qkv"} <= inner and "ff" not in inner
    for chain in chains:
        for outer in chain[:-1]:
            assert not outer.startswith("moe-"), chain
            if chain[-1].startswith("moe-"):
                assert outer == "decode-step", chain


def test_traces_report_their_routing_and_the_decode_layout(model, tmp_path):
    cfg, dalle, variables, text, codes = model
    reg = metrics.init()
    tel = telemetry.init(tmp_path, run_id="moe-layout")
    try:
        jax.jit(lambda v, t, c: dalle.apply(v, t, c))(variables, text, codes)
        first, caches = tile_prefill(*prefill_codes(dalle, variables,
                                                    text[:1]), 4)
        jax.jit(lambda v, f, c, k: decode_codes(dalle, v, f, c, k))(
            variables, first, caches, jax.random.PRNGKey(0))
        rendered = reg.render()
    finally:
        telemetry.shutdown()
        metrics.shutdown()
    events = telemetry.read_events(tel.path)
    routes = [e for e in events
              if e["kind"] == "moe" and e["name"] == "route"]
    # the forward over 2 x 24 tokens, then the prefill over 1 x 9
    assert [(e["tokens"], e["experts"], e["k"]) for e in routes] == [
        (48, 8, 3), (9, 8, 3)]
    layout = [e for e in events
              if e["kind"] == "decode" and e["name"] == "moe_layout"]
    assert len(layout) == 1
    bank_bytes = 3 * 8 * 32 * 24 * 4
    assert {k: layout[0][k] for k in (
        "layers", "experts", "experts_per_token", "rows",
        "expert_bytes_per_layer", "window_layers", "kv_slots_per_row")} == {
        "layers": 4, "experts": 8, "experts_per_token": 3, "rows": 4,
        "expert_bytes_per_layer": bank_bytes,
        "window_layers": 3, "kv_slots_per_row": 24 + 3 * 8}
    for line in ("graft_decode_moe_layers 4", "graft_decode_window_layers 3",
                 "graft_decode_kv_slots_per_row 48",
                 "graft_decode_kv_layers 4", "graft_decode_ssm_layers 0"):
        assert line in rendered, line
    text_report = render_text(build_report(events))
    assert "-- decode --" in text_report
    assert ("routed experts: 4 layers of 8, 3 a token, at 4 "
            f"rows ({bank_bytes} bytes of banks a layer); 3 window layers, "
            "48 key slots a row") in text_report


# --- the other configurations' programs have not moved --------------------------------------

def _digest(lowered) -> str:
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


def _programs(cfg):
    """The three programs a generate cell and a train cell run, lowered at
    toy width: forward loss + gradient, prefill, the decode scan."""
    dalle = DALLE(cfg)
    text = jnp.ones((2, cfg.text_seq_len), jnp.int32)
    codes = jnp.zeros((2, cfg.image_seq_len), jnp.int32)
    variables = jax.eval_shape(dalle.init, jax.random.PRNGKey(0), text, codes)
    first, caches = jax.eval_shape(
        lambda v, t: prefill_codes(dalle, v, t), variables, text)
    return {
        "grad": jax.jit(jax.grad(lambda v, t, c: dalle.apply(
            v, t, c, return_loss=True))).lower(variables, text, codes),
        "prefill": jax.jit(lambda v, t: prefill_codes(dalle, v, t)).lower(
            variables, text),
        "decode": jax.jit(lambda v, f, c, k: decode_codes(
            dalle, v, f, c, k, filter_thres=0.9)).lower(
                variables, first, caches, jax.random.PRNGKey(0))}


#: sha256[:16] of each program's StableHLO text at PR 32's parent (a59357f),
#: written by this very function run in a checkout of it.  ``decode``: as
#: PR 33 left it, which moved ``decode_step``'s masked read into one jitted
#: function the layers share (the same operations at these widths, one read
#: a cache; fd8f1b4a8f8bcc36 / 97c4b3e90ce5d854 / 9c92037e888ce7ad before).
PARENT_PROGRAMS = {
    "jamba-tiny": {"grad": "538fdfe5a0388221", "prefill": "fd637bdb401f3a54", "decode": "bafb4c6346238832"},
    "cub200-tiny": {"grad": "060a49282343e35c", "prefill": "b6c3bf8521b2e723", "decode": "37a99c20f70ee99c"},
    "lucid1024-tiny": {"grad": "a49d823ab2963c63", "prefill": "7599dad17aa699e1", "decode": "4aed9037a547f015"},
}


def _other_configs():
    from dalle_pytorch_tpu import presets

    return {
        "jamba-tiny": presets.preset_config("jamba-tiny"),
        "cub200-tiny": presets.tiny_config(
            depth=4, attn_types=("full", "axial_row", "axial_col",
                                 "conv_like"), dtype=jnp.bfloat16),
        "lucid1024-tiny": presets.tiny_config(
            attn_types=("full",), attn_dropout=0.1, ff_dropout=0.1,
            dtype=jnp.bfloat16)}


@pytest.mark.parametrize("name", ["jamba-tiny", "cub200-tiny",
                                  "lucid1024-tiny"])
def test_the_other_configurations_lower_to_the_parents_text(name):
    """``TrunkSpec``'s and ``AttnPattern``'s new fields default to what the
    code did before them: the twins of ``jamba2-3b``, ``cub200`` and
    ``lucid1024`` lower, program for program, to the text they lowered to at
    the parent commit."""
    got = {k: _digest(v) for k, v in _programs(_other_configs()[name]).items()}
    assert got == PARENT_PROGRAMS[name], got
