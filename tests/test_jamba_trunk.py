"""DALL-E over a ``TrunkSpec`` trunk (PERF.md, Findings PR 27): Mamba-1
layers among multi-query attention layers, RMSNorm, SwiGLU, one tied table.

Tiny widths, seeded weights, float32 parameters unless a test says bfloat16,
on the CPU.  The program is held to ``benchmark/reference_jamba2_3b.py``
(which imports nothing from it): the state-space operators in their two
forms, multi-query attention, prefill + ``decode_step`` logits, the loss and
its gradients; then the carry through ``tile_prefill``, ``decode_codes`` and
the ``SlotArena``, the refusing asserts, the parameter dtypes, the sharding
rules, the train step and the trace-time counters.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from benchmark import reference_jamba2_3b as reference  # noqa: E402
from dalle_pytorch_tpu import DALLE, DALLEConfig  # noqa: E402
from dalle_pytorch_tpu.models.dalle import (  # noqa: E402
    decode_codes, prefill_codes, tile_prefill)
from dalle_pytorch_tpu.obs import metrics, prof, telemetry  # noqa: E402
from dalle_pytorch_tpu.obs.report import build_report, render_text  # noqa: E402
from dalle_pytorch_tpu.ops import ssm  # noqa: E402
from dalle_pytorch_tpu.ops.attention import (  # noqa: E402
    AttnPattern, MultiHeadAttention, kv_fold_factor)
from dalle_pytorch_tpu.ops.transformer import TrunkSpec  # noqa: E402

TRUNK = dict(mixers=["mamba", "attention", "mamba"], ff_dim=96, kv_heads=1,
             ssm_state=4, ssm_dt_rank=4, param_dtype="float32")
GEOMETRY = dict(dim=32, depth=3, heads=4, dim_head=8, num_text_tokens=50,
                text_seq_len=8, num_image_tokens=32, image_size=32,
                image_fmap_size=4)

#: Largest |program - reference| in units of the reference logits' standard
#: deviation, for float32 parameters and caches on the CPU: both sides are
#: float32 and differ in the order of sums only (the chunked scan against
#: the sequential one), which measures 1e-5.  1e-3 is a hundred times that,
#: and fails a bfloat16 recurrent state (3e-2) and a dropped norm (4.2) alike.
LOGIT_TOL = 1e-3


def _model(**overrides):
    cfg = DALLEConfig(**{**GEOMETRY, "trunk": TRUNK, "kv_cache_bf16": False,
                         **overrides})
    dalle = DALLE(cfg)
    rng = np.random.default_rng(0)
    text = jnp.asarray(rng.integers(1, 50, (2, cfg.text_seq_len)),
                       jnp.int32).at[:, 5:].set(0)
    codes = jnp.asarray(rng.integers(0, 32, (2, cfg.image_seq_len)),
                        jnp.int32)
    variables = dalle.init(jax.random.PRNGKey(0), text, codes)
    # move every leaf off its initial value (gains 1, biases 0), so that
    # each one matters to the comparison
    variables = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(
            jax.random.PRNGKey(a.size), a.shape, a.dtype), variables)
    return cfg, dalle, variables, text, codes


@pytest.fixture(scope="module")
def model():
    return _model()


def _err_std(got, ref):
    return float((jnp.abs(got - ref) / ref.std(-1, keepdims=True)).max())


def _teacher_forced(dalle, variables, text, codes):
    """Image logits through ``DALLE.prefill`` and ``DALLE.decode_step``."""
    cfg = dalle.cfg
    first, caches = dalle.apply(variables, text, method=DALLE.prefill)
    outs = [first]
    for t in range(cfg.image_seq_len - 1):
        logits, caches = dalle.apply(
            variables, codes[:, t], caches,
            jnp.asarray(cfg.text_seq_len + 1 + t), method=DALLE.decode_step)
        outs.append(logits)
    return jnp.stack(outs, axis=1)


# --- the state-space operators ------------------------------------------------

def _scan_inputs(n, b=2, d_in=6, N=4, seed=0):
    r = np.random.default_rng(seed)
    u = jnp.asarray(r.normal(size=(b, n, d_in)), jnp.float32)
    delta = jnp.asarray(r.uniform(1e-3, 0.5, size=(b, n, d_in)), jnp.float32)
    A = -jnp.exp(jnp.asarray(r.normal(size=(N, d_in)), jnp.float32))
    B = jnp.asarray(r.normal(size=(b, n, N)), jnp.float32)
    C = jnp.asarray(r.normal(size=(b, n, N)), jnp.float32)
    return u, delta, A, B, C


def _sequential_scan(u, delta, A, B, C):
    """The recurrence as the reference writes it: one step per position."""
    h = np.zeros((u.shape[0], A.shape[0], u.shape[2]))
    ys = []
    for t in range(u.shape[1]):
        h = (np.exp(delta[:, t, None] * A) * h
             + (delta[:, t] * u[:, t])[:, None] * B[:, t, :, None])
        ys.append((h * C[:, t, :, None]).sum(1))
    return np.stack(ys, 1), h


@pytest.mark.parametrize("n,chunk", [(7, 64), (8, 4), (13, 4), (5, 1)])
def test_chunked_scan_matches_sequential_across_chunk_boundaries(n, chunk):
    """A sequence inside one chunk, chunks that divide it, chunks that do
    not (the padded tail leaves the state alone), and chunks of one."""
    args = _scan_inputs(n)
    want_y, want_h = _sequential_scan(*map(np.asarray, args))
    y, h = ssm.selective_scan(*args, chunk=chunk)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h, want_h, rtol=1e-5, atol=1e-5)


def test_step_form_matches_sequence_form_and_carries_its_state():
    u, delta, A, B, C = _scan_inputs(9)
    y_seq, h_seq = ssm.selective_scan(u, delta, A, B, C, chunk=4)
    h = jnp.zeros_like(h_seq)
    for t in range(u.shape[1]):
        y, h = ssm.selective_scan_step(h, u[:, t], delta[:, t], A, B[:, t],
                                       C[:, t])
        np.testing.assert_allclose(y, y_seq[:, t], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h, h_seq, rtol=1e-5, atol=1e-5)
    # the sequence form continues from a carried state
    y2, h2 = ssm.selective_scan(u[:, 5:], delta[:, 5:], A, B[:, 5:], C[:, 5:],
                                h0=ssm.selective_scan(
                                    u[:, :5], delta[:, :5], A, B[:, :5],
                                    C[:, :5])[1])
    np.testing.assert_allclose(y2, y_seq[:, 5:], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h2, h_seq, rtol=1e-5, atol=1e-5)


def test_conv_step_matches_sequence_form_and_rolls_its_window():
    r = np.random.default_rng(1)
    u = jnp.asarray(r.normal(size=(2, 6, 5)), jnp.float32)
    kernel = jnp.asarray(r.normal(size=(4, 5)), jnp.float32)
    bias = jnp.asarray(r.normal(size=(5,)), jnp.float32)
    want = np.zeros((2, 6, 5))
    padded = np.pad(np.asarray(u), ((0, 0), (3, 0), (0, 0)))
    for t in range(6):
        want[:, t] = (padded[:, t:t + 4] * np.asarray(kernel)).sum(1) + bias
    out, window = ssm.causal_conv(u, kernel, bias)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(window, u[:, 3:])
    win = jnp.zeros((2, 3, 5))
    for t in range(6):
        step, win = ssm.causal_conv_step(u[:, t], kernel, bias, win)
        np.testing.assert_allclose(step, want[:, t], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(win, window)


def test_scan_is_differentiable_like_the_sequential_one():
    args = _scan_inputs(6)

    def loss(fn, *a):
        y, h = fn(*a)
        return (y ** 2).sum() + h.sum()

    def sequential(u, delta, A, B, C):
        h = jnp.zeros((u.shape[0], A.shape[0], u.shape[2]))
        ys = []
        for t in range(u.shape[1]):
            y, h = ssm.selective_scan_step(h, u[:, t], delta[:, t], A,
                                           B[:, t], C[:, t])
            ys.append(y)
        return jnp.stack(ys, 1), h

    got = jax.grad(lambda *a: loss(
        lambda *b: ssm.selective_scan(*b, chunk=4), *a), argnums=range(5))(
            *args)
    want = jax.grad(lambda *a: loss(sequential, *a), argnums=range(5))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


# --- multi-query attention ------------------------------------------------------

@pytest.mark.parametrize("kv_heads", [1, 2])
def test_grouped_attention_matches_the_reference(kv_heads):
    """``heads`` queries over ``kv_heads`` keys: the forward pass against
    the reference's (which repeats each key head), and ``decode_step``
    through a cache against the forward pass."""
    n, dim, heads, dh = 10, 16, 4, 8
    attn = MultiHeadAttention(
        pattern=AttnPattern("full", seq_len=n, text_len=4, fmap=0), dim=dim,
        heads=heads, dim_head=dh, kv_heads=kv_heads, use_bias=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, n, dim))
    variables = attn.init(jax.random.PRNGKey(1), x)
    assert set(variables["params"]) == {"to_q", "to_kv", "to_out"}
    assert variables["params"]["to_kv"]["kernel"].shape == (dim, 2, kv_heads,
                                                            dh)
    out, (k, v) = attn.apply(variables, x, return_kv=True)
    assert k.shape == (2, kv_heads, n, dh)
    block = {"norm": {"scale": jnp.ones((dim,))}, "attn": variables["params"]}
    # the reference's attention norms its input: undo it with unit-RMS rows
    unit = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    want = reference._attention(block, x, 1e-6, dh)
    np.testing.assert_allclose(attn.apply(variables, unit), want, rtol=1e-4,
                               atol=1e-5)

    ck = jnp.zeros((2, kv_heads, n, dh))
    cv = jnp.zeros_like(ck)
    for t in range(n):
        step, ck, cv = attn.apply(variables, x[:, t:t + 1], ck, cv,
                                  jnp.asarray(t),
                                  method=MultiHeadAttention.decode_step)
        np.testing.assert_allclose(step[:, 0], out[:, t], rtol=1e-4,
                                   atol=1e-5)


def test_grouped_caches_stay_plain_at_every_shape():
    """``dim_head`` 128 fills the lanes; and where the fold would apply to
    ungrouped heads (4 x 64 at 32 rows), a grouped layer keeps its cache."""
    assert kv_fold_factor(20, 128, jnp.bfloat16) == 1
    assert kv_fold_factor(4, 64, jnp.bfloat16) == 2
    pattern = AttnPattern("full", seq_len=10, text_len=4, fmap=0)
    cache = jnp.zeros((32, 2, 10, 64), jnp.bfloat16)
    for kv_heads, want in ((2, cache.shape), (None, (32, 2, 10, 128))):
        attn = MultiHeadAttention(pattern=pattern, dim=16, heads=4,
                                  dim_head=64, kv_heads=kv_heads)
        full = jnp.zeros((32, 4 if kv_heads is None else 2, 10, 64),
                         jnp.bfloat16)
        got = attn.apply({}, full, method=MultiHeadAttention.lane_dense_cache)
        assert got.shape == want


# --- the model against the reference ----------------------------------------------

def test_forward_logits_and_mask_match_the_reference(model):
    cfg, dalle, variables, text, codes = model
    got = np.asarray(dalle.apply(variables, text, codes))
    want = np.asarray(reference.joint_logits(variables["params"], cfg, text,
                                             codes))
    allowed = np.isfinite(want)
    np.testing.assert_array_equal(allowed, got > -1e30)
    ref = jnp.where(allowed, want, 0.0)
    assert _err_std(jnp.where(allowed, got, 0.0), ref) <= LOGIT_TOL


def test_prefill_and_decode_step_match_the_reference_forward(model):
    cfg, dalle, variables, text, codes = model
    got = _teacher_forced(dalle, variables, text, codes)
    want = reference.image_logits(variables["params"], cfg, text, codes)
    assert got.shape == want.shape == (2, cfg.image_seq_len,
                                       cfg.num_image_tokens)
    assert _err_std(got, want) <= LOGIT_TOL


@pytest.mark.parametrize("departure,least", [
    (dict(state_dtype=jnp.bfloat16), 1e-2),   # a bfloat16 recurrent state
    (dict(norm_dbc=False), 1.0),              # Jamba's dt/B/C norms left out
])
def test_the_tolerance_fails_each_departure(model, departure, least):
    cfg, _, variables, text, codes = model
    want = reference.image_logits(variables["params"], cfg, text, codes)
    off = reference.image_logits(variables["params"], cfg, text, codes,
                                 **departure)
    assert _err_std(off, want) > least > LOGIT_TOL


def test_loss_and_gradients_match_the_reference(model):
    cfg, dalle, variables, text, codes = model
    params = variables["params"]
    loss, grads = jax.value_and_grad(lambda p: dalle.apply(
        {"params": p}, text, codes, return_loss=True))(params)
    want_loss, want = jax.value_and_grad(
        lambda p: reference.train_loss(p, cfg, text, codes))(params)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    # every leaf's gradient, relative to that leaf's largest: float32 on
    # both sides, so only the order of sums differs (measured 6e-6)
    worst = jax.tree.map(lambda g, w: float(
        jnp.abs(g - w).max() / (jnp.abs(w).max() + 1e-12)), grads, want)
    assert max(jax.tree.leaves(worst)) <= 1e-4, worst


def test_bfloat16_program_stays_near_the_reference():
    """Parameters stored in bfloat16 and bfloat16 activations, as the
    benchmark runs the model: tenths of a standard deviation at worst at
    this toy width (0.11 measured), the reference reading the same bfloat16
    tree."""
    cfg, dalle, variables, text, codes = _model(
        trunk={**TRUNK, "param_dtype": "bfloat16"}, dtype=jnp.bfloat16,
        kv_cache_bf16=True)
    got = _teacher_forced(dalle, variables, text, codes)
    want = reference.image_logits(variables["params"], cfg, text, codes)
    assert _err_std(got.astype(jnp.float32), want) <= 0.3


# --- the configuration field --------------------------------------------------------

def test_trunk_arrives_as_a_dict_and_the_config_stays_hashable():
    cfg = DALLEConfig(**GEOMETRY, trunk=dict(TRUNK))
    assert isinstance(cfg.trunk, TrunkSpec)
    assert cfg.mixers == ("mamba", "attention", "mamba")
    assert cfg.kv_heads == 1 and hash(cfg) == hash(
        DALLEConfig(**GEOMETRY, trunk=TrunkSpec(**TRUNK)))
    saved = cfg.to_dict()
    assert saved["trunk"]["mixers"] == ["mamba", "attention", "mamba"]
    import json
    assert DALLEConfig.from_dict(json.loads(json.dumps(saved))) == cfg


def test_without_a_trunk_the_parameter_tree_keeps_its_names():
    cfg = DALLEConfig(**{**GEOMETRY, "depth": 2})
    assert cfg.trunk is None and cfg.mixers == ("attention",) * 2
    assert "trunk" in cfg.to_dict()
    text = jnp.zeros((1, cfg.text_seq_len), jnp.int32)
    codes = jnp.zeros((1, cfg.image_seq_len), jnp.int32)
    params = jax.eval_shape(DALLE(cfg).init, jax.random.PRNGKey(0), text,
                            codes)["params"]
    assert set(params) == {"text_emb", "image_emb", "text_pos_emb",
                           "image_pos_emb", "transformer", "final_norm",
                           "to_logits_dense"}
    assert set(params["transformer"]) == {
        f"layers_{i}_{part}" for i in range(2) for part in ("attn", "ff")}
    assert set(params["transformer"]["layers_0_attn"]["attn"]) == {
        "to_qkv", "to_out"}
    assert set(params["transformer"]["layers_0_ff"]) == {
        "norm", "dense_in", "dense_out", "scale"}


@pytest.mark.parametrize("field,value", [
    ("reversible", True), ("weights_int8", True),
    ("kv_cache_int8", True), ("sparse_attn", True), ("ring_axis", "sp"),
    ("ff_experts", 4), ("attn_dropout", 0.1), ("ff_dropout", 0.1)])
def test_paths_without_a_meaning_for_recurrent_layers_refuse(field, value):
    with pytest.raises(AssertionError):
        DALLEConfig(**GEOMETRY, trunk=dict(TRUNK), **{field: value})


@pytest.mark.parametrize("bad", [
    dict(mixers=["mamba", "lstm"]), dict(mixers=[]), dict(norm="layer"),
    dict(ff="geglu"), dict(param_dtype="float16")])
def test_trunk_spec_refuses_what_it_cannot_build(bad):
    with pytest.raises(AssertionError):
        TrunkSpec(**{**TRUNK, **bad})


def test_matrices_are_made_in_bfloat16_and_small_tensors_in_float32():
    cfg = DALLEConfig(**GEOMETRY, dtype=jnp.bfloat16,
                      trunk={**TRUNK, "param_dtype": "bfloat16"})
    text = jnp.zeros((1, cfg.text_seq_len), jnp.int32)
    codes = jnp.zeros((1, cfg.image_seq_len), jnp.int32)
    params = jax.eval_shape(DALLE(cfg).init, jax.random.PRNGKey(0), text,
                            codes)["params"]
    flat = {jax.tree_util.keystr(path): leaf.dtype for path, leaf in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    f32 = ("A_log", "'D'", "dt_bias", "norm", "pos_emb")
    for name, dtype in flat.items():
        want = jnp.float32 if any(tag in name for tag in f32) else jnp.bfloat16
        assert dtype == want, (name, dtype)
    assert flat["['table']['embedding']"] == jnp.bfloat16
    init = DALLE(cfg).init(jax.random.PRNGKey(0), text, codes)["params"]
    mixer = init["transformer"]["layers_0_ssm"]["ssm"]
    np.testing.assert_allclose(
        mixer["A_log"][0], np.log(np.arange(1, TRUNK["ssm_state"] + 1)),
        rtol=1e-6)
    dt = jax.nn.softplus(mixer["dt_bias"])
    assert float(dt.min()) >= 1e-3 * 0.99 and float(dt.max()) <= 1e-1 * 1.01
    np.testing.assert_array_equal(mixer["D"], 1.0)


# --- the carry: tile_prefill, decode_codes, the arena ---------------------------------

def test_prefill_carries_two_kinds_of_state_and_tiles_them(model):
    cfg, dalle, variables, text, _ = model
    first, caches = prefill_codes(dalle, variables, text[:1])
    d_in = 2 * cfg.dim
    shapes = [tuple(a.shape for a in entry) for entry in caches]
    assert shapes == [
        ((1, 3, d_in), (1, 4, d_in)),
        ((1, 1, cfg.seq_len, 8), (1, 1, cfg.seq_len, 8)),
        ((1, 3, d_in), (1, 4, d_in))]
    assert caches[0][1].dtype == jnp.float32
    tiled_first, tiled = tile_prefill(first, caches, 3)
    assert tiled_first.shape[0] == 3
    for one, many in zip(jax.tree.leaves(caches), jax.tree.leaves(tiled)):
        assert many.shape == (3,) + one.shape[1:]
        for row in range(3):
            np.testing.assert_array_equal(many[row], one[0])
    # a batch-2 prefill is no prompt to fan out
    with pytest.raises(AssertionError):
        tile_prefill(*prefill_codes(dalle, variables, text), 3)


def test_decode_codes_matches_a_loop_of_full_forward_passes(model):
    """Greedy ``decode_codes`` over the mixed carry against re-running the
    whole forward pass for every token (no cache, no state)."""
    cfg, dalle, variables, text, _ = model
    first, caches = prefill_codes(dalle, variables, text)
    got = np.asarray(jax.jit(lambda v, f, c: decode_codes(
        dalle, v, f, c, jax.random.PRNGKey(3), filter_thres=1.0))(
            variables, first, caches))
    split = cfg.total_text_tokens
    codes = jnp.zeros((2, 0), jnp.int32)
    for t in range(cfg.image_seq_len):
        padded = jnp.pad(codes, ((0, 0), (0, cfg.image_seq_len - t)))
        logits = dalle.apply(variables, text, padded)
        nxt = logits[:, cfg.text_seq_len + t, split:].argmax(-1)
        codes = jnp.concatenate([codes, nxt[:, None].astype(jnp.int32)], 1)
    np.testing.assert_array_equal(got, codes)


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
    """Admit, tick with an inactive slot, admit mid-flight at another depth,
    retire, re-admit into the freed slot: every request's codes are the
    static sampler's, and each entry point compiled once."""
    texts, refs, server = served
    srv = server(2)
    h0 = srv.submit(texts[0])
    for _ in range(5):                 # slot 1 idle: its state must not move
        srv.step()
    h1 = srv.submit(texts[1])          # joins mid-flight
    for _ in range(3):
        srv.step()
    h2, h3 = srv.submit(texts[2]), srv.submit(texts[3])   # wait for a slot
    srv.run_until_idle(max_ticks=400)
    for h, ref in zip((h0, h1, h2, h3), refs):
        np.testing.assert_array_equal(h.result(0), ref)
    assert srv.trace_counts() == {"prefill": 1, "admit": 1, "tick": 1}


def test_arena_state_has_slots_on_axis_zero_and_idle_slots_stand_still(model):
    from dalle_pytorch_tpu.serve.engine import SlotArena

    cfg, dalle, variables, text, _ = model
    arena = SlotArena(dalle, variables, 3, filter_thres=1.0)
    d_in = 2 * cfg.dim
    assert [tuple(a.shape for a in e) for e in arena.state["caches"]] == [
        ((3, 3, d_in), (3, 4, d_in)),
        ((3, 1, cfg.seq_len, 8), (3, 1, cfg.seq_len, 8)),
        ((3, 3, d_in), (3, 4, d_in))]
    first, caches = arena.prefill(text[:1])
    arena.admit(1, first, caches, jax.random.PRNGKey(0), 1.0, clock=0)
    np.testing.assert_array_equal(arena.state["caches"][0][1][1],
                                  caches[0][1][0])
    before = jax.tree.map(np.asarray, arena.state["caches"])
    arena.tick(np.array([False, True, False]), clock=0)
    after = arena.state["caches"]
    for kind, old, new in zip(cfg.mixers, before, after):
        if kind != "mamba":
            continue
        for o, n in zip(old, new):
            np.testing.assert_array_equal(n[0], o[0])      # idle slots
            np.testing.assert_array_equal(n[2], o[2])
            assert not np.array_equal(n[1], o[1])          # the active one


def test_prefix_cache_serves_whole_prompts_of_recurrent_state(served):
    """A payload that holds recurrent state is the state after the whole
    prompt: the cache's exact-match lookup is the only hit it can serve."""
    texts, refs, server = served
    srv = server(2, prefix_cache=True)
    handles = [srv.submit(texts[0]), srv.submit(texts[0]),
               srv.submit(texts[1])]
    srv.run_until_idle(max_ticks=400)
    for h, ref in zip(handles, (refs[0], refs[0], refs[1])):
        np.testing.assert_array_equal(h.result(0), ref)
    assert srv.prefill_count == 2
    assert srv.stats()["prefix"]["hits"] == 1


# --- sharding rules, the train step, the presets ---------------------------------------

def test_every_new_leaf_meets_a_sharding_rule(model):
    import re

    from dalle_pytorch_tpu.parallel.plan import PARTITION_RULES

    _, _, variables, _, _ = model
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            variables["params"])[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if leaf.ndim < 2 or "pos_emb" in name:
            continue
        spec = next(spec for pat, spec in PARTITION_RULES
                    if re.match(pat, name))
        assert len(spec) == leaf.ndim, (name, spec, leaf.shape)
        if name.endswith(("in_proj/kernel", "to_q/kernel", "gate/kernel",
                          "up/kernel", "table/embedding")):
            assert "tp" in spec and "fsdp" in spec, (name, spec)


def test_fsdp_tp_shardings_lower_for_the_trunk(model):
    from dalle_pytorch_tpu.parallel.plan import ParallelPlan

    _, _, variables, _, _ = model
    part = ParallelPlan("fsdp2.tp2", fsdp=2, tp=2).partitioner(
        devices=jax.devices()[:4])
    shardings = part.param_shardings(variables["params"])
    placed = jax.device_put(variables["params"], shardings)
    table = placed["table"]["embedding"]
    assert table.sharding.shard_shape(table.shape) == (
        table.shape[0] // 2, table.shape[1] // 2)


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
    assert np.isfinite(losses).all() and losses[-1] < 0.7 * losses[0], losses
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), params,
                         variables["params"])
    assert min(jax.tree.leaves(moved)) > 0, moved


def test_the_model_is_reachable_by_name():
    from dalle_pytorch_tpu import presets

    cfg = presets.preset_config("jamba2-3b")
    assert cfg.mixers.count("attention") == 2 and cfg.mixers[7] == cfg.mixers[
        21] == "attention" and len(cfg.mixers) == 28
    assert cfg.total_tokens == 65536 and cfg.trunk.ff_dim == 8192
    assert presets.check_param_band("jamba2-3b")
    tiny = presets.preset_config("jamba-tiny")
    assert set(tiny.mixers) == {"mamba", "attention"}
    import json
    bench = json.loads((REPO / "benchmark/configs/jamba2-3b.json").read_text())
    # the benchmark's file dates from PR 27: every field it names, and the
    # fields added since at the values that leave this trunk as it was
    trunk = cfg.to_dict()["trunk"]
    assert {k: trunk[k] for k in bench["dalle"]["trunk"]} == bench["dalle"][
        "trunk"]
    assert TrunkSpec(**bench["dalle"]["trunk"]) == cfg.trunk
    for key in ("dim", "depth", "heads", "dim_head", "text_seq_len",
                "num_text_tokens"):
        assert getattr(cfg, key) == bench["dalle"][key], key


# --- spans and counters ---------------------------------------------------------------

def test_state_space_scopes_are_siblings_of_the_attention_scopes(model):
    """``ssm-proj``, ``ssm-conv`` and ``ssm-scan`` are in the scope table, a
    decode step's equations sit under them, and none is nested in another or
    inside ``attn-scores``, ``attn-cache`` or ``ff``."""
    import re

    cfg, dalle, variables, text, codes = model
    assert {"ssm-proj", "ssm-conv", "ssm-scan"} <= set(prof.SCOPES)
    first, caches = prefill_codes(dalle, variables, text)
    jaxpr = jax.make_jaxpr(lambda v, c, s: dalle.apply(
        v, c, s, jnp.asarray(cfg.text_seq_len + 1),
        method=DALLE.decode_step))(variables, codes[:, 0], caches)
    stacks = {str(eqn.source_info.name_stack) for eqn in jaxpr.jaxpr.eqns}
    chains = {tuple(re.findall(r"graftprof:([a-z0-9_-]+)", s))
              for s in stacks}
    inner = {c[-1] for c in chains if c}
    assert {"ssm-proj", "ssm-conv", "ssm-scan", "attn-scores", "ff"} <= inner
    for chain in chains:
        for outer in chain[:-1]:
            assert not outer.startswith("ssm-"), chain
            if chain[-1].startswith("ssm-"):
                assert outer not in ("attn-scores", "attn-cache", "ff"), chain


def test_decode_trace_reports_its_state_layout(model, tmp_path):
    cfg, dalle, variables, text, _ = model
    reg = metrics.init()
    tel = telemetry.init(tmp_path, run_id="state-layout")
    try:
        first, caches = tile_prefill(*prefill_codes(dalle, variables,
                                                    text[:1]), 4)
        jax.jit(lambda v, f, c, k: decode_codes(dalle, v, f, c, k))(
            variables, first, caches, jax.random.PRNGKey(0))
        rendered = reg.render()
    finally:
        telemetry.shutdown()
        metrics.shutdown()
    events = telemetry.read_events(tel.path)
    layout = [e for e in events
              if e["kind"] == "decode" and e["name"] == "state_layout"]
    d_in = 2 * cfg.dim
    row_bytes = (2 * (3 * d_in * 4 + 4 * d_in * 4)
                 + 2 * cfg.seq_len * 8 * 4)
    assert len(layout) == 1
    assert (layout[0]["ssm_layers"], layout[0]["kv_layers"],
            layout[0]["state_bytes_per_row"], layout[0]["rows"]) == (
                2, 1, row_bytes, 4)
    for line in ("graft_decode_ssm_layers 2", "graft_decode_kv_layers 1",
                 f"graft_decode_state_bytes_per_row {row_bytes}",
                 "graft_decode_kv_plain_layers 1"):
        assert line in rendered, line
    text_report = render_text(build_report(events))
    assert "-- decode --" in text_report
    assert (f"decode state: 1 layers of keys and values, 2 recurrent; "
            f"{row_bytes} bytes a row") in text_report
