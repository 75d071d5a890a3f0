"""DALL-E over a linear-attention hybrid trunk (PERF.md, Findings PR 34):
gated-delta-rule layers among full-attention layers, the norm on each
sublayer's output, normed queries and keys, an untied head under the client's
learned position embeddings.

Tiny widths, seeded weights, float32 parameters unless a test says bfloat16,
on the CPU.  The program is held to ``benchmark/reference_olmo_hybrid_7b.py``
(which imports nothing from it): forward logits, prefill + ``decode_step``
through the state, the loss and its gradients; then the carry through
``tile_prefill``, ``decode_codes`` and the ``SlotArena``, the refusing
asserts, the parameter dtypes, the sharding rules, the train step, the other
trunks' parameter trees and the trace-time counters.
"""
from __future__ import annotations

import hashlib
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from benchmark import reference_olmo_hybrid_7b as reference  # noqa: E402
from dalle_pytorch_tpu import DALLE, DALLEConfig, presets  # noqa: E402
from dalle_pytorch_tpu.models.dalle import (  # noqa: E402
    decode_codes, prefill_codes, tile_prefill)
from dalle_pytorch_tpu.obs import metrics, prof, telemetry  # noqa: E402
from dalle_pytorch_tpu.obs.report import build_report, render_text  # noqa: E402
from dalle_pytorch_tpu.ops.transformer import TrunkSpec  # noqa: E402

TRUNK = dict(mixers=["gdn", "gdn", "gdn", "attention"], ff_dim=96, kv_heads=4,
             norm_at="output", qk_norm=True, lin_key_dim=8, lin_value_dim=16,
             tied_table=False, param_dtype="float32")
GEOMETRY = dict(dim=64, depth=4, heads=4, dim_head=16, num_text_tokens=50,
                text_seq_len=8, num_image_tokens=32, image_size=32,
                image_fmap_size=4)

#: Largest |program - reference| in units of the reference logits' standard
#: deviation, for float32 parameters and state on the CPU: both sides are
#: float32 and differ in the order of sums only (the chunked rule against the
#: sequential one), which measures 8e-5.  1e-3 is ten times that, and fails a
#: bfloat16 state (8e-2) and 8-bit matrix products (2.5) alike.
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
    # move every leaf off its initial value (gains 1), so that each one
    # matters to the comparison
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


def _leaves(params):
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}


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
    split = cfg.total_text_tokens
    np.testing.assert_allclose(
        want, reference.joint_logits(variables["params"], cfg, text, codes)[
            :, cfg.text_seq_len:, split:], rtol=1e-6)


@pytest.mark.parametrize("departure,least", [
    (dict(state_dtype=jnp.bfloat16), 1e-2),          # a bfloat16 state
    (dict(matmul_dtype=jnp.float8_e4m3fn), 1.0),     # 8-bit matrix products
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
    # both sides, so only the order of sums differs
    worst = jax.tree.map(lambda g, w: float(
        jnp.abs(g - w).max() / (jnp.abs(w).max() + 1e-12)), grads, want)
    assert max(jax.tree.leaves(worst)) <= 1e-3, worst
    assert min(float(jnp.abs(g).max()) for g in jax.tree.leaves(grads)) > 0


def test_bfloat16_program_stays_near_the_reference():
    """Parameters stored in bfloat16 and bfloat16 activations, as the
    benchmark runs the model, the reference reading the same bfloat16 tree:
    under a standard deviation at this toy width (0.3-0.8 measured: 64
    channels average little rounding away), where a dropped norm reads
    whole ones."""
    cfg, dalle, variables, text, codes = _model(
        trunk={**TRUNK, "param_dtype": "bfloat16"}, dtype=jnp.bfloat16,
        kv_cache_bf16=True)
    got = _teacher_forced(dalle, variables, text, codes)
    want = reference.image_logits(variables["params"], cfg, text, codes)
    assert _err_std(got.astype(jnp.float32), want) <= 1.5
    _, caches = prefill_codes(dalle, variables, text)
    assert caches[0][0].dtype == jnp.bfloat16       # the window
    assert caches[0][1].dtype == jnp.float32        # the state
    assert caches[3][0].dtype == jnp.bfloat16       # keys


# --- the configuration field --------------------------------------------------------

def test_trunk_arrives_as_a_dict_and_the_config_stays_hashable():
    cfg = DALLEConfig(**GEOMETRY, trunk=dict(TRUNK))
    assert isinstance(cfg.trunk, TrunkSpec)
    assert cfg.mixers == ("gdn", "gdn", "gdn", "attention")
    assert cfg.cache_lens == (0, 0, 0, cfg.seq_len)
    assert not cfg.rotary and cfg.kv_heads == 4
    assert hash(cfg) == hash(DALLEConfig(**GEOMETRY,
                                         trunk=TrunkSpec(**TRUNK)))
    saved = cfg.to_dict()
    assert saved["trunk"]["norm_at"] == "output" and saved["trunk"]["qk_norm"]
    assert DALLEConfig.from_dict(json.loads(json.dumps(saved))) == cfg


@pytest.mark.parametrize("field,value", [
    ("reversible", True), ("weights_int8", True),
    ("kv_cache_int8", True)])
def test_paths_without_a_form_for_a_matrix_state_refuse(field, value):
    with pytest.raises(AssertionError, match="linear-attention state"):
        DALLEConfig(**GEOMETRY, trunk=dict(TRUNK), **{field: value})


@pytest.mark.parametrize("bad", [
    dict(lin_key_dim=0), dict(lin_value_dim=0),
    dict(mixers=["attention"]),                    # sizes without the layers
    dict(norm_at="middle"),
    dict(mixers=["gdn", "mamba"]),                 # no output norm there
    dict(ff="moe_reglu", experts=4, experts_per_token=2, expert_dim=8)])
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
    f32 = ("A_log", "dt_bias", "norm", "pos_emb")
    for name, leaf in _leaves(params).items():
        want = jnp.float32 if any(tag in name for tag in f32) else jnp.bfloat16
        assert leaf.dtype == want, (name, leaf.dtype)
    assert set(params) == {"table", "head", "text_pos_emb", "image_pos_emb",
                           "transformer", "final_norm"}
    assert params["head"].shape == params["table"]["embedding"].shape == (
        cfg.total_tokens, cfg.dim)


# --- the carry: tile_prefill, decode_codes, the arena ---------------------------------

def test_prefill_carries_two_kinds_of_state_and_tiles_them(model):
    cfg, dalle, variables, text, _ = model
    first, caches = prefill_codes(dalle, variables, text[:1])
    channels = 4 * (8 + 8 + 16)
    shapes = [tuple(a.shape for a in entry) for entry in caches]
    assert shapes == [((1, 3, channels), (1, 4, 8, 16))] * 3 + [
        ((1, 4, cfg.seq_len, 16), (1, 4, cfg.seq_len, 16))]
    assert caches[0][1].dtype == jnp.float32
    assert float(jnp.abs(caches[0][1]).max()) > 1e-3
    tiled_first, tiled = tile_prefill(first, caches, 3)
    assert tiled_first.shape[0] == 3
    for one, many in zip(jax.tree.leaves(caches), jax.tree.leaves(tiled)):
        assert many.shape == (3,) + one.shape[1:]
        for row in range(3):
            np.testing.assert_array_equal(many[row], one[0])


def test_decode_codes_matches_stepwise_decode(model):
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


def test_an_arena_tick_is_the_static_scan_at_equal_rows(model):
    """Three slots admitted at one clock and ticked together against the
    static sampler over the same three prompts in one batch: the same codes,
    and the same linear-attention states, row for row."""
    from dalle_pytorch_tpu.serve.engine import SlotArena

    cfg, dalle, variables, text, _ = model
    texts = jnp.concatenate([text, text[:1, ::-1]])
    arena = SlotArena(dalle, variables, 3, filter_thres=1.0)
    for slot in range(3):
        first, caches = arena.prefill(texts[slot:slot + 1])
        arena.admit(slot, first, caches, jax.random.PRNGKey(0), 1.0, clock=0)
    for clock in range(cfg.image_seq_len - 1):
        arena.tick(np.ones(3, bool), clock=clock)
    first, caches = prefill_codes(dalle, variables, texts)
    want = decode_codes(dalle, variables, first, caches,
                        jax.random.PRNGKey(1), filter_thres=1.0)
    np.testing.assert_array_equal(arena.state["out"], want)
    # the state the static path would hold after the same inputs
    for t in range(cfg.image_seq_len - 1):
        _, caches = dalle.apply(variables, want[:, t], caches,
                                jnp.asarray(cfg.text_seq_len + 1 + t),
                                method=DALLE.decode_step)
    for layer in range(3):
        np.testing.assert_allclose(arena.state["caches"][layer][1],
                                   caches[layer][1], rtol=1e-3, atol=1e-5)


def test_arena_state_has_slots_on_axis_zero_and_idle_slots_stand_still(model):
    from dalle_pytorch_tpu.serve.engine import SlotArena

    cfg, dalle, variables, text, _ = model
    arena = SlotArena(dalle, variables, 3, filter_thres=1.0)
    channels = 4 * (8 + 8 + 16)
    assert [tuple(a.shape for a in e) for e in arena.state["caches"]] == [
        ((3, 3, channels), (3, 4, 8, 16))] * 3 + [
        ((3, 4, cfg.seq_len, 16), (3, 4, cfg.seq_len, 16))]
    first, caches = arena.prefill(text[:1])
    arena.admit(1, first, caches, jax.random.PRNGKey(0), 1.0, clock=0)
    np.testing.assert_array_equal(arena.state["caches"][0][1][1],
                                  caches[0][1][0])
    before = jax.tree.map(np.asarray, arena.state["caches"])
    arena.tick(np.array([False, True, False]), clock=0)
    for old, new in zip(before[:3], arena.state["caches"][:3]):
        for o, n in zip(old, new):
            np.testing.assert_array_equal(n[0], o[0])      # idle slots
            np.testing.assert_array_equal(n[2], o[2])
            assert not np.array_equal(n[1], o[1])          # the active one


# --- sharding rules, the train step, the presets ---------------------------------------

def test_every_new_leaf_meets_a_sharding_rule(model):
    from dalle_pytorch_tpu.lint import plans
    from dalle_pytorch_tpu.parallel.plan import PARTITION_RULES

    _, _, variables, _, _ = model
    leaves = _leaves(variables["params"])
    for name, leaf in leaves.items():
        if leaf.ndim < 2 or "pos_emb" in name:
            continue
        spec = next(spec for pat, spec in PARTITION_RULES
                    if re.match(pat, name))
        assert len(spec) == leaf.ndim, (name, spec, leaf.shape)
        if "/gdn/" in name:
            assert "tp" in spec, (name, spec)
            # heads over tp: the axis of 4 heads here
            assert leaf.shape[list(spec).index("tp")] == 4, (name, spec)
    for name in ("A_log", "dt_bias"):
        assert any(re.match(pat, f"transformer/layers_0_gdn/gdn/{name}")
                   for pat, _ in PARTITION_RULES)
    # graftplan's P1 over the tree: nothing this trunk adds is uncovered or
    # matched twice (to_q's two rules predate it: jamba-tiny reads the same)
    shapes = {name: (leaf.shape, leaf.dtype.itemsize)
              for name, leaf in leaves.items()}
    found = [f.message for f in plans.check_rule_coverage(shapes,
                                                          preset="olmo")]
    assert all("attn/to_q/kernel" in m for m in found), found


@pytest.mark.parametrize("plan", ["dp", "fsdp", "tp", "fsdp2.tp2"])
def test_registered_plans_place_every_leaf(model, plan):
    from dalle_pytorch_tpu.parallel.plan import ParallelPlan

    _, _, variables, _, _ = model
    part = ParallelPlan.parse(plan).partitioner(devices=jax.devices()[:4])
    shardings = part.param_shardings(variables["params"])
    placed = jax.device_put(variables["params"], shardings)
    q_proj = placed["transformer"]["layers_0_gdn"]["gdn"]["q_proj"]["kernel"]
    ways = dict(part.mesh.shape)
    assert q_proj.sharding.shard_shape(q_proj.shape) == (
        64 // ways.get("fsdp", 1), 4 // ways.get("tp", 1), 8)
    for leaf in jax.tree.leaves(placed):
        assert len(leaf.sharding.device_set) == 4


def test_train_step_trains_the_trunk(model):
    from dalle_pytorch_tpu.training import (make_dalle_train_step,
                                            make_optimizer)

    _, dalle, variables, text, codes = model
    tx = make_optimizer(3e-3)
    params = jax.tree.map(jnp.copy, variables["params"])
    opt_state = tx.init(params)
    step = make_dalle_train_step(dalle, tx, donate=False)
    losses = []
    for i in range(8):
        params, opt_state, loss = step(params, opt_state, None, text, codes,
                                       jax.random.PRNGKey(i))
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < 0.8 * losses[0], losses
    # a gradient reached every leaf: each one moved
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), params,
                         variables["params"])
    assert min(jax.tree.leaves(moved)) > 0, moved


def _tree_digest(cfg):
    text = jnp.zeros((1, cfg.text_seq_len), jnp.int32)
    codes = jnp.zeros((1, cfg.image_seq_len), jnp.int32)
    params = jax.eval_shape(DALLE(cfg).init, jax.random.PRNGKey(0), text,
                            codes)["params"]
    listing = sorted((name, tuple(leaf.shape), str(leaf.dtype))
                     for name, leaf in _leaves(params).items())
    return len(listing), hashlib.sha256(repr(listing).encode()).hexdigest()[
        :16]


@pytest.mark.parametrize("preset,leaves,digest", [
    ("jamba-tiny", 47, "f903fb9b2cff93b6"),
    ("smallthinker-tiny", 39, "24a7bfeb03bbbeca")])
def test_the_other_trunks_parameter_trees_are_as_they_were(preset, leaves,
                                                           digest):
    """Names, shapes and dtypes of every leaf, as commit dbc620d (before the
    linear-attention mixer, the output norm and the q/k norm) built them."""
    assert _tree_digest(presets.preset_config(preset)) == (leaves, digest)


def test_the_model_is_reachable_by_name():
    cfg = presets.preset_config("olmo-hybrid-7b")
    assert cfg.mixers == ("gdn", "gdn", "gdn", "attention") * 2
    assert cfg.total_tokens == 100352 and cfg.trunk.ff_dim == 11008
    assert presets.check_param_band("olmo-hybrid-7b")
    tiny = presets.preset_config("olmo-hybrid-tiny")
    assert set(tiny.mixers) == {"gdn", "attention"}
    bench = json.loads(
        (REPO / "benchmark/configs/olmo-hybrid-7b.json").read_text())
    assert TrunkSpec(**bench["dalle"]["trunk"]) == cfg.trunk
    for key in ("dim", "depth", "heads", "dim_head", "text_seq_len",
                "num_text_tokens"):
        assert getattr(cfg, key) == bench["dalle"][key], key


# --- spans and counters ---------------------------------------------------------------

def test_linear_attention_scopes_are_siblings_of_the_attention_scopes(model):
    """``gdn-proj``, ``gdn-conv`` and ``gdn-state`` are in the scope table, a
    decode step's equations sit under them, none is nested in another or
    inside ``attn-scores``, ``attn-cache`` or ``ff``, and the output norms
    sit under the scope of the sublayer they close."""
    cfg, dalle, variables, text, codes = model
    assert {"gdn-proj", "gdn-conv", "gdn-state"} <= set(prof.SCOPES)
    first, caches = prefill_codes(dalle, variables, text)
    jaxpr = jax.make_jaxpr(lambda v, c, s: dalle.apply(
        v, c, s, jnp.asarray(cfg.text_seq_len + 1),
        method=DALLE.decode_step))(variables, codes[:, 0], caches)
    stacks = {str(eqn.source_info.name_stack) for eqn in jaxpr.jaxpr.eqns}
    chains = {tuple(re.findall(r"graftprof:([a-z0-9_-]+)", s))
              for s in stacks}
    inner = {c[-1] for c in chains if c}
    assert {"gdn-proj", "gdn-conv", "gdn-state", "attn-scores", "attn-out",
            "ff"} <= inner
    for chain in chains:
        for outer in chain[:-1]:
            assert not outer.startswith("gdn-"), chain
            if chain[-1].startswith("gdn-"):
                assert outer not in ("attn-scores", "attn-cache", "ff"), chain
    # every rsqrt (a norm) of the step sits under a sublayer's scope
    norms = {tuple(re.findall(r"graftprof:([a-z0-9_-]+)",
                              str(eqn.source_info.name_stack)))[-1]
             for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "rsqrt"}
    assert norms == {"gdn-state", "gdn-proj", "attn-qkv", "attn-out", "ff",
                     "logits-head"}


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
    channels = 4 * (8 + 8 + 16)
    row_bytes = (3 * (3 * channels * 4 + 4 * 8 * 16 * 4)
                 + 2 * cfg.seq_len * 4 * 16 * 4)
    assert len(layout) == 1
    assert (layout[0]["ssm_layers"], layout[0]["linear_layers"],
            layout[0]["kv_layers"], layout[0]["linear_state_shape"],
            layout[0]["state_bytes_per_row"], layout[0]["rows"],
            layout[0]["linear_one_pass_layers"]) == (
                0, 3, 1, [4, 8, 16], row_bytes, 4, 0)
    for line in ("graft_decode_linear_layers 3", "graft_decode_ssm_layers 0",
                 "graft_decode_kv_layers 1",
                 f"graft_decode_state_bytes_per_row {row_bytes}"):
        assert line in rendered, line
    text_report = render_text(build_report(events))
    assert "-- decode --" in text_report
    assert (f"decode state: 1 layers of keys and values, 3 recurrent; "
            f"{row_bytes} bytes a row") in text_report
    assert ("linear attention: 3 of the recurrent layers, a float32 state "
            "of [4, 8, 16] a row\n") in text_report


@pytest.mark.parametrize("trunk,want", [
    # two heads of 64 fill a lane tile: the kernel's state, in all three
    (dict(TRUNK, lin_value_dim=64), 3),
    (None, None),                       # no linear layer, no count
], ids=["foldable", "no-linear-layer"])
def test_the_state_layout_counts_the_states_updated_in_one_pass(tmp_path,
                                                                trunk, want):
    """The ``decode.state_layout`` record of a trace of ``decode_codes``
    says how many linear layers' states the tick updates in one pass
    (ops/linear_attention.py::one_pass_step), with its gauge and the
    report's line; the tiny twin's 16-lane state reads 0
    (test_decode_trace_reports_its_state_layout)."""
    cfg, dalle, variables, text, _ = _model(trunk=trunk)
    reg = metrics.init()
    tel = telemetry.init(tmp_path, run_id="one-pass")
    try:
        first, caches = tile_prefill(*prefill_codes(dalle, variables,
                                                    text[:1]), 2)
        jax.jit(lambda v, f, c, k: decode_codes(dalle, v, f, c, k)).lower(
            variables, first, caches, jax.random.PRNGKey(0))
        rendered = reg.render()
    finally:
        telemetry.shutdown()
        metrics.shutdown()
    events = telemetry.read_events(tel.path)
    layout = [e for e in events
              if e["kind"] == "decode" and e["name"] == "state_layout"]
    assert len(layout) == 1
    assert layout[0].get("linear_one_pass_layers") == want
    report = render_text(build_report(events))
    if want:
        assert f"graft_decode_linear_one_pass_layers {want}" in rendered
        assert ("linear attention: 3 of the recurrent layers, a float32 "
                "state of [2, 8, 128] a row; state updated in one pass"
                ) in report
    else:
        assert "graft_decode_linear_one_pass_layers" not in rendered
        assert "linear attention" not in report
