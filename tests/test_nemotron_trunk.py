"""DALL-E over the Nemotron-H trunk (PERF.md, section 6): layers of ONE
sublayer each, a Mamba-2 mixer, routed relu^2 experts beside a shared one,
or grouped attention, as ``hybrid_override_pattern`` says.

Tiny widths, seeded weights, float32 parameters, on the CPU.  The program is
held to ``benchmark/reference_nemotron_3_nano_30b_a3b.py`` (which imports
nothing from it): the chunked state-space form against the per-position
recurrence, forward logits, prefill + ``decode_step`` through the state,
the ``SlotArena`` against ``decode_codes``, the expert shares against the
uncut layer, the older shared-expert trunks unchanged, and controls that the
comparison must refuse.
"""
from __future__ import annotations

import dataclasses
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

from benchmark import reference_nemotron_3_nano_30b_a3b as reference  # noqa: E402
from dalle_pytorch_tpu import DALLE, DALLEConfig, presets  # noqa: E402
from dalle_pytorch_tpu.models.dalle import (  # noqa: E402
    decode_codes, prefill_codes, tile_prefill)
from dalle_pytorch_tpu.obs import prof, telemetry  # noqa: E402
from dalle_pytorch_tpu.ops.ssm import ssd, ssd_step  # noqa: E402
from dalle_pytorch_tpu.ops.transformer import (  # noqa: E402
    TrunkSpec, is_recurrent, is_stateless, layer_cache_lens)

#: Largest |program - reference| in units of the reference logits' standard
#: deviation, for float32 parameters and state on the CPU: both sides are
#: float32 and differ in the order of sums only (the chunked form against
#: the per-position recurrence, the bank products against a loop over
#: experts), which measures 3e-6.  1e-4 is thirty times that; a bfloat16
#: state reads 4e-3 and every planted fault 0.3 or more.
LOGIT_TOL = 1e-4

#: Largest |program state - reference state| over the largest reference
#: state element, after the last position: 1.5e-7 read on the CPU (float32
#: both sides); a bfloat16 state reads 2e-3.
STATE_TOL = 1e-5


def _model(name="nemotron-tiny", **overrides):
    cfg = presets.preset_config(name, kv_cache_bf16=False, **overrides)
    dalle = DALLE(cfg)
    rng = np.random.default_rng(0)
    text = jnp.asarray(rng.integers(1, 50, (2, cfg.text_seq_len)),
                       jnp.int32).at[:, 5:].set(0)
    codes = jnp.asarray(rng.integers(0, cfg.num_image_tokens,
                                     (2, cfg.image_seq_len)), jnp.int32)
    variables = jax.jit(dalle.init)(jax.random.PRNGKey(0), text, codes)
    return cfg, dalle, variables, text, codes


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def program_out(model):
    cfg, dalle, variables, text, codes = model
    return _teacher_forced(dalle, variables, text, codes)


@pytest.fixture(scope="module")
def reference_out(model):
    cfg, _, variables, text, codes = model
    return reference.image_logits(variables["params"], cfg, text, codes)


def _err_std(got, ref):
    return float((jnp.abs(got - ref) / ref.std(-1, keepdims=True)).max())


def _teacher_forced(dalle, variables, text, codes):
    """Image logits through ``DALLE.prefill`` and ``DALLE.decode_step``, and
    the decode state after the last position."""
    cfg = dalle.cfg

    @jax.jit
    def run(variables, text, codes):
        first, caches = dalle.apply(variables, text, method=DALLE.prefill)

        def step(carry, code):
            caches, index = carry
            logits, caches = dalle.apply(variables, code, caches, index,
                                         method=DALLE.decode_step)
            return (caches, index + 1), logits

        (caches, _), rest = jax.lax.scan(
            step, (caches, jnp.asarray(cfg.text_seq_len + 1)),
            codes[:, :-1].T)
        return jnp.concatenate([first[:, None], rest.swapaxes(0, 1)],
                               1), caches

    return run(variables, text, codes)


def _ssd_states(cfg, caches):
    return jnp.stack([caches[i][1] for i, kind in enumerate(cfg.mixers)
                      if kind == "mamba2"])


# --- the state-space operator ------------------------------------------------------

def _rule_inputs(n, seed, b=2, H=4, P=8, G=2, N=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(keys[0], (b, n, H, P))
    B = jax.random.normal(keys[1], (b, n, G, N))
    C = jax.random.normal(keys[2], (b, n, G, N))
    delta = jax.nn.softplus(jax.random.normal(keys[3], (b, n, H)) - 1.0)
    A = -jnp.exp(jax.random.uniform(keys[4], (H,), minval=0.0,
                                    maxval=2.7))
    return x, B, C, delta, A


@pytest.mark.parametrize("n,chunk", [(21, 8), (5, 8), (16, 8), (37, 16)],
                         ids=["n21-chunk8", "n5-below-a-chunk", "n16-whole",
                              "n37-chunk16"])
def test_the_chunked_form_is_the_per_position_recurrence(n, chunk):
    """``ssd`` in chunks of ``chunk`` (padded with delta 0 where the chunk
    does not divide ``n``, or one short chunk) against the reference's
    sequential rule: the read-out at every position and the state after the
    last, to float32 rounding."""
    x, B, C, delta, A = _rule_inputs(n, seed=n)
    y, h = ssd(x, delta, A, B, C, chunk=chunk)
    y_ref, h_ref = reference.rule(x, B, C, delta, A)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h, h_ref, rtol=1e-5, atol=1e-5)


def test_the_chunked_form_continues_a_state_and_the_step_continues_it():
    """A sequence split in two: the chunked form from the first half's state
    gives the whole sequence's state; ``ssd_step`` one position at a time
    from it gives the same as the chunked form over those positions."""
    x, B, C, delta, A = _rule_inputs(19, seed=3)
    _, h_whole = reference.rule(x, B, C, delta, A)
    _, h9 = ssd(x[:, :9], delta[:, :9], A, B[:, :9], C[:, :9], chunk=4)
    _, h = ssd(x[:, 9:], delta[:, 9:], A, B[:, 9:], C[:, 9:], h0=h9,
               chunk=4)
    np.testing.assert_allclose(h, h_whole, rtol=1e-5, atol=1e-5)
    h, ys = h9, []
    for t in range(9, 19):
        y, h = ssd_step(h, x[:, t], delta[:, t], A, B[:, t], C[:, t])
        ys.append(y)
    y_ref, _ = reference.rule(x, B, C, delta, A)
    np.testing.assert_allclose(jnp.stack(ys, 1), y_ref[:, 9:], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(h, h_whole, rtol=1e-5, atol=1e-5)


def test_a_head_reads_the_group_of_its_block():
    """Head ``h`` reads ``B`` and ``C`` of group ``h // (H / G)``: with
    every group but one zeroed, only that group's heads read anything."""
    x, B, C, delta, A = _rule_inputs(6, seed=5, H=4, G=2)
    B, C = B.at[:, :, 0].set(0.0), C.at[:, :, 0].set(0.0)
    y, _ = ssd(x, delta, A, B, C, chunk=4)
    assert float(jnp.abs(y[:, :, :2]).max()) == 0.0
    assert float(jnp.abs(y[:, :, 2:]).max()) > 0.0


# --- the model against the reference ----------------------------------------------

def test_forward_logits_match_the_reference(model, reference_out):
    cfg, dalle, variables, text, codes = model
    want, _ = reference_out
    got = jax.jit(dalle.apply)(variables, text, codes)[
        :, cfg.text_seq_len:, cfg.total_text_tokens:]
    assert _err_std(got, want) < LOGIT_TOL
    loss = jax.jit(lambda v, t, c: dalle.apply(v, t, c, return_loss=True))(
        variables, text, codes)
    want_loss = reference.train_loss(variables["params"], cfg, text, codes)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)


def test_prefill_and_decode_step_match_the_reference_forward(
        model, program_out, reference_out):
    """The prompt through the chunked form (9 positions: one whole chunk of
    8 and one more), then one step a code against the carried state: logits
    at every image position and each Mamba-2 layer's state after the last
    position."""
    cfg = model[0]
    want, extras = reference_out
    got, caches = program_out
    assert _err_std(got, want) < LOGIT_TOL
    states = _ssd_states(cfg, caches)
    err = float(jnp.abs(states - extras["states"]).max()
                / jnp.abs(extras["states"]).max())
    assert err < STATE_TOL, err


CONTROLS = reference.FAULTS + ("bf16_state",)


@pytest.mark.parametrize("control", CONTROLS)
def test_the_comparison_refuses_each_control(model, program_out, control):
    """Each departure planted in the reference (a bfloat16 state; head ``h``
    on group ``h % G``; the gated norm over all channels; relu for relu^2;
    no routed scaling; the selection bias in the weights; no shared expert;
    no convolution bias; the banks taken for other experts) fails the logit
    tolerance, or the bfloat16 state the state's."""
    cfg, dalle, variables, text, codes = model
    got, caches = program_out
    kw = ({"state_dtype": jnp.bfloat16} if control == "bf16_state"
          else {"fault": control})
    planted, extras = reference.image_logits(variables["params"], cfg, text,
                                             codes, **kw)
    logit_fails = _err_std(got, planted) > LOGIT_TOL
    states = _ssd_states(cfg, caches)
    state_fails = float(jnp.abs(states - extras["states"]).max()
                        / jnp.abs(extras["states"]).max()) > STATE_TOL
    assert logit_fails if control != "bf16_state" else state_fails


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """Four devices each holding 2 of 8 experts (the shared expert, router
    and norm alike on all): the expert layer's outputs, with the shared
    expert's part counted once, add up to the reference's uncut layer; the
    program's own shares add up alike."""
    from dalle_pytorch_tpu.ops.moe import ExpertsSwiGLUShared

    dim, experts, k, f, fs = 16, 8, 3, 12, 20
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, dim))

    def layer(held, first):
        return ExpertsSwiGLUShared(dim=dim, experts=experts, k=k,
                                   expert_dim=f, held=held, first=first,
                                   shared_dim=fs, act="relu2", scale=2.5)

    whole = layer(experts, 0)
    params = whole.init(jax.random.PRNGKey(2), x)["params"]
    want = whole.apply({"params": params}, x)
    no_banks = dict(params, w_up=params["w_up"][:0],
                    w_down=params["w_down"][:0])
    shared = layer(0, 0).apply({"params": no_banks}, x)
    parts = []
    for first in range(0, experts, 2):
        share = dict(params, w_up=params["w_up"][first:first + 2],
                     w_down=params["w_down"][first:first + 2])
        parts.append(layer(2, first).apply({"params": share}, x) - shared)
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=1e-5,
                               atol=1e-5)
    # the reference's share of the same layer, the uncut one besides
    p = {"norm": {"scale": jnp.ones((dim,))}, "moe": params}

    def ref(first, held):
        moe = dict(params, w_up=params["w_up"][first:first + held],
                   w_down=params["w_down"][first:first + held])
        y, *_ = reference._experts(
            {"norm": p["norm"], "moe": moe}, x, eps=1e-5, k=k, scale=2.5,
            first=first, routing=None, low=None, fault=None)
        return y
    ref_shared = ref(0, 0)
    ref_parts = [ref(first, 2) - ref_shared for first in range(0, experts, 2)]
    np.testing.assert_allclose(sum(ref_parts) + ref_shared, ref(0, experts),
                               rtol=1e-5, atol=1e-5)


# --- the decode paths -------------------------------------------------------------

def test_a_layer_without_a_mixer_holds_no_state(model):
    """``MEMEM*EME``: the expert layers hold nothing (None: no leaf), the
    Mamba-2 layers ``(window, h)`` with the state ``[rows, H, P, N]``
    float32, the attention layer its keys and values; ``tile_prefill``
    tiles what there is."""
    cfg, dalle, variables, text, _ = model
    assert cfg.mixers == ("mamba2", "none", "mamba2", "none", "mamba2",
                          "attention", "none", "mamba2", "none")
    assert layer_cache_lens(cfg.trunk, cfg.depth, cfg.seq_len) == (
        0, 0, 0, 0, 0, cfg.seq_len, 0, 0, 0)
    first, caches = jax.jit(lambda v, t: tile_prefill(
        *prefill_codes(dalle, v, t), 3))(variables, text[:1])
    for kind, cache in zip(cfg.mixers, caches):
        if is_stateless(kind):
            assert cache is None
        elif kind == "mamba2":
            window, h = cache
            assert window.shape == (3, 3, 4 * 8 + 2 * 2 * 8)
            assert h.shape == (3, 4, 8, 8) and h.dtype == jnp.float32
        else:
            assert cache[0].shape == (3, 2, cfg.seq_len, cfg.dim_head)
    assert len(jax.tree.leaves(caches)) == 2 * 5
    assert dalle.apply(variables, cfg.dim and jnp.float32,
                       method=DALLE.arena_forms)[1] is None


def test_an_arena_tick_is_the_static_scan_bit_for_bit(model):
    """Three slots admitted at one clock and ticked together against the
    static sampler over the same three prompts in one batch: the same
    codes, bit for bit, and the same Mamba-2 states and windows (to float32
    rounding: the stepwise calls that rebuild them compile apart from the
    tick); an idle slot's state stands still, bit for bit."""
    from dalle_pytorch_tpu.serve.engine import SlotArena

    cfg, dalle, variables, text, _ = model
    texts = jnp.concatenate([text, text[:1, ::-1]])
    arena = SlotArena(dalle, variables, 4, filter_thres=1.0)
    assert arena.state["caches"][1] is None
    for slot in range(3):
        first, caches = arena.prefill(texts[slot:slot + 1])
        arena.admit(slot, first, caches, jax.random.PRNGKey(0), 1.0, clock=0)
    idle = jax.tree.map(lambda a: np.asarray(a[3]), arena.state["caches"][0])
    for clock in range(cfg.image_seq_len - 1):
        arena.tick(np.array([True, True, True, False]), clock=clock)
    first, caches = prefill_codes(dalle, variables, texts)
    want = decode_codes(dalle, variables, first, caches,
                        jax.random.PRNGKey(1), filter_thres=1.0)
    np.testing.assert_array_equal(arena.state["out"][:3], want)
    step = jax.jit(lambda c, caches, i: dalle.apply(
        variables, c, caches, i, method=DALLE.decode_step)[1])
    for t in range(cfg.image_seq_len - 1):
        caches = step(want[:, t], caches,
                      jnp.asarray(cfg.text_seq_len + 1 + t))
    for i, kind in enumerate(cfg.mixers):
        if kind == "mamba2":
            for got, ref in zip(arena.state["caches"][i], caches[i]):
                np.testing.assert_allclose(got[:3], ref, rtol=1e-5,
                                           atol=1e-6)
    for got, before in zip(arena.state["caches"][0], idle):
        np.testing.assert_array_equal(got[3], before)
    layout = arena.layout()
    assert (layout["recurrent_layers"], layout["stateless_layers"]) == (4, 4)
    assert arena.trace_counts() == {"prefill": 1, "admit": 1, "tick": 1}


def test_the_static_scan_carries_the_mamba2_state_in_float32(model):
    """``decode_codes`` over a ``tile_prefill`` broadcast hands back its
    scan's final carry: each Mamba-2 state float32 ``[rows, H, P, N]``, the
    state a teacher-forced chain of ``DALLE.decode_step`` over the codes it
    drew leaves, to float32 rounding (the two compile apart): within
    ``STATE_TOL`` of the largest element, where a state rounded to bfloat16
    once reads 2e-3."""
    cfg, dalle, variables, text, _ = model
    spec = cfg.trunk
    first, caches = tile_prefill(*prefill_codes(dalle, variables, text[:1]),
                                 3)
    codes, carried = jax.jit(lambda v, f, c, k: decode_codes(
        dalle, v, f, c, k, filter_thres=0.5, return_caches=True))(
            variables, first, caches, jax.random.PRNGKey(4))

    @jax.jit
    def chain(caches, codes):
        def step(carry, code):
            caches, index = carry
            _, caches = dalle.apply(variables, code, caches, index,
                                    method=DALLE.decode_step)
            return (caches, index + 1), None

        return jax.lax.scan(step, (caches, jnp.asarray(cfg.text_seq_len + 1)),
                            codes[:, :-1].T)[0][0]

    want = _ssd_states(cfg, chain(caches, codes))
    got = _ssd_states(cfg, carried)
    assert got.dtype == jnp.float32
    assert got.shape == (4, 3, spec.ssd_heads, spec.ssd_head_dim,
                         spec.ssm_state)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) / scale < STATE_TOL
    narrowed = want.astype(jnp.bfloat16).astype(jnp.float32)
    assert float(jnp.abs(narrowed - want).max()) / scale > STATE_TOL


# --- the spec, the parameters, the plan --------------------------------------------

def test_spec_names_each_layers_sublayers():
    spec = presets.preset_config("nemotron-tiny").trunk
    assert [spec.ff_kind(i) for i in range(9)] == [
        None, "moe_swiglu_shared", None, "moe_swiglu_shared", None, None,
        "moe_swiglu_shared", None, "moe_swiglu_shared"]
    assert spec.routed_layers(9) == 4 and spec.expert_matrices == 2
    assert is_recurrent("mamba2") and not is_recurrent("none")
    assert is_stateless("none") and not is_stateless("mamba2")
    older = TrunkSpec(mixers=("attention",), ff_dim=8)
    assert older.sublayers == 2 and older.ff_kind(0) == "swiglu"
    assert older.expert_act == "swiglu" and older.shared_dim == 0


@pytest.mark.parametrize("bad", [
    dict(mixers=("mamba2",), ff_dim=8),                        # no heads
    dict(mixers=("attention",), ff_dim=8, ssd_heads=4, ssd_head_dim=8,
         ssd_groups=2),                                        # no mamba2
    dict(mixers=("mamba2",), ff_dim=8, ssd_heads=6, ssd_head_dim=8,
         ssd_groups=4),                                        # 6 in 4
    dict(mixers=("attention",), ff_dim=8, sublayers=3),
    dict(mixers=("attention",), ff_dim=8, expert_act="relu2"),  # no experts
    dict(mixers=("none",), ff_dim=8, norm_at="output"),
])
def test_trunk_spec_refuses_what_it_cannot_build(bad):
    with pytest.raises(AssertionError):
        TrunkSpec(**bad)


def _leaves(params):
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}


def test_relu2_banks_have_no_gate_and_the_shared_expert_its_own_width(model):
    cfg, _, variables, _, _ = model
    moe = variables["params"]["transformer"]["layers_1_ff"]["moe"]
    assert set(moe) == {"w_router", "router_bias", "w_up", "w_down",
                        "shared_up", "shared_down"}
    assert moe["w_up"].shape == (4, cfg.dim, 16)
    assert moe["shared_up"].shape == (cfg.dim, 24)


def test_matrices_are_bfloat16_and_small_tensors_float32():
    cfg = presets.preset_config("nemotron-tiny", dtype=jnp.bfloat16)
    cfg = DALLEConfig(**{**cfg.to_dict(), "dtype": jnp.bfloat16,
                         "trunk": dict(cfg.to_dict()["trunk"],
                                       param_dtype="bfloat16")})
    dalle = DALLE(cfg)
    params = jax.eval_shape(
        dalle.init, jax.random.PRNGKey(0),
        jnp.zeros((1, cfg.text_seq_len), jnp.int32),
        jnp.zeros((1, cfg.image_seq_len), jnp.int32))["params"]
    for name, leaf in _leaves(params).items():
        small = re.search(r"(A_log|/D|dt_bias|norm_gain|router_bias|scale|"
                          r"pos_emb)", name)
        assert leaf.dtype == (jnp.float32 if small else jnp.bfloat16), name


def test_every_new_leaf_meets_a_sharding_rule(model):
    from dalle_pytorch_tpu.parallel.plan import PARTITION_RULES

    _, _, variables, _, _ = model
    for name, leaf in _leaves(variables["params"]).items():
        if leaf.ndim < 2 or "pos_emb" in name:
            continue
        spec = next((spec for pat, spec in PARTITION_RULES
                     if re.match(pat, name)), None)
        assert spec is not None and len(spec) == leaf.ndim, (name, spec)


@pytest.mark.parametrize("plan", ["dp", "fsdp", "tp", "fsdp2.tp2"])
def test_registered_plans_place_every_leaf(model, plan):
    from dalle_pytorch_tpu.parallel.plan import ParallelPlan

    _, _, variables, _, _ = model
    part = ParallelPlan.parse(plan).partitioner(devices=jax.devices()[:4])
    placed = jax.device_put(variables["params"],
                            part.param_shardings(variables["params"]))
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
    for i in range(6):
        params, opt_state, loss = step(params, opt_state, None, text, codes,
                                       jax.random.PRNGKey(i))
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < 0.8 * losses[0], losses


def test_the_model_is_reachable_by_name():
    cfg = presets.preset_config("nemotron-3-nano-30b-a3b")
    assert cfg.total_tokens == 131072 and cfg.trunk.ssd_heads == 64
    assert presets.check_param_band("nemotron-3-nano-30b-a3b")
    bench = json.loads((REPO / "benchmark/configs/"
                        "nemotron-3-nano-30b-a3b.json").read_text())
    assert TrunkSpec(**bench["dalle"]["trunk"]) == cfg.trunk
    for key in ("dim", "depth", "heads", "dim_head", "text_seq_len",
                "num_text_tokens"):
        assert getattr(cfg, key) == bench["dalle"][key], key


# --- the older shared-expert trunks --------------------------------------------------

def _logits_and_shapes(cfg):
    """The forward logits of seeded weights and every leaf's shape."""
    dalle = DALLE(cfg)
    rng = np.random.default_rng(0)
    text = jnp.asarray(rng.integers(1, cfg.num_text_tokens,
                                    (2, cfg.text_seq_len)), jnp.int32)
    codes = jnp.asarray(rng.integers(0, cfg.num_image_tokens,
                                     (2, cfg.image_seq_len)), jnp.int32)
    v = jax.jit(dalle.init)(jax.random.PRNGKey(3), text, codes)
    return (np.asarray(jax.jit(dalle.apply)(v, text, codes)),
            jax.tree_util.tree_map(jnp.shape, v))


@pytest.mark.parametrize("name", ["glm-flash-tiny", "laguna-tiny"])
def test_the_gated_shared_expert_trunks_are_bit_identical(name):
    """``glm-4.7-flash``'s and ``laguna-s-2.1``'s tiny twins leave
    ``expert_act`` and ``shared_dim`` unstated: that is gated SwiGLU banks
    and a shared expert ``shared_experts x expert_dim`` wide, the same
    parameter tree and the same logits bit for bit as stating both."""
    cfg = presets.preset_config(name)
    spec = cfg.trunk
    assert (spec.expert_act, spec.shared_dim) == ("swiglu", 0)
    stated = dataclasses.replace(cfg, trunk=dataclasses.replace(
        spec, expert_act="swiglu",
        shared_dim=spec.shared_experts * spec.expert_dim))
    logits, shapes = _logits_and_shapes(cfg)
    stated_logits, stated_shapes = _logits_and_shapes(stated)
    assert shapes == stated_shapes
    np.testing.assert_array_equal(logits, stated_logits)
    moe = next(layer["moe"] for layer in
               shapes["params"]["transformer"].values() if "moe" in layer)
    assert moe["w_gate"] == moe["w_up"]
    assert moe["shared_gate"] == moe["shared_up"] == (
        cfg.dim, spec.shared_experts * spec.expert_dim)


# --- spans and counters -------------------------------------------------------------

def test_mamba2_scopes_are_registered_and_cover_the_step(model):
    """``ssd-proj``, ``ssd-conv`` and ``ssd-state`` are in the scope table
    and a decode step's state-sized equations sit under ``ssd-state``."""
    cfg, dalle, variables, text, _ = model
    for name in ("ssd-proj", "ssd-conv", "ssd-state"):
        assert name in prof.SCOPES
    _, caches = jax.eval_shape(lambda v, t: prefill_codes(dalle, v, t),
                               variables, text)
    lowered = jax.jit(lambda v, c, k: dalle.apply(
        v, jnp.zeros((2,), jnp.int32), c, k,
        method=DALLE.decode_step)).lower(variables, caches,
                                         jnp.asarray(cfg.text_seq_len + 1))
    text_ = lowered.as_text(debug_info=True)
    for name in ("ssd-proj", "ssd-conv", "ssd-state"):
        assert f"graftprof:{name}" in text_, name


def test_decode_trace_reports_the_stateless_layers(model, tmp_path):
    cfg, dalle, variables, text, _ = model
    first, caches = jax.eval_shape(
        lambda v, t: prefill_codes(dalle, v, t), variables, text)
    telemetry.init(tmp_path, run_id="stateless")
    try:
        jax.jit(lambda v, f, c, k: decode_codes(
            dalle, v, f, c, k, filter_thres=0.9)).lower(
                variables, first, caches, jax.random.PRNGKey(0))
    finally:
        telemetry.shutdown()
    records = [json.loads(line) for path in tmp_path.rglob("*.jsonl")
               for line in path.read_text().splitlines()]
    layout = next(r for r in records if r.get("name") == "state_layout")
    assert (layout["ssm_layers"], layout["kv_layers"],
            layout["stateless_layers"]) == (4, 1, 4)
    moe = next(r for r in records if r.get("name") == "moe_layout")
    assert moe["layers"] == 4
    assert moe["expert_bytes_per_layer"] == 2 * 4 * cfg.dim * 16 * 4
