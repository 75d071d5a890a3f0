"""DALL-E over a window-and-global, shared-expert ``TrunkSpec`` trunk (PERF.md,
Findings PR 40): sliding-window layers of one head count beside YaRN-rotated
global layers of another over the same key heads, a sigmoid gate a head,
softmax-routed SwiGLU experts scaled by 2.5 beside a shared expert, on the
experts this device holds.

Tiny widths (``presets.laguna_tiny_config``), seeded weights, float32
parameters and caches, on the CPU.  The program is held to
``benchmark/reference_laguna_s_2_1.py`` (it imports nothing from the program):
the forward pass, prefill + ticks past several wraps of the rings, the caches'
contents, each planted fault; then the rotation's table at the published
values, the gate and the head counts, the routing rule, the shares of the
experts adding up to the uncut layer, the arena against the static sampler,
the spec's stated fields, the presets, the sharding rules and the scope.
"""
from __future__ import annotations

import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from benchmark import reference_laguna_s_2_1 as reference  # noqa: E402
from dalle_pytorch_tpu import DALLE, DALLEConfig, presets  # noqa: E402
from dalle_pytorch_tpu.models.dalle import (  # noqa: E402
    decode_codes, prefill_codes)
from dalle_pytorch_tpu.obs import prof  # noqa: E402
from dalle_pytorch_tpu.ops import moe  # noqa: E402
from dalle_pytorch_tpu.ops.attention import (  # noqa: E402
    YARN_BETA_FAST, YARN_BETA_SLOW, YaRN, AttnPattern, MultiHeadAttention,
    apply_rope, yarn_ramp, yarn_table)
from dalle_pytorch_tpu.ops.transformer import TrunkSpec  # noqa: E402

#: Largest |program - reference| in units of the reference logits' standard
#: deviation, float32 on both sides: they differ in the order of sums only
#: (4e-6 measured).  1e-3 is two hundred and fifty times that; each planted
#: fault reads over 0.1.
LOGIT_TOL = 1e-3
PROMPT_PRIME = 3     # 9 + 3 positions prefilled: the window of 4 wraps first


def build(seed=0, **trunk):
    base = presets.laguna_tiny_config().trunk
    cfg = presets.laguna_tiny_config(
        trunk=dataclasses.replace(base, **trunk), kv_cache_bf16=False)
    dalle = DALLE(cfg)
    key = jax.random.PRNGKey(seed)
    text = jax.random.randint(key, (2, cfg.text_seq_len), 1, 50)
    codes = jax.random.randint(jax.random.fold_in(key, 1),
                               (2, cfg.image_seq_len), 0, 32)
    variables = dalle.init(key, text, codes)
    return cfg, dalle, variables, text, codes


@pytest.fixture(scope="module")
def model():
    return build()


def _err_std(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    keep = np.isfinite(want)
    std = np.nanstd(np.where(keep, want, np.nan), -1, keepdims=True)
    return float((np.abs(np.where(keep, got - want, 0)) / std).max())


def _stepwise(dalle, variables, text, codes, n_prime):
    """Image-phase logits ``[b, image_seq_len - n_prime, codes]`` through
    prefill and ticks, the experts chosen at each tick, and the caches after
    the last position."""
    cfg = dalle.cfg
    first, caches = dalle.apply(variables, text, codes[:, :n_prime],
                                method=DALLE.prefill)
    outs, index = [first], cfg.text_seq_len + 1 + n_prime
    for p in range(n_prime, cfg.image_seq_len - 1):
        logits, caches = dalle.apply(variables, codes[:, p], caches,
                                     jnp.asarray(index),
                                     method=DALLE.decode_step)
        outs.append(logits)
        index += 1
    return jnp.stack(outs, 1), caches


def _reference(variables, cfg, text, codes, **kw):
    logits, extras = [], []
    for i in range(text.shape[0]):
        out, ext = reference.image_logits(variables["params"], cfg,
                                          text[i:i + 1], codes[i:i + 1], **kw)
        logits.append(out)
        extras.append(ext)
    return jnp.concatenate(logits), extras


# --- the program against the reference ---------------------------------------------------

def test_forward_logits_and_mask_match_the_reference(model):
    cfg, dalle, variables, text, codes = model
    got = dalle.apply(variables, text, codes)
    want = np.concatenate([np.asarray(reference.joint_logits(
        variables["params"], cfg, text[i:i + 1], codes[i:i + 1]))
        for i in range(2)])
    np.testing.assert_array_equal(np.isfinite(want), np.asarray(got) > -1e30)
    assert _err_std(got, want) <= LOGIT_TOL


@pytest.mark.parametrize("n_prime", [0, PROMPT_PRIME, 9])
def test_prefill_and_ticks_past_the_rings_wraps_match_the_reference(
        model, n_prime):
    """Ticks from position 9 + n_prime to 23 through rings of 4 slots: the
    rings wrap three to four times inside the ticks (and in the prefill of
    9 to 18 positions), the global layers read every position, YaRN-rotated
    over their leading half."""
    cfg, dalle, variables, text, codes = model
    assert cfg.cache_lens == (24, 4, 4, 4, 24)
    got, _ = _stepwise(dalle, variables, text, codes, n_prime)
    want, _ = _reference(variables, cfg, text, codes)
    assert _err_std(got, want[:, n_prime:]) <= LOGIT_TOL


def test_the_caches_hold_the_references_rotated_keys_and_values(model):
    """A global layer's cache holds every position's key (YaRN-rotated) and
    value; a window layer's ring, slot ``s``, the last position ``p = s mod
    4``, rotated by the window's rope."""
    cfg, dalle, variables, text, codes = model
    _, caches = _stepwise(dalle, variables, text, codes, PROMPT_PRIME)
    _, extras = _reference(variables, cfg, text, codes)
    n = cfg.seq_len
    for i, ((ck, cv), kind) in enumerate(zip(caches, cfg.mixers)):
        held = (np.arange(n) if kind == "rotated"
                else n - 1 - np.remainder(n - 1 - np.arange(4), 4))
        for row in range(2):
            want_k, want_v = extras[row]["kv"][i]
            np.testing.assert_allclose(ck[row], want_k[0][:, held],
                                       atol=2e-5)
            np.testing.assert_allclose(cv[row], want_v[0][:, held],
                                       atol=2e-5)


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_tolerance_fails_each_planted_fault(model, fault):
    cfg, dalle, variables, text, codes = model
    got, _ = _stepwise(dalle, variables, text, codes, PROMPT_PRIME)
    want, _ = _reference(variables, cfg, text, codes, fault=fault)
    assert _err_std(got, want[:, PROMPT_PRIME:]) > 100 * LOGIT_TOL


def test_decode_codes_is_the_stepwise_oracle(model):
    """The static scan (lane-dense carry, rings, tiling) draws what greedy
    decoding through ``decode_step`` draws."""
    cfg, dalle, variables, text, codes = model
    prime = codes[:, :PROMPT_PRIME]
    first, caches = prefill_codes(dalle, variables, text, prime_codes=prime)
    got = decode_codes(dalle, variables, first, caches,
                       jax.random.PRNGKey(0), n_prime=PROMPT_PRIME,
                       prime_codes=prime, filter_thres=1.0)
    logits, caches = dalle.apply(variables, text, prime, method=DALLE.prefill)
    out, index = [jnp.argmax(logits, -1)], cfg.text_seq_len + 1 + PROMPT_PRIME
    while len(out) < cfg.image_seq_len - PROMPT_PRIME:
        logits, caches = dalle.apply(variables, out[-1], caches,
                                     jnp.asarray(index),
                                     method=DALLE.decode_step)
        out.append(jnp.argmax(logits, -1))
        index += 1
    np.testing.assert_array_equal(got[:, PROMPT_PRIME:], jnp.stack(out, 1))
    np.testing.assert_array_equal(got[:, :PROMPT_PRIME], prime)


# --- the rotation ------------------------------------------------------------------------

def test_the_yarn_table_at_the_published_values():
    """Laguna's global layers: theta 5e5 over 64 dimensions, factor 128 over
    8,192 positions, betas 32 and 1: the ramp runs from pair 9 (9.04 rounded
    down) to 18 (17.49 rounded up), the attention factor is 0.1 ln 128 + 1 =
    1.4852; pairs under 9 keep RoPE's frequency, pairs from 18 on turn 128
    times slower, and the reference's table (written apart) agrees."""
    yarn = YaRN(128.0, 8192)
    assert yarn_ramp(5e5, 64, yarn) == (9, 18)
    assert reference.yarn_ramp(5e5, 64, 8192, 32.0, 1.0) == (9, 18)
    assert yarn.scale == pytest.approx(1.4852030263919618, rel=1e-12)
    assert yarn.scale == pytest.approx(reference.attention_factor(128.0),
                                       rel=1e-12)
    table = yarn_table(5e5, 64, yarn)
    base = 5e5 ** (-np.arange(0, 64, 2) / 64)
    assert table.dtype == np.float32 and table.shape == (32,)
    np.testing.assert_allclose(table[:10], base[:10], rtol=1e-6)   # ramp 0
    np.testing.assert_allclose(table[18:], base[18:] / 128, rtol=1e-6)
    mid = 13                                  # ramp (13 - 9) / 9
    np.testing.assert_allclose(
        table[mid], base[mid] / 128 * 4 / 9 + base[mid] * 5 / 9, rtol=1e-6)
    np.testing.assert_allclose(table, reference.frequencies(
        5e5, 64, dict(factor=128.0, original=8192)), rtol=2e-6)


def test_partial_rotation_turns_the_leading_dimensions_only():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 7, 16))
    pos = jnp.arange(7)
    got = apply_rope(x, pos, 5e5, 8)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_allclose(got[..., :8], apply_rope(x[..., :8], pos, 5e5),
                               rtol=1e-6, atol=1e-6)
    # with a table and a factor: the rotated part scales, the rest does not
    freq = yarn_table(5e5, 8, YaRN(128.0, 8192))
    scaled = apply_rope(x, pos, 5e5, 8, freq, 1.5)
    np.testing.assert_array_equal(scaled[..., 8:], x[..., 8:])
    np.testing.assert_allclose(
        jnp.linalg.norm(scaled[..., :8], axis=-1),
        1.5 * jnp.linalg.norm(x[..., :8], axis=-1), rtol=1e-5)
    # the default rotation is the operations it was before it took a table
    half = 8
    freq = 5e5 ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = pos.astype(jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    lo, hi = x[..., :half], x[..., half:]
    np.testing.assert_array_equal(
        apply_rope(x, pos, 5e5),
        jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1))


# --- the gate and the head counts ----------------------------------------------------------

def test_each_kind_has_its_own_heads_and_a_gate_a_head(model):
    cfg, _, variables, _, _ = model
    layers = variables["params"]["transformer"]
    for i, kind in enumerate(cfg.mixers):
        attn = layers[f"layers_{i}_attn"]["attn"]
        heads = 6 if kind == "window" else 4
        assert attn["to_q"]["kernel"].shape == (32, heads, 16)
        assert attn["to_kv"]["kernel"].shape == (32, 2, 2, 16)
        assert attn["to_gate"]["kernel"].shape == (32, heads)
        assert attn["to_out"]["kernel"].shape == (heads * 16, 32)
    with pytest.raises(AssertionError):           # 5 over 2 key heads
        presets.laguna_tiny_config(trunk=dataclasses.replace(
            presets.laguna_tiny_config().trunk, window_heads=5))


@pytest.mark.parametrize("window", [0, 4])
def test_the_gate_scales_each_heads_output(window):
    """With the gate's kernel zero every head is halved (sigmoid 0): the
    layer's output is half the ungated one's, on the forward path, in the
    prefill's keys and values (untouched) and in a tick."""
    pattern = AttnPattern(variant="full", seq_len=12, text_len=5, fmap=0,
                          window=window)
    kw = dict(pattern=pattern, dim=16, heads=6, dim_head=8, kv_heads=2,
              use_bias=False, rope_theta=1e4)
    gated = MultiHeadAttention(head_gate=True, **kw)
    plain = MultiHeadAttention(**kw)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 16))
    params = gated.init(jax.random.PRNGKey(2), x)["params"]
    params = dict(params, to_gate={"kernel": jnp.zeros((16, 6))})
    bare = {k: v for k, v in params.items() if k != "to_gate"}
    out, kv = gated.apply({"params": params}, x, return_kv=True)
    want, want_kv = plain.apply({"params": bare}, x, return_kv=True)
    np.testing.assert_allclose(out, want / 2, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(kv[0], want_kv[0])
    slots = pattern.cache_len
    cache = jnp.zeros((2, 2, slots, 8))
    tick = gated.apply({"params": params}, x[:, :1], cache, cache,
                       jnp.asarray(0), method=MultiHeadAttention.decode_step)
    tick_plain = plain.apply({"params": bare}, x[:, :1], cache, cache,
                             jnp.asarray(0),
                             method=MultiHeadAttention.decode_step)
    np.testing.assert_allclose(tick[0], tick_plain[0] / 2, rtol=1e-6,
                               atol=1e-6)
    with pytest.raises(AssertionError):         # the fused to_qkv has none
        MultiHeadAttention(pattern=pattern, dim=16, heads=2, dim_head=8,
                           head_gate=True).init(jax.random.PRNGKey(0), x)


def test_the_gate_is_a_scope_of_its_own(model):
    assert "attn-gate" in prof.SCOPES
    cfg, dalle, variables, text, codes = model
    _, caches = dalle.apply(variables, text, None, method=DALLE.prefill)
    lowered = jax.jit(lambda v, c, k, i: dalle.apply(
        v, c, k, i, method=DALLE.decode_step)).lower(
            variables, codes[:, 0], caches, jnp.asarray(cfg.text_seq_len + 1))
    text_ = lowered.as_text(debug_info=True)
    for scope in ("attn-gate", "attn-qkv", "attn-scores", "attn-cache",
                  "attn-out", "moe-route", "moe-experts", "ff"):
        assert f"graftprof:{scope}" in text_, scope


# --- the routing -------------------------------------------------------------------------

def test_softmax_routing_renormalises_then_scales():
    logits = jax.random.normal(jax.random.PRNGKey(4), (5, 16)) * 2
    probs, idx, combine = moe.route(logits, 4, "softmax", None, 2.5)
    _, _, unit = moe.route(logits, 4)
    np.testing.assert_array_equal(combine, 2.5 * unit)
    np.testing.assert_allclose(combine.sum(-1), 2.5, rtol=1e-6)
    top = np.sort(np.asarray(probs), -1)[:, -4:]
    np.testing.assert_allclose(np.sort(np.asarray(jnp.take_along_axis(
        combine, idx, -1)), -1), 2.5 * top / top.sum(-1, keepdims=True),
        rtol=1e-6)
    with pytest.raises(AssertionError):
        moe.route(logits, 4, "softmax", jnp.zeros((16,)), 2.5)


def test_a_softmax_layer_carries_no_selection_bias(model):
    cfg, _, variables, _, _ = model
    for i in range(1, cfg.depth):
        assert set(variables["params"]["transformer"][f"layers_{i}_ff"]
                   ["moe"]) == {"w_router", "w_gate", "w_up", "w_down",
                                "shared_gate", "shared_up", "shared_down"}
    glm = presets.glm_flash_tiny_config()
    assert glm.trunk.scoring == "sigmoid"
    params = jax.eval_shape(DALLE(glm).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32),
                            jnp.zeros((1, 16), jnp.int32))["params"]
    assert "router_bias" in params["transformer"]["layers_1_ff"]["moe"]


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen devices each hold 2 of 32 experts of ONE routed layer with the
    same router and shared expert: the routed parts of their results, with
    the shared expert counted once, add up to what the uncut reference gives
    for the whole layer; and each share equals the reference given the same
    share."""
    dim, width, experts, k, held = 32, 24, 32, 10, 2
    key = jax.random.PRNGKey(5)
    kw = dict(dim=dim, experts=experts, k=k, expert_dim=width,
              scoring="softmax", scale=2.5)
    whole = moe.ExpertsSwiGLUShared(held=experts, **kw)
    m = jax.random.normal(key, (2, 9, dim))
    full = whole.init(jax.random.fold_in(key, 1), m)["params"]
    assert "router_bias" not in full
    unit = m / jnp.sqrt(jnp.mean(m * m, -1, keepdims=True) + 1e-6)

    def ref_layer(bank, first, shared=1.0):
        p = {"norm": {"scale": jnp.ones((dim,))}, "moe": dict(full, **bank)}
        knobs = {"scale": jnp.float32(2.5), "shared": jnp.float32(shared),
                 "first": jnp.int32(first)}
        return reference._experts(p, m, None, knobs, eps=1e-6, k=k,
                                  low=None)[0]

    want = ref_layer({}, 0)
    shared = want - ref_layer({}, 0, shared=0.0)
    routed = jnp.zeros_like(want)
    for first in range(0, experts, held):
        bank = {name: full[name][first:first + held]
                for name in ("w_gate", "w_up", "w_down")}
        share = moe.ExpertsSwiGLUShared(held=held, first=first, **kw)
        got = share.apply({"params": dict(full, **bank)}, unit)
        np.testing.assert_allclose(got, ref_layer(bank, first), atol=2e-5)
        routed = routed + (got - shared)
    np.testing.assert_allclose(routed + shared, want, atol=5e-5)
    assert float(jnp.abs(got - want).max()) > 0.05


# --- the spec ------------------------------------------------------------------------------

def test_the_spec_states_its_scoring_and_its_rotations():
    spec = presets.laguna_tiny_config().trunk
    assert spec.scoring == "softmax" and spec.routed
    assert spec.mixers == ("rotated", "window", "window", "window")
    assert spec.rotary and spec.yarn == YaRN(128.0, 8192)
    # a configuration written before the field keeps its family's scoring
    old = dict(mixers=["mla"], ff_dim=8, ff="moe_swiglu_shared", q_rank=4,
               kv_rank=4, nope_dim=4, rope_dim=4, value_dim=4, experts=4,
               experts_per_token=2, expert_dim=4)
    assert TrunkSpec(**old).scoring == "sigmoid"
    assert TrunkSpec(**dict(old, scoring="softmax")).scoring == "softmax"
    assert TrunkSpec(mixers=["attention"], ff="moe_reglu", experts=4,
                     experts_per_token=2, expert_dim=4).scoring == "softmax"
    assert TrunkSpec(mixers=["attention"], ff_dim=8).scoring == "softmax"


@pytest.mark.parametrize("bad", [
    dict(mixers=["rotated"], ff_dim=8),                        # no theta
    dict(mixers=["attention"], ff_dim=8, global_rope_theta=1e4),
    dict(mixers=["attention"], ff_dim=8, window_heads=4),
    dict(mixers=["window"], window=4, ff_dim=8, yarn_factor=8.0,
         yarn_original_len=16),                                # no "rotated"
    dict(mixers=["rotated"], ff_dim=8, global_rope_theta=1e4,
         global_rope_fraction=0.0),
    dict(mixers=["attention"], ff="moe_reglu", experts=4,
         experts_per_token=2, expert_dim=4, scoring="sigmoid"),
    dict(mixers=["attention"], ff_dim=8, scoring="top1"),
])
def test_trunk_spec_refuses_what_it_cannot_build(bad):
    with pytest.raises(AssertionError):
        TrunkSpec(**bad)


def test_the_preset_is_the_benchmarks_configuration():
    import json

    cfg = presets.laguna_s_2_1_config()
    body = json.loads(
        (REPO / "benchmark/configs/laguna-s-2.1.json").read_text())
    trunk = TrunkSpec(**body["dalle"]["trunk"])
    assert trunk == cfg.trunk
    assert (cfg.dim, cfg.depth, cfg.heads, cfg.dim_head, cfg.text_seq_len,
            cfg.num_text_tokens, cfg.image_fmap_size) == (
        body["hidden_size"], body["num_hidden_layers"],
        body["num_attention_heads"], body["head_dim"], 256, 91904, 64)
    assert cfg.total_tokens == body["vocab_size"] == 100352
    assert cfg.mixers == ("rotated", "window", "window", "window", "rotated")
    assert [72 if kind == "window" else 48 for kind in cfg.mixers] == (
        body["num_attention_heads_per_layer"][:5])
    assert cfg.cache_lens == (4352, 512, 512, 512, 4352)
    t = cfg.trunk
    rope = body["rope_parameters"]
    assert (t.global_rope_theta, t.global_rope_fraction, t.yarn_factor,
            t.yarn_original_len, YARN_BETA_FAST, YARN_BETA_SLOW,
            pytest.approx(t.yarn.scale, rel=1e-12)) == (
        rope["full_attention"]["rope_theta"],
        rope["full_attention"]["partial_rotary_factor"],
        rope["full_attention"]["factor"],
        rope["full_attention"]["original_max_position_embeddings"],
        rope["full_attention"]["beta_fast"],
        rope["full_attention"]["beta_slow"],
        rope["full_attention"]["attention_factor"])
    assert (t.rope_theta, t.window) == (
        rope["sliding_attention"]["rope_theta"], body["sliding_window"])
    assert (t.experts, t.experts_per_token, t.expert_dim, t.route_scale,
            t.ff_dim, t.shared_experts * t.expert_dim, t.experts_held) == (
        256, body["num_experts_per_tok"], body["moe_intermediate_size"],
        body["moe_routed_scaling_factor"], body["intermediate_size"],
        body["shared_expert_intermediate_size"], body["num_experts"])
    assert presets.preset_param_count("laguna-s-2.1") == pytest.approx(
        1.6525e9, rel=1e-3)
    assert presets.check_param_band("laguna-tiny")


def test_every_new_leaf_meets_a_sharding_rule(model):
    import re

    from dalle_pytorch_tpu.parallel.plan import TRUNK_RULES

    _, _, variables, _, _ = model
    paths = ["/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in jax.tree_util.tree_flatten_with_path(
                 variables["params"])[0]]
    gates = [p for p in paths if p.endswith("to_gate/kernel")]
    assert len(gates) == 5
    for path in gates:
        assert any(re.match(rule, path) for rule, _ in TRUNK_RULES), path


# --- the arena ------------------------------------------------------------------------------

def test_arena_over_the_trunk_matches_static_decode_code_for_code():
    """Admit, tick, admit mid-flight at another depth (the global layers'
    rows then sit at different rotations, the rings at their own
    positions), retire, re-admit into the freed slot: every request's codes
    are the static sampler's, bit for bit."""
    from dalle_pytorch_tpu.serve import GenerationServer

    cfg, dalle, variables, _, _ = build(seed=2)
    texts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(i), (cfg.text_seq_len,), 1, 50), np.int32)
        for i in range(4)]
    prefill = jax.jit(lambda p, t: prefill_codes(dalle, p, t))

    def static(i):
        first, caches = prefill(variables, jnp.asarray(texts[i])[None])
        return np.asarray(decode_codes(dalle, variables, first, caches,
                                       jax.random.PRNGKey(7),
                                       filter_thres=1.0))[0]

    refs = [static(i) for i in range(4)]
    srv = GenerationServer(dalle, variables, num_slots=2, filter_thres=1.0)
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
    assert math.isfinite(float(np.asarray(refs).sum()))
