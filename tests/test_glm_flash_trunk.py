"""DALL-E over a latent-attention, shared-expert ``TrunkSpec`` trunk (PERF.md,
Findings PR 38): multi-head latent attention whose decode cache is one normed
latent and one rotated key a position, read in the absorbed form; a leading
dense layer; sigmoid-routed SwiGLU experts with a selection bias, a scale and a
shared expert, on the experts this device holds.

Tiny widths, seeded weights, float32 parameters, on the CPU.  The program is
held to ``benchmark/reference_glm_4_7_flash.py`` (the published form; it
imports nothing from the program): the absorbed tick against the published
form position by position, the cache's contents, the forward pass and its
routing, prefill + ticks (primed and not), the shares of the experts adding up
to the uncut layer, the bias, the loss; then the arena's latent slots, the
bounded read's buckets, the refusing asserts, the records and gauges, the
presets, the sharding rules, and the older trunks' programs, which must not
have moved.
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

from benchmark import reference_glm_4_7_flash as reference  # noqa: E402
from dalle_pytorch_tpu import DALLE, DALLEConfig, presets  # noqa: E402
from dalle_pytorch_tpu.models.dalle import (  # noqa: E402
    decode_codes, generate_codes, prefill_codes, tile_prefill)
from dalle_pytorch_tpu.obs import metrics, prof, telemetry  # noqa: E402
from dalle_pytorch_tpu.obs.report import build_report, render_text  # noqa: E402
from dalle_pytorch_tpu.ops import moe  # noqa: E402
from dalle_pytorch_tpu.ops.attention import AttnPattern, read_bounds  # noqa: E402
from dalle_pytorch_tpu.ops.latent_attention import LatentAttention  # noqa: E402
from dalle_pytorch_tpu.ops.transformer import (  # noqa: E402
    TrunkSpec, cache_position_axis, is_latent, is_recurrent, is_rotary,
    is_routed, layer_cache_lens)

#: the tiny twin: every mechanism, the ranks and the parts of a head all of
#: different sizes, so that a transposed split cannot pass
TRUNK = dict(mixers=["mla"], ff_dim=80, norm_eps=1e-5, ff="moe_swiglu_shared",
             rope_theta=1e6, q_rank=24, kv_rank=20, nope_dim=12, rope_dim=4,
             value_dim=10, dense_layers=1, experts=8, experts_per_token=2,
             expert_dim=24, experts_held=2, experts_first=2, shared_experts=1,
             route_scale=1.8, tied_table=False, param_dtype="float32")
GEOMETRY = dict(dim=32, depth=3, heads=4, dim_head=16, num_text_tokens=50,
                text_seq_len=8, num_image_tokens=32, image_size=32,
                image_fmap_size=4)

#: Largest |program - reference| in units of the reference logits' standard
#: deviation, float32 on both sides: they differ in the order of sums (the
#: absorbed form re-associates the products) and read 3e-6.  1e-3 is three
#: hundred times that; each planted fault reads over 0.05.
LOGIT_TOL = 1e-3


def build(trunk=TRUNK, geometry=GEOMETRY, seed=0, **cfg_kw):
    # the float32 cache: the comparison is of the mathematics, not of bf16
    cfg = DALLEConfig(trunk=dict(trunk), kv_cache_bf16=False, **geometry,
                      **cfg_kw)
    dalle = DALLE(cfg)
    key = jax.random.PRNGKey(seed)
    text = jax.random.randint(key, (2, cfg.text_seq_len), 0, 50)
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
    std = np.where(keep, want, np.nan)
    std = np.nanstd(std, -1, keepdims=True)
    return float((np.abs(np.where(keep, got - want, 0)) / std).max())


def _stepwise(dalle, variables, text, codes, n_prime):
    """Image-phase logits ``[b, image_seq_len - n_prime, codes]`` through
    prefill and ticks, and the caches after the last position."""
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


# --- the absorbed form is the published form ---------------------------------------------

def test_the_absorbed_tick_equals_the_published_form_position_by_position():
    """One layer alone: its sequence form (every position's keys and values
    decompressed) against ``decode_step`` (no position decompressed) fed the
    same inputs one at a time, and the cache after ``n`` ticks against what
    the sequence form says the cache holds."""
    n, dim = 11, 32
    layer = LatentAttention(
        pattern=AttnPattern(variant="full", seq_len=n, text_len=3, fmap=0,
                            causal=True),
        dim=dim, heads=4, q_rank=24, kv_rank=20, nope_dim=12, rope_dim=4,
        value_dim=10, rope_theta=1e6, eps=1e-5)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, n, dim))
    params = layer.init(jax.random.PRNGKey(1), x)
    want, (c, k_rope) = layer.apply(params, x, return_kv=True)
    caches = layer.apply(params, 2, n, jnp.float32,
                         method=LatentAttention.init_cache)
    assert [a.shape for a in caches] == [(2, n, 20), (2, n, 4)]
    for t in range(n):
        got, *caches = layer.apply(params, x[:, t:t + 1], *caches,
                                   jnp.asarray(t),
                                   method=LatentAttention.decode_step)
        np.testing.assert_allclose(got[:, 0], want[:, t], atol=2e-6)
    np.testing.assert_allclose(caches[0], c, atol=1e-6)
    np.testing.assert_allclose(caches[1], k_rope, atol=1e-6)


def test_the_cache_holds_the_references_latent_and_rotated_key(model):
    cfg, dalle, variables, text, codes = model
    _, caches = _stepwise(dalle, variables, text, codes, 0)
    _, extras = reference.hidden(variables["params"], cfg, text, codes)
    assert len(caches) == cfg.depth
    for (c, k_rope), (want_c, want_kr) in zip(caches, extras["latent"]):
        assert c.shape == (2, cfg.seq_len, 20)
        assert k_rope.shape == (2, cfg.seq_len, 4)
        np.testing.assert_allclose(c, want_c, atol=2e-5)
        np.testing.assert_allclose(k_rope, want_kr, atol=2e-5)
    # and a fault planted in the reference is seen there
    for fault, pick in (("unnormed_latent", 0), ("unrotated_key", 1)):
        planted = reference.hidden(variables["params"], cfg, text, codes,
                                   fault=fault)[1]["latent"]
        assert float(np.abs(np.asarray(caches[0][pick])
                            - np.asarray(planted[0][pick])).max()) > 0.05


# --- the trunk against the reference -----------------------------------------------------

def test_forward_logits_mask_and_routing_match_the_reference(model):
    cfg, dalle, variables, text, codes = model
    got, state = dalle.apply(variables, text, codes,
                             mutable=["intermediates"])
    want = reference.joint_logits(variables["params"], cfg, text, codes)
    assert _err_std(got, want) <= LOGIT_TOL
    # the phase mask: the same entries are suppressed
    np.testing.assert_array_equal(np.asarray(got) < -1e30,
                                  ~np.isfinite(np.asarray(want)))
    _, extras = reference.hidden(variables["params"], cfg, text, codes)
    layers = state["intermediates"]["transformer"]
    assert "moe" not in layers.get("layers_0_ff", {})     # the dense layer
    for i in (1, 2):
        sown = layers[f"layers_{i}_ff"]["moe"]
        np.testing.assert_array_equal(
            np.sort(sown["top_idx"][0], -1),
            np.sort(extras["top_idx"][i - 1], -1))
        np.testing.assert_allclose(
            np.sort(sown["top_weight"][0], -1),
            np.sort(extras["weight"][i - 1], -1), atol=1e-6)
    assert float(extras["gap"].min()) > 1e-4    # no tie decides this seed


@pytest.mark.parametrize("n_prime", [0, 5, 9])
def test_prefill_and_absorbed_ticks_match_the_references_forward(model,
                                                                 n_prime):
    cfg, dalle, variables, text, codes = model
    got, _ = _stepwise(dalle, variables, text, codes, n_prime)
    want, _ = reference.image_logits(variables["params"], cfg, text, codes)
    assert _err_std(got, want[:, n_prime:]) <= LOGIT_TOL


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_tolerance_fails_each_planted_fault(model, fault):
    cfg, dalle, variables, text, codes = model
    got = dalle.apply(variables, text, codes)
    want = reference.joint_logits(variables["params"], cfg, text, codes,
                                  fault=fault)
    assert _err_std(got, want) > 50 * LOGIT_TOL


@pytest.mark.parametrize("n_prime", [0, 7])
def test_decode_codes_with_prime_codes_matches_a_stepwise_oracle(model,
                                                                 n_prime):
    """The jitted scan over a tiled prefill draws, greedily, the codes a
    stepwise argmax over the program's own ticks draws."""
    cfg, dalle, variables, text, codes = model
    prime = codes[:1, :n_prime]
    first, caches = prefill_codes(dalle, variables, text[:1],
                                  prime_codes=prime)
    first, caches = tile_prefill(first, caches, 3)
    got = decode_codes(dalle, variables, first, caches,
                       jax.random.PRNGKey(0), n_prime=n_prime,
                       prime_codes=jnp.repeat(prime, 3, 0), filter_thres=1.0)
    assert got.shape == (3, cfg.image_seq_len)
    logits, caches = dalle.apply(variables, text[:1], prime,
                                 method=DALLE.prefill)
    want, index = list(np.asarray(prime[0])), cfg.text_seq_len + 1 + n_prime
    while len(want) < cfg.image_seq_len:
        want.append(int(jnp.argmax(logits[0])))
        logits, caches = dalle.apply(
            variables, jnp.asarray(want[-1:]), caches, jnp.asarray(index),
            method=DALLE.decode_step)
        index += 1
    for row in np.asarray(got):
        np.testing.assert_array_equal(row, want)


def test_loss_matches_the_reference_and_a_train_step_trains(model):
    from dalle_pytorch_tpu.training import (make_dalle_train_step,
                                            make_optimizer)

    cfg, dalle, variables, text, codes = model
    got = dalle.apply(variables, text, codes, return_loss=True)
    want = reference.train_loss(variables["params"], cfg, text, codes)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    grads = jax.grad(lambda p: dalle.apply({"params": p}, text, codes,
                                           return_loss=True))(
        variables["params"])
    want_grads = jax.grad(lambda p: reference.train_loss(
        p, cfg, text, codes))(variables["params"])
    for name in ("w_qa", "w_qb", "w_kva", "w_kvb", "w_o"):
        np.testing.assert_allclose(
            grads["transformer"]["layers_1_attn"]["mla"][name],
            want_grads["transformer"]["layers_1_attn"]["mla"][name],
            atol=2e-5)
    # the selection bias enters a choice only: no gradient reaches it
    assert not np.asarray(
        grads["transformer"]["layers_1_ff"]["moe"]["router_bias"]).any()
    tx = make_optimizer(3e-3)
    params = jax.tree.map(jnp.copy, variables["params"])
    opt = tx.init(params)
    step = make_dalle_train_step(dalle, tx, donate=False)
    losses = []
    for i in range(12):
        params, opt, loss = step(params, opt, None, text, codes,
                                 jax.random.PRNGKey(i))
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < 0.8 * losses[0], losses


# --- the share --------------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """Four devices each hold 2 of the 8 experts (``experts_first`` 0, 2, 4,
    6) of ONE routed layer with the same router, bias and shared expert: the
    routed parts of their results, with the shared expert counted once, add
    up to what the uncut reference gives for the whole layer; and each share
    equals the reference given the same share."""
    dim, width, experts, k = 32, 24, 8, 2
    key = jax.random.PRNGKey(3)
    whole = moe.ExpertsSwiGLUShared(dim=dim, experts=experts, k=k,
                                    expert_dim=width, held=experts,
                                    scale=1.8)
    m = jax.random.normal(key, (2, 9, dim))
    full = whole.init(jax.random.fold_in(key, 1), m)["params"]

    # the reference norms its input; hand the program the normed input
    unit = m / jnp.sqrt(jnp.mean(m * m, -1, keepdims=True) + 1e-5)

    def ref_layer(bank, first, fault=None):
        p = {"norm": {"scale": jnp.ones((dim,))}, "moe": dict(full, **bank)}
        return reference._experts(p, m, eps=1e-5, k=k, scale=1.8,
                                  first=first, routing=None, low=None,
                                  fault=fault)[0]

    want = ref_layer({}, 0)
    shared = want - ref_layer({}, 0, fault="no_shared_expert")
    routed = jnp.zeros_like(want)
    for first in (0, 2, 4, 6):
        bank = {name: full[name][first:first + 2]
                for name in ("w_gate", "w_up", "w_down")}
        share = moe.ExpertsSwiGLUShared(dim=dim, experts=experts, k=k,
                                        expert_dim=width, held=2,
                                        first=first, scale=1.8)
        got = share.apply({"params": dict(full, **bank)}, unit)
        np.testing.assert_allclose(got, ref_layer(bank, first), atol=2e-5)
        routed = routed + (got - shared)
    np.testing.assert_allclose(routed + shared, want, atol=5e-5)
    # and no share alone is the layer
    assert float(jnp.abs(got - want).max()) > 0.05


def test_the_bias_moves_the_choice_and_not_the_weight():
    logits = jnp.asarray([[2.0, 1.0, 0.9, -1.0]])
    scores, idx, combine = moe.route(logits, 2, "sigmoid", None, 1.8)
    assert sorted(np.asarray(idx[0])) == [0, 1]
    bias = jnp.asarray([0.0, 0.0, 0.2, 0.0])       # lifts expert 2 over 1
    scores_b, idx_b, combine_b = moe.route(logits, 2, "sigmoid", bias, 1.8)
    assert sorted(np.asarray(idx_b[0])) == [0, 2]
    np.testing.assert_array_equal(scores, scores_b)
    sc = np.asarray(jax.nn.sigmoid(logits))[0]
    want = np.zeros(4, np.float32)
    want[[0, 2]] = 1.8 * sc[[0, 2]] / (sc[0] + sc[2] + 1e-20)   # no 0.2 here
    np.testing.assert_allclose(combine_b[0], want, rtol=1e-6)
    assert float(combine_b.sum()) == pytest.approx(1.8, rel=1e-6)
    # a bias that changes no choice changes nothing at all
    same = moe.route(logits, 2, "sigmoid", jnp.full((4,), 0.05), 1.8)
    np.testing.assert_array_equal(same[2], combine)


@pytest.mark.parametrize("e,k", [(4, 2), (8, 3), (64, 6)])
def test_softmax_routing_is_bit_for_bit_what_it_was(e, k):
    """``route``'s default is the rule ``moe_reglu`` and the 2021 block's
    experts had before it took a scoring: the same operations in the same
    order."""
    logits = jax.random.normal(jax.random.PRNGKey(e), (7, e)) * 3
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, k)
    onehot = jax.nn.one_hot(top_idx, e, dtype=probs.dtype)
    combine = (top_p[..., None] * onehot).sum(axis=-2)
    combine = combine / jnp.clip(combine.sum(axis=-1, keepdims=True), 1e-9)
    got = moe.route(logits, k)
    for a, b in zip(got, (probs, top_idx, combine)):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(AssertionError):
        moe.route(logits, k, "softmax", jnp.zeros((e,)))


# --- the older trunks' programs have not moved --------------------------------------------

def _digest(lowered) -> str:
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


def _programs(cfg):
    """tests/test_smallthinker_trunk.py::_programs: forward loss + gradient,
    prefill, the decode scan, lowered at toy width."""
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


#: sha256[:16] of each program's StableHLO text at PR 38's parent (ef3ac1f),
#: written by this very function run in a checkout of it (the twins of
#: ``jamba2-3b``, ``cub200`` and ``lucid1024`` are held by
#: tests/test_smallthinker_trunk.py).
PARENT_PROGRAMS = {
    "smallthinker-tiny": {"grad": "3dcce9ff5cf96938",
                          "prefill": "ee4d84663de2029c",
                          "decode": "8972395f61e812cf"},
    "olmo-hybrid-tiny": {"grad": "5a603f3db6c2211c",
                         "prefill": "fb608d3545267d5f",
                         "decode": "629be7f7007ecbe5"},
}


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_the_older_routed_and_hybrid_trunks_lower_to_the_parents_text(name):
    """``moe_reglu`` under the shared ``route`` and the factored bank
    products, rotated window layers, rings, grouped keys and linear-attention
    layers under the wider ``TrunkSpec`` and ``prefill``: operation for
    operation the programs of the parent commit."""
    got = {k: _digest(v)
           for k, v in _programs(presets.preset_config(name)).items()}
    assert got == PARENT_PROGRAMS[name], got


# --- the spec ------------------------------------------------------------------------------

def test_spec_names_each_layers_kinds_by_predicates():
    spec = TrunkSpec(**TRUNK)
    assert spec.rotary and spec.routed and spec.scoring == "sigmoid"
    assert [spec.ff_kind(i) for i in range(3)] == [
        "swiglu", "moe_swiglu_shared", "moe_swiglu_shared"]
    assert spec.held_experts == 2
    assert is_latent("mla") and not is_recurrent("mla") and is_rotary("mla")
    assert is_rotary("window") and not is_rotary("attention")
    assert is_routed("moe_reglu") and not is_routed("swiglu")
    assert cache_position_axis("mla") == 1
    assert cache_position_axis("attention") == 2
    assert layer_cache_lens(spec, 3, 24) == (24, 24, 24)
    cfg = DALLEConfig(trunk=dict(TRUNK), **GEOMETRY)
    assert cfg.rotary and cfg.mixers == ("mla",) * 3
    again = DALLEConfig.from_dict(cfg.to_dict())
    assert again == cfg and hash(again) == hash(cfg)
    older = TrunkSpec(mixers=("attention",), ff_dim=8)
    assert not older.rotary and not older.routed
    assert older.scoring == "softmax" and older.ff_kind(0) == "swiglu"


@pytest.mark.parametrize("bad", [
    dict(q_rank=0),                                # 'mla' without a size
    dict(mixers=["attention"]),                    # sizes without 'mla'
    dict(experts_held=7, experts_first=2),         # past the router's width
    dict(ff_dim=0),                                # a dense layer needs it
    dict(ff="moe_reglu"),                          # the share is not its
    dict(norm_at="output"),
])
def test_trunk_spec_refuses_what_it_cannot_build(bad):
    with pytest.raises(AssertionError):
        TrunkSpec(**dict(TRUNK, **bad))


@pytest.mark.parametrize("field,value", [
    ("reversible", True), ("weights_int8", True),
    ("kv_cache_int8", True), ("attn_dropout", 0.1), ("ring_axis", "sp")])
def test_paths_without_a_form_for_a_latent_cache_refuse(field, value):
    with pytest.raises(AssertionError) as refusal:
        DALLEConfig(trunk=dict(TRUNK), **GEOMETRY, **{field: value})
    if field in ("reversible", "weights_int8", "kv_cache_int8"):
        assert "'mla'" in str(refusal.value)
        assert "moe_swiglu_shared" in str(refusal.value)


def test_parameter_tree_dtypes_and_the_presets():
    cfg = presets.preset_config("glm-flash-tiny", dtype=jnp.bfloat16)
    assert cfg.trunk.experts_first == 2 and cfg.trunk.held_experts == 2
    bf = dataclasses.replace(
        cfg, trunk=dataclasses.replace(cfg.trunk, param_dtype="bfloat16"))
    shapes = jax.eval_shape(
        DALLE(bf).init, jax.random.PRNGKey(0),
        jnp.zeros((1, cfg.text_seq_len), jnp.int32),
        jnp.zeros((1, cfg.image_seq_len), jnp.int32))["params"]
    assert "text_pos_emb" not in shapes and "head" in shapes
    mla = shapes["transformer"]["layers_1_attn"]["mla"]
    experts = shapes["transformer"]["layers_1_ff"]["moe"]
    assert mla["w_kvb"].shape == (20, 4, 22) and mla["w_o"].shape == (4, 10,
                                                                      32)
    assert experts["w_gate"].shape == (2, 32, 24)          # the banks held
    assert experts["w_router"].shape == (32, 8)            # the whole router
    for name in ("w_qa", "w_qb", "w_kva", "w_kvb", "w_o"):
        assert mla[name].dtype == jnp.bfloat16
    for tree, names in ((mla, ("q_norm", "kv_norm")),
                        (experts, ("router_bias",))):
        for name in names:
            assert tree[name].dtype == jnp.float32
    assert set(shapes["transformer"]["layers_0_ff"]) == {"norm", "gate",
                                                         "up", "down"}
    presets.check_param_band("glm-flash-tiny")
    # the chip's share at the published widths: 1.146B parameters
    assert presets.preset_param_count("glm-4.7-flash") == 1_146_384_896
    presets.check_param_band("glm-4.7-flash")
    full = presets.preset_config("glm-4.7-flash")
    assert (full.seq_len, full.total_tokens) == (4352, 154880)


def test_the_preset_is_the_benchmarks_configuration():
    from benchmark import harness

    cell = harness.load_cell("glm-4.7-flash-generate")
    cfg, _ = harness.build_configs(cell.config)
    assert cfg == presets.preset_config("glm-4.7-flash")
    body = cell.config
    assert body["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert body["published"] == {"num_hidden_layers": 47,
                                 "n_routed_experts": 64}
    t = cfg.trunk
    assert (body["hidden_size"], body["num_attention_heads"],
            body["q_lora_rank"], body["kv_lora_rank"],
            body["qk_nope_head_dim"], body["qk_rope_head_dim"],
            body["v_head_dim"], body["intermediate_size"],
            body["moe_intermediate_size"], body["num_experts_per_tok"],
            body["routed_scaling_factor"], body["vocab_size"],
            body["rope_theta"], body["rms_norm_eps"],
            body["first_k_dense_replace"], body["n_shared_experts"]) == (
        cfg.dim, cfg.heads, t.q_rank, t.kv_rank, t.nope_dim, t.rope_dim,
        t.value_dim, t.ff_dim, t.expert_dim, t.experts_per_token,
        t.route_scale, cfg.total_tokens, t.rope_theta, t.norm_eps,
        t.dense_layers, t.shared_experts)
    assert (body["num_hidden_layers"], body["n_routed_experts"]) == (
        cfg.depth, t.held_experts)
    assert t.experts == body["published"]["n_routed_experts"]
    tiny = harness.build_configs(
        harness.load_cell("glm-4.7-flash-generate", rehearse=True).config)[0]
    sizes = (tiny.trunk.q_rank, tiny.trunk.kv_rank, tiny.trunk.nope_dim,
             tiny.trunk.rope_dim, tiny.trunk.value_dim)
    assert len(set(sizes)) == len(sizes)


def test_every_new_leaf_meets_a_sharding_rule(model):
    import re

    from dalle_pytorch_tpu.parallel.plan import PARTITION_RULES

    _, _, variables, _, _ = model
    flat = jax.tree_util.tree_flatten_with_path(variables["params"])[0]
    names = {"/".join(p.key for p in path): leaf for path, leaf in flat}
    new = {name: leaf for name, leaf in names.items()
           if "/mla/" in name or "/moe/" in name}
    assert len(new) == 3 * 7 + 2 * 8
    catch_all = len(PARTITION_RULES) - 1
    for name, leaf in new.items():
        if leaf.ndim < 2:
            continue                       # gains and the bias: replicated
        hit = next(i for i, (pattern, _) in enumerate(PARTITION_RULES)
                   if re.match(pattern, name))
        assert hit < catch_all, name
        assert len(PARTITION_RULES[hit][1]) == leaf.ndim, name


@pytest.mark.parametrize("plan", ["dp", "fsdp", "tp", "fsdp2.tp2"])
def test_registered_plans_place_every_leaf(model, plan):
    """``W_qa`` / ``W_kva`` replicated in (their norms want the whole width),
    ``W_qb`` / ``W_kvb`` / ``W_o`` over ``tp`` by head, the banks whole."""
    from dalle_pytorch_tpu.parallel.plan import ParallelPlan

    _, _, variables, _, _ = model
    part = ParallelPlan.parse(plan).partitioner(devices=jax.devices()[:4])
    placed = jax.device_put(variables["params"],
                            part.param_shardings(variables["params"]))
    ways = dict(part.mesh.shape)
    tp, fsdp = ways.get("tp", 1), ways.get("fsdp", 1)
    mla = placed["transformer"]["layers_1_attn"]["mla"]
    experts = placed["transformer"]["layers_1_ff"]["moe"]

    def shard(leaf):
        return leaf.sharding.shard_shape(leaf.shape)

    assert shard(mla["w_qa"]) == (32 // fsdp, 24)
    assert shard(mla["w_kva"]) == (32 // fsdp, 24)
    assert shard(mla["w_qb"]) == (24, 4 // tp, 16)
    assert shard(mla["w_kvb"]) == (20, 4 // tp, 22)
    assert shard(mla["w_o"]) == (4 // tp, 10, 32 // fsdp)
    assert shard(experts["w_gate"]) == (2, 32, 24)
    assert shard(experts["shared_gate"]) == (32 // fsdp, 24 // tp)
    for leaf in jax.tree.leaves(placed):
        assert len(leaf.sharding.device_set) == 4


# --- the bounded read, the arena ------------------------------------------------------------

def test_the_bounded_reads_buckets_cover_the_latent_cache():
    """A latent cache of 16 + 256 slots chooses among the buckets of every
    dense-read cache (``read_bounds``), and the read over the chosen prefix
    is the read over the whole cache."""
    geometry = dict(GEOMETRY, text_seq_len=16, image_fmap_size=16, depth=2)
    cfg, dalle, variables, text, codes = build(geometry=geometry)
    assert cfg.seq_len == 272 and read_bounds(272) == (128, 256, 272)
    bounds = dalle.apply(variables, method=DALLE.dense_read_bounds)
    assert bounds == [(128, 256, 272)] * 2
    first, caches = dalle.apply(variables, text, codes[:, :100],
                                method=DALLE.prefill)
    index = cfg.text_seq_len + 1 + 100          # 117: the first bucket
    for step in range(14):                      # crosses into the second
        want, _ = dalle.apply(
            variables, codes[:, 100 + step], caches, jnp.full((2,), index),
            None, jnp.int32(index), method=DALLE.decode_step)   # whole cache
        got, caches = dalle.apply(variables, codes[:, 100 + step], caches,
                                  jnp.asarray(index),
                                  method=DALLE.decode_step)
        np.testing.assert_allclose(got, want, atol=2e-5)
        index += 1
    assert index == 131
    lowered = jax.jit(lambda v, c, k, i: dalle.apply(
        v, c, k, i, method=DALLE.decode_step)).lower(
            variables, codes[:, 0], caches, jnp.asarray(3))
    assert lowered.as_text().count("stablehlo.case") == 2    # one a layer


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


def test_arena_with_latent_slots_matches_static_decode_code_for_code(served):
    """Admit, tick with an inactive slot, admit mid-flight at another depth
    (the rows then sit at different rotations), retire, re-admit into the
    freed slot: every request's codes are the static sampler's, and each
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


def test_arena_stores_latent_slots_as_the_layer_carries_them(model, tmp_path):
    from dalle_pytorch_tpu.serve.engine import SlotArena

    cfg, dalle, variables, text, _ = model
    tel = telemetry.init(str(tmp_path / "tel"))
    try:
        arena = SlotArena(dalle, variables, 3, filter_thres=1.0)
    finally:
        telemetry.shutdown()
    assert [tuple(a.shape for a in e) for e in arena.state["caches"]] == [
        ((3, cfg.seq_len, 20), (3, cfg.seq_len, 4))] * 3
    layout = arena.layout()
    assert (layout["latent_layers"], layout["plain_layers"],
            layout["folded_layers"], layout["ring_layers"],
            layout["recurrent_layers"]) == (3, 0, 0, 0, 0)
    assert layout["install_bytes_per_slot"] == 3 * cfg.seq_len * 24 * 4
    said = [e for e in telemetry.read_events(tel.path)
            if e["kind"] == "serve" and e["name"] == "arena_layout"]
    assert len(said) == 1 and said[0]["latent_layers"] == 3
    # an install rolls the prefilled latent into the slot's rotation
    first, caches = arena.prefill(text[:1])
    arena.admit(1, first, caches, jax.random.PRNGKey(0), 1.0, clock=5)
    rot = (5 - (cfg.text_seq_len + 1)) % cfg.seq_len
    np.testing.assert_array_equal(
        arena.state["caches"][2][0][1], np.roll(caches[2][0][0], rot, 0))


# --- spans and counters ------------------------------------------------------------------

def test_latent_scopes_are_siblings_of_the_attention_scopes(model):
    assert {"mla-proj", "mla-read"} <= set(prof.SCOPES)
    cfg, dalle, variables, text, codes = model
    _, caches = dalle.apply(variables, text, None, method=DALLE.prefill)
    lowered = jax.jit(lambda v, c, k, i: dalle.apply(
        v, c, k, i, method=DALLE.decode_step)).lower(
            variables, codes[:, 0], caches, jnp.asarray(cfg.text_seq_len + 1))
    text_ = lowered.as_text(debug_info=True)
    for scope in ("mla-proj", "mla-read", "attn-cache", "moe-route",
                  "moe-experts", "ff"):
        assert f"graftprof:{scope}" in text_, scope
    assert "graftprof:attn-scores" not in text_
    assert "graftprof:mla-read/graftprof:" not in text_
    # the tick decompresses no cached position: no product's operand is as
    # long as the cache and as wide as a head's keys and values
    plain = lowered.as_text()
    assert f"tensor<2x{cfg.seq_len}x4x22x" not in plain
    assert f"tensor<2x4x{cfg.seq_len}x22x" not in plain


def test_traces_report_the_latent_cache_and_the_routing(model, tmp_path):
    cfg, dalle, variables, text, codes = model
    cfg = dataclasses.replace(cfg, kv_cache_bf16=True)
    dalle = DALLE(cfg)
    tel = telemetry.init(str(tmp_path / "tel"))
    metrics.init()
    try:
        jax.jit(lambda v, t, k: generate_codes(
            dalle, v, t, k, filter_thres=0.9)).lower(
                variables, text, jax.random.PRNGKey(0))
        dalle.apply(variables, text, codes)
        rendered = metrics.active().render()
    finally:
        telemetry.shutdown()
        metrics.shutdown()
    events = telemetry.read_events(tel.path)

    def last(kind, name):
        found = [e for e in events if e["kind"] == kind and e["name"] == name]
        assert found, (kind, name)
        return found[-1]

    state = last("decode", "state_layout")
    assert (state["latent_layers"], state["kv_layers"], state["ssm_layers"],
            state["linear_layers"]) == (3, 0, 0, 0)
    assert state["latent_bytes_per_position"] == (20 + 4) * 2
    assert state["latent_bytes_walked_per_position"] == 2 * 128 * 2
    assert state["state_bytes_per_row"] == 3 * cfg.seq_len * 24 * 2
    layout = last("decode", "kv_layout")
    assert (layout["kv_latent_layers"], layout["kv_plain_layers"],
            layout["kv_lane_dense_layers"]) == (3, 0, 0)
    reach = last("decode", "kv_reach")
    assert reach["unbounded_layers"] == 3 and reach["read_share"] == 1.0
    routed = last("decode", "moe_layout")
    assert (routed["layers"], routed["scoring"], routed["experts"],
            routed["experts_held"], routed["shared_experts"]) == (
        2, "sigmoid", 8, 2, 1)
    assert routed["expert_bytes_per_layer"] == 3 * 2 * 32 * 24 * 4
    route = last("moe", "route")
    assert (route["layers"], route["scoring"], route["experts_held"],
            route["shared_experts"], route["k"]) == (2, "sigmoid", 2, 1, 2)
    kernel = last("attention", "kernel")
    assert (kernel["latent_layers"], kernel["dense_layers"],
            kernel["flash_layers"]) == (3, 0, 0)
    # a latent 20 wide is no whole lane tile: the plain read, in two passes
    assert (kernel["latent_read"], kernel["block"]) == ("two_pass", 0)
    for line in ("graft_decode_latent_layers 3", "graft_decode_kv_layers 0",
                 "graft_decode_moe_layers 2", "graft_attn_latent_layers 3"):
        assert line in rendered, line
    report = render_text(build_report(events))
    assert ("latent cache: 3 layers, 48 bytes a position (512 as stored); "
            "read in two passes" in report)
    assert "routed experts: 2 layers of 8, 2 a token" in report
    assert "sigmoid scores, 2 held, 1 shared" in report


def test_traces_report_the_one_pass_read_where_the_shapes_take_it(tmp_path):
    """A twin whose latent fills a lane tile (128 + 64 rotary values), over
    192 + 24 x 24 = 768 bfloat16 slots: the records describe the kernel's
    walk (ops/latent_attention.py::one_pass_read): blocks of 256 positions,
    their ends as the prefixes of ``decode.kv_reach``, the fold's bytes."""
    trunk = dict(TRUNK, kv_rank=128, rope_dim=64)
    geometry = dict(GEOMETRY, depth=2, text_seq_len=192, image_size=192,
                    image_fmap_size=24)
    cfg = DALLEConfig(trunk=trunk, dtype=jnp.bfloat16, **geometry)
    dalle = DALLE(cfg)
    text = jnp.ones((2, cfg.text_seq_len), jnp.int32)
    codes = jnp.zeros((2, cfg.image_seq_len), jnp.int32)
    variables = jax.eval_shape(dalle.init, jax.random.PRNGKey(0), text, codes)
    tel = telemetry.init(str(tmp_path / "tel"))
    try:
        jax.jit(lambda v, t, k: generate_codes(
            dalle, v, t, k, filter_thres=0.9)).lower(
                variables, text, jax.random.PRNGKey(0))
        jax.eval_shape(lambda v: dalle.apply(v, text, codes), variables)
    finally:
        telemetry.shutdown()
    events = telemetry.read_events(tel.path)

    def last(kind, name):
        return [e for e in events
                if e["kind"] == kind and e["name"] == name][-1]

    kernel = last("attention", "kernel")
    assert (kernel["latent_layers"], kernel["latent_read"],
            kernel["block"]) == (2, "one_pass", 256)
    reach = last("decode", "kv_reach")
    assert (reach["bounded_layers"], reach["unbounded_layers"],
            reach["buckets"]) == (2, 0, 6)
    # ticks at positions 193..767 read the blocks that hold them
    ticks = np.arange(cfg.text_seq_len + 1, cfg.seq_len)
    assert reach["read_share"] == pytest.approx(
        (256 * (ticks // 256 + 1)).mean() / 768)
    state = last("decode", "state_layout")
    assert state["latent_bytes_per_position"] == (128 + 64) * 2
    assert state["latent_bytes_walked_per_position"] == (128 + 64) * 2
    report = render_text(build_report(events))
    assert ("latent cache: 2 layers, 384 bytes a position (384 as stored); "
            "read in one pass, 256 positions a block" in report)
    assert "kv cache reach: 2 layers' dense reads bounded by the position " \
        "(6 prefixes)" in report
