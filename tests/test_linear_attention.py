"""The gated delta rule (ops/linear_attention.py; PERF.md, Findings PR 34):
its two forms against each other and against the sequential scan of
``benchmark/reference_olmo_hybrid_7b.py`` (which imports nothing from the
program), the carried state's layout, the mixer layer's two calls, the
convolution it shares with ``ops/ssm.py``, and the two norms the family adds
around its sublayers, each against a hand-written line of ``jnp``.

Tiny widths, seeded inputs, float32, on the CPU.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from benchmark import reference_olmo_hybrid_7b as reference  # noqa: E402
from dalle_pytorch_tpu.ops import linear_attention as la  # noqa: E402
from dalle_pytorch_tpu.ops import ssm  # noqa: E402
from dalle_pytorch_tpu.ops.attention import (  # noqa: E402
    AttnPattern, MultiHeadAttention)
from dalle_pytorch_tpu.ops.transformer import (  # noqa: E402
    Transformer, TrunkSpec, is_recurrent, layer_cache_lens, layer_mixers)


def _rule_inputs(n, b=2, h=4, dk=8, dv=64, seed=0, beta=(0.0, 2.0)):
    r = np.random.default_rng(seed)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    q = la.l2_norm(f32(r.normal(size=(b, n, h, dk))), 1e-6) * dk ** -0.5
    k = la.l2_norm(f32(r.normal(size=(b, n, h, dk))), 1e-6)
    v = f32(r.normal(size=(b, n, h, dv)))
    g = -f32(r.uniform(0.01, 1.0, size=(b, n, h)))
    return q, k, v, g, f32(r.uniform(*beta, size=(b, n, h)))


def _stepwise(q, k, v, g, beta, S=None):
    b, n, h, dk = k.shape
    if S is None:
        S = la.fold_state(jnp.zeros((b, h, dk, v.shape[-1])))
    outs = []
    for t in range(n):
        o, S = la.gated_delta_step(S, q[:, t], k[:, t], v[:, t], g[:, t],
                                   beta[:, t])
        outs.append(o)
    return jnp.stack(outs, 1), S


# --- the rule's two forms and the reference ------------------------------------

@pytest.mark.parametrize("n,chunk", [(7, 64), (8, 4), (13, 4), (5, 1)])
@pytest.mark.parametrize("h,dv", [(4, 64), (3, 16)])   # two heads a lane
def test_step_rule_and_reference_agree(n, chunk, h, dv):  # tile, and one
    """A sequence inside one chunk, chunks that divide it, chunks that do
    not (the padded tail leaves the state alone), chunks of one; in both
    layouts of the carried state."""
    args = _rule_inputs(n, h=h, dv=dv)
    want, S_want = reference.delta_rule(*args, jnp.float32)
    o_rule, S_rule = la.gated_delta_rule(*args, chunk=chunk)
    o_step, S_step = _stepwise(*args)
    np.testing.assert_allclose(o_rule, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(o_step, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(S_rule, S_step, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(la.unfold_state(S_step, h), S_want,
                               rtol=1e-5, atol=1e-6)
    assert S_rule.shape == (2, h // la.state_fold(h, dv), 8,
                            la.state_fold(h, dv) * dv)


@pytest.mark.parametrize("beta", [(1.0, 2.0), (0.0, 1.0)])
def test_rule_holds_for_writes_stronger_than_one(beta):
    """``beta`` in (1, 2) (``linear_allow_neg_eigval``) flips the sign of
    what a key read before: the same three agree, and the state stays
    bounded (``I - beta k k^T`` has eigenvalues in (-1, 1])."""
    args = _rule_inputs(40, beta=beta)
    want = reference.delta_rule(*args, jnp.float32)[0]
    o_rule, S = la.gated_delta_rule(*args, chunk=16)
    np.testing.assert_allclose(o_rule, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(_stepwise(*args)[0], want, rtol=1e-4,
                               atol=1e-5)
    assert float(jnp.abs(S).max()) < 10.0


def test_rule_continues_from_a_carried_state():
    args = _rule_inputs(11)
    o_all, S_all = la.gated_delta_rule(*args, chunk=4)
    head = [a[:, :6] for a in args]
    tail = [a[:, 6:] for a in args]
    _, S_mid = la.gated_delta_rule(*head, chunk=4)
    o_tail, S_end = la.gated_delta_rule(*tail, S0=S_mid, chunk=4)
    np.testing.assert_allclose(o_tail, o_all[:, 6:], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(S_end, S_all, rtol=1e-5, atol=1e-6)
    o_step, S_step = _stepwise(*tail, S=S_mid)
    np.testing.assert_allclose(o_step, o_all[:, 6:], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(S_step, S_all, rtol=1e-5, atol=1e-6)


def test_rule_is_differentiable_like_the_stepwise_one():
    args = _rule_inputs(9)

    def loss(fn, *a):
        o, S = fn(*a)
        return (o ** 2).sum() + S.sum()

    got = jax.grad(lambda *a: loss(
        lambda *b: la.gated_delta_rule(*b, chunk=4), *a),
        argnums=range(5))(*args)
    want = jax.grad(lambda *a: loss(_stepwise, *a), argnums=range(5))(*args)
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_state_stays_float32_whatever_the_inputs_dtype():
    args = [a.astype(jnp.bfloat16) for a in _rule_inputs(6)]
    o, S = la.gated_delta_rule(*args)
    assert o.dtype == S.dtype == jnp.float32
    o, S2 = la.gated_delta_step(S, *(a[:, 0] for a in args))
    assert o.dtype == S2.dtype == jnp.float32 and S2.shape == S.shape


@pytest.mark.parametrize("heads,dv,fold", [
    (30, 192, 2), (4, 64, 2), (4, 16, 1), (3, 192, 1), (8, 128, 1),
    (8, 32, 4)])
def test_state_layout_fills_the_lanes_where_the_heads_allow(heads, dv, fold):
    assert la.state_fold(heads, dv) == fold
    S = jnp.arange(2 * heads * 3 * dv, dtype=jnp.float32).reshape(
        2, heads, 3, dv)
    folded = la.fold_state(S)
    assert folded.shape == (2, heads // fold, 3, fold * dv)
    assert fold == 1 or folded.shape[-1] % la.LANES == 0
    np.testing.assert_array_equal(la.unfold_state(folded, heads), S)
    # head g * fold + f's columns are lanes [f * dv, (f + 1) * dv) of group g
    np.testing.assert_array_equal(folded[:, 0, :, (fold - 1) * dv:],
                                  S[:, fold - 1])


# --- the mixer layer -----------------------------------------------------------------

def _mixer(heads=4, dk=8, dv=64):
    mixer = la.GatedDeltaMixer(dim=32, heads=heads, key_dim=dk, value_dim=dv)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 32))
    variables = mixer.init(jax.random.PRNGKey(1), x)
    return mixer, variables, x


def test_mixer_has_the_layers_leaves_and_their_initial_values():
    mixer, variables, _ = _mixer()
    p = variables["params"]
    shapes = jax.tree.map(lambda a: a.shape, p)
    assert shapes == {
        "q_proj": {"kernel": (32, 4, 8)}, "k_proj": {"kernel": (32, 4, 8)},
        "v_proj": {"kernel": (32, 4, 64)}, "g_proj": {"kernel": (32, 4, 64)},
        "a_proj": {"kernel": (32, 4)}, "b_proj": {"kernel": (32, 4)},
        "conv_q": (4, 4, 8), "conv_k": (4, 4, 8), "conv_v": (4, 4, 64),
        "A_log": (4,), "dt_bias": (4,), "o_norm": (64,),
        "o_proj": {"kernel": (4, 64, 32)}}
    rate = np.exp(np.asarray(p["A_log"]))
    assert (rate > 0).all() and (rate <= 16).all()
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert dt.min() >= 1e-3 * 0.99 and dt.max() <= 1e-1 * 1.01
    np.testing.assert_array_equal(p["o_norm"], 1.0)
    window, S = mixer.apply(variables, 5, method=mixer.init_state)
    assert window.shape == (5, 3, 4 * (8 + 8 + 64)) and S.shape == (
        5, 2, 8, 128) and S.dtype == jnp.float32


def test_mixer_decode_steps_match_its_sequence_form_and_its_state():
    mixer, variables, x = _mixer()
    out, (window, S) = mixer.apply(variables, x, return_state=True)
    np.testing.assert_allclose(mixer.apply(variables, x), out)
    w, s = mixer.apply(variables, 2, method=mixer.init_state)
    for t in range(x.shape[1]):
        step, w, s = mixer.apply(variables, x[:, t:t + 1], w, s,
                                 method=mixer.decode_step)
        np.testing.assert_allclose(step[:, 0], out[:, t], rtol=1e-4,
                                   atol=1e-5)
    np.testing.assert_allclose(w, window, rtol=1e-6)
    np.testing.assert_allclose(s, S, rtol=1e-4, atol=1e-6)
    assert float(jnp.abs(S).max()) > 1e-3        # a state worth carrying


def test_three_convolutions_run_as_one_over_the_shared_operator():
    """``ops/ssm.py::causal_conv`` over the q, k and v channels side by side
    is the three depthwise convolutions the layer defines, and its window is
    the last three positions of all of them: one leaf for the decode state."""
    mixer, variables, x = _mixer()
    p = variables["params"]
    proj = {n: jnp.einsum("bnd,dhe->bnhe", x, p[f"{n}_proj"]["kernel"])
            for n in "qkv"}
    want = [reference._conv(proj[n], p[f"conv_{n}"]) for n in "qkv"]
    flat = lambda a: a.reshape(a.shape[:-2] + (-1,))  # noqa: E731
    taps = jnp.concatenate([p[f"conv_{n}"].reshape(4, -1) for n in "qkv"], -1)
    got, window = ssm.causal_conv(
        jnp.concatenate([flat(proj[n]) for n in "qkv"], -1), taps, 0.0)
    np.testing.assert_allclose(
        got, jnp.concatenate([flat(w) for w in want], -1), rtol=1e-5,
        atol=1e-6)
    _, (carried, _) = mixer.apply(variables, x, return_state=True)
    np.testing.assert_allclose(carried, window, rtol=1e-6)
    np.testing.assert_allclose(
        carried, jnp.concatenate([flat(proj[n]) for n in "qkv"], -1)[:, -3:],
        rtol=1e-6)
    step, rolled = ssm.causal_conv_step(
        jnp.zeros((2, taps.shape[1])), taps, 0.0, window)
    assert rolled.shape == window.shape
    np.testing.assert_array_equal(rolled[:, :2], window[:, 1:])


def test_mixer_matches_the_reference_layer():
    mixer, variables, x = _mixer()
    want = reference._linear_attention({"gdn": variables["params"]}, x, 1e-6,
                                       jnp.float32, None)
    np.testing.assert_allclose(mixer.apply(variables, x), want, rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("form", ["sequence", "step"])
def test_mixer_sows_what_its_rule_is_given(form):
    """``rule_inputs`` under ``intermediates`` (nothing unless the caller
    makes the collection mutable): the reference's sequential rule over them
    leaves the state the mixer returns, in both of its forms."""
    mixer, variables, x = _mixer()
    if form == "sequence":
        (_, (_, S)), sown = mixer.apply(variables, x, return_state=True,
                                        mutable=["intermediates"])
        given = sown["intermediates"]["rule_inputs"][0]
    else:
        window, S = mixer.init_state(2)
        given = []
        for t in range(x.shape[1]):
            (_, window, S), sown = mixer.apply(
                variables, x[:, t:t + 1], window, S,
                method=la.GatedDeltaMixer.decode_step,
                mutable=["intermediates"])
            given.append(sown["intermediates"]["rule_inputs"][0])
        given = tuple(jnp.stack(a, axis=1) for a in zip(*given))
    assert [a.shape for a in given] == [
        (2, 9, 4, 8), (2, 9, 4, 8), (2, 9, 4, 64), (2, 9, 4), (2, 9, 4)]
    want = reference.delta_rule(*given)[1]
    np.testing.assert_allclose(la.unfold_state(S, 4), want, rtol=1e-5,
                               atol=1e-6)
    assert "intermediates" not in mixer.apply(variables, x,
                                              mutable=["losses"])[1]


# --- the two norms the family adds ------------------------------------------------------

def test_qk_norm_is_an_rms_norm_over_the_whole_projection():
    n, dim, heads, dh = 6, 16, 4, 8
    kw = dict(pattern=AttnPattern("full", seq_len=n, text_len=2, fmap=0),
              dim=dim, heads=heads, dim_head=dh, kv_heads=2, use_bias=False)
    attn = MultiHeadAttention(qk_norm=True, **kw)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, n, dim))
    variables = attn.init(jax.random.PRNGKey(1), x)
    p = variables["params"]
    assert p["q_norm"].shape == (heads * dh,) and p["k_norm"].shape == (
        2 * dh,)
    p = dict(p, q_norm=p["q_norm"] + 0.3, k_norm=p["k_norm"] - 0.2)
    q, k, v = attn.apply({"params": p}, x, method=attn._qkv)
    plain = MultiHeadAttention(**kw)
    bare = {n_: p[n_] for n_ in ("to_q", "to_kv", "to_out")}
    q0, k0, v0 = plain.apply({"params": bare}, x, method=plain._qkv)

    def hand(a, gain):      # [b, h, n, d] -> the norm over all heads' width
        flat = a.transpose(0, 2, 1, 3).reshape(2, n, -1)
        flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                    + 1e-6) * gain
        return flat.reshape(2, n, a.shape[1], dh).transpose(0, 2, 1, 3)

    np.testing.assert_allclose(q, hand(q0, p["q_norm"]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(k, hand(k0, p["k_norm"]), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(v, v0)
    # the cache holds the normed keys: a decode step reads what __call__ did
    out, (ck_full, _) = attn.apply({"params": p}, x, return_kv=True)
    np.testing.assert_allclose(ck_full, k, rtol=1e-6)
    ck = jnp.zeros((2, 2, n, dh))
    cv = jnp.zeros_like(ck)
    for t in range(n):
        step, ck, cv = attn.apply({"params": p}, x[:, t:t + 1], ck, cv,
                                  jnp.asarray(t), method=attn.decode_step)
        np.testing.assert_allclose(step[:, 0], out[:, t], rtol=1e-4,
                                   atol=1e-5)
    np.testing.assert_allclose(ck, k, rtol=1e-5, atol=1e-6)
    with pytest.raises(AssertionError):
        MultiHeadAttention(qk_norm=True, **dict(kw, kv_heads=None)).init(
            jax.random.PRNGKey(1), x)


@pytest.mark.parametrize("mixer", ["attention", "gdn"])
def test_output_norm_closes_each_sublayer(mixer):
    """``norm_at = "output"``: ``h1 = h + Norm(Mixer(h))``, ``out = h1 +
    Norm(FF(h1))``, the sublayers on the un-normed stream, against the
    blocks applied by hand."""
    spec = TrunkSpec(mixers=(mixer,), ff_dim=24, kv_heads=2,
                     norm_at="output", lin_key_dim=4 * (mixer == "gdn"),
                     lin_value_dim=8 * (mixer == "gdn"), param_dtype="float32")
    model = Transformer(dim=16, depth=1, seq_len=6, heads=2, dim_head=8,
                        text_len=3, trunk=spec)
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (2, 6, 16))
    variables = model.init(jax.random.PRNGKey(1), x)
    p = jax.tree.map(lambda a: a + 0.1 * jax.random.normal(
        jax.random.PRNGKey(a.size), a.shape), variables["params"])
    name = "layers_0_gdn" if mixer == "gdn" else "layers_0_attn"
    assert set(p) == {name, "layers_0_mixer_norm", "layers_0_ff",
                      "layers_0_ff_norm"}
    assert "norm" not in p[name] and "norm" not in p["layers_0_ff"]

    def rms(a, gain):
        return a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True)
                                 + 1e-6) * gain

    if mixer == "gdn":
        mixed = la.GatedDeltaMixer(dim=16, heads=2, key_dim=4,
                                   value_dim=8).apply(
            {"params": p[name]["gdn"]}, x)
    else:
        mixed = MultiHeadAttention(
            pattern=AttnPattern("full", seq_len=6, text_len=3, fmap=0),
            dim=16, heads=2, dim_head=8, kv_heads=2, use_bias=False).apply(
                {"params": p[name]["attn"]}, x)
    h1 = x + rms(mixed, p["layers_0_mixer_norm"]["scale"])
    ff = p["layers_0_ff"]
    want = h1 + rms((jax.nn.silu(h1 @ ff["gate"]["kernel"])
                     * (h1 @ ff["up"]["kernel"])) @ ff["down"]["kernel"],
                    p["layers_0_ff_norm"]["scale"])
    np.testing.assert_allclose(model.apply({"params": p}, x), want,
                               rtol=1e-4, atol=1e-5)
    # the same trunk with the norm on the inputs is another function
    pre = Transformer(dim=16, depth=1, seq_len=6, heads=2, dim_head=8,
                      text_len=3, trunk=TrunkSpec(**{
                          **{f.name: getattr(spec, f.name)
                             for f in spec.__dataclass_fields__.values()},
                          "norm_at": "input"}))
    pre_vars = pre.init(jax.random.PRNGKey(1), x)
    assert set(pre_vars["params"]) == {name, "layers_0_ff"}
    assert "norm" in pre_vars["params"][name]


# --- the predicate -----------------------------------------------------------------------

def test_one_predicate_names_the_recurrent_kinds():
    assert [is_recurrent(k) for k in ("attention", "window", "mamba",
                                      "gdn")] == [False, False, True, True]
    spec = TrunkSpec(mixers=("gdn", "attention"), ff_dim=8, lin_key_dim=4,
                     lin_value_dim=8)
    assert layer_mixers(spec, 4) == ("gdn", "attention", "gdn", "attention")
    assert layer_cache_lens(spec, 4, 20) == (0, 20, 0, 20)
