"""Ulysses (all-to-all) sequence parallelism vs single-device dense
attention, on the same 8-virtual-CPU-device meshes as the ring tests."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dalle_pytorch_tpu.ops.attention import AttnPattern
from dalle_pytorch_tpu.parallel.ulysses import ulysses_attention_sharded

from attention_refs import dense_reference

TEXT, FMAP = 8, 4
N = TEXT + FMAP * FMAP  # 24 -> 3 per device on sp=8
B, H, DH = 2, 8, 8      # H=8: divisible by every sp size used below


@pytest.fixture(scope="module")
def mesh8():
    devices = np.asarray(jax.devices()[:8]).reshape(1, 8)
    return Mesh(devices, ("dp", "sp"))


@pytest.fixture(scope="module")
def mesh2x4():
    devices = np.asarray(jax.devices()[:8]).reshape(2, 4)
    return Mesh(devices, ("dp", "sp"))


def rand_qkv(key):
    ks = jax.random.split(key, 3)
    return tuple(jax.random.normal(k, (B, H, N, DH)) for k in ks)


@pytest.mark.parametrize("causal", [
    True, pytest.param(False, marks=pytest.mark.slow)])
def test_ulysses_matches_dense(mesh8, causal):
    q, k, v = rand_qkv(jax.random.PRNGKey(0))
    out = ulysses_attention_sharded(q, k, v, mesh8, causal=causal)
    ref = dense_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# one representative pattern stays in the fast tier ("sparse": the
# most irregular predicate); the rest of the sweep is nightly-only
@pytest.mark.parametrize("variant", [
    pytest.param("full", marks=pytest.mark.slow),
    pytest.param("axial_row", marks=pytest.mark.slow),
    pytest.param("axial_col", marks=pytest.mark.slow),
    pytest.param("conv_like", marks=pytest.mark.slow),
    "sparse",
])
def test_ulysses_with_patterns(mesh8, variant):
    pattern = AttnPattern(variant=variant, seq_len=N - 1, text_len=TEXT,
                          fmap=FMAP)
    q, k, v = rand_qkv(jax.random.PRNGKey(1))
    out = ulysses_attention_sharded(q, k, v, mesh8, pattern=pattern)
    ref = dense_reference(q, k, v, pattern=pattern)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_dp_times_sp(mesh2x4):
    """dp=2 x sp=4: batch and sequence sharded simultaneously."""
    q, k, v = rand_qkv(jax.random.PRNGKey(2))
    out = ulysses_attention_sharded(q, k, v, mesh2x4)
    ref = dense_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_ulysses_gradients(mesh8):
    q, k, v = rand_qkv(jax.random.PRNGKey(3))
    tangent = jax.random.normal(jax.random.PRNGKey(4), q.shape)

    def loss_ulysses(q, k, v):
        return jnp.sum(ulysses_attention_sharded(q, k, v, mesh8) * tangent)

    def loss_dense(q, k, v):
        return jnp.sum(dense_reference(q, k, v) * tangent)

    g_u = jax.grad(loss_ulysses, argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gu, gd in zip(g_u, g_d):
        np.testing.assert_allclose(np.asarray(gu), np.asarray(gd),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_transformer_ulysses_matches_local(mesh2x4):
    """A Transformer stack under shard_map with sp_impl='ulysses' matches
    the same stack run unsharded."""
    from dalle_pytorch_tpu.ops.transformer import Transformer

    dim = 32
    common = dict(dim=dim, depth=2, seq_len=N - 1, causal=True, heads=H,
                  dim_head=DH, attn_types=("full", "axial_row"),
                  image_fmap_size=FMAP, text_len=TEXT)
    tf_sp = Transformer(**common, ring_axis="sp", sp_impl="ulysses")
    tf_local = Transformer(**common)

    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (2, N, dim))
    params = tf_local.init(jax.random.PRNGKey(1), x)["params"]
    ref = tf_local.apply({"params": params}, x)

    spec = P("dp", "sp", None)
    fn = shard_map(
        lambda p, x: tf_sp.apply({"params": p}, x),
        mesh=mesh2x4, in_specs=(P(), spec), out_specs=spec, check_vma=False)
    with mesh2x4:
        out = fn(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
