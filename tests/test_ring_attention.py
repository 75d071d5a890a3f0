"""Ring attention (sequence parallelism) vs single-device dense attention.

The TPU-native analog of multi-node testing without a cluster (SURVEY.md
§4): an 8-virtual-CPU-device mesh with the sequence sharded over 'sp'.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dalle_pytorch_tpu.ops.attention import AttnPattern
from dalle_pytorch_tpu.parallel.ring import ring_attention_sharded

from attention_refs import dense_reference

TEXT, FMAP = 8, 4
N = TEXT + FMAP * FMAP  # 24 -> 3 per device on sp=8
B, H, DH = 2, 2, 8


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
def test_ring_matches_dense(mesh8, causal):
    q, k, v = rand_qkv(jax.random.PRNGKey(0))
    out = ring_attention_sharded(q, k, v, mesh8, causal=causal)
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
def test_ring_with_patterns(mesh8, variant):
    pattern = AttnPattern(variant=variant, seq_len=N - 1, text_len=TEXT,
                          fmap=FMAP)
    q, k, v = rand_qkv(jax.random.PRNGKey(1))
    out = ring_attention_sharded(q, k, v, mesh8, pattern=pattern)
    ref = dense_reference(q, k, v, pattern=pattern)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_dp_times_sp(mesh2x4):
    """dp=2 x sp=4: batch and sequence sharded simultaneously."""
    q, k, v = rand_qkv(jax.random.PRNGKey(2))
    out = ring_attention_sharded(q, k, v, mesh2x4)
    ref = dense_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_ring_gradients(mesh8):
    q, k, v = rand_qkv(jax.random.PRNGKey(3))
    tangent = jax.random.normal(jax.random.PRNGKey(4), q.shape)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, mesh8) * tangent)

    def loss_dense(q, k, v):
        return jnp.sum(dense_reference(q, k, v) * tangent)

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gr, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"d{name} mismatch")


def test_transformer_sequence_parallel(mesh8):
    """A full Transformer stack under shard_map with ring_axis='sp' equals
    the plain single-device stack: attention rides the ring, everything else
    is position-wise."""
    from dalle_pytorch_tpu.ops.transformer import Transformer

    dim = 16
    common = dict(dim=dim, depth=2, seq_len=N - 1, causal=True, heads=2,
                  dim_head=8, attn_types=("full", "axial_row"),
                  image_fmap_size=FMAP, text_len=TEXT)
    dense_tf = Transformer(**common)
    ring_tf = Transformer(**common, ring_axis="sp")

    x = jax.random.normal(jax.random.PRNGKey(6), (B, N, dim))
    params = dense_tf.init(jax.random.PRNGKey(7), x)["params"]

    ref = dense_tf.apply({"params": params}, x)

    spec = P(None, "sp", None)
    sp_apply = shard_map(
        lambda p, x: ring_tf.apply({"params": p}, x),
        mesh=mesh8, in_specs=(P(), spec), out_specs=spec, check_vma=False)
    out = jax.jit(sp_apply)(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_under_jit(mesh8):
    """jit-compiled, sharded inputs — the production usage shape."""
    from jax.sharding import NamedSharding

    q, k, v = rand_qkv(jax.random.PRNGKey(5))
    sharding = NamedSharding(mesh8, P(None, None, "sp", None))
    q, k, v = (jax.device_put(t, sharding) for t in (q, k, v))

    fn = jax.jit(lambda q, k, v: ring_attention_sharded(q, k, v, mesh8))
    out = fn(q, k, v)
    ref = dense_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
