"""The delta rule's tick in one pass (PERF.md, Findings): the Pallas
kernel of ops/linear_attention_pallas.py run through the interpreter on the
CPU and held to ``gated_delta_step``'s ``jnp`` form, once and over a chain of
steps fed back through it; the predicate that says which states take it; the
state's buffer aliased to the updated state in its lowering for the TPU; and
the kernel kept beside the compile cache.

The kernel is compiled for a described v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dalle_pytorch_tpu.ops import kept  # noqa: E402
from dalle_pytorch_tpu.ops import linear_attention as la  # noqa: E402
from dalle_pytorch_tpu.ops.linear_attention_pallas import delta_step  # noqa: E402


def operands(rows, heads, dk, dv, seed=0):
    """A carried state and one position's inputs, as the mixer makes them:
    unit keys, queries scaled by ``d_k^-0.5``, ``g <= 0``, ``beta`` in (0,
    2)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    S = la.fold_state(jax.random.normal(ks[0], (rows, heads, dk, dv)))
    q = la.l2_norm(jax.random.normal(ks[1], (rows, heads, dk)), 1e-6)
    k = la.l2_norm(jax.random.normal(ks[2], (rows, heads, dk)), 1e-6)
    g = -jax.random.uniform(ks[4], (rows, heads), minval=0.01, maxval=1.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[5], (rows, heads)))
    return (S, q * dk ** -0.5, k, jax.random.normal(ks[3], (rows, heads, dv)),
            g, beta)


#: float32 on both sides, in the same order of operations but for the sums
#: over ``d_k`` (the kernel's a sublane tile at a time)
TOL = dict(rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("rows,heads,dk,dv", [
    (2, 30, 96, 192),         # the cell's heads: 15 groups of two
    (4, 4, 96, 64),           # two groups of two heads
    (2, 3, 16, 128),          # one head a group, one lane tile
    (3, 2, 8, 256),           # one head a group, two lane tiles
], ids=["cell-heads", "four-heads", "fold-1", "fold-1-two-lane-tiles"])
def test_the_kernel_is_the_jnp_step(rows, heads, dk, dv):
    args = operands(rows, heads, dk, dv)
    o, S = delta_step(*args, interpret=True)
    o_want, S_want = la._plain_step(*args)
    assert o.shape == (rows, heads, dv) and o.dtype == jnp.float32
    assert S.shape == args[0].shape and S.dtype == jnp.float32
    np.testing.assert_allclose(o, o_want, **TOL)
    np.testing.assert_allclose(S, S_want, **TOL)


def test_a_chain_of_steps_does_not_drift():
    """32 positions, each step's state fed to the next, through the kernel
    and through the ``jnp`` form."""
    steps = 32
    S, *_ = operands(2, 4, 96, 64)
    inputs = [jnp.stack(a) for a in zip(*(operands(2, 4, 96, 64, seed=t)[1:]
                                          for t in range(steps)))]

    def chain(step):
        def tick(S, x):
            o, S = step(S, *x)
            return S, o
        return jax.lax.scan(tick, S, inputs)

    S_got, o_got = chain(functools.partial(delta_step, interpret=True))
    S_want, o_want = chain(la._plain_step)
    np.testing.assert_allclose(o_got, o_want, **TOL)
    np.testing.assert_allclose(S_got, S_want, **TOL)


@pytest.mark.parametrize("heads,dk,dv,dtype,want", [
    (30, 96, 192, jnp.float32, True),       # the cell: two heads a group
    (4, 96, 64, jnp.float32, True),
    (8, 96, 128, jnp.float32, True),        # one head a whole lane tile
    (30, 96, 192, jnp.bfloat16, False),     # a bfloat16 state
    (4, 8, 16, jnp.float32, False),         # the tiny twin: fold 1, 16 lanes
    (3, 96, 192, jnp.float32, False),       # an odd head count: fold 1
    (4, 12, 64, jnp.float32, False),        # d_k off the sublane tiles
], ids=["cell", "four-heads", "fold-1-whole-tile", "bfloat16", "twin",
        "odd-heads", "dk-12"])
def test_the_predicate_table(heads, dk, dv, dtype, want):
    assert la.one_pass_step(heads, dk, dv, dtype) is want


def test_the_state_operand_is_the_updated_states_buffer():
    """Lowered for the TPU (``jax.export``, from this CPU host), the kernel's
    one custom call hands its fourth operand, the state, to its second
    result: the update is made in place."""
    avals = [jax.ShapeDtypeStruct(a.shape, a.dtype)
             for a in operands(2, 4, 96, 64)]
    text = jax.export.export(
        jax.jit(delta_step),
        platforms=("tpu",))(*avals).mlir_module()
    calls = [line for line in text.splitlines()
             if "stablehlo.custom_call @tpu_custom_call" in line]
    assert len(calls) == 1
    assert re.findall(r"output_operand_alias<([^>]*)>", calls[0]) == [
        "output_tuple_indices = [1], operand_index = 3, "
        "operand_tuple_indices = []"]


def test_the_step_keeps_its_kernel_beside_the_compile_cache(tmp_path):
    """With a compile cache the kernel is written as a ``jax.export`` file
    and read back by a later trace; the step binds it as one opaque
    operation in its TPU arm and, on the CPU, returns the ``jnp`` form's
    result."""
    args = operands(2, 4, 96, 64)
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.clear_caches()
    try:
        text = str(jax.make_jaxpr(la.gated_delta_step)(*args))
        assert text.count("kept_kernel") == 1
        assert "call_exported" not in text and "pallas_call" not in text
        kept_files = [p.name.split("-")[:2] for p in tmp_path.iterdir()
                      if p.suffix == ".jaxexport"]
        assert kept_files == [["linear", "delta_step"]]
        kept.exported.cache_clear()                 # a later process
        got = jax.jit(la.gated_delta_step)(*args)
        for a, b in zip(got, jax.jit(la._plain_step)(*args)):
            np.testing.assert_array_equal(a, b)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.clear_caches()


def test_a_state_the_predicate_refuses_never_meets_the_kernel():
    args = operands(2, 4, 8, 16)           # the tiny twin
    text = str(jax.make_jaxpr(la.gated_delta_step)(*args))
    assert "platform_index" not in text and "kept_kernel" not in text
