"""Contract-checker tests: the chip-free invariants hold on the clean tree
(tiny geometry, eval_shape/jaxpr only — seconds on CPU), and — the part
that proves the checker has teeth — deliberately broken models ARE caught:
a prefill whose caches ignore kv_cache_bf16, a decode step that upcasts
the full cache to f32 (PR 1's measured XLA-hoist failure mode), and an
attn@v contraction that drops the f32-accumulation contract."""
from __future__ import annotations

import dataclasses
import importlib.util
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dalle_pytorch_tpu import DALLE  # noqa: E402
from dalle_pytorch_tpu.models import dalle as dalle_mod  # noqa: E402
from dalle_pytorch_tpu.ops.attention import MultiHeadAttention  # noqa: E402


def _load_cc():
    spec = importlib.util.spec_from_file_location(
        "contract_check", REPO / "tools" / "contract_check.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cc():
    return _load_cc()


# --- clean tree: the contracts hold --------------------------------------


@pytest.mark.parametrize("kv_bf16", [True, False])
def test_cache_dtype_contract_holds(cc, kv_bf16):
    cc.check_cache_dtype(cc.tiny_config(kv_cache_bf16=kv_bf16))


def test_bf16_model_cache_is_bf16(cc):
    cc.check_cache_dtype(cc.tiny_config(dtype=jnp.bfloat16,
                                        kv_cache_bf16=False))


@pytest.mark.parametrize("kv_bf16", [True, False])
def test_decode_jaxpr_contracts_hold(cc, kv_bf16):
    cfg = cc.tiny_config(kv_cache_bf16=kv_bf16)
    cc.check_decode_dots_accumulate_f32(cfg)
    cc.check_no_f32_cache_materialization(cfg)


@pytest.mark.parametrize("strategy", ["dp", "fsdp", "tp", "sp_ring",
                                      "sp_ulysses"])
def test_strategy_shardings_resolve(cc, strategy):
    cc.check_strategy(strategy)


def test_run_all_quick_exits_zero(cc, capsys):
    assert cc.run_all(quick=True) == 0
    out = capsys.readouterr().out
    assert "FAIL" not in out.splitlines()[-1]


# --- broken invariants: the checker catches them --------------------------


def test_broken_cache_dtype_is_caught(cc):
    """A model whose prefill ignores the bf16-cache flag (e.g. the
    prefill-side cast silently dropped in a refactor) must fail C1."""
    cfg_flag_on = cc.tiny_config(kv_cache_bf16=True)
    liar = DALLE(dataclasses.replace(cfg_flag_on, kv_cache_bf16=False))
    with pytest.raises(cc.ContractViolation, match="cache k dtype"):
        cc.check_cache_dtype(cfg_flag_on, dalle=liar)


def test_full_cache_f32_upcast_is_caught(cc, monkeypatch):
    """The exact PR 1 failure mode: upcasting the bf16 caches to f32 at the
    top of the decode step materializes a full f32 cache copy per step —
    C3 must see the full-cache-sized convert in the decode jaxpr."""
    orig = DALLE.decode_step

    def upcasting_decode_step(self, code, caches, index, mask=None,
                              write_pos=None, qweights=None):
        dtypes = [(k.dtype, v.dtype) for k, v in caches]
        caches = [(k.astype(jnp.float32), v.astype(jnp.float32))
                  for k, v in caches]
        logits, caches = orig(self, code, caches, index, mask, write_pos,
                              qweights)
        # round-trip back to the storage dtype so the scan carry matches —
        # exactly the convert pair XLA would hoist into a resident f32 copy
        caches = [(k.astype(dk), v.astype(dv))
                  for (k, v), (dk, dv) in zip(caches, dtypes)]
        return logits, caches

    monkeypatch.setattr(dalle_mod.DALLE, "decode_step",
                        upcasting_decode_step)
    cfg = cc.tiny_config(kv_cache_bf16=True)
    with pytest.raises(cc.ContractViolation, match="full-cache f32"):
        cc.check_no_f32_cache_materialization(cfg)


def test_dropped_f32_accumulation_is_caught(cc, monkeypatch):
    """Stripping preferred_element_type from the decode attn@v contraction
    reverts to bf16 accumulation — C2 must flag the bf16 dot."""

    def sloppy_attn_v(attn, v, v_scale, out_dtype):
        return jnp.einsum("bhij,bhjd->bhid", attn.astype(v.dtype),
                          v).astype(out_dtype)

    monkeypatch.setattr(MultiHeadAttention, "_attn_v",
                        staticmethod(sloppy_attn_v))
    cfg = cc.tiny_config(kv_cache_bf16=True)
    with pytest.raises(cc.ContractViolation, match="bf16 operand"):
        cc.check_decode_dots_accumulate_f32(cfg)


# --- int8 quantized serving (ISSUE 7) -------------------------------------


def test_int8_contracts_hold(cc):
    cfg = cc.tiny_config(kv_cache_int8=True, weights_int8=True)
    cc.check_cache_dtype(cfg)
    cc.check_decode_dots_accumulate_f32(cfg)
    cc.check_no_f32_cache_materialization(cfg)
    cc.check_serve_tick_no_dequant(cfg)


def test_int8_cache_layout_lie_is_caught(cc):
    """A prefill that keeps float caches while the config claims int8
    storage must fail C1's layout check."""
    cfg_flag_on = cc.tiny_config(kv_cache_int8=True)
    liar = DALLE(dataclasses.replace(cfg_flag_on, kv_cache_int8=False))
    with pytest.raises(cc.ContractViolation, match="int8, scale"):
        cc.check_cache_dtype(cfg_flag_on, dalle=liar)


def test_dequantized_weight_hoist_is_caught(cc, monkeypatch):
    """A qdense that dequantizes the whole kernel before the dot (int8 ->
    f32 at full weight size — exactly what XLA would hoist out of the
    decode loop) must fail C3's weight walk, in the decode AND the
    serve-tick jaxpr."""
    from dalle_pytorch_tpu.ops import attention as attn_mod
    from dalle_pytorch_tpu.ops import quant as quant_mod

    def dequantizing_qdense(x, qkernel, scale, bias=None,
                            mul_dtype=jnp.bfloat16):
        w = qkernel.astype(jnp.float32) * scale
        spec = {2: "...a,ab->...b", 4: "...a,abcd->...bcd"}[qkernel.ndim]
        out = jnp.einsum(spec, x.astype(jnp.float32), w,
                         preferred_element_type=jnp.float32)
        return out if bias is None else out + bias

    # both the module-level import binding (attention) and the local
    # imports (FFBlock, DALLE._head) must see the broken version
    monkeypatch.setattr(quant_mod, "qdense", dequantizing_qdense)
    monkeypatch.setattr(attn_mod, "qdense", dequantizing_qdense)
    cfg = cc.tiny_config(kv_cache_int8=True, weights_int8=True)
    with pytest.raises(cc.ContractViolation, match="dequantized weight"):
        cc.check_no_f32_cache_materialization(cfg)
    with pytest.raises(cc.ContractViolation, match="dequantized weight"):
        cc.check_serve_tick_no_dequant(cfg)


def test_strategy_misconfiguration_is_caught(cc):
    """A plan whose shapes cannot shard (sp that doesn't divide the
    sequence) must surface as a ContractViolation, not a deep jax trace."""
    # tiny geometry: seq_len = 9 + 16 = 25, indivisible by sp_size=2
    cfg = cc.tiny_config(text_seq_len=9, ring_axis="sp", sp_impl="ring",
                         sp_size=2)

    def bad_cfg(**overrides):
        merged = {**dict(text_seq_len=9, ring_axis="sp", sp_impl="ring",
                         sp_size=2), **overrides}
        return dataclasses.replace(cfg, **{
            k: v for k, v in merged.items() if k in ("text_seq_len",
                                                     "ring_axis", "sp_impl",
                                                     "sp_size")})

    with pytest.raises(cc.ContractViolation, match="sp_ring"):
        cc.check_strategy("sp_ring", make_cfg=bad_cfg)
