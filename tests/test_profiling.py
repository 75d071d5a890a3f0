"""Profiling utilities: FLOP estimates, MFU math, step timer."""
from __future__ import annotations

import time

import pytest

from dalle_pytorch_tpu import DALLEConfig
from dalle_pytorch_tpu.utils.profiling import (StepTimer, dalle_train_flops,
                                               device_peak_flops,
                                               transformer_train_flops)


def test_flops_scale_with_config():
    cfg1 = DALLEConfig(dim=256, num_text_tokens=7800, text_seq_len=80,
                       depth=8, num_image_tokens=8192, image_size=256,
                       image_fmap_size=32)
    cfg2 = DALLEConfig(dim=256, num_text_tokens=7800, text_seq_len=80,
                       depth=16, num_image_tokens=8192, image_size=256,
                       image_fmap_size=32)
    f1, f2 = dalle_train_flops(cfg1, 16), dalle_train_flops(cfg2, 16)
    assert f2 > f1 > 0
    # depth doubling should roughly double the per-layer term
    assert 1.5 < f2 / f1 < 2.1
    # batch linearity
    assert abs(dalle_train_flops(cfg1, 32) / f1 - 2.0) < 1e-6


def test_flops_magnitude_sane():
    """CUB config ~2 TFLOP per step at batch 16 (hand-derived in review)."""
    cfg = DALLEConfig(dim=256, num_text_tokens=7800, text_seq_len=80,
                      depth=8, num_image_tokens=8192, image_size=256,
                      image_fmap_size=32)
    f = dalle_train_flops(cfg, 16)
    assert 0.5e12 < f < 5e12, f


def test_flops_count_phase_sliced_head():
    """The head term must match the phase-sliced matmuls the training loss
    executes (models/dalle.py::loss_from_hidden) — NOT a dense
    ``seq x total_vocab`` head, which overstates FLOPs/MFU by ~9% at the
    CUB geometry.  Pins both the override plumbing and the exact term, so
    a revert to dense-head accounting fails here."""
    cfg = DALLEConfig(dim=256, num_text_tokens=7800, text_seq_len=80,
                      depth=8, num_image_tokens=8192, image_size=256,
                      image_fmap_size=32)
    common = dict(dim=cfg.dim, depth=cfg.depth, seq_len=cfg.seq_len + 1,
                  heads=cfg.heads, dim_head=cfg.dim_head, ff_mult=4,
                  vocab=cfg.total_tokens, batch=16)
    dense_head = transformer_train_flops(**common)
    sliced = dalle_train_flops(cfg, 16)
    sliced_head_fwd = 2 * cfg.dim * (
        cfg.text_seq_len * cfg.total_text_tokens
        + cfg.image_seq_len * cfg.num_image_tokens)
    expected = transformer_train_flops(**common, logits_flops=sliced_head_fwd)
    assert sliced == expected
    # the sliced head must be a real reduction vs the dense-head count
    assert sliced < dense_head
    assert 0.05 < 1 - sliced / dense_head < 0.15


@pytest.mark.parametrize("kind,peak", [
    ("TPU v5 lite", 197e12), ("TPU v5e", 197e12), ("TPU v5", 459e12),
    ("TPU v6 lite", 918e12), ("TPU v4", 275e12),
    ("cpu", None), ("TPU v9 hypothetical", None),
])
def test_peak_flops_known_kinds_or_none(kind, peak):
    """A kind the table does not know has NO peak — never a default."""
    assert device_peak_flops(kind) == peak


def test_step_timer(monkeypatch):
    t = StepTimer(flops_per_step=1e12)
    assert t.peak is None  # the suite's CPU devices are not in the table
    assert t.tick(8) == {}  # first tick only arms the timer
    time.sleep(0.01)
    out = t.tick(8)
    assert out["step_time_s"] > 0
    assert out["images_per_sec"] > 0
    assert "mfu" not in out  # unknown device: MFU is "not measured"

    import types

    from dalle_pytorch_tpu.utils import profiling
    fake = types.SimpleNamespace(device_kind="TPU v5 lite")
    monkeypatch.setattr(profiling.jax, "devices", lambda: [fake])
    t = StepTimer(flops_per_step=1e12)
    t.tick(8)
    time.sleep(0.01)
    assert 0 < t.tick(8)["mfu"] < 1e6


def test_step_timer_loader_stall():
    """stall_s feeds the loader-stall EMA and the stall fraction — the
    surface the monitor uses to tell an input-bound run from a slow
    chip.  Fraction is clamped to 1 (a stall can't exceed the step)."""
    t = StepTimer()
    t.tick(8, stall_s=0.0)
    time.sleep(0.01)
    out = t.tick(8, stall_s=0.004)
    assert out["loader_stall_s"] > 0
    assert 0 < out["loader_stall_frac"] <= 1.0
    # without stall_s the stall keys stay absent (folder runs without the
    # prefetcher keep their old reporting shape)
    t2 = StepTimer()
    t2.tick(4)
    time.sleep(0.002)
    assert "loader_stall_s" not in t2.tick(4)
    # clamp: an absurd stall reading still reports a fraction <= 1
    t3 = StepTimer()
    t3.tick(4, stall_s=0.0)
    time.sleep(0.002)
    assert t3.tick(4, stall_s=10.0)["loader_stall_frac"] == 1.0


def test_transformer_flops_terms():
    # attention term must dominate at long seq, ff at large dim
    long_seq = transformer_train_flops(dim=64, depth=1, seq_len=4096,
                                       heads=4, dim_head=16, ff_mult=4,
                                       vocab=100, batch=1)
    short_seq = transformer_train_flops(dim=64, depth=1, seq_len=256,
                                        heads=4, dim_head=16, ff_mult=4,
                                        vocab=100, batch=1)
    assert long_seq > short_seq * 16  # quadratic attention term visible


# The analytic-vs-XLA cost_analysis band (dalle_train_flops lands in
# [0.85, 1.0] of the compiler's count — measured 96.4% at the CUB
# geometry) lives in tests/test_perf_model.py::
# test_production_step_regression_bands, alongside the other compiler-
# model gates, so the CUB-sized compile is paid once per slow-tier run.
