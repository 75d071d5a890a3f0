"""Which attention core a forward without a cache runs (PR 28): the selection
by shape (``ops/attention.py::flash_tiles``), its resolution where the program
is lowered (on the CPU: the dense branch, bit for bit), the split under a
plan's mesh, and the counters that say what was chosen."""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dalle_pytorch_tpu import DALLE, DALLEConfig  # noqa: E402
from dalle_pytorch_tpu.obs import metrics, telemetry  # noqa: E402
from dalle_pytorch_tpu.obs.report import build_report, render_text  # noqa: E402
from dalle_pytorch_tpu.ops import attention  # noqa: E402
from dalle_pytorch_tpu.ops.attention import AttnPattern, flash_tiles  # noqa: E402
from dalle_pytorch_tpu.training import (make_dalle_train_step,  # noqa: E402
                                        make_optimizer)

BF16, F32 = jnp.bfloat16, jnp.float32
CUB, LUCID, FMAP64 = (80, 32), (256, 32), (80, 64)     # (text, fmap)

# (text, fmap), heads, dim_head, dtype, variant, kv_heads, ring_axis -> tiles
TABLE = [
    ((17, 8), 8, 64, BF16, "full", None, None, None),        # n = 81
    ((1, 16), 8, 64, BF16, "full", None, None, None),        # n = 257
    ((16, 24), 2, 64, BF16, "full", None, None, (128, 128)),  # n = 592
    (CUB, 8, 64, BF16, "full", None, None, (384, 384)),      # n = 1104
    (CUB, 8, 64, BF16, "axial_row", None, None, (128, 128)),
    (CUB, 8, 64, BF16, "axial_col", None, None, (384, 384)),
    (CUB, 8, 64, BF16, "conv_like", None, None, (128, 128)),
    (LUCID, 16, 64, BF16, "full", None, None, (256, 256)),   # n = 1280
    (LUCID, 4, 64, BF16, "full", None, None, (256, 256)),    # dp4.tp4's shard
    (LUCID, 8, 128, BF16, "full", None, None, (256, 256)),
    (LUCID, 1, 128, BF16, "full", None, None, (256, 256)),   # a head a program
    (FMAP64, 8, 64, BF16, "full", None, None, (384, 384)),   # n = 4176
    (FMAP64, 8, 64, BF16, "conv_like", None, None, (128, 128)),
    (LUCID, 20, 128, BF16, "full", 1, None, None),           # grouped keys
    (CUB, 8, 64, BF16, "full", None, "sp", None),            # the sp plans
    (CUB, 8, 64, F32, "full", None, None, None),             # f32 activations
    (CUB, 8, 16, BF16, "full", None, None, None),            # a narrow head
    # the operand contract (PR 35): the kernel reads to_qkv's own array in
    # 128-column blocks of whole heads, at the sequence's own length
    (CUB, 3, 64, BF16, "full", None, None, None),            # 192 columns
    (CUB, 1, 64, BF16, "full", None, None, None),            # half a block
    (CUB, 2, 192, BF16, "full", None, None, None),           # heads astride
    ((17, 24), 8, 64, BF16, "full", None, None, None),       # n = 593
    ((24, 24), 8, 64, BF16, "full", None, None, None),       # n = 600 = 16*37.5
]


@pytest.mark.parametrize(
    "geom,heads,dh,dtype,variant,kv_heads,ring_axis,want", TABLE)
def test_selection_table(geom, heads, dh, dtype, variant, kv_heads,
                         ring_axis, want):
    text, fmap = geom
    n = text + fmap * fmap
    pattern = AttnPattern(variant=variant, seq_len=n - 1, text_len=text,
                          fmap=fmap)
    assert flash_tiles(n, heads, dh, dtype, pattern, kv_heads,
                       ring_axis) == want


# --- a tiny cub200: four patterns, bf16, dim_head 64, n = 592 ----------------

def tiny_cub(**overrides):
    cfg = DALLEConfig(
        dim=64, num_text_tokens=64, text_seq_len=16, depth=4, heads=2,
        dim_head=64, attn_types=("full", "axial_row", "axial_col",
                                 "conv_like"),
        num_image_tokens=32, image_size=96, image_fmap_size=24,
        dtype=jnp.bfloat16, **overrides)
    dalle = DALLE(cfg)
    rng = jax.random.PRNGKey(0)
    text = jax.random.randint(rng, (2, cfg.text_seq_len), 0, 64)
    codes = jax.random.randint(rng, (2, cfg.image_seq_len), 0, 32)
    params = dalle.init(rng, text, codes)["params"]
    return cfg, dalle, params, text, codes


def test_cpu_step_is_the_dense_branch_bit_for_bit(monkeypatch):
    """Every layer of the tiny cub200 is given tiles, yet the step lowered
    on the CPU holds no kernel and computes the loss and the update of the
    dense branch called directly, bit for bit."""
    cfg, dalle, params, text, codes = tiny_cub()
    chosen = []
    real = attention.flash_tiles

    def spy(*args):
        chosen.append(real(*args))
        return chosen[-1]

    monkeypatch.setattr(attention, "flash_tiles", spy)
    tx = make_optimizer(1e-3)
    opt = tx.init(params)
    args = (params, opt, None, text, codes, jax.random.PRNGKey(1))
    step = make_dalle_train_step(dalle, tx, donate=False)
    assert "tpu_custom_call" not in step.lower(*args).as_text()
    assert chosen and all(t == (128, 128) for t in chosen)
    got = step(*args)

    monkeypatch.setattr(attention, "flash_tiles", lambda *a: None)
    want = make_dalle_train_step(dalle, tx, donate=False)(*args)
    for a, b in zip(jax.tree.leaves((got[2], got[0])),
                    jax.tree.leaves((want[2], want[0]))):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_prefill_keeps_the_dense_branch(monkeypatch):
    """A prefill (it returns its keys and values) never asks for tiles."""
    from dalle_pytorch_tpu.models.dalle import prefill_codes

    cfg, dalle, params, text, _ = tiny_cub()
    monkeypatch.setattr(attention, "flash_tiles",
                        lambda *a: pytest.fail("prefill asked for tiles"))
    jax.eval_shape(lambda p, t: prefill_codes(dalle, {"params": p}, t),
                   params, text[:1])


# --- the counters -----------------------------------------------------------

def _trace_records(tmp_path, cfg, batch=2):
    dalle = DALLE(cfg)
    text = jnp.zeros((batch, cfg.text_seq_len), jnp.int32)
    codes = jnp.zeros((batch, cfg.image_seq_len), jnp.int32)
    shapes = jax.eval_shape(
        lambda r: dalle.init(r, text[:1], codes[:1])["params"],
        jax.random.PRNGKey(0))
    reg = metrics.init()
    tel = telemetry.init(tmp_path, run_id="attn-kernel")
    try:
        jax.eval_shape(lambda p: dalle.apply(
            {"params": p}, text, codes, return_loss=True), shapes)
        rendered = reg.render()
    finally:
        telemetry.shutdown()
        metrics.shutdown()
    events = telemetry.read_events(tel.path)
    return [e for e in events if e["kind"] == "attention"
            and e["name"] == "kernel"], rendered, events


def test_cub200_trace_reports_eight_flash_layers(tmp_path):
    """One trace of the cub200 model: one ``attention.kernel`` record, three
    gauges and a line under ``-- attention --`` say what the table says."""
    from benchmark import harness
    from dalle_pytorch_tpu.ops.attention_pallas import block_counts

    cfg = harness.build_configs(harness.load_cell("cub200-train").config)[0]
    records, rendered, events = _trace_records(tmp_path, cfg)
    assert len(records) == 1            # init's own pass does not speak
    rec = records[0]
    computed = blocks = 0
    for variant in cfg.attn_types:
        pattern = AttnPattern(variant=variant, seq_len=cfg.seq_len,
                              text_len=cfg.text_seq_len + 1,
                              fmap=cfg.image_fmap_size)
        tiles = flash_tiles(1104, 8, 64, jnp.bfloat16, pattern)
        assert tiles == ((384, 384) if variant in ("full", "axial_col")
                         else (128, 128))
        skipped, partly, wholly = block_counts(pattern, 1104, *tiles)
        computed += 2 * (partly + wholly)
        blocks += 2 * (skipped + partly + wholly)
    assert (rec["flash_layers"], rec["dense_layers"]) == (8, 0)
    assert rec["tiles"] == ["128x128", "384x384"] and rec["n"] == 1104
    assert rec["blocks_computed_share"] == round(computed / blocks, 4)
    assert 0.3 < rec["blocks_computed_share"] < 0.7
    # the operand contract: two heads of 64 side by side on a program's
    # lanes, nothing padded in HBM (48 rows a sequence before PR 35)
    assert (rec["heads_per_program"], rec["hbm_pad_rows"]) == (2, 0)
    assert "graft_attn_heads_per_program 2" in rendered
    assert "graft_attn_hbm_pad_rows 0" in rendered
    # one pallas_call for a layer's backward (two until PR 41)
    assert rec["backward_calls"] == 1
    assert "graft_attn_backward_calls 1" in rendered
    assert "graft_attn_flash_layers 8" in rendered
    assert "graft_attn_dense_layers 0" in rendered
    assert "graft_attn_blocks_computed_share 0." in rendered
    report = build_report(events)
    assert report["attention"]["flash_layers"] == 8
    text = render_text(report)
    assert "-- attention --" in text
    assert ("attention core: 8 layers on the flash kernel (tiles 128x128, "
            "384x384") in text
    assert ("2 heads a program, 0 rows of padding in HBM, 1 backward "
            "call(s) a layer") in text


def test_jamba_trace_reports_no_flash_layer(tmp_path):
    """The jamba2-3b trunk's two attention layers have grouped keys: dense."""
    from benchmark import harness

    cfg = harness.build_configs(
        harness.load_cell("jamba2-3b-generate").config)[0]
    records, rendered, _ = _trace_records(tmp_path, cfg, batch=1)
    assert len(records) == 1
    assert (records[0]["flash_layers"], records[0]["dense_layers"]) == (0, 2)
    assert records[0]["blocks_computed_share"] == 0.0
    assert records[0]["heads_per_program"] == 0     # no flash layer speaks
    assert records[0]["backward_calls"] == 0
    assert "graft_attn_flash_layers 0" in rendered
    assert "graft_attn_dense_layers 2" in rendered


# --- under a plan's mesh ----------------------------------------------------

def test_kernel_call_is_split_over_the_plans_mesh(monkeypatch):
    """Under a plan's mesh the switched core's two halves run in a
    ``shard_map`` over the batch and head axes of the plan (here ``dp2.tp2``
    on four virtual devices; the kernel's two halves replaced by the dense
    reference and its VJP, on the same per-shard arguments), and a batch
    the mesh does not divide keeps the dense branch."""
    from attention_refs import dense_reference
    from dalle_pytorch_tpu.ops import attention_pallas
    from dalle_pytorch_tpu.parallel.plan import ParallelPlan

    part = ParallelPlan.parse("dp2.tp2").partitioner(
        devices=jax.devices()[:4])
    pattern = AttnPattern(variant="axial_row", seq_len=24, text_len=8, fmap=4)
    seen = []

    def fake_halves(n, heads, dim_head, dtype, pattern, has_bias, **tiles):
        def ref(qkv, bias):     # of a shard: five axes, or flat over three
            q, k, v = qkv.reshape(*qkv.shape[:2], 3, heads,
                                  dim_head).transpose(2, 0, 3, 1, 4)
            out = dense_reference(q, k, v, pattern,
                                  key_pad_bias=bias).astype(qkv.dtype)
            return out.transpose(0, 2, 1, 3).reshape(*qkv.shape[:2], -1)

        # residuals in the kernel's own layout: qkv as the projection wrote
        # it, the bias, o as to_out reads it, a block of statistics a head
        def forward(qkv, bias):
            seen.append(qkv.shape)
            out = ref(qkv, bias)
            return out, (qkv, bias, out, jnp.zeros(
                (qkv.shape[0], heads, 1, n), jnp.float32))

        def backward(residuals, g):
            return (*jax.vjp(lambda qkv: ref(qkv, residuals[1]),
                             residuals[0])[1](g), None)

        return forward, backward

    monkeypatch.setattr(attention_pallas, "flash_attention_halves",
                        fake_halves)
    q, k, v, g = jax.random.normal(jax.random.PRNGKey(0), (4, 4, 2, 24, 16))
    qkv = jnp.stack([q, k, v]).transpose(1, 3, 0, 2, 4)  # [b, n, 3, h, dh]
    with attention.kernel_mesh(part):
        mesh = attention._kernel_mesh[-1]
    core = attention._Core(attention.kernel_pattern(pattern), jnp.dtype(F32),
                           (128, 128), mesh, 2, 16)
    forward, backward = core.halves(qkv, None)
    out, residuals = jax.jit(forward)(qkv, None)
    got = jax.jit(backward)(residuals, g.transpose(0, 2, 1, 3).reshape(
        4, 24, 32))
    assert set(seen) == {(2, 24, 3, 1, 16)}  # batch over dp, heads over tp
    ref, vjp = jax.vjp(lambda q, k, v: dense_reference(q, k, v, pattern),
                       q, k, v)
    np.testing.assert_allclose(
        np.asarray(out.reshape(4, 24, 2, 16).transpose(0, 2, 1, 3)),
        np.asarray(ref), atol=2e-5, rtol=2e-5)
    assert got.shape == qkv.shape       # dq, dk, dv where q, k, v lie
    for a, b in zip(got.transpose(2, 0, 3, 1, 4), vjp(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)

    # a layer whose shape asks for the kernel, at a batch of 3: no kernel
    # call is built, the dense branch's output
    monkeypatch.setattr(attention, "flash_tiles", lambda *a: (128, 128))
    layer = attention.MultiHeadAttention(pattern=pattern, dim=32, heads=2,
                                         dim_head=16)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 24, 32))
    params = layer.init(jax.random.PRNGKey(1), x)
    del seen[:]
    with attention.kernel_mesh(part):
        odd = jax.jit(layer.apply)(params, x)
    assert not seen
    # (without a mesh the same layer hands the kernel one flat array)
    np.testing.assert_array_equal(np.asarray(odd),
                                  np.asarray(layer.apply(params, x)))


@pytest.mark.parametrize("heads,want", [(2, None), (4, (128, 128))])
def test_tp_split_must_leave_whole_lane_blocks(monkeypatch, heads, want):
    """The selection sees the heads a shard holds: ``tp2`` leaves one
    64-wide head of two (half a lane block: the dense branch, GSPMD's to
    place) and two of four (the kernel, inside the ``shard_map``)."""
    from dalle_pytorch_tpu.parallel.plan import ParallelPlan

    part = ParallelPlan.parse("dp2.tp2").partitioner(
        devices=jax.devices()[:4])
    pattern = AttnPattern(variant="axial_row", seq_len=591, text_len=16,
                          fmap=24)
    asked = []
    real = attention.flash_tiles

    def spy(n, heads, *rest):
        asked.append((heads, real(n, heads, *rest)))
        return asked[-1][1]

    monkeypatch.setattr(attention, "flash_tiles", spy)
    layer = attention.MultiHeadAttention(pattern=pattern, dim=32, heads=heads,
                                         dim_head=64, dtype=BF16)
    x = jnp.zeros((2, 592, 32), BF16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)   # keep no kernel
    try:
        with attention.kernel_mesh(part):
            traced = str(jax.make_jaxpr(layer.apply)(params, x))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert asked == [(heads // 2, want)]
    assert ("shard_map" in traced) == (want is not None)
    assert ("pallas_call" in traced) == (want is not None)


# --- the kernels, kept between processes ------------------------------------

def test_kernels_are_kept_beside_the_compile_cache(tmp_path, monkeypatch):
    """The model's default path keeps its traced kernels as ``jax.export``
    artefacts where the program keeps its compile cache: the first use
    traces and writes them, a later process (here: the in-process memo
    cleared) reads the bytes and never builds a ``pallas_call``; a direct
    call that does not ask for it touches no file."""
    from dalle_pytorch_tpu.ops import attention_pallas as ap

    pattern = attention.kernel_pattern(AttnPattern(
        variant="axial_row", seq_len=591, text_len=16, fmap=24,
        layout_seed=3))
    assert pattern.layout_seed == 0     # layers of one variant: one kernel
    static = ap._Static(pattern, 592, 2, 64, 128, 128, False, False, True)
    avals = (((4, 592, 3 * 2 * 64), jnp.dtype(jnp.bfloat16)), None)
    built = []
    real = ap._pallas
    monkeypatch.setattr(ap, "_pallas",
                        lambda *a, **k: built.append(1) or real(*a, **k))
    ap._exported.cache_clear()
    first = ap._exported(str(tmp_path), "fwd", static, avals)
    files = sorted(p.name for p in tmp_path.iterdir())
    assert len(files) == 1 and files[0].startswith("flash-fwd-")
    assert built == [1] and first.platforms == ("tpu",)
    ap._exported.cache_clear()
    again = ap._exported(str(tmp_path), "fwd", static, avals)
    assert built == [1]                 # read back, not traced
    assert again.in_avals == first.in_avals
    assert again.mlir_module_serialized == first.mlir_module_serialized
    ap._exported.cache_clear()

    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "unused"))
    try:
        q = jnp.ones((1, 1, 592, 64), jnp.bfloat16)
        jax.eval_shape(lambda q: ap.flash_pattern_attention(
            q, q, q, pattern, interpret=True), q)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert not list((tmp_path / "unused").glob("flash-*"))
