"""The form the serving arena STORES its caches in (ISSUE 37).

``MultiHeadAttention.arena_form`` decides it per layer from what the trace
can see; ``SlotArena`` allocates and installs by it; the aligned step reads
and writes the arrays where they lie.  The load-bearing properties:

* **Exactness in the stored form**: a bf16 model with ``dim_head`` 64 and an
  even head count (the fold engages; ``full`` layers head-folded, the sliced
  ones position-major besides) serves, per request, the codes ``decode_codes``
  gives, over staggered admissions, retirements and several wraps of the
  clock; so does the int8 arena in the form the rule gives it.
* **No retrace** across slots and wraps.
* **What the programs ask for** (the lowered text: the CPU compiler's layouts
  are not the chip's): no transposition of a whole cache in the tick, each
  cache out of it through one ``dynamic_update_slice``, an install that
  updates one slot's rows of the stored form.
* **The ``serve.arena_layout`` record**, its gauges and the report's line.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu import DALLE, DALLEConfig, VAEConfig
from dalle_pytorch_tpu.models.dalle import decode_codes, prefill_codes
from dalle_pytorch_tpu.obs import metrics, telemetry
from dalle_pytorch_tpu.obs.report import build_report, render_text
from dalle_pytorch_tpu.ops.attention import AttnPattern, MultiHeadAttention
from dalle_pytorch_tpu.ops.quant import CacheForm
from dalle_pytorch_tpu.serve import GenerationServer, SlotArena
from dalle_pytorch_tpu.serve.engine import relayout_bytes

VCFG = VAEConfig(image_size=16, num_tokens=32, codebook_dim=16, num_layers=2,
                 hidden_dim=8)
CYCLE = ("full", "axial_row", "axial_col", "conv_like")
HEAD_MAJOR, POSITION_MAJOR = CacheForm(2), CacheForm(2, position_major=True)


def config(**over):
    fields = dict(dim=32, num_text_tokens=50, text_seq_len=6, depth=4,
                  heads=2, dim_head=64, attn_types=CYCLE)
    fields.update(over)
    return DALLEConfig.from_vae(VCFG, **fields)


def build(cfg, prompts=6):
    dalle = DALLE(cfg)
    texts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(i), (cfg.text_seq_len,), 1, 50), np.int32)
        for i in range(prompts)]
    params = dalle.init(
        jax.random.PRNGKey(0), jnp.asarray(texts[0])[None],
        jnp.zeros((1, cfg.image_seq_len), jnp.int32), return_loss=True)
    return dalle, params, texts


def greedy_refs(dalle, params, texts):
    prefill = jax.jit(lambda p, t: prefill_codes(dalle, p, t))

    def ref(text):
        first_logits, caches = prefill(params, jnp.asarray(text)[None])
        return np.asarray(decode_codes(
            dalle, params, first_logits, caches, jax.random.PRNGKey(7),
            filter_thres=1.0))[0]

    return [ref(text) for text in texts]


@pytest.fixture(scope="module")
def folded():
    """The model whose arena folds: bf16 caches (``kv_cache_bf16``, the
    default), two heads of 64, the ``cub200`` cycle."""
    cfg = config()
    dalle, params, texts = build(cfg)
    return cfg, dalle, params, texts, greedy_refs(dalle, params, texts)


@pytest.fixture(autouse=True)
def _closed_streams():
    yield
    telemetry.shutdown()
    metrics.shutdown()


# --- the rule ----------------------------------------------------------------

def attn(variant, heads=2, dim_head=64, **kw):
    return MultiHeadAttention(
        dim=32, heads=heads, dim_head=dim_head,
        pattern=AttnPattern(variant=variant, seq_len=22, text_len=7, fmap=4),
        **kw)


@pytest.mark.parametrize("variant, heads, dim_head, dtype, kw, want", [
    ("full", 2, 64, jnp.bfloat16, {}, HEAD_MAJOR),
    ("axial_row", 2, 64, jnp.bfloat16, {}, POSITION_MAJOR),
    ("axial_col", 2, 64, jnp.bfloat16, {}, POSITION_MAJOR),
    ("conv_like", 2, 64, jnp.bfloat16, {}, POSITION_MAJOR),
    ("axial_row", 8, 64, jnp.int8, {}, POSITION_MAJOR),
    ("full", 8, 32, jnp.bfloat16, {}, CacheForm(4)),
    ("full", 2, 64, jnp.float32, {}, CacheForm()),       # its dots would round
    ("axial_row", 2, 64, jnp.float32, {}, CacheForm()),
    ("full", 3, 64, jnp.bfloat16, {}, CacheForm()),      # an odd head count
    ("full", 2, 128, jnp.bfloat16, {}, CacheForm()),     # fills the lanes
    ("axial_col", 2, 96, jnp.bfloat16, {}, CacheForm()),  # divides them not
    ("full", 4, 64, jnp.bfloat16, {"kv_heads": 2}, CacheForm()),   # grouped
    ("full", 4, 64, jnp.bfloat16, {"kv_heads": 4}, CacheForm()),
], ids=lambda v: getattr(v, "__name__", None) or str(v))
def test_the_rule_decides_from_shapes_dtype_and_pattern(
        variant, heads, dim_head, dtype, kw, want):
    assert attn(variant, heads, dim_head, **kw).arena_form(dtype) == want


def test_a_ring_keeps_the_plain_form():
    ring = MultiHeadAttention(
        dim=32, heads=2, dim_head=64, kv_heads=2,
        pattern=AttnPattern(variant="full", seq_len=22, text_len=7, fmap=4,
                            window=8))
    assert ring.arena_form(jnp.bfloat16) == CacheForm()


def test_cache_form_round_trips_and_names_its_axes():
    kv = jnp.arange(3 * 4 * 5 * 8, dtype=jnp.float32).reshape(3, 4, 5, 8)
    assert CacheForm().store(kv) is kv
    for form in (CacheForm(2), CacheForm(2, True), CacheForm(4, True)):
        stored = form.store(kv)
        assert stored.shape == form.shape(3, 4, 5, 8)
        assert stored.shape[form.position_axis] == 5
        # head g * fold + f at lanes [f * dh, (f + 1) * dh) of group g
        groups = (stored.transpose(0, 2, 1, 3) if form.position_major
                  else stored)
        heads = groups.reshape(
            3, 4 // form.fold, 5, form.fold, 8).transpose(0, 1, 3, 2, 4)
        np.testing.assert_array_equal(heads.reshape(kv.shape), kv)
        # what the folded dots take: the groups as stored, or one group of
        # every head side by side, head h at lanes [h * dh, (h + 1) * dh)
        dots = form.for_dots(stored)
        if form.position_major:
            assert dots.shape == (3, 1, 5, 4 * 8)
            np.testing.assert_array_equal(
                dots.reshape(3, 5, 4, 8).transpose(0, 2, 1, 3), kv)
        else:
            assert dots is stored


# --- exactness and no retrace in the stored form ----------------------------

def test_the_arena_stores_what_the_rule_says(folded):
    cfg, dalle, params, _, _ = folded
    arena = SlotArena(dalle, params, num_slots=3)
    assert arena._forms == [HEAD_MAJOR, POSITION_MAJOR, POSITION_MAJOR,
                            POSITION_MAJOR]
    for form, (k, v) in zip(arena._forms, arena.state["caches"]):
        assert k.shape == v.shape == form.shape(3, 2, cfg.seq_len, 64)
        assert k.shape[0] == 3 and k.shape[-1] == 128   # slots major, lanes
        assert k.dtype == v.dtype == jnp.bfloat16


def test_staggered_requests_over_several_wraps_match_decode_codes(folded):
    cfg, dalle, params, texts, refs = folded
    srv = GenerationServer(dalle, params, num_slots=3, filter_thres=1.0)
    handles = []
    for i in range(10):                 # admissions two steps apart
        handles.append((srv.submit(texts[i % len(texts)]), i % len(texts)))
        srv.step()
        srv.step()
    srv.run_until_idle(max_ticks=2000)
    assert srv._clock > 2 * cfg.seq_len           # the clock wrapped
    for handle, i in handles:
        np.testing.assert_array_equal(handle.result(0), refs[i])
    assert srv.trace_counts() == {"prefill": 1, "admit": 1, "tick": 1}


def test_sampled_codes_do_not_depend_on_the_slot_or_the_phase(folded):
    """Not greedy: the same (prompt, key) through different slots, clock
    phases and neighbours draws the same codes."""
    _, dalle, params, texts, _ = folded
    key = np.asarray([5, 6], np.uint32)
    outs = []
    for before in (0, 3):
        srv = GenerationServer(dalle, params, num_slots=2, filter_thres=0.5)
        for j in range(before):
            srv.submit(texts[1 + j], key=np.asarray([1, j], np.uint32))
            srv.step()
        handle = srv.submit(texts[0], key=key)
        srv.run_until_idle(max_ticks=500)
        outs.append(handle.result(0))
        assert srv.trace_counts() == {"prefill": 1, "admit": 1, "tick": 1}
    np.testing.assert_array_equal(outs[0], outs[1])


def test_span_reads_match_the_gather_in_the_stored_form(folded):
    cfg, _, params, texts, _ = folded
    outs = {}
    for span in (True, False):
        srv = GenerationServer(
            DALLE(dataclasses.replace(cfg, aligned_span_decode=span)), params,
            num_slots=2, filter_thres=0.5)
        handles = [srv.submit(texts[i % len(texts)],
                              key=np.asarray([9, i], np.uint32))
                   for i in range(5)]
        srv.run_until_idle(max_ticks=1000)
        outs[span] = [h.result(0) for h in handles]
    for a, b in zip(outs[True], outs[False]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_bf16_and_int8_arenas_match_in_the_stored_form(folded, int8):
    cfg, _, params, texts, refs = folded
    if int8:
        cfg = dataclasses.replace(cfg, kv_cache_int8=True, weights_int8=True)
        refs = greedy_refs(DALLE(cfg), params, texts[:3])
    srv = GenerationServer(DALLE(cfg), params, num_slots=2, filter_thres=1.0)
    assert srv.arena._forms[0] == HEAD_MAJOR
    assert srv.arena._forms[1:] == [POSITION_MAJOR] * 3
    h0 = srv.submit(texts[0])
    for _ in range(5):
        srv.step()
    h1 = srv.submit(texts[1])           # joins mid-flight
    for _ in range(3):
        srv.step()
    h2 = srv.submit(texts[2])           # queued: both slots taken
    srv.run_until_idle(max_ticks=300)
    for handle, ref in ((h0, refs[0]), (h1, refs[1]), (h2, refs[2])):
        np.testing.assert_array_equal(handle.result(0), ref)
    assert srv.trace_counts() == {"prefill": 1, "admit": 1, "tick": 1}
    if int8:
        for k, v in srv.arena.state["caches"]:
            assert k[0].dtype == jnp.int8 and k[0].shape[-1] == 128
            assert k[1].shape == v[1].shape == (2, cfg.heads, 1, 1)


# --- what the programs ask for ------------------------------------------------

def tensor(array) -> str:
    dtype = {"bfloat16": "bf16", "int8": "i8", "float32": "f32"}[
        str(array.dtype)]
    return "tensor<" + "x".join(map(str, array.shape)) + f"x{dtype}>"


def lowered(arena):
    def shape(*dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype)

    prefill = arena._prefill.lower(
        arena.variables, shape(1, arena.dalle.cfg.text_seq_len))
    first_logits, caches1 = prefill.out_info
    admit = arena._admit.lower(
        arena.state, shape(), first_logits, caches1,
        shape(2, dtype=jnp.uint32), shape(dtype=jnp.float32), shape())
    return arena._lower_decode().as_text(), admit.as_text()


def test_the_tick_moves_no_whole_cache_and_the_install_writes_one_slot(
        folded):
    _, dalle, params, _, _ = folded
    arena = SlotArena(dalle, params, num_slots=3)
    tick, admit = lowered(arena)
    caches = [a for pair in arena.state["caches"] for a in pair]
    kinds = {tensor(a) for a in caches}
    assert len(kinds) == 2              # head-major and position-major
    for kind in kinds:
        held = sum(tensor(a) == kind for a in caches)
        for text in (tick, admit):
            # no transposition or broadcast of a whole cache ...
            assert not re.findall(
                r"stablehlo\.(?:transpose|broadcast_in_dim|convert|gather)"
                r"[^\n]*-> " + re.escape(kind), text)
            # ... and each cache leaves through one dynamic_update_slice
            updates = re.findall(
                r"stablehlo\.dynamic_update_slice[^\n]*: \(" + re.escape(kind)
                + r", (tensor<[^>]*>)[^\n]*-> " + re.escape(kind), text)
            assert len(updates) == held
            # the tick's update is one column of every slot, the install's
            # is every column of one slot: that slot's rows, stored form
            dims = kind[len("tensor<"):].split("x")
            whole = ("1x" + "x".join(dims[1:])
                     if text is admit else None)
            for update in updates:
                if whole:
                    assert update == f"tensor<{whole}"
                else:
                    assert update.count("x1x") == 1 and \
                        update.startswith("tensor<3x")

def test_relayout_bytes_reads_a_compiled_programs_text():
    text = """
  %copy.1 = bf16[128,4,1104,128]{3,1,2,0:T(4,128)(2,1)} copy(%p0), sharding=x
  %copy.2 = bf16[128,4,1104,128]{3,2,1,0:T(8,128)(2,1)} copy(%dus.1)
  %t.3 = s8[4,1104,128,128]{3,2,1,0} transpose(%p1), dimensions={1,2,0,3}
  %copy.4 = f32[128,8192]{1,0} copy(%logits)
  %fusion.5 = bf16[128,4,1104,128]{3,2,1,0} fusion(%p2), kind=kLoop
  ROOT %copy.6 = bf16[1104,128,4,128]{3,2,1,0} copy(%p3)
"""
    n = 128 * 4 * 1104 * 128
    assert relayout_bytes(text, {n}) == 3 * 2 * n + n
    assert relayout_bytes(text, {7}) == 0


# --- the record, the gauges, the report's line -------------------------------

def layout_of(cfg, slots=2):
    dalle, params, _ = build(cfg, prompts=1)
    return SlotArena(dalle, params, num_slots=slots).layout()


def test_the_record_counts_the_cub200_cycle_folded_and_the_rest_plain():
    """``cub200``'s attention shapes (8 heads of 64 over 8 layers of the
    cycle, bf16 caches) at a toy width and length."""
    cub = layout_of(config(depth=8, heads=8), slots=4)
    per_slot = 16 * 22 * 8 * 64 * 2
    assert cub == {
        "slots": 4, "folded_layers": 8, "plain_layers": 0, "ring_layers": 0,
        "recurrent_layers": 0, "install_bytes_per_slot": per_slot,
        "tick_relayout_bytes": cub["tick_relayout_bytes"]}
    assert cub["tick_relayout_bytes"] >= 0
    f32 = layout_of(config(depth=8, heads=8, kv_cache_bf16=False))
    assert (f32["folded_layers"], f32["plain_layers"]) == (0, 8)
    assert f32["install_bytes_per_slot"] == 2 * per_slot
    wide = layout_of(config(heads=2, dim_head=128))
    assert (wide["folded_layers"], wide["plain_layers"]) == (0, 4)


def test_the_record_counts_grouped_rings_and_recurrent_layers():
    """A trunk's layers keep the plain form, whatever their shapes: grouped
    keys (two key heads of 64 under four queries, bf16), rings, recurrent
    entries."""
    geometry = dict(dim=32, depth=4, heads=4, dim_head=64, num_text_tokens=50,
                    text_seq_len=8, num_image_tokens=32, image_size=32,
                    image_fmap_size=4)

    def layout(**trunk):
        cfg = DALLEConfig(**geometry, trunk=dict(kv_heads=2, **trunk))
        dalle = DALLE(cfg)
        params = dalle.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
            jnp.zeros((1, cfg.image_seq_len), jnp.int32), return_loss=True)
        arena = SlotArena(dalle, params, num_slots=2)
        for k, _ in arena.state["caches"]:
            assert jnp.ndim(k) != 4 or k.shape[:2] == (2, 2), k.shape
        return arena.layout()

    routed = layout(mixers=["attention", "window", "window", "window"],
                    window=8, rope_theta=1.5e6, ff="moe_reglu", experts=4,
                    experts_per_token=2, expert_dim=16, tied_table=False)
    assert {k: routed[k] for k in ("folded_layers", "plain_layers",
                                   "ring_layers", "recurrent_layers")} == {
        "folded_layers": 0, "plain_layers": 1, "ring_layers": 3,
        "recurrent_layers": 0}
    hybrid = layout(mixers=["mamba", "attention", "mamba", "mamba"],
                    ff_dim=64, ssm_state=4, ssm_dt_rank=4)
    assert {k: hybrid[k] for k in ("folded_layers", "plain_layers",
                                   "ring_layers", "recurrent_layers")} == {
        "folded_layers": 0, "plain_layers": 1, "ring_layers": 0,
        "recurrent_layers": 3}


def test_a_built_arena_writes_one_record_sets_its_gauges_and_is_printed(
        tmp_path, folded):
    _, dalle, params, texts, refs = folded
    telemetry.init(tmp_path, run_id="layout", beacon_every=0)
    reg = metrics.init()
    srv = GenerationServer(dalle, params, num_slots=3, filter_thres=1.0)
    handle = srv.submit(texts[0])
    srv.run_until_idle(max_ticks=100)
    telemetry.shutdown()
    np.testing.assert_array_equal(handle.result(0), refs[0])
    # the record's compile is not the entry point's
    assert srv.trace_counts() == {"prefill": 1, "admit": 1, "tick": 1}
    events = telemetry.read_events(tmp_path)
    records = [r for r in events
               if r["kind"] == "serve" and r["name"] == "arena_layout"]
    assert len(records) == 1
    want = srv.arena.layout()
    assert {k: records[0][k] for k in want} == want
    assert want["folded_layers"] == 4 and want["slots"] == 3
    rendered = reg.render()
    assert "graft_serve_arena_folded_layers 4" in rendered
    assert ("graft_serve_tick_relayout_bytes "
            f"{want['tick_relayout_bytes']}") in rendered
    report = build_report(events)
    assert report["serve"]["arena"]["folded_layers"] == 4
    text = render_text(report)
    line = next(ln for ln in text.splitlines()
                if ln.startswith("arena layout:"))
    assert "3 slots" in line and "4 layers stored head-folded" in line
    assert f"writes {want['install_bytes_per_slot']} bytes" in line
    assert text.index("-- serve --") < text.index(line)


def test_no_listener_no_record_and_no_compile(folded, monkeypatch):
    _, dalle, params, _, _ = folded
    monkeypatch.setattr(SlotArena, "layout", lambda self: pytest.fail(
        "layout() compiles the tick: only where someone listens"))
    assert telemetry.get() is None and metrics.active() is None
    SlotArena(dalle, params, num_slots=2)


def test_a_report_without_an_arena_keeps_its_no_serve_events_line():
    text = render_text(build_report([
        {"kind": "step", "name": "train", "seq": 1, "t": 0.0}]))
    assert "no serve events" in text and "arena layout" not in text
