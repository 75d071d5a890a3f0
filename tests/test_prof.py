"""graftprof contract tests (ISSUE 14 / DESIGN.md §18).

Four promises are pinned here:

* the jaxpr cost walker tracks XLA's own compiled cost model — loosely at
  the elementwise-heavy tiny geometry (tier-1), within 2% at the
  matmul-dominated CUB geometry (slow, the calibration the _ZERO_FLOP
  table documents);
* the committed PERF_LEDGER.json machinery round-trips: fingerprints are
  canonical, predicted/measured rows merge without clobbering, the
  drift gate goes red on the deliberately-broken twins (a hoisted
  full-cache f32 convert, a dropped donation) and stays green on
  identical rows;
* the graftscope join works end to end on CPU: trainers' `prof.predicted`
  events render in obs_report's predicted-vs-measured section, the
  mfu_vs_predicted alert fires against the ledger reference, and
  ``prof.append_measured`` lands measured rows under the prediction's
  fingerprint;
* the chip-spec table cannot drift from lint/spmd.py's HBM budget table.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from dalle_pytorch_tpu.obs import prof

REPO = Path(__file__).resolve().parent.parent


# --- the cost walker ------------------------------------------------------


def test_scope_rejects_bad_names():
    with pytest.raises(prof.ProfError):
        prof.scope("Not A Slug")
    with prof.scope("attn-qkv"):
        pass  # valid slugs build a usable context manager


def test_every_documented_scope_is_entered_by_the_package():
    """``SCOPES`` is the contract the ledger rows enumerate: each name in it
    is entered by a ``scope("...")`` call somewhere in the package, so a
    deleted path takes its scope names with it."""
    import ast

    entered = set()
    for path in (REPO / "dalle_pytorch_tpu").rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call) and node.args
                    and getattr(node.func, "attr",
                                getattr(node.func, "id", None)) == "scope"
                    and isinstance(node.args[0], ast.Constant)):
                entered.add(node.args[0].value)
    assert set(prof.SCOPES) <= entered, set(prof.SCOPES) - entered


def test_attribute_matmul_exact_and_scoped():
    m, k, n = 8, 16, 4

    def step(x, w):
        with prof.scope("ff"):
            return x @ w

    x = jax.ShapeDtypeStruct((m, k), jnp.float32)
    w = jax.ShapeDtypeStruct((k, n), jnp.float32)
    attr = prof.attribute_fn(step, x, w)
    assert attr["scopes"]["ff"]["flops"] == 2 * m * n * k
    # bytes = operands + outputs at jaxpr shapes
    assert attr["scopes"]["ff"]["bytes"] == 4 * (m * k + k * n + m * n)
    assert attr["unattributed"] == {"flops": 0, "bytes": 0}
    prof.check_coverage(attr)  # residual 0


def test_innermost_scope_wins_and_scan_multiplies():
    L = 7

    def step(x):
        with prof.scope("decode-step"):
            def body(c, _):
                with prof.scope("attn-cache"):
                    return c @ c, None

            y, _ = jax.lax.scan(body, x, None, length=L)
        return y

    x = jax.ShapeDtypeStruct((4, 4), jnp.float32)
    attr = prof.attribute_fn(step, x)
    # the matmul inside the scan body lands on the INNER scope, once per
    # trip — not on the enclosing decode-step
    assert attr["scopes"]["attn-cache"]["flops"] == L * 2 * 4 * 4 * 4


def test_backward_equations_keep_forward_scope():
    def loss(w, x):
        with prof.scope("ff"):
            h = x @ w
        with prof.scope("loss"):
            return (h.astype(jnp.float32) ** 2).sum()

    w = jax.ShapeDtypeStruct((16, 16), jnp.bfloat16)
    x = jax.ShapeDtypeStruct((8, 16), jnp.bfloat16)
    attr = prof.attribute_fn(jax.grad(loss), w, x)
    # the transposed matmul of the backward pass still carries the ff
    # scope through jvp/transpose name-stack wrapping: fwd + bwd-wrt-w
    assert attr["scopes"]["ff"]["flops"] >= 2 * (2 * 8 * 16 * 16)
    prof.check_coverage(attr, max_residual=0.30)


def test_coverage_gate_raises_on_unscoped_program():
    def step(x):
        return x @ x

    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    attr = prof.attribute_fn(step, x)
    assert attr["residual"]["flops"] == 1.0
    with pytest.raises(prof.CoverageError, match="DESIGN.md"):
        prof.check_coverage(attr, label="unscoped")


def _tiny_dalle_step_and_args():
    from dalle_pytorch_tpu import DALLE, DALLEConfig
    from dalle_pytorch_tpu.training import make_dalle_train_step, make_optimizer

    cfg = DALLEConfig(dim=32, depth=2, heads=4, dim_head=8,
                      num_text_tokens=50, text_seq_len=8,
                      num_image_tokens=32, image_size=64, image_fmap_size=4)
    model = DALLE(cfg)
    rng = jax.random.PRNGKey(0)
    text = jnp.zeros((4, cfg.text_seq_len), jnp.int32)
    codes = jnp.zeros((4, cfg.image_seq_len), jnp.int32)
    shapes = jax.eval_shape(
        lambda r: model.init(r, text[:1], codes[:1])["params"], rng)
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    tx = make_optimizer(3e-4)
    opt_state = jax.jit(tx.init)(params)
    step = make_dalle_train_step(model, tx, jit=False)
    return cfg, step, (params, opt_state, None, text, codes, rng)


def test_attribution_tracks_compiled_cost_tiny():
    from dalle_pytorch_tpu.utils.profiling import compiled_cost_summary

    _, step, args = _tiny_dalle_step_and_args()
    attr = prof.attribute(jax.make_jaxpr(step)(*args))
    # every model cost center is scoped — the ≤5% coverage gate the
    # sweep enforces holds at the tiny geometry too
    prof.check_coverage(attr, label="dalle-tiny")
    comp = compiled_cost_summary(step, *args)
    # the tiny geometry is elementwise-heavy, so the walker (zero-flop
    # data movement, no fusion) sits a few percent from XLA's count;
    # the 2% claim is the CUB matmul regime (slow test below)
    ratio = attr["total"]["flops"] / comp["flops"]
    assert 0.85 <= ratio <= 1.10, ratio


@pytest.mark.slow
def test_attribution_within_2pct_of_compiled_at_cub():
    # the calibration behind the _ZERO_FLOP table: at a matmul-dominated
    # CUB-geometry program (the CLIP tower pair, unsharded — the one
    # sweep row whose compiled stats are whole-program, not per-shard)
    # the walker is within 2% of HloCostAnalysis at OPT0
    from dalle_pytorch_tpu.lint import spmd
    from dalle_pytorch_tpu.models.clip import CLIP, CLIPConfig
    from dalle_pytorch_tpu.training import make_clip_train_step, make_optimizer

    cfg = CLIPConfig(dim_text=256, dim_image=256, dim_latent=256,
                     num_text_tokens=7800, text_enc_depth=4, text_seq_len=80,
                     text_heads=8, num_visual_tokens=512, visual_enc_depth=6,
                     visual_heads=8, visual_image_size=224,
                     visual_patch_size=32)
    clip = CLIP(cfg)
    tx = make_optimizer(1e-3)
    B = 8
    text = jax.ShapeDtypeStruct((B, cfg.text_seq_len), jnp.int32)
    images = jax.ShapeDtypeStruct(
        (B, cfg.visual_image_size, cfg.visual_image_size, 3), jnp.float32)
    mask = jax.ShapeDtypeStruct((B, cfg.text_seq_len), jnp.bool_)
    fs = jax.ShapeDtypeStruct((), jnp.float32)
    params = jax.eval_shape(
        lambda t, im, m: clip.init(jax.random.PRNGKey(0), t, im,
                                   text_mask=m), text, images, mask)["params"]
    opt = jax.eval_shape(tx.init, params)
    step = make_clip_train_step(clip, tx, health=True)
    args = (params, opt, text, images, mask, fs)
    attr = prof.attribute(jax.make_jaxpr(step)(*args), default_scope="clip")
    with spmd.fresh_stats_compile():
        compiled = step.lower(*args).compile(
            {"xla_backend_optimization_level": 0})
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    ratio = attr["total"]["flops"] / float(ca["flops"])
    assert abs(ratio - 1.0) <= 0.02, ratio


# --- roofline + chip specs ------------------------------------------------


def test_chip_specs_pin_lint_hbm_table():
    from dalle_pytorch_tpu.lint.spmd import CHIP_HBM_BYTES

    for chip, spec in prof.CHIP_SPECS.items():
        assert CHIP_HBM_BYTES[chip] == spec.hbm_bytes, chip


def _attr(flops, nbytes, scope="ff"):
    return {"scopes": {scope: {"flops": flops, "bytes": nbytes}},
            "unattributed": {"flops": 0, "bytes": 0},
            "total": {"flops": flops, "bytes": nbytes},
            "residual": {"flops": 0.0, "bytes": 0.0}}


def test_roofline_bound_selection():
    spec = prof.CHIP_SPECS["v4-8"]
    # intensity far above the ridge: flop-bound, MFU ceiling = 1.0
    flop_bound = prof.roofline(_attr(int(1e15), int(1e9)), "v4-8")
    assert flop_bound["bound"] == "flop"
    assert flop_bound["predicted_mfu"] == pytest.approx(1.0)
    # far below: byte-bound, step time = traffic / bw
    byte_bound = prof.roofline(_attr(int(1e9), int(1e12)), "v4-8",
                               devices=1, traffic_bytes=int(1e12))
    assert byte_bound["bound"] == "byte"
    assert byte_bound["pred_step_time_s"] == pytest.approx(1e12 / spec.hbm_bw)
    assert byte_bound["predicted_mfu"] < 0.01
    with pytest.raises(prof.ProfError):
        prof.roofline(_attr(1, 1), "v9-1000")


def test_predicted_serve_bytes_per_token_matches_cost_model():
    from dalle_pytorch_tpu import DALLEConfig
    from dalle_pytorch_tpu.utils.profiling import dalle_decode_cache_bytes

    for kw in ({}, {"kv_cache_int8": True}):
        cfg = DALLEConfig(dim=32, depth=2, heads=4, dim_head=8,
                          num_text_tokens=50, text_seq_len=8,
                          num_image_tokens=32, image_size=64,
                          image_fmap_size=4, **kw)
        assert (prof.predicted_serve_bytes_per_token(cfg, 8)
                == dalle_decode_cache_bytes(cfg, 8) // 8)
    # int8 arenas count the f32 scale planes, not just the payload
    int8 = DALLEConfig(dim=32, depth=2, heads=4, dim_head=8,
                       num_text_tokens=50, text_seq_len=8,
                       num_image_tokens=32, image_size=64, image_fmap_size=4,
                       kv_cache_int8=True)
    assert (prof.predicted_serve_bytes_per_token(int8, 8) * 8
            > 2 * 2 * 8 * 4 * int8.seq_len * 8)  # > bare int8 payload


# --- fingerprints + ledger round trip -------------------------------------


def test_row_fingerprint_canonical():
    a = prof.row_fingerprint({"x": 1, "y": "z"})
    assert a == prof.row_fingerprint({"y": "z", "x": 1})  # order-free
    assert a != prof.row_fingerprint({"x": 2, "y": "z"})
    assert len(a) == 12


def test_fingerprint_payload_matches_manual_convention():
    import dataclasses

    from dalle_pytorch_tpu import DALLEConfig

    cfg = DALLEConfig(dim=32, depth=2, heads=4, dim_head=8,
                      num_text_tokens=50, text_seq_len=8,
                      num_image_tokens=32, image_size=64, image_fmap_size=4)
    # the convention train_dalle.py builds inline — the helper must hash
    # identically or trainer lookups silently miss their ledger row
    manual = {**{k: str(v) for k, v in
                 sorted(dataclasses.asdict(cfg).items())},
              "target": "dalle/dp", "plan": "dp", "batch": 16}
    helper = prof.fingerprint_payload(cfg, target="dalle/dp", plan="dp",
                                      batch=16)
    assert prof.row_fingerprint(manual) == prof.row_fingerprint(helper)


def _predicted_row(flops=1000, nbytes=500, target="t", plan="p",
                   compiled=None, config=None):
    attr = _attr(flops, nbytes)
    roof = prof.roofline(attr, "v4-8")
    return prof.predicted_row(
        target=target, plan=plan, chip="v4-8",
        config=config or {"geom": "tiny", "target": target, "plan": plan},
        attr=attr, roof=roof, compiled=compiled)


def test_ledger_round_trip_preserves_measured(tmp_path):
    p = tmp_path / "ledger.json"
    row = _predicted_row()
    ledger = prof.load_ledger(p)  # missing file -> empty schema
    assert ledger == {"v": 1, "rows": {}}
    prof.upsert_predicted(ledger, row)
    prof.save_ledger(ledger, p)
    # measured rows append under the same fingerprint, bounded history
    for i in range(12):
        prof.append_measured({"value": float(i), "unit": "img/s"},
                             fingerprint=row["fingerprint"], path=p)
    again = prof.load_ledger(p)
    hist = again["rows"][row["fingerprint"]]["measured"]
    assert len(hist) == 8  # keep_last trims
    assert hist[-1]["value"] == 11.0
    # a recomputed predicted row does NOT clobber the measured history
    prof.upsert_predicted(again, _predicted_row(flops=1001))
    prof.save_ledger(again, p)
    final = prof.load_ledger(p)
    assert len(final["rows"][row["fingerprint"]]["measured"]) == 8
    assert final["rows"][row["fingerprint"]]["total"]["flops"] == 1001
    # future-schema refusal
    p.write_text(json.dumps({"v": 99, "rows": {}}))
    with pytest.raises(prof.ProfError, match="schema"):
        prof.load_ledger(p)


def test_ledger_path_env_override(tmp_path, monkeypatch):
    monkeypatch.setenv("GRAFT_PERF_LEDGER", str(tmp_path / "scratch.json"))
    assert prof.ledger_path() == tmp_path / "scratch.json"
    monkeypatch.delenv("GRAFT_PERF_LEDGER")
    assert prof.ledger_path() == REPO / "PERF_LEDGER.json"


# --- the drift gate vs the broken twins -----------------------------------


def _cache_step_attr(hoisted_convert: bool):
    """A decode-ish cache touch: the broken twin converts the FULL cache
    to f32 and back each step (the classic silent perf bug a dtype
    refactor introduces) instead of updating the bf16 cache in place."""

    def step(cache, x):
        with prof.scope("attn-cache"):
            c = cache
            if hoisted_convert:
                c = c.astype(jnp.float32).astype(jnp.bfloat16)
            c = jax.lax.dynamic_update_slice(c, x, (0, 0))
        with prof.scope("attn-out"):
            return (c.astype(jnp.float32) ** 2).sum()

    cache = jax.ShapeDtypeStruct((64, 1024), jnp.bfloat16)
    x = jax.ShapeDtypeStruct((64, 1), jnp.bfloat16)
    return prof.attribute_fn(step, cache, x)


def test_drift_gate_catches_full_cache_f32_convert():
    config = {"geom": "tiny", "target": "decode", "plan": "single"}

    def row(attr):
        return prof.predicted_row(target="decode", plan="single",
                                  chip="v4-8", config=config, attr=attr,
                                  roof=prof.roofline(attr, "v4-8"))

    good, broken = (row(_cache_step_attr(h)) for h in (False, True))
    committed = {"v": 1, "rows": {good["fingerprint"]: good}}
    # same config fingerprint, drifted code — exactly what the gate is for
    assert prof.diff_ledger(committed, {good["fingerprint"]: good}) == []
    problems = prof.diff_ledger(committed, {good["fingerprint"]: broken})
    assert any("attn-cache bytes" in p for p in problems), problems


def test_drift_gate_catches_dropped_donation_and_new_rows():
    compiled = {"flops": 10_000, "bytes_accessed": 50_000,
                "argument_bytes": 4_000, "output_bytes": 4_000,
                "temp_bytes": 1_000, "donated_bytes": 4_000}
    good = _predicted_row(compiled=compiled)
    dropped = _predicted_row(compiled=dict(compiled, donated_bytes=0))
    committed = {"v": 1, "rows": {good["fingerprint"]: good}}
    problems = prof.diff_ledger(committed, {good["fingerprint"]: dropped})
    assert any("donated_bytes" in p for p in problems), problems
    # missing + extra fingerprints both surface
    other = _predicted_row(config={"geom": "other"})
    problems = prof.diff_ledger(committed, {other["fingerprint"]: other})
    assert any("no longer produced" in p for p in problems)
    assert any("not in the committed ledger" in p for p in problems)
    # measured-only stubs (bench rows at unswept geometries) never gate
    stub = {"fingerprint": "feedbeefcafe", "target": "t",
            "measured": [{"value": 1.0}]}
    committed["rows"]["feedbeefcafe"] = stub
    assert prof.diff_ledger(committed, {good["fingerprint"]: good}) == []


# --- the graftscope join: predicted_for, report, alert, bench --------------


def _seed_ledger(path):
    row = _predicted_row(flops=int(4e12), nbytes=int(1e12),
                         target="dalle/dp", plan="dp",
                         config={"geom": "x", "target": "dalle/dp",
                                 "plan": "dp", "batch": 16})
    ledger = {"v": 1, "rows": {}}
    prof.upsert_predicted(ledger, row)
    prof.save_ledger(ledger, path)
    return row


def test_predicted_for_exact_and_plan_fallback(tmp_path):
    p = tmp_path / "ledger.json"
    row = _seed_ledger(p)
    exact = prof.predicted_for(fingerprint=row["fingerprint"], path=p)
    assert exact["exact"] and exact["fingerprint"] == row["fingerprint"]
    assert exact["mfu"] == row["roofline"]["predicted_mfu"]
    # unknown fingerprint, known (target, plan): plan-level ceiling
    fall = prof.predicted_for(fingerprint="0" * 12, target="dalle/dp",
                              plan="dp", path=p)
    assert fall is not None and not fall["exact"]
    assert prof.predicted_for(fingerprint="0" * 12, target="nope",
                              path=p) is None
    assert prof.predicted_for(fingerprint="0" * 12,
                              path=tmp_path / "absent.json") is None


def test_report_renders_predicted_vs_measured():
    from dalle_pytorch_tpu.obs.report import build_report, render_text

    events = [{"kind": "prof", "name": "predicted", "run": "r", "host": 0,
               "t": 1.0, "fingerprint": "abcdefabcdef", "exact": True,
               "chip": "v4-8", "mfu": 0.8, "pred_step_time_s": 0.25,
               "bound": "byte", "target": "dalle/dp"}]
    events += [{"kind": "step", "name": "train", "run": "r", "host": 0,
                "t": 1.0 + i, "step": i, "mfu": 0.4, "step_time_s": 0.5}
               for i in range(1, 4)]
    rep = build_report(events)
    assert rep["prof"]["predicted_mfu"] == 0.8
    assert rep["prof"]["measured_mfu"] == 0.4
    assert rep["prof"]["attained_frac"] == pytest.approx(0.5)
    text = render_text(rep)
    assert "roofline (predicted vs measured)" in text
    assert "abcdefabcdef" in text


def test_mfu_vs_predicted_alert_fires_against_ledger_ref():
    from dalle_pytorch_tpu.obs import alerts

    rule = next(r for r in alerts.DEFAULT_RULES
                if r.name == "mfu_vs_predicted")
    eng = alerts.AlertEngine(rules=(rule,))
    fired = []
    # no reference yet: low MFU alone stays silent
    for i in range(6):
        fired += eng.observe({"kind": "step", "name": "train",
                              "mono": float(i), "mfu": 0.05, "seq": i})
    assert fired == []
    # the trainer's run-start event installs the roofline reference
    # (late enough that the pre-ref samples have aged out of the 120s
    # window — the engine evaluates on the ref record too)...
    fired += eng.observe({"kind": "prof", "name": "predicted",
                          "mono": 200.0, "mfu": 0.8, "seq": 6})
    # ...healthy steps (>= 0.5 x ceiling) stay green
    for i in range(7, 13):
        fired += eng.observe({"kind": "step", "name": "train",
                              "mono": 200.0 + i, "mfu": 0.7, "seq": i})
    assert fired == []
    for i in range(13, 19):  # attained < half the ceiling: fire
        fired += eng.observe({"kind": "step", "name": "train",
                              "mono": 400.0 + i, "mfu": 0.3, "seq": i})
    assert [a["rule"] for a in fired] == ["mfu_vs_predicted"]


def test_graftprof_report_cli(tmp_path):
    p = tmp_path / "ledger.json"
    row = _seed_ledger(p)
    prof.append_measured({"metric": "train_images_per_s", "value": 50.0,
                          "unit": "img/s", "mfu": 0.3},
                         fingerprint=row["fingerprint"], path=p)
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "graftprof.py"),
         "--report", "--ledger", str(p)],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert out.returncode == 0, out.stderr
    assert row["fingerprint"] in out.stdout
    assert "dalle/dp" in out.stdout
    # gap column: measured 0.3 over the predicted ceiling
    pred = row["roofline"]["predicted_mfu"]
    assert f"{0.3 / pred:.0%}" in out.stdout


# --- the managed capture hook ---------------------------------------------


def test_xprof_window_arming(tmp_path, monkeypatch):
    monkeypatch.delenv("GRAFT_XPROF", raising=False)
    monkeypatch.delenv("GRAFT_XPROF_WINDOW", raising=False)
    assert not prof.XprofWindow().armed  # unset env = disarmed
    monkeypatch.setenv("GRAFT_XPROF", str(tmp_path / "tr"))
    w = prof.XprofWindow()
    assert w.armed and w.logdir == str(tmp_path / "tr")
    monkeypatch.setenv("GRAFT_XPROF_WINDOW", "3:5")
    w = prof.XprofWindow(logdir=tmp_path / "tr2")
    assert (w.start, w.stop) == (3, 5)
    w.logdir = None  # the trainers' non-root disarm
    w.on_step(3)
    assert not w.active
    w.close()  # exit-path safety net is a no-op when never opened


def test_xprof_window_captures_trace(tmp_path):
    w = prof.XprofWindow(logdir=tmp_path / "trace", start=1, stop=2)
    synced = []
    w.on_step(0)
    assert not w.active
    w.on_step(1)  # window opens: jax.profiler.start_trace under the hood
    assert w.active
    jax.block_until_ready(jax.jit(lambda x: x * 2)(jnp.ones((4,))))
    w.on_step(2, sync=lambda: synced.append(True))  # closes after sync
    assert not w.active and synced == [True]
    assert (tmp_path / "trace").exists()
    w.close()  # idempotent


# --- the sampler's scope and the spans on the profiler's clock --------------


def _tiny_decode_and_args():
    from dalle_pytorch_tpu import DALLE, DALLEConfig
    from dalle_pytorch_tpu.models.dalle import (decode_codes, prefill_codes,
                                                tile_prefill)

    cfg = DALLEConfig(dim=32, depth=2, heads=4, dim_head=8,
                      num_text_tokens=50, text_seq_len=8,
                      num_image_tokens=32, image_size=64, image_fmap_size=4)
    model = DALLE(cfg)
    text = jnp.ones((1, cfg.text_seq_len), jnp.int32)
    codes = jnp.zeros((1, cfg.image_seq_len), jnp.int32)
    variables = {"params": model.init(jax.random.PRNGKey(0), text,
                                      codes)["params"]}
    first, caches = tile_prefill(*prefill_codes(model, variables, text), 2)

    def decode(v, first, caches, key):
        return decode_codes(model, v, first, caches, key, filter_thres=0.9)

    return decode, (variables, first, caches, jax.random.PRNGKey(1))


def test_sampler_has_its_own_scope_and_totals_do_not_move(monkeypatch):
    import contextlib

    from dalle_pytorch_tpu.models import dalle as dalle_mod

    decode, args = _tiny_decode_and_args()
    # the compiled program's counting loops (the top-k cut-off's, one in the
    # first code's sampler and one in the scan body's) carry the scope: what
    # the benchmark's trace_reduce.scopes_of reads to name `sample/...` in a
    # breakdown; nothing sorts (the cut-off was a `sample/sort` until PR 31)
    hlo = jax.jit(decode).lower(*args).compile().as_text()
    loops = [line for line in hlo.splitlines()
             if " while(" in line and 'graftprof:sample/while"' in line]
    assert len(loops) == 2, loops
    assert not [line for line in hlo.splitlines() if " sort(" in line]
    assert "sample" in prof.SCOPES

    attr = prof.attribute(jax.make_jaxpr(decode)(*args))
    assert attr["scopes"]["sample"]["flops"] > 0
    assert attr["scopes"]["sample"]["bytes"] > 0

    # without the scope the same equations fall to decode-step: the row's
    # totals are the same, only the attribution moves
    real_scope = prof.scope
    monkeypatch.setattr(
        dalle_mod.prof, "scope",
        lambda name: (contextlib.nullcontext() if name == "sample"
                      else real_scope(name)))
    # (a new function object: make_jaxpr caches on the function's identity)
    bare = prof.attribute(jax.make_jaxpr(lambda *a: decode(*a))(*args))
    monkeypatch.undo()
    assert "sample" not in bare["scopes"]
    assert bare["total"] == attr["total"]
    for key in ("flops", "bytes"):
        assert (bare["scopes"]["decode-step"][key]
                == attr["scopes"]["decode-step"][key]
                + attr["scopes"]["sample"][key])
    for name in ("attn-scores", "attn-cache", "ff"):
        assert bare["scopes"][name] == attr["scopes"][name]


def test_spans_are_mirrored_into_a_profiler_capture(tmp_path):
    import gzip

    from dalle_pytorch_tpu.obs import telemetry

    telemetry.init(tmp_path / "run", run_id="r")
    try:
        with prof.capture(tmp_path / "trace"):
            with telemetry.span("serve", "prefill", slot=0):
                jax.block_until_ready(jax.jit(lambda x: x * 2)(jnp.ones(4)))
    finally:
        telemetry.shutdown()
    planes = list((tmp_path / "trace").glob("plugins/profile/*/*.xplane.pb"))
    assert planes
    blob = planes[0].read_bytes()
    if blob[:2] == b"\x1f\x8b":
        blob = gzip.decompress(blob)
    assert b"graft:serve.prefill" in blob
    # the stream's own record is unchanged
    spans = [r for r in telemetry.read_events(tmp_path / "run")
             if r["kind"] == "serve" and r["name"] == "prefill"]
    assert [r["ph"] for r in spans] == ["B", "E"]
    # no stream active: the shared null span, no annotation, no allocation
    assert telemetry.span("serve", "prefill") is telemetry._NULL_SPAN
