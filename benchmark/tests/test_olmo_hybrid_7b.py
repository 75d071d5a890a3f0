"""What PR 34 adds to the benchmark: the ``olmo-hybrid-7b`` configuration's
file against the published numbers, its arithmetic, the four readers on a
reduction with known answers, their silence where the program has no
linear-attention scopes, the cell's wiring by name, and the driver's
comparison on a timed request of the tiny twin: sound, and with a fault
planted in the tick's rule or in the tiling."""
import json
from pathlib import Path

import pytest

from benchmark import harness, rooflines_olmo_hybrid_7b as rooflines
from benchmark import trace_reduce

REPO = Path(__file__).resolve().parent.parent.parent
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "olmo-hybrid-7b-generate"
NEW = ["gen_gdn_state_share_pct", "gen_gdn_proj_share_pct",
       "gen_gdn_step_roofline", "gen_gdn_decode_roofline"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def cfg():
    return harness.build_configs(harness.load_cell(CELL).config)[0]


def test_the_configuration_holds_every_published_number():
    body = json.loads(
        (REPO / "benchmark/configs/olmo-hybrid-7b.json").read_text())
    catalog = {"model_type": "olmo_hybrid", "vocab_size": 100352,
               "hidden_size": 3840, "intermediate_size": 11008,
               "num_attention_heads": 30, "num_key_value_heads": 30,
               "hidden_act": "silu", "max_position_embeddings": 65536,
               "attention_bias": False, "rms_norm_eps": 1e-6,
               "tie_word_embeddings": False, "linear_num_key_heads": 30,
               "linear_num_value_heads": 30, "linear_key_head_dim": 96,
               "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
               "linear_allow_neg_eigval": True,
               "rope_parameters": {"rope_theta": None}}
    for key, value in catalog.items():
        assert body[key] == value, key
    assert body["layer_types"] == (["linear_attention"] * 3
                                   + ["full_attention"]) * 8
    # the one cut: depth, two whole periods
    assert body["reduced"] == ["num_hidden_layers"]
    assert body["num_hidden_layers"] == 8
    assert body["published"] == {"num_hidden_layers": 32}
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "olmo-hybrid-7b")
    assert entry["reduced"] == body["reduced"]
    assert entry["source"] == body["source"]
    d, t = body["dalle"], body["dalle"]["trunk"]
    assert body["dtype"] == "bfloat16"
    assert (d["dim"], d["depth"], d["heads"], d["dim_head"]) == (
        body["hidden_size"], body["num_hidden_layers"],
        body["num_attention_heads"], 3840 // 30)
    assert (t["ff_dim"], t["kv_heads"], t["lin_key_dim"], t["lin_value_dim"],
            t["lin_conv"], t["norm_eps"], t["norm_at"], t["qk_norm"],
            t["tied_table"]) == (11008, 30, 96, 192, 4, 1e-6, "output",
                                 True, False)
    kinds = {"linear_attention": "gdn", "full_attention": "attention"}
    assert [kinds[k] for k in body["layer_types"][:4]] == t["mixers"]
    assert (d["num_text_tokens"] + d["text_seq_len"]
            + body["vae"]["num_tokens"]) == body["vocab_size"]
    lucid = json.loads((REPO / "benchmark/configs/lucid1024.json").read_text())
    assert body["vae"] == lucid["vae"]
    for convention in ("norm placement", "q/k norm", "positions",
                       "convolution", "l2 norm", "beta and decay",
                       "output gate", "vocabulary", "precision",
                       "initialisation", "vae"):
        assert len(body["assumed"][convention]) > 40, convention
    assert "8 of 32 layers" in body["deployment"]


def test_the_arithmetic_gives_the_issues_sizes(cfg):
    assert cfg.mixers.count("gdn") == 6 and cfg.mixers.count(
        "attention") == 2
    p = rooflines.decode_weight_params(cfg)
    # 6 x 215.56M + 2 x 185.80M of trunk matrices + the head's image rows
    assert p["matrix"] == pytest.approx(
        6 * 215.56e6 + 2 * 185.80e6 + 8192 * 3840, rel=1e-3)
    weights = rooflines.decode_weight_bytes(cfg)
    assert weights == pytest.approx(3.33e9 + 0.063e9, rel=3e-3)
    state = rooflines.gdn_step_bytes(cfg, 64)
    # 6 layers x 64 rows x (30 x 96 x 192 x 4 B state + 3 x 11520 x 2 B
    # window), read and written, and the taps
    assert state == pytest.approx(
        6 * 2 * 64 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
        + 6 * (4 * 11520 * 2 + (60 + 192) * 4), rel=1e-9)
    assert state == pytest.approx(1.70e9 + 0.053e9, rel=3e-3)
    kv = rooflines.decode_kv_bytes(cfg, 64)
    assert kv == pytest.approx(2 * 64 * 769.0 * 2 * 30 * 128 * 2, rel=1e-9)
    tick = rooflines.tick_least_s(cfg, 64, PEAKS)
    assert tick["bound"] == "bytes"
    assert tick["bytes"] == pytest.approx(weights + state + kv)
    assert tick["bytes"] == pytest.approx(6.66e9, rel=3e-3)
    assert tick["seconds"] == pytest.approx(8.13e-3, rel=3e-3)
    assert tick["flops"] / 197e12 < 0.3 * tick["seconds"]
    step = rooflines.gdn_step_least_s(cfg, 64, PEAKS)
    assert step["bound"] == "bytes" and step["seconds"] == pytest.approx(
        2.14e-3, rel=1e-2)
    # one row: weights dominate and the FLOPs stay far below
    assert rooflines.tick_least_s(cfg, 1, PEAKS)["bound"] == "bytes"


HLO = "\n".join(
    f'  %{name} = f32[2]{{0}} fusion(%p), kind=kLoop, metadata={{op_name='
    f'"jit(bench_decode)/graftprof:decode-step/while/body/{path}"}}'
    for name, path in [
        ("fusion.1", "graftprof:gdn-proj/dot_general"),
        ("fusion.2", "graftprof:gdn-conv/mul"),
        ("fusion.3", "graftprof:gdn-state/reduce_sum"),
        ("fusion.4", "graftprof:ff/dot_general"),
        ("fusion.5", "graftprof:attn-scores/dot_general")])


def reduction(scopes=None):
    """One traced call of the decode program, 4 ticks: per tick 2 us under
    gdn-proj, 1 under gdn-conv, 3 under gdn-state, 3 under ff, 1 under
    attn-scores: 40 us busy."""
    us, ops, t = 1000, [], 0
    for _ in range(4):
        for name, dur in (("fusion.1", 2), ("fusion.2", 1), ("fusion.3", 3),
                          ("fusion.4", 3), ("fusion.5", 1)):
            ops.append([name, t, dur * us, "jit_bench_decode"])
            t += dur * us
    raw = {"devices": [{"name": "/device:TPU:0", "ops": ops,
                        "modules": [["jit_bench_decode", 0, t]],
                        "collectives": []}], "host_spans": []}
    if scopes is None:
        scopes = {"jit_bench_decode": trace_reduce.scopes_of(HLO)}
    return trace_reduce.reduce(raw, scopes=scopes)


def fake_run(cfg, trace, peaks=PEAKS):
    outcome = harness.Outcome(
        correct=True, attempted=1, failed=0, end_to_end={},
        host={"rows": 64, "decode_steps_traced": 4})
    return harness.Run(cell=None, dalle_cfg=cfg, vae_cfg=None, devices=[],
                       peaks=peaks, outcome=outcome, trace=trace)


def test_readers_on_a_reduction_with_known_answers(cfg):
    run = fake_run(cfg, reduction())
    read = {name: harness.load_reader(name)(run) for name in NEW}
    assert read["gen_gdn_state_share_pct"] == pytest.approx(40.0)
    assert read["gen_gdn_proj_share_pct"] == pytest.approx(20.0)
    least = rooflines.gdn_step_least_s(cfg, 64, PEAKS)["seconds"]
    assert read["gen_gdn_step_roofline"] == pytest.approx(100 * least / 4e-6)
    tick = rooflines.tick_least_s(cfg, 64, PEAKS)["seconds"]
    assert read["gen_gdn_decode_roofline"] == pytest.approx(
        100 * tick / 10e-6)


def test_readers_are_silent_where_there_is_nothing_to_read(cfg):
    """No trace (a rehearsal), a program without the scopes (a checkout from
    before PR 34, whose HLO names none of them), no peaks, a configuration
    without a trunk or whose trunk has no linear layer: None, never an
    exception."""
    from dalle_pytorch_tpu import DALLEConfig

    bare = reduction(scopes={})
    plain = DALLEConfig(dim=32)
    jamba = harness.build_configs(
        harness.load_cell("jamba2-3b-generate").config)[0]
    for name in NEW:
        read = harness.load_reader(name)
        assert read(fake_run(cfg, None)) is None, name
        if name != "gen_gdn_decode_roofline":
            assert read(fake_run(cfg, bare)) is None, name
    for name in ("gen_gdn_step_roofline", "gen_gdn_decode_roofline"):
        read = harness.load_reader(name)
        assert read(fake_run(cfg, reduction(), peaks=None)) is None
        assert read(fake_run(plain, reduction())) is None
        assert read(fake_run(jamba, reduction())) is None


def test_the_cell_and_its_metrics_are_wired_by_name():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1
    assert cell.traffic["driver"] == "generate_olmo_hybrid_7b"
    assert (cell.traffic["fanout"], cell.traffic["filter_thres"],
            cell.traffic["temperature"], cell.traffic["check_sequences"]) == (
        64, 0.9, 1.0, 2)
    assert cell.traffic["text"] == {"kind": "random_ids", "min_len": 8,
                                    "max_len": 64}
    assert {m["name"] for m in cell.end_to_end} == {"gen_tokens_per_s",
                                                    "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= reported and "gen_decode_roofline" not in reported
    # every reader jamba2-3b-generate shares with the other generate cells
    shared = {m["name"] for m in MANIFEST["per_layer"]
              if {"jamba2-3b-generate", "cub200-generate"} <= set(
                  m.get("workloads", []))}
    assert shared <= reported and "gen_ff_share_pct" in shared
    for m in MANIFEST["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == (
                "gen_tokens_per_s") and m["source"] == "device_trace"
    assert MANIFEST["per_layer"][-4:] == [
        m for m in MANIFEST["per_layer"] if m["name"] in NEW]
    assert MANIFEST["workloads"][-1]["name"] == CELL
    tiny = harness.load_cell(CELL, rehearse=True)
    assert tiny.config["dalle"]["trunk"]["mixers"] == ["gdn", "gdn", "gdn",
                                                       "attention"]
    assert tiny.config["dalle"]["trunk"]["lin_value_dim"] == 16
    harness.load_driver(cell)


def test_the_reference_imports_nothing_from_the_program():
    text = (REPO / "benchmark/reference_olmo_hybrid_7b.py").read_text()
    assert "import dalle_pytorch_tpu" not in text
    assert "from dalle_pytorch_tpu" not in text


def test_each_limit_lies_between_its_two_readings():
    from benchmark.drivers import generate_olmo_hybrid_7b as driver

    assert (driver.LOGIT_READ[1] * 1.5 <= driver.LOGIT_TOL
            <= driver.LOWPREC_READ[0] / 1.5)
    # shares: the room counted in codes that come out otherwise
    assert (2 * (1 - driver.REDRAW_READ[0]) <= 1 - driver.REDRAW_SHARE
            <= (1 - driver.LOWPREC_REDRAW_READ[1]) / 2)
    assert (driver.STATE_READ[1] * 10 <= driver.STATE_TOL
            <= driver.BF16_STATE_READ[0] / 10)


# --- the comparison, with faults planted ----------------------------------------------

def timed_and_compared(fault, seed=0):
    """The driver's ``compare`` on what a timed request of the tiny twin
    returned (the twin in float32, so that the sound program redraws every
    code), with one fault planted: ``bf16_state``, the tick's rule rounding
    the state to bfloat16 after every update (in the timed scan and in the
    teacher-forced program alike); ``state_lost``, a tiling that hands the
    timed scan zero states (the timed path alone).  None: the program as it
    is."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.drivers import generate_olmo_hybrid_7b as driver
    from dalle_pytorch_tpu.models.dalle import tile_prefill
    from dalle_pytorch_tpu.ops import linear_attention as la

    cell = harness.load_cell(CELL, rehearse=True)
    config = json.loads(json.dumps(cell.config))
    config["dtype"] = config["dalle"]["trunk"]["param_dtype"] = "float32"
    dalle_cfg, vae_cfg = harness.build_configs(config)
    tr, fanout = cell.traffic, int(cell.traffic["fanout"])
    step = la.gated_delta_step
    if fault == "bf16_state":
        def rounding(*args):
            o, S = step(*args)
            return o, S.astype(jnp.bfloat16).astype(S.dtype)
        la.gated_delta_step = rounding
    try:
        b = driver.build(cell, dalle_cfg, vae_cfg)
        params = jax.jit(b["init_dalle"])(jax.random.PRNGKey(seed))
        prompts = harness.make_prompts(cell, dalle_cfg, 1, seed)
        key = jax.random.PRNGKey(seed + 1)
        first, caches = tile_prefill(
            *b["prefill"]({"params": params}, prompts), fanout)
        if fault == "state_lost":
            caches = [(cache[0], jnp.zeros_like(cache[1]))
                      if kind == "gdn" else cache
                      for kind, cache in zip(dalle_cfg.mixers, caches)]
        codes = np.asarray(b["decode"]({"params": params}, first, caches,
                                       key))
        rows = np.array([0, fanout - 1])
        return driver.compare(
            b["dalle"], params, np.repeat(prompts, 2, axis=0), codes[rows],
            rows=rows, fanout=fanout, key=key,
            filter_thres=float(tr["filter_thres"]),
            temperature=float(tr["temperature"]))
    finally:
        la.gated_delta_step = step


@pytest.mark.parametrize("fault,fails", [
    (None, set()), ("bf16_state", {"state"}), ("state_lost", {"redraw"})])
def test_the_comparison_fails_what_is_planted_and_nothing_else(fault, fails):
    """The logits see neither fault: a bfloat16 state moves them by less
    than the program's own rounding, and they are not taken from the timed
    scan.  Every control fails its limit in every case."""
    from benchmark.drivers import generate_olmo_hybrid_7b as driver

    v = timed_and_compared(fault)
    passed = {"logits": v["logit_err_std"] <= driver.LOGIT_TOL,
              "redraw": v["redraw_share"] >= driver.REDRAW_SHARE,
              "state": v["state_err"] <= driver.STATE_TOL}
    assert {name for name, ok in passed.items() if not ok} == fails, v
    assert v["ok"] is (not fails)
    assert v["lowprec_err_std"] > driver.LOGIT_TOL
    assert v["lowprec_redraw_share"] < driver.REDRAW_SHARE
    assert v["bf16_state_err"] > driver.STATE_TOL
    if fault is None:
        assert v["redraw_share"] == 1.0 and v["state_err"] < 1e-5
