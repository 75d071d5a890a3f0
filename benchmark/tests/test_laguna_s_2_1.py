"""What PR 40 adds to the benchmark: the ``laguna-s-2.1`` configuration against
the catalog's row, its arithmetic (the numbers of ISSUE 40), the three readers
on a reduction with known answers, their silence where there is nothing to
read, the cell's wiring by name, the cell's rehearsal on the CPU, and the
driver's comparison on the tiny twin: sound, and every control failing its
limit."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import harness, rooflines_laguna_s_2_1 as rooflines
from benchmark import trace_reduce

REPO = Path(__file__).resolve().parent.parent.parent
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "laguna-s-2.1-generate"
NEW = ["gen_swa_decode_roofline", "gen_swa_read_roofline",
       "gen_attn_gate_share_pct"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl),
#: less its four 48-entry lists (checked below by their pattern)
CATALOG = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_attention_heads": 48,
    "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "moe_apply_router_weight_on_input": False,
    "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0}


@pytest.fixture(scope="module")
def cfg():
    return harness.build_configs(harness.load_cell(CELL).config)[0]


def test_the_configuration_holds_every_published_number_but_the_share():
    body = json.loads(
        (REPO / "benchmark/configs/laguna-s-2.1.json").read_text())
    for key, value in CATALOG.items():
        assert body[key] == value, key
    global_ = [i % 4 == 0 for i in range(48)]
    assert body["layer_types"] == [
        "full_attention" if g else "sliding_attention" for g in global_]
    assert body["num_attention_heads_per_layer"] == [
        48 if g else 72 for g in global_]
    assert body["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert body["gating_types"] == ["per_head"] * 48
    assert (body["num_hidden_layers"], body["num_experts"]) == (5, 16)
    assert body["published"] == {"num_hidden_layers": 48, "num_experts": 256}
    assert body["reduced"] == ["num_hidden_layers", "num_experts"]
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "laguna-s-2.1")
    assert entry["source"] == body["source"] == (
        "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json")
    assert entry["reduced"] == body["reduced"]
    assert body["dtype"] == "bfloat16"
    assert (body["hidden_size"], body["num_attention_heads"],
            body["num_key_value_heads"], body["head_dim"],
            body["sliding_window"], body["num_experts_per_tok"],
            body["moe_routed_scaling_factor"], body["moe_intermediate_size"],
            body["shared_expert_intermediate_size"],
            body["intermediate_size"], body["vocab_size"]) == (
        3072, 48, 8, 128, 512, 10, 2.5, 1024, 1024, 12288, 100352)
    for said in ("sixteen chips share each layer", "data-parallel",
                 "16 a chip", "layers 0-4", "pipeline stages",
                 "whole vocabulary", "1.653B", "3.31 GB"):
        assert said in body["deployment"], said
    d, t = body["dalle"], body["dalle"]["trunk"]
    assert (d["dim"], d["depth"], d["heads"], d["dim_head"],
            d["text_seq_len"], d["num_text_tokens"]) == (
        3072, 5, 48, 128, 256, 91904)
    assert (t["mixers"], t["window_heads"], t["kv_heads"], t["window"],
            t["rope_theta"], t["global_rope_theta"],
            t["global_rope_fraction"], t["yarn_factor"],
            t["yarn_original_len"], t["head_gate"], t["scoring"],
            t["experts"], t["experts_per_token"], t["expert_dim"],
            t["experts_held"], t["experts_first"], t["shared_experts"],
            t["route_scale"], t["dense_layers"], t["ff_dim"],
            t["norm_eps"], t["tied_table"]) == (
        ["rotated", "window", "window", "window"], 72, 8, 512, 10000.0,
        500000.0, 0.5, 128.0, 8192, True,
        "softmax", 256, 10, 1024, 16, 0, 1, 2.5, 1, 12288, 1e-6, False)
    assert (d["num_text_tokens"] + d["text_seq_len"]
            + body["vae"]["num_tokens"]) == body["vocab_size"]
    fmap = body["vae"]["image_size"] // 2 ** body["vae"]["num_layers"]
    assert d["text_seq_len"] + fmap ** 2 == 4352
    for key in ("gate", "partial rotation", "yarn", "shared expert",
                "no q/k norm, no selection bias", "router", "window",
                "positions", "vocabulary", "absent experts"):
        assert key in body["assumed"], key
    tiny = body["tiny"]["dalle"]
    assert tiny["trunk"]["window"] < tiny["text_seq_len"] + 16
    assert (tiny["trunk"]["experts"], tiny["trunk"]["experts_held"]) == (8, 2)


def test_the_arithmetic_gives_the_issues_numbers(cfg):
    from dalle_pytorch_tpu.presets import preset_param_count

    assert cfg.mixers == ("rotated", "window", "window", "window", "rotated")
    assert cfg.cache_lens == (4352, 512, 512, 512, 4352)
    # parameters: 1.653B, 3.31 GB in bfloat16
    params = preset_param_count("laguna-s-2.1")
    assert params == pytest.approx(1.653e9, rel=1e-3)
    assert 2 * params == pytest.approx(3.31e9, rel=2e-3)
    assert rooflines.attention_params(cfg, False) == pytest.approx(
        44.19e6, rel=1e-3)
    assert rooflines.attention_params(cfg, True) == pytest.approx(
        63.14e6, rel=1e-3)
    assert rooflines.expert_params(cfg) == pytest.approx(9.437e6, rel=1e-4)
    assert rooflines.kv_bytes_per_position(cfg) == 4096
    # caches at 96 rows: the global layers 3.42 GB, the rings 0.60 GB
    assert 2 * 96 * 4352 * 4096 == pytest.approx(3.42e9, rel=2e-3)
    assert 3 * 96 * 512 * 4096 == pytest.approx(0.604e9, rel=2e-3)
    # reach: a global layer's query 3,201 positions on average, a ring's 512
    assert rooflines.reachable_positions(cfg, False, 1792, 2303) == (
        pytest.approx(np.mean([2050 + t for t in range(2303)])))
    assert rooflines.reachable_positions(cfg, True, 1792, 2303) == 512
    kv = rooflines.kv_read_bytes(cfg, 96, 1792, 2303)
    assert kv == pytest.approx(3.12e9, rel=2e-3)
    assert rooflines.experts_touched(cfg, 96) == pytest.approx(
        16 * (1 - (1 - 10 / 256) ** 96)) == pytest.approx(15.65, abs=0.01)
    weights = rooflines.weight_bytes(cfg, 96)
    assert weights == pytest.approx(2.10e9, rel=5e-3)
    tick = rooflines.tick_least_s(cfg, 96, 1792, 2303, PEAKS)
    assert tick["bound"] == "bytes"
    assert tick["seconds"] == pytest.approx(6.37e-3, rel=5e-3)
    assert kv / tick["bytes"] == pytest.approx(0.60, abs=0.01)
    read = rooflines.read_least_s(cfg, 96, 1792, 2303, PEAKS)
    assert read["bound"] == "bytes" and read["bytes"] == kv
    # without the window the three window layers would read 3.78 GB
    assert 3 * 96 * 4096 * rooflines.reachable_positions(
        cfg, False, 1792, 2303) == pytest.approx(3.78e9, rel=5e-3)


HLO = "\n".join(
    f'  %{name} = f32[2]{{0}} fusion(%p), kind=kLoop, metadata={{op_name='
    f'"jit(bench_decode)/graftprof:decode-step/while/body/{path}"}}'
    for name, path in [
        ("fusion.1", "graftprof:attn-qkv/dot_general"),
        ("fusion.2", "graftprof:attn-scores/dot_general"),
        ("fusion.3", "graftprof:moe-experts/dot_general"),
        ("fusion.4", "graftprof:attn-gate/logistic")])


def reduction(scopes=None):
    """One traced call of the decode program, 4 ticks: per tick 1 us under
    attn-qkv, 5 under attn-scores, 3 under moe-experts, 1 under attn-gate:
    40 us busy."""
    us, ops, t = 1000, [], 0
    for _ in range(4):
        for name, dur in (("fusion.1", 1), ("fusion.2", 5), ("fusion.3", 3),
                          ("fusion.4", 1)):
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
        host={"rows": 96, "decode_steps_traced": 4, "n_prime": 1792})
    return harness.Run(cell=None, dalle_cfg=cfg, vae_cfg=None, devices=[],
                       peaks=peaks, outcome=outcome, trace=trace)


def test_readers_on_a_reduction_with_known_answers(cfg):
    run = fake_run(cfg, reduction())
    read = {name: harness.load_reader(name)(run) for name in NEW}
    assert read["gen_attn_gate_share_pct"] == pytest.approx(10.0)
    least = rooflines.read_least_s(cfg, 96, 1792, 4, PEAKS)["seconds"]
    assert read["gen_swa_read_roofline"] == pytest.approx(100 * least / 5e-6)
    tick = rooflines.tick_least_s(cfg, 96, 1792, 4, PEAKS)["seconds"]
    assert read["gen_swa_decode_roofline"] == pytest.approx(
        100 * tick / 10e-6)
    # the shared readers the cell joins read the same reduction
    assert harness.load_reader("gen_attn_scores_share_pct")(run) == (
        pytest.approx(50.0))
    assert harness.load_reader("gen_moe_experts_share_pct")(run) == (
        pytest.approx(30.0))


def test_readers_are_silent_where_there_is_nothing_to_read(cfg):
    """No trace (a rehearsal), a program without the scopes, no peaks, or a
    configuration without window layers of their own head count: None,
    never an exception."""
    bare = reduction(scopes={})
    routed = harness.build_configs(
        harness.load_cell("smallthinker-21ba3b-generate").config)[0]
    for name in NEW:
        read = harness.load_reader(name)
        assert read(fake_run(cfg, None)) is None, name
        if name != "gen_swa_decode_roofline":
            assert read(fake_run(cfg, bare)) is None, name
    for name in ("gen_swa_read_roofline", "gen_swa_decode_roofline"):
        read = harness.load_reader(name)
        assert read(fake_run(cfg, reduction(), peaks=None)) is None
        assert read(fake_run(routed, reduction())) is None


def test_the_cell_and_its_metrics_are_wired_by_name():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1
    tr = cell.traffic
    assert tr["driver"] == "generate_laguna_s_2_1"
    # everything but the fan-out is the other primed trunks' traffic
    other = harness.load_cell("glm-4.7-flash-generate").traffic
    assert {k for k in tr if tr[k] != other[k]} == {"driver", "what",
                                                    "fanout"}
    assert tr["fanout"] == 96
    assert {m["name"] for m in cell.end_to_end} == {"gen_tokens_per_s",
                                                    "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= reported
    assert {"gen_decode_tick_ms", "gen_attn_scores_share_pct",
            "gen_attn_cache_share_pct", "gen_ff_share_pct",
            "gen_moe_experts_share_pct", "gen_moe_route_share_pct",
            "gen_vae_decode_share_pct", "gen_sampler_share_pct",
            "gen_unscoped_share_pct", "gen_device_idle_pct",
            "gen_hbm_planned_gb", "gen_window_compiles",
            "setup_trace_lower_s", "setup_compile_load_s", "setup_programs",
            "setup_cache_misses"} <= reported
    assert not reported & {"gen_moe_experts_roofline",
                           "gen_moe_decode_roofline", "gen_decode_roofline",
                           "gen_mla_read_roofline"}
    for m in MANIFEST["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "gen_tokens_per_s" and m["unit"] == "%"
    assert MANIFEST["workloads"][-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    for entry in MANIFEST["configs"] + MANIFEST["workloads"]:
        assert len(entry["why"]) <= 200, entry["name"]
    harness.load_driver(cell)


def test_the_parent_refuses_the_configuration_at_once():
    """A ``TrunkSpec`` from before PR 40 has none of the new fields: built
    from this configuration's dict it raises, and the run exits 1."""
    trunk = harness.load_cell(CELL).config["dalle"]["trunk"]
    assert {"scoring", "window_heads", "global_rope_theta",
            "global_rope_fraction", "yarn_factor", "head_gate"} <= set(trunk)


def test_the_reference_is_plain_and_imports_nothing_from_the_program():
    text = (REPO / "benchmark/reference_laguna_s_2_1.py").read_text()
    assert "import dalle_pytorch_tpu" not in text
    assert "from dalle_pytorch_tpu" not in text
    assert "Precision.HIGHEST" in text and "pallas" not in text.lower()


# --- the cell rehearses, and the comparison with its controls ---------------------

def test_the_cell_rehearses_on_the_cpu():
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3000000019", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False                   # a rehearsal never is
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    assert set(line["metrics"]) == {"gen_tokens_per_s", "setup_s"}


def readings(seed=0, sequences=2):
    """The driver's ``compare`` on the tiny twin over seeded codes in place
    of sampled ones (so the redraw reads nothing here)."""
    import jax

    from benchmark.drivers import generate_laguna_s_2_1 as driver

    cell = harness.load_cell(CELL, rehearse=True)
    dalle_cfg, vae_cfg = harness.build_configs(cell.config)
    tr = cell.traffic
    b = driver.build(cell, dalle_cfg, vae_cfg)
    params = jax.jit(b["init_dalle"])(jax.random.PRNGKey(seed))
    prompts = harness.make_prompts(cell, dalle_cfg, sequences, seed)
    codes = driver.make_primes(dalle_cfg, sequences, dalle_cfg.image_seq_len,
                               seed)
    return driver.compare(
        b["dalle"], params, prompts, codes, int(tr["prime_codes"]),
        rows=np.arange(sequences), fanout=int(tr["fanout"]),
        key=jax.random.PRNGKey(seed), filter_thres=tr["filter_thres"],
        temperature=tr["temperature"])


def test_the_comparison_passes_the_program_and_every_control_fails():
    from benchmark.drivers import generate_laguna_s_2_1 as driver

    v = readings()
    assert v["codes_in_range"]
    # the program, bfloat16 at toy width, inside every limit but the
    # redraw's (seeded codes were never drawn from these logits)
    assert v["logit_err_std"] <= driver.LOGIT_TOL
    assert v["kv_err"] <= driver.KV_TOL
    assert v["route_weight_err"] <= driver.ROUTE_WEIGHT_TOL
    assert min(v["route_reach_min"]) >= 1 - driver.ROUTE_MARGIN
    assert v["route_tie_share"] <= driver.ROUTE_TIE_CAP
    # each control, by the limit that is to catch it
    assert v["lowprec_err_std"] > driver.LOGIT_TOL               # e4m3
    assert set(v["fault_kv_err"]) == set(driver.ROTATION_FAULTS)
    assert all(e > driver.KV_TOL for e in v["fault_kv_err"].values())
    assert v["fault_weight_err"] > driver.ROUTE_WEIGHT_TOL     # no 2.5
    assert v["fault_reach"] < 1 - driver.ROUTE_MARGIN          # shifted
    assert set(v["fault_err_std"]) == set(driver.LOGIT_FAULTS)
    assert all(e > driver.LOGIT_TOL for e in v["fault_err_std"].values())
    assert v["controls_fail"]


def test_a_ring_slot_holds_the_last_position_of_its_residue():
    from benchmark.drivers.generate_laguna_s_2_1 import ring_positions

    held = ring_positions(4352, 512)
    assert (held % 512 == np.arange(512)).all()
    assert held.min() == 4352 - 512 and held.max() == 4351
    np.testing.assert_array_equal(ring_positions(10, 4), [8, 9, 6, 7])
