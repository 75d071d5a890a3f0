"""What the ``nemotron-3-nano-30b-a3b`` configuration and its cell add to the
benchmark: the configuration against the catalog's row, its arithmetic
(1.603B parameters, 16 experts, layers ``MEMEM*EME``), the traffic file,
the roofline functions on hand-counted bytes, the four readers on a reduction
with known answers and their silence where there is nothing to read, the
cell's wiring by name, its rehearsal on the CPU, and the driver's comparison
on the tiny twin: sound, and every control failing its limit."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import harness
from benchmark import rooflines_nemotron_3_nano_30b_a3b as rooflines
from benchmark import trace_reduce

REPO = Path(__file__).resolve().parent.parent.parent
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "nemotron-3-nano-30b-a3b-generate"
CONFIG = "benchmark/configs/nemotron-3-nano-30b-a3b.json"
NEW = ["gen_ssd_state_share_pct", "gen_ssd_proj_share_pct",
       "gen_ssd_step_roofline", "gen_ssd_decode_roofline"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = {
    'attention_bias': False,
    'chunk_size': 128,
    'conv_kernel': 4,
    'expand': 2,
    'head_dim': 128,
    'hidden_size': 2688,
    'hybrid_override_pattern': 'MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME',
    'intermediate_size': 1856,
    'layer_norm_epsilon': 1e-05,
    'mamba_head_dim': 64,
    'mamba_hidden_act': 'silu',
    'mamba_num_heads': 64,
    'mamba_proj_bias': False,
    'max_position_embeddings': 262144,
    'mlp_bias': False,
    'mlp_hidden_act': 'relu2',
    'model_type': 'nemotron_h',
    'moe_intermediate_size': 1856,
    'moe_shared_expert_intermediate_size': 3712,
    'n_group': 1,
    'n_groups': 8,
    'n_routed_experts': 128,
    'n_shared_experts': 1,
    'norm_eps': 1e-05,
    'norm_topk_prob': True,
    'num_attention_heads': 32,
    'num_experts_per_tok': 6,
    'num_hidden_layers': 52,
    'num_key_value_heads': 2,
    'num_logits_to_keep': 1,
    'partial_rotary_factor': 1,
    'rescale_prenorm_residual': True,
    'residual_in_fp32': False,
    'rope_theta': 10000,
    'routed_scaling_factor': 2.5,
    'sliding_window': None,
    'ssm_state_size': 128,
    'tie_word_embeddings': False,
    'time_step_floor': 0.0001,
    'time_step_max': 0.1,
    'time_step_min': 0.001,
    'topk_group': 1,
    'use_bias': False,
    'use_conv_bias': True,
    'use_mamba_kernels': True,
    'vocab_size': 131072}


@pytest.fixture(scope="module")
def cfg():
    return harness.build_configs(harness.load_cell(CELL).config)[0]


def test_the_configuration_holds_every_published_number_but_the_share():
    body = json.loads((REPO / CONFIG).read_text())
    for key, value in CATALOG.items():
        if key in ("num_hidden_layers", "n_routed_experts"):
            continue
        assert body[key] == value, key
    assert (body["num_hidden_layers"], body["n_routed_experts"]) == (9, 16)
    assert body["published"] == {"num_hidden_layers": 52,
                                 "n_routed_experts": 128}
    assert body["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "nemotron-3-nano-30b-a3b")
    assert entry["source"] == body["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
        "blob/main/config.json")
    assert entry["reduced"] == body["reduced"] and entry["file"] == CONFIG
    assert body["dtype"] == "bfloat16"
    for said in ("v5e-8", "eight chips share each layer", "16 a chip",
                 "data-parallel", "layers 0-8", "MEMEM*EME", "1.603B",
                 "3.21 GB", "pipeline stages", "whole 131,072-row"):
        assert said in body["deployment"], said
    d, t = body["dalle"], body["dalle"]["trunk"]
    assert (d["dim"], d["depth"], d["heads"], d["dim_head"],
            d["text_seq_len"], d["num_text_tokens"]) == (
        2688, 9, 32, 128, 256, 122624)
    letters = {"M": "mamba2", "E": "none", "*": "attention"}
    assert t["mixers"] == [letters[c] for c in
                           body["hybrid_override_pattern"][:9]]
    assert (t["sublayers"], t["kv_heads"], t["norm_eps"], t["ssm_state"],
            t["ssm_conv"], t["ssd_heads"], t["ssd_head_dim"],
            t["ssd_groups"], t["ssd_chunk"], t["expert_act"], t["scoring"],
            t["experts"], t["experts_per_token"], t["expert_dim"],
            t["experts_held"], t["experts_first"], t["shared_experts"],
            t["shared_dim"], t["route_scale"], t["tied_table"]) == (
        1, 2, 1e-5, 128, 4, 64, 64, 8, 128, "relu2", "sigmoid", 128, 6,
        1856, 16, 0, 1, 3712, 2.5, False)
    # every published width is the trunk's
    assert (body["mamba_num_heads"], body["mamba_head_dim"],
            body["n_groups"], body["ssm_state_size"], body["conv_kernel"],
            body["chunk_size"], body["moe_intermediate_size"],
            body["moe_shared_expert_intermediate_size"],
            body["num_experts_per_tok"], body["routed_scaling_factor"],
            body["num_key_value_heads"], body["head_dim"]) == (
        t["ssd_heads"], t["ssd_head_dim"], t["ssd_groups"], t["ssm_state"],
        t["ssm_conv"], t["ssd_chunk"], t["expert_dim"], t["shared_dim"],
        t["experts_per_token"], t["route_scale"], t["kv_heads"],
        d["dim_head"])
    assert (d["num_text_tokens"] + d["text_seq_len"]
            + body["vae"]["num_tokens"]) == body["vocab_size"]
    fmap = body["vae"]["image_size"] // 2 ** body["vae"]["num_layers"]
    assert d["text_seq_len"] + fmap ** 2 == 1280
    for key in ("layers held", "attention", "router precision",
                "initialisation", "state", "head to group", "group norm",
                "vocabulary", "experts held"):
        assert key in body["assumed"], key
    tiny = body["tiny"]["dalle"]
    assert tiny["trunk"]["ssd_chunk"] < tiny["text_seq_len"] + 1


def test_the_arithmetic_gives_the_cuts_numbers(cfg):
    from dalle_pytorch_tpu.presets import preset_param_count

    assert cfg.mixers == ("mamba2", "none", "mamba2", "none", "mamba2",
                          "attention", "none", "mamba2", "none")
    params = preset_param_count("nemotron-3-nano-30b-a3b")
    assert params == pytest.approx(1.603e9, rel=1e-3)
    assert 2 * params == pytest.approx(3.21e9, rel=2e-3)
    per = rooflines.layer_params(cfg)
    # in_proj 2,688 x 10,304, out_proj 4,096 x 2,688, the taps and the bias
    assert per["mamba2"]["matrix"] == (2688 * 10304 + 4096 * 2688
                                       + 5 * 6144)
    assert per["mamba2"]["matrix"] == pytest.approx(38.74e6, rel=1e-3)
    assert per["attention"]["matrix"] == pytest.approx(23.40e6, rel=1e-3)
    assert per["none"]["experts"] == 16 * 2 * 2688 * 1856
    assert per["none"]["experts"] / 16 == pytest.approx(9.978e6, rel=1e-3)
    assert 2 * 2688 * 3712 == pytest.approx(19.96e6, rel=1e-3)
    # the published model: 23 M + 23 E (all 128 experts) + 6 * + table, head
    whole = (23 * per["mamba2"]["matrix"] + 6 * per["attention"]["matrix"]
             + 23 * (per["none"]["matrix"] + 8 * per["none"]["experts"])
             + 2 * 131072 * 2688)
    assert whole == pytest.approx(31.58e9, rel=2e-3)


def test_the_roofline_counts_hand_counted_bytes(cfg):
    """At 256 rows: the state 4 layers x 256 rows x 2 MiB read and written
    (4.29 GB) with the update's inputs and outputs; the weights (the
    experts' 16 banks, shared expert and router x4; the projections; the
    attention; the head's 8,192 image rows); the attention layer's cache
    (2 kv heads x 128, mean reach 770 positions)."""
    state = 4 * 256 * 64 * 64 * 128 * 4 * 2
    assert state == pytest.approx(4.29e9, rel=2e-3)
    io = 4 * 256 * (6144 + 64 + 2 * 4096) * 2
    small = 4 * (3 * 64 + 4096) * 4
    assert rooflines.ssd_step_bytes(cfg, 256) == state + io + small
    assert rooflines.window_bytes(cfg, 256) == 4 * 2 * 256 * 3 * 6144 * 2
    experts = 4 * (16 * 9.978e6 + 19.96e6 + 0.344e6) * 2
    assert experts == pytest.approx(1.44e9, rel=2e-3)
    weights = rooflines.decode_weight_bytes(cfg)
    assert weights == pytest.approx(
        experts + 4 * 38.74e6 * 2 + 23.40e6 * 2 + 8192 * 2688 * 2, rel=2e-3)
    reach = 257 + 1 + 1022 / 2
    assert rooflines.decode_kv_bytes(cfg, 256) == pytest.approx(
        reach * 2 * 2 * 128 * 2 * 256)
    tick = rooflines.tick_least_s(cfg, 256, PEAKS)
    assert tick["bound"] == "bytes"
    assert tick["bytes"] == pytest.approx(6.44e9, rel=2e-3)
    assert tick["seconds"] == pytest.approx(7.87e-3, rel=2e-3)
    step = rooflines.ssd_step_least_s(cfg, 256, PEAKS)
    assert step["bound"] == "bytes"
    assert step["seconds"] == pytest.approx(5.28e-3, rel=2e-3)
    assert step["bytes"] / tick["bytes"] == pytest.approx(0.67, abs=0.01)


def test_the_traffic_file_states_the_cells_loop():
    tr = harness.load_cell(CELL).traffic
    assert tr["driver"] == "generate_nemotron_3_nano_30b_a3b"
    assert (tr["fanout"], tr["filter_thres"], tr["temperature"],
            tr["prime_codes"], tr["check_sequences"]) == (256, 0.9, 1.0, 0,
                                                          2)
    assert tr["text"] == {"kind": "random_ids", "min_len": 8, "max_len": 64}
    assert tr["fanout"] % tr["vae_decode_chunk"] == 0
    tiny = harness.load_cell(CELL, rehearse=True).traffic
    assert tiny["fanout"] % tiny["vae_decode_chunk"] == 0


HLO = "\n".join(
    f'  %{name} = f32[2]{{0}} fusion(%p), kind=kLoop, metadata={{op_name='
    f'"jit(bench_decode)/graftprof:decode-step/while/body/{path}"}}'
    for name, path in [
        ("fusion.1", "graftprof:ssd-proj/dot_general"),
        ("fusion.2", "graftprof:ssd-state/multiply"),
        ("fusion.3", "graftprof:moe-experts/dot_general"),
        ("fusion.4", "graftprof:ssd-conv/add")])


def reduction(scopes=None):
    """One traced call of the decode program, 4 ticks: per tick 2 us under
    ssd-proj, 5 under ssd-state, 2 under moe-experts, 1 under ssd-conv: 40
    us busy."""
    us, ops, t = 1000, [], 0
    for _ in range(4):
        for name, dur in (("fusion.1", 2), ("fusion.2", 5), ("fusion.3", 2),
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
        host={"rows": 256, "decode_steps_traced": 4})
    return harness.Run(cell=None, dalle_cfg=cfg, vae_cfg=None, devices=[],
                       peaks=peaks, outcome=outcome, trace=trace)


def test_readers_on_a_reduction_with_known_answers(cfg):
    run = fake_run(cfg, reduction())
    read = {name: harness.load_reader(name)(run) for name in NEW}
    assert read["gen_ssd_state_share_pct"] == pytest.approx(50.0)
    assert read["gen_ssd_proj_share_pct"] == pytest.approx(20.0)
    step = rooflines.ssd_step_least_s(cfg, 256, PEAKS)["seconds"]
    assert read["gen_ssd_step_roofline"] == pytest.approx(100 * step / 5e-6)
    tick = rooflines.tick_least_s(cfg, 256, PEAKS)["seconds"]
    assert read["gen_ssd_decode_roofline"] == pytest.approx(
        100 * tick / 10e-6)
    assert harness.load_reader("gen_moe_experts_share_pct")(run) == (
        pytest.approx(20.0))


def test_readers_are_silent_where_there_is_nothing_to_read(cfg):
    """No trace (a rehearsal), a program without the scopes (the parent's),
    no peaks, or a configuration without Mamba-2 layers: None, never an
    exception."""
    bare = reduction(scopes={})
    other = harness.build_configs(
        harness.load_cell("olmo-hybrid-7b-generate").config)[0]
    for name in NEW:
        read = harness.load_reader(name)
        assert read(fake_run(cfg, None)) is None, name
        if name != "gen_ssd_decode_roofline":
            assert read(fake_run(cfg, bare)) is None, name
    for name in ("gen_ssd_step_roofline", "gen_ssd_decode_roofline"):
        read = harness.load_reader(name)
        assert read(fake_run(cfg, reduction(), peaks=None)) is None
        assert read(fake_run(other, reduction())) is None


def test_the_cell_and_its_metrics_are_wired_by_name():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"gen_tokens_per_s",
                                                    "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= reported
    assert {"gen_decode_tick_ms", "gen_attn_scores_share_pct",
            "gen_attn_cache_share_pct", "gen_moe_experts_share_pct",
            "gen_moe_route_share_pct", "gen_vae_decode_share_pct",
            "gen_sampler_share_pct", "gen_unscoped_share_pct",
            "gen_device_idle_pct", "gen_hbm_planned_gb",
            "gen_window_compiles", "setup_trace_lower_s",
            "setup_compile_load_s", "setup_programs",
            "setup_cache_misses"} <= reported
    for m in MANIFEST["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "gen_tokens_per_s" and m["unit"] == "%"
    assert [w["name"] for w in MANIFEST["workloads"]].count(CELL) == 1
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    for entry in MANIFEST["configs"] + MANIFEST["workloads"]:
        assert len(entry["why"]) <= 200, entry["name"]
    harness.load_driver(cell)


def test_the_parent_refuses_the_configuration_at_once():
    """A ``TrunkSpec`` from before the Mamba-2 mixer knows neither the ``"mamba2"``
    mixer nor the new fields: built from this configuration's dict it
    raises, and the run exits 1."""
    trunk = harness.load_cell(CELL).config["dalle"]["trunk"]
    assert "mamba2" in trunk["mixers"] and "none" in trunk["mixers"]
    assert {"sublayers", "ssd_heads", "ssd_head_dim", "ssd_groups",
            "ssd_chunk", "expert_act", "shared_dim"} <= set(trunk)


def test_the_reference_is_plain_and_imports_nothing_from_the_program():
    text = (REPO / "benchmark/reference_nemotron_3_nano_30b_a3b.py"
            ).read_text()
    assert "import dalle_pytorch_tpu" not in text
    assert "from dalle_pytorch_tpu" not in text
    assert "Precision.HIGHEST" in text and "pallas" not in text.lower()
    assert "lax.scan" in text          # the recurrence, position by position


# --- the cell rehearses, and the comparison with its controls ---------------------

def test_the_cell_rehearses_on_the_cpu():
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3000000019", "--seconds", "0.3", "--trace", "0", "--rehearse"],
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

    from benchmark.drivers import generate_glm_4_7_flash as loop
    from benchmark.drivers import generate_nemotron_3_nano_30b_a3b as driver

    cell = harness.load_cell(CELL, rehearse=True)
    dalle_cfg, vae_cfg = harness.build_configs(cell.config)
    tr = cell.traffic
    b = loop.build(cell, dalle_cfg, vae_cfg)
    params = jax.jit(b["init_dalle"])(jax.random.PRNGKey(seed))
    prompts = harness.make_prompts(cell, dalle_cfg, sequences, seed)
    codes = np.random.default_rng(seed).integers(
        0, dalle_cfg.num_image_tokens, (sequences, dalle_cfg.image_seq_len))
    return driver.compare(
        b["dalle"], params, prompts, codes, 0, rows=np.arange(sequences),
        fanout=int(tr["fanout"]), key=jax.random.PRNGKey(seed),
        filter_thres=tr["filter_thres"], temperature=tr["temperature"])


def test_the_comparison_passes_the_program_and_every_control_fails():
    from benchmark.drivers import generate_nemotron_3_nano_30b_a3b as driver

    v = readings()
    assert v["codes_in_range"]
    # the program, bfloat16 at toy width, inside every limit but the
    # redraw's (seeded codes were never drawn from these logits)
    assert v["logit_err_std"] <= driver.LOGIT_TOL
    assert v["state_err"] <= driver.STATE_TOL
    assert v["route_reach_min"] >= 1 - driver.ROUTE_MARGIN
    assert v["route_weight_err"] <= driver.ROUTE_WEIGHT_TOL
    # each control, by the limit that is to catch it
    assert v["lowprec_err_std"] > driver.LOGIT_TOL               # e4m3
    assert v["bf16_state_err"] > driver.STATE_TOL
    assert set(v["fault_err_std"]) == set(driver.LOGIT_FAULTS)
    assert all(e > driver.LOGIT_TOL for e in v["fault_err_std"].values())
    assert set(v["fault_weight_err"]) == set(driver.WEIGHT_FAULTS)
    assert all(e > driver.ROUTE_WEIGHT_TOL
               for e in v["fault_weight_err"].values())
    assert v["fault_reach"] < 1 - driver.ROUTE_MARGIN        # shifted choice
    assert v["timed_state_err"] <= driver.STATE_TOL  # the timed path's carry
