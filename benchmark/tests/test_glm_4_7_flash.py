"""What PR 38 adds to the benchmark: the ``glm-4.7-flash`` configuration
against the catalog's row, its arithmetic (the numbers of ISSUE 38), the four
readers on a reduction with known answers, their silence where the program has
no latent-attention scopes, the cell's wiring by name, the cell's rehearsal on
the CPU, and the driver's comparison on the tiny twin: sound, and every
control failing its limit."""
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import harness, rooflines_glm_4_7_flash as rooflines
from benchmark import trace_reduce

REPO = Path(__file__).resolve().parent.parent.parent
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "glm-4.7-flash-generate"
NEW = ["gen_mla_read_share_pct", "gen_mla_proj_share_pct",
       "gen_mla_read_roofline", "gen_mla_decode_roofline"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_shared_experts": 1, "routed_scaling_factor": 1.8,
    "num_experts_per_tok": 4, "first_k_dense_replace": 1,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}


@pytest.fixture(scope="module")
def cfg():
    return harness.build_configs(harness.load_cell(CELL).config)[0]


def test_the_configuration_holds_every_published_number_but_the_share():
    body = json.loads(
        (REPO / "benchmark/configs/glm-4.7-flash.json").read_text())
    for key, value in CATALOG.items():
        assert body[key] == value, key
    assert (body["num_hidden_layers"], body["n_routed_experts"]) == (5, 8)
    assert body["published"] == {"num_hidden_layers": 47,
                                 "n_routed_experts": 64}
    assert body["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "glm-4.7-flash")
    assert entry["source"] == body["source"] == (
        "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json")
    assert entry["reduced"] == body["reduced"]
    assert body["dtype"] == "bfloat16"
    for said in ("eight chips share each layer", "data-parallel",
                 "8 a chip", "layers 0-4", "pipeline stages",
                 "whole vocabulary"):
        assert said in body["deployment"], said
    d, t = body["dalle"], body["dalle"]["trunk"]
    assert (d["dim"], d["depth"], d["heads"], d["dim_head"]) == (
        2048, 5, 20, 192 + 64)
    assert (t["q_rank"], t["kv_rank"], t["nope_dim"], t["rope_dim"],
            t["value_dim"], t["ff_dim"], t["experts"],
            t["experts_per_token"], t["expert_dim"], t["experts_held"],
            t["experts_first"], t["shared_experts"], t["route_scale"],
            t["dense_layers"], t["rope_theta"], t["norm_eps"],
            t["tied_table"], t["ff"], t["mixers"]) == (
        768, 512, 192, 64, 256, 10240, 64, 4, 1536, 8, 0, 1, 1.8, 1, 1e6,
        1e-5, False, "moe_swiglu_shared", ["mla"])
    assert (d["num_text_tokens"] + d["text_seq_len"]
            + body["vae"]["num_tokens"]) == body["vocab_size"]
    fmap = body["vae"]["image_size"] // 2 ** body["vae"]["num_layers"]
    assert d["text_seq_len"] + fmap ** 2 == 4352
    for key in ("rotation pairing", "vocabulary", "selection bias",
                "initialisation", "precision", "vae",
                "next-token prediction", "absent experts", "router"):
        assert key in body["assumed"], key
    tiny = body["tiny"]["dalle"]["trunk"]
    sizes = [tiny[k] for k in ("q_rank", "kv_rank", "nope_dim", "rope_dim",
                               "value_dim")]
    assert len(set(sizes)) == 5
    assert (tiny["experts"], tiny["experts_held"],
            tiny["experts_per_token"]) == (8, 2, 2)
    assert body["tiny"]["dalle"]["depth"] == 3       # 1 dense + 2 routed


def test_the_arithmetic_gives_the_issues_numbers(cfg):
    assert cfg.mixers == ("mla",) * 5 and sum(cfg.cache_lens) == 5 * 4352
    assert rooflines.latent_bytes_per_position(cfg) == 1152
    assert rooflines.latent_flops_per_position(cfg) == 20 * (576 + 512) * 2
    # prompt 2,049 positions, 2,303 scan steps: step t decodes position
    # 2049 + t and reaches 2050 + t positions
    assert rooflines.reachable_positions(cfg, 1792, 2303) == pytest.approx(
        np.mean([2050 + t for t in range(2303)])) == pytest.approx(3201.0)
    latent = rooflines.latent_read_bytes(cfg, 128, 1792, 2303)
    assert latent == pytest.approx(2.36e9, rel=2e-3)
    assert rooflines.latent_read_flops(cfg, 128, 1792, 2303) == (
        pytest.approx(89.2e9, rel=2e-3))
    # the whole cache: 3.21 GB where 20 heads of 256 + 256 would be 57 GB
    assert 5 * 128 * 4352 * 1152 == pytest.approx(3.21e9, rel=2e-3)
    assert 5 * 128 * 4352 * 20 * 512 * 2 == pytest.approx(57e9, rel=2e-3)
    assert rooflines.attention_params(cfg) == pytest.approx(21.76e6, rel=1e-3)
    assert rooflines.expert_params(cfg) == pytest.approx(9.437e6, rel=1e-4)
    assert rooflines.experts_touched(cfg, 128) == pytest.approx(
        8 * (1 - 0.9375 ** 128)) == pytest.approx(7.998, abs=1e-3)
    assert rooflines.experts_touched(cfg, 1) == pytest.approx(0.5)
    assert rooflines.weight_bytes(cfg, 128) == pytest.approx(1.06e9, rel=5e-3)
    tick = rooflines.tick_least_s(cfg, 128, 1792, 2303, PEAKS)
    assert tick["bound"] == "bytes"
    assert latent / tick["bytes"] == pytest.approx(0.69, abs=0.005)
    assert tick["seconds"] == pytest.approx(4.17e-3, rel=5e-3)
    read = rooflines.mla_read_least_s(cfg, 128, 1792, 2303, PEAKS)
    assert read["bound"] == "bytes"
    assert read["flops"] / PEAKS["bf16_flops"] == pytest.approx(0.45e-3,
                                                                rel=0.01)
    # a tick that decompressed every cached position instead
    assert (5 * 128 * 4352 * 512 * 8960 * 2) == pytest.approx(25.6e12,
                                                               rel=2e-3)


HLO = "\n".join(
    f'  %{name} = f32[2]{{0}} fusion(%p), kind=kLoop, metadata={{op_name='
    f'"jit(bench_decode)/graftprof:decode-step/while/body/{path}"}}'
    for name, path in [
        ("fusion.1", "graftprof:mla-proj/dot_general"),
        ("fusion.2", "graftprof:mla-read/dot_general"),
        ("fusion.3", "graftprof:moe-experts/dot_general"),
        ("fusion.4", "graftprof:attn-cache/dynamic_update_slice")])


def reduction(scopes=None):
    """One traced call of the decode program, 4 ticks: per tick 1 us under
    mla-proj, 5 under mla-read, 3 under moe-experts, 1 under attn-cache: 40
    us busy."""
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
        host={"rows": 128, "decode_steps_traced": 4, "n_prime": 1792})
    return harness.Run(cell=None, dalle_cfg=cfg, vae_cfg=None, devices=[],
                       peaks=peaks, outcome=outcome, trace=trace)


def test_readers_on_a_reduction_with_known_answers(cfg):
    run = fake_run(cfg, reduction())
    read = {name: harness.load_reader(name)(run) for name in NEW}
    assert read["gen_mla_read_share_pct"] == pytest.approx(50.0)
    assert read["gen_mla_proj_share_pct"] == pytest.approx(10.0)
    least = rooflines.mla_read_least_s(cfg, 128, 1792, 4, PEAKS)["seconds"]
    assert read["gen_mla_read_roofline"] == pytest.approx(100 * least / 5e-6)
    tick = rooflines.tick_least_s(cfg, 128, 1792, 4, PEAKS)["seconds"]
    assert read["gen_mla_decode_roofline"] == pytest.approx(
        100 * tick / 10e-6)
    # the shared readers the cell joins read the same reduction
    assert harness.load_reader("gen_moe_experts_share_pct")(run) == (
        pytest.approx(30.0))
    assert harness.load_reader("gen_attn_cache_share_pct")(run) == (
        pytest.approx(10.0))


def test_readers_are_silent_where_there_is_nothing_to_read(cfg):
    """No trace (a rehearsal), a program without the scopes (a checkout from
    before PR 38, as the driver runs the traced cells on the parent), no
    peaks, or a configuration without latent layers: None, never an
    exception."""
    from dalle_pytorch_tpu import DALLEConfig

    bare = reduction(scopes={})
    plain = DALLEConfig(dim=32)
    routed = harness.build_configs(
        harness.load_cell("smallthinker-21ba3b-generate").config)[0]
    for name in NEW:
        read = harness.load_reader(name)
        assert read(fake_run(cfg, None)) is None, name
        if name != "gen_mla_decode_roofline":
            assert read(fake_run(cfg, bare)) is None, name
    for name in ("gen_mla_read_roofline", "gen_mla_decode_roofline"):
        read = harness.load_reader(name)
        assert read(fake_run(cfg, reduction(), peaks=None)) is None
        assert read(fake_run(plain, reduction())) is None
        assert read(fake_run(routed, reduction())) is None


def test_the_cell_and_its_metrics_are_wired_by_name():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1
    tr = cell.traffic
    assert tr["driver"] == "generate_glm_4_7_flash"
    assert (tr["fanout"], tr["filter_thres"], tr["temperature"],
            tr["prime_codes"], tr["check_sequences"],
            tr["vae_decode_chunk"]) == (128, 0.9, 1.0, 1792, 2, 16)
    assert tr["text"] == {"kind": "random_ids", "min_len": 8, "max_len": 64}
    # everything but the fan-out is the other routed trunk's traffic
    other = harness.load_cell("smallthinker-21ba3b-generate").traffic
    differ = {k for k in tr if tr[k] != other[k]}
    assert differ == {"driver", "what", "fanout"}
    assert {m["name"] for m in cell.end_to_end} == {"gen_tokens_per_s",
                                                    "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= reported
    assert {"gen_decode_tick_ms", "gen_attn_cache_share_pct",
            "gen_ff_share_pct", "gen_moe_experts_share_pct",
            "gen_moe_route_share_pct", "gen_vae_decode_share_pct",
            "gen_sampler_share_pct", "gen_unscoped_share_pct",
            "gen_device_idle_pct", "gen_hbm_planned_gb",
            "gen_window_compiles", "setup_trace_lower_s",
            "setup_compile_load_s", "setup_programs",
            "setup_cache_misses"} <= reported
    assert not reported & {"gen_attn_scores_share_pct",
                           "gen_moe_experts_roofline",
                           "gen_moe_decode_roofline", "gen_decode_roofline"}
    for m in MANIFEST["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "gen_tokens_per_s" and m["unit"] == "%"
    assert len(MANIFEST["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    assert MANIFEST["workloads"][-1]["name"] == CELL
    harness.load_driver(cell)


def test_the_parent_refuses_the_configuration_at_once():
    """A ``TrunkSpec`` from before PR 38 has none of the new fields: built
    from this configuration's dict it raises (and before that, the parent's
    manifest has no such workload: exit 1, no hang)."""
    trunk = harness.load_cell(CELL).config["dalle"]["trunk"]
    assert {"q_rank", "kv_rank", "nope_dim", "rope_dim", "value_dim",
            "dense_layers", "experts_held", "experts_first",
            "shared_experts", "route_scale"} <= set(trunk)


def test_the_reference_is_plain_and_imports_nothing_from_the_program():
    text = (REPO / "benchmark/reference_glm_4_7_flash.py").read_text()
    assert "import dalle_pytorch_tpu" not in text
    assert "from dalle_pytorch_tpu" not in text
    assert "Precision.HIGHEST" in text and "pallas" not in text.lower()


# --- the cell rehearses, and the comparison with its controls ---------------------

def test_the_cell_rehearses_on_the_cpu():
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3000000019", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=900,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False                   # a rehearsal never is
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    assert set(line["metrics"]) == {"gen_tokens_per_s", "setup_s"}


def readings(seed=0, rehearse=True, sequences=2):
    """The driver's ``compare`` on the cell's model (the tiny twin, or the
    whole configuration on a chip) over seeded codes in place of sampled ones
    (so the redraw reads nothing here)."""
    import jax

    from benchmark.drivers import generate_glm_4_7_flash as driver

    cell = harness.load_cell(CELL, rehearse=rehearse)
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
    from benchmark.drivers import generate_glm_4_7_flash as driver

    v = readings()
    assert v["codes_in_range"]
    # the program, bfloat16 at toy width, inside every limit but the
    # redraw's (seeded codes were never drawn from these logits)
    assert v["logit_err_std"] <= driver.LOGIT_TOL
    assert v["latent_err"] <= driver.LATENT_TOL
    assert v["rope_err"] <= driver.ROPE_TOL
    assert v["route_weight_err"] <= driver.ROUTE_WEIGHT_TOL
    assert min(v["route_reach_min"]) >= 1 - driver.ROUTE_MARGIN
    assert v["route_tie_share"] <= driver.ROUTE_TIE_CAP
    # each control, by the limit that is to catch it
    assert v["lowprec_err_std"] > driver.LOGIT_TOL               # e4m3
    assert v["fault_latent_err"] > driver.LATENT_TOL      # un-normed latent
    assert v["fault_rope_err"] > driver.ROPE_TOL          # un-rotated key
    assert v["fault_weight_err"] > driver.ROUTE_WEIGHT_TOL  # bias in weights
    assert set(v["fault_err_std"]) == {"no_shared_expert", "other_experts"}
    assert all(e > driver.LOGIT_TOL for e in v["fault_err_std"].values())
    assert v["controls_fail"] == (v["lowprec_redraw_share"]
                                  < driver.REDRAW_SHARE)
