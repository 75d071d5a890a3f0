"""What PR 27 adds to the benchmark: the ``jamba2-3b`` configuration's
arithmetic, the four readers on a reduction with known answers, their silence
where the program has no state-space scopes, and the cell's wiring by name."""
import json
from pathlib import Path

import pytest

from benchmark import harness, rooflines_jamba2_3b as rooflines
from benchmark import trace_reduce

REPO = Path(__file__).resolve().parent.parent.parent
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "jamba2-3b-generate"
NEW = ["gen_ssm_scan_share_pct", "gen_ssm_proj_share_pct",
       "gen_ssm_step_roofline", "gen_hybrid_decode_roofline"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def cfg():
    return harness.build_configs(harness.load_cell(CELL).config)[0]


def test_the_configuration_holds_every_published_number():
    body = json.loads((REPO / "benchmark/configs/jamba2-3b.json").read_text())
    catalog = {"attn_layer_offset": 7, "attn_layer_period": 14,
               "hidden_size": 2560, "intermediate_size": 8192,
               "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
               "mamba_expand": 2, "num_attention_heads": 20,
               "num_hidden_layers": 28, "num_key_value_heads": 1,
               "rms_norm_eps": 1e-6, "vocab_size": 65536, "num_experts": 1,
               "tie_word_embeddings": True}
    for key, value in catalog.items():
        assert body[key] == value, key
    d, t = body["dalle"], body["dalle"]["trunk"]
    assert body["reduced"] == [] and body["dtype"] == "bfloat16"
    assert (d["dim"], d["depth"], d["heads"], d["dim_head"]) == (
        body["hidden_size"], body["num_hidden_layers"],
        body["num_attention_heads"], 2560 // 20)
    assert (t["ff_dim"], t["kv_heads"], t["ssm_expand"], t["ssm_state"],
            t["ssm_conv"], t["ssm_dt_rank"], t["norm_eps"]) == (
        8192, 1, 2, 16, 4, 160, 1e-6)
    assert len(t["mixers"]) == body["attn_layer_period"]
    assert [i for i, m in enumerate(t["mixers"]) if m == "attention"] == [
        body["attn_layer_offset"]]
    assert (d["num_text_tokens"] + d["text_seq_len"]
            + body["vae"]["num_tokens"]) == body["vocab_size"]
    lucid = json.loads((REPO / "benchmark/configs/lucid1024.json").read_text())
    assert body["vae"] == lucid["vae"]


def test_the_arithmetic_gives_the_issues_sizes(cfg):
    assert cfg.mixers.count("mamba") == 26 and cfg.mixers.count(
        "attention") == 2
    weights = rooflines.decode_weight_bytes(cfg)
    assert weights == pytest.approx(5.77e9, rel=2e-3)
    state = rooflines.ssm_step_bytes(cfg, 128)
    # 26 layers x 128 rows x 5120 channels x (16 x 4 + 3 x 2) bytes, read
    # and written, and 0.1 GB of small tensors
    assert state == pytest.approx(
        26 * (2 * 128 * 5120 * (16 * 4 + 3 * 2)) + 26 * 4.17e6, rel=2e-3)
    kv = rooflines.decode_kv_bytes(cfg, 128)
    assert kv == pytest.approx(2 * 128 * 769.0 * 2 * 128 * 2, rel=1e-6)
    tick = rooflines.hybrid_tick_least_s(cfg, 128, PEAKS)
    assert tick["bound"] == "bytes"
    assert tick["seconds"] == pytest.approx((weights + state + kv) / 819e9)
    assert tick["seconds"] == pytest.approx(10.2e-3, rel=1e-2)
    assert tick["flops"] / 197e12 == pytest.approx(3.77e-3, rel=1e-2)
    step = rooflines.ssm_step_least_s(cfg, 128, PEAKS)
    assert step["bound"] == "bytes" and step["seconds"] == pytest.approx(
        3.04e-3, rel=1e-2)
    # one row: weights dominate and the FLOPs stay far below
    assert rooflines.hybrid_tick_least_s(cfg, 1, PEAKS)["bound"] == "bytes"


HLO = "\n".join(
    f'  %{name} = f32[2]{{0}} fusion(%p), kind=kLoop, metadata={{op_name='
    f'"jit(bench_decode)/graftprof:decode-step/while/body/{path}"}}'
    for name, path in [
        ("fusion.1", "graftprof:ssm-proj/dot_general"),
        ("fusion.2", "graftprof:ssm-conv/mul"),
        ("fusion.3", "graftprof:ssm-scan/exp"),
        ("fusion.4", "graftprof:ff/dot_general"),
        ("fusion.5", "graftprof:attn-scores/dot_general")])


def reduction(scopes=None):
    """One traced call of the decode program, 4 ticks: per tick 2 us under
    ssm-proj, 1 under ssm-conv, 3 under ssm-scan, 3 under ff, 1 under
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
        host={"rows": 128, "decode_steps_traced": 4})
    return harness.Run(cell=None, dalle_cfg=cfg, vae_cfg=None, devices=[],
                       peaks=peaks, outcome=outcome, trace=trace)


def test_readers_on_a_reduction_with_known_answers(cfg):
    run = fake_run(cfg, reduction())
    read = {name: harness.load_reader(name)(run) for name in NEW}
    assert read["gen_ssm_scan_share_pct"] == pytest.approx(40.0)
    assert read["gen_ssm_proj_share_pct"] == pytest.approx(20.0)
    least = rooflines.ssm_step_least_s(cfg, 128, PEAKS)["seconds"]
    assert read["gen_ssm_step_roofline"] == pytest.approx(
        100 * least / 4e-6)
    tick = rooflines.hybrid_tick_least_s(cfg, 128, PEAKS)["seconds"]
    assert read["gen_hybrid_decode_roofline"] == pytest.approx(
        100 * tick / 10e-6)


def test_readers_are_silent_where_there_is_nothing_to_read(cfg):
    """No trace (a rehearsal), a program without the scopes (a checkout from
    before PR 27, whose HLO names none of them), no peaks, or a configuration
    without a trunk: None, never an exception."""
    from dalle_pytorch_tpu import DALLEConfig

    bare = reduction(scopes={})
    plain = DALLEConfig(dim=32)
    for name in NEW:
        read = harness.load_reader(name)
        assert read(fake_run(cfg, None)) is None, name
        if name != "gen_hybrid_decode_roofline":
            assert read(fake_run(cfg, bare)) is None, name
    for name in ("gen_ssm_step_roofline", "gen_hybrid_decode_roofline"):
        assert harness.load_reader(name)(
            fake_run(cfg, reduction(), peaks=None)) is None
    assert harness.load_reader("gen_hybrid_decode_roofline")(
        fake_run(plain, reduction())) is None


def test_the_cell_and_its_metrics_are_wired_by_name():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1
    assert cell.traffic["driver"] == "generate_jamba2_3b"
    assert (cell.traffic["fanout"], cell.traffic["filter_thres"],
            cell.traffic["temperature"], cell.traffic["check_sequences"]) == (
        128, 0.9, 1.0, 4)
    assert cell.traffic["text"] == {"kind": "random_ids", "min_len": 8,
                                    "max_len": 64}
    assert {m["name"] for m in cell.end_to_end} == {"gen_tokens_per_s",
                                                    "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= reported and "gen_decode_roofline" not in reported
    shared = {m["name"] for m in MANIFEST["per_layer"]
              if "cub200-generate" in m.get("workloads", [])}
    assert shared - reported == {"gen_decode_roofline"}
    for m in MANIFEST["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == (
                "gen_tokens_per_s")
    tiny = harness.load_cell(CELL, rehearse=True)
    assert tiny.config["dalle"]["trunk"]["mixers"] == ["mamba", "attention",
                                                       "mamba"]
    assert tiny.config["dalle"]["trunk"]["kv_heads"] == 1
    harness.load_driver(cell)


def test_the_reference_imports_nothing_from_the_program():
    text = (REPO / "benchmark/reference_jamba2_3b.py").read_text()
    assert "import dalle_pytorch_tpu" not in text
    assert "from dalle_pytorch_tpu" not in text
