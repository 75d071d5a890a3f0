"""What PR 32 adds to the benchmark: the ``smallthinker-21ba3b``
configuration against the catalog's row, its arithmetic, the four readers on
a reduction with known answers, their silence where the program has no
routed-expert scopes, the cell's wiring by name, and the driver's comparison
on the tiny twin: sound, and with a routing fault planted in the program
(``planted`` and ``readings`` also serve a run at full size on the chip:
PERF.md, Findings PR 32, gives its numbers)."""
import contextlib
import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import harness, rooflines_smallthinker_21ba3b as rooflines
from benchmark import trace_reduce

REPO = Path(__file__).resolve().parent.parent.parent
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "smallthinker-21ba3b-generate"
NEW = ["gen_moe_experts_share_pct", "gen_moe_route_share_pct",
       "gen_moe_experts_roofline", "gen_moe_decode_roofline"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
PERIOD = [0, 1, 1, 1]
CATALOG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-6, "rope_layout": PERIOD * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": PERIOD * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}


@pytest.fixture(scope="module")
def cfg():
    return harness.build_configs(harness.load_cell(CELL).config)[0]


def test_the_configuration_holds_every_published_number_but_the_depth():
    body = json.loads(
        (REPO / "benchmark/configs/smallthinker-21ba3b.json").read_text())
    for key, value in CATALOG.items():
        assert body[key] == value, key
    assert body["num_hidden_layers"] == 4
    assert body["published"] == {"num_hidden_layers": 52}
    assert body["reduced"] == ["num_hidden_layers"]
    assert body["dtype"] == "bfloat16" and "12 further chips" in body[
        "deployment"]
    d, t = body["dalle"], body["dalle"]["trunk"]
    assert (d["dim"], d["depth"], d["heads"], d["dim_head"]) == (
        body["hidden_size"], 4, body["num_attention_heads"],
        body["head_dim"])
    assert (t["kv_heads"], t["window"], t["rope_theta"], t["experts"],
            t["experts_per_token"], t["expert_dim"], t["norm_eps"],
            t["tied_table"], t["ff"]) == (
        4, 4096, 1500000, 64, 6, 768, 1e-6, False, "moe_reglu")
    # one whole period, in the published order: 0 = global and unrotated
    assert [int(m == "window") for m in t["mixers"]] == body[
        "sliding_window_layout"][:4] == body["rope_layout"][:4]
    assert (d["num_text_tokens"] + d["text_seq_len"]
            + body["vae"]["num_tokens"]) == body["vocab_size"]
    fmap = body["vae"]["image_size"] // 2 ** body["vae"]["num_layers"]
    assert d["text_seq_len"] + fmap ** 2 == 4352 > t["window"]
    for key in ("router input", "secondary experts", "bias", "rope",
                "vocabulary", "positions", "vae", "initialisation"):
        assert key in body["assumed"], key
    tiny = body["tiny"]["dalle"]
    assert tiny["trunk"]["window"] < tiny["text_seq_len"] + 1


def test_the_arithmetic_gives_the_issues_sizes(cfg):
    assert cfg.mixers == ("attention", "window", "window", "window")
    assert sum(cfg.cache_lens) == 3 * 4096 + 4352 == 16640
    assert rooflines.experts_touched(cfg, 64) == pytest.approx(
        64 * (1 - 0.90625 ** 64)) == pytest.approx(63.88, abs=0.01)
    assert rooflines.experts_touched(cfg, 1) == pytest.approx(6.0)
    experts = rooflines.moe_experts_least_s(cfg, 64, PEAKS)
    assert experts["bound"] == "bytes"
    assert experts["bytes"] == pytest.approx(3.02e9, rel=2e-3)
    assert experts["flops"] == pytest.approx(4 * 64 * 6 * 6 * 2560 * 768)
    assert rooflines.other_weight_bytes(cfg) == pytest.approx(
        4 * (20.97e6 + 0.164e6) * 2 + 8192 * 2560 * 2, rel=2e-3)
    # prompt 2,049 positions, 2,303 scan steps: step t decodes position
    # 2049 + t and reaches 2050 + t keys, the window layers 4,096 at most
    keys = rooflines.reachable_keys(cfg, 1792, 2303)
    glob = 2050 + 2302 / 2
    ring = (sum(range(2050, 4097)) + 4096 * (2303 - 2047)) / 2303
    assert keys == pytest.approx(glob + 3 * ring, rel=1e-9)
    kv = rooflines.decode_kv_bytes(cfg, 64, 1792, 2303)
    assert kv == pytest.approx(keys * 2 * 4 * 128 * 2 * 64)
    assert kv == pytest.approx(1.67e9, rel=5e-3)
    tick = rooflines.tick_least_s(cfg, 64, 1792, 2303, PEAKS)
    assert tick["bound"] == "bytes"
    assert tick["bytes"] == pytest.approx(4.90e9, rel=2e-3)
    assert tick["seconds"] == pytest.approx(5.98e-3, rel=2e-3)
    # the chosen experts' FLOPs overtake the banks' bytes past 2,600 rows
    assert rooflines.moe_experts_least_s(cfg, 2049, PEAKS)["bound"] == "bytes"
    assert rooflines.moe_experts_least_s(cfg, 4096, PEAKS)["bound"] == "flops"


HLO = "\n".join(
    f'  %{name} = f32[2]{{0}} fusion(%p), kind=kLoop, metadata={{op_name='
    f'"jit(bench_decode)/graftprof:decode-step/while/body/{path}"}}'
    for name, path in [
        ("fusion.1", "graftprof:moe-route/dot_general"),
        ("fusion.2", "graftprof:moe-experts/dot_general"),
        ("fusion.3", "graftprof:attn-scores/dot_general"),
        ("fusion.4", "graftprof:attn-cache/dynamic_update_slice")])


def reduction(scopes=None):
    """One traced call of the decode program, 4 ticks: per tick 1 us under
    moe-route, 5 under moe-experts, 3 under attn-scores, 1 under attn-cache:
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
        host={"rows": 64, "decode_steps_traced": 4, "n_prime": 1792})
    return harness.Run(cell=None, dalle_cfg=cfg, vae_cfg=None, devices=[],
                       peaks=peaks, outcome=outcome, trace=trace)


def test_readers_on_a_reduction_with_known_answers(cfg):
    run = fake_run(cfg, reduction())
    read = {name: harness.load_reader(name)(run) for name in NEW}
    assert read["gen_moe_experts_share_pct"] == pytest.approx(50.0)
    assert read["gen_moe_route_share_pct"] == pytest.approx(10.0)
    least = rooflines.moe_experts_least_s(cfg, 64, PEAKS)["seconds"]
    assert read["gen_moe_experts_roofline"] == pytest.approx(
        100 * least / 5e-6)
    tick = rooflines.tick_least_s(cfg, 64, 1792, 4, PEAKS)["seconds"]
    assert read["gen_moe_decode_roofline"] == pytest.approx(
        100 * tick / 10e-6)


def test_readers_are_silent_where_there_is_nothing_to_read(cfg):
    """No trace (a rehearsal), a program without the scopes (a checkout from
    before PR 32), no peaks, or a configuration without routed experts:
    None, never an exception."""
    from dalle_pytorch_tpu import DALLEConfig

    bare = reduction(scopes={})
    plain = DALLEConfig(dim=32)
    jamba = harness.build_configs(
        harness.load_cell("jamba2-3b-generate").config)[0]
    for name in NEW:
        read = harness.load_reader(name)
        assert read(fake_run(cfg, None)) is None, name
        if name != "gen_moe_decode_roofline":
            assert read(fake_run(cfg, bare)) is None, name
    for name in ("gen_moe_experts_roofline", "gen_moe_decode_roofline"):
        read = harness.load_reader(name)
        assert read(fake_run(cfg, reduction(), peaks=None)) is None
        assert read(fake_run(plain, reduction())) is None
        assert read(fake_run(jamba, reduction())) is None


def test_the_cell_and_its_metrics_are_wired_by_name():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1
    tr = cell.traffic
    assert tr["driver"] == "generate_smallthinker_21ba3b"
    assert (tr["fanout"], tr["filter_thres"], tr["temperature"],
            tr["prime_codes"], tr["check_sequences"]) == (64, 0.9, 1.0, 1792,
                                                          2)
    assert tr["text"] == {"kind": "random_ids", "min_len": 8, "max_len": 64}
    assert tr["prime_codes"] == 0.4375 * 4096 and tr["fanout"] % tr[
        "vae_decode_chunk"] == 0
    assert tr["tiny"]["fanout"] == 4 and tr["tiny"]["check_sequences"] == 2
    assert {m["name"] for m in cell.end_to_end} == {"gen_tokens_per_s",
                                                    "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= reported
    shared = {m["name"] for m in MANIFEST["per_layer"]
              if "cub200-generate" in m.get("workloads", [])}
    assert shared - reported == {"gen_ff_share_pct", "gen_decode_roofline"}
    assert not {m for m in reported if "ssm" in m or "hybrid" in m}
    for m in MANIFEST["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == (
                "gen_tokens_per_s")
    tiny = harness.load_cell(CELL, rehearse=True)
    trunk = tiny.config["dalle"]["trunk"]
    assert (trunk["window"], trunk["experts"], trunk["experts_per_token"],
            len(trunk["mixers"])) == (8, 8, 3, 4)
    harness.load_driver(cell)


def test_the_parent_refuses_the_configuration_at_once():
    """A ``TrunkSpec`` from before PR 32 has none of the new fields: built
    from this configuration's dict it raises (what the driver sees when it
    tries the new cell on the parent commit: exit 1, no hang)."""
    import dataclasses

    from dalle_pytorch_tpu.ops.transformer import TrunkSpec

    trunk = harness.load_cell(CELL).config["dalle"]["trunk"]
    old = {f.name for f in dataclasses.fields(TrunkSpec)} - {
        "window", "rope_theta", "experts", "experts_per_token", "expert_dim",
        "tied_table"}
    assert set(trunk) - old       # a parent's TrunkSpec(**trunk): TypeError


def test_the_reference_imports_nothing_from_the_program():
    text = (REPO / "benchmark/reference_smallthinker_21ba3b.py").read_text()
    assert "import dalle_pytorch_tpu" not in text
    assert "from dalle_pytorch_tpu" not in text


# --- the comparison, with faults planted ------------------------------------------

FAULTS = ("after_attention", "normed_input", "top5_twice")


@contextlib.contextmanager
def planted(fault):
    """The program with one routing fault: the router reading the state
    after attention (the usual placement, which this family departs from),
    the router fed the normed input, or five experts with the first taken
    twice.  None: the program as it is."""
    import jax

    from dalle_pytorch_tpu.ops import moe
    from dalle_pytorch_tpu.ops.transformer import Transformer as T

    saved = T._router_logits, T._ff, moe.route
    if fault == "after_attention":
        def _ff(self, ind, x, routed, **kw):
            return saved[1](self, ind, x,
                            self.ff_blocks[ind].router_logits(x), **kw)
        T._ff = _ff
    elif fault == "normed_input":
        def _router_logits(self, ind, x):
            return self.ff_blocks[ind].router_logits(
                self.attn_blocks[ind]._normed(x))
        T._router_logits = _router_logits
    elif fault == "top5_twice":
        def route(logits, k):
            probs, top_idx, _ = saved[2](logits, k)
            top_idx = top_idx.at[..., -1].set(top_idx[..., 0])
            kept = probs * jax.nn.one_hot(top_idx[..., :-1],
                                          probs.shape[-1]).sum(-2)
            return probs, top_idx, kept / kept.sum(-1, keepdims=True)
        moe.route = route
    else:
        assert fault is None, fault
    try:
        yield
    finally:
        T._router_logits, T._ff, moe.route = saved


def readings(fault, seed=0, rehearse=True, sequences=1):
    """The driver's ``compare`` on the cell's model (the tiny twin, or the
    whole configuration on a chip) with ``fault`` planted, over seeded
    codes in place of sampled ones (so the redraw reads nothing here)."""
    import jax

    from benchmark.drivers import generate_smallthinker_21ba3b as driver

    cell = harness.load_cell(CELL, rehearse=rehearse)
    dalle_cfg, vae_cfg = harness.build_configs(cell.config)
    tr = cell.traffic
    b = driver.build(cell, dalle_cfg, vae_cfg)
    params = jax.jit(b["init_dalle"])(jax.random.PRNGKey(seed))
    prompts = harness.make_prompts(cell, dalle_cfg, sequences, seed)
    codes = driver.make_primes(dalle_cfg, sequences, dalle_cfg.image_seq_len,
                               seed)
    with planted(fault):
        return driver.compare(
            b["dalle"], params, prompts, codes, int(tr["prime_codes"]),
            rows=np.arange(sequences), fanout=int(tr["fanout"]),
            key=jax.random.PRNGKey(seed), filter_thres=tr["filter_thres"],
            temperature=tr["temperature"])


def test_the_comparison_passes_the_program_and_fails_planted_routing():
    from benchmark.drivers import generate_smallthinker_21ba3b as driver

    def verdicts(v):
        return {"logits": v["logit_err_std"] <= driver.LOGIT_TOL,
                "first": v["route_reach_min"][0]
                >= 1 - driver.ROUTE_MARGIN_FIRST,
                "reach": min(v["route_reach_min"][1:])
                >= 1 - driver.ROUTE_MARGIN,
                "ties": v["route_tie_share"] <= driver.ROUTE_TIE_CAP}

    assert all(verdicts(readings(None)).values())
    after = verdicts(readings("after_attention"))
    assert not (after["first"] or after["reach"] or after["ties"])
    assert not verdicts(readings("top5_twice"))["ties"]
    # gains of 1 leave the normed input's ranking the input's own: the
    # sets agree and the weights do not, which the logits see
    normed = verdicts(readings("normed_input"))
    assert not normed["logits"]
