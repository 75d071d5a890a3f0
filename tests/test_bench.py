"""bench.py: the stage runner, the measurement builders at toy size, and
the perf_ab / collect_ab tools around it.

The real measurement needs the TPU chip; here ``run`` and the builders are
monkeypatched or shrunk.  What is pinned: every record names the device it
ran on, a failing stage ends the process non-zero, and nothing the suite
does reaches the committed history or ledger.
"""
from __future__ import annotations

import json

import pytest

import bench


def _tiny_cfg():
    import jax.numpy as jnp

    from dalle_pytorch_tpu import DALLEConfig

    return DALLEConfig(dim=32, num_text_tokens=64, text_seq_len=8, depth=2,
                       heads=2, dim_head=16, attn_types=("full",),
                       num_image_tokens=32, image_size=32, image_fmap_size=4,
                       dtype=jnp.float32)


@pytest.fixture
def fake_stages(monkeypatch, tmp_path):
    """main() with the headline and the generation builders replaced by
    constants — and the ledger + history pointed into tmp_path, so no run
    of the suite can append to the committed PERF_LEDGER.json or
    all-logs-tpu/bench-history.jsonl."""
    cfg = _tiny_cfg()
    monkeypatch.setenv("GRAFT_PERF_LEDGER", str(tmp_path / "ledger.json"))
    monkeypatch.setenv("BENCH_HISTORY", str(tmp_path / "hist.jsonl"))
    monkeypatch.setattr(bench, "run",
                        lambda steps=bench.STEPS: (42.5, 1.0, cfg, 16))
    monkeypatch.setattr(
        bench, "make_gen_measure_deferred",
        lambda batch=8: ((lambda: (lambda: (1.0, 1.0))), cfg))
    return tmp_path


def test_main_json_names_the_device(fake_stages, capsys):
    """The driver-facing JSON line (with self-describing meta) is the only
    thing on stdout, and carries platform, device_kind and device count."""
    import jax

    bench.main()
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    parsed = json.loads(out[0])
    assert parsed["value"] == 42.5
    assert parsed["meta"] == {"steps": bench.STEPS, "batch": 16,
                              "codes_path": True, "use_pallas": False}
    assert parsed["platform"] == "cpu"
    assert parsed["device_kind"] == jax.devices()[0].device_kind
    assert parsed["device_count"] == len(jax.devices())


def test_bench_steps_env_reaches_run(fake_stages, monkeypatch, capsys):
    seen = {}
    cfg = _tiny_cfg()

    def run(steps=bench.STEPS):
        seen["steps"] = steps
        return 42.5, 1.0, cfg, 16

    monkeypatch.setattr(bench, "run", run)
    monkeypatch.setenv("BENCH_STEPS", "7")
    bench.main()
    assert seen == {"steps": 7}
    assert json.loads(capsys.readouterr().out)["meta"]["steps"] == 7


@pytest.mark.parametrize("stage", ["headline", "generation", "history"])
def test_a_failing_stage_ends_the_run(fake_stages, monkeypatch, stage):
    """No stage failure can end in exit 0: whatever a stage raises
    propagates out of main() — the headline, an informational stage after
    the JSON is out, and the history write alike."""
    def boom(*a, **k):
        raise RuntimeError(f"{stage} boom")

    if stage == "headline":
        monkeypatch.setattr(bench, "run", boom)
    elif stage == "generation":
        monkeypatch.setattr(bench, "make_gen_measure_deferred",
                            lambda batch=8: (boom, _tiny_cfg()))
    else:
        monkeypatch.setattr(bench, "record_history", boom)
    with pytest.raises(RuntimeError, match=f"{stage} boom"):
        bench.main()


def test_no_exception_swallowing_left_in_bench():
    """Stages are plain calls: bench.py holds no ``except`` at all (the
    probe / wedge guard / watchdog / retry layer is gone)."""
    import ast
    from pathlib import Path

    tree = ast.parse(Path(bench.__file__).read_text())
    assert not [n for n in ast.walk(tree) if isinstance(n, ast.Try)]
    for gone in ("_run_with_retry", "_bounded_device_call", "_wedge_guard",
                 "_probe_in_process", "FIRST_STEPS"):
        assert not hasattr(bench, gone), gone


@pytest.mark.slow
def test_perf_ab_tool(monkeypatch, capsys):
    """tools/perf_ab.py runs interleaved variants end-to-end (tiny config)."""
    from pathlib import Path

    monkeypatch.syspath_prepend(str(Path(__file__).resolve().parent.parent
                                    / "tools"))
    import jax.numpy as jnp

    import perf_ab
    from dalle_pytorch_tpu import DALLEConfig

    def tiny_config(use_pallas=False):
        return DALLEConfig(
            dim=32, num_text_tokens=64, text_seq_len=8, depth=2, heads=2,
            dim_head=16, attn_types=("full", "axial_row"),
            num_image_tokens=32, image_size=32, image_fmap_size=4,
            use_pallas=use_pallas, dtype=jnp.float32)

    monkeypatch.setattr(bench, "cub200_config", tiny_config)
    seen_batches = {}
    real_mtm = bench.make_train_measure

    def spying_mtm(steps, batch=16, **overrides):
        seen_batches[batch] = True
        return real_mtm(steps, batch=batch, **overrides)

    monkeypatch.setattr(bench, "make_train_measure", spying_mtm)
    assert perf_ab.main(["--list"]) == 0
    assert perf_ab.main(["baseline", "full-attn", "batch64", "--reps", "2",
                         "--steps", "2"]) == 0
    out = capsys.readouterr().out
    assert "medians:" in out and "baseline" in out and "full-attn" in out
    # the batch64 variant's override must actually reach make_train_measure
    assert seen_batches == {16: True, 64: True}

    seen_gen_calls = []
    real_mgm = bench.make_gen_measure_deferred  # what perf_ab builds from

    def spying_mgm(batch=8, **overrides):
        seen_gen_calls.append((batch, overrides))
        return real_mgm(batch=batch, **overrides)

    monkeypatch.setattr(bench, "make_gen_measure_deferred", spying_mgm)
    assert perf_ab.main(["gen", "gen64", "--reps", "1"]) == 0
    out = capsys.readouterr().out
    assert "tok/s" in out
    assert seen_gen_calls == [(8, {}), (64, {})]

    # gen-dense must select the dense-cache control through the CONFIG
    # (sliced_kv_decode=False) — the choice rides the traced model config,
    # so a retrace can never silently measure the sliced path (the r3
    # monkeypatch-around-the-compile approach this replaced)
    seen_gen_calls.clear()
    assert perf_ab.main(["gen-dense", "--reps", "1"]) == 0
    assert seen_gen_calls == [(8, {"sliced_kv_decode": False})]

    # the bf16-KV-cache A/B pair rides the traced config the same way:
    # f32 activations (the eval dtype) with the cache knob on vs off
    seen_gen_calls.clear()
    assert perf_ab.main(["gen_bf16", "gen_f32cache", "--reps", "1"]) == 0
    assert seen_gen_calls == [
        (8, {"dtype": jnp.float32, "kv_cache_bf16": True}),
        (8, {"dtype": jnp.float32, "kv_cache_bf16": False})]


def test_perf_ab_rejects_bad_args(monkeypatch, capsys):
    from pathlib import Path

    monkeypatch.syspath_prepend(str(Path(__file__).resolve().parent.parent
                                    / "tools"))
    import perf_ab

    with pytest.raises(SystemExit):  # typo'd variant -> usage error, fast
        perf_ab.main(["palas"])
    with pytest.raises(SystemExit):
        perf_ab.main(["baseline", "--reps", "0"])
    with pytest.raises(SystemExit):  # repeated names would silently collapse
        perf_ab.main(["baseline", "baseline"])


def test_env_flag_semantics(monkeypatch):
    """Boolean env knobs must be OFF-able: X=0/false/no/off (any case)
    parse as False; bool(os.environ.get(X)) treated '0' as ON (the
    BENCH_PALLAS / GRAFT_DRYRUN_FULL footgun of review round 5)."""
    from dalle_pytorch_tpu.utils.helpers import env_flag

    monkeypatch.delenv("X_FLAG", raising=False)
    assert env_flag("X_FLAG") is False
    assert env_flag("X_FLAG", default=True) is True
    for off in ("0", "false", "no", "off", "", "False", " 0 ", "OFF"):
        monkeypatch.setenv("X_FLAG", off)
        assert env_flag("X_FLAG") is False, repr(off)
        assert env_flag("X_FLAG", default=True) is False, repr(off)
    for on in ("1", "true", "yes", "512", "on"):
        monkeypatch.setenv("X_FLAG", on)
        assert env_flag("X_FLAG") is True, repr(on)


def test_bench_pallas_env_zero_is_off(monkeypatch):
    """BENCH_PALLAS=0 must benchmark the baseline (non-pallas) config —
    an operator disabling the flag with 0 used to silently flip the
    headline bench onto the pallas path."""
    monkeypatch.setenv("BENCH_PALLAS", "0")
    seen = {}

    def fake_mtm(steps, batch=16, **overrides):
        seen.update(overrides)
        return (lambda: (1.0, 1.0)), bench.cub200_config(), batch

    monkeypatch.setattr(bench, "make_train_measure", fake_mtm)
    bench.run(steps=1)
    assert seen.get("use_pallas") is False

    seen.clear()
    monkeypatch.setenv("BENCH_PALLAS", "1")
    bench.run(steps=1)
    assert seen.get("use_pallas") is True


@pytest.mark.slow
def test_fused_rank_measure_tiny(monkeypatch):
    """make_fused_rank_measure compiles and measures the fused generate ->
    VAE-decode -> CLIP-rerank pipeline (tiny geometry)."""
    import jax.numpy as jnp

    from dalle_pytorch_tpu import DALLEConfig

    monkeypatch.setattr(
        bench, "cub200_config",
        lambda use_pallas=False: DALLEConfig(
            dim=32, num_text_tokens=64, text_seq_len=8, depth=2, heads=2,
            dim_head=16, attn_types=("full", "axial_row"),
            num_image_tokens=32, image_size=32, image_fmap_size=4,
            dtype=jnp.float32))
    measure = bench.make_fused_rank_measure(batch=2, num_images=4)
    ips, dt = measure()
    assert ips > 0 and dt > 0


def test_vae_measure_tiny(monkeypatch):
    """make_vae_measure compiles and measures the stage-1 train loop."""
    from dalle_pytorch_tpu import VAEConfig

    monkeypatch.setattr(
        bench, "vae128_config",
        lambda: VAEConfig(image_size=16, num_tokens=32, codebook_dim=16,
                          num_layers=2, num_resnet_blocks=0, hidden_dim=16))
    measure = bench.make_vae_measure(steps=2, batch=2)
    ips, dt = measure()
    assert ips > 0 and dt > 0


def test_collect_ab_parses_medians(tmp_path, capsys, monkeypatch):
    """tools/collect_ab.py turns perf_ab logs into one markdown table,
    skipping failed/truncated stages but still collecting the rest."""
    from pathlib import Path

    monkeypatch.syspath_prepend(str(Path(__file__).resolve().parent.parent
                                    / "tools"))
    import collect_ab

    good = tmp_path / "chip_ab_core.log"
    good.write_text(
        "compiling baseline...\n"
        "rep0 baseline      100.00 img/s\n"
        "rep0 full-head      90.00 img/s\n"
        "\nmedians:\n"
        "  baseline       101.50 img/s  (spread 100.00-103.00)\n"
        "  full-head       90.00 img/s  (spread 88.00-91.00)\n")
    gen = tmp_path / "chip_gen.log"
    gen.write_text("\nmedians:\n"
                   "  gen           8400.00 tok/s  (spread 8300.00-8500.00)\n")
    bad = tmp_path / "chip_ab_pallas.log"
    bad.write_text("compiling pallas...\nTimeoutError: stage timed out\n")

    rc = collect_ab.main([str(good), str(gen), str(bad),
                          str(tmp_path / "missing.log")])
    assert rc == 0
    out = capsys.readouterr()
    table = out.out.splitlines()
    assert table[0].startswith("| run | variant")
    assert "| ab_core | baseline | 101.50 img/s | 100.00-103.00 |" in table
    assert "| ab_core | full-head | 90.00 img/s | 88.00-91.00 |" in table
    assert "| gen | gen | 8400.00 tok/s | 8300.00-8500.00 |" in table
    assert "ab_pallas" not in out.out  # failed stage skipped...
    assert "no medians block" in out.err  # ...but reported
    assert "no such file" in out.err

    # no inputs / nothing parsable -> distinct exit codes
    assert collect_ab.main([]) == 2
    assert collect_ab.main([str(bad)]) == 1


def test_collect_ab_same_named_logs_both_kept(tmp_path, capsys, monkeypatch):
    """Two logs with the same filename (different run dirs) must both land
    in the table, not silently overwrite each other."""
    from pathlib import Path

    monkeypatch.syspath_prepend(str(Path(__file__).resolve().parent.parent
                                    / "tools"))
    import collect_ab

    block = ("\nmedians:\n"
             "  baseline       {v:.2f} img/s  (spread {v:.2f}-{v:.2f})\n")
    a = tmp_path / "runA" / "chip_ab_core.log"
    b = tmp_path / "runB" / "chip_ab_core.log"
    a.parent.mkdir(); b.parent.mkdir()
    a.write_text(block.format(v=100.0))
    b.write_text(block.format(v=200.0))
    assert collect_ab.main([str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert "| ab_core | baseline | 100.00 img/s" in out
    assert "| ab_core' | baseline | 200.00 img/s" in out


def test_history_recorded_on_chip_not_on_cpu(fake_stages, monkeypatch,
                                             capsys):
    """A successful main() appends self-describing lines to the bench
    history on real chips, and never from CPU runs (tests/dev smoke).  The
    ledger join it exercises lands in the scratch ledger, not the
    committed one."""
    import types
    from pathlib import Path

    hist = fake_stages / "hist.jsonl"
    committed = Path(bench.__file__).with_name("PERF_LEDGER.json")
    before = committed.read_bytes()

    # CPU platform (the suite's environment): no history line
    bench.main()
    assert not hist.exists()

    # fake chip platform: one appended, self-describing line per stage
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                                 memory_stats=lambda: None)
    monkeypatch.setattr(bench.jax, "devices", lambda: [fake])
    bench.main()
    capsys.readouterr()
    lines = [json.loads(l) for l in hist.read_text().splitlines()]
    # headline + one gen record per batch (8, 64)
    assert [r.get("metric") for r in lines] == [
        "dalle_cub200_train_throughput",
        "dalle_cub200_gen_throughput", "dalle_cub200_gen_throughput"]
    rec = lines[0]
    assert rec["value"] == 42.5 and rec["device"] == "TPU v5 lite"
    assert (rec["platform"], rec["device_kind"], rec["device_count"]) == (
        "tpu", "TPU v5 lite", 1)
    assert rec["mfu"] >= 0 and rec["tflops"] >= 0 and "ts" in rec
    assert [r["meta"]["batch"] for r in lines[1:]] == [8, 64]
    assert all(r["unit"] == "image_tokens/sec" for r in lines[1:])
    assert all(r["platform"] == "tpu" for r in lines)

    assert (fake_stages / "ledger.json").exists()
    assert committed.read_bytes() == before


def test_mfu_absent_on_an_unknown_device(fake_stages, monkeypatch, capsys):
    """A device the peaks table does not know: the record carries no MFU
    ("not measured") rather than one computed from an assumed peak."""
    import types

    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v9 mystery",
                                 memory_stats=lambda: None)
    monkeypatch.setattr(bench.jax, "devices", lambda: [fake])
    bench.main()
    assert "MFU not measured" in capsys.readouterr().err
    rec = json.loads((fake_stages / "hist.jsonl").read_text().splitlines()[0])
    assert "mfu" not in rec and rec["tflops"] >= 0
