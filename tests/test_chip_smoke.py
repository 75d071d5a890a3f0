"""chip_smoke.py rehearsed on the CPU at toy width (rehearsal 1 of the
on-chip-measurement guide), plus the compile-cache placement it relies on.

Every phase the chip runs is a plain function of a ``SmokeSize``; here each
runs once at a size XLA:CPU compiles in seconds, with the Pallas kernels
under Pallas' own interpreter context (steered from the test — the smoke
has no switch for it).  The command line itself must refuse the CPU.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

TOY = dataclasses.replace(
    chip_smoke.FULL,
    image_size=16, n_images=8,
    vae_hparams=dict(EPOCHS=1, BATCH_SIZE=4, NUM_TOKENS=32, NUM_LAYERS=2,
                     NUM_RESNET_BLOCKS=0, EMB_DIM=16, HID_DIM=16),
    dalle_hparams=dict(BATCH_SIZE=2, MODEL_DIM=32, TEXT_SEQ_LEN=12, DEPTH=2,
                       HEADS=2, DIM_HEAD=16,
                       ATTN_TYPES=["axial_row", "conv_like"]),
    ckpt_every=2, gen_images=2, serve_requests=3, serve_slots=2,
    # the narrowest call the compiled kernel takes: n = 128, two heads of 64
    attn_text=64, attn_fmap=8, attn_shape=(2, 2, 64), attn_blocks=(128,),
    plan_batch=4, fleet_replicas=4, fleet_requests=4, fleet_slots=1)


def toy_config(**overrides):
    """The toy the sharded-step and fleet rehearsals share (the two attention
    variants the CLI toy above does not have; test_serve covers all four)."""
    from dalle_pytorch_tpu import DALLEConfig

    kw = dict(dim=32, num_text_tokens=64, text_seq_len=8, depth=2, heads=2,
              dim_head=16, attn_types=("full", "axial_col"),
              num_image_tokens=32, image_size=16, image_fmap_size=4,
              dtype=jnp.bfloat16)
    kw.update(overrides)
    return DALLEConfig(**kw)


# --- the one-chip phases, in order, each feeding the next ------------------

@pytest.fixture(scope="module")
def work(tmp_path_factory):
    return tmp_path_factory.mktemp("smoke")


@pytest.fixture(scope="module")
def dataset(work):
    return chip_smoke.make_dataset(work, TOY, seed=0)


@pytest.fixture(scope="module")
def vae(work, dataset):
    return chip_smoke.phase_train_vae(work, TOY, dataset[0])


@pytest.fixture(scope="module")
def dalle(work, dataset, vae):
    return chip_smoke.phase_train_dalle(work, TOY, dataset[0], vae["ckpt"])


def test_phase_data_is_seeded(dataset, tmp_path):
    folder, captions = dataset
    assert len(list(folder.glob("*.png"))) == TOY.n_images
    assert len(list(folder.glob("*.txt"))) == TOY.n_images
    again, captions2 = chip_smoke.make_dataset(tmp_path, TOY, seed=0)
    assert captions2 == captions
    assert ((again / "bird_0003.png").read_bytes()
            == (folder / "bird_0003.png").read_bytes())
    _, other = chip_smoke.make_dataset(tmp_path / "s1", TOY, seed=1)
    assert other != captions


def test_phase_train_vae(vae):
    assert vae["ckpt"].exists() and np.isfinite(vae["losses"]).all()


def test_phase_train_dalle(dalle):
    """>= 4 optimizer steps, finite losses, the first inside the ln-uniform
    window of ITS geometry, a manifest-valid managed checkpoint read back."""
    assert len(dalle["losses"]) >= 4
    assert dalle["ckpt"].name == "data.msgpack" and dalle["ckpt_step"] >= 1
    cfg = dalle["cfg"]
    from dalle_pytorch_tpu.data.tokenizer import HugTokenizer

    # the text vocabulary is the bundled BPE's own, as the trainer reads it
    assert cfg.num_text_tokens == HugTokenizer(TOY.bpe_path).vocab_size
    assert (cfg.dim, cfg.depth, cfg.text_seq_len) == (32, 2, 12)
    assert tuple(cfg.attn_types) == ("axial_row", "conv_like")


def test_expected_first_loss_at_cub_width():
    """(ln 7880 + 7 ln 8192) / 8: the window chip_smoke holds the first
    full-width loss to is [8.5, 10.5]."""
    want = chip_smoke.expected_first_loss(chip_smoke.cub_config())
    assert abs(want - (np.log(7880) + 7 * np.log(8192)) / 8) < 1e-9
    assert 8.5 < want - 0.5 and want + 1.5 < 10.6


def test_phase_generate(work, dataset, dalle):
    out = chip_smoke.phase_generate(work, TOY, dalle["ckpt"], dataset[1][0],
                                    "cpu")
    assert out["files"] == TOY.gen_images


def test_phase_serve(dataset, dalle):
    out = chip_smoke.phase_serve(TOY, dalle["ckpt"], dataset[1], "cpu")
    assert out["requests"] == TOY.serve_requests
    assert out["trace_counts"] == {"prefill": 1, "admit": 1, "tick": 1}
    # XLA:CPU rounds a batch-1 and a batch-S product alike: as deployed the
    # arena already equals the static sampler here, not just above the floor
    assert out["agreement"] == 1.0
    assert jax.config.jax_default_matmul_precision is None  # restored


def test_phase_serve_catches_a_mismatch(dataset, dalle, monkeypatch):
    """The bit-match is a check, not a print: a reference that differs in
    one code passes the deployed-precision floor and fails the exact
    comparison."""
    real = chip_smoke.greedy_references

    def off_by_one(*a, **k):
        refs = [r.copy() for r in real(*a, **k)]
        refs[1][5] = (refs[1][5] + 1) % 32
        return refs

    monkeypatch.setattr(chip_smoke, "greedy_references", off_by_one)
    with pytest.raises(chip_smoke.SmokeFailure, match="request 1: 1 codes"):
        chip_smoke.phase_serve(TOY, dalle["ckpt"], dataset[1], "cpu")


# --- phase 6: the checks tools/chip_equiv.py ran ----------------------------

def test_phase_pallas_under_the_interpreter(capsys):
    """Kernel vs dense, four variants, forward and gradients — the tool's
    smoke mode, now steered from here.  Under the interpreter no
    ``tpu_custom_call`` may be in the HLO, and the phase checks that too."""
    with pltpu.force_tpu_interpret_mode():
        records = chip_smoke.phase_pallas(TOY, "cpu")
    assert [r["variant"] for r in records] == list(chip_smoke.VARIANTS)
    assert capsys.readouterr().out.count("PASS attention[") == 4


def test_phase_pallas_refuses_the_interpreter_under_the_tpu_name():
    """Told it runs on a TPU, the phase insists on the compiled kernel:
    interpreter output under the kernel's name is a failure."""
    with pltpu.force_tpu_interpret_mode():
        with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
            chip_smoke.phase_pallas(TOY, "tpu")


def test_variant_seed_is_stable():
    """FAIL reproducibility: the per-variant PRNG seed is identical across
    processes (crc32, not PYTHONHASHSEED-randomized hash())."""
    code = ("import chip_smoke; print([chip_smoke.variant_seed(v) "
            "for v in chip_smoke.VARIANTS])")
    seeds = {subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, check=True, text=True,
        capture_output=True,
        env=dict(os.environ, PYTHONHASHSEED=str(h))).stdout
        for h in (1, 2)}
    assert seeds == {str([chip_smoke.variant_seed(v)
                          for v in chip_smoke.VARIANTS]) + "\n"}


# --- --chips 4 on four virtual devices --------------------------------------

def test_four_chip_path_runs_only_its_own_phases(monkeypatch, capsys):
    """``--chips 4``: the sharded step + its single-device comparison and
    the replica fleet + its single-server comparison — and no one-chip
    phase.  Rehearsed on four of the suite's virtual CPU devices."""
    monkeypatch.setattr(chip_smoke, "cub_config", toy_config)
    for name in ("make_dataset", "phase_train_vae", "phase_train_dalle",
                 "phase_generate", "phase_serve", "phase_pallas"):
        monkeypatch.setattr(chip_smoke, name, lambda *a, **k: pytest.fail(
            "a one-chip phase ran under --chips 4"))
    chip_smoke.run_four_chips(TOY, seed=0, platform="cpu",
                              devices=jax.devices()[:4])
    out = capsys.readouterr().out
    assert "single-device step on" in out
    import re

    # params really spread: ~1/4 of the bytes per device under fsdp4 (plus
    # the replicated norm scales and biases), ~1/2 under 2-way tp
    share = {spec: float(re.search(
        rf"plan {re.escape(spec)}: .* share ([0-9.]+) of", out).group(1))
        for spec in TOY.plan_specs}
    assert share["fsdp4"] < 0.35 and share["dp2.tp2"] < 0.6, share
    assert out.count("collectives {'all-") == len(TOY.plan_specs)
    assert out.count("params + arena on ['TFRT_CPU_") == 4
    assert "phase fleet: 4 requests over 4 replicas bit-match" in out


# --- the command line -------------------------------------------------------

def _run_cli(*argv, env=None, cwd=REPO):
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *argv], cwd=cwd,
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


@pytest.mark.parametrize("argv", [(), ("--chips", "4")])
def test_main_refuses_the_cpu(argv, tmp_path):
    """No TPU: message, non-zero exit, ``"ok": false`` — and nothing ran
    (no work directory, no phase output)."""
    proc = _run_cli(*argv, "--work", str(tmp_path / "w"))
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert "needs" in proc.stderr and "[chip_smoke]" not in proc.stdout
    assert not (tmp_path / "w").exists()


def test_no_except_exception_around_phases():
    """A phase that raises ends the run non-zero: chip_smoke.py holds no
    handler that could turn a failure into exit 0."""
    import ast

    tree = ast.parse((REPO / "chip_smoke.py").read_text())
    handlers = [h for h in ast.walk(tree) if isinstance(h, ast.ExceptHandler)]
    assert [ast.unparse(h.type) for h in handlers] == [
        "md.PackageNotFoundError"]


# --- compile-cache placement ------------------------------------------------

_CACHE_PROBE = """
import sys; sys.path.insert(0, {repo!r})
import jax
from dalle_pytorch_tpu.cli import enable_compilation_cache
before = jax.config.jax_compilation_cache_dir
enable_compilation_cache()
print(repr(before), repr(jax.config.jax_compilation_cache_dir))
"""


def _cache_dirs(cwd, **env):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(repo=str(REPO))],
        cwd=cwd, env=dict(base, **env), check=True, capture_output=True,
        text=True, timeout=120)
    before, after = proc.stdout.split()
    return eval(before), eval(after)  # reprs of str/None this test printed


def test_cache_dir_from_environment_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax has read it, and the helper sets
    no directory in code."""
    placed = str(tmp_path / "placed-from-outside")
    assert _cache_dirs(tmp_path, JAX_COMPILATION_CACHE_DIR=placed) == (
        placed, placed)


def test_cache_dir_default_is_fixed_under_the_checkout(tmp_path):
    """Unset: <checkout>/.cache/xla — the same absolute path whatever the
    working directory (the path is part of the cache key)."""
    want = str(REPO / ".cache" / "xla")
    other = tmp_path / "elsewhere"
    other.mkdir()
    assert _cache_dirs(REPO) == (None, want)
    assert _cache_dirs(other) == (None, want)


def test_first_cache_configuration_wins(monkeypatch):
    """A tool invoked in-process never redirects the cache its host
    configured (here: the suite's own, placed by conftest)."""
    from dalle_pytorch_tpu.cli import enable_compilation_cache

    before = jax.config.jax_compilation_cache_dir
    assert before == os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert before == os.path.normpath(before)
    enable_compilation_cache()
    assert jax.config.jax_compilation_cache_dir == before
