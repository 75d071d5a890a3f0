"""tools/fetch_and_convert.sh dry-run: the one-command pretrained-weights
path must be executable end-to-end today (synthesized released-format
checkpoints -> convert_weights.py -> smoke decode), so the real-download
path is one flag away the moment egress exists (ref downloads at /root/reference/dalle_pytorch/vae.py:29-33)."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

pytestmark = pytest.mark.slow  # full-size graphs: full tier only


def test_fetch_and_convert_dry_run(tmp_path):
    out = tmp_path / "pretrained"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        ["sh", str(REPO / "tools" / "fetch_and_convert.sh"), "--dry-run",
         str(out)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for name in ("openai_jax.msgpack", "vqgan_jax.msgpack",
                 "clip_jax.msgpack"):
        assert (out / name).exists(), name
    for png in ("vqgan_smoke.png", "openai_smoke.png"):
        assert (out / "smoke" / png).stat().st_size > 0, png
    # idempotence: a second run keeps existing artifacts and still smokes
    proc2 = subprocess.run(
        ["sh", str(REPO / "tools" / "fetch_and_convert.sh"), "--dry-run",
         str(out)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr
    assert "have synthesized checkpoints" in proc2.stdout
