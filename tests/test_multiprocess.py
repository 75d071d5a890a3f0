"""Real multi-process integration tests: 2 and 4 JAX processes on CPU.

Everything else in the suite tests distributed behavior single-process on a
virtual device mesh; these spawn actual `jax.distributed` processes
(the multi-host topology, minus the network) and drive the full train_dalle
CLI through them — collective checkpoint saves, per-process data sharding,
cross-process loss averaging, and the collective preemption stop where
SIGTERM lands on only ONE host.  The train and preemption paths run at
BOTH 2 and 4 ranks: rank-indexing bugs (off-by-one shard math, root-vs-
"the other process" assumptions) are invisible at 2 processes, where
every non-root rank is rank 1.
"""
from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # full tier only (--runslow)

REPO = Path(__file__).resolve().parent.parent

# Capability probe: some jaxlib CPU builds cannot run cross-process
# collectives at all ("Multiprocess computations aren't implemented on
# the CPU backend") — every test in this module would fail identically,
# drowning real regressions in red.  Probe once with the smallest
# possible 2-process collective and skip the module with the backend's
# own reason when the capability is missing.
_PROBE = """
import sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(coordinator_address=sys.argv[1],
                           num_processes=2, process_id=int(sys.argv[2]))
from jax.experimental import multihost_utils
multihost_utils.process_allgather(jax.process_index())
print("MP-PROBE-OK")
"""


@pytest.fixture(scope="module", autouse=True)
def _require_multiprocess_cpu(tmp_path_factory):
    addr = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    workers = [subprocess.Popen(
        [sys.executable, "-c", _PROBE, addr, str(pid)], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for pid in (0, 1)]
    outs = []
    try:
        for w in workers:
            outs.append(w.communicate(timeout=300)[0])
    finally:
        for w in workers:
            if w.poll() is None:
                w.kill()
    combined = "\n".join(outs)
    if "Multiprocess computations aren't implemented" in combined:
        pytest.skip("container jaxlib limitation: Multiprocess computations "
                    "aren't implemented on the CPU backend")
    assert all("MP-PROBE-OK" in o for o in outs), (
        f"multiprocess capability probe failed for another reason:\n"
        f"{combined[-3000:]}")

# BATCH_SIZE is per-host and must satisfy check_batch_size (>= process
# count), and each process's data shard (32 samples / nprocs) must hold at
# least one drop_last batch at 4 ranks: 8 >= 4.
DALLE_HPARAMS = dict(BATCH_SIZE=4, MODEL_DIM=32, TEXT_SEQ_LEN=8, DEPTH=2,
                     HEADS=2, DIM_HEAD=16, ATTN_TYPES=["full", "axial_row"])
VAE_HPARAMS = dict(EPOCHS=1, BATCH_SIZE=4, NUM_TOKENS=32, NUM_LAYERS=2,
                   NUM_RESNET_BLOCKS=0, EMB_DIM=16, HID_DIM=16)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture(scope="module")
def mp_workdir(tmp_path_factory):
    """Tiny dataset + tokenizer + a single-process-trained VAE checkpoint."""
    from PIL import Image
    from tokenizers import Tokenizer, models, pre_tokenizers

    work = tmp_path_factory.mktemp("mp")
    data = work / "data"
    data.mkdir()
    rng = np.random.default_rng(0)
    words = ["red", "green", "blue", "bird"]
    for i in range(32):
        img = (rng.uniform(size=(16, 16, 3)) * 255).astype(np.uint8)
        Image.fromarray(img).save(data / f"s{i}.png")
        (data / f"s{i}.txt").write_text(
            " ".join(rng.choice(words, 3)) + "\n")
    vocab = {"[UNK]": 0}
    for w in words:
        vocab[w] = len(vocab)
    tok = Tokenizer(models.WordLevel(vocab, unk_token="[UNK]"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    tok.save(str(work / "tok.json"))

    env = _env(work, VAE_HPARAMS)
    subprocess.run(
        [sys.executable, str(REPO / "train_vae.py"),
         "--image_folder", str(data), "--image_size", "16"],
        cwd=work, env=env, check=True, capture_output=True, timeout=600)
    assert (work / "vae-final.pt").exists()
    return work


def _env(workdir, hparams, n_local_devices: int = 2):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={n_local_devices}",
        DALLE_TPU_HPARAMS=json.dumps(hparams),
        JAX_COMPILATION_CACHE_DIR=str(Path(workdir) / "jaxcache"),
    )
    return env


def _spawn_train(workdir, port, pid, extra_args=(), epochs=1, nprocs=2):
    """Launch one training process, stdout+stderr to a log file — a PIPE
    would deadlock if a child filled the buffer while the test polls.
    Local device count scales down as the process count scales up (2x2 or
    4x1 = 4 global devices), keeping the global mesh — and the compile
    cost on the 1-core CI box — constant across parametrizations."""
    args = [sys.executable, str(REPO / "train_dalle.py"),
            "--vae_path", str(workdir / "vae-final.pt"),
            "--image_text_folder", str(workdir / "data"),
            "--bpe_path", str(workdir / "tok.json"),
            "--truncate_captions", "--epochs", str(epochs),
            "--distributed_backend", "gspmd",
            "--coordinator_address", f"127.0.0.1:{port}",
            "--num_processes", str(nprocs), "--process_id", str(pid),
            *extra_args]
    log = open(workdir / f"proc{pid}.log", "w")
    env = _env(workdir, DALLE_HPARAMS, n_local_devices=4 // nprocs)
    proc = subprocess.Popen(args, cwd=workdir, env=env,
                            stdout=log, stderr=subprocess.STDOUT, text=True)
    proc._log_path = workdir / f"proc{pid}.log"  # type: ignore[attr-defined]
    proc._log_file = log  # type: ignore[attr-defined]
    return proc


def _finish(procs, timeout=900):
    """Wait for both processes; on any failure path kill BOTH (a surviving
    peer would block forever in a collective waiting for the dead one).
    Returns each process's full output."""
    try:
        for p in procs:
            p.wait(timeout=timeout)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
            p._log_file.close()
    return [p._log_path.read_text() for p in procs]


@pytest.mark.parametrize("nprocs", [2, 4])
def test_multi_process_train(mp_workdir, nprocs):
    """Full train_dalle run across real processes (4 global devices):
    per-process data shards, GSPMD grad sync, collective msgpack save.
    4 ranks catches rank-indexing bugs 2 cannot (every non-root rank is
    rank 1 at nprocs=2)."""
    (mp_workdir / "dalle-final.pt").unlink(missing_ok=True)
    port = _free_port()
    procs = [_spawn_train(mp_workdir, port, pid, nprocs=nprocs)
             for pid in range(nprocs)]
    outs = _finish(procs)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out[-3000:]}"
    assert (mp_workdir / "dalle-final.pt").exists()

    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    ckpt = load_checkpoint(mp_workdir / "dalle-final.pt")
    assert set(ckpt) >= {"hparams", "weights", "opt_state", "epoch"}
    # root prints/logs; every non-root rank stays quiet about epochs
    assert "epoch 0 done" in outs[0]
    for out in outs[1:]:
        assert "epoch 0 done" not in out


@pytest.mark.parametrize("nprocs", [2, 4])
def test_multi_process_preemption_single_sigterm(mp_workdir, nprocs):
    """SIGTERM delivered to only ONE of the processes: the stop decision is
    collective, so ALL processes leave the loop at the same step, save one
    coherent resume checkpoint together, and exit cleanly — the multi-host
    preemption story end-to-end.  At 4 ranks the signal lands on a MIDDLE
    rank (neither root nor last), the case 2 ranks cannot express."""
    for f in ("dalle.pt", "dalle-final.pt"):
        (mp_workdir / f).unlink(missing_ok=True)
    port = _free_port()
    hb_dir = mp_workdir / f"hb{nprocs}"
    procs = [_spawn_train(mp_workdir, port, pid, epochs=500, nprocs=nprocs,
                          extra_args=("--heartbeat_dir", str(hb_dir)))
             for pid in range(nprocs)]
    # wait for training to actually progress (heartbeats appear), then
    # preempt just one NON-root process
    try:
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            if all((hb_dir / f"heartbeat-p{pid}.json").exists()
                   for pid in range(nprocs)):
                break
            for p in procs:
                assert p.poll() is None, \
                    p._log_path.read_text()[-3000:]
            time.sleep(2)
        else:
            raise AssertionError("training never produced heartbeats")
        procs[nprocs // 2].send_signal(signal.SIGTERM)
    except BaseException:
        for p in procs:
            if p.poll() is None:
                p.kill()
        raise

    outs = _finish(procs, timeout=600)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out[-3000:]}"
    assert "interrupted at epoch" in outs[0]  # root announced the stop
    assert (mp_workdir / "dalle.pt").exists()
    assert not (mp_workdir / "dalle-final.pt").exists()

    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    ckpt = load_checkpoint(mp_workdir / "dalle.pt")
    assert set(ckpt) >= {"hparams", "weights", "opt_state", "epoch"}


def test_two_process_sharded_save_resumes_single_process(mp_workdir,
                                                         monkeypatch):
    """--sharded_checkpoints written collectively by TWO processes (host-
    local scalars like the injected lr get lifted to replicated global
    arrays) restores in ONE process — elastic across process counts."""
    for f in ("dalle-final.pt", "dalle-final.pt.orbax"):
        path = mp_workdir / f
        if path.is_dir():
            import shutil

            shutil.rmtree(path)
        else:
            path.unlink(missing_ok=True)
    port = _free_port()
    procs = [_spawn_train(mp_workdir, port, pid,
                          extra_args=("--sharded_checkpoints",))
             for pid in (0, 1)]
    outs = _finish(procs)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out[-3000:]}"
    final = mp_workdir / "dalle-final.pt.orbax"
    assert final.is_dir()

    # resume in THIS (single) process on a different mesh
    monkeypatch.setenv("DALLE_TPU_HPARAMS", json.dumps({"BATCH_SIZE": 4}))
    monkeypatch.chdir(mp_workdir)
    import train_dalle

    train_dalle.main(["--dalle_path", str(final),
                      "--image_text_folder", str(mp_workdir / "data"),
                      "--bpe_path", str(mp_workdir / "tok.json"),
                      "--truncate_captions", "--epochs", "2",
                      "--mesh_tp", "2"])
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    assert int(load_checkpoint(mp_workdir / "dalle-final.pt")["epoch"]) == 2
