"""Import-health: every module under tools/ and dalle_pytorch_tpu/ imports
on a CPU-only box with NO import-time backend queries and NO filesystem
side effects.

This pins the BACKEND001 guarantee end-to-end: the AST rule flags
module-level ``jax.devices()``-style calls it can see, but a transitive
import chain can still reach one (or build a concrete jnp array at module
scope, which initializes a backend just the same).  A process that merely
imports a module must not thereby hold the chip — a chip belongs to one
process at a time, and ``chip_smoke.py`` imports the entry scripts into the
one process that may — nor freeze the platform before its caller chose it.

One subprocess imports everything with tripwires on the public jax device
queries and on xla_bridge's backend-init entry points, so the test also
catches queries issued from inside dependencies on our modules' behalf.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

_DRIVER = r"""
import importlib, importlib.util, json, os, pkgutil, sys
from pathlib import Path

repo = sys.argv[1]
sys.path.insert(0, repo)

import jax
from jax._src import xla_bridge as xb

violations, failures = [], []
current = ["<jax import>"]


def _trip(name, orig):
    def wrapper(*a, **k):
        violations.append(f"{current[0]}: {name}() called at import time")
        return orig(*a, **k)
    for attr in ("cache_clear", "cache_info"):  # lru_cache'd originals
        if hasattr(orig, attr):
            setattr(wrapper, attr, getattr(orig, attr))
    return wrapper


for name in ("backends", "get_backend"):
    if hasattr(xb, name):
        setattr(xb, name, _trip(f"xla_bridge.{name}", getattr(xb, name)))
for name in ("devices", "local_devices", "device_count",
             "local_device_count", "default_backend", "process_index"):
    if hasattr(jax, name):
        setattr(jax, name, _trip(f"jax.{name}", getattr(jax, name)))

before = set(os.listdir(repo))

targets = []
current[0] = "dalle_pytorch_tpu"
import dalle_pytorch_tpu

for m in pkgutil.walk_packages(dalle_pytorch_tpu.__path__,
                               prefix="dalle_pytorch_tpu."):
    targets.append(("pkg", m.name))
for f in sorted(Path(repo, "tools").glob("*.py")):
    targets.append(("tool", str(f)))
for f in ("chip_smoke.py", "train_vae.py", "train_dalle.py",
          "generate.py", "genrank.py"):
    targets.append(("tool", str(Path(repo, f))))

for kind, target in targets:
    current[0] = target
    try:
        if kind == "pkg":
            importlib.import_module(target)
        else:
            spec = importlib.util.spec_from_file_location(
                "toolmod_" + Path(target).stem, target)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[spec.name] = mod  # dataclasses look the module up
            spec.loader.exec_module(mod)
    except BaseException as e:  # SystemExit at import is a failure too
        failures.append(f"{target}: {type(e).__name__}: {e}")
current[0] = "<post-import>"

new_files = sorted((set(os.listdir(repo)) - before) - {"__pycache__"})
print(json.dumps({"violations": violations, "failures": failures,
                  "new_files": new_files, "imported": len(targets)}))
"""


def test_all_modules_import_clean_on_cpu():
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               PYTHONDONTWRITEBYTECODE="1")
    # no inherited XLA device-count flags: the modules must import (not
    # run) regardless of mesh geometry
    proc = subprocess.run(
        [sys.executable, "-c", _DRIVER, str(REPO)],
        capture_output=True, text=True, timeout=600, cwd=str(REPO),
        env=env)
    assert proc.returncode == 0, (
        f"import driver crashed\nstdout:\n{proc.stdout}\n"
        f"stderr:\n{proc.stderr[-3000:]}")
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["failures"] == [], "\n".join(report["failures"])
    assert report["violations"] == [], "\n".join(report["violations"])
    assert report["new_files"] == [], (
        f"import-time filesystem side effects: {report['new_files']}")
    # the sweep actually covered the tree (fails if discovery breaks)
    assert report["imported"] >= 30, report["imported"]
