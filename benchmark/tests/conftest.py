"""Run as ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`` from the
root of the repo.  Not part of tier-1, which collects ``tests/`` only."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
REPO = Path(__file__).resolve().parent.parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
