"""graftlint rule-engine tests: per-rule positive/negative/pragma fixtures,
the pragma-justification contract, baseline round-trip, the ENV001 --fix
rewrite — and the gate that keeps the repo itself clean (the tier-1 twin of
CI's lint job, so a new lintable bug class can't land silently)."""
from __future__ import annotations

import importlib.util
import json
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dalle_pytorch_tpu.lint import (FINDINGS_JSON_SCHEMA, RULES,  # noqa: E402
                                    Finding, filter_baseline,
                                    findings_to_json, findings_to_sarif,
                                    fingerprint, fix_env001, lint_paths,
                                    lint_source, load_baseline,
                                    prune_baseline, stale_baseline_entries,
                                    write_baseline)


def rules_of(findings):
    return [f.rule for f in findings]


def lint(src, **kwargs):
    return lint_source(textwrap.dedent(src), **kwargs)


# --- ENV001 --------------------------------------------------------------


def test_env001_truth_contexts_flagged():
    src = """
    import os
    if os.environ.get("A"):
        pass
    x = 1 if os.environ.get("B") else 2
    y = flag and os.environ.get("C")
    z = bool(os.environ.get("D"))
    w = not os.getenv("E")
    """
    found = lint(src, select=("ENV001",))
    assert rules_of(found) == ["ENV001"] * 5


def test_env001_value_uses_clean():
    src = """
    import os
    path = os.environ.get("CACHE", "/tmp/x")
    n = int(os.environ.get("N", "0"))
    if os.environ.get("MODE") == "fast":
        pass
    parts = os.environ.get("LIST", "").split(",")
    """
    assert lint(src, select=("ENV001",)) == []


def test_env001_pragma_with_reason_suppresses():
    src = """
    import os
    # graftlint: disable=ENV001 (address-valued: presence is the signal)
    if os.environ.get("COORD_ADDR"):
        pass
    """
    assert lint(src, select=("ENV001",)) == []


def test_env001_same_line_pragma_suppresses():
    src = """
    import os
    if os.environ.get("X"):  # graftlint: disable=ENV001 (value-valued var)
        pass
    """
    assert lint(src, select=("ENV001",)) == []


def test_pragma_without_justification_is_an_error():
    src = """
    import os
    if os.environ.get("X"):  # graftlint: disable=ENV001
        pass
    """
    found = lint(src, select=("ENV001",))
    # the bare pragma does NOT suppress, and is itself flagged
    assert sorted(rules_of(found)) == ["ENV001", "PRAGMA001"]


# --- SEED001 -------------------------------------------------------------


def test_seed001_hash_flagged_crc32_clean():
    bad = """
    import jax
    key = jax.random.PRNGKey(hash(name))
    """
    good = """
    import jax, zlib
    key = jax.random.PRNGKey(zlib.crc32(name.encode()))
    """
    assert rules_of(lint(bad, select=("SEED001",))) == ["SEED001"]
    assert lint(good, select=("SEED001",)) == []


def test_seed001_pragma():
    src = """
    cache_key = hash(obj)  # graftlint: disable=SEED001 (in-process memo key, never a seed)
    """
    assert lint(src, select=("SEED001",)) == []


# --- BACKEND001 ----------------------------------------------------------


def test_backend001_module_level_query_flagged():
    src = """
    import jax
    SMOKE = jax.default_backend() != "tpu"
    """
    assert rules_of(lint(src, select=("BACKEND001",))) == ["BACKEND001"]


@pytest.mark.parametrize("query", [
    "devices", "local_devices", "default_backend", "device_count",
    "local_device_count", "process_count", "process_index"])
def test_backend001_every_query_flagged_without_exemption(query):
    """Plain rule, no sanctioned preamble: whatever ran earlier at module
    level (here a platform setting), a module-level backend query still
    initializes the backend on import and is flagged."""
    src = f"""
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    X = jax.{query}()
    """
    assert rules_of(lint(src, select=("BACKEND001",))) == ["BACKEND001"]


def test_backend001_function_scope_clean():
    # queries inside functions run when called, not on import
    src = """
    import jax
    def main():
        return len(jax.devices())
    """
    assert lint(src, select=("BACKEND001",)) == []


# --- DOT001 --------------------------------------------------------------


def test_dot001_missing_pref_flagged():
    src = """
    import jax.numpy as jnp
    s = jnp.einsum("bhid,bhjd->bhij", q, k)
    o = jnp.dot(a, b)
    g = jax.lax.dot_general(a, b, dims)
    """
    assert rules_of(lint(src, select=("DOT001",))) == ["DOT001"] * 3


def test_dot001_with_pref_clean_and_numpy_ignored():
    src = """
    import jax.numpy as jnp
    import numpy as np
    s = jnp.einsum("ij,jk->ik", a, b, preferred_element_type=jnp.float32)
    host = np.dot(x, y)
    """
    assert lint(src, select=("DOT001",)) == []


def test_dot001_pragma():
    src = """
    import jax.numpy as jnp
    # graftlint: disable=DOT001 (uniform: both operands cast to self.dtype)
    s = jnp.einsum("ij,jk->ik", a, b)
    """
    assert lint(src, select=("DOT001",)) == []


# --- TRACE001 ------------------------------------------------------------


def test_trace001_host_sync_in_jit_flagged():
    src = """
    import jax
    import numpy as np
    @jax.jit
    def step(x):
        v = x.sum().item()
        host = np.asarray(x)
        return v, host
    """
    assert rules_of(lint(src, select=("TRACE001",))) == ["TRACE001"] * 2


def test_trace001_scan_body_flagged_outside_clean():
    src = """
    import jax
    import numpy as np
    def body(carry, x):
        return carry, np.asarray(x)
    out = jax.lax.scan(body, 0, xs)
    host = np.asarray(out)  # outside any traced context: fine
    """
    assert rules_of(lint(src, select=("TRACE001",))) == ["TRACE001"]


def test_trace001_pragma():
    src = """
    import jax
    @jax.jit
    def step(x):
        return x.sum().item()  # graftlint: disable=TRACE001 (test-only fixture)
    """
    assert lint(src, select=("TRACE001",)) == []


# --- EXC001 --------------------------------------------------------------


def test_exc001_swallowing_flagged_reraise_clean():
    src = """
    try:
        risky()
    except Exception:
        pass
    try:
        risky()
    except:
        log()
    try:
        risky()
    except Exception as e:
        log(e)
        raise
    try:
        risky()
    except ValueError:
        pass
    """
    assert rules_of(lint(src, select=("EXC001",))) == ["EXC001"] * 2


def test_exc001_pragma_line_above():
    src = """
    try:
        risky()
    # graftlint: disable=EXC001 (informational only; failure must not kill the run)
    except Exception:
        pass
    """
    assert lint(src, select=("EXC001",)) == []


# --- CKPT001 -------------------------------------------------------------


def test_ckpt001_raw_durable_writes_flagged():
    src = """
    from pathlib import Path
    ckpt_path = "run/ckpt-00000001/data.msgpack"
    with open(ckpt_path, "wb") as f:
        f.write(b"x")
    Path("hb/heartbeat-p0.json").write_text("{}")
    manifest = Path("run") / "manifest.json"
    with manifest.open("w") as f:
        f.write("{}")
    """
    found = lint(src, select=("CKPT001",), path="train_x.py")
    assert rules_of(found) == ["CKPT001"] * 3


def test_ckpt001_reads_and_unrelated_writes_clean():
    src = """
    with open(ckpt_path, "rb") as f:
        data = f.read()
    with open("results.txt", "w") as f:
        f.write("ok")
    log_path.write_text("line")
    mode = compute_mode()
    open(ckpt_path, mode)  # non-literal mode: not provably a write
    """
    assert lint(src, select=("CKPT001",), path="train_x.py") == []


def test_ckpt001_utils_helpers_exempt():
    """The atomic-rename helpers themselves live under utils/ and must be
    allowed to touch checkpoint bytes; the same write anywhere else is
    flagged."""
    src = 'open(ckpt_tmp, "wb").write(b"x")\n'
    assert lint_source(src, path="dalle_pytorch_tpu/utils/checkpoint.py",
                       select=("CKPT001",)) == []
    assert rules_of(lint_source(src, path="tools/convert.py",
                                select=("CKPT001",))) == ["CKPT001"]


def test_ckpt001_covers_shard_manifest_writes():
    """The streaming shard sets (data/stream.py) are durable run state too:
    a torn shard or shard-index write corrupts the whole corpus view, so
    raw writes to shard-ish targets are in CKPT001's scope."""
    src = """
    from pathlib import Path
    with open(shard_index_path, "w") as f:
        f.write("{}")
    Path(shard_dir / "shard-000001.tar").write_bytes(b"x")
    """
    found = lint(src, select=("CKPT001",), path="tools/make_x.py")
    assert rules_of(found) == ["CKPT001"] * 2
    # routing through the utils/ atomic helpers is the sanctioned path
    clean = "atomic_write_json(shard_index_path, index)\n"
    assert lint_source(clean, path="tools/make_x.py",
                       select=("CKPT001",)) == []


def test_ckpt001_pragma_with_reason_suppresses():
    src = ("open(ckpt_debug_dump, 'w').write('x')  "
           "# graftlint: disable=CKPT001 (debug dump, not durable run state)\n")
    assert lint_source(src, path="train_x.py", select=("CKPT001",)) == []


# --- OBS001 --------------------------------------------------------------


def test_obs001_hot_path_prints_flagged():
    """Bare prints in the step/serve/ckpt/data hot paths must route
    through telemetry.note or TrainLogger — that print is the narration
    the post-mortem stream needs."""
    src = """
    def save(step):
        print(f"saving {step}")
    print("module-level narration", flush=True)
    """
    for path in ("dalle_pytorch_tpu/utils/ckpt_manager.py",
                 "dalle_pytorch_tpu/serve/scheduler.py",
                 "dalle_pytorch_tpu/data/stream.py",
                 "dalle_pytorch_tpu/training.py"):
        assert rules_of(lint(src, select=("OBS001",),
                             path=path)) == ["OBS001"] * 2, path


def test_obs001_out_of_scope_paths_clean():
    """Pure-computation subtrees, the sinks themselves, tools/ and code
    outside the package keep their prints — the rule is scoped to the hot
    paths whose narration the stream must carry."""
    src = 'print("hello")\n'
    for path in ("dalle_pytorch_tpu/models/dalle.py",
                 "dalle_pytorch_tpu/ops/attention.py",
                 "dalle_pytorch_tpu/obs/telemetry.py",
                 "dalle_pytorch_tpu/utils/logging.py",
                 "dalle_pytorch_tpu/lint/engine.py",
                 "tools/monitor.py", "train_dalle.py"):
        assert lint_source(src, select=("OBS001",), path=path) == [], path


def test_obs001_note_and_pragma_clean():
    src = """
    from dalle_pytorch_tpu.obs import telemetry
    telemetry.note("ckpt", "save_retry", "retrying", step=3)
    print("cli surface")  # graftlint: disable=OBS001 (interactive CLI output, never a run's narration)
    """
    assert lint(src, select=("OBS001",),
                path="dalle_pytorch_tpu/utils/ckpt_manager.py") == []


# --- OBS002 --------------------------------------------------------------


def test_obs002_wall_clock_duration_math_flagged():
    """Durations from wall-clock deltas skew across the fleet and step
    under NTP — both the direct `time.time() - t0` form and a tracked
    name assigned from time.time() are flagged inside the package."""
    src = """
    import time
    def f():
        t0 = time.time()
        work()
        return time.time() - t0
    def g(deadline):
        start = time.time()
        return deadline - start
    """
    found = lint(src, select=("OBS002",),
                 path="dalle_pytorch_tpu/serve/scheduler.py")
    assert rules_of(found) == ["OBS002"] * 2


def test_obs002_monotonic_and_out_of_scope_clean():
    """time.monotonic()/perf_counter durations, bare timestamps, and code
    outside dalle_pytorch_tpu/ (tools, trainers) stay clean."""
    mono = """
    import time
    def f():
        t0 = time.monotonic()
        return time.monotonic() - t0
    stamp = {"time": time.time()}
    """
    assert lint(mono, select=("OBS002",),
                path="dalle_pytorch_tpu/utils/x.py") == []
    wall = "import time\nd = time.time() - t0\n"
    for path in ("tools/monitor.py", "train_dalle.py", "chip_smoke.py"):
        assert lint_source(wall, select=("OBS002",), path=path) == [], path


def test_obs002_pragma_with_reason_suppresses():
    src = ("import time\n"
           "age = time.time() - path.stat().st_mtime  "
           "# graftlint: disable=OBS002 (cross-clock: mtimes live on the "
           "wall clock)\n")
    assert lint_source(src, select=("OBS002",),
                       path="dalle_pytorch_tpu/utils/x.py") == []


# --- OBS003 --------------------------------------------------------------


def test_obs003_direct_profiler_calls_flagged():
    """Unmanaged jax.profiler entry points leave on-chip trace windows
    the telemetry stream never hears about — flagged everywhere (trainers
    and tools included: the capture must ride a prof.xprof span)."""
    src = """
    import jax
    def window(logdir):
        jax.profiler.start_trace(logdir)
        work()
        jax.profiler.stop_trace()
    def ctx(logdir):
        with jax.profiler.trace(logdir):
            work()
    """
    for path in ("train_dalle.py", "tools/loss_curve.py",
                 "dalle_pytorch_tpu/utils/profiling.py"):
        assert rules_of(lint(src, select=("OBS003",),
                             path=path)) == ["OBS003"] * 3, path


def test_obs003_prof_module_exempt_and_capture_clean():
    """obs/prof.py IS the managed entry point (exempt); call sites using
    prof.capture / XprofWindow are what the rule migrates code toward."""
    raw = "import jax\njax.profiler.start_trace('/tmp/x')\n"
    assert lint_source(raw, select=("OBS003",),
                       path="dalle_pytorch_tpu/obs/prof.py") == []
    managed = """
    from dalle_pytorch_tpu.obs import prof
    with prof.capture("/tmp/x"):
        work()
    prof.XprofWindow(logdir="/tmp/x").on_step(0)
    """
    assert lint(managed, select=("OBS003",), path="train_dalle.py") == []


def test_obs003_pragma_with_reason_suppresses():
    src = ("import jax\n"
           "jax.profiler.start_trace('/tmp/x')  "
           "# graftlint: disable=OBS003 (throwaway debugging scratch, no "
           "telemetry stream attached)\n")
    assert lint_source(src, select=("OBS003",), path="tools/scratch.py") == []


# --- MEM001 --------------------------------------------------------------


def test_mem001_direct_memory_polls_flagged():
    """Unmanaged jax device-memory polls produce samples the telemetry
    stream never hears about (no mem.watermark, no graft_hbm_* gauges,
    invisible to the leak-gate baseline) — flagged everywhere, trainers
    and tools included."""
    src = """
    import jax
    def probe(path):
        blob = jax.profiler.device_memory_profile()
        n = len(jax.live_arrays())
        open(path, 'wb').write(blob)
        return n
    """
    for path in ("train_dalle.py", "tools/monitor.py",
                 "dalle_pytorch_tpu/utils/profiling.py"):
        assert rules_of(lint(src, select=("MEM001",),
                             path=path)) == ["MEM001"] * 2, path


def test_mem001_mem_module_exempt_and_tracker_clean():
    """obs/mem.py IS the managed entry point (exempt); call sites using
    MemTracker / live_buffer_stats are what the rule migrates code
    toward."""
    raw = ("import jax\n"
           "jax.profiler.device_memory_profile()\n"
           "jax.live_arrays()\n")
    assert lint_source(raw, select=("MEM001",),
                       path="dalle_pytorch_tpu/obs/mem.py") == []
    managed = """
    from dalle_pytorch_tpu.obs import mem
    tracker = mem.MemTracker(chip="v4-8")
    tracker.snapshot("init")
    mem.live_buffer_stats()
    mem.write_device_memory_profile("/tmp/x.pprof")
    """
    assert lint(managed, select=("MEM001",), path="train_dalle.py") == []


def test_mem001_pragma_with_reason_suppresses():
    src = ("import jax\n"
           "print(jax.live_arrays())  "
           "# graftlint: disable=MEM001 (throwaway debugging scratch, no "
           "telemetry stream attached)\n")
    assert lint_source(src, select=("MEM001",), path="tools/scratch.py") == []


# --- SRV001 --------------------------------------------------------------


def test_srv001_blocking_waits_without_timeout_flagged():
    """future.result() / queue.get() / lock.acquire() with no timeout in
    serve/ are the hang a dead replica turns into — all three forms
    flagged."""
    src = """
    def wait_all(fut, q, lock):
        a = fut.result()
        b = q.get()
        lock.acquire()
        return a, b
    """
    found = lint(src, select=("SRV001",),
                 path="dalle_pytorch_tpu/serve/router.py")
    assert rules_of(found) == ["SRV001"] * 3


def test_srv001_bounded_waits_and_out_of_scope_clean():
    """Timeouts (positional or keyword), keyed dict .get, and the same
    blocking forms OUTSIDE serve/ all stay clean."""
    bounded = """
    def wait_all(fut, q, lock, d):
        a = fut.result(5.0)
        b = fut.result(timeout=2.0)
        c = q.get(timeout=0.1)
        lock.acquire(timeout=1.0)
        return a, b, c, d.get("key"), os.environ.get("X", "")
    """
    assert lint(bounded, select=("SRV001",),
                path="dalle_pytorch_tpu/serve/scheduler.py") == []
    blocking = "x = fut.result()\ny = q.get()\n"
    for path in ("dalle_pytorch_tpu/utils/faults.py", "tools/monitor.py",
                 "train_dalle.py", "tests/test_router.py"):
        assert lint_source(blocking, select=("SRV001",), path=path) == [], \
            path


def test_srv001_pragma_with_reason_suppresses():
    src = ("done = fut.result()  "
           "# graftlint: disable=SRV001 (the future is already done: "
           "resolved by the callback that called us)\n")
    assert lint_source(src, select=("SRV001",),
                       path="dalle_pytorch_tpu/serve/router.py") == []


# --- THR001 --------------------------------------------------------------


def test_thr001_raw_lock_construction_flagged():
    """threading.Lock/RLock/Condition construction (dotted or imported
    bare) outside utils/locks.py bypasses the graftrace witness."""
    src = """
    import threading
    from threading import RLock, Condition
    a = threading.Lock()
    b = RLock()
    c = Condition()
    """
    found = lint(src, select=("THR001",),
                 path="dalle_pytorch_tpu/serve/router.py")
    assert rules_of(found) == ["THR001"] * 3


def test_thr001_traced_wrappers_events_and_exempt_paths_clean():
    """Traced wrappers, Events (no ordering to witness), and the two
    exempt surfaces — locks.py itself and analyzer fixtures — stay
    clean."""
    src = """
    import threading
    from dalle_pytorch_tpu.utils import locks
    a = locks.TracedLock("a")
    b = locks.TracedRLock("b")
    c = locks.TracedCondition(name="c")
    e = threading.Event()
    """
    assert lint(src, select=("THR001",),
                path="dalle_pytorch_tpu/serve/router.py") == []
    raw = "import threading\nx = threading.Lock()\n"
    for path in ("dalle_pytorch_tpu/utils/locks.py",
                 "dalle_pytorch_tpu/lint/threads_fixtures.py"):
        assert lint_source(raw, select=("THR001",), path=path) == [], path


def test_thr001_pragma_with_reason_suppresses():
    src = ("import threading\n"
           "x = threading.Lock()  "
           "# graftlint: disable=THR001 (signal-handler side: the witness "
           "itself must never run under this lock)\n")
    assert lint_source(src, select=("THR001",),
                       path="dalle_pytorch_tpu/obs/telemetry.py") == []


# --- THR002 --------------------------------------------------------------


def test_thr002_sleep_poll_loop_flagged():
    """A while loop polling shared state with time.sleep in serve/ never
    wakes early for close/stop — flagged."""
    src = """
    import time
    def wait_ready(self):
        while not self.ready:
            time.sleep(0.01)
    """
    found = lint(src, select=("THR002",),
                 path="dalle_pytorch_tpu/serve/router.py")
    assert rules_of(found) == ["THR002"]


def test_thr002_event_wait_and_out_of_scope_clean():
    """Event-wait pacing (wakes on close) is the fix and stays clean; the
    same sleep-poll outside serve/ is out of scope."""
    src = """
    def wait_ready(self):
        while not self.ready:
            self._stop_evt.wait(0.01)
    """
    assert lint(src, select=("THR002",),
                path="dalle_pytorch_tpu/serve/router.py") == []
    poll = ("import time\n"
            "def spin(self):\n"
            "    while not self.ready:\n"
            "        time.sleep(0.01)\n")
    for path in ("dalle_pytorch_tpu/utils/faults.py", "tools/monitor.py",
                 "tests/test_router.py"):
        assert lint_source(poll, select=("THR002",), path=path) == [], path


def test_thr002_pragma_with_reason_suppresses():
    src = ("import time\n"
           "def drive(self):\n"
           "    while self.pending:\n"
           "        time.sleep(0.001)  "
           "# graftlint: disable=THR002 (open-loop pacing against the "
           "local clock, not shared state)\n")
    assert lint_source(src, select=("THR002",),
                       path="dalle_pytorch_tpu/serve/scheduler.py") == []


# --- engine machinery ----------------------------------------------------


# --- DON001 --------------------------------------------------------------


def test_don001_jit_without_donation_in_factory_flagged():
    src = """
    import jax

    def make_toy_train_step(model, tx):
        def train_step(params, opt_state, batch):
            return params, opt_state
        return jax.jit(train_step)
    """
    assert rules_of(lint(src, select=("DON001",))) == ["DON001"]


def test_don001_stated_donation_clean():
    src = """
    import jax
    from functools import partial

    def make_toy_train_step(model, tx):
        def train_step(params, opt_state, batch):
            return params, opt_state
        return jax.jit(train_step, donate_argnums=(0, 1))

    def make_eval_step(model):
        # an explicit empty donation is a statement, not an omission
        return jax.jit(lambda p, b: p, donate_argnums=())

    def make_named_train_step(model):
        @partial(jax.jit, donate_argnames=("params",))
        def train_step(params, batch):
            return params
        return train_step
    """
    assert lint(src, select=("DON001",)) == []


def test_don001_jit_outside_factory_clean():
    src = """
    import jax
    encode_fn = jax.jit(encode)

    def not_a_factory():
        return jax.jit(lambda x: x)
    """
    assert lint(src, select=("DON001",)) == []


def test_don001_pragma():
    src = """
    import jax

    def make_probe_step():
        # graftlint: disable=DON001 (stateless probe: nothing to donate)
        return jax.jit(lambda x: x * 2)
    """
    assert lint(src, select=("DON001",)) == []


# --- DON002 --------------------------------------------------------------


def test_don002_donated_arg_read_after_call_flagged():
    src = """
    import jax

    def run(params, opt_state, batches):
        step = jax.jit(train_step, donate_argnums=(0, 1))
        for batch in batches:
            new_params, new_opt, loss = step(params, opt_state, batch)
        return params  # deleted buffer: runtime error on the pod
    """
    found = lint(src, select=("DON002",))
    assert rules_of(found) == ["DON002"]
    assert "'params'" in found[0].message


def test_don002_rebinding_idiom_clean():
    src = """
    import jax

    def run(params, opt_state, batches):
        step = jax.jit(train_step, donate_argnums=(0, 1))
        for batch in batches:
            params, opt_state, loss = step(params, opt_state, batch)
        return params
    """
    assert lint(src, select=("DON002",)) == []


def test_don002_factory_call_tracked_and_donate_false_exempt():
    src = """
    def run(params, opt_state, batches):
        step = make_toy_train_step(model, tx)
        params2, opt2, loss = step(params, opt_state, batches[0])
        save(params)

    def run_undonating(params, opt_state, batches):
        step = make_toy_train_step(model, tx, donate=False)
        params2, opt2, loss = step(params, opt_state, batches[0])
        save(params)
    """
    found = lint(src, select=("DON002",))
    assert rules_of(found) == ["DON002"]
    assert found[0].line < 7  # only the donating factory's call site


def test_don002_nested_def_params_shadow_outer_names():
    """Regression: a nested wrapper whose parameters shadow the outer
    names must not attribute its inner step call to the outer scope
    (the train_dalle.py frozen-VAE wrapper shape)."""
    src = """
    def run(params, opt_state, use_wrapper):
        _codes_step = make_toy_train_step(model, tx)
        if use_wrapper:
            def train_step(params, opt_state, batch):
                return _codes_step(params, opt_state, batch)
        else:
            train_step = _codes_step
        for batch in batches:
            params, opt_state, loss = train_step(params, opt_state, batch)
        save(params)
    """
    assert lint(src, select=("DON002",)) == []


def test_don002_cross_function_helper_forward_flagged():
    """The cross-function escape (carried PR 5 follow-up): a helper that
    forwards its own parameters to a donating call donates them too — the
    CALLER's variable is dead after the helper returns, and a later read
    is the same use-after-donation the same-scope rule catches."""
    src = """
    def train(params, opt_state, batches, model, tx):
        _codes_step = make_toy_train_step(model, tx)

        def run_step(params, opt_state, batch):
            return _codes_step(params, opt_state, encode(batch))

        new_p, new_o, loss = run_step(params, opt_state, batches[0])
        save(params)  # stale: donated through the helper
    """
    found = lint(src, select=("DON002",))
    assert rules_of(found) == ["DON002"]
    assert "'params'" in found[0].message


def test_don002_cross_function_chain_resolves_fixed_point():
    """helper-of-helper: the donation signature propagates through the
    chain (module-level defs), flagging the caller of the OUTERMOST
    wrapper."""
    src = """
    import jax
    step = jax.jit(f, donate_argnums=(0, 1))

    def inner(params, opt_state, batch):
        return step(params, opt_state, batch)

    def outer(params, opt_state, batch):
        return inner(params, opt_state, batch)

    def train(params, opt_state, batches):
        new_p, new_o, loss = outer(params, opt_state, batches[0])
        save(params)
    """
    found = lint(src, select=("DON002",))
    assert rules_of(found) == ["DON002"]
    assert "'params'" in found[0].message


def test_don002_cross_function_clean_shapes():
    """Negatives: a helper over a donate=False factory donates nothing;
    a caller that REBINDS through the helper (the trainers' idiom) is the
    clean shape."""
    src = """
    def train(params, opt_state, batches, model, tx):
        _codes_step = make_toy_train_step(model, tx, donate=False)

        def run_step(params, opt_state, batch):
            return _codes_step(params, opt_state, batch)

        new_p, new_o, loss = run_step(params, opt_state, batches[0])
        save(params)
    """
    assert lint(src, select=("DON002",)) == []

    src2 = """
    import jax
    step = jax.jit(f, donate_argnums=(0, 1))

    def helper(params, opt_state, batch):
        params, opt_state, loss = step(params, opt_state, batch)
        return params, opt_state, loss

    def train(params, opt_state, batches):
        for batch in batches:
            params, opt_state, loss = helper(params, opt_state, batch)
        save(params)
    """
    assert lint(src2, select=("DON002",)) == []


def test_don002_pragma():
    src = """
    import jax

    def run(params, opt_state, batch):
        step = jax.jit(train_step, donate_argnums=(0,))
        # graftlint: disable=DON002 (step aborts before the read on this branch)
        new_params, loss = step(params, opt_state, batch)
        return params
    """
    assert lint(src, select=("DON002",)) == []


# --- PLAN001 --------------------------------------------------------------


def test_plan001_hand_constructed_sharding_flagged():
    """Mesh/NamedSharding/PartitionSpec construction (dotted, bare, or
    aliased — including the lazy in-function imports this repo uses)
    outside parallel/ bypasses the ParallelPlan rule table."""
    src = """
    import jax

    def place(params, devices):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(devices, ("x",))
        sh = NamedSharding(mesh, P("x"))
        spec = jax.sharding.PartitionSpec(None)
        return sh, spec
    """
    found = lint(src, select=("PLAN001",),
                 path="dalle_pytorch_tpu/serve/replica.py")
    assert rules_of(found) == ["PLAN001"] * 4


def test_plan001_partitioner_path_and_exempt_surfaces_clean():
    """Plan-mediated sharding (the Partitioner API) never constructs the
    jax.sharding types by hand, and the two exempt surfaces — the
    parallel/ package that implements the contract and analyzer fixture
    files — stay clean."""
    src = """
    from dalle_pytorch_tpu.parallel.plan import PLAN_REGISTRY

    def place(params):
        part = PLAN_REGISTRY["fsdp"].partitioner()
        return part.param_specs(params), part.shard_batch
    """
    assert lint(src, select=("PLAN001",),
                path="dalle_pytorch_tpu/serve/replica.py") == []
    raw = ("def f(devices):\n"
           "    from jax.sharding import Mesh\n"
           "    return Mesh(devices, ('x',))\n")
    for path in ("dalle_pytorch_tpu/parallel/mesh.py",
                 "dalle_pytorch_tpu/lint/plans_fixtures.py"):
        assert lint_source(raw, select=("PLAN001",), path=path) == [], path


def test_plan001_pragma_with_reason_suppresses():
    src = ("def f(devices):\n"
           "    from jax.sharding import Mesh\n"
           "    return Mesh(devices, ('_all',))  "
           "# graftlint: disable=PLAN001 (checkpoint IO is plan-agnostic: "
           "restore must work under any plan)\n")
    assert lint_source(src, select=("PLAN001",),
                       path="dalle_pytorch_tpu/utils/checkpoint.py") == []


# --- PRAGMA002: unused suppressions --------------------------------------


def test_pragma002_unused_suppression_flagged():
    src = """
    x = 1  # graftlint: disable=ENV001 (legacy reason, code since rewritten)
    """
    found = lint(src, select=("ENV001",))
    assert rules_of(found) == ["PRAGMA002"]


def test_pragma002_used_suppression_clean():
    src = """
    import os
    if os.environ.get("X"):  # graftlint: disable=ENV001 (value-valued var)
        pass
    """
    assert lint(src, select=("ENV001",)) == []


def test_pragma002_not_judged_when_rule_not_run():
    # an ENV001 pragma cannot be called unused when ENV001 wasn't run
    src = """
    x = 1  # graftlint: disable=ENV001 (reason)
    """
    assert lint(src, select=("SEED001",)) == []


def test_pragma002_multi_rule_pragma_judged_only_fully_selected():
    src = """
    import os
    if os.environ.get("X"):  # graftlint: disable=ENV001,SEED001 (reason)
        pass
    """
    # full run: ENV001 fires and is suppressed -> pragma is used
    assert lint(src) == []
    # SEED001-only run: the pragma names a rule that wasn't run -> skip
    assert lint(src, select=("SEED001",)) == []


# --- machine-readable output ---------------------------------------------


def test_findings_json_validates_against_schema():
    import jsonschema

    src = 'import os\nif os.environ.get("A"):\n    pass\n'
    findings = lint_source(src, path="x.py")
    doc = findings_to_json(findings, files_scanned=1)
    jsonschema.validate(doc, FINDINGS_JSON_SCHEMA)
    assert doc["counts"] == {"ENV001": 1}
    assert doc["findings"][0]["fingerprint"] == fingerprint(findings[0])
    # empty documents validate too (the clean-tree CI artifact)
    jsonschema.validate(findings_to_json([], files_scanned=0),
                        FINDINGS_JSON_SCHEMA)


def test_findings_sarif_minimal_shape():
    src = 'import os\nif os.environ.get("A"):\n    pass\n'
    doc = findings_to_sarif(lint_source(src, path="x.py"))
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "graftlint"
    (res,) = run["results"]
    assert res["ruleId"] == "ENV001"
    assert res["locations"][0]["physicalLocation"]["artifactLocation"][
        "uri"] == "x.py"
    assert res["partialFingerprints"]["graftlint/v1"].startswith("x.py::")


def test_cli_format_json_and_output(tmp_path):
    import jsonschema

    spec = importlib.util.spec_from_file_location(
        "graftlint_cli3", REPO / "tools" / "graftlint.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    dirty = tmp_path / "dirty.py"
    dirty.write_text('import os\nif os.environ.get("A"):\n    pass\n')
    out = tmp_path / "lint.json"
    rc = mod.main([str(dirty), "--baseline", str(tmp_path / "no-bl.json"),
                   "--format", "json", "--output", str(out)])
    assert rc == 1  # findings still fail the run in machine formats
    doc = json.loads(out.read_text())
    jsonschema.validate(doc, FINDINGS_JSON_SCHEMA)
    assert doc["counts"] == {"ENV001": 1}


# --- stale-baseline accounting -------------------------------------------


def test_stale_baseline_entries_and_prune(tmp_path):
    dirty = tmp_path / "legacy.py"
    dirty.write_text('import os\nif os.environ.get("A"):\n    pass\n')
    bl = tmp_path / "bl.json"
    findings = lint_paths([str(dirty)])
    write_baseline(findings, bl)
    # finding fixed -> its fingerprint is stale
    dirty.write_text("x = 1\n")
    now = lint_paths([str(dirty)])
    stale = stale_baseline_entries(now, load_baseline(bl))
    assert len(stale) == 1 and "ENV001" in stale[0]
    dropped = prune_baseline(now, bl)
    assert dropped == stale
    assert load_baseline(bl) == set()
    # pruning an already-clean baseline is a no-op
    assert prune_baseline(now, bl) == []
    assert prune_baseline(now, tmp_path / "missing.json") == []


def test_syntax_error_reported_not_crashed():
    found = lint_source("def broken(:\n    pass\n", path="x.py")
    assert rules_of(found) == ["PARSE001"]


def test_baseline_roundtrip(tmp_path):
    src = 'import os\nif os.environ.get("A"):\n    pass\n'
    found = lint_source(src, path="mod.py")
    assert rules_of(found) == ["ENV001"]
    bl = tmp_path / "baseline.json"
    write_baseline(found, bl)
    assert filter_baseline(found, load_baseline(bl)) == []
    # the baseline is line-number independent: shifting the finding down
    # two lines still matches its fingerprint
    shifted = lint_source("import sys\nimport json\n" + src, path="mod.py")
    assert filter_baseline(shifted, load_baseline(bl)) == []
    # a NEW finding is not masked by the old baseline
    fresh = lint_source('import os\nx = bool(os.environ.get("OTHER_VAR"))\n',
                        path="mod.py")
    assert rules_of(filter_baseline(fresh, load_baseline(bl))) == ["ENV001"]


def test_missing_baseline_is_empty(tmp_path):
    assert load_baseline(tmp_path / "nope.json") == set()


def test_fix_env001_rewrites_and_imports():
    src = ('import os\n'
           'if os.environ.get("KILL_SWITCH"):\n'
           '    pass\n'
           'path = os.environ.get("CACHE", "/tmp")\n')
    fixed, n = fix_env001(src)
    assert n == 1
    assert 'if env_flag("KILL_SWITCH"):' in fixed
    assert "from dalle_pytorch_tpu.utils.helpers import env_flag" in fixed
    # the value-valued read is untouched
    assert 'os.environ.get("CACHE", "/tmp")' in fixed
    # the fixed source is ENV001-clean and still parses
    assert lint_source(fixed, select=("ENV001",)) == []


def test_fix_env001_skips_unfixable_default():
    # a truthy default changes semantics under env_flag -> left for a human
    src = 'import os\nif os.environ.get("X", "1"):\n    pass\n'
    fixed, n = fix_env001(src)
    assert n == 0 and fixed == src


def test_fix_env001_no_duplicate_import():
    src = ('from dalle_pytorch_tpu.utils.helpers import env_flag\n'
           'import os\n'
           'if os.environ.get("A"):\n'
           '    pass\n')
    fixed, n = fix_env001(src)
    assert n == 1
    assert fixed.count("import env_flag") == 1


# --- the repo gate -------------------------------------------------------

LINT_TARGETS = ["dalle_pytorch_tpu", "tools", "chip_smoke.py",
                "train_dalle.py", "genrank.py", "train_vae.py"]


def test_repo_is_graftlint_clean():
    """The acceptance gate: the cleaned tree stays clean.  Every future
    suppression must carry an inline justification (PRAGMA001 enforces it)
    or a baseline entry."""
    findings = filter_baseline(
        lint_paths([str(REPO / p) for p in LINT_TARGETS]),
        load_baseline(REPO / ".graftlint-baseline.json"))
    assert findings == [], "\n".join(f.format() for f in findings)


def test_cli_clean_exit_and_finding_exit(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "graftlint_cli", REPO / "tools" / "graftlint.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    dirty = tmp_path / "dirty.py"
    dirty.write_text('import os\nif os.environ.get("A"):\n    pass\n')
    assert mod.main([str(clean)]) == 0
    assert mod.main([str(dirty)]) == 1
    assert mod.main([str(dirty), "--select", "EXC001"]) == 0
    # --fix makes the dirty file clean in place
    assert mod.main([str(dirty), "--fix"]) == 0
    assert 'env_flag("A")' in dirty.read_text()


def test_cli_write_baseline(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "graftlint_cli2", REPO / "tools" / "graftlint.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    dirty = tmp_path / "legacy.py"
    dirty.write_text('import os\nif os.environ.get("A"):\n    pass\n')
    bl = tmp_path / "bl.json"
    assert mod.main([str(dirty), "--baseline", str(bl),
                     "--write-baseline"]) == 0
    data = json.loads(bl.read_text())
    assert len(data["suppressed"]) == 1
    # with the baseline, the legacy finding is grandfathered
    assert mod.main([str(dirty), "--baseline", str(bl)]) == 0


def test_every_rule_has_fixture_coverage():
    """Meta: the rule registry and this file stay in sync — adding a rule
    without positive-fixture coverage fails here."""
    covered = {"ENV001", "SEED001", "BACKEND001", "DOT001", "TRACE001",
               "EXC001", "CKPT001", "OBS001", "OBS002", "OBS003", "SRV001",
               "THR001", "THR002", "DON001", "DON002", "MEM001", "PLAN001"}
    assert covered == set(RULES)


def test_fingerprint_stability():
    f = Finding(path="a.py", rule="ENV001", line=3, col=0, message="m",
                line_text="  if os.environ.get('X'):  ")
    g = Finding(path="a.py", rule="ENV001", line=99, col=4, message="other",
                line_text="if os.environ.get('X'):")
    assert fingerprint(f) == fingerprint(g)
