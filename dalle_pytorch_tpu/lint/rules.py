"""The graftlint rule catalog — each rule is one bug class this repo has
actually shipped (or nearly shipped) and then paid chip time to find.

A rule is a function ``(FileCtx) -> Iterator[(node, message)]``; the engine
owns pragma handling, baselines and reporting.  Rules are deliberately
syntactic (no type inference): they over-approximate, and the pragma's
mandatory justification is the escape hatch where the human knows better.
"""
from __future__ import annotations

import ast
import dataclasses
import re
from typing import Dict, Iterator, List, Optional, Tuple

RuleHit = Tuple[ast.AST, str]


@dataclasses.dataclass
class FileCtx:
    """Parsed source handed to each rule."""

    path: str
    tree: ast.Module
    lines: List[str]


# --- helpers -------------------------------------------------------------


def _attr_chain(node: ast.AST) -> str:
    """Dotted name of a Name/Attribute chain ('jax.lax.scan'), '' otherwise."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_env_get(call: ast.Call) -> bool:
    """``os.environ.get(...)`` / ``environ.get(...)`` / ``os.getenv(...)``."""
    chain = _attr_chain(call.func)
    return chain.endswith("environ.get") or chain.endswith("os.getenv") \
        or chain == "getenv"


def _walk_skip_defs(node: ast.AST) -> Iterator[ast.AST]:
    """ast.walk that does not descend into nested function/class bodies."""
    stack = [node]
    while stack:
        n = stack.pop()
        yield n
        for child in ast.iter_child_nodes(n):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
                continue
            stack.append(child)


# --- ENV001: raw truthiness on os.environ.get ----------------------------


def rule_env001(ctx: FileCtx) -> Iterator[RuleHit]:
    """``bool(os.environ.get(X))`` treats ``X=0`` as ON — an operator
    disabling a flag with 0 silently enables it (the BENCH_PALLAS /
    GRAFT_DRYRUN_FULL footgun, hit twice).  Boolean env knobs must parse
    through ``utils.helpers.env_flag``; value-valued vars where truthiness
    is genuinely presence-of-value (addresses, paths) carry a pragma."""
    msg = ("raw truthiness on an environment read ('VAR=0' counts as ON); "
           "use dalle_pytorch_tpu.utils.helpers.env_flag for boolean flags")
    truth_exprs: list = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.If, ast.While, ast.IfExp)):
            truth_exprs.append(node.test)
        elif isinstance(node, ast.Assert):
            truth_exprs.append(node.test)
        elif isinstance(node, ast.BoolOp):
            truth_exprs.extend(node.values)
        elif isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Not):
            truth_exprs.append(node.operand)
        elif isinstance(node, ast.comprehension):
            truth_exprs.extend(node.ifs)
        elif isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id == "bool":
            truth_exprs.extend(node.args)
    for expr in truth_exprs:
        if isinstance(expr, ast.Call) and _is_env_get(expr):
            yield expr, msg


# --- SEED001: hash()-derived seeds ---------------------------------------


def rule_seed001(ctx: FileCtx) -> Iterator[RuleHit]:
    """Python string hashes are per-process randomized (PYTHONHASHSEED), so
    a ``hash()``-derived seed draws different data on every rerun — an
    on-chip FAIL that doesn't reproduce (the round-5 kernel-equivalence bug;
    ``chip_smoke.variant_seed`` is the crc32 form).
    Use ``zlib.crc32`` for stable content-derived seeds."""
    msg = ("hash() is per-process randomized (PYTHONHASHSEED) — a seed or "
           "PRNGKey derived from it will not reproduce across reruns; use "
           "zlib.crc32 for stable content-derived seeds")
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id == "hash":
            yield node, msg


# --- BACKEND001: module-level backend queries ----------------------------

_BACKEND_QUERIES = frozenset((
    "devices", "local_devices", "default_backend", "device_count",
    "local_device_count", "process_count", "process_index",
))


def rule_backend001(ctx: FileCtx) -> Iterator[RuleHit]:
    """No backend query at import time.  A module-level ``jax.devices()`` /
    ``jax.default_backend()`` initializes the backend the moment the module
    is imported: a process that merely imports a tool then holds the chip
    (a chip belongs to one process at a time, so a child that needs it
    fails or hangs), and platform / device-count settings made after the
    import can no longer take effect.  Query the backend inside the
    function that needs it."""
    msg = ("module-level {} initializes the JAX backend at import time — "
           "the importing process then holds the chip and later platform "
           "settings are ignored; query the backend inside the function "
           "that needs it")
    for node in _walk_skip_defs(ctx.tree):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr in _BACKEND_QUERIES \
                and _attr_chain(node.func.value) == "jax":
            yield node, msg.format(f"{_attr_chain(node.func)}()")


# --- DOT001: dot-family calls without an accumulation contract -----------

_DOT_FUNCS = frozenset(("einsum", "dot", "matmul", "tensordot"))
_JAX_NUMPY_RECEIVERS = frozenset(("jnp", "jax.numpy", "jaxnp"))
_LAX_RECEIVERS = frozenset(("lax", "jax.lax"))


def rule_dot001(ctx: FileCtx) -> Iterator[RuleHit]:
    """A jnp dot/einsum with no ``preferred_element_type`` leaves the
    accumulation dtype to inference from the (possibly mixed) operand
    dtypes — and lets XLA satisfy a mixed-dtype dot by hoisting a full
    f32 convert of the wider operand (the bf16-KV-cache defeat PR 1
    measured: it more than doubled decode cache bytes).  Every jnp-level
    dot states ``preferred_element_type`` explicitly, or carries a pragma
    proving the operand dtypes are uniform by construction."""
    msg = ("{} without preferred_element_type: the accumulation/output "
           "dtype is inferred from operand dtypes, and a mixed-dtype dot "
           "lets XLA materialize a full f32 convert of the wider operand; "
           "pass preferred_element_type (usually jnp.float32) or pragma "
           "with a proof the operands are dtype-uniform")
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) \
                or not isinstance(node.func, ast.Attribute):
            continue
        recv = _attr_chain(node.func.value)
        is_dot = (node.func.attr in _DOT_FUNCS
                  and recv in _JAX_NUMPY_RECEIVERS) \
            or (node.func.attr == "dot_general" and recv in _LAX_RECEIVERS)
        if not is_dot:
            continue
        if any(kw.arg == "preferred_element_type" for kw in node.keywords):
            continue
        yield node, msg.format(f"{recv}.{node.func.attr}")


# --- TRACE001: host syncs inside traced code -----------------------------

_SCAN_BODY_ARGS = {  # callable-position args of the structured control flow
    "scan": (0,), "map": (0,), "while_loop": (0, 1), "fori_loop": (2,),
    "cond": (1, 2), "switch": ()  # switch takes a list — handled below
}
_HOST_SYNC_RECEIVERS = frozenset(("np", "numpy", "onp"))


def _is_jit_decorator(dec: ast.AST) -> bool:
    if isinstance(dec, ast.Call):
        # @partial(jax.jit, ...) / @jax.jit(...) / @nn.jit(...)
        chain = _attr_chain(dec.func)
        if chain.endswith("partial") and dec.args:
            return _attr_chain(dec.args[0]).endswith("jit")
        return chain.endswith("jit") or chain.endswith("pjit")
    return _attr_chain(dec).endswith("jit") or _attr_chain(dec).endswith("pjit")


def rule_trace001(ctx: FileCtx) -> Iterator[RuleHit]:
    """``.item()`` / ``np.asarray`` / ``float()`` on a traced value inside a
    ``@jax.jit`` or ``lax.scan`` body either fails at trace time on a path
    nobody ran, or (worse, via callbacks/weak types) forces a device sync
    per step.  Host fetches belong outside the traced program."""
    msg = ("host-sync call {} inside a traced ({}) body: this blocks on "
           "device transfer per trace or fails on untested paths; hoist "
           "the host fetch out of the traced program")
    traced: list = []  # (body_root, why)
    defs_by_name: dict = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(node.name, node)
            if any(_is_jit_decorator(d) for d in node.decorator_list):
                traced.append((node, "@jit"))
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) \
                or not isinstance(node.func, ast.Attribute):
            continue
        if _attr_chain(node.func.value) not in _LAX_RECEIVERS:
            continue
        for pos in _SCAN_BODY_ARGS.get(node.func.attr, ()):
            if pos >= len(node.args):
                continue
            arg = node.args[pos]
            if isinstance(arg, ast.Lambda):
                traced.append((arg, f"lax.{node.func.attr}"))
            elif isinstance(arg, ast.Name) and arg.id in defs_by_name:
                traced.append((defs_by_name[arg.id],
                               f"lax.{node.func.attr}"))

    seen = set()
    for body_root, why in traced:
        for node in ast.walk(body_root):
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            bad = None
            if isinstance(node.func, ast.Attribute):
                recv = _attr_chain(node.func.value)
                if node.func.attr == "item" and not node.args:
                    bad = ".item()"
                elif node.func.attr in ("asarray", "array") \
                        and recv in _HOST_SYNC_RECEIVERS:
                    bad = f"{recv}.{node.func.attr}()"
                elif node.func.attr == "device_get" and recv == "jax":
                    bad = "jax.device_get()"
            elif isinstance(node.func, ast.Name) \
                    and node.func.id in ("float", "int") \
                    and len(node.args) == 1 \
                    and isinstance(node.args[0],
                                   (ast.Attribute, ast.Subscript)):
                bad = f"{node.func.id}()"
            if bad:
                seen.add(id(node))
                yield node, msg.format(bad, why)


# --- EXC001: broad excepts that swallow XLA errors -----------------------


def rule_exc001(ctx: FileCtx) -> Iterator[RuleHit]:
    """``except:`` / ``except Exception:`` with no re-raise swallows
    ``XlaRuntimeError`` — which is how a lost device, an OOM, or a
    cross-host desync presents.  A swallowed one turns a loud failure into
    silent corruption.  Narrow the class, re-raise, or pragma with the
    reason this specific handler may eat everything."""
    msg = ("{} swallows XlaRuntimeError (lost device / OOM / desync "
           "present as generic exceptions); catch a narrower class, "
           "re-raise, or pragma with why swallowing is safe here")
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if node.type is None:
            label = "bare 'except:'"
        else:
            names = [node.type] if not isinstance(node.type, ast.Tuple) \
                else list(node.type.elts)
            broad = [n for n in names
                     if _attr_chain(n).split(".")[-1] in ("Exception",
                                                          "BaseException")]
            if not broad:
                continue
            label = f"'except {_attr_chain(broad[0])}:'"
        if any(isinstance(n, ast.Raise) for n in ast.walk(node)):
            continue  # the handler re-raises — errors still propagate
        yield node, msg.format(label)


# --- CKPT001: raw durable-state writes outside the atomic helpers --------

# "shard"/"index" cover the streaming shard sets (data/stream.py): the
# shard index IS a manifest — a torn index.json makes the whole corpus
# unreadable — so raw writes to shard-ish targets route through the same
# atomic helpers (helpers.atomic_write_json / temp + os.replace).
_CKPT_TOKENS = ("ckpt", "checkpoint", "heartbeat", "manifest", "shard")
_WRITE_MODE_CHARS = "wax"


def _literal_mode(call: ast.Call, pos: int) -> str:
    """The mode string of an open()-style call, '' if absent/non-literal.
    ``pos`` is the mode's positional index: 1 for builtin ``open(file,
    mode)``, 0 for ``Path.open(mode)``."""
    mode = None
    if len(call.args) > pos:
        mode = call.args[pos]
    for kw in call.keywords:
        if kw.arg == "mode":
            mode = kw.value
    if isinstance(mode, ast.Constant) and isinstance(mode.value, str):
        return mode.value
    return ""


def rule_ckpt001(ctx: FileCtx) -> Iterator[RuleHit]:
    """Durable run state (checkpoints, heartbeats, manifests) written with
    a raw ``open(..., "wb")`` / ``write_text`` can be torn by a crash or
    preemption mid-write — and a torn checkpoint is exactly the failure
    the crash-consistency layer exists to survive.  Every durable-state
    write must go through the atomic-rename helpers in ``utils/``
    (``save_checkpoint``, ``CheckpointManager``, ``Heartbeat._write``:
    temp file + fsync + ``os.replace``), which are themselves exempt.
    Syntactic over-approximation: any write-mode open / ``write_text`` /
    ``write_bytes`` whose target expression mentions a checkpoint-ish
    token; pragma with a justification where the write is provably not
    durable state (or already renamed into place)."""
    msg = ("raw {} to a checkpoint/heartbeat/manifest path can be torn by "
           "a crash mid-write; route durable-state writes through the "
           "atomic-rename helpers in dalle_pytorch_tpu/utils "
           "(save_checkpoint / CheckpointManager / Heartbeat), or pragma "
           "with why this write is not durable state")
    parts = ctx.path.replace("\\", "/").split("/")
    if "utils" in parts:  # the atomic helpers live here
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        target = None
        label = None
        if isinstance(node.func, ast.Name) and node.func.id == "open" \
                and node.args:
            mode = _literal_mode(node, 1)
            if any(c in mode for c in _WRITE_MODE_CHARS):
                target = ast.unparse(node.args[0])
                label = f'open(..., "{mode}")'
        elif isinstance(node.func, ast.Attribute):
            if node.func.attr == "open":
                mode = _literal_mode(node, 0)
                if any(c in mode for c in _WRITE_MODE_CHARS):
                    target = ast.unparse(node.func.value)
                    label = f'.open("{mode}")'
            elif node.func.attr in ("write_text", "write_bytes"):
                target = ast.unparse(node.func.value)
                label = f".{node.func.attr}()"
        if target and any(tok in target.lower() for tok in _CKPT_TOKENS):
            yield node, msg.format(label)


# --- OBS001: bare print() in step/serve/ckpt hot paths --------------------

# package subtrees whose narration must reach the telemetry stream: the
# step/serve/ckpt/data hot paths every post-mortem replays.  models/ops/
# parallel are pure computation (no narration), lint is host tooling, and
# the sinks themselves (obs/, utils/logging.py's TrainLogger) are exempt —
# a sink printing is the sink working.
_OBS_HOT_SUBTREES = ("serve", "data", "utils")
_OBS_HOT_FILES = ("training.py",)
_OBS_EXEMPT = (("utils", "logging.py"),)


def rule_obs001(ctx: FileCtx) -> Iterator[RuleHit]:
    """A bare ``print()`` in a hot path (step loop, serve scheduler,
    checkpoint manager, data pipeline) narrates to a terminal nobody is
    watching and to no one else: the bench rounds that died on a hung
    device call left NO attributable timeline because every layer logged
    this way.  Operator messages in ``dalle_pytorch_tpu/``'s serve/data/utils
    subtrees (and training.py) must go through ``obs.telemetry.note`` —
    the stderr line AND the stream event in one call — or TrainLogger;
    pragma with a reason where a raw print is genuinely correct (e.g. a
    CLI-only surface)."""
    msg = ("bare print() in a step/serve/ckpt hot path leaves no record in "
           "the run's telemetry stream; use dalle_pytorch_tpu.obs."
           "telemetry.note (stderr line + stream event) or TrainLogger, or "
           "pragma with why a raw print is correct here")
    parts = tuple(ctx.path.replace("\\", "/").split("/"))
    if "dalle_pytorch_tpu" not in parts:
        return
    sub = parts[parts.index("dalle_pytorch_tpu") + 1:]
    if not sub or any(sub[-len(ex):] == ex for ex in _OBS_EXEMPT):
        return
    if sub[0] not in _OBS_HOT_SUBTREES and sub[-1] not in _OBS_HOT_FILES:
        return
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id == "print":
            yield node, msg


# --- OBS002: wall-clock duration math -------------------------------------


def _is_time_time(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) \
        and _attr_chain(node.func) == "time.time"


def rule_obs002(ctx: FileCtx) -> Iterator[RuleHit]:
    """``time.time() - t0`` measures a duration with a clock that NTP can
    step backwards mid-run and that skews by seconds across a fleet — the
    exact wobble obs/align.py exists to undo.  Inside
    ``dalle_pytorch_tpu/``, durations must come from ``time.monotonic()``
    (or ``perf_counter``); wall clock is reserved for envelope timestamps
    (telemetry ``t``, heartbeat ``time``) that cross processes.  Flags a
    subtraction whose operand is a direct ``time.time()`` call or a name
    assigned from one in the same scope; genuinely cross-clock math
    (wall vs a file mtime) carries a pragma saying so.  Aliased imports
    escape — the usual syntactic over-approximation contract."""
    msg = ("duration math on a time.time() delta: wall clocks skew across "
           "hosts and NTP can step them mid-run; use time.monotonic() for "
           "durations (wall clock is for envelope timestamps only), or "
           "pragma with why this subtraction is genuinely cross-clock")
    parts = tuple(ctx.path.replace("\\", "/").split("/"))
    if "dalle_pytorch_tpu" not in parts:
        return
    scopes = [ctx.tree] + [
        n for n in ast.walk(ctx.tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    for scope in scopes:
        wall_names = {
            node.targets[0].id
            for node in _walk_skip_defs(scope)
            if isinstance(node, ast.Assign) and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and _is_time_time(node.value)}
        for node in _walk_skip_defs(scope):
            if not isinstance(node, ast.BinOp) \
                    or not isinstance(node.op, ast.Sub):
                continue
            if any(_is_time_time(side)
                   or (isinstance(side, ast.Name) and side.id in wall_names)
                   for side in (node.left, node.right)):
                yield node, msg


# --- OBS003: unmanaged jax.profiler entry points ---------------------------

_OBS3_PROFILER_CALLS = frozenset(("profiler.start_trace",
                                  "profiler.stop_trace", "profiler.trace"))
_OBS3_EXEMPT = (("obs", "prof.py"),)


def rule_obs003(ctx: FileCtx) -> Iterator[RuleHit]:
    """A direct ``jax.profiler.start_trace/stop_trace/trace`` call outside
    ``obs/prof.py``'s managed ``capture()`` helper produces an on-chip
    trace window the telemetry stream never hears about: the Perfetto
    fleet merge can't correlate it, a death inside it leaves the profiler
    wedged with no torn-span record, and graftscope's run report shows a
    step-time crater with no cause.  Route captures through
    ``obs.prof.capture(logdir)`` (or ``prof.XprofWindow`` for step-window
    arming) — one entry point that opens the trace inside a ``prof.xprof``
    span; pragma with a reason where a raw call is genuinely correct
    (e.g. a debugging scratch script)."""
    msg = ("direct jax.profiler trace call outside obs/prof.py: the "
           "on-chip capture window never lands in the telemetry stream "
           "(no prof.xprof span, no fleet correlation, no torn-span "
           "record on death); use dalle_pytorch_tpu.obs.prof.capture / "
           "XprofWindow, or pragma with why an unmanaged trace is "
           "correct here")
    parts = tuple(ctx.path.replace("\\", "/").split("/"))
    if any(parts[-len(ex):] == ex for ex in _OBS3_EXEMPT):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        if any(chain == c or chain.endswith("." + c)
               for c in _OBS3_PROFILER_CALLS):
            yield node, msg


# --- MEM001: unmanaged device-memory polling entry points ------------------

_MEM1_POLL_CALLS = frozenset(("device_memory_profile",
                              "profiler.device_memory_profile",
                              "live_arrays"))
_MEM1_EXEMPT = (("obs", "mem.py"),)


def rule_mem001(ctx: FileCtx) -> Iterator[RuleHit]:
    """A direct ``jax.profiler.device_memory_profile`` /
    ``jax.live_arrays`` call outside ``obs/mem.py`` produces a memory
    sample the observability stack never hears about: no
    ``mem.watermark`` telemetry record, no ``graft_hbm_*`` gauges, no
    ``hbm_headroom`` alert input, and the serve leak gate's baseline
    census can't account for it (a stray ``live_arrays()`` in a hot loop
    is itself a way to pin buffers).  Route polling through
    ``obs.mem.MemTracker`` / ``mem.live_buffer_stats`` /
    ``mem.device_memory_stats`` / ``mem.write_device_memory_profile`` —
    the OBS003 one-managed-entry-point discipline, applied to the
    memory APIs; pragma with a reason where a raw call is genuinely
    correct (e.g. a debugging scratch script)."""
    msg = ("direct jax device-memory poll outside obs/mem.py: the sample "
           "never lands in the telemetry stream (no mem.watermark record, "
           "no graft_hbm_* gauges, no hbm_headroom alert input, invisible "
           "to the serve leak-gate baseline); use dalle_pytorch_tpu.obs."
           "mem.MemTracker / live_buffer_stats / device_memory_stats / "
           "write_device_memory_profile, or pragma with why an unmanaged "
           "poll is correct here")
    parts = tuple(ctx.path.replace("\\", "/").split("/"))
    if any(parts[-len(ex):] == ex for ex in _MEM1_EXEMPT):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        if any(chain == c or chain.endswith("." + c)
               for c in _MEM1_POLL_CALLS):
            yield node, msg


# --- SRV001: unbounded blocking waits in serve/ ---------------------------

_SRV_BLOCKING = frozenset(("result", "get", "acquire"))


def rule_srv001(ctx: FileCtx) -> Iterator[RuleHit]:
    """A blocking wait without a timeout inside ``dalle_pytorch_tpu/serve/``
    turns a dead replica into a hung router: the whole fleet tier exists
    to convert losses into typed errors, and one ``future.result()`` with
    no deadline quietly reintroduces the infinite hang the SLO layer can
    never shed.  Flags ``.result()`` / ``.get()`` / ``.acquire()`` calls
    that pass neither a positional argument nor a ``timeout=`` keyword
    (a zero-arg ``.get()`` is the blocking queue form — dict ``.get``
    always takes a key).  ``with lock:`` blocks are fine (bounded by the
    holder, not a wait-for-event); pragma with why a wait is provably
    bounded where the rule over-approximates."""
    msg = ("blocking {}() without a timeout in serve/: a dead replica or a "
           "lost wakeup turns this wait into a hang no SLO policy can "
           "shed; pass an explicit timeout (and handle expiry) or pragma "
           "with why this wait is bounded")
    parts = tuple(ctx.path.replace("\\", "/").split("/"))
    if "dalle_pytorch_tpu" not in parts:
        return
    sub = parts[parts.index("dalle_pytorch_tpu") + 1:]
    if not sub or sub[0] != "serve":
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) \
                or not isinstance(node.func, ast.Attribute) \
                or node.func.attr not in _SRV_BLOCKING:
            continue
        if node.args:
            continue  # positional timeout (result(t), get(block, t))
        if any(kw.arg == "timeout" for kw in node.keywords):
            continue
        yield node, msg.format(node.func.attr)


# --- DON001/DON002: buffer donation (the AST side of graftspmd S2) --------

_STEP_FACTORY_RE = re.compile(r"^make_\w*step\w*$")
_TRAIN_STEP_FACTORY_RE = re.compile(r"^make_\w*train_step$")


def _jit_call_keywords(call: ast.Call) -> Optional[List[ast.keyword]]:
    """The keyword list of a jit/pjit wrapping call (including the
    ``partial(jax.jit, ...)`` form), or None if ``call`` is not one."""
    chain = _attr_chain(call.func)
    if chain.endswith("partial") and call.args \
            and _attr_chain(call.args[0]).split(".")[-1] in ("jit", "pjit"):
        return list(call.keywords)
    if chain.split(".")[-1] in ("jit", "pjit"):
        return list(call.keywords)
    return None


def rule_don001(ctx: FileCtx) -> Iterator[RuleHit]:
    """A train-step factory that jits without ``donate_argnums`` ships a
    step holding params+opt_state alive TWICE across the update (inputs
    kept by the caller, outputs fresh buffers) — at CUB geometry that is
    ~350 MiB of silent HBM overhead per chip, and the optimizer-state
    double is exactly how plans that "should fit" OOM.  Every jit inside
    a ``make_*step*`` factory must state its donation (an explicit empty
    ``donate_argnums=()`` is a statement, and the dynamic half — whether
    the donation survives compilation — is graftspmd S2's job)."""
    msg = ("jit inside step factory {!r} without donate_argnums: the "
           "returned step keeps params/opt_state buffers alive twice "
           "across the update; state the donation explicitly "
           "(donate_argnums=(0, 1), or =() with a pragma-level reason)")
    for fn in ast.walk(ctx.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                or not _STEP_FACTORY_RE.match(fn.name):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            kws = _jit_call_keywords(node)
            if kws is None:
                continue
            if not any(kw.arg in ("donate_argnums", "donate_argnames")
                       for kw in kws):
                yield node, msg.format(fn.name)


def _donated_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
    """Positional indices a call's assignee will donate, if statically
    knowable: ``jax.jit(..., donate_argnums=<literal>)`` or a
    ``make_*_train_step(...)`` factory call (donates (0, 1) unless built
    with ``donate=False`` or ``jit=False``)."""
    kws = {kw.arg: kw.value for kw in call.keywords}
    jit_kws = _jit_call_keywords(call)
    if jit_kws is not None:
        da = kws.get("donate_argnums")
        if isinstance(da, ast.Constant) and isinstance(da.value, int):
            return (da.value,)
        if isinstance(da, ast.Tuple) and all(
                isinstance(e, ast.Constant) and isinstance(e.value, int)
                for e in da.elts):
            return tuple(e.value for e in da.elts)
        return None
    if isinstance(call.func, ast.Name) \
            and _TRAIN_STEP_FACTORY_RE.match(call.func.id):
        for off in ("donate", "jit"):
            v = kws.get(off)
            if isinstance(v, ast.Constant) and v.value is False:
                return None
        return (0, 1)
    return None


def _target_names(node: ast.AST) -> List[str]:
    if isinstance(node, ast.Name):
        return [node.id]
    if isinstance(node, (ast.Tuple, ast.List)):
        out: List[str] = []
        for e in node.elts:
            out.extend(_target_names(e.value if isinstance(e, ast.Starred)
                                     else e))
        return out
    return []


def _helper_donation_signatures(tree) -> Dict[str, Tuple[int, ...]]:
    """Per-function donated-PARAMETER positions: the cross-function half
    of DON002.  A helper that forwards its own parameter to a donated
    position of a tracked donating call (a donating jit/factory
    assignment visible anywhere in the file, or another already-resolved
    helper — fixed point, so helper-of-helper chains resolve) effectively
    donates that parameter: the CALLER's variable is dead after the
    helper returns, exactly as if it had called the jit directly.  Name
    resolution is file-global and syntactic (no scope analysis) — the
    over-approximation a pragma can override, same contract as the rest
    of the rule."""
    # every single-name donating assignment anywhere in the file (module
    # scope, function bodies, nested defs): the closure-captured
    # `_codes_step = make_*_train_step(...)` idiom must resolve inside
    # the sibling nested def that forwards to it
    assigned: Dict[str, Tuple[int, ...]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Call):
            pos = _donated_positions(node.value)
            if pos:
                assigned[node.targets[0].id] = pos
    fns = [n for n in ast.walk(tree)
           if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    signatures: Dict[str, Tuple[int, ...]] = {}
    changed = True
    while changed:
        changed = False
        for fn in fns:
            param_idx = {a.arg: i for i, a in enumerate(fn.args.args)}
            donated: set = set(signatures.get(fn.name, ()))
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call) \
                        or not isinstance(node.func, ast.Name):
                    continue
                callee = node.func.id
                positions = assigned.get(callee) or signatures.get(callee)
                if not positions:
                    continue
                for pos in positions:
                    if pos < len(node.args) \
                            and isinstance(node.args[pos], ast.Name) \
                            and node.args[pos].id in param_idx:
                        donated.add(param_idx[node.args[pos].id])
            if donated and tuple(sorted(donated)) \
                    != signatures.get(fn.name):
                signatures[fn.name] = tuple(sorted(donated))
                changed = True
    return signatures


def rule_don002(ctx: FileCtx) -> Iterator[RuleHit]:
    """A variable passed at a donated position is DEAD after the call —
    jax invalidates the buffer — yet a read after the call is only caught
    at runtime ("array has been deleted"), typically on the untested
    resume/periodic-save path.  Flags donated args that are read again
    later in the same scope without the call statement rebinding them
    (the ``params, opt_state, ... = step(params, opt_state, ...)`` idiom
    is the clean shape).  Tracks single-name assignments from
    ``jax.jit(..., donate_argnums=...)`` and ``make_*_train_step(...)``
    calls, AND — the cross-function escape — helpers that forward their
    own parameters to such a call (:func:`_helper_donation_signatures`):
    a caller's variable handed to ``run_step(params, ...)`` is just as
    dead as one handed to the jit directly, and reading it afterwards is
    the same use-after-donation.  Syntactic over-approximation — a read
    on a disjoint branch needs a pragma with the reason."""
    msg = ("{!r} is donated by this call (position {}) and its buffer is "
           "deleted, but it is read again at line {} in the same scope; "
           "rebind it from the call's outputs or drop the later read")
    helper_sigs = _helper_donation_signatures(ctx.tree)
    scopes = [ctx.tree] + [
        n for n in ast.walk(ctx.tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    for scope in scopes:
        body = scope.body if hasattr(scope, "body") else []
        wrapped = ast.Module(body=body, type_ignores=[])
        # per-scope tracking: a name is donating only while its latest
        # single-name assignment in THIS scope is a donating jit/factory
        # call (a donate=False or unrelated reassignment drops it).
        # Helpers with donation signatures seed the map — a nested `def
        # run_step(...)` binding in this scope, or a module-level helper.
        donating: Dict[str, Tuple[int, ...]] = dict(helper_sigs)
        for node in _walk_skip_defs(wrapped):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                pos = _donated_positions(node.value) \
                    if isinstance(node.value, ast.Call) else None
                if pos:
                    donating[node.targets[0].id] = pos
                else:
                    donating.pop(node.targets[0].id, None)
        if not donating:
            continue
        loads = [(n.lineno, n.id) for n in _walk_skip_defs(wrapped)
                 if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)]
        for stmt in body:
            yield from _don002_stmt(stmt, donating, loads, msg)


_STMT_CONTAINERS = (ast.ExceptHandler,) + (
    (ast.match_case,) if hasattr(ast, "match_case") else ())


def _own_exprs(stmt: ast.AST) -> Iterator[ast.AST]:
    """The expressions belonging to this statement itself — its header and
    inline values, but not its sub-statements (each gets its own
    rebinding context) and not nested def/lambda bodies (their params
    shadow outer names)."""
    skip = (ast.stmt, ast.Lambda) + _STMT_CONTAINERS
    stack = [c for c in ast.iter_child_nodes(stmt)
             if not isinstance(c, skip)]
    while stack:
        n = stack.pop()
        yield n
        stack.extend(c for c in ast.iter_child_nodes(n)
                     if not isinstance(c, skip))


def _don002_stmt(stmt: ast.AST, donating, loads, msg) -> Iterator[RuleHit]:
    """Check one statement's own expressions for tracked donating calls,
    recursing into compound-statement bodies (each inner statement carries
    its own rebinding context) but not nested defs."""
    if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                         ast.ClassDef)):
        return
    for child in ast.iter_child_nodes(stmt):
        if isinstance(child, (ast.stmt,) + _STMT_CONTAINERS):
            yield from _don002_stmt(child, donating, loads, msg)
    rebound = [n for t in stmt.targets for n in _target_names(t)] \
        if isinstance(stmt, ast.Assign) else []
    end = stmt.end_lineno or stmt.lineno
    for node in _own_exprs(stmt):
        if not isinstance(node, ast.Call) \
                or not isinstance(node.func, ast.Name):
            continue
        positions = donating.get(node.func.id)
        if not positions:
            continue
        for pos in positions:
            if pos >= len(node.args) or not isinstance(node.args[pos],
                                                       ast.Name):
                continue
            name = node.args[pos].id
            if name in rebound:
                continue
            later = [ln for ln, nid in loads if nid == name and ln > end]
            if later:
                yield node, msg.format(name, pos, min(later))


# --- THR001/THR002: thread discipline (the AST side of graftrace) ---------

_THR_LOCK_CTORS = frozenset(("Lock", "RLock", "Condition"))


def rule_thr001(ctx: FileCtx) -> Iterator[RuleHit]:
    """Raw ``threading.Lock/RLock/Condition`` construction outside
    ``utils/locks.py`` bypasses the graftrace witness: that lock's
    acquisitions never land in the order graph or the contention stats,
    so the chaos suites can no longer prove the fleet deadlock-free.
    Construct through ``locks.TracedLock/TracedRLock/TracedCondition``
    (drop-in, free when the witness is disarmed).  ``threading.Event`` is
    fine — events carry no ordering.  Fixture files (``*_fixtures.py``)
    are exempt: their raw locks are the analyzer's test subjects."""
    msg = ("raw threading.{}() bypasses the graftrace lock-order witness; "
           "construct via utils.locks.Traced{} (same semantics, witness "
           "sees it) or pragma with why this lock must stay untraced")
    norm = ctx.path.replace("\\", "/")
    if norm.endswith("utils/locks.py") or norm.endswith("_fixtures.py"):
        return
    from_imports = set()
    for node in ctx.tree.body:
        if isinstance(node, ast.ImportFrom) and node.module == "threading":
            from_imports.update(a.asname or a.name for a in node.names)
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        name = chain.split(".")[-1]
        if name not in _THR_LOCK_CTORS:
            continue
        if chain == f"threading.{name}" or (chain == name
                                            and name in from_imports):
            yield node, msg.format(name, name)


def rule_thr002(ctx: FileCtx) -> Iterator[RuleHit]:
    """A ``while`` loop that polls shared state with ``time.sleep`` under
    ``dalle_pytorch_tpu/serve/`` burns its poll interval on every state
    change it is waiting for — and worse, never wakes early for shutdown,
    so a close() racing the loop waits out the full interval (or hangs,
    if the condition can no longer become true).  Wait on a
    ``threading.Event``/``Condition`` instead (``stop_evt.wait(dt)`` is
    the drop-in form: same pacing, immediate wakeup on close).  Pragma
    the open-loop cases that pace against a local clock rather than
    shared state."""
    msg = ("while-loop polls with sleep() in serve/: sleeps never wake "
           "early for close/stop and add a full interval of latency per "
           "state change; wait on an Event/Condition "
           "(e.g. stop_evt.wait(dt)) or pragma with why this loop paces "
           "a local clock, not shared state")
    parts = tuple(ctx.path.replace("\\", "/").split("/"))
    if "dalle_pytorch_tpu" not in parts:
        return
    sub = parts[parts.index("dalle_pytorch_tpu") + 1:]
    if not sub or sub[0] != "serve":
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.While):
            continue
        for inner in _walk_skip_defs(node):
            if isinstance(inner, ast.Call) \
                    and _attr_chain(inner.func).split(".")[-1] == "sleep" \
                    and _attr_chain(inner.func) in ("time.sleep", "sleep"):
                yield inner, msg
                break


_PLAN_SHARDING_CTORS = frozenset(("Mesh", "NamedSharding", "PartitionSpec"))


def rule_plan001(ctx: FileCtx) -> Iterator[RuleHit]:
    """Hand-constructed ``Mesh``/``NamedSharding``/``PartitionSpec``
    outside ``parallel/`` bypasses the ParallelPlan contract: the sharding
    never flows through PARTITION_RULES, so graftplan's P1-P4 analyses
    (rule coverage, axis divisibility, HBM fit, collective placement —
    lint/plans.py) cannot see it, and spec strings drift from the plan the
    run declared.  Go through the plan registry and ``Partitioner``
    (``plan.partitioner().param_specs/shard_batch``) instead, or pragma
    with why this sharding is genuinely outside the plan's rule table.
    The ``parallel/`` package itself and fixture files are exempt: they
    are where the contract is implemented and tested."""
    msg = ("hand-constructed {}() bypasses the ParallelPlan rule table — "
           "graftplan's static analyses can't see this sharding; build it "
           "through parallel.plan/Partitioner or pragma with why it lives "
           "outside the plan contract")
    norm = ctx.path.replace("\\", "/")
    if "/parallel/" in norm or norm.startswith("parallel/") \
            or norm.endswith("_fixtures.py"):
        return
    # local aliases of the ctors: `from jax.sharding import
    # PartitionSpec as P` must still match — walk the WHOLE tree, since
    # this repo imports jax lazily inside functions (ENV001 discipline)
    aliases = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom) \
                and node.module in ("jax.sharding", "jax.experimental.pjit"):
            for a in node.names:
                if a.name in _PLAN_SHARDING_CTORS:
                    aliases[a.asname or a.name] = a.name
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        name = chain.split(".")[-1]
        if name in _PLAN_SHARDING_CTORS and (
                chain == f"jax.sharding.{name}"
                or chain == f"sharding.{name}"):
            yield node, msg.format(name)
        elif chain in aliases:
            yield node, msg.format(aliases[chain])


RULES = {
    "ENV001": rule_env001,
    "SEED001": rule_seed001,
    "BACKEND001": rule_backend001,
    "DOT001": rule_dot001,
    "TRACE001": rule_trace001,
    "EXC001": rule_exc001,
    "CKPT001": rule_ckpt001,
    "OBS001": rule_obs001,
    "OBS002": rule_obs002,
    "OBS003": rule_obs003,
    "MEM001": rule_mem001,
    "SRV001": rule_srv001,
    "THR001": rule_thr001,
    "THR002": rule_thr002,
    "DON001": rule_don001,
    "DON002": rule_don002,
    "PLAN001": rule_plan001,
}
