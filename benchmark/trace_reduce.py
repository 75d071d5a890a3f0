"""From a profiler trace to numbers: device busy and idle time, device time
per ``graftprof:`` scope and per jitted program, exposed collective time, and
the breakdown the next issue's writer reads.

Two halves, so that the arithmetic can be checked without a chip:

* :func:`extract` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
  into plain lists (what ``tests/data/`` keeps a recording of);
* :func:`reduce` is pure Python over those lists.

What a TPU trace looks like (jax 0.9.0 / libtpu 0.0.34, looked at by hand in
PR 22, see PERF.md): one plane ``/device:TPU:<n>`` per chip.  Its line
``XLA Modules`` holds one event per executed program, named
``jit_<function>(<id>)``.  Its line ``XLA Ops`` holds one event per executed
HLO instruction, named by the instruction's whole text (``%fusion.43 = ...``);
where an op spans others (a ``while`` and its body) time is counted once, as
each event's *self* time.  Its line ``Async XLA Ops`` holds what runs beside
the core: copies, slices and the collectives' ``-start``..``-done`` spans.
The events carry no ``op_name``: their stats are offsets and durations only.
So an op's ``graftprof:`` scope (``obs/prof.py::scope``, a
``jax.named_scope``) is looked up by instruction name in the compiled
program's own HLO text, whose ``metadata={op_name="..."}`` keeps the scope
path; the innermost scope wins (:func:`scopes_of`).  Host spans
(``jax.profiler.TraceAnnotation``) are events of the ``/host:CPU`` plane on
the same clock.
"""
from __future__ import annotations

import dataclasses
import re
import statistics
from typing import Optional

SCOPE_RE = re.compile(r"graftprof:([a-z0-9_-]+)")
COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")
BENCH_SPAN_PREFIX = "bench:"
UNSCOPED = "(no scope)"
OPS_LINE, MODULES_LINE, ASYNC_LINE = "XLA Ops", "XLA Modules", "Async XLA Ops"
INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


# --- extraction -----------------------------------------------------------------

def instr_name(text: str) -> str:
    m = INSTR_RE.match(text)
    return m.group(1) if m else text[:64]


def program_name(module_event_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", module_event_name)


def scopes_of(hlo_text: str) -> dict:
    """``{instruction name: innermost graftprof scope}`` of one compiled
    program, from the ``op_name`` each instruction's metadata keeps."""
    out = {}
    for line in hlo_text.splitlines():
        m = INSTR_RE.match(line)
        if not m:
            continue
        op_name = OP_NAME_RE.search(line)
        found = SCOPE_RE.findall(op_name.group(1)) if op_name else []
        if found:
            out[m.group(1)] = found[-1]
    return out


def extract(xplane_path) -> dict:
    """``{"devices": [{"name", "ops": [[instruction, start_ns, dur_ns,
    program]], "modules": [[program, start_ns, dur_ns]], "collectives":
    [[instruction, start_ns, dur_ns]]}], "host_spans": [[name, start_ns,
    dur_ns]]}``.  ``program`` is the module event that contains the op;
    ``collectives`` are the asynchronous line's collective spans.
    ``devices`` is empty when no device plane was traced."""
    import bisect

    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(xplane_path))
    devices, host_spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            modules = sorted(
                ([program_name(e.name), int(e.start_ns), int(e.duration_ns)]
                 for e in (lines[MODULES_LINE].events
                           if MODULES_LINE in lines else [])),
                key=lambda m: m[1])
            starts = [m[1] for m in modules]
            ops = []
            for e in lines[OPS_LINE].events:
                start, dur = int(e.start_ns), int(e.duration_ns)
                k = bisect.bisect_right(starts, start) - 1
                inside = k >= 0 and start < modules[k][1] + modules[k][2]
                ops.append([instr_name(e.name), start, dur,
                            modules[k][0] if inside else ""])
            coll = [[instr_name(e.name), int(e.start_ns), int(e.duration_ns)]
                    for e in (lines[ASYNC_LINE].events
                              if ASYNC_LINE in lines else [])
                    if COLLECTIVE_RE.search(instr_name(e.name))]
            if ops:
                devices.append({"name": plane.name, "ops": ops,
                                "modules": modules, "collectives": coll})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans += [[e.name, int(e.start_ns), int(e.duration_ns)]
                               for e in line.events
                               if e.name.startswith(BENCH_SPAN_PREFIX)]
    return {"devices": devices, "host_spans": host_spans}


# --- interval arithmetic ----------------------------------------------------------

def union(intervals) -> list:
    """Sorted, disjoint ``[start, end]`` covering the same points."""
    out = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def length(intervals) -> int:
    return sum(end - start for start, end in intervals)


def subtract(a, b) -> list:
    """The part of the disjoint sorted ``a`` that the disjoint sorted ``b``
    does not cover."""
    out, j = [], 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append([cur, end])
    return out


def self_times(ops) -> list:
    """``[(instruction, program, self_ns)]``: each event's duration minus the
    events nested inside it (a ``while`` op and its body)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [op[2] for op in ops]
    stack = []
    for i in order:
        _, start, dur, _ = ops[i]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
        stack.append(i)
    return [(ops[i][0], ops[i][3], max(own[i], 0)) for i in range(len(ops))]


# --- reduction --------------------------------------------------------------------

@dataclasses.dataclass
class Reduced:
    chips: int
    window_s: float               # the traced stretch, host clock
    busy_s: float                 # union of device-op intervals, chip mean
    scope_s: dict                 # scope -> device self seconds, chip mean
    program_s: dict               # program -> {"seconds", "calls", "median_s"}
    collective_s: float           # a collective in flight, device 0
    collective_exposed_s: float   # ... while no other op runs, device 0
    breakdown: dict

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def scope_share(self, scope: str) -> Optional[float]:
        """Share of device busy time under ``scope`` (innermost scope of each
        op), or None when nothing ran under it."""
        if not self.scope_s.get(scope):
            return None
        return self.scope_s[scope] / self.busy_s

    def program(self, name: str) -> Optional[dict]:
        """``{"seconds", "calls", "median_s"}`` of the program the trace calls
        ``name`` (``jit_<function>``), or None if it did not run."""
        return self.program_s.get(name)


def reduce(raw: dict, window_s: Optional[float] = None,
           scopes: Optional[dict] = None) -> Optional[Reduced]:
    """``scopes`` is ``{program name: scopes_of(its HLO text)}``; an op of a
    program that is not in it has no scope.  None when no operation ran on a
    device."""
    devices = raw["devices"]
    if not devices:
        return None
    scopes = scopes or {}
    n = len(devices)
    busy_ns, scope_ns, ops_ns = 0, {}, {}
    for dev in devices:
        busy_ns += length(union([s, s + d] for _, s, d, _ in dev["ops"]))
        for name, program, own in self_times(dev["ops"]):
            scope = scopes.get(program, {}).get(name) or UNSCOPED
            scope_ns[scope] = scope_ns.get(scope, 0) + own
            key = (scope, re.sub(r"[.\d]+$", "", name))
            ops_ns[key] = ops_ns.get(key, 0) + own
    first = devices[0]
    spans = [[s, s + d] for _, s, d, _ in first["ops"]]
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    if window_s is None:
        window_s = (hi - lo) / 1e9

    programs = {}
    for dev in devices:
        for name, _, dur in dev["modules"]:
            programs.setdefault(name, []).append(dur)
    program_s = {k: {"seconds": sum(v) / n / 1e9, "calls": len(v) // n,
                     "median_s": statistics.median(v) / 1e9}
                 for k, v in programs.items()}

    coll = union([[s, s + d] for name, s, d, _ in first["ops"]
                  if COLLECTIVE_RE.search(name)]
                 + [[s, s + d] for _, s, d in first.get("collectives", [])])
    # a while/conditional spans its body, so only leaf events say that
    # compute runs
    leaves = _leaves(first["ops"])
    compute = union([s, s + d] for name, s, d, _ in leaves
                    if not COLLECTIVE_RE.search(name))
    exposed = subtract(coll, compute)

    busy0 = union(spans)
    gaps = subtract([[lo, hi]], busy0)
    host = raw.get("host_spans", [])

    def covering(gap):
        best, best_ns = "no bench span", 0
        for name, s, d in host:
            over = min(gap[1], s + d) - max(gap[0], s)
            if over > best_ns:
                best, best_ns = name, over
        return best

    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    top_ops = sorted(ops_ns.items(), key=lambda kv: -kv[1])[:10]
    breakdown = {
        "device_ops": [[f"{scope}/{name}", ns / n / 1e9]
                       for (scope, name), ns in top_ops],
        "idle_gaps": [[covering(g), (g[1] - g[0]) / 1e9] for g in top_gaps]}
    return Reduced(
        chips=n, window_s=float(window_s), busy_s=busy_ns / n / 1e9,
        scope_s={k: v / n / 1e9 for k, v in scope_ns.items()},
        program_s=program_s, collective_s=length(coll) / 1e9,
        collective_exposed_s=length(exposed) / 1e9, breakdown=breakdown)


def _leaves(ops) -> list:
    """Events with no event nested inside them."""
    order = sorted(ops, key=lambda op: (op[1], -op[2]))
    out = []
    for idx, op in enumerate(order):
        nxt = order[idx + 1] if idx + 1 < len(order) else None
        if nxt is None or nxt[1] >= op[1] + op[2]:
            out.append(op)
    return out
