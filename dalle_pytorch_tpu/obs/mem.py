"""graftmem: device-memory attribution + the committed HBM ledger
(DESIGN.md §19) — the memory-side twin of :mod:`obs.prof`.

**Predicted side.**  :func:`peak_live` runs a linear-scan liveness walk
over a traced jaxpr — every variable is live from the equation that
produces it to its last use (arguments for the whole call: XLA holds arg
buffers unless donated) — and reports the peak resident bytes together
with a snapshot of WHO was live at the peak: resident *planes* (params /
opt-state / weights / arena / args, labelled from the caller's argument
trees) and per-``prof.scope`` *activations* (the producing equation's
innermost graftprof scope).  Phase builders fold the walk and the
opt0-compiled memory stats (``lint/spmd.py`` S4 conventions, donation
credit from the S2-verified alias audit) into the memory timeline one
run actually traverses::

    init          params + opt state resident (compiled argument bytes)
    step_peak     args + outputs + temps − donation credit
    ckpt          step_peak + forfeited donation credit (the async
                  snapshot pins the old state, so XLA cannot alias it
                  into the next step's outputs)
    serve_steady  weights + arena planes (int8 payload AND f32 scale
                  planes — they are real arena state) + tick transients

:func:`headroom_verdict` folds a timeline against ``prof.CHIP_SPECS``
HBM per chip (same 0.9 allocator-fragmentation margin as S4's
``check_hbm_budget``); ``tools/graftmem.py`` sweeps every train-step
factory × plan plus decode / serve-tick and commits the result as
``memory`` rows merged into the SAME ``PERF_LEDGER.json`` fingerprints
graftprof owns.  :func:`diff_memory` is the CI drift gate: >5% peak
bytes in any phase without a ledger update goes red, naming the scope
or plane that moved most.

**Measured side.**  :class:`MemTracker` is the repo's ONE managed entry
point over ``jax.live_arrays()`` / the allocator stats behind
``jax.profiler.device_memory_profile`` (graftlint MEM001 flags direct
calls elsewhere, mirroring OBS003's discipline for trace windows): it
polls at phase boundaries, emits ``mem.watermark`` telemetry records
(→ ``graft_hbm_{used,peak,headroom}_bytes`` gauges via
``obs/metrics.py``, the ``hbm_headroom`` alert rule, and obs_report's
"memory (predicted vs measured)" section), and carries the serve leak
gate: :meth:`MemTracker.baseline` after warmup, then
:meth:`MemTracker.check_baseline` after admit/retire churn or a chaos
drill — live-buffer count and bytes must return to the baseline, or a
retire path is keeping a cache reference.

Like the rest of ``obs/``, module-level imports are stdlib-only — jax is
imported lazily inside the functions that trace or poll, so the read
side (ledger diffs, reports) runs without an accelerator.
"""
from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from . import prof, telemetry

#: The phase timeline the ledger rows enumerate (serve rows carry
#: serve_steady; train rows the first three).
PHASES = ("init", "step_peak", "ckpt", "serve_steady")

#: Resident-plane labels (vs. activation scopes, which come from the
#: graftprof SCOPES list).
PLANES = ("params", "opt-state", "weights", "arena", "args", "consts")

#: Same allocator-fragmentation margin as lint/spmd.check_hbm_budget.
HBM_MARGIN = 0.9

#: The drift-gate tolerance: >5% peak bytes per phase = red.
MEM_BYTES_TOL = 0.05

# internal label for a sub-jaxpr's invars — they alias the enclosing
# equation's operands, which the outer walk already counts
_OPERANDS = "_operands"


class MemError(RuntimeError):
    """Memory attribution / ledger / tracker contract violation."""


class LeakError(MemError):
    """Live buffers did not return to the post-warmup baseline."""


# --- aval plumbing ---------------------------------------------------------


def _nbytes(v) -> int:
    """Byte size of a jaxpr atom (Var / Literal / anything with an aval
    or shape+dtype)."""
    aval = getattr(v, "aval", v)
    return prof._aval_nums(aval)[1]


def tree_bytes(tree) -> int:
    """Total bytes of a pytree of arrays / ShapeDtypeStructs / avals."""
    import jax

    return sum(_nbytes(leaf) for leaf in jax.tree.leaves(tree))


def arg_planes(*pairs) -> List[Tuple[str, int]]:
    """Expand ``(label, tree)`` pairs into the per-flat-leaf plane spec
    :func:`peak_live` maps onto the jaxpr's invars (flattening order ==
    positional argument order)."""
    import jax

    return [(label, len(jax.tree.leaves(tree))) for label, tree in pairs]


# --- the peak-live walker --------------------------------------------------


def _live_walk(jaxpr, default_scope: Optional[str],
               invar_labels: Optional[Sequence[Tuple[str, int]]]) -> dict:
    """Linear-scan liveness over one (open) jaxpr.

    Returns ``peak_bytes`` (authoritative), ``peak_snapshot`` (label ->
    bytes live at the peak — attribution, not guaranteed to sum to the
    peak when a sub-jaxpr's internal transient dominates), and
    ``invar_bytes``.  Higher-order equations (pjit/scan/while/cond/...)
    contribute their body's internal peak beyond its operands; ``scan``
    reuses its per-trip buffers, so — unlike the flops walker — nothing
    multiplies by trip count."""
    eqns = jaxpr.eqns
    n = len(eqns)
    last: Dict[object, int] = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not hasattr(v, "val"):  # skip Literals
                last[v] = i
    for v in jaxpr.outvars:
        if not hasattr(v, "val"):
            last[v] = n  # outputs live to the end

    live: Dict[object, Tuple[int, str]] = {}
    by_label: Dict[str, int] = {}
    live_total = 0

    def _add(v, label: str) -> None:
        nonlocal live_total
        if hasattr(v, "val") or v in live:
            return
        b = _nbytes(v)
        if not b:
            return
        live[v] = (b, label)
        by_label[label] = by_label.get(label, 0) + b
        live_total += b

    def _drop(v) -> None:
        nonlocal live_total
        ent = live.pop(v, None)
        if ent is None:
            return
        b, label = ent
        by_label[label] -= b
        if not by_label[label]:
            del by_label[label]
        live_total -= b

    flat_labels: List[str] = []
    for label, count in (invar_labels or ()):
        flat_labels.extend([label] * count)
    invar_bytes = 0
    for j, v in enumerate(jaxpr.invars):
        invar_bytes += _nbytes(v)
        last.setdefault(v, n)  # argument buffers persist for the call
        _add(v, flat_labels[j] if j < len(flat_labels) else "args")
    for v in jaxpr.constvars:
        invar_bytes += _nbytes(v)
        last.setdefault(v, n)
        _add(v, "consts")

    dying: Dict[int, List[object]] = {}
    for v, i in last.items():
        dying.setdefault(i, []).append(v)

    peak = live_total
    peak_snap = dict(by_label)
    for i, eqn in enumerate(eqns):
        sc = _eqn_label(eqn, default_scope)
        out_b = sum(_nbytes(v) for v in eqn.outvars)
        inner_extra = 0
        inner_snap: Optional[dict] = None
        for sub in prof._sub_jaxprs(eqn.params):
            r = _live_walk(sub, sc, [(_OPERANDS, len(sub.invars))])
            extra = max(0, r["peak_bytes"] - r["invar_bytes"])
            if extra > inner_extra:
                inner_extra = extra
                inner_snap = {k: b for k, b in r["peak_snapshot"].items()
                              if k != _OPERANDS}
        transient = live_total + out_b + inner_extra
        if transient > peak:
            peak = transient
            peak_snap = dict(by_label)
            peak_snap[sc] = peak_snap.get(sc, 0) + out_b
            if inner_snap:
                for k, b in inner_snap.items():
                    peak_snap[k] = peak_snap.get(k, 0) + b
        for v in eqn.outvars:
            if last.get(v, -1) > i:
                _add(v, sc)
        for v in dying.get(i, ()):
            _drop(v)
    return {"peak_bytes": peak, "peak_snapshot": peak_snap,
            "invar_bytes": invar_bytes}


def _eqn_label(eqn, default_scope: Optional[str]) -> str:
    return prof._eqn_scope(eqn) or default_scope or prof.UNATTRIBUTED


def peak_live(jaxpr, *, default_scope: Optional[str] = None,
              planes: Optional[Sequence[Tuple[str, int]]] = None) -> dict:
    """Peak resident bytes of a (closed) jaxpr with a who-was-live
    attribution.

    ``planes`` maps leading flattened invars to resident-plane labels
    (build with :func:`arg_planes`); the remainder label ``args``.
    Returns a JSON-ready dict: ``peak_bytes``, ``planes`` (resident
    argument planes at the peak), ``scopes`` (activation bytes per
    graftprof scope at the peak), and ``resident_bytes`` (all planes —
    what persists between steps)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr -> Jaxpr
    r = _live_walk(inner, default_scope, planes)
    plane_set = set(PLANES) | {lbl for lbl, _ in (planes or ())}
    out_planes = {k: b for k, b in sorted(r["peak_snapshot"].items())
                  if k in plane_set}
    scopes = {k: b for k, b in sorted(r["peak_snapshot"].items())
              if k not in plane_set}
    return {
        "peak_bytes": int(r["peak_bytes"]),
        "planes": out_planes,
        "scopes": scopes,
        "resident_bytes": int(sum(out_planes.values())),
    }


def peak_live_fn(fn, *args, default_scope: Optional[str] = None,
                 planes: Optional[Sequence[Tuple[str, int]]] = None) -> dict:
    """``peak_live(jax.make_jaxpr(fn)(*args))`` — args may be
    ShapeDtypeStructs (abstract trace, nothing executes)."""
    import jax

    return peak_live(jax.make_jaxpr(fn)(*args),
                     default_scope=default_scope, planes=planes)


# --- phase timelines -------------------------------------------------------


def train_phases(compiled: dict) -> Dict[str, int]:
    """The per-device memory timeline of one train step from its
    opt0-compiled stats (graftprof's ``compiled`` row fields: argument /
    output / temp bytes + the donation-audit credit standing in for the
    alias stat opt0 zeroes)."""
    a = int(compiled["argument_bytes"])
    o = int(compiled["output_bytes"])
    t = int(compiled["temp_bytes"])
    don = int(compiled.get("donated_bytes", 0))
    return {
        "init": a,
        "step_peak": a + o + t - don,
        "ckpt": a + o + t,
    }


def analytic_train_phases(*, params_bytes: int, opt_bytes: int,
                          walker_peak_bytes: int, resident_bytes: int,
                          devices: int = 1,
                          shard_factor: int = 1) -> Dict[str, int]:
    """The chip-free stand-in for rows too slow to compile (the same
    carve-out graftprof's decode row takes): resident state divided by
    the plan's shard factor, activations = the walker's global peak
    minus resident planes divided across devices.  An approximation —
    held stable by construction, which is what the drift gate needs."""
    init = (params_bytes + opt_bytes) // max(shard_factor, 1)
    act = max(0, walker_peak_bytes - resident_bytes) // max(devices, 1)
    return {
        "init": init,
        "step_peak": init + act,
        "ckpt": 2 * init + act,  # snapshot pins the state: no donation
    }


def decode_phases(*, params_bytes: int, walker_peak_bytes: int
                  ) -> Dict[str, int]:
    """Decode scan: weights resident, plus the scan's internal peak
    (caches + per-step transients) from the liveness walk."""
    return {"init": int(params_bytes),
            "step_peak": int(walker_peak_bytes)}


def serve_phases(*, walker_peak_bytes: int) -> Dict[str, int]:
    """Serve steady state IS the tick's peak-live: weights + the whole
    arena (int8 payloads and their f32 scale planes are both real state)
    + tick transients, all resident for as long as the server is up."""
    return {"serve_steady": int(walker_peak_bytes)}


# --- headroom verdict ------------------------------------------------------


def headroom_verdict(phases: Dict[str, int], chip: str,
                     margin: float = HBM_MARGIN) -> dict:
    """Fold a phase timeline against one chip's per-device HBM.  ``fits``
    uses the same 0.9 margin as S4's check_hbm_budget — allocator
    fragmentation eats the rest."""
    if chip not in prof.CHIP_SPECS:
        raise MemError(f"unknown chip {chip!r}; known: "
                       f"{sorted(prof.CHIP_SPECS)}")
    hbm = prof.CHIP_SPECS[chip].hbm_bytes
    peak_phase = max(phases, key=lambda k: phases[k])
    peak = int(phases[peak_phase])
    return {
        "chip": chip,
        "hbm_bytes": int(hbm),
        "margin": margin,
        "peak_phase": peak_phase,
        "peak_bytes": peak,
        "headroom_bytes": int(hbm - peak),
        "headroom_frac": round(1.0 - peak / hbm, 4),
        "fits": peak <= margin * hbm,
    }


# --- ledger memory rows (merged under graftprof's fingerprints) ------------


def memory_row(*, phases: Dict[str, int], planes: Dict[str, int],
               scopes: Dict[str, int], walker_peak_bytes: int,
               devices: int = 1, chips: Sequence[str] = ("v4-8", "v5e-4"),
               note: Optional[str] = None) -> dict:
    """One ``memory`` sub-row: the phase timeline, the peak-live
    attribution, and a headroom verdict per chip spec."""
    row = {
        "phases": {k: int(v) for k, v in phases.items()},
        "planes": {k: int(v) for k, v in sorted(planes.items())},
        "scopes": {k: int(v) for k, v in sorted(scopes.items())},
        "walker_peak_bytes": int(walker_peak_bytes),
        "devices": int(devices),
        "headroom": {chip: headroom_verdict(phases, chip)
                     for chip in chips},
    }
    if note:
        row["note"] = note
    return row


def upsert_memory(ledger: dict, fingerprint: str, memrow: dict, *,
                  target: str = "", plan: str = "") -> None:
    """Merge a memory sub-row into the ledger row under ``fingerprint``
    — the graftprof fields (scopes/total/roofline/compiled/measured) are
    never clobbered, and measured memory history is preserved across
    recomputes (the upsert_predicted contract, one level down)."""
    row = ledger["rows"].setdefault(
        fingerprint, {"fingerprint": fingerprint, "target": target,
                      "plan": plan})
    old = row.get("memory", {})
    if old.get("measured"):
        memrow = dict(memrow, measured=old["measured"])
    row["memory"] = memrow


def append_measured_memory(snap: dict, *, fingerprint: str,
                           path: Optional[os.PathLike] = None,
                           keep_last: int = 8) -> dict:
    """Append one measured watermark (a :meth:`MemTracker.snapshot`
    dict from a real chip) under the prediction's fingerprint —
    read-modify-write, atomic publish, bounded history.  Measured rows
    never gate."""
    p = Path(path) if path is not None else prof.ledger_path()
    ledger = prof.load_ledger(p)
    row = ledger["rows"].setdefault(
        fingerprint, {"fingerprint": fingerprint, "target": ""})
    mem = row.setdefault("memory", {})
    hist = mem.setdefault("measured", [])
    hist.append(dict(snap, t=round(time.time(), 3)))
    del hist[:-keep_last]
    prof.save_ledger(ledger, p)
    return row


def diff_memory(committed: dict, recomputed: Dict[str, dict],
                bytes_tol: float = MEM_BYTES_TOL) -> List[str]:
    """The CI drift gate: diff HEAD's recomputed memory rows against the
    committed ledger.  A phase whose peak bytes drifted >5% goes red
    with the guilty scope/plane named (the attribution entry that moved
    most); missing/extra fingerprints surface too.  Rows without a
    predicted memory sub-row (graftprof-only rows, measured-only stubs)
    never gate."""
    problems: List[str] = []
    old_rows = {fp: r for fp, r in committed.get("rows", {}).items()
                if "phases" in r.get("memory", {})}
    for fp in sorted(set(old_rows) - set(recomputed)):
        r = old_rows[fp]
        problems.append(
            f"{fp} ({r.get('target')}/{r.get('plan')}): memory row in the "
            "ledger but no longer produced by the sweep — remove it with "
            "`graftmem --update` if the target was retired")
    for fp in sorted(set(recomputed) - set(old_rows)):
        problems.append(
            f"{fp}: new memory row not in the committed ledger — run "
            "`graftmem --update` and commit")
    for fp in sorted(set(old_rows) & set(recomputed)):
        old = old_rows[fp]["memory"]
        new = recomputed[fp]
        label = (f"{fp} ({old_rows[fp].get('target')}"
                 f"/{old_rows[fp].get('plan')})")
        guilty = _guilty_entry(old, new)
        for phase in sorted(set(old["phases"]) | set(new.get("phases", {}))):
            a = old["phases"].get(phase, 0)
            b = new.get("phases", {}).get(phase, 0)
            d = prof._rel(a, b)
            if d > bytes_tol:
                problems.append(
                    f"{label}: phase {phase} peak bytes drifted {d:.1%} "
                    f"(ledger {a:.4g} -> HEAD {b:.4g}, tol "
                    f"{bytes_tol:.0%}){guilty} — a memory-relevant change "
                    "landed without a ledger update; rerun `graftmem "
                    "--update` and commit the diff if intended")
    return problems


def _guilty_entry(old: dict, new: dict) -> str:
    """Name the scope/plane whose peak-live bytes moved most — the
    attribution half of a phase-drift message."""
    worst, worst_d, worst_delta = None, 0.0, 0
    for table in ("scopes", "planes"):
        keys = set(old.get(table, {})) | set(new.get(table, {}))
        for k in keys:
            a = old.get(table, {}).get(k, 0)
            b = new.get(table, {}).get(k, 0)
            d = prof._rel(a, b)
            if d > worst_d:
                worst, worst_d, worst_delta = k, d, b - a
    if worst is None or worst_d == 0.0:
        return ""
    sign = "+" if worst_delta >= 0 else "-"
    return (f" — guilty scope: {worst} ({sign}{abs(worst_delta):.4g} "
            f"bytes, {worst_d:.1%})")


def predicted_memory_for(*, fingerprint: Optional[str] = None,
                         target: Optional[str] = None,
                         plan: Optional[str] = None,
                         chip: str = "v4-8",
                         path: Optional[os.PathLike] = None
                         ) -> Optional[dict]:
    """Ledger lookup for a run's predicted memory timeline — exact
    fingerprint first, then the (target, plan) row (prof.predicted_for's
    fallback contract).  Returns the ``mem.predicted`` event payload or
    None when the ledger has nothing relevant."""
    try:
        ledger = prof.load_ledger(path)
    except (OSError, ValueError, prof.ProfError):
        return None
    rows = ledger.get("rows", {})
    row = rows.get(fingerprint) if fingerprint else None
    if (row is None or "phases" not in row.get("memory", {})) and target:
        for r in rows.values():
            if (r.get("target") == target and "phases" in r.get("memory", {})
                    and (plan is None or r.get("plan") == plan)):
                row = r
                break
    if row is None or "phases" not in row.get("memory", {}):
        return None
    mem = row["memory"]
    verdict = mem.get("headroom", {}).get(chip)
    out = {
        "fingerprint": row["fingerprint"],
        "exact": row["fingerprint"] == fingerprint,
        "chip": chip,
        "phases": dict(mem["phases"]),
    }
    if verdict:
        out.update(peak_phase=verdict["peak_phase"],
                   peak_bytes=verdict["peak_bytes"],
                   headroom_bytes=verdict["headroom_bytes"],
                   headroom_frac=verdict["headroom_frac"],
                   fits=verdict["fits"])
    return out


# --- the measured side: the one managed poll point (MEM001) ----------------


def live_buffer_stats() -> dict:
    """Count + bytes of every live jax array in the process — the
    repo's ONE ``jax.live_arrays()`` call site (graftlint MEM001).
    Works on any backend, which is what lets the serve leak gate run
    chip-free in CI."""
    import jax

    count = 0
    total = 0
    for a in jax.live_arrays():
        count += 1
        try:
            total += int(a.nbytes)
        except (AttributeError, TypeError):  # deleted-under-us / exotic
            pass
    return {"count": count, "bytes": total}


def device_memory_stats() -> List[dict]:
    """Per-device allocator stats where the backend exposes them
    (TPU/GPU ``Device.memory_stats``, the same counters
    ``jax.profiler.device_memory_profile`` aggregates); ``[]`` on CPU.
    The one managed surface over those counters (MEM001)."""
    import jax

    out = []
    for d in jax.devices():
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:  # graftlint: disable=EXC001 (backend-optional API: CPU raises/returns None; absence just means no device counters)
            stats = None
        if not stats:
            continue
        out.append({
            "id": int(d.id),
            "kind": str(getattr(d, "device_kind", "?")),
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
            "bytes_limit": int(stats.get("bytes_limit", 0)),
        })
    return out


def write_device_memory_profile(path) -> str:
    """Dump the backend's pprof memory profile to ``path`` — the managed
    ``jax.profiler.device_memory_profile`` passthrough for deep dives."""
    import jax

    blob = jax.profiler.device_memory_profile()
    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_bytes(blob)
    return str(p)


def host_rss_bytes() -> Optional[int]:
    """Resident set size of this process from /proc (linux); None where
    that is unavailable."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        return None
    return None


def heartbeat_snapshot() -> dict:
    """The compact memory fields a heartbeat carries (utils/failure.py):
    host RSS always, summed per-device used/peak when the backend
    exposes allocator stats — enough for ``monitor`` to show a dying
    host's memory trajectory without parsing a telemetry stream."""
    out: dict = {}
    rss = host_rss_bytes()
    if rss:
        out["rss_mb"] = round(rss / 1e6, 1)
    try:
        devs = device_memory_stats()
    except Exception:  # graftlint: disable=EXC001 (heartbeats must never die on a wedged backend probe; the snapshot just goes without device fields)
        devs = []
    if devs:
        out["hbm_used_mb"] = round(
            sum(d["bytes_in_use"] for d in devs) / 1e6, 1)
        out["hbm_peak_mb"] = round(
            sum(d["peak_bytes_in_use"] for d in devs) / 1e6, 1)
    return out


def _collect_garbage() -> None:
    import gc

    gc.collect()


class MemTracker:
    """Managed phase-boundary memory watermarks + the leak gate.

    Mirrors ``prof.capture``'s one-entry-point contract for the polling
    APIs: every watermark lands as a ``mem.watermark`` telemetry record
    (phase, live buffer count/bytes, per-device used/peak, host RSS,
    headroom against the HBM limit), which ``obs/metrics.py`` turns
    into the ``graft_hbm_*`` gauges and the ``hbm_headroom`` alert rule
    watches.  ``hbm_bytes`` pins the limit explicitly (tests, CPU);
    ``chip`` reads it from ``prof.CHIP_SPECS``; with neither, the limit
    comes from device ``bytes_limit`` when the backend reports one.

    The leak gate: :meth:`baseline` after warmup captures the reference
    live-buffer census (after a GC pass, so dead python references
    don't count); :meth:`check_baseline` after churn re-polls and
    raises :class:`LeakError` if count or bytes grew past tolerance —
    the contract serve chaos rows (admit/retire ×N, mid-decode kill,
    rolling restart) hold in CI."""

    def __init__(self, hbm_bytes: Optional[int] = None,
                 chip: Optional[str] = None, emit: bool = True):
        if hbm_bytes is None and chip is not None:
            if chip not in prof.CHIP_SPECS:
                raise MemError(f"unknown chip {chip!r}; known: "
                               f"{sorted(prof.CHIP_SPECS)}")
            hbm_bytes = prof.CHIP_SPECS[chip].hbm_bytes
        self.hbm_bytes = hbm_bytes
        self.emit = emit
        self._peak = 0
        self._baseline: Optional[dict] = None

    def snapshot(self, phase: str, **extra) -> dict:
        """Poll live buffers + device counters at one phase boundary and
        emit the ``mem.watermark`` record."""
        live = live_buffer_stats()
        devs = device_memory_stats()
        used = (sum(d["bytes_in_use"] for d in devs) if devs
                else live["bytes"])
        dev_peak = sum(d["peak_bytes_in_use"] for d in devs)
        self._peak = max(self._peak, used, dev_peak)
        rec = {
            "phase": phase,
            "live_count": live["count"],
            "live_bytes": live["bytes"],
            "used_bytes": int(used),
            "peak_bytes": int(self._peak),
            "devices": len(devs),
        }
        rss = host_rss_bytes()
        if rss:
            rec["rss_bytes"] = rss
        limit = self.hbm_bytes
        if limit is None and devs:
            limit = sum(d["bytes_limit"] for d in devs) // len(devs) or None
        if limit:
            rec["hbm_limit_bytes"] = int(limit)
            rec["headroom_bytes"] = int(limit - used)
            rec["headroom_frac"] = round(1.0 - used / limit, 4)
        if self.emit:
            telemetry.emit("mem", "watermark", **rec, **extra)
        return rec

    # --- the leak gate ----------------------------------------------------

    def baseline(self, phase: str = "baseline", **extra) -> dict:
        """Capture the post-warmup reference census (GC first: python
        garbage is not a device leak)."""
        _collect_garbage()
        self._baseline = self.snapshot(phase, **extra)
        return self._baseline

    def check_baseline(self, label: str = "", *, tol_count: int = 0,
                       tol_bytes: int = 0,
                       phase: str = "leak-check") -> dict:
        """Re-poll and compare against :meth:`baseline`.  Raises
        :class:`LeakError` when live buffers grew past tolerance;
        returns the delta dict (also emitted as ``mem.leak_check``)."""
        if self._baseline is None:
            raise MemError("check_baseline before baseline(): capture the "
                           "post-warmup census first")
        _collect_garbage()
        snap = self.snapshot(phase)
        d_count = snap["live_count"] - self._baseline["live_count"]
        d_bytes = snap["live_bytes"] - self._baseline["live_bytes"]
        ok = d_count <= tol_count and d_bytes <= tol_bytes
        if self.emit:
            telemetry.emit("mem", "leak_check", label=label, ok=ok,
                           count_delta=d_count, bytes_delta=d_bytes,
                           baseline_count=self._baseline["live_count"],
                           baseline_bytes=self._baseline["live_bytes"])
        if not ok:
            raise LeakError(
                f"leak gate [{label or 'serve'}]: live buffers grew by "
                f"{d_count} arrays / {d_bytes} bytes over the post-warmup "
                f"baseline ({self._baseline['live_count']} arrays, "
                f"{self._baseline['live_bytes']} bytes) — a retire/stop "
                "path is keeping a cache reference (DESIGN.md §19 "
                "leak-gate contract)")
        return {"ok": ok, "count_delta": d_count, "bytes_delta": d_bytes}
