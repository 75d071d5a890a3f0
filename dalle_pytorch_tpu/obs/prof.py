"""graftprof: per-module roofline attribution + the committed perf ledger
(DESIGN.md §18).

The write side of the repo's perf observability: models wrap their cost
centers in ``scope(name)`` (a ``jax.named_scope`` carrying the
``graftprof:`` prefix), :func:`attribute` walks a traced jaxpr and sums
analytic ``flops`` / ``bytes`` per scope (innermost scope wins; backward
equations keep their forward scope through jvp/transpose name-stack
wrapping; ``scan`` bodies multiply by trip count), and :func:`roofline`
folds the totals into the chip spec table to predict step time
(max(FLOP-time, byte-time)) and the MFU ceiling.  ``tools/graftprof.py``
sweeps every train-step factory × plan plus decode/serve-tick and
commits the rows to ``PERF_LEDGER.json``; :func:`diff_ledger` is the CI
drift gate (>2% flops / >5% bytes without a ledger update = red).

Like the rest of ``obs/``, module-level imports are stdlib-only — jax is
imported lazily inside the functions that trace or capture, so the read
side (reports, the drift diff, ledger plumbing) runs without an
accelerator.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import re
import time
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

# --- scope list -----------------------------------------------------------

SCOPE_PREFIX = "graftprof:"

#: The cost centers the models annotate (DESIGN.md §18).  A scope
#: not in this tuple still attributes (the walker matches the prefix, not
#: the table) — the table is the documented contract and what the ledger
#: rows enumerate.
SCOPES = ("embed", "attn-qkv", "attn-scores", "attn-cache", "attn-out",
          "ff", "logits-head", "vae-conv", "optimizer", "decode-step",
          "serve-tick", "sample",
          "ssm-proj", "ssm-conv", "ssm-scan", "moe-route", "moe-experts",
          "gdn-proj", "gdn-conv", "gdn-state", "mla-proj", "mla-read",
          "attn-gate", "ssd-proj", "ssd-conv", "ssd-state")

#: Residual bucket for equations under no scope.
UNATTRIBUTED = "unattributed"

_SCOPE_RE = re.compile(r"graftprof:([a-z0-9_-]+)")
_NAME_RE = re.compile(r"^[a-z0-9][a-z0-9_-]*$")


class ProfError(RuntimeError):
    """Attribution / ledger contract violation."""


class CoverageError(ProfError):
    """Unattributed residual above the gate — a cost center lost its
    scope (or a new one landed without annotation)."""


def scope(name: str):
    """``jax.named_scope`` carrying the graftprof prefix — the one way
    model code marks a cost center.  Returns a context manager usable as
    a decorator (``named_scope`` is both)."""
    if not _NAME_RE.match(name):
        raise ProfError(f"bad scope name {name!r}: lowercase slug expected")
    import jax

    return jax.named_scope(SCOPE_PREFIX + name)


# --- the jaxpr cost walker ------------------------------------------------

# Pure data movement: XLA's HloCostAnalysis charges these zero flops (the
# bytes still count), so the walker mirrors it — the 2%-of-compiled gate
# in tests/test_prof.py is calibrated against this table.
_ZERO_FLOP = frozenset((
    "reshape", "broadcast_in_dim", "transpose", "squeeze", "expand_dims",
    "slice", "dynamic_slice", "dynamic_update_slice", "concatenate", "pad",
    "rev", "gather", "iota", "copy", "stop_gradient", "convert_element_type",
    "bitcast_convert_type", "split", "select_n",
))

# Transcendentals land in HloCostAnalysis's separate counter, not flops.
_TRANSCENDENTAL = frozenset((
    "exp", "exp2", "expm1", "log", "log1p", "tanh", "logistic", "sqrt",
    "rsqrt", "cbrt", "erf", "erfc", "erf_inv", "sin", "cos", "tan", "pow",
))


def _aval_nums(aval) -> Tuple[int, int]:
    """(element count, byte size) of one abstract value; (0, 0) for
    non-array avals (tokens)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0, 0
    size = 1
    for d in shape:
        size *= int(d)
    return size, size * dtype.itemsize


def _eqn_scope(eqn) -> Optional[str]:
    """Innermost graftprof scope on the equation's name stack, or None.
    The stack survives autodiff as ``transpose(jvp(graftprof:ff))`` —
    the regex sees through the wrapping, and the LAST match is the
    innermost scope, so nested scopes (decode-step around attn-cache)
    attribute to the tighter one."""
    src = getattr(eqn, "source_info", None)
    stack = getattr(src, "name_stack", None)
    if stack is None:
        return None
    found = _SCOPE_RE.findall(str(stack))
    return found[-1] if found else None


def _sub_jaxprs(params: dict) -> Iterator[object]:
    # lint/spmd.py's structural matcher: every higher-order primitive
    # (pjit/scan/while/cond/shard_map/remat/custom_*) carries its nested
    # jaxprs under different param keys
    for val in params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                yield inner
            elif hasattr(v, "eqns"):
                yield v


def _eqn_cost(eqn) -> Tuple[int, int]:
    """(flops, bytes) of one first-order equation.  dot_general =
    2·out·K (K = contracted extent), conv = 2·out·(kernel/out_features),
    other math = one flop per output element; bytes = operands + outputs
    at jaxpr-level shapes (pre-fusion traffic — an upper bound on the
    fused program's bytes_accessed, stable across XLA versions, which is
    what a drift gate needs)."""
    prim = eqn.primitive.name
    out_size = out_bytes = 0
    for v in eqn.outvars:
        s, b = _aval_nums(getattr(v, "aval", None))
        out_size += s
        out_bytes += b
    in_bytes = 0
    for v in eqn.invars:
        _, b = _aval_nums(getattr(v, "aval", None))
        in_bytes += b

    if prim == "dot_general":
        (lhs_contract, _), _ = eqn.params["dimension_numbers"]
        lhs_shape = eqn.invars[0].aval.shape
        k = 1
        for i in lhs_contract:
            k *= int(lhs_shape[i])
        flops = 2 * out_size * k
    elif prim == "conv_general_dilated":
        rhs = eqn.invars[1].aval
        dn = eqn.params["dimension_numbers"]
        out_features = int(rhs.shape[dn.rhs_spec[0]])
        rhs_size, _ = _aval_nums(rhs)
        flops = 2 * out_size * (rhs_size // max(out_features, 1))
    elif prim in _ZERO_FLOP or prim in _TRANSCENDENTAL:
        flops = 0
    else:
        flops = out_size
    return flops, in_bytes + out_bytes


def _walk(jaxpr, inherited: Optional[str], mult: int,
          acc: Dict[str, List[int]]) -> None:
    for eqn in jaxpr.eqns:
        sc = _eqn_scope(eqn) or inherited
        subs = list(_sub_jaxprs(eqn.params))
        if subs:
            m = mult
            if eqn.primitive.name == "scan":
                m = mult * int(eqn.params.get("length", 1))
            # cond branches are all walked (summed) — conservative, and
            # the models keep real cost out of cond bodies
            for sub in subs:
                _walk(sub, sc, m, acc)
            continue
        flops, nbytes = _eqn_cost(eqn)
        if not flops and not nbytes:
            continue
        cell = acc.setdefault(sc or UNATTRIBUTED, [0, 0])
        cell[0] += flops * mult
        cell[1] += nbytes * mult


def attribute(jaxpr, *, default_scope: Optional[str] = None,
              scale: int = 1) -> dict:
    """Walk a (closed) jaxpr and attribute analytic flops/bytes per
    graftprof scope.

    ``scale`` multiplies every number — ``shard_map`` plans trace one
    shard's program, so callers pass the mesh device count to recover
    the global figures.  Returns a JSON-ready dict: per-scope numbers,
    totals, and the unattributed residual fractions the ≤5% coverage
    gate reads."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr -> Jaxpr
    acc: Dict[str, List[int]] = {}
    _walk(inner, default_scope, 1, acc)
    scopes = {name: {"flops": f * scale, "bytes": b * scale}
              for name, (f, b) in sorted(acc.items())
              if name != UNATTRIBUTED}
    un_f, un_b = acc.get(UNATTRIBUTED, (0, 0))
    total_f = sum(s["flops"] for s in scopes.values()) + un_f * scale
    total_b = sum(s["bytes"] for s in scopes.values()) + un_b * scale
    return {
        "scopes": scopes,
        "unattributed": {"flops": un_f * scale, "bytes": un_b * scale},
        "total": {"flops": total_f, "bytes": total_b},
        "residual": {
            "flops": (un_f * scale / total_f) if total_f else 0.0,
            "bytes": (un_b * scale / total_b) if total_b else 0.0,
        },
    }


def attribute_fn(fn, *args, default_scope: Optional[str] = None,
                 scale: int = 1) -> dict:
    """``attribute(jax.make_jaxpr(fn)(*args))`` — args may be
    ShapeDtypeStructs (abstract trace, nothing executes)."""
    import jax

    return attribute(jax.make_jaxpr(fn)(*args),
                     default_scope=default_scope, scale=scale)


def check_coverage(attr: dict, max_residual: float = 0.05,
                   label: str = "program") -> None:
    """The coverage gate: unattributed flops AND bytes residual ≤ 5% —
    a new cost center must be scoped before its row can land."""
    res = attr["residual"]
    bad = {k: v for k, v in res.items() if v > max_residual}
    if bad:
        detail = ", ".join(f"{k} {v:.1%}" for k, v in sorted(bad.items()))
        raise CoverageError(
            f"graftprof coverage [{label}]: unattributed residual {detail} "
            f"exceeds {max_residual:.0%} — a cost center is missing its "
            "scope() annotation (the SCOPES list, DESIGN.md §18)")


# --- chip specs + roofline ------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Per-device peaks.  ``hbm_bytes`` mirrors lint/spmd.py's
    CHIP_HBM_BYTES (pinned by tests/test_prof.py so the two tables
    cannot drift)."""

    devices: int
    peak_flops: float  # FLOP/s per device (bf16 MXU)
    hbm_bw: float      # bytes/s per device
    hbm_bytes: int     # capacity per device

    @property
    def ridge(self) -> float:
        """Arithmetic intensity (flops/byte) where the roofline bends."""
        return self.peak_flops / self.hbm_bw


CHIP_SPECS: Dict[str, ChipSpec] = {
    "v4-8": ChipSpec(devices=4, peak_flops=275e12, hbm_bw=1228e9,
                     hbm_bytes=32 * 1024 ** 3),
    "v5e-4": ChipSpec(devices=4, peak_flops=197e12, hbm_bw=819e9,
                      hbm_bytes=16 * 1024 ** 3),
}


def roofline(attr: dict, chip: str, *,
             traffic_bytes: Optional[int] = None,
             devices: Optional[int] = None) -> dict:
    """Fold an attribution into the chip's roofline.

    ``traffic_bytes`` is the PER-DEVICE HBM stream of one step — callers
    with a compiled program pass its memory-analysis sum (args + outputs
    + temps, opt0-stable); without one the walker's global bytes divided
    across devices stand in.  Predicted step time = max(FLOP-time,
    byte-time); predicted MFU is the ceiling measured MFU is judged
    against (obs_report's predicted-vs-measured section)."""
    if chip not in CHIP_SPECS:
        raise ProfError(f"unknown chip {chip!r}; known: "
                        f"{sorted(CHIP_SPECS)}")
    spec = CHIP_SPECS[chip]
    n = devices or spec.devices
    flops = attr["total"]["flops"]
    if traffic_bytes is None:
        traffic_bytes = attr["total"]["bytes"] // max(n, 1)
    flop_time = flops / (spec.peak_flops * n)
    byte_time = traffic_bytes / spec.hbm_bw
    pred = max(flop_time, byte_time)
    scopes = {}
    for name, cell in attr["scopes"].items():
        intensity = cell["flops"] / cell["bytes"] if cell["bytes"] else 0.0
        scopes[name] = {
            "intensity": round(intensity, 3),
            "bound": "flop" if intensity >= spec.ridge else "byte",
        }
    return {
        "chip": chip,
        "devices": n,
        "ridge": round(spec.ridge, 2),
        "flop_time_s": flop_time,
        "byte_time_s": byte_time,
        "pred_step_time_s": pred,
        "bound": "byte" if byte_time > flop_time else "flop",
        "predicted_mfu": (flop_time / pred) if pred else 0.0,
        "traffic_bytes": int(traffic_bytes),
        "scopes": scopes,
    }


# --- config fingerprint + ledger ------------------------------------------

LEDGER_NAME = "PERF_LEDGER.json"
LEDGER_SCHEMA_VERSION = 1


def row_fingerprint(payload: dict) -> str:
    """12-hex-char key of one (target, plan, geometry) point: sha256 of
    the canonical JSON (sorted keys, no whitespace, non-JSON values
    stringified).  Predicted and measured rows meet on this key."""
    blob = json.dumps(payload, sort_keys=True, separators=(",", ":"),
                      default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def fingerprint_payload(config, **extra) -> dict:
    """Canonical fingerprint payload for a config dataclass (or dict) plus
    the run point (``target=``, ``plan=``, ``batch=``, ...): dataclass
    fields stringified and sorted, sweep knobs appended raw.  Every
    producer — tools/graftprof.py predicted rows, the trainers'
    ``prof.predicted`` lookup, :func:`append_measured` — builds this SAME
    dict so their rows meet on one key."""
    import dataclasses

    d = dict(config) if isinstance(config, dict) else dataclasses.asdict(config)
    return {**{k: str(v) for k, v in sorted(d.items())}, **extra}


def ledger_path(root: Optional[os.PathLike] = None) -> Path:
    """Resolve the ledger file: GRAFT_PERF_LEDGER env override (tests,
    scratch sweeps) > ``root``/PERF_LEDGER.json > repo root next to this
    package."""
    env = os.environ.get("GRAFT_PERF_LEDGER")
    if env:
        return Path(env)
    if root is not None:
        return Path(root) / LEDGER_NAME
    return Path(__file__).resolve().parent.parent.parent / LEDGER_NAME


def load_ledger(path: Optional[os.PathLike] = None) -> dict:
    p = Path(path) if path is not None else ledger_path()
    if not p.exists():
        return {"v": LEDGER_SCHEMA_VERSION, "rows": {}}
    doc = json.loads(p.read_text())
    if doc.get("v", 0) > LEDGER_SCHEMA_VERSION:
        raise ProfError(
            f"perf ledger {p} has schema v{doc.get('v')} > "
            f"{LEDGER_SCHEMA_VERSION} — update the tree before diffing")
    doc.setdefault("rows", {})
    return doc


def save_ledger(ledger: dict, path: Optional[os.PathLike] = None) -> Path:
    """Atomic publish (tmp + rename), rows sorted by fingerprint so the
    committed file diffs cleanly."""
    p = Path(path) if path is not None else ledger_path()
    doc = dict(ledger)
    doc["v"] = LEDGER_SCHEMA_VERSION
    doc["rows"] = {k: doc["rows"][k] for k in sorted(doc["rows"])}
    p.parent.mkdir(parents=True, exist_ok=True)
    tmp = p.with_name(p.name + ".tmp")
    tmp.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")
    os.replace(tmp, p)
    return p


def predicted_row(*, target: str, plan: str, chip: str, config: dict,
                  attr: dict, roof: dict,
                  compiled: Optional[dict] = None) -> dict:
    """One ledger row.  ``config`` is the fingerprint payload (geometry +
    batch + dtype + plan) — the same dict a measured run must hash to
    land beside this prediction."""
    fp = row_fingerprint(config)
    row = {
        "fingerprint": fp,
        "target": target,
        "plan": plan,
        "chip": chip,
        "config": config,
        "scopes": attr["scopes"],
        "unattributed": attr["unattributed"],
        "total": attr["total"],
        "residual": {k: round(v, 4) for k, v in attr["residual"].items()},
        "roofline": {
            "pred_step_time_s": roof["pred_step_time_s"],
            "predicted_mfu": round(roof["predicted_mfu"], 4),
            "bound": roof["bound"],
            "ridge": roof["ridge"],
            "traffic_bytes": roof["traffic_bytes"],
            "devices": roof["devices"],
        },
    }
    if compiled is not None:
        row["compiled"] = {k: int(v) for k, v in sorted(compiled.items())}
    return row


def upsert_predicted(ledger: dict, row: dict) -> None:
    """Install/refresh a predicted row, preserving any measured rows
    already recorded under the fingerprint."""
    old = ledger["rows"].get(row["fingerprint"])
    if old and old.get("measured"):
        row = dict(row, measured=old["measured"])
    ledger["rows"][row["fingerprint"]] = row


def append_measured(measured: dict, *, fingerprint: Optional[str] = None,
                    config: Optional[dict] = None, target: str = "",
                    path: Optional[os.PathLike] = None,
                    keep_last: int = 8) -> dict:
    """Append one measured row (tok/s / img/s + MFU from a real run)
    under the prediction's fingerprint — read-modify-write, atomic
    publish.  A fingerprint with no predicted row still lands (stub row)
    so a measurement never waits for a sweep."""
    if fingerprint is None:
        if config is None:
            raise ProfError("append_measured needs fingerprint or config")
        fingerprint = row_fingerprint(config)
    p = Path(path) if path is not None else ledger_path()
    ledger = load_ledger(p)
    row = ledger["rows"].setdefault(
        fingerprint, {"fingerprint": fingerprint, "target": target,
                      "config": config or {}})
    hist = row.setdefault("measured", [])
    hist.append(dict(measured, t=round(time.time(), 3)))
    del hist[:-keep_last]
    save_ledger(ledger, p)
    return row


# --- the CI drift gate ----------------------------------------------------

FLOPS_TOL = 0.02
BYTES_TOL = 0.05


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(a), abs(b), 1.0)


def diff_ledger(committed: dict, recomputed: Dict[str, dict],
                flops_tol: float = FLOPS_TOL,
                bytes_tol: float = BYTES_TOL) -> List[str]:
    """Diff HEAD's recomputed predicted rows against the committed
    ledger.  Returns human-readable problems (empty = green): missing /
    extra fingerprints, per-scope or total flops drift > 2%, bytes drift
    > 5%, and compiled-stat drift (bytes_accessed / live buffers /
    donated bytes) at the byte tolerance — the broken twins (a hoisted
    full-cache convert, a dropped int8 scale plane, a dropped donation)
    all land in one of these.  Measured rows never gate."""
    problems = []
    old_rows = {fp: r for fp, r in committed.get("rows", {}).items()
                if "total" in r}  # measured-only stubs don't gate
    for fp in sorted(set(old_rows) - set(recomputed)):
        r = old_rows[fp]
        problems.append(
            f"{fp} ({r.get('target')}/{r.get('plan')}): in the ledger but "
            "no longer produced by the sweep — remove it with "
            "`graftprof --update` if the target was retired")
    for fp in sorted(set(recomputed) - set(old_rows)):
        r = recomputed[fp]
        problems.append(
            f"{fp} ({r.get('target')}/{r.get('plan')}): new row not in the "
            "committed ledger — run `graftprof --update` and commit")
    for fp in sorted(set(old_rows) & set(recomputed)):
        old, new = old_rows[fp], recomputed[fp]
        label = f"{fp} ({new.get('target')}/{new.get('plan')})"

        def _gate(what, a, b, tol):
            d = _rel(a, b)
            if d > tol:
                problems.append(
                    f"{label}: {what} drifted {d:.1%} "
                    f"(ledger {a:.4g} -> HEAD {b:.4g}, tol {tol:.0%}) — "
                    "a perf-relevant change landed without a ledger "
                    "update; rerun `graftprof --update` and commit the "
                    "diff if intended")

        for name in sorted(set(old.get("scopes", {}))
                           | set(new.get("scopes", {}))):
            o = old.get("scopes", {}).get(name, {"flops": 0, "bytes": 0})
            n = new.get("scopes", {}).get(name, {"flops": 0, "bytes": 0})
            _gate(f"scope {name} flops", o["flops"], n["flops"], flops_tol)
            _gate(f"scope {name} bytes", o["bytes"], n["bytes"], bytes_tol)
        _gate("total flops", old["total"]["flops"], new["total"]["flops"],
              flops_tol)
        _gate("total bytes", old["total"]["bytes"], new["total"]["bytes"],
              bytes_tol)
        for field in sorted(set(old.get("compiled", {}))
                            & set(new.get("compiled", {}))):
            tol = flops_tol if field == "flops" else bytes_tol
            _gate(f"compiled {field}", old["compiled"][field],
                  new["compiled"][field], tol)
    return problems


# --- graftscope integration ----------------------------------------------


def predicted_for(*, fingerprint: Optional[str] = None,
                  target: Optional[str] = None, plan: Optional[str] = None,
                  path: Optional[os.PathLike] = None) -> Optional[dict]:
    """Look up the predicted-MFU fields for a run: exact fingerprint
    first, else the (target, plan) row — geometry tweaks still get the
    plan's ceiling as a reference.  Returns the ``prof.predicted`` event
    payload (fingerprint / chip / mfu / pred_step_time_s / bound) or
    None when the ledger has nothing relevant."""
    try:
        ledger = load_ledger(path)
    except (OSError, ValueError, ProfError):
        return None
    rows = ledger.get("rows", {})
    row = rows.get(fingerprint) if fingerprint else None
    if row is None and target:
        for r in rows.values():
            if (r.get("target") == target and "roofline" in r
                    and (plan is None or r.get("plan") == plan)):
                row = r
                break
    if row is None or "roofline" not in row:
        return None
    roof = row["roofline"]
    return {
        "fingerprint": row["fingerprint"],
        "exact": row["fingerprint"] == fingerprint,
        "chip": row.get("chip"),
        "mfu": roof["predicted_mfu"],
        "pred_step_time_s": roof["pred_step_time_s"],
        "bound": roof["bound"],
    }


def predicted_serve_bytes_per_token(cfg, num_slots: int) -> int:
    """Per-decoded-token HBM stream of one serve tick: the whole arena's
    cache read (int8 payloads + f32 scale planes counted —
    ``profiling.dalle_decode_cache_bytes``) amortized over the slots a
    full tick advances.  GenerationServer.stats() and the /metrics serve
    instruments export this beside the measured occupancy."""
    from ..utils.profiling import dalle_decode_cache_bytes

    return int(dalle_decode_cache_bytes(cfg, num_slots)
               // max(num_slots, 1))


# --- managed on-chip capture (the OBS003 contract) ------------------------


@contextlib.contextmanager
def capture(logdir):
    """The repo's ONE managed ``jax.profiler`` entry point (graftlint
    OBS003 flags direct calls elsewhere): wraps start/stop_trace in a
    ``prof.xprof`` telemetry span so the on-chip trace window lands
    correlated in the Perfetto fleet merge."""
    import jax

    from . import telemetry

    logdir = str(logdir)
    with telemetry.span("prof", "xprof", logdir=logdir):
        jax.profiler.start_trace(logdir)
        try:
            yield logdir
        finally:
            jax.profiler.stop_trace()


class XprofWindow:
    """Arm an on-chip trace around a step window — the ``GRAFT_XPROF`` /
    ``--xprof_dir`` hook both trainers drive.

    ``logdir`` falls back to the GRAFT_XPROF env var (unset/empty =
    disarmed, so production runs pay one attribute check per step);
    the window defaults to steps [start, stop) with
    ``GRAFT_XPROF_WINDOW=a:b`` overriding.  ``on_step(i, sync)`` opens
    the capture at the window start and closes it (after ``sync()``
    drains the device queue) at the end; ``close()`` is the exit-path
    safety net."""

    def __init__(self, logdir=None, start: int = 10, stop: int = 20):
        self.logdir = str(logdir) if logdir else (
            os.environ.get("GRAFT_XPROF") or None)  # graftlint: disable=ENV001 (path-valued var: empty/unset mean off)
        window = os.environ.get("GRAFT_XPROF_WINDOW", "")
        if window:
            a, _, b = window.partition(":")
            start, stop = int(a), int(b or int(a) + 10)
        self.start, self.stop = start, stop
        self._cm = None

    @property
    def armed(self) -> bool:
        return self.logdir is not None

    @property
    def active(self) -> bool:
        return self._cm is not None

    def on_step(self, i: int, sync=None) -> None:
        if self.logdir is None:
            return
        if self._cm is None and self.start <= i < self.stop:
            self._cm = capture(self.logdir)
            self._cm.__enter__()
        elif self._cm is not None and i >= self.stop:
            self.close(sync)

    def close(self, sync=None) -> None:
        if self._cm is None:
            return
        try:
            if sync is not None:
                sync()
        finally:
            cm, self._cm = self._cm, None
            cm.__exit__(None, None, None)
