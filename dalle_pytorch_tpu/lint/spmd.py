"""graftspmd: jaxpr-level SPMD analyses for jitted step programs (S1-S4).

graftlint (engine.py/rules.py) sees source; ``tools/contract_check.py``
sees shapes and dtypes.  Between them sits the class of bugs that only the
*traced program* exposes, and that are the three most expensive ways to
waste a TPU pod:

* **S1 collective order** — under ``shard_map`` every shard runs the same
  traced jaxpr, so the only way shards can issue *different* collective
  sequences (the classic SPMD deadlock: half the mesh waits in a
  ``ppermute`` the other half never enters) is a collective dominated by
  data-dependent control flow.  :func:`collective_trace` walks the jaxpr
  (recursing through ``pjit``/``shard_map``/``scan``/``remat`` bodies),
  records the unconditional collective sequence, and flags any collective
  under a ``while`` (data-dependent trip count) or inside ``cond``
  branches whose collective signatures differ (shards taking different
  branches would desynchronize).  ``cond`` branches whose collective
  sequences are *identical* are allowed — every shard issues the same ops
  in the same order whichever branch it takes (parallel/pipeline.py's
  drain-bubble ``cond`` is the motivating clean case).
* **S2 donation audit** — a forgotten ``donate_argnums`` silently doubles
  params+opt_state HBM (the buffers live twice across the update).
  :func:`audit_donation` reads the AOT ``lowered.args_info`` donation
  flags per pytree leaf and, when a compiled executable is given, parses
  the optimized HLO's ``input_output_alias`` config to verify every
  donated leaf is *actually aliased* to an output — jax drops donation
  silently when a donated input matches no output (the
  refactor-changed-the-return-structure bug), which is exactly when you
  want to hear about it.  (``memory_analysis().alias_size_in_bytes`` is
  NOT used: XLA:CPU zeroes it at backend opt level 0 and on
  cache-deserialized executables even when the aliases are honored.)
* **S3 retrace sentinel** — a weak-hash or unhashable static arg retraces
  the step every call (the recompile storm that reads as "TPU is slow").
  :func:`count_traces` drives a jitted fn through N simulated steps with
  fresh inputs and fails if the executable cache grew past one entry.
* **S4 static HBM budget** — :func:`hbm_estimate` sums the per-device live
  bytes of a compiled step (arguments + outputs − donated aliases + peak
  XLA temporaries); :func:`check_hbm_budget` gates the sum against a
  per-chip capacity table so an oversized plan fails on CPU in seconds,
  not on the pod at step 0.

Everything here is chip-free by the same construction as contract_check:
AOT tracing/lowering on a virtual 8-device CPU mesh, zero FLOPs (S3 runs
tiny concrete steps — the one analysis that needs execution, at toy
geometry).  ``tools/spmd_check.py`` is the CLI that applies these to every
train-step factory in ``training.py`` (STEP_FACTORIES) under every
parallelism plan; ``lint/spmd_fixtures.py`` holds the deliberately-broken
models that prove each analysis has teeth.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple


class SPMDViolation(AssertionError):
    """A statically-decidable SPMD property of a traced program is broken."""


@contextlib.contextmanager
def fresh_stats_compile():
    """Compile with the persistent XLA compilation cache fully bypassed:
    a cache-deserialized executable can report zeroed or stale
    ``memory_analysis()`` stats (the cache serializes the executable, not
    all of its analyses), which would corrupt the S4 budget — and an
    executable compiled for a described, unattached TPU is written to the
    cache but cannot be read back without a chip
    (tests/test_tpu_compile.py).
    Toggling ``jax_enable_compilation_cache`` alone does NOT stop
    disk-cache reads on the AOT ``lowered.compile()`` path — the cache
    directory itself must be unset for the duration.  The analyzed
    executables are always compiled fresh; everything else (the S3 tiny
    steps, test suites) keeps the cache."""
    import jax

    prev_enabled = jax.config.jax_enable_compilation_cache
    prev_dir = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_enable_compilation_cache", False)
        jax.config.update("jax_compilation_cache_dir", None)
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_enable_compilation_cache", prev_enabled)


# --- S1: collective order -------------------------------------------------

# cross-shard primitives in jaxprs: a shard blocking in any of
# these waits for every peer on the named axes.  axis_index is deliberately
# absent (it is shard-local — no synchronization).
COLLECTIVE_PRIMS = frozenset((
    "psum", "pmin", "pmax", "ppermute", "pbroadcast", "all_gather",
    "all_to_all", "psum_scatter", "reduce_scatter", "pgather",
    "all_gather_invariant",
))


@dataclasses.dataclass(frozen=True)
class CollectiveSite:
    """One collective equation, located by its structural context."""

    prim: str
    axes: Tuple[str, ...]
    shapes: Tuple[str, ...]          # "f32[2,8]"-style operand avals
    context: Tuple[str, ...]         # enclosing HOP chain, outermost first

    @property
    def signature(self) -> Tuple:
        """Deadlock-relevant identity: two shards match a collective by
        primitive, mesh axes, and operand shapes — context excluded, so
        identical sequences reached through different branches compare
        equal."""
        return (self.prim, self.axes, self.shapes)

    def format(self) -> str:
        ctx = ">".join(self.context) or "top"
        return f"{self.prim}[{','.join(self.axes)}]({','.join(self.shapes)}) @ {ctx}"


def _aval_str(var) -> str:
    aval = getattr(var, "aval", None)
    if aval is None:
        return "?"
    return f"{getattr(aval.dtype, 'name', aval.dtype)}{list(aval.shape)}"


def _collective_axes(params: dict) -> Tuple[str, ...]:
    axes = params.get("axes") or params.get("axis_name") or ()
    if isinstance(axes, str):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def _sub_jaxprs(params: dict):
    """Every nested jaxpr in an equation's params (pjit/scan/shard_map/
    remat/custom_* all carry theirs under different keys — match by
    structure, like contract_check._iter_eqns)."""
    for val in params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                yield inner
            elif hasattr(v, "eqns"):
                yield v


def _walk_collectives(jaxpr, context: Tuple[str, ...],
                      sites: List[CollectiveSite],
                      violations: List[str]) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            sites.append(CollectiveSite(
                prim=name, axes=_collective_axes(eqn.params),
                shapes=tuple(_aval_str(v) for v in eqn.invars),
                context=context))
        elif name == "cond":
            # branches: executed under a traced predicate — shards may take
            # different branches, so a collective here only stays in lockstep
            # if EVERY branch issues the identical collective sequence
            branch_sites: List[List[CollectiveSite]] = []
            for i, br in enumerate(eqn.params["branches"]):
                bs: List[CollectiveSite] = []
                _walk_collectives(br.jaxpr, context + (f"cond#b{i}",), bs,
                                  violations)
                branch_sites.append(bs)
            sigs = [tuple(s.signature for s in bs) for bs in branch_sites]
            if any(s != sigs[0] for s in sigs[1:]):
                seqs = "; ".join(
                    f"branch {i}: [{', '.join(s.format() for s in bs) or 'none'}]"
                    for i, bs in enumerate(branch_sites))
                violations.append(
                    "collective under data-dependent control flow: cond "
                    f"branches at {'>'.join(context) or 'top'} issue "
                    f"DIFFERENT collective sequences ({seqs}) — shards "
                    "taking different branches deadlock the mesh")
            elif sigs[0]:
                # identical on every branch: unconditional in effect
                sites.extend(branch_sites[0])
        elif name == "while":
            for key in ("cond_jaxpr", "body_jaxpr"):
                ws: List[CollectiveSite] = []
                _walk_collectives(eqn.params[key].jaxpr,
                                  context + (f"while.{key[:4]}",), ws,
                                  violations)
                for s in ws:
                    violations.append(
                        f"collective {s.format()} inside a while loop's "
                        f"{key} — the trip count is data-dependent, so "
                        "shards can disagree on how many times the "
                        "collective runs (SPMD deadlock)")
        else:
            # scan (static trip count), pjit, shard_map, remat, custom_jvp/
            # vjp, ...: uniform across shards — recurse transparently
            for sub in _sub_jaxprs(eqn.params):
                _walk_collectives(sub, context + (name,), sites, violations)


def collective_trace(closed_jaxpr) -> Tuple[List[CollectiveSite], List[str]]:
    """Walk a (Closed)Jaxpr; return the unconditionally-executed collective
    sequence and the S1 violations (collectives whose execution a shard
    could skip or repeat differently from its peers)."""
    sites: List[CollectiveSite] = []
    violations: List[str] = []
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    _walk_collectives(jaxpr, (), sites, violations)
    return sites, violations


def check_collective_order(closed_jaxpr, label: str = "step") -> List[CollectiveSite]:
    """S1 gate: raise :class:`SPMDViolation` on any conditionally-executed
    collective; return the (safe) unconditional sequence for reporting."""
    sites, violations = collective_trace(closed_jaxpr)
    if violations:
        raise SPMDViolation(
            f"S1 collective order [{label}]: " + " | ".join(violations))
    return sites


# --- S1 extension: scan collective schedules ------------------------------
#
# Per-body uniformity (above) proves every shard issues the same sequence
# *per scan iteration*; a pipelined step additionally needs the TOTAL
# schedule — iteration count x per-iteration sequence — to be a static
# fact, because the microbatch scan is where the stage-to-stage ppermutes
# live and a count mismatch between stages is a deadlock the per-body view
# cannot see.  scan's trip count is static by construction, so the
# schedule is decidable: extract it, and let the caller pin the
# per-iteration sequence invariant across schedule-shaping knobs
# (tools/spmd_check.py compares num_microbatches=2 vs 4 — the sequence
# must be identical, only the length may change).


@dataclasses.dataclass(frozen=True)
class ScanSchedule:
    """The collective schedule of one collective-bearing scan: ``length``
    iterations, each issuing ``per_iteration`` in order (branch-matched
    conds already flattened; a branch-DIVERGENT cond inside the body is an
    S1 violation raised during extraction, not a schedule)."""

    context: Tuple[str, ...]             # enclosing HOP chain of the scan
    length: int                          # static trip count
    per_iteration: Tuple[Tuple, ...]     # CollectiveSite.signature sequence

    @property
    def total(self) -> int:
        return self.length * len(self.per_iteration)

    def format(self) -> str:
        prims = ",".join(sig[0] for sig in self.per_iteration)
        ctx = ">".join(self.context) or "top"
        return (f"{self.length} iterations x [{prims}] = {self.total} "
                f"collectives @ {ctx}")


def scan_collective_schedule(closed_jaxpr,
                             label: str = "step") -> List[ScanSchedule]:
    """Every collective-bearing ``scan`` in the program, outermost first,
    as a static schedule.  Raises :class:`SPMDViolation` if a scan body
    hides a collective under data-dependent control flow (the conditions
    under which no static schedule exists)."""
    out: List[ScanSchedule] = []

    def walk(jaxpr, context: Tuple[str, ...]) -> None:
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "scan":
                body = eqn.params["jaxpr"].jaxpr
                sites: List[CollectiveSite] = []
                violations: List[str] = []
                _walk_collectives(body, context + ("scan",), sites,
                                  violations)
                if violations:
                    raise SPMDViolation(
                        f"S1 scan schedule [{label}]: "
                        + " | ".join(violations))
                if sites:
                    out.append(ScanSchedule(
                        context=context, length=int(eqn.params["length"]),
                        per_iteration=tuple(s.signature for s in sites)))
                # the body was fully analyzed above — no double recursion
            else:
                for sub in _sub_jaxprs(eqn.params):
                    walk(sub, context + (name,))

    walk(getattr(closed_jaxpr, "jaxpr", closed_jaxpr), ())
    return out


# --- S2: donation audit ---------------------------------------------------


@dataclasses.dataclass
class DonationAudit:
    """Per-leaf donation facts of one AOT-lowered program."""

    donated_bytes: int
    undonated_bytes: int
    # (arg label, pytree path, bytes) for undonated leaves over the
    # reporting threshold — informational unless the label was expected
    # to donate
    undonated_big: List[Tuple[str, str, int]]
    # pytree paths of leaves under expected-donated labels that the jit
    # did NOT mark donated
    missing: List[str]
    donated_leaves: int = 0              # array leaves marked donated
    aliased_params: Optional[int] = None  # compiled executable's aliases

    @property
    def donated_fraction(self) -> float:
        """Requested-donated share of the total argument bytes (global,
        pre-sharding).  Donated and undonated args shard across the same
        mesh, so the share survives partitioning — S4 uses it to convert
        per-device argument bytes into per-device aliased bytes."""
        total = self.donated_bytes + self.undonated_bytes
        return self.donated_bytes / total if total else 0.0

    def ok(self) -> bool:
        if self.missing:
            return False
        if self.aliased_params is None:
            return True
        return self.aliased_params >= self.donated_leaves


def _leaf_bytes(aval) -> int:
    n = 1
    for d in aval.shape:
        n *= int(d)
    return n * aval.dtype.itemsize


def _path_str(path) -> str:
    parts = []
    for p in path:
        key = getattr(p, "key", None)
        if key is None:
            key = getattr(p, "idx", None)
        if key is None:
            key = getattr(p, "name", p)
        parts.append(str(key))
    return "/".join(parts)


def compiled_alias_count(compiled) -> int:
    """Count the distinct aliased input parameters in a compiled
    executable's optimized-HLO ``input_output_alias`` config — the
    compiler's ACTUAL aliasing decision, read from ``compiled.as_text()``
    (``memory_analysis().alias_size_in_bytes`` is zeroed at backend opt
    level 0 and on cache-deserialized executables even when the aliases
    are honored, so it cannot carry this check).  Entries look like
    ``{output_index}: (param_number, {param_tuple_index}, may-alias)``;
    distinct (param_number, tuple_index) pairs are counted so tupled
    parameters audit correctly."""
    import re

    txt = compiled.as_text()
    key = "input_output_alias={"
    start = txt.find(key)
    if start < 0:
        return 0
    i = start + len(key) - 1
    depth = 0
    end = i
    for end in range(i, len(txt)):
        if txt[end] == "{":
            depth += 1
        elif txt[end] == "}":
            depth -= 1
            if depth == 0:
                break
    body = txt[i:end + 1]
    pairs = set(re.findall(r"\(\s*(\d+)\s*,\s*\{([^}]*)\}", body))
    return len(pairs)


def audit_donation(lowered, arg_labels: Sequence[str],
                   expect_donated: Sequence[int] = (0, 1),
                   compiled=None, big: int = 1 << 20) -> DonationAudit:
    """S2: read per-leaf donation off ``lowered.args_info``.

    ``arg_labels`` names the positional args (for reporting);
    ``expect_donated`` are the positional indices whose every array leaf
    must be donated (params/opt_state for a train step).  ``compiled``
    (optional) adds the did-the-compiler-actually-alias check via
    :func:`compiled_alias_count`.
    """
    import jax

    info = lowered.args_info
    donated = 0
    undonated = 0
    donated_leaves = 0
    undonated_big: List[Tuple[str, str, int]] = []
    missing: List[str] = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(info):
        # args_info paths start ((args, kwargs) idx, arg idx, per-arg path)
        arg_idx = getattr(path[1], "idx", None) if len(path) > 1 else None
        label = (arg_labels[arg_idx]
                 if arg_idx is not None and arg_idx < len(arg_labels)
                 else f"arg{arg_idx}")
        size = _leaf_bytes(getattr(leaf, "aval", None) or leaf._aval)
        if getattr(leaf, "donated", False):
            donated += size
            donated_leaves += 1
        else:
            undonated += size
            if arg_idx in tuple(expect_donated):
                missing.append(f"{label}/{_path_str(path[2:])}")
            elif size >= big:
                undonated_big.append(
                    (label, _path_str(path[2:]), size))
    aliased = None
    if compiled is not None:
        aliased = compiled_alias_count(compiled)
    return DonationAudit(donated_bytes=donated, undonated_bytes=undonated,
                         undonated_big=sorted(undonated_big,
                                              key=lambda t: -t[2]),
                         missing=missing, donated_leaves=donated_leaves,
                         aliased_params=aliased)


def check_donation(lowered, arg_labels: Sequence[str],
                   expect_donated: Sequence[int] = (0, 1),
                   compiled=None, label: str = "step") -> DonationAudit:
    """S2 gate: raise when an expected-donated leaf is undonated, or when
    the compiler silently dropped the requested aliasing."""
    audit = audit_donation(lowered, arg_labels, expect_donated, compiled)
    if audit.missing:
        head = ", ".join(audit.missing[:5])
        more = f" (+{len(audit.missing) - 5} more)" if len(audit.missing) > 5 else ""
        raise SPMDViolation(
            f"S2 donation [{label}]: {len(audit.missing)} leaves of the "
            f"donated args are NOT donated ({head}{more}) — the step holds "
            "these buffers twice across the update; pass donate_argnums")
    if not audit.ok():
        raise SPMDViolation(
            f"S2 donation [{label}]: {audit.donated_leaves} leaves were "
            f"requested donated but the compiled executable aliases only "
            f"{audit.aliased_params} input parameters to outputs — jax "
            "dropped donation silently (a donated input matches no "
            "output's shape/dtype/sharding, e.g. a refactored return "
            "structure)")
    return audit


# --- S3: retrace sentinel -------------------------------------------------


def count_traces(jitted, make_args: Callable[[int], tuple],
                 steps: int = 3, label: str = "step") -> int:
    """S3: run ``jitted(*make_args(i))`` for ``steps`` simulated steps and
    return the executable-cache population.  A healthy step traces ONCE;
    every additional entry is a recompile that will repeat per epoch on
    the pod.  Unhashable static args (the list-keyed footgun) surface as a
    violation instead of an opaque jax error."""
    cache_size = getattr(jitted, "_cache_size", None)
    if cache_size is None:
        raise SPMDViolation(
            f"S3 retrace [{label}]: jitted function exposes no _cache_size "
            "— jax upgraded past the sentinel; re-pin the trace-count API")
    for i in range(steps):
        try:
            jitted(*make_args(i))
        except (TypeError, ValueError) as e:
            raise SPMDViolation(
                f"S3 retrace [{label}]: step {i} failed to hash its static "
                f"args ({type(e).__name__}: {e}) — an unhashable static "
                "arg (list/dict/ndarray) defeats the jit cache entirely")
    return int(cache_size())


def check_single_trace(jitted, make_args: Callable[[int], tuple],
                       steps: int = 3, label: str = "step") -> None:
    n = count_traces(jitted, make_args, steps=steps, label=label)
    if n > 1:
        raise SPMDViolation(
            f"S3 retrace [{label}]: {steps} simulated steps produced {n} "
            "traces — a static arg with value-unstable hashing (fresh "
            "object per call, float jitter, changing shape) recompiles "
            "the step; hoist it to a traced arg or intern the static")


# --- S4: static HBM budget ------------------------------------------------

# Usable per-chip HBM.  None = unbounded (the virtual CPU mesh).  v4 chips
# carry 32 GiB HBM2, v5e 16 GiB HBM2 (public TPU system specs); the
# margin in check_hbm_budget leaves headroom for XLA's runtime scratch
# and fragmentation, which the static sum cannot see.
CHIP_HBM_BYTES: Dict[str, Optional[int]] = {
    "cpu-virtual": None,
    "v4-8": 32 * 1024 ** 3,
    "v5e-4": 16 * 1024 ** 3,
}


@dataclasses.dataclass(frozen=True)
class HBMEstimate:
    """Per-device live bytes of one compiled step program."""

    argument_bytes: int
    output_bytes: int
    alias_bytes: int
    temp_bytes: int

    @property
    def total_bytes(self) -> int:
        """Peak live estimate: inputs resident + non-aliased outputs +
        XLA temporaries.  Donated aliases are subtracted once — a donated
        output lands in its input's buffer."""
        return (self.argument_bytes + self.output_bytes
                - self.alias_bytes + self.temp_bytes)

    def format(self) -> str:
        mib = 1024 ** 2
        return (f"args {self.argument_bytes / mib:.0f} MiB + out "
                f"{self.output_bytes / mib:.0f} - alias "
                f"{self.alias_bytes / mib:.0f} + temp "
                f"{self.temp_bytes / mib:.0f} = "
                f"{self.total_bytes / mib:.0f} MiB/device")


def hbm_estimate(compiled) -> HBMEstimate:
    """S4: static per-device memory of a compiled (SPMD-partitioned)
    program.  On the virtual mesh the compiled module IS the per-device
    program, so these sizes are already per-chip."""
    ma = compiled.memory_analysis()
    return HBMEstimate(
        argument_bytes=int(ma.argument_size_in_bytes),
        output_bytes=int(ma.output_size_in_bytes),
        alias_bytes=int(ma.alias_size_in_bytes),
        temp_bytes=int(ma.temp_size_in_bytes))


def check_hbm_budget(estimate: HBMEstimate, chip: str,
                     margin: float = 0.9, label: str = "step") -> None:
    """S4 gate: the static live sum must fit ``margin`` of the chip's HBM.
    Unknown chips are a configuration error, not a pass."""
    if chip not in CHIP_HBM_BYTES:
        raise SPMDViolation(
            f"S4 hbm [{label}]: unknown chip {chip!r}; known: "
            f"{sorted(CHIP_HBM_BYTES)}")
    if not estimate.argument_bytes:
        raise SPMDViolation(
            f"S4 hbm [{label}]: the compiled executable reports zero "
            "argument bytes — cache-deserialized executables carry no "
            "memory stats, so this budget would gate nothing; re-compile "
            "under spmd.fresh_stats_compile()")
    cap = CHIP_HBM_BYTES[chip]
    if cap is None:
        return
    budget = int(cap * margin)
    if estimate.total_bytes > budget:
        raise SPMDViolation(
            f"S4 hbm [{label}]: static live bytes {estimate.format()} "
            f"exceed {margin:.0%} of {chip} HBM "
            f"({budget / 1024 ** 2:.0f} MiB) — this plan OOMs at step 0; "
            "shard further (fsdp/sp), cut the batch, or enable remat")
