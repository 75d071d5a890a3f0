"""graftplan — static ParallelPlan contract analyses (P1-P4).

graftspmd reads the traced programs and graftrace reads the lock graph;
this module reads the *sharding contract itself*: the regex rule table
(``parallel/plan.PARTITION_RULES``), the plan registry, and the preset
geometries, cross-checked chip-free against declared chip topologies.
Four pure analyses, each provable against a deliberately-broken fixture
twin (``plans_fixtures.py``, ``tools/plan_check.py --selftest``):

* **P1 rule coverage / ambiguity** — every shardable (ndim >= 2) param
  leaf of every preset matches a ``PARTITION_RULES`` entry or a declared
  replication pattern (:data:`P1_REPLICATED`).  An unmatched leaf
  silently replicates (the exact failure dalle-mini hand-audited its
  rule tables against); two *conflicting* non-terminal rules matching
  the same leaf make the table order load-bearing — first-hit-wins
  silently shadows the loser, so the overlap is a finding.
* **P2 axis divisibility** — ``mesh._prune_spec`` SILENTLY drops any
  rule axis that does not divide the param dim, and
  ``Partitioner.shard_batch`` silently replicates a batch the data axes
  don't divide.  P2 makes both degradations loud: for each (preset x
  plan x topology) cell it resolves the mesh axis sizes (``dp=None``
  absorption included) and flags every sharded dim the mesh would
  silently un-shard.
* **P3 analytic HBM fit** — per-leaf sharded state residency (params +
  optimizer moments, divided by exactly the axis products that survive
  P2's divisibility) folded through the graftmem phase model against
  ``CHIP_SPECS`` x0.9.  The hard gate covers the phases sharding alone
  controls — ``init`` (state resident) and ``ckpt`` (snapshot pins the
  state twice, no donation); the walker's global activation peak rides
  along as the advisory ``step_peak`` (the committed cub-512 memory row
  precedent: the no-remat f32 walker is deliberately pessimistic, and
  the compiled S4 proof under ``spmd_check --presets`` owns step-peak
  truth).
* **P4 collective placement** — for dcn hybrid plans: fsdp/tp axes must
  fit inside one ICI slice (a multi-slice topology without a matching
  ``dcn_dp`` axis leaves slice pinning undefined), and in the traced
  step only a ``psum`` over the dp axis (the grad all-reduce) may cross
  DCN — any other collective over a DCN-crossing axis is a finding.
  The jaxpr walk reuses graftspmd's collective classes
  (``spmd.collective_trace``), so shard_map plans with explicit
  collectives are covered by the same sweep.

``tools/plan_check.py`` is the CLI (the graftrace shape: default sweep,
``--select``, ``--json``, ``--selftest``); ``tools/plan_search.py``
reuses the same analyses as hard feasibility gates and adds the
roofline score (:func:`score_cell`) to pick the committed
``PLAN_LEDGER.json`` winners.

Chip topologies are declared here (:data:`TOPOLOGIES`), separate from
``prof.CHIP_SPECS``: a chip spec is one device's peaks; a topology is
how many of them, in how many DCN-connected slices.  Waivers
(:data:`WAIVERS`) are the pragma equivalent for cell-anchored findings
— empty at HEAD; every entry needs a written reason.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from dalle_pytorch_tpu.parallel.plan import (PARTITION_RULES, PLAN_REGISTRY,
                                             ParallelPlan)

ANALYSES = ("P1", "P2", "P3", "P4")

#: Mirror of obs/mem.HBM_MARGIN — allocator fragmentation eats the rest.
HBM_MARGIN = 0.9

#: Analytic DCN bandwidth per device (bytes/s) for the autotuner's
#: multi-slice penalty term: the grad all-reduce's ring streams ~2x the
#: per-device grad shard over the data-center network.  Held stable by
#: construction (the drift gate compares scores computed from it).
DCN_BW = 25e9

#: 2-D+ leaves that are replicated BY DESIGN, not by rule-table
#: fall-through: position embeddings (tiny, consumed whole every step)
#: and the per-layer layerscale vectors.  P1 flags any other >=2-D leaf
#: that matches no PARTITION_RULES entry — new param surfaces must either
#: get a rule or be declared here, with a reason, in review.
P1_REPLICATED = (
    r".*pos_emb/(embedding|row|col)$",
    r".*(attn|ff)/scale$",
)

#: Cell-anchored waivers, the pragma equivalent for findings that have no
#: source line to annotate: (code, cell regex, reason).  Empty at HEAD —
#: plan_check reports a waived finding as suppressed, and an entry that
#: matches nothing is itself an error (the PRAGMA002 discipline).
WAIVERS: Tuple[Tuple[str, str, str], ...] = ()


class PlanAnalysisError(Exception):
    """Harness errors (unknown preset/chip, malformed waiver) — distinct
    from findings, which are contract violations."""


def _fmt_bytes(n: int) -> str:
    for unit, div in (("GiB", 2**30), ("MiB", 2**20), ("KiB", 2**10)):
        if n >= div:
            return f"{n / div:.1f} {unit}"
    return f"{n} B"


@dataclasses.dataclass(frozen=True)
class Finding:
    """One contract violation, anchored to its (preset x plan @ topology)
    cell rather than a source line."""

    code: str      # P1..P4
    cell: str      # e.g. "cub-1024 x fsdp4.tp2 @ v5e-8"
    message: str

    def render(self) -> str:
        return f"{self.cell}: {self.code} {self.message}"


# --- chip topologies ------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Topology:
    """A concrete device pool: ``chip`` names the per-device
    ``prof.CHIP_SPECS`` entry, ``devices`` how many, ``slices`` how many
    DCN-connected ICI islands they form (1 = single slice, everything on
    ICI)."""

    name: str
    chip: str
    devices: int
    slices: int = 1

    def __post_init__(self):
        if self.devices % self.slices:
            raise PlanAnalysisError(
                f"topology {self.name!r}: {self.devices} devices not "
                f"divisible into {self.slices} slices")

    @property
    def per_slice(self) -> int:
        return self.devices // self.slices


#: The topology ladder the analyzer and autotuner sweep.  Single-slice
#: pods first, then the multi-slice rung where dcn plans earn their keep.
TOPOLOGIES: Tuple[Topology, ...] = (
    Topology("v4-8", "v4-8", 4),
    Topology("v5e-4", "v5e-4", 4),
    Topology("v4-16", "v4-8", 8),
    Topology("v5e-8", "v5e-4", 8),
    Topology("2x-v5e-8", "v5e-4", 16, slices=2),
)


def topology(name: str) -> Topology:
    for t in TOPOLOGIES:
        if t.name == name:
            return t
    raise PlanAnalysisError(f"unknown topology {name!r}; known: "
                            f"{[t.name for t in TOPOLOGIES]}")


# --- plan candidates ------------------------------------------------------

#: The autotuner's candidate grid, as plan specs.  Covers every dense
#: (rule-table) registry plan's spec — dp, fsdp (fsdp4), tp (tp2),
#: cub-512 (fsdp4), cub-1024 (fsdp4.tp2) — plus the hybrids the registry
#: doesn't name and the dcn variants for multi-slice topologies.
#: sp/pp/ep plans are out of scope here: they own the inner mesh axis,
#: the partition rules prune to replicated under their meshes, and their
#: shard_map steps are scored by graftprof's per-shard walk instead.
CANDIDATE_SPECS: Tuple[str, ...] = (
    "dp",
    "fsdp4",
    "fsdp8",
    "tp2",
    "fsdp2.tp2",
    "fsdp4.tp2",
    "dcn2.fsdp2",
    "dcn2.fsdp2.tp2",
    "dcn2.fsdp4.tp2",
)


@functools.lru_cache(maxsize=None)
def candidate_plans() -> Tuple[ParallelPlan, ...]:
    return tuple(ParallelPlan.parse(s) for s in CANDIDATE_SPECS)


# --- mesh-axis resolution (the dp=None absorption, chip-free) -------------


def resolve_axis_sizes(plan: ParallelPlan, topo: Topology
                       ) -> Tuple[Optional[Dict[str, int]], Optional[str]]:
    """Resolve the plan's mesh axis sizes on a topology — the same
    arithmetic ``mesh.make_mesh`` performs, without devices.  Returns
    ``(sizes, None)`` with sizes keyed by mesh axis name, or
    ``(None, reason)`` when the plan cannot build on this topology at
    all (an infeasibility, not a finding: the autotuner records the
    reason, the analyzer skips the cell)."""
    n = topo.devices
    if plan.sp > 1 or plan.pp > 1 or plan.ep > 1:
        axis = "sp" if plan.sp > 1 else "pp" if plan.pp > 1 else "ep"
        inner = getattr(plan, axis)
        if n % inner:
            return None, (f"{n} devices not divisible by {axis}={inner}")
        dp = plan.dp if plan.dp is not None else n // inner
        if dp * inner != n:
            return None, (f"dp{dp} x {axis}{inner} != {n} devices")
        return {"dp": dp, axis: inner}, None
    inner = plan.fsdp * plan.tp
    if plan.dp is None:
        if n % inner:
            return None, (f"{n} devices not divisible by "
                          f"fsdp{plan.fsdp} x tp{plan.tp} = {inner}")
        dp = n // inner
    else:
        dp = plan.dp
        if dp * inner != n:
            return None, (f"dp{dp} x fsdp{plan.fsdp} x tp{plan.tp} "
                          f"= {dp * inner} != {n} devices")
    if dp == 0:
        return None, (f"fsdp{plan.fsdp} x tp{plan.tp} = {inner} ways "
                      f"exceed {n} devices")
    if plan.dcn_dp > 1 and dp % plan.dcn_dp:
        return None, f"dp={dp} not divisible by dcn_dp={plan.dcn_dp}"
    return {"dp": dp, "fsdp": plan.fsdp, "tp": plan.tp}, None


# --- rule matching (P1/P2 share it) ---------------------------------------


@functools.lru_cache(maxsize=8)
def _compiled(rules) -> Tuple:
    return tuple((re.compile(pat), spec) for pat, spec in rules)


def matching_rules(path: str, rules=PARTITION_RULES) -> List[int]:
    """Indices of every rule whose pattern matches the '/'-joined param
    path (the Partitioner takes index 0 — first hit wins)."""
    return [i for i, (pat, _) in enumerate(_compiled(rules))
            if pat.match(path)]


def winning_spec(path: str, rules=PARTITION_RULES):
    """The spec the Partitioner would pick, before divisibility pruning
    (None = no rule matches: replicated by fall-through)."""
    hits = matching_rules(path, rules)
    return rules[hits[0]][1] if hits else None


def _spec_axes(spec) -> Tuple[Tuple[Tuple[str, ...], ...], ...]:
    """Per-dim tuples of axis names (empty tuple = unsharded dim)."""
    out = []
    for names in spec:
        if names is None:
            out.append(())
        else:
            out.append((names,) if isinstance(names, str) else tuple(names))
    return tuple(out)


def leaf_shard_factor(shape: Tuple[int, ...], spec,
                      sizes: Dict[str, int]) -> int:
    """The divisor ``_prune_spec`` would actually realize for this leaf:
    the product of axis sizes over dims where every named axis exists in
    the mesh and the product divides the dim.  1 = fully replicated."""
    if spec is None:
        return 1
    factor = 1
    for dim, names in enumerate(_spec_axes(spec)):
        if not names or dim >= len(shape):
            continue
        size = 1
        for nm in names:
            size *= sizes.get(nm, 1)
        if size > 1 and all(nm in sizes for nm in names) \
                and shape[dim] % size == 0:
            factor *= size
    return factor


# --- P1: rule-table coverage / ambiguity ----------------------------------


def check_rule_coverage(param_shapes: Dict[str, Tuple[Tuple[int, ...], int]],
                        rules=PARTITION_RULES, *,
                        preset: str = "?") -> List[Finding]:
    """P1.  ``param_shapes`` maps '/'-joined leaf paths to (shape,
    itemsize) — :func:`preset_cost` builds it from ``jax.eval_shape``,
    fixtures hand-craft it."""
    findings: List[Finding] = []
    cell = f"{preset} x PARTITION_RULES"
    replicated_ok = tuple(re.compile(p) for p in P1_REPLICATED)
    terminal = len(rules) - 1
    for path, (shape, _item) in sorted(param_shapes.items()):
        hits = matching_rules(path, rules)
        if not hits:
            if len(shape) >= 2 and not any(p.match(path)
                                           for p in replicated_ok):
                findings.append(Finding(
                    "P1", cell,
                    f"param leaf {path} {tuple(shape)} matches no "
                    "PARTITION_RULES entry — it silently replicates on "
                    "every mesh; add a rule (or declare it in "
                    "plans.P1_REPLICATED with a reason)"))
            continue
        winner = rules[hits[0]][1]
        for i in hits[1:]:
            if i == terminal:
                continue  # the declared catch-all default may overlap
            if tuple(rules[i][1]) != tuple(winner):
                findings.append(Finding(
                    "P1", cell,
                    f"param leaf {path} matches rule #{hits[0]} "
                    f"({rules[hits[0]][0]!r} -> {winner}) AND rule #{i} "
                    f"({rules[i][0]!r} -> {rules[i][1]}) with conflicting "
                    "specs — first-hit-wins silently shadows the loser; "
                    "tighten one pattern so the table order is not "
                    "load-bearing"))
    return findings


# --- P2: axis divisibility -------------------------------------------------


def check_divisibility(param_shapes: Dict[str, Tuple[Tuple[int, ...], int]],
                       plan: ParallelPlan, topo: Topology, *,
                       preset: str = "?", batch: Optional[int] = None,
                       rules=None) -> List[Finding]:
    """P2.  Every axis a rule shards by must divide its dim on this
    topology's resolved mesh — otherwise ``_prune_spec`` silently drops
    the axis and the leaf replicates (the memory the plan promised to
    shard quietly comes back).  ``batch`` additionally gates
    ``shard_batch``'s silent replicated fallback."""
    rules = plan.rules if rules is None else rules
    sizes, why = resolve_axis_sizes(plan, topo)
    if sizes is None:
        return []  # infeasible cell: the autotuner records `why`
    findings: List[Finding] = []
    cell = f"{preset} x {plan.spec()} @ {topo.name}"
    for path, (shape, item) in sorted(param_shapes.items()):
        spec = winning_spec(path, rules)
        if spec is None:
            continue
        for dim, names in enumerate(_spec_axes(spec)):
            if not names or dim >= len(shape):
                continue
            size = 1
            for nm in names:
                size *= sizes.get(nm, 1)
            if size > 1 and all(nm in sizes for nm in names) \
                    and shape[dim] % size != 0:
                leaf_bytes = item
                for s in shape:
                    leaf_bytes *= s
                findings.append(Finding(
                    "P2", cell,
                    f"{path} dim {dim} ({shape[dim]}) is not divisible by "
                    f"{'x'.join(names)}={size} — mesh._prune_spec will "
                    f"silently drop the axis and keep all "
                    f"{_fmt_bytes(leaf_bytes)} resident per device "
                    f"instead of 1/{size}"))
    if batch is not None:
        data_ways = sizes.get("dp", 1) * sizes.get("fsdp", 1)
        # data_ways > batch is a capacity infeasibility (the cell cannot
        # even give one row per group — plan_search records the reason
        # via batch_infeasible); only the silent-degradation case where
        # the batch COULD shard but doesn't divide is a finding.
        if 1 < data_ways <= batch and batch % data_ways:
            findings.append(Finding(
                "P2", cell,
                f"batch {batch} is not divisible by the data axes "
                f"dp x fsdp = {data_ways} — Partitioner.shard_batch "
                "silently falls back to a replicated batch (every device "
                "computes every row)"))
    return findings


def batch_infeasible(plan: ParallelPlan, topo: Topology,
                     batch: int) -> Optional[str]:
    """The autotuner's capacity check: more data-parallel groups than
    batch rows means the cell cannot run as intended at all (reason
    string), as opposed to P2's silent-replication finding."""
    sizes, why = resolve_axis_sizes(plan, topo)
    if sizes is None:
        return why
    data_ways = sizes.get("dp", 1) * sizes.get("fsdp", 1)
    if data_ways > batch:
        return (f"data axes dp x fsdp = {data_ways} exceed batch {batch} "
                "— fewer than one row per data-parallel group")
    return None


# --- per-preset cost model (the one expensive walk, cached) ----------------


@dataclasses.dataclass(frozen=True)
class PresetCost:
    """Everything the per-cell analyses need about one preset geometry,
    computed once: the param tree's paths/shapes, global state bytes,
    the graftmem liveness walk, and the graftprof flop/byte attribution.
    ``jaxpr`` rides along for P4's collective walk."""

    preset: str
    batch: int
    param_shapes: Dict[str, Tuple[Tuple[int, ...], int]]
    params_bytes: int
    opt_bytes: int
    flops: int
    walker_bytes: int
    walker_peak_bytes: int
    resident_bytes: int
    jaxpr: object = dataclasses.field(repr=False, hash=False, compare=False)
    config: object = dataclasses.field(repr=False, hash=False, compare=False)


@functools.lru_cache(maxsize=None)
def preset_cost(preset: str, batch: int = 8) -> PresetCost:
    """Trace the preset's real train step (health-enabled, the graftprof
    convention) once and distill the analysis inputs.  Chip-free:
    eval_shape + make_jaxpr, nothing executes or compiles — ~20 s at
    cub-1024, milliseconds at tiny."""
    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models.dalle import DALLE
    from dalle_pytorch_tpu.obs import mem, prof
    from dalle_pytorch_tpu.parallel.mesh import _path_str
    from dalle_pytorch_tpu.presets import preset_config
    from dalle_pytorch_tpu.training import (make_dalle_train_step,
                                            make_optimizer)

    cfg = preset_config(preset)
    dalle = DALLE(cfg)
    tx = make_optimizer(1e-3)
    sds = jax.ShapeDtypeStruct
    text = sds((batch, cfg.text_seq_len), jnp.int32)
    codes = sds((batch, cfg.image_seq_len), jnp.int32)
    rng = sds((2,), jnp.uint32)
    fs = sds((), jnp.float32)
    params = jax.eval_shape(dalle.init, jax.random.PRNGKey(0), text,
                            codes)["params"]
    opt = jax.eval_shape(tx.init, params)
    step = make_dalle_train_step(dalle, tx, health=True)
    jaxpr = jax.make_jaxpr(step)(params, opt, None, text, codes, rng, fs)
    attr = prof.attribute(jaxpr)
    prof.check_coverage(attr, label=f"graftplan/{preset}")
    walk = mem.peak_live(
        jaxpr,
        planes=mem.arg_planes(("params", params), ("opt-state", opt),
                              ("args", (None, text, codes, rng, fs))))
    shapes = {
        _path_str(path): (tuple(leaf.shape), int(leaf.dtype.itemsize))
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    return PresetCost(
        preset=preset, batch=batch, param_shapes=shapes,
        params_bytes=int(mem.tree_bytes(params)),
        opt_bytes=int(mem.tree_bytes(opt)),
        flops=int(attr["total"]["flops"]),
        walker_bytes=int(attr["total"]["bytes"]),
        walker_peak_bytes=int(walk["peak_bytes"]),
        resident_bytes=int(walk["resident_bytes"]),
        jaxpr=jaxpr, config=cfg)


def sharded_state_bytes(cost: PresetCost, plan: ParallelPlan,
                        sizes: Dict[str, int]) -> Tuple[int, int]:
    """Per-device (params, opt) residency under exactly the sharding the
    mesh would realize: each leaf divided by its :func:`leaf_shard_factor`
    (the P2-surviving axis product).  The Adam moments shard like their
    params (Partitioner.init_opt_state pins them so), so the optimizer
    side is 2x the sharded params plus the tree's scalar remainder,
    replicated."""
    params_sh = 0
    for path, (shape, item) in cost.param_shapes.items():
        leaf = item
        for s in shape:
            leaf *= s
        params_sh += leaf // leaf_shard_factor(
            shape, winning_spec(path, plan.rules), sizes)
    moments = 2 * cost.params_bytes
    remainder = max(0, cost.opt_bytes - moments)
    opt_sh = 2 * params_sh + remainder
    return params_sh, opt_sh


# --- P3: analytic HBM fit --------------------------------------------------


def state_phases(cost: PresetCost, plan: ParallelPlan, topo: Topology
                 ) -> Optional[Dict[str, int]]:
    """The graftmem phase timeline for one cell, per device: ``init``
    (sharded state resident) and ``ckpt`` (the between-steps snapshot
    pins the state twice — unlike ``mem.analytic_train_phases`` this
    chip-free gate models checkpointing between steps, not mid-step)
    from per-leaf sharded state, exact; ``step_peak`` adds the walker's
    global activation peak divided across devices (advisory — no-remat
    f32, see module docstring)."""
    sizes, _ = resolve_axis_sizes(plan, topo)
    if sizes is None:
        return None
    params_sh, opt_sh = sharded_state_bytes(cost, plan, sizes)
    state = params_sh + opt_sh
    act = max(0, cost.walker_peak_bytes
              - cost.resident_bytes) // max(topo.devices, 1)
    return {"init": state, "step_peak": state + act, "ckpt": 2 * state}


def check_hbm_fit(cost: PresetCost, plan: ParallelPlan, topo: Topology, *,
                  margin: float = HBM_MARGIN) -> List[Finding]:
    """P3.  Gate ``init`` and ``ckpt`` (state residency — what sharding
    alone controls) against the topology's per-device HBM at the S4
    margin."""
    from dalle_pytorch_tpu.obs import mem

    phases = state_phases(cost, plan, topo)
    if phases is None:
        return []
    gated = {k: phases[k] for k in ("init", "ckpt")}
    verdict = mem.headroom_verdict(gated, topo.chip, margin)
    if verdict["fits"]:
        return []
    cell = f"{cost.preset} x {plan.spec()} @ {topo.name}"
    return [Finding(
        "P3", cell,
        f"sharded state residency {verdict['peak_bytes'] / 2**30:.2f} GiB "
        f"in phase {verdict['peak_phase']!r} exceeds {margin:.0%} of "
        f"{topo.chip}'s {verdict['hbm_bytes'] / 2**30:.1f} GiB HBM — the "
        "plan's shard factors cannot hold this preset's params + "
        "optimizer moments; more fsdp/tp ways (or a bigger chip) needed")]


# --- P4: collective placement (dcn hybrids) --------------------------------


def crossing_axes(plan: ParallelPlan, topo: Topology
                  ) -> Tuple[set, List[str]]:
    """The mesh axes whose collectives traverse DCN on this topology,
    plus structural violations (reasons) that make placement undefined
    or force inner axes across slices."""
    problems: List[str] = []
    if topo.slices == 1:
        if plan.dcn_dp > 1:
            problems.append(
                f"plan declares dcn_dp={plan.dcn_dp} on single-slice "
                f"{topo.name} — there is no DCN boundary to pin")
        return set(), problems
    cross = {"dp"}  # dp's outer groups span the slice boundary
    if plan.dcn_dp != topo.slices:
        problems.append(
            f"multi-slice topology ({topo.slices} slices) but plan "
            f"dcn_dp={plan.dcn_dp}: mesh construction cannot pin the "
            "slice boundary, so fsdp/tp collective placement is "
            "undefined — declare dcn_dp equal to the slice count")
    inner = plan.fsdp * plan.tp * plan.sp * plan.pp * plan.ep
    if inner > topo.per_slice:
        problems.append(
            f"fsdp/tp ways ({inner}) exceed the {topo.per_slice} devices "
            "of one ICI slice — their all-gathers would cross DCN")
        for axis in ("fsdp", "tp", "sp", "pp", "ep"):
            if getattr(plan, axis) > 1:
                cross.add(axis)
    return cross, problems


def check_collective_placement(plan: ParallelPlan, topo: Topology, *,
                               preset: str = "?",
                               jaxpr=None) -> List[Finding]:
    """P4.  Structural slice-pinning checks plus the graftspmd-classified
    jaxpr walk: only a ``psum`` over the dp axis (the grad all-reduce)
    may cross DCN."""
    cross, problems = crossing_axes(plan, topo)
    cell = f"{preset} x {plan.spec()} @ {topo.name}"
    findings = [Finding("P4", cell, p) for p in problems]
    if jaxpr is not None and cross:
        from dalle_pytorch_tpu.lint import spmd

        sites, _ = spmd.collective_trace(jaxpr)
        for site in sites:
            hit = set(site.axes) & cross
            if not hit:
                continue
            if site.prim == "psum" and set(site.axes) <= {"dp"}:
                continue  # the one collective allowed to cross DCN
            findings.append(Finding(
                "P4", cell,
                f"{site.prim} over axes {tuple(site.axes)} crosses DCN "
                f"(crossing axes here: {sorted(cross)}) — only the dp "
                "grad all-reduce may; pin this collective to ICI axes "
                "or restructure the plan"))
    return findings


# --- the autotuner's score model ------------------------------------------

#: Bump when the score arithmetic changes — part of every ledger row's
#: fingerprint, so a model change reads as "update the ledger", never as
#: silent drift.
SCORE_MODEL = 1


def score_cell(cost: PresetCost, plan: ParallelPlan, topo: Topology
               ) -> Optional[dict]:
    """The chip-free roofline score for one feasible cell: predicted
    step time = max(flop time, per-device byte stream) + the DCN
    all-reduce penalty on multi-slice topologies.  The byte stream is
    the per-device sharded state plus the walker's activation share —
    plan-sensitive through exactly the per-leaf shard factors P2
    validates.  Deterministic pure arithmetic: the drift gate compares
    it exactly."""
    from dalle_pytorch_tpu.obs import mem, prof

    sizes, _ = resolve_axis_sizes(plan, topo)
    if sizes is None:
        return None
    spec = prof.CHIP_SPECS[topo.chip]
    params_sh, opt_sh = sharded_state_bytes(cost, plan, sizes)
    state = params_sh + opt_sh
    act = max(0, cost.walker_peak_bytes
              - cost.resident_bytes) // max(topo.devices, 1)
    traffic = state + act
    flop_time = cost.flops / (spec.peak_flops * topo.devices)
    byte_time = traffic / spec.hbm_bw
    dcn_time = (2 * params_sh / DCN_BW) if topo.slices > 1 else 0.0
    pred = max(flop_time, byte_time) + dcn_time
    phases = state_phases(cost, plan, topo)
    verdict = mem.headroom_verdict(
        {k: phases[k] for k in ("init", "ckpt")}, topo.chip)
    return {
        "pred_step_time_s": pred,
        "predicted_mfu": (flop_time / pred) if pred else 0.0,
        "bound": "byte" if byte_time > flop_time else "flop",
        "flop_time_s": flop_time,
        "byte_time_s": byte_time,
        "dcn_time_s": dcn_time,
        "state_bytes": int(state),
        "act_bytes": int(act),
        "traffic_bytes": int(traffic),
        "headroom_frac": verdict["headroom_frac"],
        "walker_step_peak_bytes": int(phases["step_peak"]),
    }


# --- the sweep -------------------------------------------------------------

#: The presets the default contract sweep covers — the geometries the
#: ISSUE gates (tiny is test-only: its deliberately-awkward 58-row text
#: vocab exercises _prune_spec fallbacks in tests, not the repo gate).
SWEEP_PRESETS = ("cub", "cub-512", "cub-1024")


def analyze_cell(cost: PresetCost, plan: ParallelPlan, topo: Topology, *,
                 select: Sequence[str] = ANALYSES) -> List[Finding]:
    """P2-P4 for one (preset x plan @ topology) cell (P1 is rules x
    preset, plan-independent — see :func:`analyze`).  Infeasible cells
    return no findings: infeasibility is the autotuner's concern."""
    sizes, _ = resolve_axis_sizes(plan, topo)
    if sizes is None:
        return []
    out: List[Finding] = []
    if "P2" in select:
        out.extend(check_divisibility(cost.param_shapes, plan, topo,
                                      preset=cost.preset, batch=cost.batch))
    if "P3" in select:
        out.extend(check_hbm_fit(cost, plan, topo))
    if "P4" in select and (topo.slices > 1 or plan.dcn_dp > 1):
        out.extend(check_collective_placement(plan, topo,
                                              preset=cost.preset,
                                              jaxpr=cost.jaxpr))
    return out


def _feasible_pairing(plan: ParallelPlan, topo: Topology) -> bool:
    """The analyzer's cell filter: dcn plans pair with multi-slice
    topologies (and vice versa) — the mismatched pairings are
    infeasibilities P4 would flag structurally, which the autotuner
    records as reasons rather than failures."""
    return (plan.dcn_dp > 1) == (topo.slices > 1)


def plans_for(preset: str) -> List[ParallelPlan]:
    """The contract sweep's plan set for one preset.  A scale rung is
    pinned to its own registry plan — the committed (geometry, plan)
    pairing is the contract; whether OTHER plans could hold it is the
    autotuner's question, answered in PLAN_LEDGER.json, not a repo
    defect.  The production geometries sweep the dense registry plans
    plus the whole candidate grid (dcn hybrids included, which is what
    gives P4 live cells at HEAD)."""
    from dalle_pytorch_tpu.presets import SCALE_PRESETS

    if preset in SCALE_PRESETS:
        return [PLAN_REGISTRY[preset]]
    dense = [p for p in PLAN_REGISTRY.values()
             if p.sp == 1 and p.pp == 1 and p.ep == 1]
    by_spec = {p.spec(): p for p in list(candidate_plans()) + dense}
    return [by_spec[s] for s in sorted(by_spec)]


def analyze(presets: Sequence[str] = SWEEP_PRESETS, *,
            select: Sequence[str] = ANALYSES,
            topologies: Sequence[Topology] = TOPOLOGIES,
            plans: Optional[Sequence[ParallelPlan]] = None,
            batch: int = 8) -> List[Finding]:
    """The full contract sweep: P1 per preset, P2-P4 per feasible cell
    (:func:`plans_for` x :data:`TOPOLOGIES`, capacity-infeasible cells
    skipped)."""
    findings: List[Finding] = []
    for preset in presets:
        cost = preset_cost(preset, batch)
        if "P1" in select:
            findings.extend(check_rule_coverage(cost.param_shapes,
                                                preset=preset))
        for topo in topologies:
            for plan in (plans_for(preset) if plans is None else plans):
                if not _feasible_pairing(plan, topo):
                    continue
                if batch_infeasible(plan, topo, batch) is not None:
                    continue
                findings.extend(analyze_cell(cost, plan, topo,
                                             select=select))
    return findings


def apply_waivers(findings: Iterable[Finding],
                  waivers: Sequence[Tuple[str, str, str]] = WAIVERS
                  ) -> Tuple[List[Finding], List[Tuple[Finding, str]],
                             List[str]]:
    """Split findings into (kept, waived-with-reason, unused-waiver
    errors) — the PRAGMA001/002 discipline for cell-anchored findings:
    every waiver carries a reason, and a waiver matching nothing is
    itself reported."""
    waivers = tuple(waivers)
    used = [False] * len(waivers)
    kept: List[Finding] = []
    waived: List[Tuple[Finding, str]] = []
    for f in findings:
        reason = None
        for i, (code, cell_pat, why) in enumerate(waivers):
            if f.code == code and re.search(cell_pat, f.cell):
                reason, used[i] = why, True
                break
        if reason is None:
            kept.append(f)
        else:
            waived.append((f, reason))
    unused = [f"waiver ({waivers[i][0]!r}, {waivers[i][1]!r}) matched no "
              "finding — stale suppression, remove it"
              for i, u in enumerate(used) if not u]
    return kept, waived, unused
