"""ParallelPlan — the ONE declarative source of the sharding contract.

Before this module, the mapping "mesh axes + partition rules -> shardings"
lived in three hand-kept places: ``Partitioner.param_shardings`` applied at
init, the same shardings rebuilt at checkpoint restore, and the
``training._pin_update_shardings`` constraint pinning the step outputs —
plus a fourth copy in ``tools/spmd_check.py``'s per-plan expectation table.
Each copy could drift silently (the ROADMAP "sharding-spec drift" hazard).
A :class:`ParallelPlan` replaces all of them: one frozen object holding the
mesh axis sizes and the regex rule table, from which every consumer
*derives* —

* ``plan.make_mesh()`` / ``plan.partitioner()`` build the run's mesh and
  :class:`~dalle_pytorch_tpu.parallel.mesh.Partitioner` (init shardings,
  restore templates, and the update-output pin all read the SAME
  partitioner, so they cannot disagree);
* ``plan.config_overrides()`` is the model-config half of the contract
  (``ring_axis``/``sp_impl``/``sp_size`` for the sequence-parallel plans)
  that ``tools/spmd_check.py`` and the trainers previously each spelled
  out by hand;
* ``plan.to_manifest()`` is what :class:`CheckpointManager` records in
  every checkpoint manifest, so a resume can *say* which plan + topology
  wrote the checkpoint it is resharding from (elastic resume);
* :data:`PLAN_REGISTRY` names the six canonical plans (dp / fsdp / tp /
  sp-ring / sp-ulysses / pp) the analysis suite gates — spmd_check's
  matrix is generated from this registry, not maintained beside it.

Plan specs (``ParallelPlan.parse``) are dot-separated axis tokens::

    dp            # pure data parallel, dp absorbs every device
    dp2.tp4       # 2-way data x 4-way tensor parallel
    fsdp4         # 4-way ZeRO-style parameter sharding (dp absorbs rest)
    sp-ring2      # 2-way ring sequence parallelism
    sp-ulysses2   # 2-way Ulysses (head<->sequence all-to-all)
    pp2           # 2-stage GPipe pipeline
    dcn2.fsdp2    # 2 slices over DCN x 2-way fsdp inside each

or one of the registry names above.  The partition rule table itself
(:data:`PARTITION_RULES`, the dalle-mini-style regex -> PartitionSpec map,
SNIPPETS [1]) lives here too; ``mesh.DEFAULT_RULES`` re-exports it.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional, Tuple

from jax.sharding import PartitionSpec as P

# Rules for the leaves of a ``TrunkSpec`` trunk (ops/transformer.py), which
# share no name with the DALL-E block's: they lead the table below.
TRUNK_RULES: Tuple[Tuple[str, P], ...] = (
    # multi-query attention (TrunkSpec trunks): queries [dim, heads, dh]
    # over tp like the fused kernel's heads; the few key/value heads
    # [dim, 2, kv_heads, dh] stay whole on every tp shard
    (r".*attn/to_q/kernel$", P("fsdp", "tp", None)),
    (r".*attn/to_kv/kernel$", P("fsdp", None, None, None)),
    # the head gate [dim, heads]: one column a query head, over tp with them
    (r".*attn/to_gate/kernel$", P("fsdp", "tp")),
    # latent attention: the two down-projections [dim, rank] feed norms over
    # their whole width, so they are replicated in (fsdp on the model
    # width alone); heads are independent from the up-projections on, so
    # w_qb [q_rank, heads, d], w_kvb [kv_rank, heads, d] column-parallel and
    # w_o [heads, d_v, dim] row-parallel over tp
    (r".*attn/mla/(w_qa|w_kva)$", P("fsdp", None)),
    (r".*attn/mla/(w_qb|w_kvb)$", P(None, "tp", None)),
    (r".*attn/mla/w_o$", P("tp", None, "fsdp")),
    # Mamba mixer: the d_in channels are independent through the
    # convolution and the scan, so they split over tp — in_proj [dim, 2,
    # d_in] and dt_proj [R, d_in] column-parallel, x_proj [d_in, R + 2N] and
    # out_proj [d_in, dim] row-parallel, A_log [d_in, N] with its channels
    (r".*ssm/in_proj/kernel$", P("fsdp", None, "tp")),
    (r".*ssm/dt_proj/kernel$", P(None, "tp")),
    (r".*ssm/x_proj/kernel$", P("tp", None)),
    (r".*ssm/out_proj/kernel$", P("tp", "fsdp")),
    (r".*ssm/conv_kernel$", P(None, "tp")),
    (r".*ssm/A_log$", P("tp", None)),
    # Mamba-2 mixer: in_proj's [z | x B C | dt] columns cross the head
    # and group boundaries, so both projections [dim, width] and [d_in,
    # dim] split over fsdp on the model width alone; the taps, the bias and
    # the per-head vectors stay whole
    (r".*ssd/in_proj/kernel$", P("fsdp", None)),
    (r".*ssd/out_proj/kernel$", P(None, "fsdp")),
    (r".*ssd/conv_kernel$", P(None, None)),
    # gated-delta-rule mixer: heads are independent through the
    # convolutions and the rule, so they split over tp — the four wide
    # projections [dim, heads, d] column-parallel, o_proj [heads, d_v, dim]
    # row-parallel, the two per-head projections [dim, heads], the
    # convolution taps [conv, heads, d] and the per-head vectors with them
    (r".*gdn/(q_proj|k_proj|v_proj|g_proj)/kernel$", P("fsdp", "tp", None)),
    (r".*gdn/o_proj/kernel$", P("tp", None, "fsdp")),
    (r".*gdn/(a_proj|b_proj)/kernel$", P("fsdp", "tp")),
    (r".*gdn/conv_(q|k|v)$", P(None, "tp", None)),
    (r".*gdn/(A_log|dt_bias)$", P("tp")),
    # SwiGLU: gate and up column-parallel, down row-parallel
    (r".*ff/(gate|up)/kernel$", P("fsdp", "tp")),
    (r".*ff/down/kernel$", P("tp", "fsdp")),
    # routed experts: the banks [experts, in, out] and the router whole on
    # every device (no ``ep`` plan yet: ROADMAP R1), so ``dp`` replicates
    # them like everything else
    (r".*ff/moe/(w_gate|w_up|w_down)$", P(None, None, None)),
    (r".*ff/moe/w_router$", P(None, None)),
    # the shared expert beside them is a SwiGLU: as ff/(gate|up|down)
    (r".*ff/moe/shared_(gate|up)$", P("fsdp", "tp")),
    (r".*ff/moe/shared_down$", P("tp", "fsdp")),
    # the table [vocab, dim], tied or with its separate head of the same
    # shape: as the token embeddings below
    (r".*table/embedding$", P("fsdp", "tp")),
    (r"head$", P("fsdp", "tp")),
)

# Default partition rules for our models' flax param trees.  Matched against
# the '/'-joined param path; first hit wins; default = replicated.
# Dense kernels are [d_in, d_out]; embeddings are [vocab, dim].
PARTITION_RULES: Tuple[Tuple[str, P], ...] = TRUNK_RULES + (
    # fused QKV [dim, 3, heads, dh]: fsdp on features, tp on heads
    (r".*to_qkv/kernel$", P("fsdp", None, "tp", None)),
    # column-parallel projections (split output features over tp)
    (r".*(to_q|to_k|to_v)/kernel$", P("fsdp", "tp")),
    (r".*ff/dense_in/kernel$", P("fsdp", "tp")),
    # row-parallel projections (split input features over tp)
    (r".*to_out/kernel$", P("tp", "fsdp")),
    (r".*ff/dense_out/kernel$", P("tp", "fsdp")),
    # token embeddings: vocab over fsdp (the big dim — ZeRO memory win),
    # features over tp (matches the logits head's tp-sharded vocab).  NOT
    # P("tp","fsdp"): features-over-fsdp makes the embedding-gradient
    # scatter reshard its cotangent from batch-sharded to fsdp-on-features
    # with a tile permutation GSPMD can only do by full rematerialization
    # ("Involuntary full rematerialization" per step, wasted ICI bandwidth)
    (r".*(text_emb|image_emb)/embedding$", P("fsdp", "tp")),
    # per-phase head kernels (PhaseLogits): each phase tp-shards its OWN
    # vocab dim, so the phase boundary is a param boundary — the sliced
    # head works under tp with no interior-slice resharding
    (r".*to_logits_dense/(text_kernel|image_kernel)$", P("fsdp", "tp")),
    (r".*to_logits_dense/(text_bias|image_bias)$", P("tp")),
    # conv kernels (VAE): shard output channels over fsdp only
    (r".*codebook/embedding$", P(None, "fsdp")),
    (r".*/kernel$", P(None, None)),
)

_TOKEN_RE = re.compile(
    r"^(?P<axis>dp|fsdp|tp|pp|ep|dcn|sp-ring|sp-ulysses|sp)(?P<n>\d*)$")


@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    """One parallelism plan: mesh axis sizes + the partition rule table.

    ``dp=None`` means the data axis absorbs every device the other axes
    don't claim (so one spec string serves any device count — the elastic
    half of elastic resume).  ``rules`` is the regex table the Partitioner
    compiles; it is part of the plan so a run with custom rules records
    *that* contract in its manifests too.
    """

    name: str
    dp: Optional[int] = None
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    pp: int = 1
    ep: int = 1
    dcn_dp: int = 1
    sp_impl: Optional[str] = None  # 'ring' | 'ulysses' when sp > 1
    rules: Tuple[Tuple[str, P], ...] = PARTITION_RULES

    def __post_init__(self):
        if self.sp > 1 and self.sp_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"plan {self.name!r}: sp={self.sp} needs sp_impl "
                "'ring' or 'ulysses'")
        if (self.sp > 1) + (self.pp > 1) + (self.ep > 1) > 1:
            raise ValueError(
                f"plan {self.name!r}: sp/pp/ep are mutually exclusive")
        if (self.sp > 1 or self.pp > 1 or self.ep > 1) and (
                self.fsdp > 1 or self.tp > 1 or self.dcn_dp > 1):
            raise ValueError(
                f"plan {self.name!r}: sp/pp/ep own the inner mesh axis; "
                "they cannot combine with fsdp/tp/dcn_dp")

    # --- derivation: every consumer reads these, none keeps a copy --------

    def mesh_kwargs(self) -> dict:
        """Keyword args for :func:`mesh.make_mesh` — the mesh half of the
        contract (what spmd_check's hand-kept PLANS table used to spell)."""
        out = {}
        if self.dp is not None:
            out["dp"] = self.dp
        for key in ("fsdp", "tp", "sp", "pp", "ep", "dcn_dp"):
            val = getattr(self, key)
            if val != 1:
                out[key] = val
        return out

    def make_mesh(self, devices=None):
        from .mesh import make_mesh

        return make_mesh(devices=devices, **self.mesh_kwargs())

    def partitioner(self, devices=None, mesh=None):
        """The run's Partitioner, built FROM this plan: init shardings,
        checkpoint-restore templates, and the step-output pin all derive
        from the one object returned here."""
        from .mesh import Partitioner

        return Partitioner(mesh=mesh if mesh is not None
                           else self.make_mesh(devices), plan=self)

    def config_overrides(self) -> dict:
        """The model-config (DALLEConfig) half of the plan — the execution
        strategy is per-run, never stored in checkpoints."""
        if self.sp > 1:
            return dict(ring_axis="sp", sp_impl=self.sp_impl,
                        sp_size=self.sp)
        return {}

    # --- identity / serialization -----------------------------------------

    def spec(self) -> str:
        """Canonical spec string (``ParallelPlan.parse`` round-trips it)."""
        parts = []
        if self.dp is not None:
            parts.append(f"dp{self.dp}")
        if self.dcn_dp > 1:
            parts.append(f"dcn{self.dcn_dp}")
        for key in ("fsdp", "tp", "pp", "ep"):
            if getattr(self, key) > 1:
                parts.append(f"{key}{getattr(self, key)}")
        if self.sp > 1:
            parts.append(f"sp-{self.sp_impl}{self.sp}")
        return ".".join(parts) or "dp"

    def to_manifest(self) -> dict:
        """The checkpoint-manifest record of this plan: enough for a later
        resume (possibly on different hardware) to know exactly what wrote
        the checkpoint.  Rules ride as their pattern strings — the specs
        are derivable, the identity check is what matters."""
        return {
            "name": self.name,
            "spec": self.spec(),
            "axes": {k: getattr(self, k) for k in
                     ("dp", "fsdp", "tp", "sp", "pp", "ep", "dcn_dp")},
            "sp_impl": self.sp_impl,
            "rule_patterns": [pat for pat, _ in self.rules],
        }

    @classmethod
    def from_manifest(cls, rec: dict) -> "ParallelPlan":
        """Rebuild a plan identity from a manifest record (rules fall back
        to the current table: the patterns in the record are the written
        run's identity, not restorable PartitionSpecs)."""
        axes = dict(rec.get("axes") or {})
        return cls(name=str(rec.get("name", rec.get("spec", "dp"))),
                   dp=axes.get("dp"),
                   fsdp=int(axes.get("fsdp", 1)), tp=int(axes.get("tp", 1)),
                   sp=int(axes.get("sp", 1)), pp=int(axes.get("pp", 1)),
                   ep=int(axes.get("ep", 1)),
                   dcn_dp=int(axes.get("dcn_dp", 1)),
                   sp_impl=rec.get("sp_impl"))

    @classmethod
    def parse(cls, spec: str) -> "ParallelPlan":
        """Parse a CLI plan spec: a registry name or dot-separated axis
        tokens (module docstring grammar)."""
        spec = (spec or "").strip()
        if spec in PLAN_REGISTRY:
            return PLAN_REGISTRY[spec]
        kwargs: dict = {}
        sp_impl = None
        for token in filter(None, spec.split(".")):
            m = _TOKEN_RE.match(token)
            if not m:
                raise ValueError(
                    f"bad plan token {token!r} in {spec!r}: expected "
                    "axis tokens like dp2, fsdp4, tp2, sp-ring2, pp2, dcn2 "
                    f"or a registry name ({', '.join(sorted(PLAN_REGISTRY))})")
            axis, n = m.group("axis"), m.group("n")
            size = int(n) if n else None
            if axis == "dp":
                kwargs["dp"] = size  # dp with no count = absorb
                continue
            if size is None:
                raise ValueError(
                    f"bad plan token {token!r} in {spec!r}: every axis but "
                    "dp needs an explicit way count")
            if axis.startswith("sp"):
                if axis == "sp":
                    raise ValueError(
                        f"bad plan token {token!r} in {spec!r}: sequence "
                        "parallelism must name its scheme (sp-ring2 or "
                        "sp-ulysses2)")
                sp_impl = axis.split("-", 1)[1]
                axis = "sp"
            if axis == "dcn":
                axis = "dcn_dp"
            if axis in kwargs and axis != "dp":
                raise ValueError(f"duplicate axis {axis!r} in plan {spec!r}")
            kwargs[axis] = size
        return cls(name=spec or "dp", sp_impl=sp_impl, **kwargs)

    @classmethod
    def from_mesh_flags(cls, *, fsdp: int = 1, tp: int = 1, dcn_dp: int = 1,
                        sp: int = 1, sp_impl: Optional[str] = None,
                        pp: int = 1) -> "ParallelPlan":
        """The legacy CLI surface (--mesh_fsdp/--mesh_tp/--mesh_dcn_dp/
        --mesh_sp/--pipeline_stages) expressed as a plan — so runs without
        --plan still record a faithful plan identity in their manifests."""
        plan = cls(name="flags", fsdp=int(fsdp), tp=int(tp),
                   dcn_dp=int(dcn_dp), sp=int(sp),
                   sp_impl=sp_impl if int(sp) > 1 else None, pp=int(pp))
        return dataclasses.replace(plan, name=plan.spec())


# The six canonical plans the analysis suite gates (sized for the 8-device
# virtual test mesh; dp absorbs the remainder on any larger topology).
# tools/spmd_check.py generates its per-plan matrix FROM this registry —
# a new plan here is automatically traced, or loudly missing a harness.
# Scale-preset entries (presets.SCALE_PRESETS, e.g. cub-512) pair a plan
# with a scaled config geometry; spmd_check excludes them from the
# per-push matrix and proves their S4 budget under ``--presets``.
PLAN_REGISTRY = {
    "dp": ParallelPlan("dp"),
    "fsdp": ParallelPlan("fsdp", fsdp=4),
    "tp": ParallelPlan("tp", tp=2),
    "sp-ring": ParallelPlan("sp-ring", sp=2, sp_impl="ring"),
    "sp-ulysses": ParallelPlan("sp-ulysses", sp=2, sp_impl="ulysses"),
    "pp": ParallelPlan("pp", pp=2),
    # the dim-512 scale rung: ZeRO param sharding is what makes ~345M fit
    # a 16 GiB chip at all (presets.cub512_config is the geometry half)
    "cub-512": ParallelPlan("cub-512", fsdp=4),
    # the dim-1024 MFU rung (~1.3B, presets.cub1024_config): the fsdp x tp
    # hybrid — all 8 ways go to state sharding, none to dp, and splitting
    # features over tp on top of fsdp keeps the per-device all-gather
    # working set below pure fsdp-8's (tools/plan_search.py's chip-free
    # sweep scores this cell against the alternatives, dcn variants
    # included, and PLAN_LEDGER.json pins the winner per topology)
    "cub-1024": ParallelPlan("cub-1024", fsdp=4, tp=2),
}


def resolve_plan_args(args) -> ParallelPlan:
    """Resolve the run's plan — ``--plan`` wins, else the legacy mesh
    flags — and write the resolved axis sizes back onto ``args`` so every
    downstream flag consumer (mesh construction, sp/pp mode selection,
    flag validation) reads ONE contract.  Trainers call this right after
    ``parse_args``; the returned plan is what the CheckpointManager
    records in manifests."""
    spec = getattr(args, "plan", None)
    if not spec:
        return ParallelPlan.from_mesh_flags(
            fsdp=getattr(args, "mesh_fsdp", 1),
            tp=getattr(args, "mesh_tp", 1),
            dcn_dp=getattr(args, "mesh_dcn_dp", 1),
            sp=getattr(args, "mesh_sp", 1),
            sp_impl=getattr(args, "sp_impl", None),
            pp=getattr(args, "pipeline_stages", 1))
    plan = ParallelPlan.parse(spec)
    if plan.ep > 1:
        raise ValueError("--plan with an ep axis is not supported by the "
                         "trainers (MoE expert sharding is a model-config "
                         "concern, see ops/moe.py)")
    if plan.sp > 1 and not hasattr(args, "mesh_sp"):
        raise ValueError(f"--plan {spec}: this trainer has no sequence-"
                         "parallel path")
    if plan.pp > 1 and not hasattr(args, "pipeline_stages"):
        raise ValueError(f"--plan {spec}: this trainer has no pipeline-"
                         "parallel path")
    args.mesh_fsdp, args.mesh_tp = plan.fsdp, plan.tp
    args.mesh_dcn_dp = plan.dcn_dp
    if hasattr(args, "mesh_sp"):
        args.mesh_sp = plan.sp
        if plan.sp > 1 and plan.sp_impl:
            args.sp_impl = plan.sp_impl
    if hasattr(args, "pipeline_stages"):
        args.pipeline_stages = plan.pp
    return plan


def current_topology() -> dict:
    """The topology half of a checkpoint manifest's provenance record:
    what hardware this process is actually running on right now."""
    import jax

    return {"device_count": jax.device_count(),
            "process_count": jax.process_count(),
            "platform": jax.default_backend()}


def describe_transition(written: Optional[dict], run_plan: "ParallelPlan",
                        topology: Optional[dict] = None) -> Optional[str]:
    """One operator line describing a cross-topology resume, or None when
    the checkpoint was written under this exact plan + topology (nothing
    to reshard).  ``written`` is the manifest's ``plan`` record."""
    if not written:
        return None
    topo_now = current_topology()
    same_plan = written.get("spec") == run_plan.spec()
    same_topo = (topology is None
                 or (topology.get("device_count") == topo_now["device_count"]
                     and topology.get("process_count")
                     == topo_now["process_count"]))
    if same_plan and same_topo:
        return None
    wrote = written.get("spec", "?")
    wrote_dev = (topology or {}).get("device_count", "?")
    return (f"elastic resume: checkpoint written under plan {wrote} "
            f"({wrote_dev} devices); resharding onto plan {run_plan.spec()} "
            f"({topo_now['device_count']} devices)")
