"""Device mesh + sharding utilities (the GSPMD heart of the framework).

The reference scales with NCCL data parallelism only (SURVEY.md §2.2:
DeepSpeed engine allreduce, Horovod DistributedOptimizer).  TPU-natively all
of that collapses into: build a `jax.sharding.Mesh`, annotate shardings, and
let XLA insert the collectives over ICI/DCN.  This module owns:

* mesh construction with named axes ``('dp', 'fsdp', 'tp')`` — data,
  fully-sharded-data (ZeRO-ish), tensor parallel;
* regex partition rules mapping flax param paths -> `PartitionSpec` (pattern
  after dalle-mini-style partitioning, see SNIPPETS.md [1]);
* global batch construction from per-process host arrays
  (`jax.make_array_from_process_local_data`) — the analog of torch's
  ``DistributedSampler`` + ``.cuda()`` H2D step.
"""
from __future__ import annotations

import re
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# The partition rule table lives on the declarative plan (plan.py is the
# single source of the sharding contract); this name survives for the many
# existing call sites that read it from here.
from .plan import PARTITION_RULES as DEFAULT_RULES  # noqa: E402,F401


def make_mesh(dp: Optional[int] = None, fsdp: int = 1, tp: int = 1,
              devices=None, dcn_dp: int = 1, sp: int = 1, pp: int = 1,
              ep: int = 1) -> Mesh:
    """Build a ('dp','fsdp','tp') mesh.  `dp=None` absorbs remaining devices.

    ``dcn_dp > 1`` targets multi-slice topologies (TPU pods joined over the
    data-center network): the ``dp`` axis is laid out so its outer ``dcn_dp``
    groups are whole slices — data-parallel gradient ``psum``s hierarchically
    reduce inside each slice over ICI first and only the per-slice partials
    cross DCN, while fsdp/tp collectives stay entirely on ICI.  ``dp`` counts
    the *total* data-parallel ways (ICI ways x dcn_dp).

    ``sp > 1`` / ``pp > 1`` / ``ep > 1`` instead build a ('dp','sp') /
    ('dp','pp') / ('dp','ep') mesh for sequence-parallel (ring/Ulysses
    shard_map), pipeline-parallel (GPipe shard_map), or expert-parallel
    (ep-sharded MoE kernels, ops/moe.py::ep_shard_moe_params) training —
    those strategies own their inner axis, so they are mutually exclusive
    with each other and with fsdp/tp/dcn_dp in one mesh.
    """
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if sp > 1 or pp > 1 or ep > 1:
        inner_name, inner = (("sp", sp) if sp > 1 else
                             ("pp", pp) if pp > 1 else ("ep", ep))
        assert (sp > 1) + (pp > 1) + (ep > 1) == 1, (
            "sp, pp and ep are mutually exclusive")
        assert fsdp == 1 and tp == 1 and dcn_dp == 1, (
            f"{inner_name} cannot be combined with fsdp/tp/dcn_dp in one mesh")
        assert n % inner == 0, f"{n} devices not divisible by {inner_name}={inner}"
        if dp is None:
            dp = n // inner
        assert dp * inner == n, f"mesh {dp}x{inner} != {n} devices"
        return Mesh(np.asarray(devices).reshape(dp, inner), ("dp", inner_name))
    if dp is None:
        assert n % (fsdp * tp) == 0, f"{n} devices not divisible by fsdp*tp={fsdp * tp}"
        dp = n // (fsdp * tp)
    assert dp * fsdp * tp == n, f"mesh {dp}x{fsdp}x{tp} != {n} devices"
    dev_array = np.asarray(devices).reshape(dp, fsdp, tp)
    if dcn_dp > 1:
        assert dp % dcn_dp == 0, f"dp={dp} not divisible by dcn_dp={dcn_dp}"
        slice_ids = {getattr(d, "slice_index", None) for d in devices}
        if None not in slice_ids and len(slice_ids) > 1:
            from jax.experimental import mesh_utils

            # genuine multi-slice topology: let shape/topology mismatches
            # raise — silently falling back would break the slice-local ICI
            # reduction guarantee that is the whole point of dcn_dp
            dev_array = mesh_utils.create_hybrid_device_mesh(
                (dp // dcn_dp, fsdp, tp), (dcn_dp, 1, 1), devices=devices)
        # else: no slice topology (CPU meshes in tests, single slice) —
        # row-major order already groups contiguous devices on the outer dp
        # axis, which is the right fallback layout
    return Mesh(dev_array, ("dp", "fsdp", "tp"))


def _path_str(path) -> str:
    parts = []
    for p in path:
        key = getattr(p, "key", None)
        parts.append(str(key) if key is not None else str(p))
    return "/".join(parts)


def _prune_spec(spec: P, mesh: Mesh, shape) -> P:
    """Drop axes of size 1, axes absent from the mesh (sp/pp meshes carry
    no fsdp/tp), and axes that don't divide the dim — keeps rules valid on
    any mesh (e.g. pure-dp) without per-mesh rule sets."""
    out = []
    for dim, names in enumerate(spec):
        if names is None:
            out.append(None)
            continue
        names_t = (names,) if isinstance(names, str) else tuple(names)
        size = 1
        for nm in names_t:
            size *= mesh.shape.get(nm, 1)
        missing = any(nm not in mesh.shape for nm in names_t)
        if missing or size == 1 or dim >= len(shape) or shape[dim] % size != 0:
            out.append(None)
        else:
            out.append(names if isinstance(names, str) else names_t)
    return P(*out)


class Partitioner:
    """Owns the mesh + param/batch shardings for a training run.

    Built from a :class:`~dalle_pytorch_tpu.parallel.plan.ParallelPlan`
    (``plan.partitioner()`` / ``Partitioner(plan=...)``), which is the
    single source of the mesh axes and rule table — init shardings,
    checkpoint-restore templates (:meth:`opt_state_templates`), and the
    step-output pin (``training._pin_update_shardings``) all read THIS
    object, so the three former hand-kept copies cannot drift."""

    def __init__(self, mesh: Optional[Mesh] = None,
                 rules: Optional[Sequence[Tuple[str, P]]] = None,
                 batch_axes=("dp", "fsdp"), plan=None):
        if rules is None:
            rules = plan.rules if plan is not None else DEFAULT_RULES
        self.plan = plan
        if mesh is None:
            mesh = plan.make_mesh() if plan is not None else make_mesh()
        self.mesh = mesh
        self.rules = [(re.compile(pat), spec) for pat, spec in rules]
        # drop batch axes the mesh doesn't have (sp/pp meshes carry no fsdp)
        self.batch_axes = tuple(a for a in batch_axes if a in self.mesh.shape)
        self.batch_spec = P(self.batch_axes)
        self.data_sharding = NamedSharding(self.mesh, self.batch_spec)
        self.repl_sharding = NamedSharding(self.mesh, P())

    def spec_for(self, path, value) -> P:
        s = _path_str(path)
        for pat, spec in self.rules:
            if pat.match(s):
                return _prune_spec(spec, self.mesh, value.shape)
        return P()

    def param_specs(self, params):
        return jax.tree_util.tree_map_with_path(
            lambda p, v: self.spec_for(p, v), params
        )

    def param_shardings(self, params):
        return jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec),
            self.param_specs(params),
            is_leaf=lambda x: isinstance(x, P),
        )

    def shard_params(self, params):
        return jax.device_put(params, self.param_shardings(params))

    def init_opt_state(self, tx, params):
        """Fresh optimizer state with the Adam moments sharded like their
        params (the path rules match the ``mu``/``nu`` subtrees too — their
        leaf paths end in the same param names); scalar leaves (count,
        injected lr) fall through to replicated.  Without explicit
        out_shardings GSPMD is free to pick arbitrary moment layouts, which
        shows up as involuntary-rematerialization resharding in the update
        step."""
        sds = jax.eval_shape(tx.init, params)
        return jax.jit(tx.init, out_shardings=self.param_shardings(sds))(params)

    def opt_state_templates(self, opt_state) -> list:
        """Flat leaves of ``opt_state`` as ShapeDtypeStructs carrying THIS
        run's opt-state shardings — the restore targets for an elastic
        sharded-checkpoint load.  Single source of the opt-state sharding
        contract: a state restored through these lands on exactly the
        layout ``init_opt_state`` would have produced fresh."""
        return [
            jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=s)
            for t, s in zip(jax.tree.leaves(opt_state),
                            jax.tree.leaves(self.param_shardings(opt_state)))]

    def replicate(self, tree):
        return jax.device_put(tree, self.repl_sharding)

    def shard_batch(self, batch):
        """Per-process numpy batch -> globally sharded jax.Array.

        Under multi-process JAX each host holds its shard of the global
        batch (the DataLoader already gives disjoint slices).  Assembly is
        explicit per-device placement + ``make_array_from_single_device_
        arrays`` (SNIPPETS [2]): each addressable device receives exactly
        its rows of the logical global array, so a resumed run on a
        DIFFERENT topology (more hosts, a reshaped mesh) feeds the same
        global batch without any host gather.  When the addressable shards
        are not one contiguous block of rows (an exotic device order this
        framework's meshes don't produce), placement falls back to
        ``make_array_from_process_local_data``.
        """
        batch_size = 1
        for nm in self.batch_axes:
            batch_size *= self.mesh.shape[nm]

        def _shard(x):
            x = np.asarray(x)
            global_rows = x.shape[0] * jax.process_count()
            if global_rows % batch_size != 0:
                if jax.process_count() > 1:
                    # A replicated fallback would be *wrong* multi-host: each
                    # process holds different rows of what the runtime would
                    # treat as one identical replicated array.
                    raise ValueError(
                        f"global batch {global_rows} not divisible by mesh batch "
                        f"axes ({batch_size}); use drop_last=True or pad the "
                        "final batch"
                    )
                axes = None
            else:
                axes = self.batch_axes
            sharding = NamedSharding(self.mesh, P(axes, *([None] * (x.ndim - 1))))
            return self._assemble_global(x, sharding, global_rows)

        return jax.tree.map(_shard, batch)

    def _assemble_global(self, x, sharding, global_rows: int):
        """Explicit global-batch assembly: device_put each addressable
        device's row slice, then bind the buffers into one global array.
        The host's rows sit at one contiguous block of the global batch
        (this framework's meshes are row-major with processes owning
        contiguous device blocks); the block's offset is read off the
        sharding's own index map rather than assumed."""
        global_shape = (global_rows,) + x.shape[1:]
        idx_map = sharding.addressable_devices_indices_map(global_shape)

        def rows(idx):
            rsl = idx[0] if idx else slice(None)
            start = 0 if rsl.start is None else int(rsl.start)
            stop = global_shape[0] if rsl.stop is None else int(rsl.stop)
            return start, stop

        spans = {dev: rows(idx) for dev, idx in idx_map.items()}
        row0 = min(s for s, _ in spans.values())
        row1 = max(e for _, e in spans.values())
        if row1 - row0 != x.shape[0]:
            # addressable shards don't tile this host's block contiguously:
            # let jax work out the local-to-global correspondence
            return jax.make_array_from_process_local_data(sharding, x)
        buffers = [jax.device_put(x[s - row0:e - row0], dev)
                   for dev, (s, e) in spans.items()]
        return jax.make_array_from_single_device_arrays(
            global_shape, sharding, buffers)
