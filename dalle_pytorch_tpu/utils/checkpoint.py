"""Checkpoint save/load.

Format parity with the reference (`train_dalle.py:174-184`,
`train_vae.py:110-119`): a single file holding a dict with keys
``hparams`` / ``vae_params`` / ``weights`` (and, fixing the reference's gap
noted in SURVEY.md §5.3, optionally ``opt_state`` + ``step`` so training can
resume exactly).  Serialized with flax msgpack instead of torch pickles —
single-writer (process 0) semantics.
"""
from __future__ import annotations

import os
import tempfile
from pathlib import Path
from typing import Any

import jax
import numpy as np
from flax import serialization


def _to_numpy(tree):
    """Arrays -> numpy; tuples -> lists (msgpack has no tuple type — configs
    restore them via their `from_dict`, e.g. VAEConfig.normalization)."""
    if isinstance(tree, (list, tuple)):
        return [_to_numpy(v) for v in tree]
    if isinstance(tree, dict):
        return {k: _to_numpy(v) for k, v in tree.items()}
    if hasattr(tree, "shape"):
        return np.asarray(tree)
    return tree


class CheckpointCorruptError(RuntimeError):
    """A checkpoint file failed to deserialize — truncated or corrupt."""


def save_checkpoint(path: str | Path, obj: dict) -> None:
    """Atomically write `obj` (a pytree of arrays + plain python) to `path`.
    The temp file is fsynced before the rename so a crash right after the
    publish cannot leave a renamed-but-empty file behind."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    data = serialization.msgpack_serialize(_to_numpy(obj))
    fd, tmp = tempfile.mkstemp(dir=str(path.parent), prefix=path.name, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def load_checkpoint(path: str | Path) -> Any:
    """Load either checkpoint format: a msgpack file, or (when `path` is a
    directory) an Orbax sharded checkpoint — so every CLI load site accepts
    both transparently.  A file that fails to deserialize (truncated by a
    kill mid-write, or corrupt) raises :class:`CheckpointCorruptError`
    naming the file and its size instead of a bare msgpack unpack error."""
    if is_sharded_checkpoint(path):
        return load_checkpoint_sharded(path)
    with open(path, "rb") as f:
        data = f.read()
    try:
        return serialization.msgpack_restore(data)
    except Exception as e:  # msgpack raises several unpack error classes
        raise CheckpointCorruptError(
            f"checkpoint {path} is corrupt or truncated ({len(data)} bytes): "
            f"{e}.  If this run keeps managed checkpoints (a --ckpt_dir with "
            "manifests), resume with --resume auto — "
            "CheckpointManager.latest_valid() skips corrupt checkpoints and "
            "falls back to the previous good one.") from e


def is_process_zero() -> bool:
    return jax.process_index() == 0


def _replicated_sharding():
    """A concrete fully-replicated sharding over every device — the
    placement shared by the sharded save's scalar lifting and the partial
    restore, so the two can never drift apart."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    return NamedSharding(Mesh(np.asarray(jax.devices()), ("_all",)),  # graftlint: disable=PLAN001 (checkpoint IO is plan-agnostic by design: restore must work under ANY plan, so it pins an explicit fully-replicated placement on a private mesh)
                         PartitionSpec())  # graftlint: disable=PLAN001 (the replicated spec of that plan-agnostic placement)


def save_checkpoint_sharded(path: str | Path, obj: dict) -> None:
    """Orbax-backed save for sharded/multi-host training: arrays are written
    per-shard by the hosts that own them (no gather to process 0, unlike the
    msgpack path, which `host_fetch`es everything).  `obj` may mix jax
    Arrays (possibly sharded), numpy, and plain python.  Layout: an Orbax
    PyTree checkpoint directory at ``path`` (use a ``.orbax`` suffix to keep
    it distinguishable from the single-file msgpack checkpoints).
    """
    try:
        import orbax.checkpoint as ocp
    except ImportError as e:
        raise SystemExit(
            "sharded checkpoints need orbax: pip install "
            "'dalle-pytorch-tpu[sharded]'") from e

    path = Path(path).resolve()
    if jax.process_count() > 1:
        # host-local jax.Arrays (the jit-init optax count, the injected lr
        # scalar from set_learning_rate) are unserializable multi-host;
        # their values are identical on every process by construction, so
        # lift them to replicated global arrays — after CHECKING that
        # construction-time assumption: lifting divergent local buffers
        # would silently persist an arbitrary process's value
        from jax.experimental import multihost_utils

        repl = _replicated_sharding()
        local = [np.asarray(leaf) for leaf in jax.tree.leaves(obj)
                 if (isinstance(leaf, jax.Array) and leaf.is_fully_addressable
                     and len(leaf.devices()) < jax.device_count())]
        if local:
            multihost_utils.assert_equal(
                local, "host-local checkpoint leaves diverge across "
                       "processes; refusing to save an arbitrary one")

        def globalize(leaf):
            if (isinstance(leaf, jax.Array)
                    and leaf.is_fully_addressable
                    and len(leaf.devices()) < jax.device_count()):
                return multihost_utils.host_local_array_to_global_array(
                    np.asarray(leaf), repl.mesh, repl.spec)
            return leaf

        obj = jax.tree.map(globalize, obj)
    with ocp.PyTreeCheckpointer() as ckptr:
        ckptr.save(path, args=ocp.args.PyTreeSave(obj), force=True)


def _restore_with_skips(ckptr, ocp, path, item):
    """Restore ``item``, where a ``...`` leaf (``ocp.PLACEHOLDER``) means
    "skip reading this leaf": it comes back as ``...``."""
    return ckptr.restore(path, args=ocp.args.PyTreeRestore(
        item=item,
        restore_args=ocp.checkpoint_utils.construct_restore_args(item)))


def load_checkpoint_sharded(path: str | Path, target=None):
    """Restore an Orbax checkpoint directory.  With `target` (a pytree of
    jax.ShapeDtypeStruct with shardings, or arrays), arrays restore directly
    onto the target shardings — each host reads only its shards.  The CLI
    resume path does exactly this via the two-phase ``load_sharded_small``
    flow (configs first, then arrays straight onto the new run's mesh), so
    sharded resumes never materialize the full tree in host memory and work
    across topology changes."""
    import orbax.checkpoint as ocp

    path = Path(path).resolve()
    with ocp.PyTreeCheckpointer() as ckptr:
        if target is None:
            return ckptr.restore(path)
        # target leaves may be: ShapeDtypeStruct w/ sharding (restore onto
        # it), a plain value (restored by value), or the ``...`` sentinel
        # (skip this leaf entirely — it comes back as ``...``)
        return _restore_with_skips(ckptr, ocp, path, target)


def is_sharded_checkpoint(path: str | Path) -> bool:
    """Orbax checkpoints are directories; msgpack checkpoints are files."""
    return Path(path).is_dir()


def load_sharded_small(path: str | Path):
    """Phase 1 of a two-phase elastic resume: restore ONLY the non-array
    leaves of an Orbax checkpoint (hparams, scheduler scalars, epoch, ...).
    Array leaves come back as the ``...`` (Ellipsis) placeholder sentinel.

    The caller uses the restored configs to rebuild the model and compute
    this run's shardings, replaces each placeholder with a matching
    ``jax.ShapeDtypeStruct`` carrying the new sharding, and passes the tree
    to ``load_checkpoint_sharded(path, target=...)`` — arrays then restore
    straight onto the new topology with each host reading only its shards,
    never materializing the full tree in host memory.
    """
    import orbax.checkpoint as ocp

    path = Path(path).resolve()
    # 0-d leaves that were saved as (replicated) jax Arrays — optax count,
    # the injected lr — must restore onto a concrete sharding; restoring
    # them "by value" leaves the deserializer without one and fails
    repl = _replicated_sharding()
    with ocp.PyTreeCheckpointer() as ckptr:
        meta = ckptr.metadata(path).item_metadata.tree

        def to_item(node):
            if isinstance(node, dict):
                return {k: to_item(v) for k, v in node.items()}
            if isinstance(node, list):
                return [to_item(v) for v in node]
            # leaf metadata: >=1-d shapes are real arrays (skip); 0-d /
            # shapeless leaves (python scalars, strings, optax counts) are
            # cheap — restore their values.  Typed dummies, not None: a None
            # item leaf is an empty subtree to orbax and never gets restored
            shape = getattr(node, "shape", None)
            if shape:  # non-empty tuple
                return ...  # skip sentinel (ocp.PLACEHOLDER on new orbax)
            dtype = getattr(node, "dtype", None)
            if dtype is not None:
                if getattr(node, "sharding", None) is not None:
                    return jax.ShapeDtypeStruct((), dtype, sharding=repl)
                return np.zeros((), dtype)
            return ""  # string leaf

        item = to_item(meta)
        restored = _restore_with_skips(ckptr, ocp, path, item)
    # leaves restored by value come back as 0-d numpy arrays; configs
    # rebuilt from them need plain Python scalars (``[None] * depth``,
    # hashable shapes)
    return jax.tree.map(
        lambda v: v.item() if isinstance(v, np.ndarray) and v.ndim == 0
        else v, restored, is_leaf=lambda v: v is ...)


def migrate_head_kernels(tree, total_text: int):
    """In-place upgrade of legacy joint-vocab logits heads.

    Checkpoints written before the per-phase head split store
    ``to_logits_dense`` as ``{kernel: [dim, total], bias: [total]}``; the
    current layout is per-phase blocks (``text_kernel``/``image_kernel``,
    ``text_bias``/``image_bias`` — see models/dalle.py::PhaseLogits).  The
    split at ``total_text`` is an exact column partition of the old joint
    matmul, so migrated checkpoints are bit-identical.  Safe to call on
    current checkpoints (no-op).  Returns the tree.
    """
    if isinstance(tree, (list, tuple)):
        # serialized optimizer states nest param-shaped subtrees (the Adam
        # moments) inside chain lists — migrate those too
        for v in tree:
            migrate_head_kernels(v, total_text)
        return tree
    if not isinstance(tree, dict):
        return tree
    for key, val in tree.items():
        if key == "to_logits_dense" and isinstance(val, dict) \
                and "kernel" in val:
            kern = np.asarray(val.pop("kernel"))
            bias = np.asarray(val.pop("bias"))
            assert kern.shape[1] > total_text, (
                f"legacy head kernel width {kern.shape[1]} does not cover "
                f"total_text_tokens={total_text}")
            val["text_kernel"] = kern[:, :total_text]
            val["image_kernel"] = kern[:, total_text:]
            val["text_bias"] = bias[:total_text]
            val["image_bias"] = bias[total_text:]
        else:
            migrate_head_kernels(val, total_text)
    return tree


def migrate_qkv_kernels(tree, dim_head: int = 64):
    """In-place upgrade of legacy flat fused-QKV kernels.

    Checkpoints written before the DenseGeneral refactor store
    ``to_qkv/kernel`` as ``[dim, 3*heads*dim_head]``; the current layout is
    ``[dim, 3, heads, dim_head]`` (bit-compatible reshape).  Heads are
    inferred from the flat width.  Safe to call on current checkpoints
    (no-op).  Returns the tree.
    """
    if not isinstance(tree, dict):
        return tree
    for key, val in tree.items():
        if key == "to_qkv" and isinstance(val, dict):
            kern = val.get("kernel")
            if kern is not None and np.ndim(kern) == 2:
                kern = np.asarray(kern)
                width = kern.shape[1]
                assert width % (3 * dim_head) == 0, (
                    f"legacy to_qkv kernel width {width} not divisible by "
                    f"3*dim_head={3 * dim_head}")
                heads = width // (3 * dim_head)
                val["kernel"] = kern.reshape(kern.shape[0], 3, heads, dim_head)
        else:
            migrate_qkv_kernels(val, dim_head)
    return tree
