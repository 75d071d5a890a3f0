"""Pallas kernels kept between processes, for the decode tick's kernels
(ops/latent_attention_pallas.py, ops/linear_attention_pallas.py).

As ops/attention_pallas.py keeps the train path's kernels: importing Pallas,
tracing a kernel's unrolled body and lowering it to Mosaic's MLIR is Python,
seconds a process (PERF.md, Findings: `setup_s` 23 -> 29 s in
`glm-4.7-flash-generate`), where the XLA executable around the kernels loads
from the compile cache.  So each call is kept beside that cache as a
``jax.export`` artefact, keyed by what it is built from; a later process
reads the bytes and binds one ``call_exported``, without importing Pallas.
"""
from __future__ import annotations

import functools
import hashlib
import importlib
import os
from pathlib import Path

import jax
from jax.extend import core as jex_core
from jax.interpreters import mlir


def _module(name: str):
    return importlib.import_module(f"{__package__}.{name}")


@functools.lru_cache(maxsize=None)
def exported(cache_dir: str, module: str, name: str, statics, avals,
             salt=()):
    """``<module>.<name>(*avals, **statics)`` as a ``jax.export.Exported`` for
    the TPU: read from ``cache_dir``, or traced, lowered and written there.
    The key holds the module's source, the versions and ``salt`` (what the
    kernel reads from elsewhere); the file is ``<prefix>-<name>-<key>``,
    ``prefix`` the module's first word."""
    from jax import export

    import jaxlib

    source = Path(__file__).with_name(f"{module}.py")
    key = hashlib.sha256(repr((
        name, statics, avals, *salt, jax.__version__, jaxlib.__version__,
        hashlib.sha256(source.read_bytes()).hexdigest())).encode()).hexdigest()
    path = (Path(cache_dir)
            / f"{module.split('_')[0]}-{name}-{key[:40]}.jaxexport")
    try:
        return export.deserialize(bytearray(path.read_bytes()))
    except OSError:
        pass
    exported = export.export(
        jax.jit(functools.partial(getattr(_module(module), name),
                                  **dict(statics))),
        platforms=("tpu",))(*[jax.ShapeDtypeStruct(*a) for a in avals])
    tmp = path.with_suffix(f".{os.getpid()}.tmp")
    try:    # whole or not at all: another process may be writing the same
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp.write_bytes(exported.serialize())
        tmp.replace(path)
    except OSError:
        pass    # a cache that cannot be written is a cache that misses
    return exported


#: A kept kernel as one opaque operation of the program around it, its
#: artefact called where the program is lowered.  Called where the program is
#: traced, ``call_exported`` marks every result of that program as committed
#: to its device (jax 0.9: ``pxla.jaxpr_transfer_mem_kinds`` counts the
#: artefact's results as memory-space transfers), and a jitted consumer that
#: was warmed on an uncommitted array, as the benchmark's VAE decode is,
#: traces again on the codes: a compile inside the timed window.
kept_kernel_p = jex_core.Primitive("kept_kernel")
kept_kernel_p.multiple_results = True
kept_kernel_p.def_abstract_eval(
    lambda *args, exported: exported.out_avals)
mlir.register_lowering(kept_kernel_p, mlir.lower_fun(
    lambda *args, exported: jax.tree.leaves(exported.call(*args)),
    multiple_results=True))


def kernel(module: str, name: str, *args, salt=(), **statics):
    """``<module>.<name>(*args, **statics)``, through the kept artefact where
    the program keeps a compile cache (the compiled kernel for the TPU; a
    test that interprets the kernel turns the cache off)."""
    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        return getattr(_module(module), name)(*args, **statics)
    found = exported(cache_dir, module, name, tuple(sorted(statics.items())),
                     tuple((a.shape, a.dtype) for a in args), salt)
    return jax.tree.unflatten(found.out_tree,
                              kept_kernel_p.bind(*args, exported=found))
