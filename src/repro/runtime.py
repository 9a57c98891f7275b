"""Ambient runtime context: the active device mesh, and the persistent
compilation cache.

Model code (notably the MoE layer, which uses an explicit ``shard_map``
collective schedule) consults :func:`get_mesh`.  Smoke tests and single-device
runs leave it unset and take the local math path — identical semantics, no
collectives.
"""
from __future__ import annotations

import contextlib
import os
import threading
from pathlib import Path
from typing import Optional

import jax

_state = threading.local()


def get_mesh() -> Optional[jax.sharding.Mesh]:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Optional[jax.sharding.Mesh]):
    prev = get_mesh()
    _state.mesh = mesh
    try:
        yield mesh
    finally:
        _state.mesh = prev


def data_axes(mesh: Optional[jax.sharding.Mesh] = None) -> tuple[str, ...]:
    """The batch/FSDP axes present in the mesh ('pod' first when multi-pod)."""
    mesh = mesh or get_mesh()
    if mesh is None:
        return ()
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


#: JAX's persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR`` is
#: not set: one fixed directory inside the checkout (the path is part of
#: what makes a later run find the entries, so it never varies per run).
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before the first
    compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and that directory is the cache; otherwise the cache is
    ``DEFAULT_COMPILE_CACHE``.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)
