"""Device and compile-cache setup shared by the entry points.

Forced host-platform device count
---------------------------------

Several entry points (the sharded-audit CLI, ``launch/dryrun.py``, the
shard_map subprocess tests) need a multi-device CPU "mesh" backed by
``--xla_force_host_platform_device_count``.  Historically each call site
wrote ``os.environ["XLA_FLAGS"] = ...`` directly, clobbering whatever flags
the caller had set.  This helper is the one place that edits the flag: it
replaces any existing ``force_host_platform_device_count`` entry while
preserving every other flag, and (optionally) verifies the backend actually
came up with enough devices.

The forcing applies to the CPU backend only: on a TPU host the mesh is
built from the real devices (:func:`cpu_requested` tells the two apart
before any backend exists).

Persistent compilation cache
----------------------------
:func:`enable_compile_cache` is called inside the entry points' ``main``
(never at import, so the CPU test suite runs with no cache).  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it; otherwise the cache lives at ``<checkout>/.jax_cache``, a fixed
path, so a later run of the same checkout finds it again.

This module must stay importable without touching jax — callers import it
*before* jax initializes its backends.
"""
from __future__ import annotations

import os

_FLAG = "--xla_force_host_platform_device_count"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def cpu_requested() -> bool:
    """True when ``JAX_PLATFORMS`` puts the CPU first — read from the
    environment, so it can be asked before any jax backend initializes."""
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()
    return first == "cpu"


def compile_cache_dir() -> str:
    """The persistent compilation cache directory: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache``."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(CHECKOUT, ".jax_cache"))


def enable_compile_cache() -> str | None:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    on an accelerator backend; returns the directory in use (None on the CPU,
    where the cache stays off — replaying cached CPU executables in one
    process has corrupted the heap on earlier jaxlibs)."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    if not jax.config.jax_compilation_cache_dir:  # JAX read the variable
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    return jax.config.jax_compilation_cache_dir


def force_host_device_count(n: int, *, verify: bool = True) -> None:
    """Force ``n`` host (CPU) devices via ``XLA_FLAGS``, preserving other flags.

    Must be called before jax initializes its backends (i.e. before the first
    device/array/jit use in the process — importing jax is fine).  With
    ``verify=True`` the backend is initialized immediately and a
    ``RuntimeError`` is raised if fewer than ``n`` devices came up, which is
    the symptom of calling this too late.

    ``n <= 1`` removes any forced count (single-device default).
    """
    n = int(n)
    parts = [f for f in os.environ.get("XLA_FLAGS", "").split() if not f.startswith(_FLAG)]
    if n > 1:
        parts.append(f"{_FLAG}={n}")
    os.environ["XLA_FLAGS"] = " ".join(parts)
    if verify and n > 1:
        import jax

        have = jax.device_count()
        if have < n:
            raise RuntimeError(
                f"requested {n} host devices but the jax backend is already "
                f"initialized with {have}; call force_host_device_count({n}) "
                "before any jax device/array/jit use in this process"
            )
