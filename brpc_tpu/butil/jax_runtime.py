"""Process set-up for JAX: where compiled programs are cached, and which
local device an endpoint's ``#device=K`` names.

Platform choice is JAX's own: ``JAX_PLATFORMS`` in the environment (the
test suite and every smoke tool pin ``cpu``), otherwise the accelerator
the installation finds. Nothing here overrides it.

One process holds a chip at a time, so every compile of a chip run
happens in that process; the persistent compilation cache is what lets
the next process (and the next run on the same machine) skip them.
"""

from __future__ import annotations

import os
from typing import Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")
_MIN_COMPILE_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"


def ensure_compile_cache() -> Optional[str]:
    """Place JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no directory is set in code. Where it is not, the cache goes to the
    fixed ``<repo>/.jax_cache`` (the path is part of how a later run
    finds its entries, so it is never a temporary name, pid or time),
    and the variable is exported so children land in the same place.
    The one exception: a process pinned to the CPU (``JAX_PLATFORMS=cpu``,
    i.e. the tests and the smoke tools) gets no default cache and the
    answer is None — nobody waits on those compiles, and jaxlib 0.9's
    XLA:CPU loader logs a 3 KB machine-feature error for every hit.

    JAX skips programs that compiled in under a second by default; most
    of this fabric's programs (echo bodies, the toy decode step) are
    that small, so the threshold drops to zero unless the operator set
    ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``. Call before the
    first compile; idempotent."""
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        if (jax.config.jax_platforms or "").split(",") == ["cpu"]:
            return None
        path = DEFAULT_CACHE_DIR
        os.environ[CACHE_ENV] = path
        jax.config.update("jax_compilation_cache_dir", path)
    if not os.environ.get(_MIN_COMPILE_ENV):
        os.environ[_MIN_COMPILE_ENV] = "0"
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def local_device(ordinal, what: str = "endpoint"):
    """``jax.devices()[ordinal]`` (device 0 for ``None``). An ordinal
    the process does not have is an error, never device 0: a shard
    addressed at chip 3 must not quietly run on chip 0."""
    import jax

    devs = jax.devices()
    if ordinal is None:
        return devs[0]
    if not 0 <= ordinal < len(devs):
        raise ValueError(
            f"{what} names device {ordinal} but this process has "
            f"{len(devs)} {devs[0].platform} device(s)")
    return devs[ordinal]
