"""Process-level jax plumbing shared by the distributed substrate.

``shard_map`` / ``pcast_varying`` are the two mesh primitives the
substrate calls; the persistent compilation cache and multi-process
initialisation are set up here once per entry point.
"""

from __future__ import annotations

import os
import pathlib
import warnings

import jax
from jax.experimental.compilation_cache import compilation_cache as _cc

__all__ = [
    "shard_map",
    "pcast_varying",
    "enable_compilation_cache",
    "process_count",
    "process_index",
    "maybe_init_distributed",
]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True, **kwargs):
    """``jax.shard_map`` (``check_vma`` verifies the claimed varying axes)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma, **kwargs
    )


#: The checkout root (``src/repro/distributed/compat.py`` -> root).
_CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compilation_cache() -> pathlib.Path:
    """Turn on jax's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` places it when set (so a host can keep
    one cache across checkouts); otherwise it is ``.jax_cache/`` at the
    checkout root -- a fixed path, since the path is part of each entry's
    key.  Every entry point calls this once before it compiles, so a
    restarted process skips the compiles of unchanged programs.  The
    persistence thresholds drop to zero: the serving chunk programs are
    small and compile fast, exactly the entries the defaults would decline
    to keep.
    """
    cache_dir = pathlib.Path(
        os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_ROOT / ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # the cache backend latches its directory on the first compile of the
    # process; reset it so a process that already compiled picks this one up
    _cc.reset_cache()
    return cache_dir


def process_count() -> int:
    """Number of cooperating host processes (1 when ``jax.distributed`` is
    not initialised -- including the forced-host-device fallback, where a
    single process emulates many devices via
    ``--xla_force_host_platform_device_count``)."""
    try:
        return int(jax.process_count())
    except Exception:  # pragma: no cover - pre-init backends can raise
        return 1


def process_index() -> int:
    """This host's rank in [0, process_count())."""
    try:
        return int(jax.process_index())
    except Exception:  # pragma: no cover - pre-init backends can raise
        return 0


def maybe_init_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Initialise ``jax.distributed`` when a coordinator is configured.

    Resolution order: explicit arguments, then the standard environment
    (``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` /
    ``JAX_PROCESS_ID``).  With no coordinator configured this is a no-op
    returning False -- the caller is in single-process mode, and fleet
    fan-out falls back to this host's (possibly forced) local devices.
    Initialisation failures degrade the same way with a warning rather
    than killing the search.  Returns True when multi-process mode is up
    (idempotent: an already-initialised runtime short-circuits).
    """
    addr = coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if addr is None:
        return False
    if process_count() > 1:
        return True
    if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and "JAX_PROCESS_ID" in os.environ:
        process_id = int(os.environ["JAX_PROCESS_ID"])
    try:
        jax.distributed.initialize(
            coordinator_address=addr,
            num_processes=num_processes,
            process_id=process_id,
        )
        return True
    except Exception as e:  # pragma: no cover - depends on cluster env
        warnings.warn(
            f"jax.distributed.initialize({addr!r}) failed ({e}); continuing "
            "single-process with local devices",
            RuntimeWarning,
            stacklevel=2,
        )
        return False


def pcast_varying(x, axis_name: str):
    """Mark ``x`` as varying over ``axis_name`` (shard_map loop carries)."""
    return jax.lax.pcast(x, (axis_name,), to="varying")
