"""Global knobs + logging (reference L0: settings.py — module constants,
debug/profiling switches, logger creation).

The reference kept a module-level logger writing per-rank log files (the
launch scripts tee'd stdout per host). Here one helper builds a logger
tagged with the process index; everything else that was a settings.py
constant is an explicit dataclass/CLI flag in the trainer instead.
"""

from __future__ import annotations

import ctypes
import logging
import os
import sys

DEBUG = bool(int(os.environ.get("GTOPK_DEBUG", "0")))
# Flag-guarded per-step timing decomposition (reference profiling switch).
PROFILING = bool(int(os.environ.get("GTOPK_PROFILING", "1")))


def _default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` (gitignored). A fixed path: the directory
    is part of the cache key, so one that moves never hits."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def force_cpu_mesh(n: int = 8) -> None:
    """Make this process an n-device virtual CPU mesh: set
    ``JAX_PLATFORMS=cpu`` and the host-platform device-count flag. Any
    inherited device-count flag is REPLACED (the parent may itself have
    been forced to a different count). Must run before the jax backend
    starts; shared by tests/conftest.py and every CPU-mesh benchmark
    script."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    # jax reads JAX_PLATFORMS when it is imported; a caller that imported
    # it first (the package's own utils do) needs the config set as well.
    import jax

    jax.config.update("jax_platforms", "cpu")


# A backend compile (or its load from the persistent cache) of this many
# seconds or more is followed by a pass over the allocator's arenas.
TRIM_AFTER_COMPILE_S = 1.0
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_trimming = False


def trim_heap() -> bool:
    """Hand the heap's freed pages back to the kernel (glibc's
    ``malloc_trim``); False where the C library has none."""
    try:
        ctypes.CDLL(None).malloc_trim(0)
    except (OSError, AttributeError):
        return False
    return True


def _trim_after_compile(event, duration, **_) -> None:
    if event == _COMPILE_EVENT and duration >= TRIM_AFTER_COMPILE_S:
        trim_heap()


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache; the ONE place in the
    repo that decides where it lives. If ``JAX_COMPILATION_CACHE_DIR`` is
    set, jax reads it and no directory is set in code (whoever launched
    the process placed the cache); otherwise the directory is the fixed
    ``<checkout>/.jax_cache``. Either way every compile of 0.3 s or more
    is persisted, whatever its size. Returns the directory in use.

    It also decides what a compile leaves behind in the process: XLA's
    compiler allocates and frees gigabytes in small pieces on every core,
    and glibc keeps the freed pieces in its arenas (5.3 GiB after the
    AFMoE decoder's step, 3.6 GiB more after the next program compiled in
    the same process; a load from the cache leaves 0.6), beside a host
    that holds flat copies of N = 504M parameters. From the first call
    on, every compile of ``TRIM_AFTER_COMPILE_S`` or more, whoever in the
    process asked for it, is followed by ``trim_heap``."""
    import jax

    global _trimming
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _default_cache_dir())
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
    if not _trimming:
        jax.monitoring.register_event_duration_secs_listener(
            _trim_after_compile)
        _trimming = True
    return jax.config.jax_compilation_cache_dir


_FMT = "%(asctime)s [%(name)s:r{rank}] %(levelname)s %(message)s"


def get_logger(name: str = "gtopk", rank: int = 0,
               log_file: str | None = None) -> logging.Logger:
    logger = logging.getLogger(f"{name}.r{rank}")
    if logger.handlers:
        return logger
    logger.setLevel(logging.DEBUG if DEBUG else logging.INFO)
    fmt = logging.Formatter(_FMT.format(rank=rank), "%H:%M:%S")
    sh = logging.StreamHandler(sys.stdout)
    sh.setFormatter(fmt)
    logger.addHandler(sh)
    if log_file:
        fh = logging.FileHandler(log_file)
        fh.setFormatter(fmt)
        logger.addHandler(fh)
    logger.propagate = False
    return logger
