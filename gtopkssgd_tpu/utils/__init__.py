"""Support layer (reference L0: settings.py + utils.py — flags, logger,
timer dicts, log accumulators) plus what the reference lacked: structured
metrics and real checkpointing.
"""

from gtopkssgd_tpu.utils.timers import (
    StepTimer,
    TimingStats,
    time_calls,
    timed_window,
)
from gtopkssgd_tpu.utils.metrics import MetricsLogger
from gtopkssgd_tpu.utils.checkpoint import CheckpointManager
from gtopkssgd_tpu.utils.settings import (
    enable_compilation_cache,
    force_cpu_mesh,
    get_logger,
)
from gtopkssgd_tpu.utils.prefetch import Prefetcher

__all__ = [
    "StepTimer",
    "TimingStats",
    "time_calls",
    "timed_window",
    "MetricsLogger",
    "CheckpointManager",
    "get_logger",
    "enable_compilation_cache",
    "force_cpu_mesh",
    "Prefetcher",
]
