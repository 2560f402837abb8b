from . import compile_stats, sync_stats
from .assertions import assertion_level, kassert, kassert_heavy, set_assertion_level
from .logger import Logger, OutputLevel, log_result_line
from .platform import force_cpu_devices
from .rng import RandomState, next_key, reseed, seed_key
from .timer import Timer, scoped_timer

__all__ = [
    "assertion_level",
    "force_cpu_devices",
    "kassert",
    "kassert_heavy",
    "Logger",
    "OutputLevel",
    "log_result_line",
    "RandomState",
    "next_key",
    "reseed",
    "seed_key",
    "set_assertion_level",
    "sync_stats",
    "Timer",
    "scoped_timer",
]
