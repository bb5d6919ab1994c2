"""Resilience subsystem: fault injection, train guard, preemption,
retry, watchdog.

A production TPU stack's uptime is decided by its behavior under
faults — NaN storms, pod preemption, page exhaustion, transient
runtime errors, wedged dispatches. This package is that layer, built
around a deterministic fault-injection registry (faults.py) so every
behavior drills on CPU tier-1:

- faults:      env/context-driven injection registry + seam helpers
- TrainGuard:  in-step all-finite check, skip counters, snapshot ring,
               rollback (guard.py; compiled half in hapi/engine.py)
- preemption:  SIGTERM/SIGINT -> flag -> checkpoint-and-exit helpers
- retry:       bounded deterministic backoff for transient errors
- Watchdog:    wedged-dispatch detection (serving health())

See docs/robustness.md for the failure model and injection points.
"""
from . import faults  # noqa: F401
from . import preemption  # noqa: F401
from .faults import TransientError, inject, scenario  # noqa: F401
from .guard import TrainGuard  # noqa: F401
from .retry import (RetryStats, backoff_schedule,  # noqa: F401
                    call_with_retries, is_transient)
from .watchdog import Watchdog  # noqa: F401

__all__ = ["faults", "preemption", "TrainGuard", "Watchdog",
           "TransientError", "RetryStats", "inject", "scenario",
           "call_with_retries", "backoff_schedule", "is_transient"]

# arm any env-specified faults at first import of the subsystem — the
# SIGTERM drill rides this
faults.load_env()
