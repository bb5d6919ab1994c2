"""Dispatch watchdog — detects a wedged device call.

A wedged dispatch (hung runtime, deadlocked collective, runaway kernel)
looks identical to a slow one from the host: the execute call just
never returns. The watchdog is a daemon thread watching a heartbeat
the caller brackets around each dispatch; when an operation stays in
flight past the timeout it flips `wedged`, bumps `wedge_count`, and
fires the `on_wedge` callback exactly once per in-flight operation
(default: record only — callers decide whether to alert, shed load,
or kill the process; ServingEngine.health() surfaces the state).

It deliberately does NOT try to cancel the dispatch: there is no safe
host-side cancellation of a running XLA execute. Detection + policy
beats a fake kill.
"""
from __future__ import annotations

import contextlib
import threading
import time

__all__ = ["Watchdog"]


class Watchdog:
    def __init__(self, timeout_s=30.0, on_wedge=None, poll_s=None):
        self.timeout_s = float(timeout_s)
        self.on_wedge = on_wedge
        self.poll_s = poll_s if poll_s is not None \
            else max(self.timeout_s / 4.0, 0.005)
        self._lock = threading.Lock()
        self._inflight_op = None
        self._inflight_since = None
        self._flagged = False       # on_wedge fired for current op
        self.wedged = False         # an op is PAST timeout right now
        self.wedge_count = 0        # ops that ever exceeded the timeout
        self.last_wedge_op = None
        self.last_wedge_elapsed = 0.0
        self._stop = threading.Event()
        self._thread = None

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(target=self._loop,
                                            daemon=True,
                                            name="paddle-tpu-watchdog")
            self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2 * self.poll_s + 1.0)
            self._thread = None

    # -- heartbeat ---------------------------------------------------------
    def begin(self, op="dispatch"):
        with self._lock:
            self._inflight_op = op
            self._inflight_since = time.monotonic()
            self._flagged = False

    def end(self):
        with self._lock:
            if self._inflight_since is not None and self._flagged:
                # the op eventually returned: it WAS wedged, is no more
                self.last_wedge_elapsed = \
                    time.monotonic() - self._inflight_since
            self._inflight_op = None
            self._inflight_since = None
            self.wedged = False

    @contextlib.contextmanager
    def watch(self, op="dispatch"):
        self.begin(op)
        try:
            yield
        finally:
            self.end()

    # -- monitor -----------------------------------------------------------
    def _loop(self):
        while not self._stop.wait(self.poll_s):
            self.check()

    def check(self):
        """One poll (the thread calls this; tests may call it directly
        for determinism). Returns True when the current op is past the
        timeout."""
        cb = None
        with self._lock:
            since = self._inflight_since
            if since is None:
                return False
            elapsed = time.monotonic() - since
            if elapsed <= self.timeout_s:
                return False
            self.wedged = True
            if not self._flagged:
                self._flagged = True
                self.wedge_count += 1
                self.last_wedge_op = self._inflight_op
                self.last_wedge_elapsed = elapsed
                cb = self.on_wedge
                op = self._inflight_op
        if cb is not None:
            cb(op, elapsed)
        return True

    def health(self):
        with self._lock:
            return {"wedged": self.wedged,
                    "wedge_count": self.wedge_count,
                    "last_wedge_op": self.last_wedge_op,
                    "last_wedge_elapsed_s": round(
                        self.last_wedge_elapsed, 4),
                    "inflight_op": self._inflight_op}
