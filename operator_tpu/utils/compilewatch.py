"""Runtime XLA-compile observation: count and attribute every compile.

The 100/min soak showed a 5.87 s p99 against a 1.08 s p50 at 60/min —
a tail consistent with mid-run XLA compiles of program shapes (prefill
bucket x guided x prefix variants) not covered by warmup.  The reference
system has no analogue (its LLM leg is an external REST call,
AIInterfaceRestClient.java:37-39); in a compiled-serving design the
SLO-relevant discipline is instead: **every program the admission policy
can select must be compiled before readiness flips**.  This watcher makes
violations observable: it taps jax's ``jax_log_compiles`` channel and
records every "Compiling jit(NAME) ..." event with a timestamp, so a
soak/bench can assert ``midrun_compiles == 0`` after its warmup mark.
Each event also says whether the backend compile was served from JAX's
persistent compilation cache (utils/platform.py) — how a second process
of the same checkout shows that it reused the first one's programs.

Usage::

    watcher = CompileWatcher()          # installs the log tap
    ... build + warm the engine ...
    watcher.mark()                      # warmup/steady-state boundary
    ... measured window ...
    watcher.events_since_mark()   # [(t_s, name, seconds, cache_hit), ...]
"""

from __future__ import annotations

import logging
import re
import threading
import time
from typing import List, Optional, Tuple

_COMPILING = re.compile(r"Compiling\s+(\S+)\s+with global shapes")
_FINISHED = re.compile(
    r"Finished XLA compilation of\s+(\S+)\s+in\s+([0-9.]+)\s+sec"
)
_CACHE_HIT = re.compile(r"Persistent compilation cache hit for")

#: events kept (oldest dropped first): a server carries the watcher for
#: its whole life, the eager host-glue compiles never stop entirely, and
#: the whole list rides on every ``GET /healthz`` poll
_MAX_EVENTS = 256


class _TapHandler(logging.Handler):
    def __init__(self, watcher: "CompileWatcher") -> None:
        super().__init__(level=logging.DEBUG)
        self._watcher = watcher

    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:  # pragma: no cover - malformed record
            return
        m = _COMPILING.search(msg)
        if m:
            self._watcher._record_start(m.group(1))
            return
        m = _FINISHED.search(msg)
        if m:
            self._watcher._record_finish(m.group(1), float(m.group(2)))
            return
        if _CACHE_HIT.search(msg):
            self._watcher._record_cache_hit()


class CompileWatcher:
    """Tap the jax compile log and expose (timestamp, program) events.

    Thread-safe: jax may log compiles from executor threads.  Enables
    ``jax_log_compiles``, which raises the three records the tap reads
    ("Compiling", "Finished XLA compilation", "Persistent compilation
    cache hit") to WARNING, and adds one handler to the ``jax`` logger;
    the logger's level is only touched when something set it ABOVE
    WARNING, where those records would never be created.
    """

    def __init__(self) -> None:
        import jax

        self._lock = threading.Lock()
        self._t0 = time.monotonic()
        self._mark: Optional[float] = None
        # [t_monotonic, name, duration_s|None, cache_hit] - duration filled
        # by the paired "Finished" record (same name, last unfinished wins)
        self._events: List[List] = []
        self._dropped = 0
        #: seconds of XLA compilation finished so far, monotonic (never
        #: cut by the bound on ``_events``): the step clock writes its
        #: difference over a step's interval (``StepRecord.compile_ms``)
        self.compile_seconds = 0.0
        jax.config.update("jax_log_compiles", True)
        self._logger = logging.getLogger("jax")
        self._prior_level = self._logger.level
        if self._logger.getEffectiveLevel() > logging.WARNING:
            self._logger.setLevel(logging.WARNING)
        self._handler = _TapHandler(self)
        self._logger.addHandler(self._handler)

    # -- record -----------------------------------------------------------
    def _append_locked(self, event: List) -> None:
        self._events.append(event)
        if len(self._events) > _MAX_EVENTS:
            del self._events[0]
            self._dropped += 1

    def _record_start(self, name: str) -> None:
        with self._lock:
            self._append_locked([time.monotonic(), name, None, False])

    def _record_finish(self, name: str, seconds: float) -> None:
        with self._lock:
            self.compile_seconds += seconds
            for ev in reversed(self._events):
                if ev[1] == name and ev[2] is None:
                    ev[2] = seconds
                    return
            # "Finished" without a matched start (pre-install compile or
            # name drift): record it anyway so nothing is silently dropped
            self._append_locked([time.monotonic(), name, seconds, False])

    def _record_cache_hit(self) -> None:
        """jax logs the hit between a program's "Compiling" and "Finished"
        records, under the MODULE name (``jit_f`` for ``jit(f)``): credit
        the newest compile still open."""
        with self._lock:
            for ev in reversed(self._events):
                if ev[2] is None:
                    ev[3] = True
                    return

    # -- query ------------------------------------------------------------
    def mark(self) -> None:
        """Set the warmup/steady-state boundary for events_since_mark()."""
        with self._lock:
            self._mark = time.monotonic()

    def events(self) -> List[Tuple[float, str, Optional[float], bool]]:
        with self._lock:
            return [(t - self._t0, n, d, h) for t, n, d, h in self._events]

    def events_since_mark(self) -> List[Tuple[float, str, Optional[float], bool]]:
        with self._lock:
            if self._mark is None:
                return [(t - self._t0, n, d, h) for t, n, d, h in self._events]
            return [
                (t - self._mark, n, d, h)
                for t, n, d, h in self._events
                if t >= self._mark
            ]

    def count_since_mark(self) -> int:
        return len(self.events_since_mark())

    def report(self) -> dict:
        """JSON view for ``GET /healthz`` and the demo summary: every
        compile this process made, oldest first — ``count`` includes
        events the bound dropped."""
        events = self.events()
        return {
            "count": len(events) + self._dropped,
            "events": [
                {"t_s": round(t, 3), "name": n,
                 "seconds": None if d is None else round(d, 3),
                 "cache_hit": h}
                for t, n, d, h in events
            ],
        }

    def close(self) -> None:
        self._logger.removeHandler(self._handler)
        self._logger.setLevel(self._prior_level)
