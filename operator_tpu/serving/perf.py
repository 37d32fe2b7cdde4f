"""Analytic per-token flops model + the serving engines' step clock.

The step clock (obs/steptrace.py) records *where* a decode step's wall
time goes; this module turns those records into *how fast the chip ran*:
an analytic flops-per-token model derived from the model config alone
(no device counters needed), a peak-TFLOPs table keyed by ``device_kind``,
and the :class:`StepClock` both engine loops record through.

Everything here is host-side orchestration: nothing is reachable from a
``jax.jit``/``pallas_call`` entry point, and the clock's only device
interaction is timing a sync the loop was about to perform anyway
(GL001 verifies this in CI — the narrow graftlint pass covers this
module and the instrumented loops).
"""

from __future__ import annotations

import collections
import gc
import time
from typing import Any, Callable, Optional

from ..obs.steptrace import (
    HOST_PARTS,
    StepRecord,
    StepRing,
    attribution,
    stall_over_ms,
)
from ..utils.timing import MetricsRegistry

#: dense matmul peak of ONE chip in TFLOP/s, keyed by ``device_kind`` as
#: JAX reports it and then by the dtype the MXU multiplies in.  Source:
#: Google Cloud documentation, "TPU v5e" (197 bf16 TFLOP/s per chip); the
#: kind string is what ``jax.devices()[0].device_kind`` read on the chip
#: (chip_smoke.py, PR 21).  A device that is not here has no peak, so no
#: MFU is reported for it — never another chip's number.
_PEAK_TFLOPS = {
    "TPU v5 lite": {"bfloat16": 197.0},
}

#: serving dtype -> the dtype its matmuls run in.  int8 is WEIGHT-ONLY
#: (models/quant.py ``mm`` casts to the activation dtype before every
#: matmul), so it is judged against the bf16 peak, not the int8 one.
_MATMUL_DTYPE = {"bf16": "bfloat16", "bfloat16": "bfloat16", "int8": "bfloat16"}


def matmul_param_count(config: Any) -> int:
    """Weights that participate in a matmul during one token's forward
    pass: the layer matrices the model's own family names
    (``models.family_of(config).layer_matrix_shapes``: attention
    projections and MLP, and a state-space mixer's in- and out-projection
    where the family has one), once for every pass a token takes through
    the stack (``total_ut_steps``: models/ouro.py), plus the LM head —
    which multiplies even when tied to the embedding.  Norm scales,
    convolution taps and the embedding GATHER move no matmul MACs, so
    they are excluded; ``param_count(params)`` counts them and is the
    storage number, not the compute number.  A family whose token meets
    only some of its layer weights says how many itself
    (``matmul_params_per_token``: models/sdar.py, 8 of 128 experts)."""
    from ..models import family_of

    family = family_of(config)
    if hasattr(family, "matmul_params_per_token"):
        layers = family.matmul_params_per_token(config)
    else:
        shapes = family.layer_matrix_shapes(config)
        layers = sum(n * rows * cols for n, rows, cols in shapes.values())
    passes = int(getattr(config, "total_ut_steps", 1))
    return passes * layers + config.hidden_size * config.vocab_size


def flops_per_token(config: Any, dtype: str = "bf16") -> float:
    """~2 FLOPs per matmul weight per generated token (multiply +
    accumulate; attention-score flops are negligible at serving sequence
    lengths).  ``dtype`` does not change the MAC count — it selects the
    peak (``peak_tflops``) the achieved number is divided by."""
    del dtype  # the MAC count is dtype-independent; kept for the API shape
    return 2.0 * matmul_param_count(config)


def peak_tflops(device_kind: str, dtype: str) -> Optional[float]:
    """The chip's matmul peak for a serving dtype, or None when the device
    or the dtype is not in the table ("not measured")."""
    row = _PEAK_TFLOPS.get(device_kind, {})
    return row.get(_MATMUL_DTYPE.get(str(dtype).lower(), ""))


def _drain(queue: collections.deque) -> list:
    """Everything in ``queue`` now, oldest first, taken out of it (another
    thread may go on appending: it is not waited for)."""
    return [queue.popleft() for _ in range(len(queue))]


class GcWatch:
    """The collector's pauses as two monotonic cumulatives: one
    ``gc.callbacks`` hook that adds each collection's wall to
    ``pause_ms`` and counts those of the oldest generation in ``gen2``.
    The step clock reads the two at every commit (``StepRecord.gc_ms`` /
    ``gc_gen2``) and drains the pauses into the
    ``podmortem_gc_pause_milliseconds`` histogram; ``annotate`` (the
    runtime's ``_annotation``) opens a ``podmortem.gc`` span with
    ``gen=<n>`` around each, so a traced slice shows the collection on
    the thread it ran on.  The hook runs on whichever thread allocated,
    with the GIL held and never nested: it touches no lock and no state
    but its own."""

    def __init__(self, annotate: Optional[Callable[..., Any]] = None) -> None:
        self.pause_ms = 0.0
        self.gen2 = 0
        self._annotate = annotate
        self._t0: Optional[float] = None
        self._span: Any = None
        #: pauses nobody has put into a histogram yet (bounded: an idle
        #: engine has no commit to drain them)
        self._undrained: collections.deque = collections.deque(maxlen=1024)

    def install(self) -> "GcWatch":
        if self not in gc.callbacks:
            gc.callbacks.append(self)
        return self

    def remove(self) -> None:
        if self in gc.callbacks:
            gc.callbacks.remove(self)

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
            if self._annotate is not None:
                self._span = self._annotate("podmortem.gc", gen=info["generation"])
                self._span.__enter__()
        elif self._t0 is not None:
            if self._span is not None:
                self._span.__exit__(None, None, None)
                self._span = None
            pause = (time.perf_counter() - self._t0) * 1e3
            self._t0 = None
            self.pause_ms += pause
            if info["generation"] == 2:
                self.gen2 += 1
            self._undrained.append(pause)

    def drain(self) -> list:
        """The pauses since the last drain, oldest first."""
        return _drain(self._undrained)


class StepClock:
    """Per-step recorder both serving loops write through.

    Owns the bounded :class:`StepRing` and the OPEN INTERVAL the next
    record will close: its start (the previous commit's end, or the start
    of the ``step()`` that found work after an idle spell — an idle
    engine is not host time) and the part the worker is in.  The loop
    calls :meth:`enter` / :meth:`leave` around each ``step()``,
    :meth:`begin` where it passes from one part to the next (every
    instant of the interval then belongs to exactly one part, so the
    host's parts tile ``host_ms``), and :meth:`observe` once per
    committed step, which closes the interval and opens the next.  It
    attaches the model's analytic flops/token so every record carries its
    achieved MFU, reads what else the process did in the interval (the
    worker's and the process's CPU clocks, :class:`GcWatch`, the compile
    watcher), keeps the intervals that stalled in :attr:`stalls`, and
    feeds the step histograms (``podmortem_step_duration_milliseconds``
    from ``wall_ms``, ``podmortem_step_host_gap_milliseconds`` from
    ``host_ms``).  Every method runs on the decode worker thread but
    :meth:`delivered`, which the event loop calls; reads (summary, ring)
    are lock-protected by the ring itself."""

    #: the parts an instant of the interval can belong to
    _PARTS = HOST_PARTS + ("wait", "xfer")
    #: stalls kept, and the records between two refreshes of the wall
    #: above which an interval is one
    _STALLS_KEPT = 64

    def __init__(
        self,
        *,
        capacity: Optional[int] = None,
        flops_per_token: Optional[float] = None,
        peak_tflops: Optional[float] = None,
        max_slots: int = 1,
        metrics: Optional[MetricsRegistry] = None,
    ) -> None:
        self.ring = StepRing(capacity)
        self.flops_per_token = flops_per_token
        self.peak_tflops = peak_tflops
        self.max_slots = max(1, int(max_slots))
        self.metrics = metrics
        #: the one clock every stamp is read from (seconds, monotonic);
        #: an attribute so tests can inject a fake one.  The worker
        #: thread's and the process's CPU clocks likewise
        self.now = time.perf_counter
        self.thread_cpu = time.thread_time
        self.process_cpu = time.process_time
        #: who counts the collector's pauses and the compiles (a
        #: :class:`GcWatch`, a ``utils/compilewatch.CompileWatcher``):
        #: wired by the engine that serves; None reads as nothing spent
        self.gc_watch: Optional[GcWatch] = None
        self.compile_watch: Optional[Any] = None
        #: the intervals that stalled, which ordinary steps do not evict
        self.stalls: collections.deque = collections.deque(maxlen=self._STALLS_KEPT)
        #: ages (seconds) of the snapshots the event loop took since the
        #: last commit.  The loop's thread appends, the worker's pops: a
        #: deque and no lock, because the worker waits out every
        #: microsecond the loop spends on a delivery (the GIL) and there
        #: are 128 a step
        self.lags: collections.deque = collections.deque()
        self._forget()

    # -- the open interval ------------------------------------------------
    def enter(self) -> None:
        """A ``step()`` begins: the worker is planning.  After an idle
        spell the interval starts here; while work was pending, the time
        since the last :meth:`leave` was the event loop's turn
        (``turn_ms``)."""
        if self._idle:
            self._open(self.now())
            self._part = "plan"
            self._idle = False
        else:
            self.begin("plan")

    def leave(self, busy: bool) -> None:
        """The ``step()`` returns; ``busy`` says whether it leaves work
        behind (rows, queued requests or dispatches in flight)."""
        self.begin("turn")
        self._idle = not busy

    def begin(self, part: str) -> float:
        """The worker passes into ``part``: the time since the last stamp
        belongs to the part it was in.  Returns the stamp."""
        now = self.now()
        self._parts[self._part] += (now - self.mark) * 1e3
        # the worker's CPU time inside the device wait is not its work
        if part == "wait":
            self._wait_cpu0 = self.thread_cpu()
        elif self._part == "wait":
            self._wait_cpu += self.thread_cpu() - self._wait_cpu0
        self._part, self.mark = part, now
        return now

    def woke(self, ms: float, rows: int) -> None:
        """The commit handed ``rows`` rows' tokens to the event loop in
        one call and spent ``ms`` inside it (part of ``commit``)."""
        self.wake_ms += ms
        self.wakeups += 1
        self.wake_rows += rows

    def delivered(self, committed_t: float) -> None:
        """EVENT LOOP thread: a snapshot whose commit began at
        ``committed_t`` (:attr:`mark` as the worker's hook read it)
        reached the loop."""
        self.lags.append(self.now() - committed_t)

    def elapsed_ms(self) -> float:
        """Wall of the open interval so far (0.0 with none open)."""
        return 0.0 if self._t0 is None else (self.now() - self._t0) * 1e3

    def _cumulatives(self) -> tuple:
        """The process-wide monotonic totals a record holds differences
        of: worker CPU s, process CPU s, collector ms, oldest-generation
        collections, compile s."""
        gc_watch, compiles = self.gc_watch, self.compile_watch
        return (
            self.thread_cpu(),
            self.process_cpu(),
            gc_watch.pause_ms if gc_watch is not None else 0.0,
            gc_watch.gen2 if gc_watch is not None else 0,
            compiles.compile_seconds if compiles is not None else 0.0,
        )

    def _open(self, t0: Optional[float], base: Optional[tuple] = None) -> None:
        self._t0 = t0
        #: the last stamp: where the part the worker is in began
        self.mark = t0 if t0 is not None else self.now()
        self._parts = dict.fromkeys(self._PARTS, 0.0)
        #: the open interval's hand-overs to the event loop so far
        self.wake_ms, self.wakeups, self.wake_rows = 0.0, 0, 0
        self._wait_cpu = 0.0
        self._base = base if base is not None else self._cumulatives()

    def _forget(self) -> None:
        #: start of the open interval; None right after construction or
        #: reset — the first record then starts at its own first stamp
        self._part = "plan"
        self._open(None)
        #: no work is pending: the loop left its last ``step()`` with
        #: none (or never ran), so the time until the next call is idle
        self._idle = True
        #: no interval is a stall until enough walls say what is usual
        self._stall_over_ms = float("inf")

    def observe(
        self,
        *,
        kind: str,
        tokens: int,
        slots: int,
        wait_ms: Optional[float] = None,
        xfer_ms: float = 0.0,
        **counts,
    ) -> StepRecord:
        """Close the open interval now as one step's record and open the
        next here, the worker still in the part it was in (a commit runs
        on to the next stamp).  The two waits are what the loop stamped
        through :meth:`begin`; a loop that timed a wait itself hands it
        in as ``wait_ms`` / ``xfer_ms``, and it is taken out of the part
        it ran in.  With no
        interval open (a step observed outside ``enter``/``leave`` on an
        idle clock: the wave engine's admission prefill) the record
        stands alone and its wall is the two waits.  ``counts`` are the
        record's optional work counts by name
        (``obs/steptrace._COUNT_FIELDS``).  MFU stays computed on billed
        ``tokens`` — the compute really ran — over the interval's wall."""
        commit_t = self.begin(self._part)
        parts = self._parts
        if wait_ms is None:
            wait_ms, xfer_ms = parts["wait"], parts["xfer"]
        else:
            wait_ms, xfer_ms = max(0.0, wait_ms), max(0.0, xfer_ms)
            parts[self._part] = max(0.0, parts[self._part] - wait_ms - xfer_ms)
        now = self._cumulatives()
        if self._idle:
            wall_ms = wait_ms + xfer_ms
            measured = {}
        else:
            wall_ms = max(0.0, (commit_t - self._t0) * 1e3)
            base = self._base
            measured = {f"{part}_ms": parts[part] for part in HOST_PARTS}
            measured.update(
                wake_ms=self.wake_ms,
                wakeups=self.wakeups,
                wake_rows=self.wake_rows,
                cpu_ms=max(0.0, now[0] - base[0] - self._wait_cpu) * 1e3,
                proc_cpu_ms=max(0.0, now[1] - base[1]) * 1e3,
                gc_ms=now[2] - base[2],
                gc_gen2=now[3] - base[3],
                compile_ms=(now[4] - base[4]) * 1e3,
                stall=wall_ms > self._stall_over_ms,
            )
        lags = _drain(self.lags)
        mfu = None
        if (
            self.flops_per_token
            and self.peak_tflops
            and wall_ms > 0
            and tokens
            and kind in ("decode", "mixed")
        ):
            achieved = tokens * self.flops_per_token / (wall_ms / 1e3) / 1e12
            mfu = achieved / self.peak_tflops
        record = self.ring.append(
            kind=kind,
            tokens=tokens,
            slots=slots,
            occupancy=min(1.0, slots / self.max_slots),
            wall_ms=wall_ms,
            wait_ms=wait_ms,
            xfer_ms=xfer_ms,
            mfu=mfu,
            delivered=len(lags),
            deliver_lag_ms=sum(lags) * 1e3,
            deliver_lag_max_ms=max(lags, default=0.0) * 1e3,
            **measured,
            **counts,
        )
        self._open(commit_t, base=now)
        self._idle = False  # a step just committed: the loop is not idle
        if record.stall:
            self.stalls.append(record)
        if record.seq % self._STALLS_KEPT == self._STALLS_KEPT - 1:
            self._stall_over_ms = stall_over_ms(
                [r.wall_ms for r in self.ring.records(self._STALLS_KEPT)]
            )
        metrics = self.metrics
        if metrics is not None:
            metrics.observe("step_duration_milliseconds", record.wall_ms)
            metrics.observe("step_host_gap_milliseconds", record.host_ms)
            if record.stall:
                metrics.incr("step_stall")
            if self.gc_watch is not None:
                for pause in self.gc_watch.drain():
                    metrics.observe("gc_pause_milliseconds", pause)
        return record

    @property
    def decode_cum_ms(self) -> float:
        """Monotonic cumulative decode-bearing wall (see StepRing) — the
        eviction-proof base request decode times are derived from."""
        return self.ring.decode_cum_ms

    def summary(self, last: Optional[int] = None) -> dict:
        """Stall-attribution summary (+ measured decode MFU) over the
        ring's current window — what /healthz and /fleet read — with the
        stalls kept from before the window too."""
        out = attribution(
            self.ring.records(last),
            flops_per_token=self.flops_per_token,
            peak_tflops=self.peak_tflops,
        )
        kept = list(self.stalls)
        out["stalls_kept"] = len(kept)
        out["last_stall"] = kept[-1].to_dict() if kept else None
        return out

    def reset(self) -> None:
        """Forget everything (device-state reset: the old timeline died
        with the old decode state; black-box dumps captured it first)."""
        self.ring.reset()
        self.stalls.clear()
        self.lags.clear()
        self._forget()
