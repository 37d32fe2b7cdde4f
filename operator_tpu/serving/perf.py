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

import time
from typing import Any, Optional

from ..obs.steptrace import StepRecord, StepRing, attribution
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


class StepClock:
    """Per-step recorder both serving loops write through.

    Owns the bounded :class:`StepRing` and the OPEN INTERVAL the next
    record will close: its start (the previous commit's end, or the start
    of the ``step()`` that found work after an idle spell — an idle
    engine is not host time) and the host phases stamped into it so far.
    The loop calls :meth:`enter` / :meth:`leave` around each ``step()``,
    :meth:`add` for each timed phase, and :meth:`observe` once per
    committed step, which closes the interval at the commit's end and
    opens the next.  It attaches the model's analytic flops/token so
    every record carries its achieved MFU, and feeds the step histograms
    (``podmortem_step_duration_milliseconds`` from ``wall_ms``,
    ``podmortem_step_host_gap_milliseconds`` from ``host_ms``).  All
    methods run on the decode worker thread; reads (summary, ring) are
    lock-protected by the ring itself."""

    _PARTS = ("plan", "pack", "commit", "turn")

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
        #: an attribute so tests can inject a fake one
        self.now = time.perf_counter
        self._forget()

    # -- the open interval ------------------------------------------------
    def enter(self) -> None:
        """A ``step()`` begins.  After an idle spell the interval starts
        here; while work was pending, the time since the last
        :meth:`leave` was the event loop's turn (``turn_ms``)."""
        now = self.now()
        if self._t0 is None or not self._busy:
            self._open(now)
        elif self._left_t is not None:
            self._parts["turn"] += (now - max(self._left_t, self._t0)) * 1e3
        self._left_t = None

    def leave(self, busy: bool) -> None:
        """The ``step()`` returns; ``busy`` says whether it leaves work
        behind (rows, queued requests or dispatches in flight)."""
        self._left_t = self.now()
        self._busy = bool(busy)

    def add(self, part: str, ms: float) -> None:
        """Stamp ``ms`` of a named host phase into the open interval."""
        self._parts[part] += max(0.0, ms)

    def elapsed_ms(self) -> float:
        """Wall of the open interval so far (0.0 with none open)."""
        return 0.0 if self._t0 is None else (self.now() - self._t0) * 1e3

    def _open(self, t0: Optional[float]) -> None:
        self._t0 = t0
        self._parts = dict.fromkeys(self._PARTS, 0.0)

    def _forget(self) -> None:
        #: start of the open interval; None right after construction or
        #: reset — the first record then starts at its own first stamp
        self._open(None)
        #: when the loop last left ``step()``, and whether work was still
        #: pending then (if not, the time until the next call is idle)
        self._left_t: Optional[float] = None
        self._busy = False

    def observe(
        self,
        *,
        kind: str,
        tokens: int,
        slots: int,
        wait_ms: float,
        xfer_ms: float = 0.0,
        commit_t: Optional[float] = None,
        **counts,
    ) -> StepRecord:
        """Close the open interval at ``commit_t`` (now when omitted) as
        one step's record and open the next there.  With no interval
        open (a step observed outside ``enter``/``leave`` on an idle
        clock: the wave engine's admission prefill) the record stands
        alone and its wall is the two waits.  ``counts`` are the record's
        optional work counts (``accepted``, ``cached_tokens``,
        ``prefill_tokens``, ``kv_pages_walked``, ``kv_blocks_walked``,
        ``q_tile_rows``,
        ``state_rows``, ``sampled_rows``, ``passes``).  MFU stays
        computed on billed ``tokens`` — the compute really ran — over the
        interval's wall."""
        if commit_t is None:
            commit_t = self.now()
        idle = self._t0 is None or (self._left_t is not None and not self._busy)
        if idle:
            wall_ms = max(0.0, wait_ms) + max(0.0, xfer_ms)
            self._open(commit_t)
        else:
            wall_ms = max(0.0, (commit_t - self._t0) * 1e3)
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
            **{f"{part}_ms": ms for part, ms in self._parts.items()},
            **counts,
        )
        self._open(commit_t)
        self._busy = True  # a step just committed: the loop is not idle
        if self.metrics is not None:
            self.metrics.observe("step_duration_milliseconds", record.wall_ms)
            self.metrics.observe("step_host_gap_milliseconds", record.host_ms)
        return record

    @property
    def decode_cum_ms(self) -> float:
        """Monotonic cumulative decode-bearing wall (see StepRing) — the
        eviction-proof base request decode times are derived from."""
        return self.ring.decode_cum_ms

    def summary(self, last: Optional[int] = None) -> dict:
        """Stall-attribution summary (+ measured decode MFU) over the
        ring's current window — what /healthz and /fleet read."""
        return attribution(
            self.ring.records(last),
            flops_per_token=self.flops_per_token,
            peak_tflops=self.peak_tflops,
        )

    def reset(self) -> None:
        """Forget everything (device-state reset: the old timeline died
        with the old decode state; black-box dumps captured it first)."""
        self.ring.reset()
        self._forget()
