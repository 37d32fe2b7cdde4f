"""The device runtime: what one chip holds and one worker thread mutates.

Both engines stand on it and it stands on neither::

    ServingEngine ──► Scheduler (serving/sched/)  ──┐
          │                                         ├──► Runtime
          └─────────► BatchedGenerator (wave)    ──┘

A :class:`Runtime` owns the weights, the paged KV pool with its page
allocator (and a recurrent model's per-slot state pools, in the same
cache object), the slot table, the RNG key, the step clock and the
admission policy both engines share (deadline budgets, prompt
truncation) — and nothing of how a step is formed.  The continuous
scheduler is built on a ``Runtime`` and reads only names defined here;
the wave engine's ``BatchedGenerator`` (serving/engine.py) subclasses it
and adds its programs, its contiguous cache, a mesh, LoRA and guided
decoding.

Not thread-safe by design: the ServingEngine serialises all calls on one
worker; the chip itself is the serial resource.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Any, Optional

from ..models.configs import ModelConfig
from ..models.tokenizer import Tokenizer
from ..utils.timing import METRICS, MetricsRegistry
from .sampler import SAMPLE_TOP_K, sample, sample_with_confidence
from .types import PageAllocator, SamplingParams, _Slot

__all__ = ["Runtime"]


def params_dtype_name(params: Any) -> str:
    """Dtype label for the step clock's flops model and the AOT-cache
    fingerprint: int8-quantized param trees carry scale leaves, so detect
    via models.quant, else report the first leaf's dtype."""
    from ..models.quant import is_quantized

    if is_quantized(params):
        return "int8"
    try:
        import jax

        leaf = jax.tree_util.tree_leaves(params)[0]
        return str(leaf.dtype)
    except Exception:  # noqa: BLE001 - label only
        return "?"


class Runtime:
    """Device state for one chip: paged and unsharded by construction."""

    #: KV pages held for the process lifetime outside any row's grant and
    #: outside the prefix store (``Scheduler.page_accounting``
    #: ``prefix_pages``): none here; the wave engine's registered shared
    #: prefixes are the one holder
    prefix_held_pages = 0

    def __init__(
        self,
        params: Any,
        config: ModelConfig,
        tokenizer: Tokenizer,
        *,
        max_slots: int = 8,
        max_seq: Optional[int] = None,
        cache_dtype: Any = None,
        metrics: Optional[MetricsRegistry] = None,
        seed: int = 0,
        page_size: int = 64,
        kv_pages: Optional[int] = None,
        sample_top_k: Optional[int] = None,
        roofline_token_s: Optional[float] = None,
        aot_cache: Any = None,
        step_ring_capacity: Optional[int] = None,
    ) -> None:
        import jax
        import jax.numpy as jnp

        self._jax = jax
        self._jnp = jnp
        self.params = params
        self.config = config
        self.tokenizer = tokenizer
        self.max_slots = max_slots
        self.max_seq = min(max_seq or config.max_seq_len, config.max_seq_len)
        self.metrics = metrics or METRICS
        # ---- step clock (obs/steptrace.py + serving/perf.py): a bounded
        # ring of per-step wall records (host / wait / xfer) with the
        # analytic flops-per-token model for the serving dtype, so every
        # decode step carries an attributed MFU (STEP_RING_CAPACITY)
        from .perf import StepClock, flops_per_token, peak_tflops

        serving_dtype = params_dtype_name(params)
        self.step_clock = StepClock(
            capacity=step_ring_capacity,
            flops_per_token=flops_per_token(config, serving_dtype),
            peak_tflops=peak_tflops(jax.devices()[0].device_kind, serving_dtype),
            max_slots=max_slots,
            metrics=self.metrics,
        )
        # deadline budgets (deadline_policy): per-token decode estimate
        # before any step has been measured; the clock is an attribute so
        # chaos tests can inject a fake one
        self.roofline_token_s = roofline_token_s
        self._clock = time.monotonic
        #: value-aware overload ladder (router/value.py OverloadPolicy):
        #: when wired, deadline_policy degrades/sheds by value under
        #: pressure; None = pre-overload-control semantics
        self.overload_policy = None
        #: opt-in chaos seam (utils/faultinject.py): consulted per step —
        #: stalls and simulated device errors for recovery tests
        self.fault_plan = None
        self.cache_dtype = cache_dtype or jnp.bfloat16
        self.sample_top_k = sample_top_k or SAMPLE_TOP_K
        #: the sampler every program of this runtime traces
        #: (serving/sampler.py), as ``sample(logits, rng, temp, top_p)``;
        #: an attribute, so a test can put a recording fake in its place
        self.sample = functools.partial(sample, top_k=self.sample_top_k)
        #: the same draw with each token's confidence beside it: what a
        #: model that denoises blocks ranks a step's positions by
        self.sample_confident = functools.partial(
            sample_with_confidence, top_k=self.sample_top_k
        )
        #: persisted AOT executables (serving/aotcache.py): a prebuilt
        #: ``AotCache`` or None.  Every program construction site routes
        #: through ``_aot_wrap``, so a warm boot (or a supervised restart)
        #: deserializes executables instead of recompiling
        self._aot = aot_cache
        if aot_cache is not None:
            aot_cache.metrics = self.metrics

        self.page_size = page_size
        self.pages_per_seq = -(-self.max_seq // page_size)
        # default: worst case + trash page (configure kv_pages smaller to
        # oversubscribe HBM — admission then backpressures on the free
        # list instead of reserving max_seq per slot up front)
        self._kv_pages = kv_pages or (max_slots * self.pages_per_seq + 1)
        self._alloc_decode_state()
        self.slots: list[_Slot] = [_Slot() for _ in range(max_slots)]
        self._rng = jax.random.PRNGKey(seed)

    # ------------------------------------------------------------------
    # device state
    # ------------------------------------------------------------------

    def _place(self, create: Any, name: str) -> Any:
        """Allocate one piece of device state.  Here it is created where
        it is used; the wave engine, which may run on a mesh, overrides
        this to create it in its sharded layout."""
        return create()

    def _alloc_decode_state(self) -> None:
        """A fresh free list over a fresh zeroed page pool.  Used at
        construction and by :meth:`reset` — one code path, so
        post-recovery state can never diverge from fresh-start state."""
        from ..ops.paged_attention import PagedKVCache

        self.allocator = PageAllocator(self._kv_pages)
        self.paged_cache = self._place(
            lambda: PagedKVCache.create(
                self.config.kv_planes, self._kv_pages,
                self.page_size, self.config.num_kv_heads,
                self.config.head_dim, self.max_slots, self.pages_per_seq,
                dtype=self.cache_dtype,
                # a model with recurrent state keeps it in the SAME
                # cache object, so whatever donates, resets or frees
                # the pool covers it
                recurrent=PagedKVCache.recurrent_shapes(
                    self.config, self.max_slots
                ),
            ),
            "paged",
        )

    def reset(self) -> None:
        """Drop every sequence and rebuild the device decode state.

        The recovery path after a device error mid-step: donated buffers
        (the page pool) may be invalid, so a fresh zeroed pool is
        allocated, all pages freed, and every slot emptied — the WEIGHTS
        are reused (never donated, still resident).  In-flight
        generations are lost; their futures were already failed by the
        ServingEngine before it calls this.
        """
        # the step timeline died with the device state (black-box dumps
        # captured the tail first — _dump_blackbox runs before reset)
        self.step_clock.reset()
        self._alloc_decode_state()
        for i in range(self.max_slots):
            self.slots[i] = _Slot()

    def free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if not s.active]

    def _aot_wrap(self, name: str, fn: Any) -> Any:
        """Route one serving program through the AOT executable cache.

        Identity when the cache is off — every construction site stays a
        plain ``jax.jit`` callable then, so the wrapping is zero-cost in
        the default configuration."""
        if self._aot is None:
            return fn
        from .aotcache import CachedProgram

        return CachedProgram(self._aot, name, fn)

    # ------------------------------------------------------------------
    # tracing
    # ------------------------------------------------------------------

    def _annotation(
        self, name: str, params_list: Optional[list] = None, **args: Any
    ):
        """Host-side profiler marker around a region of the decode worker
        thread (``jax.profiler.TraceAnnotation``).  ``args`` (``step``,
        ``kv_pages``, ...) and the obs trace tags of the wave ride as the
        span's arguments, TraceMe-encoded (``name#step=7,trace=a|b#``):
        a reader of the xplane capture (``benchmark/trace/``,
        ``jax.profiler.ProfileData``) gets them back as ``event.stats``
        under a clean name, and the tags join the flight recorder's
        per-analysis timeline.  A TraceMe costs nanoseconds while no
        profiler session is active, so every phase of every step wears
        one."""
        tags = sorted({
            p.trace_tag for p in (params_list or [])
            if p is not None and getattr(p, "trace_tag", None)
        })
        if tags:
            # "," separates arguments, so several tags join with "|"
            args["trace"] = "|".join(tags)
        try:
            return self._jax.profiler.TraceAnnotation(name, **args)
        except Exception:  # noqa: BLE001 - profiler API unavailable: annotate nothing
            return contextlib.nullcontext()

    # ------------------------------------------------------------------
    # admission policy shared by both engines, so the two modes cannot
    # diverge on what gets admitted, clamped, or refused (with
    # ``types.prompt_budget`` / ``types.pages_needed``)
    # ------------------------------------------------------------------

    def _truncate_prompt(self, ids: list, budget: int) -> list:
        """Fit ``ids`` into ``budget`` tokens: failure evidence
        concentrates at the TAIL, so the tail is what stays."""
        if len(ids) <= budget:
            return ids
        return ids[-budget:]

    def decode_token_estimate_s(self) -> float:
        """Expected seconds per decoded token: the MEASURED p50 of the
        decode_step stage once any step has run, else the constructor's
        roofline estimate (``roofline_token_s``).  0.0 = unknown — the
        policy then only rejects already-expired requests (it will not
        clamp on a guess it doesn't have)."""
        stats = self.metrics.stage("decode_step")
        if stats.count:
            return stats.p50_ms / 1e3
        return self.roofline_token_s or 0.0

    def deadline_policy(
        self,
        params: SamplingParams,
        *,
        now: "float | None" = None,
        pressure: "float | None" = None,
    ) -> "tuple[SamplingParams, str]":
        """(possibly clamped params, outcome) for one request's budget
        (utils/deadline.py): admission is the enforcement point for the
        decode leg — the one stage whose cost is predictable up front
        (max_tokens x per-token step time).

        Outcomes: ``"ok"`` (fits, untouched), ``"truncated"``
        (``max_tokens`` clamped to the roofline fit, ``deadline_clamped``
        set so the finish reason reads "deadline"), ``"degraded"``
        (overload ladder scaled ``max_tokens`` down — degrade-before-
        reject, router/value.py), ``"shed"`` (the ladder dropped the
        request outright: lowest value under storm, class unprotected),
        ``"rejected"`` (the residue cannot fit even one token).  Requests
        without a deadline pass the deadline leg untouched but can still
        be degraded or shed under pressure.

        ``pressure`` is the caller's load signal (queued + running rows):
        when an ``overload_policy`` is wired the ladder may truncate
        analysis depth BEFORE the deadline math, so the clamp sees the
        already-reduced ask."""
        policy = self.overload_policy
        degraded = False
        if (
            policy is not None
            and pressure is not None
            and not params.degraded
        ):
            residual = None
            if params.deadline is not None:
                residual = params.deadline - (
                    self._clock() if now is None else now
                )
            value = policy.model.value(
                slo_class=params.slo_class,
                residual_s=residual,
                recall_p=params.recall_p,
            )
            verdict = policy.decide(
                value, pressure, site="admission",
                request_id=params.trace_tag or "",
            )
            if verdict.action == "shed":
                return params, "shed"
            if verdict.action == "degrade":
                params = dataclasses.replace(
                    params,
                    max_tokens=max(
                        1,
                        int(params.max_tokens * verdict.degrade_tokens_frac),
                    ),
                    degraded=True,
                )
                degraded = True
        ok = "degraded" if degraded else "ok"
        if params.deadline is None:
            return params, ok
        now = self._clock() if now is None else now
        remaining = params.deadline - now
        if remaining <= 0.0:
            return params, "rejected"
        per_token = self.decode_token_estimate_s()
        if per_token <= 0.0:
            return params, ok
        fit = int(remaining / per_token)
        if fit < 1:
            return params, "rejected"
        if fit < params.max_tokens:
            return (
                dataclasses.replace(
                    params, max_tokens=fit, deadline_clamped=True
                ),
                "truncated",
            )
        return params, ok
