"""Continuous-batching generation engine — the ai-interface's compute, in-tree.

The reference POSTs each analysis to an external LLM service one request at
a time (reference AIInterfaceRestClient.java:37-39, 180 s read budget).
Here generation runs on the local TPU with **continuous batching**:

- **Slots**: the KV cache holds ``max_slots`` sequences; decode always runs
  the full ``[max_slots, 1]`` batch (a fixed shape XLA compiles once), with
  finished/empty slots masked.  A new request joins at the next step
  boundary instead of waiting for the batch to drain.
- **Batched prefill**: concurrent arrivals are tokenised, right-padded to a
  shared bucket and prefilled as ONE forward pass (BASELINE config 4: 32
  concurrent failure events -> one prefill).  Prompt shapes are bucketed to
  powers of two so XLA compiles a handful of prefill programs, not one per
  request.
- **Ragged positions**: every slot decodes at its own offset; the model's
  cache update takes a per-sequence offset vector (models/llama.py).
- **Per-slot sampling params**: temperature / top-p ride in ``[B]`` arrays,
  so requests with different AIProvider configs share one batch.

Layers: :class:`serving.runtime.Runtime` is the device state one chip
holds (weights, page pool and allocator, slot table, RNG, step clock,
the shared admission policy) and owns no way of forming a step;
:class:`BatchedGenerator` is the WAVE engine on top of it, the
synchronous JAX core (jitted prefill / decode-step programs);
:class:`ServingEngine` is the asyncio front the operator talks to
(queue, admission, futures), over either the wave engine or the
continuous scheduler (serving/sched/), which stands on a bare
``Runtime`` and on nothing in this module.  The split keeps the JAX
code testable without an event loop.

Module layout: program construction lives in :mod:`.programs`
(ProgramBuilderMixin — every jitted XLA program of the wave engine),
wave admission in :mod:`.admission` (AdmissionMixin — wave formation,
head-and-tail truncation, prefix decision, page grants, warmup grid),
the sampler in :mod:`.sampler`, shared dataclasses in :mod:`.types`.
This module keeps the wave engine's STATE and loops: its contiguous
cache and per-slot vectors, decode stepping + pipelining,
guided-automaton registry, registered prefixes, chunked-prefill job
advancement, and the async engine.

Grown-in serving subsystems (each opt-in or zero-cost when unused):
multi-step decode blocks + decode-ahead pipelining; sharded TP/DP serving
over a mesh; multi-LoRA (per-slot adapters stacked into one program);
guided decoding (choice/regex automata as scan-carried device state);
Sarathi-style chunked prefill (``prefill_chunk``); priority admission
(pipeline explanations outrank external API callers); bounded
auto-recovery after device errors (:meth:`ServingEngine._try_recover`);
and slot/page reclamation for cancelled callers.
"""

from __future__ import annotations

import asyncio
import itertools
import logging
import threading
import time
from dataclasses import dataclass
from typing import Any, Optional, Sequence

import numpy as np

from ..models.configs import ModelConfig
from ..models.llama import KVCache, forward
from ..models.tokenizer import Tokenizer
from ..obs import span as obs_span
from ..utils.timing import METRICS, MetricsRegistry
from .admission import AdmissionMixin
from .programs import ProgramBuilderMixin
from .runtime import Runtime, params_dtype_name

# re-exported types: the public import surface predates the round-5 module
# split (every consumer does `from operator_tpu.serving.engine import ...`)
from .types import (  # noqa: F401
    DeadlineExceeded,
    GenerationResult,
    OversizedRequest,
    PageAllocator,
    SamplingParams,
    _bucket,
    _PrefillJob,
    _Slot,
    check_denoise,
)

log = logging.getLogger(__name__)


class EngineStalled(RuntimeError):
    """The decode loop made no step progress within the supervisor's stall
    budget — the device (or its runtime) is wedged, not merely slow."""


@dataclass
class SupervisorPolicy:
    """Watchdog policy for the serving engine (docs/ROBUSTNESS.md).

    With a policy installed, a decode step exceeding ``stall_timeout_s`` —
    or a serve-loop death — triggers a supervised restart: the engine
    resets its device state, audits slot/page leaks, dumps a black-box
    flight-recorder record, and requeues in-flight requests up to
    ``max_requeues`` times with their residual deadlines (the deadline is
    an absolute instant, so queue time already spent stays spent).
    Without one (the default), the engine keeps the pre-supervisor
    semantics: loop death fails every in-flight future and recovery is
    lazy (``_try_recover`` on the next generate).
    """

    #: a step may legitimately hide a multi-second in-band XLA compile
    #: (novel bucket): only a genuinely wedged device should trip this.
    #: Must match OperatorConfig.supervisor_stall_s (the config-driven
    #: production default) so direct constructions behave identically
    stall_timeout_s: float = 120.0
    #: how long to wait for an abandoned (stalled) decode thread to return
    #: before resetting device state under it anyway
    join_grace_s: float = 10.0
    #: each request is re-admitted at most this many times; beyond it the
    #: supervisor gives up and fails the caller
    max_requeues: int = 1


@dataclass
class _Request:
    """One queued/admitted generation request — kept whole (prompt +
    params + priority) so the supervisor can re-admit it after an engine
    restart; the bare future the queue used to carry cannot be requeued."""

    prompt: str
    params: "SamplingParams"
    future: asyncio.Future
    priority: int = 0
    requeues: int = 0
    #: token-level streaming resume (router/resume.py): generated token
    #: ids to re-prefill VERBATIM after the prompt on a failover
    #: survivor; the result then carries only the continuation
    resume_tokens: Optional[list] = None
    #: perf_counter at submit (ServingEngine.generate) — queue wait is
    #: measured admission-minus-submit, not inferred from wall deltas
    submitted: float = 0.0
    queue_wait_ms: float = 0.0


class BatchedGenerator(AdmissionMixin, ProgramBuilderMixin, Runtime):
    """The wave engine: slot-based generation over one shared KV cache
    (single host thread), on the device state of a :class:`Runtime`.

    Not thread-safe by design: the ServingEngine serialises all calls on
    one worker; the TPU itself is the serial resource.
    """

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
        paged: bool = False,
        page_size: int = 64,
        kv_pages: Optional[int] = None,
        mesh: Any = None,
        decode_block: int = 1,
        sample_top_k: Optional[int] = None,
        pipeline_depth: int = 1,
        lora_adapters: Optional[dict[str, Any]] = None,
        lora_alpha: float = 16.0,
        prefill_chunk: Optional[int] = None,
        roofline_token_s: Optional[float] = None,
        aot_cache: Any = None,
        step_ring_capacity: Optional[int] = None,
    ) -> None:
        import jax

        # ---- what the runtime's allocation reads of the wave engine: the
        # cache layout (paged pool or contiguous rows) and the mesh
        self.paged = paged
        self.cache = None
        # ---- sharded serving (BASELINE configs 3/5): params TP on heads /
        # MLP columns, slots DP over the batch axis; one jitted program per
        # mesh — XLA inserts the tp psums and dp scatter collectives
        self.mesh = mesh
        if mesh is not None:
            from ..models.quant import is_quantized

            self._init_shardings(
                mesh, config, max_slots, quantized=is_quantized(params)
            )
            params = jax.tree_util.tree_map(
                jax.device_put, params, self._param_shardings
            )
        else:
            self._shardings = None

        # ---- multi-LoRA serving: adapters stacked [n_layers, n_adapters+1,
        # ...] with the all-zeros base at index 0; every request picks its
        # adapter per slot inside ONE compiled program (models/llama.py
        # _lora_path).  Passed as ARGUMENTS to the jitted fns — closure
        # capture would embed tens of MB as program constants.
        self.lora_alpha = lora_alpha
        if lora_adapters:
            from ..parallel.lora import stack_adapters, zero_lora

            names = sorted(lora_adapters)
            first = lora_adapters[names[0]]
            first_a = first[next(iter(first))]["a"]
            zero = zero_lora(
                config, rank=first_a.shape[-1], targets=tuple(first),
                dtype=first_a.dtype,
            )
            self.lora = stack_adapters([zero] + [lora_adapters[n] for n in names])
            self._adapter_ids: dict[Optional[str], int] = {
                None: 0, **{n: i + 1 for i, n in enumerate(names)}
            }
        else:
            self.lora = None
            self._adapter_ids = {None: 0}

        # ``aot_cache`` is a prebuilt AotCache (provider overlap path), a
        # directory path (this generator fingerprints its own cache), or
        # None = off
        if aot_cache is not None:
            from .aotcache import AotCache, generator_fingerprint

            if not isinstance(aot_cache, AotCache):
                try:
                    aot_cache = AotCache(
                        str(aot_cache),
                        generator_fingerprint(
                            config=config,
                            weight_dtype=params_dtype_name(params),
                            max_slots=max_slots,
                            max_seq=max_seq,
                            cache_dtype=cache_dtype,
                            paged=paged,
                            page_size=page_size,
                            kv_pages=kv_pages,
                            mesh=mesh,
                            decode_block=decode_block,
                            sample_top_k=sample_top_k,
                            pipeline_depth=pipeline_depth,
                            prefill_chunk=prefill_chunk,
                            lora_names=[n for n in self._adapter_ids if n],
                        ),
                        metrics=metrics or METRICS,
                    )
                except Exception:  # noqa: BLE001 - cache is an optimisation only
                    log.warning(
                        "AOT executable cache disabled: fingerprint "
                        "construction failed", exc_info=True,
                    )
                    aot_cache = None

        # ---- shared-prefix KV cache (add_shared_prefix): each registered
        # prompt prefix is prefilled ONCE into generator-owned pages;
        # admitted prompts that start with one reference those pages
        # read-only and prefill only their suffix.  Registry entries:
        # {"text", "tokens", "pages"} in registration order (the default
        # template first, then custom AIProvider promptTemplates).
        # Initialised unconditionally: reset() and the compat properties
        # read it in contiguous (non-paged) mode too, where it stays empty
        self._prefixes: list[dict] = []
        self._prefix_fns: dict[tuple, Any] = {}  # (n_pad, t_sfx, shared, guided)

        super().__init__(
            params, config, tokenizer,
            max_slots=max_slots, max_seq=max_seq, cache_dtype=cache_dtype,
            metrics=metrics, seed=seed, page_size=page_size,
            kv_pages=kv_pages, sample_top_k=sample_top_k,
            roofline_token_s=roofline_token_s, aot_cache=aot_cache,
            step_ring_capacity=step_ring_capacity,
        )
        # decode in blocks of K steps per host round-trip (lax.scan): one
        # dispatch + one token fetch per K tokens hides host latency for
        # K-1 of every K steps.  Finished slots may decode up to K-1 junk
        # tokens into their OWN cache rows/pages before the host notices —
        # harmless by the same argument that lets inactive slots keep
        # decoding garbage.  Trade-off: admissions join at block boundaries
        # (adds up to K-1 steps of queueing to p50, microseconds-to-ms).
        assert decode_block >= 1
        self.decode_block = decode_block
        # decode-ahead: blocks in flight before the host fetches tokens
        # (see step()); 1 = synchronous, 2 = one block of lookahead
        assert pipeline_depth >= 1
        if pipeline_depth * decode_block * 2 > self.max_seq:
            raise ValueError(
                f"pipeline_depth*decode_block={pipeline_depth * decode_block} "
                f"reserves more than half of max_seq={self.max_seq} as the "
                f"stop margin — generations would truncate immediately"
            )
        self.pipeline_depth = pipeline_depth
        #: optional ``hook([(slot_id, token_ids_so_far), ...])`` called
        #: once a processed block with every slot still generating that
        #: grew in it — the streaming feed (ServingEngine marshals it onto
        #: the event loop).  Called from the decode worker thread; must
        #: not block; never with an empty list.
        self.partial_hook: Optional[Any] = None
        self._inflight_blocks: list[tuple[Any, dict]] = []

        # ---- chunked prefill (Sarathi-style interleaving): a long prompt
        # is prefilled ``prefill_chunk`` tokens per engine round instead of
        # one shot, so in-flight decodes stall for at most one chunk's wall
        # time per round rather than the whole prompt's.  One job at a time;
        # its slots are RESERVED (not yet decoding) until the finish step
        # scatters the mini cache and samples the first token.  None = off.
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk={prefill_chunk} must be >= 1")
        self.prefill_chunk = prefill_chunk
        self._prefill_job: Optional[_PrefillJob] = None
        self._reserved: set[int] = set()
        self._chunk_fns: dict[tuple[int, int, int], Any] = {}
        self._finish_fns: dict[tuple, Any] = {}  # (n_pad, t_pad, guided)

        # ---- guided decoding (serving/guided.py): automaton tables stacked
        # [A_pad, S_pad, vocab] on device, per-slot (automaton, state)
        # vectors carried through the decode scan.  None = no guided slot
        # active; the unguided programs keep compiling/running untouched.
        self._guided_cache: dict[tuple, Any] = {}   # choices -> ChoiceAutomaton
        # submit-time validation mutates the cache from the HTTP event-loop
        # thread while the serve loop's executor thread reads it; the lock
        # guards bookkeeping only (builds run unlocked), and
        # _guided_protect shields an in-flight refresh wave from
        # submit-thread eviction
        self._guided_lock = threading.Lock()
        self._guided_protect: frozenset = frozenset()
        self._guided_tables = None                  # device stack, or None
        self._guided_index: dict[tuple, int] = {}   # choices -> stacked idx
        self._guided_aut_np = np.zeros((max_slots,), np.int32)
        self.guided_aut = None                      # device [B] automaton ids
        self.guided_state = None                    # device [B] DFA states
        self._decode_fn_guided = None

        # ---- the decode block, one jitted program per engine; every
        # construction site routes through _aot_wrap
        body = self._decode_block_paged if paged else self._decode_block
        if mesh is not None:
            s = self._shardings
            from jax.sharding import NamedSharding, PartitionSpec as P

            block_tokens = NamedSharding(mesh, P(None, ("dp", "fsdp")))
            if paged:
                in_shardings = (
                    self._param_shardings, s["paged"], s["tokens"],
                    s["repl"], s["batch"], s["batch"], s["batch"],
                    s["repl"], s["batch"],  # stacked lora (small), idx
                )
                out_shardings = (s["paged"], block_tokens, s["tokens"], s["repl"])
            else:
                in_shardings = (
                    self._param_shardings, s["cache"], s["tokens"],
                    s["batch"], s["repl"], s["batch"], s["batch"], s["batch"],
                    s["repl"], s["batch"],  # stacked lora (small), idx
                )
                out_shardings = (
                    s["cache"], block_tokens, s["tokens"], s["batch"], s["repl"]
                )
            decode = jax.jit(
                body, in_shardings=in_shardings, out_shardings=out_shardings,
                donate_argnums=(1,),  # cache / page pool: update in place, no copy
            )
        else:
            decode = jax.jit(body, donate_argnums=(1,))
        self._decode_fn = self._aot_wrap("decode", decode)
        # per-slot generation counter: an in-flight decode block carries the
        # epoch it was dispatched under, so tokens from a block dispatched
        # before a slot was recycled are never credited to the new sequence
        self._slot_epoch = [0] * max_slots
        # host shadow of per-slot token counts (BOTH cache layouts): the
        # decode loop must never fetch offsets from the device — at the 8B
        # target the per-step host budget is ~10ms and a blocking read eats it
        self._host_offsets = np.zeros((max_slots,), np.int64)
        # per-slot sampling tensors change only at admit/finish; cache the
        # device copies so steady-state decode transfers nothing but tokens
        self._sampling_cache: Optional[tuple] = None

        self._prefill_fns: dict[tuple, Any] = {}  # (n_pad, t_pad, guided)

    def _init_shardings(
        self, mesh: Any, config: ModelConfig, max_slots: int, *,
        quantized: bool = False,
    ) -> None:
        """Validate the mesh against the model and build the sharding table."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.mesh import kv_cache_spec, paged_cache_specs, param_shardings

        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        tp = sizes.get("tp", 1)
        dp_total = sizes.get("dp", 1) * sizes.get("fsdp", 1)
        if config.num_kv_heads % tp or config.num_heads % tp:
            raise ValueError(
                f"tp={tp} must divide kv_heads={config.num_kv_heads} "
                f"and heads={config.num_heads}"
            )
        if max_slots % dp_total:
            raise ValueError(
                f"max_slots={max_slots} must be a multiple of "
                f"dp*fsdp={dp_total} (slots shard over the data axes)"
            )
        self._dp_total = dp_total

        def ns(spec):
            return NamedSharding(mesh, spec)

        self._param_shardings = param_shardings(mesh, config, quantized=quantized)
        self._shardings = {
            "repl": ns(P()),
            "batch": ns(P(("dp", "fsdp"))),          # [B] per-slot vectors
            "tokens": ns(P(("dp", "fsdp"), None)),   # [B, 1] decode tokens
            "cache": KVCache(k=ns(kv_cache_spec()), v=ns(kv_cache_spec())),
            "paged": jax.tree_util.tree_map(
                ns, paged_cache_specs(), is_leaf=lambda x: isinstance(x, P)
            ),
        }

    def _put_batch_vec(self, array):
        """Place a per-slot [B] vector: batch sharding under a mesh (one
        host->mesh transfer), plain device array otherwise.  The one
        placement helper for guided aut/state AND the sampling tensors."""
        if self.mesh is not None:
            return self._jax.device_put(array, self._shardings["batch"])
        return self._jnp.asarray(array)

    def _guided_row_aut(self, specs: list, n_pad: int):
        """[n_pad] automaton ids for a wave's rows (padding rows duplicate
        row 0); id 0 = identity for unguided rows."""
        row_aut = np.zeros((n_pad,), np.int32)
        for row, spec in enumerate(specs):
            row_aut[row] = self._guided_index.get(spec, 0)
        for row in range(len(specs), n_pad):
            row_aut[row] = row_aut[0]
        return row_aut

    def _apply_guided_activation(self, row_aut, taken, first_state) -> None:
        """Post-activation guided bookkeeping, shared by the one-shot and
        chunked paths: bind each slot's automaton id (0/identity for
        unguided slots — this RESET matters: a recycled slot may carry a
        stale accept-state from a previous guided occupant) and scatter the
        first DFA states."""
        jnp = self._jnp
        for row, slot_id in enumerate(taken):
            self._guided_aut_np[slot_id] = row_aut[row]
        self.guided_aut = self._put_batch_vec(self._guided_aut_np)
        self.guided_state = self._put_batch_vec(
            self.guided_state.at[
                jnp.asarray(np.asarray(taken, np.int32))
            ].set(first_state[: len(taken)])
        )

    # ------------------------------------------------------------------
    # guided decoding registry (serving/guided.py)
    # ------------------------------------------------------------------

    #: automaton-state cap: bounds the [A_pad, S_pad, vocab] table (int32)
    #: the guided programs carry; matches _refresh_guided_tables' s_pad
    #: clamp so an oversized request is rejected at SUBMIT time, never at
    #: admission
    MAX_GUIDED_STATES = 1 << 14

    @staticmethod
    def _guided_spec(params: "SamplingParams | None") -> Optional[tuple]:
        """The hashable automaton key for a request: ("choice", names) or
        ("regex", pattern); None = unconstrained."""
        if params is None:
            return None
        if params.guided_choice is not None:
            return ("choice", tuple(params.guided_choice))
        if params.guided_regex is not None:
            return ("regex", str(params.guided_regex))
        return None

    def _automaton_cached(self, spec: tuple) -> bool:
        """Lock-guarded cache probe (with the LRU touch) so async submit
        paths can skip the executor hop for already-built specs."""
        with self._guided_lock:
            if spec in self._guided_cache:
                self._guided_cache[spec] = self._guided_cache.pop(spec)
                return True
        return False

    def _ensure_automaton(self, spec: tuple) -> None:
        """Build (and cache) the automaton for a guided spec; raises
        ValueError on anything unservable — called at SUBMIT time so a bad
        request can never fail a co-batched wave.

        Eviction never touches specs in ``_guided_protect`` (the full set a
        ``_refresh_guided_tables`` pass is about to index) — without that
        window, a pass ensuring >cap distinct specs could evict one it
        ensured moments earlier and KeyError inside the serve loop.

        Thread safety: submit-time validation runs on the HTTP event-loop
        thread while the serve loop's executor thread refreshes the
        stacked tables.  Cache bookkeeping (touch/evict/insert) holds
        ``_guided_lock`` — the LRU touch is a pop-then-reinsert which,
        unlocked, opens a transient-absence window for exactly the
        KeyError the protection exists to prevent.  The automaton BUILD
        runs outside the lock: DFA compilation can take seconds, and
        holding the lock through it would stall the decode loop from the
        event-loop thread (or all HTTP traffic from the executor)."""
        if self._automaton_cached(spec):
            return
        kind, payload = spec
        if kind == "choice":
            from .guided import build_choice_automaton

            automaton = build_choice_automaton(
                payload, self.tokenizer, self.config.vocab_size
            )
        else:
            from .regex_dfa import compile_regex_automaton

            automaton = compile_regex_automaton(
                payload, self.tokenizer, self.config.vocab_size,
                max_states=self.MAX_GUIDED_STATES,
            )
        if automaton.num_states > self.MAX_GUIDED_STATES:
            raise ValueError(
                f"guided automaton needs {automaton.num_states} states, "
                f"above the {self.MAX_GUIDED_STATES} cap — simplify the "
                f"choices/pattern"
            )
        with self._guided_lock:
            if spec in self._guided_cache:  # raced another builder: theirs won
                self._guided_cache[spec] = self._guided_cache.pop(spec)
                return
            # bound host memory (LRU), but never evict a spec bound to an
            # ACTIVE slot, indexed in the current stacked tables, or in the
            # refresh pass currently in flight (_guided_protect) — the
            # serve loop indexes the cache directly for those
            live = {
                self._guided_spec(slot.params)
                for slot in self.slots
                if slot.active
            }
            live.update(self._guided_index)
            live.update(self._guided_protect)
            live.discard(None)
            evictable = [k for k in self._guided_cache if k not in live]
            while len(self._guided_cache) >= 32 and evictable:
                self._guided_cache.pop(evictable.pop(0))
            self._guided_cache[spec] = automaton

    def _refresh_guided_tables(self, wave_specs: "list[tuple | None]") -> None:
        """(Re)stack the automata needed by active + newly admitted guided
        slots; None when no guided slot remains (fast unguided path)."""
        from .guided import identity_automaton, stack_automata

        jnp = self._jnp
        specs = {
            self._guided_spec(slot.params)
            for slot in self.slots
            if slot.active and self._guided_spec(slot.params)
        }
        specs.update(spec for spec in wave_specs if spec)
        if not specs:
            self._guided_tables = None
            self._guided_index = {}
            self.guided_aut = None
            self.guided_state = None
            return
        # advertise the wave to submit-thread evictions BEFORE ensuring:
        # without the protect window, an eviction between this pass's
        # ensure loop and the locked cache reads below could drop a wave
        # spec before it lands in _guided_index.  Builds themselves run
        # unlocked (inside _ensure_automaton), so a slow DFA compile here
        # never blocks HTTP submits.
        with self._guided_lock:
            self._guided_protect = frozenset(specs)
        try:
            for spec in specs:
                self._ensure_automaton(spec)
            with self._guided_lock:
                ordered = sorted(specs)
                new_index = {spec: i + 1 for i, spec in enumerate(ordered)}
                if self._guided_tables is not None and new_index == self._guided_index:
                    return  # byte-identical stack: skip the rebuild + upload
                automata = [identity_automaton(self.config.vocab_size)]
                automata += [self._guided_cache[spec] for spec in ordered]
                self._guided_index = new_index
        finally:
            # _guided_index now carries the wave (or we raised); either way
            # the explicit protect window is over
            with self._guided_lock:
                self._guided_protect = frozenset()
        a_pad = _bucket(len(automata), 2, 64)
        s_pad = _bucket(
            max(a.num_states for a in automata), 8, self.MAX_GUIDED_STATES
        )
        while len(automata) < a_pad:
            automata.append(identity_automaton(self.config.vocab_size))
        stacked = stack_automata(automata, self.config.vocab_size, state_pad=s_pad)
        if self.mesh is not None:
            # commit the replication ONCE: an uncommitted table would be
            # re-broadcast across the mesh on every decode-block dispatch
            self._guided_tables = self._jax.device_put(
                stacked, self._shardings["repl"]
            )
        else:
            self._guided_tables = jnp.asarray(stacked)
        # remap every ACTIVE slot's automaton id under the new ordering
        for i, slot in enumerate(self.slots):
            spec = self._guided_spec(slot.params) if slot.active else None
            if spec:
                self._guided_aut_np[i] = self._guided_index[spec]
            elif i not in self._reserved:
                self._guided_aut_np[i] = 0
        self.guided_aut = self._put_batch_vec(self._guided_aut_np)
        if self.guided_state is None:
            self.guided_state = self._put_batch_vec(
                np.zeros((self.max_slots,), np.int32)
            )

    # ------------------------------------------------------------------
    # shared-prefix KV cache (automatic prefix caching, paged mode)
    # ------------------------------------------------------------------

    #: registered-prefix cap: each entry owns up to ~max_seq/page_size KV
    #: pages for the engine's lifetime — a runaway CR set must not eat the
    #: pool (realistic deployments have a handful of AIProvider templates)
    MAX_SHARED_PREFIXES = 8

    @property
    def _prefix_tokens(self) -> list:
        """PRIMARY (first-registered) prefix's tokens — compatibility view
        for single-prefix call sites; multi-prefix logic iterates
        ``self._prefixes``."""
        return self._prefixes[0]["tokens"] if self._prefixes else []

    @property
    def _prefix_pages(self) -> list:
        return self._prefixes[0]["pages"] if self._prefixes else []

    @property
    def prefix_held_pages(self) -> int:
        """KV pages owned by ALL registered prefixes (leak-audit and page
        pool accounting: these are held for the engine's lifetime by
        design, never in any slot's grant)."""
        return sum(len(p["pages"]) for p in self._prefixes)

    def _prefix_keep_len(self, tokens: list) -> int:
        """Page-floored cacheable length of a prefix's tokens: leave at
        least one page of room for every suffix + generation, and at
        least one suffix token so the sampled first token always has a
        logit row (admission additionally enforces this per wave)."""
        max_keep = self.max_seq - max(self.page_size, 64)
        return (
            min(len(tokens) - 1, max_keep) // self.page_size
        ) * self.page_size

    def set_shared_prefix(self, text: str) -> int:
        """Replace every registered prefix with this one (idle engine
        required: live slots' tables may reference the released pages).
        An UNCACHEABLE text (too short) leaves the existing registry
        intact rather than clearing it first.  See
        :meth:`add_shared_prefix` for semantics."""
        if not self.paged:
            log.warning("set_shared_prefix needs paged KV; ignoring")
            return 0
        if self.num_active:
            raise RuntimeError(
                "set_shared_prefix requires an idle engine "
                f"({self.num_active} sequences active)"
            )
        if self._prefix_keep_len(self.tokenizer.encode(text)) < self.page_size:
            log.warning("shared prefix shorter than one page; not caching")
            return 0
        self.clear_shared_prefixes()
        return self.add_shared_prefix(text)

    def clear_shared_prefixes(self) -> None:
        """Release every registered prefix's pages (idle engine only)."""
        if self.num_active:
            raise RuntimeError(
                "clear_shared_prefixes requires an idle engine "
                f"({self.num_active} sequences active)"
            )
        for entry in self._prefixes:
            self.allocator.release(entry["pages"])
        self._prefixes = []
        self._prefix_fns.clear()

    def add_shared_prefix(self, text: str) -> int:
        """Prefill ``text``'s KV ONCE into generator-owned pages; later
        prompts that start with it skip recomputing that prefix.

        The serving workload this system exists for shares a prompt
        template across every request (SURVEY.md §2.2: 32 concurrent
        failure events -> one prefill), so each template's static preamble
        is prefilled once and every admission forwards only its suffix —
        the vLLM "automatic prefix caching" idea reduced to the FEW shared
        prefixes that actually occur (the default template plus custom
        AIProvider promptTemplates), with no radix tree and no refcounts:
        prefix pages are OWNED by the generator (never in any slot's
        grant, so sequence teardown can never free them).

        Sharing is decided per admission wave by TOKEN comparison (BPE
        boundaries need not align with the text prefix) against every
        registered prefix — the longest one EVERY row fully matches wins,
        rounded down to whole pages; a wave matching none falls back to
        the ordinary full prefill.  Over-budget prompts keep the fast
        path: admission truncation drops their MIDDLE, preserving the
        prefix head and the evidence tail (``_truncate_prompt``).

        Safe while serving: registration only ALLOCATES pages and updates
        the cache functionally (release paths — set/clear — require an
        idle engine).  Registration is idempotent by cached tokens.  Paged
        mode only.  Returns the number of prefix tokens cached (0 =
        nothing cached)."""
        jnp = self._jnp
        if not self.paged:
            log.warning("add_shared_prefix needs paged KV; ignoring")
            return 0
        tokens = self.tokenizer.encode(text)
        n_keep = self._prefix_keep_len(tokens)
        if n_keep < self.page_size:
            log.warning("shared prefix shorter than one page; not caching")
            return 0
        for entry in self._prefixes:
            if entry["tokens"] == tokens[:n_keep]:
                return n_keep  # idempotent: already cached
        if len(self._prefixes) >= self.MAX_SHARED_PREFIXES:
            log.warning(
                "shared-prefix registry full (%d); %r not cached",
                self.MAX_SHARED_PREFIXES, text[:60],
            )
            return 0
        need = n_keep // self.page_size
        if self.allocator.available - need < self.pages_per_seq:
            # prefixes must never starve admission: keep at least one full
            # sequence's worth of pages grantable (registration is an
            # optimisation — a refused one costs full prefill, not errors)
            log.warning(
                "shared prefix %r needs %d pages but only %d are free "
                "(one-sequence reserve %d); not cached",
                text[:60], need, self.allocator.available, self.pages_per_seq,
            )
            return 0
        pages = self.allocator.allocate(need)
        config, jax = self.config, self._jax
        score_shards = self._prefill_score_shards() if self.mesh is not None else 1

        def build_fn(params, paged, ids, table):
            from ..ops.paged_attention import write_tokens

            mini = KVCache.create(config, 1, n_keep, dtype=paged.k_pages.dtype)
            positions = jnp.arange(n_keep, dtype=jnp.int32)[None]
            kv_valid = jnp.ones((1, n_keep), bool)
            lengths = jnp.full((1,), n_keep, jnp.int32)
            _, mini = forward(
                params, config, ids, positions, cache=mini, cache_offset=0,
                kv_valid=kv_valid, score_shards=score_shards,
                prefill_lengths=lengths,
            )
            zero = jnp.zeros((1,), jnp.int32)
            scatter = jax.vmap(write_tokens, in_axes=(0, None, 0, None, None))
            from ..ops.paged_attention import PagedKVCache

            return PagedKVCache(
                k_pages=scatter(paged.k_pages, table, mini.k, zero, lengths),
                v_pages=scatter(paged.v_pages, table, mini.v, zero, lengths),
                page_table=paged.page_table, lengths=paged.lengths,
            )

        if self.mesh is not None:
            s = self._shardings
            build = jax.jit(
                build_fn,
                in_shardings=(
                    self._param_shardings, s["paged"], s["repl"], s["repl"]
                ),
                out_shardings=s["paged"],
            )
        else:
            build = jax.jit(build_fn)
        try:
            self.paged_cache = build(
                self.params,
                self.paged_cache,
                jnp.asarray([tokens[:n_keep]], jnp.int32),
                jnp.asarray([pages], jnp.int32),
            )
        except BaseException:
            self.allocator.release(pages)
            raise
        self._prefixes.append(
            {"text": text, "tokens": tokens[:n_keep], "pages": pages}
        )
        log.info("shared prefix cached: %d tokens in %d pages (%d registered)",
                 n_keep, len(pages), len(self._prefixes))
        return n_keep

    # ------------------------------------------------------------------
    # host-side API
    # ------------------------------------------------------------------

    @property
    def adapter_names(self) -> list[str]:
        """Registered LoRA adapter names (multi-LoRA serving)."""
        return sorted(name for name in self._adapter_ids if name is not None)

    def _place(self, create: Any, name: str) -> Any:
        # on a mesh the state is allocated IN its sharded layout: created
        # whole and then placed, the pool sits on device 0 first — next to
        # the still-unsharded parameters, that was an out-of-memory at 7B
        # on four chips (chip run, PR 21)
        if self.mesh is not None:
            create = self._jax.jit(create, out_shardings=self._shardings[name])
        return create()

    def _alloc_decode_state(self) -> None:
        """The runtime's page pool, or this engine's contiguous cache in
        its place, and the per-slot device vectors of the decode block."""
        jnp = self._jnp
        if self.paged:
            super()._alloc_decode_state()
        else:
            self.cache = self._place(
                lambda: KVCache.create(
                    self.config, self.max_slots, self.max_seq,
                    dtype=self.cache_dtype,
                ),
                "cache",
            )
        self.offsets = jnp.zeros((self.max_slots,), jnp.int32)
        self.last_tokens = jnp.zeros((self.max_slots, 1), jnp.int32)

    def cancel(self, slot_id: int) -> bool:
        """Abort a DECODING sequence and reclaim its slot/pages now.

        The capacity lever for client disconnects: without it an abandoned
        request decodes to max_tokens, holding its slot and KV pages the
        whole time.  The epoch bump orphans any in-flight decode-ahead
        blocks carrying the dead sequence.  Chunk-prefilling (reserved)
        slots can't be cancelled mid-job — their wave finishes first and a
        sweep catches them next round.  Returns True if a slot was freed.
        """
        if 0 <= slot_id < self.max_slots and self.slots[slot_id].active:
            self._finish(slot_id, reason="cancelled")
            return True
        return False

    def reset(self) -> None:
        """:meth:`Runtime.reset`, and with it everything the wave engine
        keeps beside the device state: in-flight blocks, the chunked
        prefill job, the guided tables, and the registered prefixes, which
        are primed again into the fresh pool."""
        self._inflight_blocks.clear()
        self._prefill_job = None
        self._reserved.clear()
        self._guided_tables = None
        self._guided_index = {}
        self._guided_aut_np[:] = 0
        self.guided_aut = None
        self.guided_state = None
        prefix_texts = [p["text"] for p in self._prefixes]
        self._prefixes = []
        self._prefix_fns.clear()
        super().reset()
        for i in range(self.max_slots):
            self._slot_epoch[i] += 1  # orphan any in-flight device tokens
        self._host_offsets[:] = 0
        self._sampling_cache = None
        # the page pool was rebuilt: re-prime every registered prefix so
        # post-recovery admissions keep their fast path.  Guarded: a
        # failed re-prime must not fail the RECOVERY — serving without
        # the optimisation beats staying down (_try_recover treats a
        # reset() exception as fatal)
        for text in prefix_texts:
            try:
                self.add_shared_prefix(text)
            except Exception:  # noqa: BLE001
                log.warning(
                    "shared-prefix re-prime failed after reset; serving "
                    "without it", exc_info=True,
                )

    def free_slots(self) -> list[int]:
        return [
            i for i, s in enumerate(self.slots)
            if not s.active and i not in self._reserved
        ]

    @property
    def num_active(self) -> int:
        # reserved (chunk-prefilling) slots count: they occupy capacity and
        # need step() calls to make progress even before decoding starts
        return sum(s.active for s in self.slots) + len(self._reserved)

    @property
    def num_decoding(self) -> int:
        return sum(s.active for s in self.slots)

    def _activate_slots(
        self, first_np, lengths, taken, params_list, page_grants, prefill_ms
    ) -> list[int]:
        """Prompt KV is in the big cache and first tokens are sampled:
        flip the slots live (shared by one-shot and chunked prefill).
        ``prefill_ms`` is prefill COMPUTE time: the chunked path passes its
        accumulated chunk+finish time, not the interleaved wall span."""
        jnp = self._jnp
        self.metrics.record("prefill", prefill_ms)
        self.metrics.record("prefill_batch", float(len(taken)))
        # step clock: the wave's prefill is one phase-separated step.  On
        # an idle clock the record stands alone and its wall is the
        # prefill compute, all of it waited for; between decode rounds it
        # closes the open interval here, of which at most ``prefill_ms``
        # is wait (the chunked path passes accumulated chunk time, which
        # earlier intervals already hold)
        self.step_clock.observe(
            kind="prefill",
            tokens=int(sum(int(n) for n in lengths)),
            slots=len(taken),
            wait_ms=float(prefill_ms),
        )
        if self.num_decoding:
            # wave-engine phase separation: this admission's prefill
            # compute ran while decode slots sat idle — the stall the
            # continuous scheduler (serving/sched/) exists to remove;
            # recorded so the difference has a number
            self.metrics.record("decode_stall", prefill_ms)

        # paged mode tracks positions in _host_offsets + paged_cache.lengths
        # only; the device offsets array belongs to the contiguous path
        offsets = None if self.paged else np.array(self.offsets)
        last = np.array(self.last_tokens)  # mutable host copy
        for row, slot_id in enumerate(taken):
            slot = self.slots[slot_id]
            self._slot_epoch[slot_id] += 1  # new generation begins
            slot.active = True
            slot.prompt_len = int(lengths[row])
            slot.generated = [int(first_np[row])]
            slot.params = params_list[row]
            slot.started = time.perf_counter()
            slot.prefill_ms = prefill_ms
            # decode time is derived from the step clock (not wall): the
            # cumulative decode-bearing ms the clock accrues between here
            # and _finish IS this slot's decode wall
            slot.decode_cum0 = self.step_clock.decode_cum_ms
            slot.pages = page_grants[row] if self.paged else []
            last[slot_id, 0] = int(first_np[row])
            self._host_offsets[slot_id] = int(lengths[row])
            if not self.paged:
                offsets[slot_id] = int(lengths[row])
        if not self.paged:
            self.offsets = jnp.asarray(offsets)
        self.last_tokens = jnp.asarray(last)
        self._sampling_cache = None  # slot set changed
        return list(taken)

    # ------------------------------------------------------------------
    # chunked prefill (Sarathi-style interleaving; prefill_chunk knob)
    # ------------------------------------------------------------------

    def _advance_prefill(self) -> None:
        """Run ONE chunk of the pending job (or its finish step)."""
        job = self._prefill_job
        assert job is not None
        jnp = self._jnp
        n_pad, t_pad = job.key
        t0 = time.perf_counter()

        if job.written < t_pad:
            # the last chunk may be PARTIAL: t_pad buckets clamp to max_seq,
            # which need not divide the chunk size — a fixed-width slice
            # there would clamp its start and silently re-forward tokens at
            # wrong positions (jax dynamic_slice semantics)
            step_chunk = min(self.prefill_chunk, t_pad - job.written)
            fn_key = (n_pad, t_pad, step_chunk)
            if fn_key not in self._chunk_fns:
                log.info("compiling prefill chunk n=%d t=%d chunk=%d",
                         n_pad, t_pad, step_chunk)
                self._chunk_fns[fn_key] = self._aot_wrap(
                    f"chunk_n{n_pad}_t{t_pad}_c{step_chunk}",
                    self._make_chunk_fn(n_pad, t_pad, step_chunk),
                )
            ids_chunk = self._jax.lax.dynamic_slice_in_dim(
                job.ids, job.written, step_chunk, axis=1
            )
            with self._annotation("podmortem.prefill_chunk", job.params_list):
                job.mini, job.last_logits = self._chunk_fns[fn_key](
                    self.params, job.mini, ids_chunk, job.lengths,
                    jnp.int32(job.written), job.last_logits,
                    self.lora, job.adapter_idx,
                )
            job.written += step_chunk
            elapsed_ms = (time.perf_counter() - t0) * 1e3
            job.chunk_ms += elapsed_ms
            self.metrics.record("prefill_chunk", elapsed_ms)
            if job.written < t_pad:
                return
            t0 = time.perf_counter()  # finish timed separately (no double count)
        # all chunks written: scatter + sample, then activate.  Guided
        # rows mask the first token at the finish step; the automaton
        # indices are resolved NOW (admissions between this job's chunks
        # may have restacked the tables)
        job_specs = [self._guided_spec(p) for p in job.params_list]
        if any(job_specs) or self._guided_tables is not None:
            self._refresh_guided_tables(job_specs)
        # SAME guard as the one-shot path: whenever tables are live, every
        # activated slot gets its automaton binding (identity for unguided
        # rows) — a recycled slot may hold a stale accept-state whose
        # padding row would mask ALL logits for an unguided occupant
        guided = self._guided_tables is not None
        row_aut = (
            self._guided_row_aut(job_specs, n_pad) if guided
            else np.zeros((n_pad,), np.int32)
        )
        guided_args = (
            (self._guided_tables, jnp.asarray(row_aut)) if guided else ()
        )
        fn_key2 = (n_pad, t_pad, guided)
        if fn_key2 not in self._finish_fns:
            self._finish_fns[fn_key2] = self._aot_wrap(
                f"finish_n{n_pad}_t{t_pad}_g{int(guided)}",
                self._make_finish_fn(n_pad, t_pad, guided),
            )
        if self.paged:
            staged, row_tables = self._stage_page_tables(
                len(job.taken), n_pad, job.slot_ids_np, job.page_grants,
                job.lengths_np,
            )
            with self._annotation("podmortem.prefill_finish", job.params_list):
                outs = self._finish_fns[fn_key2](
                    staged, job.mini, job.lengths,
                    jnp.asarray(row_tables), job.last_logits,
                    self._rng, job.temp, job.top_p, *guided_args,
                )
        else:
            with self._annotation("podmortem.prefill_finish", job.params_list):
                outs = self._finish_fns[fn_key2](
                    self.cache, job.mini, job.lengths,
                    jnp.asarray(job.slot_ids_np), job.last_logits,
                    self._rng, job.temp, job.top_p, *guided_args,
                )
        if guided:
            cache_out, first_tokens, self._rng, first_state = outs
        else:
            cache_out, first_tokens, self._rng = outs
        if self.paged:
            self.paged_cache = cache_out
        else:
            self.cache = cache_out
        self._prefill_job = None
        self._reserved.difference_update(job.taken)
        finish_ms = (time.perf_counter() - t0) * 1e3
        self._activate_slots(
            np.asarray(first_tokens), job.lengths_np, job.taken,
            job.params_list, job.page_grants, job.chunk_ms + finish_ms,
        )
        if guided:
            self._apply_guided_activation(row_aut, job.taken, first_state)

    def _sampling_tensors(self):
        """(active_np, temp_dev, top_p_dev, active_dev), rebuilt only when
        the slot set changes (admit/finish) — not every decode step."""
        if self._sampling_cache is None:
            jnp = self._jnp
            active = np.array([s.active for s in self.slots])
            temp = np.array(
                [s.params.temperature if s.active else 0.0 for s in self.slots],
                np.float32,
            )
            top_p = np.array(
                [s.params.top_p if s.active else 1.0 for s in self.slots], np.float32
            )
            adapter_idx = np.array(
                [self._adapter_ids[s.params.adapter] if s.active else 0
                 for s in self.slots],
                np.int32,
            )
            put = self._put_batch_vec
            self._sampling_cache = (
                active, put(temp), put(top_p), put(active), put(adapter_idx)
            )
        return self._sampling_cache

    def step(self) -> list[tuple[int, GenerationResult]]:
        """One decode round: dispatch a block, then process the oldest
        fetched block's tokens; returns finished (slot, result) pairs.

        With ``pipeline_depth=1`` the block just dispatched is fetched and
        processed immediately (classic synchronous decode).  With depth D>1,
        up to D-1 blocks stay IN FLIGHT while the host processes older
        tokens — the host<->device round trip overlaps the next block's
        compute.  Slots may
        decode up to (D-1) extra junk blocks past their stop condition into
        their OWN rows/pages (the max_seq guard margin accounts for it);
        per-slot epochs keep a reused slot from ever consuming a stale
        block's tokens.
        """
        if self.num_active == 0 and not self._inflight_blocks:
            return []
        clock = self.step_clock
        clock.enter()
        try:
            return self._step_round()
        finally:
            clock.leave(
                busy=self.num_active > 0 or bool(self._inflight_blocks)
            )

    def _step_round(self) -> list[tuple[int, GenerationResult]]:
        if self.fault_plan is not None:
            # chaos seam: a sleep action stalls this step (we run on the
            # decode worker, never the event loop); a raise action
            # simulates a device error mid-step, driving the
            # ServingEngine recovery path (_try_recover -> reset)
            self.fault_plan.apply("engine.step", active=self.num_active)
        if self._prefill_job is not None:
            # one chunk per round: in-flight decodes stall for at most one
            # chunk's wall time before their next block dispatches
            self._advance_prefill()
        started = time.perf_counter()
        block = self.decode_block
        if self.num_decoding:
            # HELD slots (decoding + chunk-prefill reserved) over
            # capacity — the same definition the continuous scheduler's
            # sched_occupancy uses, so the two compare like with like
            self.metrics.record(
                "batch_occupancy", 100.0 * self.num_active / self.max_slots
            )
            # the wave loop's parts: everything before this is ``plan``,
            # gathering the sampling tensors and enqueueing the block is
            # ``launch``, processing a fetched block's tokens ``commit``
            self.step_clock.begin("launch")
            with self._annotation(
                "podmortem.decode",
                [s.params for s in self.slots if s.active],
            ):
                self._dispatch_block()
        finished: list[tuple[int, GenerationResult]] = []
        # keep at most depth-1 blocks in flight; once nothing is active the
        # leftovers are flushed (their tokens belong to finished epochs)
        processed = 0
        while self._inflight_blocks and (
            len(self._inflight_blocks) >= self.pipeline_depth
            or self.num_active == 0
        ):
            finished.extend(self._process_block(*self._inflight_blocks.pop(0)))
            processed += 1
        if processed:  # dispatch-only warmup steps would skew the histograms
            elapsed_ms = (time.perf_counter() - started) * 1e3
            self.metrics.record("decode_step", elapsed_ms / (processed * block))
            if block > 1:
                self.metrics.record("decode_block", elapsed_ms / processed)
        return finished

    def _dispatch_block(self) -> None:
        """Launch one decode block; tokens stay on device until processed."""
        block = self.decode_block
        active, temp_dev, top_p_dev, active_dev, idx_dev = self._sampling_tensors()
        lora_idx = idx_dev if self.lora is not None else None
        if self._guided_tables is not None:
            fn = self._get_guided_decode_fn()
            if self.paged:
                (self.paged_cache, toks, last, self._rng,
                 self.guided_state) = fn(
                    self.params, self.paged_cache, self.last_tokens, self._rng,
                    temp_dev, top_p_dev, active_dev, self.lora, lora_idx,
                    self._guided_tables, self.guided_aut, self.guided_state,
                )
            else:
                (self.cache, toks, last, self.offsets, self._rng,
                 self.guided_state) = fn(
                    self.params, self.cache, self.last_tokens, self.offsets,
                    self._rng, temp_dev, top_p_dev, active_dev, self.lora,
                    lora_idx, self._guided_tables, self.guided_aut,
                    self.guided_state,
                )
        elif self.paged:
            self.paged_cache, toks, last, self._rng = self._decode_fn(
                self.params, self.paged_cache, self.last_tokens, self._rng,
                temp_dev, top_p_dev, active_dev, self.lora, lora_idx,
            )
        else:
            self.cache, toks, last, self.offsets, self._rng = self._decode_fn(
                self.params, self.cache, self.last_tokens, self.offsets, self._rng,
                temp_dev, top_p_dev, active_dev, self.lora, lora_idx,
            )
        self.last_tokens = last
        # snapshot which generation of each slot this block belongs to and
        # how many tokens it held pre-block, BEFORE advancing the shadow
        snapshot = {
            i: (self._slot_epoch[i], int(self._host_offsets[i]))
            for i, slot in enumerate(self.slots)
            if slot.active
        }
        self._host_offsets[active] += block
        self._inflight_blocks.append((toks, snapshot))

    def _process_block(
        self, toks, snapshot
    ) -> list[tuple[int, GenerationResult]]:
        block = self.decode_block
        # resolve the wait BEFORE the fetch: the asarray below would
        # block on the same completion event anyway, so this adds no
        # new host sync — it only splits the wait on the device from
        # the sampled-token device->host transfer (GL001: this
        # method is host loop code, never reachable from a jitted
        # entry point — same legality as the asarray it times)
        clock = self.step_clock
        # the block may have been ready long before (pipelined depth > 1),
        # in which case the wait is ~0
        clock.begin("wait")
        try:
            toks.block_until_ready()
        except AttributeError:  # fake arrays in tests
            pass
        clock.begin("xfer")
        toks_np = np.asarray(toks)  # [K, B] — the ONE host sync per block
        live = len(snapshot)  # the slots live when the block was dispatched
        # the token-processing loop below runs AFTER the record closes, so
        # its wall lands in the NEXT record's commit_ms
        clock.begin("commit")
        clock.observe(kind="decode", tokens=block * live, slots=live)
        finished: list[tuple[int, GenerationResult]] = []
        # the slots that stream, handed to the event loop in ONE call
        handed: list[tuple[int, list]] = []
        eos = self.tokenizer.eos_id
        for i, (epoch, before) in snapshot.items():
            slot = self.slots[i]
            # the slot moved on (finished, possibly re-admitted) after this
            # block was dispatched: its lanes hold junk for the new epoch
            if not slot.active or self._slot_epoch[i] != epoch:
                continue
            generated_before = len(slot.generated)
            for k in range(block):
                token = int(toks_np[k, i])
                previous = slot.generated[-1] if slot.generated else None
                # the PREVIOUS sampled token ended generation?
                if slot.params.stop_on_eos and eos is not None and previous == eos:
                    finished.append((i, self._finish(i, reason="stop")))
                    break
                if len(slot.generated) >= slot.params.max_tokens:
                    # budget already consumed (the prefill-sampled token
                    # counts); discard this token so max_tokens is exact
                    finished.append((i, self._finish(i, reason="length")))
                    break
                slot.generated.append(token)
                total = before + k + 1
                # stop pipeline_depth BLOCKS short of max_seq: the device
                # decodes that many further blocks before the host can stop
                # it, and those writes must stay inside the slot's cache
                # row / pages
                if (
                    len(slot.generated) >= slot.params.max_tokens
                    or total >= self.max_seq - self.pipeline_depth * block
                ):
                    finished.append((i, self._finish(i, reason="length")))
                    break
            if (
                self.partial_hook is not None
                # identity: _finish() swaps in a fresh _Slot, so a slot that
                # finished inside this block is skipped (its result carries
                # the tail) — `slot.active` alone would read the OLD object
                and self.slots[i] is slot
                and len(slot.generated) > generated_before
            ):
                # list COPY: the hook crosses into the event-loop thread
                # while this worker keeps appending
                handed.append((i, list(slot.generated)))
        if handed:
            self.partial_hook(handed)
        return finished

    def _finish(self, slot_id: int, *, reason: str) -> GenerationResult:
        slot = self.slots[slot_id]
        if self.paged and slot.pages:
            # point the slot's table row at the trash page BEFORE releasing
            # the grant — the freed pages may be handed to a new sequence
            # while this slot row still participates in batched decode
            from ..ops.paged_attention import PagedKVCache

            jnp = self._jnp
            paged = self.paged_cache
            self.paged_cache = PagedKVCache(
                k_pages=paged.k_pages, v_pages=paged.v_pages,
                page_table=paged.page_table.at[slot_id].set(0),
                lengths=paged.lengths.at[slot_id].set(0),
            )
            self.allocator.release(slot.pages)
        self._slot_epoch[slot_id] += 1  # stale in-flight tokens now orphaned
        self._host_offsets[slot_id] = 0
        self._sampling_cache = None  # slot set changed
        if self._guided_tables is not None:
            if self._guided_aut_np[slot_id]:
                self._guided_aut_np[slot_id] = 0
                self.guided_aut = self._put_batch_vec(self._guided_aut_np)
            if not self._guided_aut_np.any() and not any(
                s.active and self._guided_spec(s.params)
                for i, s in enumerate(self.slots)
                if i != slot_id  # this slot is finishing right now
            ):
                self._guided_tables = None  # back to the unguided programs
                self._guided_index = {}
                self.guided_aut = None
                self.guided_state = None
        eos = self.tokenizer.eos_id
        ids = [t for t in slot.generated if t != eos]
        text = self.tokenizer.decode(ids)
        if reason == "length" and slot.params.deadline_clamped:
            # the length cap was the deadline budget's roofline clamp, not
            # the caller's max_tokens — surface the difference
            reason = "deadline"
        result = GenerationResult(
            text=text,
            token_ids=ids,
            prompt_tokens=slot.prompt_len,
            completion_tokens=len(ids),
            finish_reason=reason,
            prefill_ms=slot.prefill_ms,
            # decode wall DERIVED FROM THE STEP CLOCK: the decode-bearing
            # ms the ring accrued while the slot was live (monotonic
            # cumulative, so ring eviction cannot corrupt it).  The old
            # coarse wall delta (now - slot.started) could disagree with
            # the step records; this cannot.
            decode_ms=max(
                0.0, self.step_clock.decode_cum_ms - slot.decode_cum0
            ),
            queue_wait_ms=slot.queue_wait_ms,
        )
        self.slots[slot_id] = _Slot()
        return result

    # profiling ---------------------------------------------------------
    def trace(self, log_dir: str):
        """``jax.profiler.trace`` context around a serving span: writes an
        xplane protobuf under ``log_dir`` for tensorboard/xprof (SURVEY.md
        §5 tracing — the reference has none; the TPU side needs it to
        attribute the p50 budget between prefill, decode and host work)."""
        return self._jax.profiler.trace(log_dir)

    # convenience for tests ---------------------------------------------
    def generate(self, prompt: str, params: Optional[SamplingParams] = None) -> GenerationResult:
        """Synchronous single-prompt generation (drains the whole batch)."""
        sampling = params or SamplingParams()
        [slot_id] = self.admit([prompt], [sampling])
        while True:
            for finished_id, result in self.step():
                if finished_id == slot_id:
                    return result


class ServingEngine:
    """Asyncio front: queue -> admission -> shared decode loop -> futures.

    The decode loop runs JAX calls in a worker thread so the operator's
    event loop never blocks on device sync (the reference's worker-pool
    discipline, SURVEY.md §5 race-detection entry).
    """

    def __init__(
        self,
        generator: Runtime,  # the wave BatchedGenerator, or a bare Runtime under a scheduler
        *,
        admission_wait_s: float = 0.004,
        max_queue: int = 1024,
        supervisor: Optional[SupervisorPolicy] = None,
        recorder: Optional[Any] = None,  # obs.FlightRecorder for black boxes
        scheduler: Optional[Any] = None,  # sched.Scheduler: continuous mode
    ) -> None:
        import concurrent.futures

        self.generator = generator
        self.admission_wait_s = admission_wait_s
        #: continuous-batching scheduler (serving/sched/): when set, the
        #: serve loop runs schedule→dispatch→commit steps over ragged
        #: mixed prefill+decode waves instead of the wave machinery —
        #: _pending is then keyed by scheduler req id, not slot id
        self._sched = scheduler
        if scheduler is not None:
            scheduler.partial_hook = self._on_partial_from_worker
        #: watchdog policy (None = pre-supervisor semantics: loop death
        #: fails in-flight futures, stalls hang until the step returns)
        self._supervisor = supervisor
        self.recorder = recorder
        self._supervise_task: Optional[asyncio.Task] = None
        self._supervise_wakeup = asyncio.Event()
        self._stalled = False  # last loop death was a stall (executor abandoned)
        self._gave_up = False  # supervisor exhausted its reset budget
        # survivors collected by a restart in progress: close() must still
        # fail these futures if it interrupts the supervisor mid-recovery
        self._restarting: list[_Request] = []
        # one persistent worker: no per-step thread handoff through the
        # shared default executor (contextvars copy + pool contention), and
        # all jax dispatch happens from a single consistent thread
        self._executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="tpu-decode"
        )
        # priority queue: (-priority, arrival_seq, entry) — higher-priority
        # requests admit first, FIFO within a class.  The operator pipeline
        # submits explanations at priority 10 so external completion-API
        # callers sharing the engine cannot starve incident analysis.  The
        # queue itself is unbounded; max_queue bounds only the priority<=0
        # lane (via semaphore), so a flood of external callers blocks THEIR
        # puts while high-priority puts always enter immediately — a bounded
        # PriorityQueue would grant space to put-waiters in FIFO order,
        # reintroducing the starvation at the put() boundary.
        self._queue: asyncio.PriorityQueue = asyncio.PriorityQueue()
        self._low_lane = asyncio.Semaphore(max_queue)
        self._seq = itertools.count()
        self._pending: dict[int, _Request] = {}  # slot id -> admitted request
        self._inflight: list[_Request] = []  # popped from queue, not yet admitted
        # streaming: future -> on_partial registered in generate(); slot ->
        # on_partial once admitted.  The generator's hook fires on the
        # decode worker; call_soon_threadsafe marshals it onto the loop.
        self._partial_by_future: dict[asyncio.Future, Any] = {}
        self._partial_cbs: dict[int, Any] = {}
        #: key -> token count already delivered to the stream (loop-side
        #: monotonicity guard: pipelined commits + cancellation can leave
        #: stale snapshot deliveries queued behind a restart's fresh
        #: ones — a snapshot that does not EXTEND the stream is dropped)
        self._partial_sent: dict[int, int] = {}
        # single-flight dedup for guided-automaton builds (ensure_guided)
        self._guided_builds: dict[tuple, asyncio.Future] = {}
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        if scheduler is None:
            generator.partial_hook = self._on_partial_from_worker
        self._stalled_avail: Optional[int] = None  # pages free at last stall
        self._task: Optional[asyncio.Task] = None
        self._closed = False
        self._error: Optional[BaseException] = None
        # auto-recovery after a loop death (transient device errors):
        # bounded resets per window, so a persistent fault still surfaces
        self._reset_times: list[float] = []
        self._reset_lock = asyncio.Lock()
        # per-class SLO aggregates (obs/sloledger.py SLOBoard): bounded
        # O(classes) state carried on load_report()/healthz and rolled up
        # fleet-wide by the router.  Metric-free — the operator-side
        # ledger owns the podmortem_slo_* counters, so an in-process
        # operator+serving pair never double-counts.
        from ..obs.sloledger import SLOBoard

        self._slo_board = SLOBoard()
        #: fleet KV fabric (operator_tpu/fabric/): a FabricFetcher wired
        #: post-construction when KV_FABRIC=1; admission-time prefix
        #: misses then consult the fleet index and pull pages from a
        #: holder's host pool instead of recomputing.  None = local-only
        #: (the pre-fabric behaviour, and the default).
        self.fabric: Optional[Any] = None
        #: fabric/peers.py PeerPoller feeding the fetcher's index from
        #: peer /healthz inventories (KV_FABRIC_PEERS) — the standalone
        #: replica's substitute for an in-process router's kv_index.
        #: Wired post-construction; start() runs it, close() cancels it.
        self.fabric_poller: Optional[Any] = None
        self._fabric_poll_task: Optional[asyncio.Task] = None
        #: prefill/decode disaggregation role advertised on /healthz
        #: (fabric/disagg.py): "prefill" | "decode" | "mixed"
        self.replica_role: str = "mixed"
        #: the backend this engine serves on (utils/platform.py DeviceInfo)
        #: and the process's compile log (utils/compilewatch.py), both
        #: wired by build_serving_engine and reported on GET /healthz.
        #: None on directly-constructed engines (tests).
        self.device: Optional[Any] = None
        self.compile_watch: Optional[Any] = None
        #: the collector's hook (serving/perf.py GcWatch) the step clock
        #: reads: installed by start(), removed by close()
        self._gc_watch: Optional[Any] = None

    def _unwrap(self, item: tuple) -> "_Request":
        """Pop bookkeeping for a queue entry: low-lane slots free on pop.
        Supervisor requeues re-enter at priority >= 1 (never through the
        lane), so the release here stays balanced."""
        neg_priority, _, request = item
        if neg_priority >= 0:  # priority <= 0 went through the bounded lane
            self._low_lane.release()
        return request

    def _page_stalled(self, batch: list) -> bool:
        """True when a backpressured batch has no new pages to retry with —
        skipping the retry avoids re-tokenising every waiting prompt each
        loop round while decode slowly frees pages."""
        if self._stalled_avail is None:
            return False
        allocator = getattr(self.generator, "allocator", None)
        if allocator is None:
            return False
        if allocator.available > self._stalled_avail:
            self._stalled_avail = None
            return False
        return True

    #: auto-recovery budget: at most this many loop restarts per window —
    #: a persistent device fault must still surface instead of silently
    #: thrashing (reference-equivalent discipline: the watch loop's 5s
    #: auto-restart is likewise unconditional but visible in events)
    MAX_RESETS_PER_WINDOW = 3
    RESET_WINDOW_S = 600.0

    def _reset_engine(self) -> None:
        """Rebuild device state after a loop death (decode worker).  In
        continuous mode the scheduler's host rows/queue are dropped too —
        the supervisor already collected their requests as survivors."""
        self.generator.reset()
        if self._sched is not None:
            self._sched.reset()

    async def _try_recover(self) -> None:
        """One bounded attempt to revive a dead serve loop.

        A transient device error mid-step may have invalidated the
        DONATED buffers (KV cache / page pool), so the generator rebuilds
        its decode state from scratch (weights survive); in-flight requests
        were already failed when the loop died.  Leaves ``_error`` set when
        the reset budget is exhausted or the rebuild itself fails.
        """
        async with self._reset_lock:
            if self._error is None or self._closed:  # raced another caller
                return
            now = time.monotonic()
            self._reset_times = [
                t for t in self._reset_times if now - t < self.RESET_WINDOW_S
            ]
            if len(self._reset_times) >= self.MAX_RESETS_PER_WINDOW:
                return
            self._reset_times.append(now)
            log.warning(
                "serving engine loop died (%s); resetting device state and "
                "restarting (%d/%d resets in window)",
                self._error, len(self._reset_times), self.MAX_RESETS_PER_WINDOW,
            )
            loop = asyncio.get_running_loop()
            try:
                await loop.run_in_executor(self._executor, self._reset_engine)
            except Exception as exc:  # noqa: BLE001 - rebuild failed: stay dead
                log.exception("engine reset failed; staying down")
                self._error = exc
                return
            self._error = None
            self._task = None  # the caller's generate() starts a fresh loop

    # ------------------------------------------------------------------
    # supervisor (SupervisorPolicy; docs/ROBUSTNESS.md)
    # ------------------------------------------------------------------
    async def _supervise(self) -> None:
        """Watchdog task: woken by a serve-loop death (error or stall),
        performs the supervised restart.  Runs for the engine's lifetime so
        recovery is PROACTIVE — in-flight work is requeued immediately, not
        lazily when the next caller happens to notice."""
        while not self._closed:
            await self._supervise_wakeup.wait()
            self._supervise_wakeup.clear()
            if self._closed:
                return
            if self._error is None:
                continue
            try:
                await self._supervised_restart()
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 - the watchdog must outlive one bad restart
                log.exception("supervised engine restart itself failed")

    def _audit_leaks(self) -> dict:
        """Post-reset invariant check: every slot free, every non-prefix
        page back in the pool.  A non-empty result means reset() has a
        reclamation bug — surfaced as podmortem_supervisor_leak_total and
        in the black-box dump rather than silently shrinking capacity."""
        generator = self.generator
        leaks: dict = {}
        free = len(generator.free_slots())
        if free != generator.max_slots:
            leaks["slots"] = generator.max_slots - free
        allocator = getattr(generator, "allocator", None)
        if allocator is not None:
            expected = allocator.num_pages - 1 - generator.prefix_held_pages
            if allocator.available != expected:
                leaks["pages"] = expected - allocator.available
        return leaks

    def _step_tail(self) -> dict:
        """What a black-box dump keeps of the step clock: the last step
        records, and the stalls it kept from before them (``obs.view
        --steps`` / ``--stalls`` render both); empty before any step."""
        clock = self.generator.step_clock
        tail = {
            "steps": [r.to_dict() for r in clock.ring.records(last=32)],
            "stalls": [r.to_dict() for r in list(clock.stalls)],
        }
        return {key: rows for key, rows in tail.items() if rows}

    def _dump_blackbox(self, reason: str, extra: dict) -> None:
        """Black-box flight-recorder dump for a supervisor event — a
        synthetic one-span trace (there is no ambient analysis trace on
        the engine's own watchdog) carrying the restart context."""
        recorder = self.recorder
        if recorder is None:
            try:
                from ..obs import RECORDER as recorder
            except Exception:  # noqa: BLE001 - forensics must never block recovery
                return
        try:
            # the stall's preceding timeline: the last step records BEFORE
            # the reset wipes the clock (obs.view --steps renders them)
            if "steps" not in extra:
                extra = {**extra, **self._step_tail()}
        except Exception:  # noqa: BLE001 - forensics must never block recovery
            pass
        try:
            from ..obs import Tracer

            tracer = Tracer(recorder=recorder)
            with tracer.trace(
                "engine.supervisor", attributes={"reason": reason}
            ) as root:
                pass
            recorder.black_box(root.trace_id, reason, extra)
        except Exception:  # noqa: BLE001 - forensics must never block recovery
            log.warning("supervisor black-box dump failed", exc_info=True)

    def _collect_survivors(self) -> "tuple[list[_Request], int]":
        """Gather every in-flight request (admitted, in hand, queued) for
        requeueing; requests already requeued ``max_requeues`` times are
        failed now.  Returns (requeue list, gaveup count)."""
        assert self._supervisor is not None
        requests: list[_Request] = []
        for slot_id, request in self._pending.items():
            callback = self._partial_cbs.get(slot_id)
            if callback is not None:
                # re-arm streaming: the old slot id dies with the engine
                # state, the re-admitted request gets a fresh one
                self._partial_by_future[request.future] = callback[0]
            requests.append(request)
        self._pending.clear()
        self._partial_cbs.clear()
        self._partial_sent.clear()
        requests.extend(self._inflight)
        self._inflight.clear()
        while not self._queue.empty():
            requests.append(self._unwrap(self._queue.get_nowait()))
        retry: list[_Request] = []
        gaveup = 0
        for request in requests:
            if request.future.done():
                self._partial_by_future.pop(request.future, None)
            elif request.requeues >= self._supervisor.max_requeues:
                self._partial_by_future.pop(request.future, None)
                failure = RuntimeError(
                    "request failed after a supervised engine restart "
                    f"(requeued {request.requeues}x)"
                )
                failure.__cause__ = self._error
                request.future.set_exception(failure)
                self.generator.metrics.incr("supervisor_gaveup")
                gaveup += 1
            else:
                retry.append(request)
        return retry, gaveup

    def _fail_survivors(self, retry: "list[_Request]", why: str) -> int:
        failed = 0
        for request in retry:
            if request.future.done():
                continue
            self._partial_by_future.pop(request.future, None)
            failure = RuntimeError(why)
            failure.__cause__ = self._error
            request.future.set_exception(failure)
            self.generator.metrics.incr("supervisor_gaveup")
            failed += 1
        return failed

    def _give_up_restart(
        self, retry: "list[_Request]", gaveup: int, *,
        reason: str, cause: str, message: str, outcome: str,
    ) -> None:
        """Terminal exit of a supervised restart: fail the survivors, mark
        the engine given-up, drain stragglers that enqueued DURING the
        restart (after survivor collection emptied the queue — no serve
        loop is left to consume them), and leave a black-box dump."""
        gaveup += self._fail_survivors(retry, message)
        self._restarting = []
        self._gave_up = True
        self._fail_outstanding(RuntimeError(message))
        self._dump_blackbox(reason, {
            "cause": cause, "gaveup": gaveup, "requeued": 0,
            "outcome": outcome,
        })

    async def _supervised_restart(self) -> None:
        """The supervisor's recovery sequence: collect survivors, retire a
        stalled decode thread, reset device state (bounded resets per
        window — a persistent fault must surface, not thrash), audit
        slot/page leaks, restart the loop, requeue survivors once with
        their residual deadlines, and leave a black-box dump behind."""
        policy = self._supervisor
        assert policy is not None
        loop = asyncio.get_running_loop()
        restart_t0 = time.monotonic()
        stalled = self._stalled
        reason = "engine-stall" if stalled else "engine-error"
        cause = str(self._error)
        # the stall's preceding step timeline, captured BEFORE the device
        # reset wipes the step clock with the rest of decode state
        try:
            step_tail = self._step_tail()
        except Exception:  # noqa: BLE001 - forensics must never block recovery
            step_tail = {}
        retry, gaveup = self._collect_survivors()
        # parked here until requeued/failed: if close() interrupts this
        # restart, _fail_outstanding still reaches these futures
        self._restarting = retry
        if stalled:
            # the wedged worker thread cannot be interrupted: ABANDON its
            # executor and give the orphan a bounded grace to come back —
            # in the common case (a transient runtime hiccup) it returns
            # and the reset below runs with no concurrent mutator; in a
            # true device hang we proceed under it after the grace (the
            # reset rebuilds all decode state anyway)
            import concurrent.futures
            import threading

            old = self._executor
            self._executor = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="tpu-decode"
            )
            # a DEDICATED daemon thread performs the blocking join:
            # parking old.shutdown(wait=True) on the shared default
            # executor would permanently consume one of its threads every
            # time the wedged decode thread never returns
            joiner = threading.Thread(
                target=lambda: old.shutdown(wait=True),
                name="tpu-decode-reaper", daemon=True,
            )
            joiner.start()
            await loop.run_in_executor(None, joiner.join, policy.join_grace_s)
            if joiner.is_alive():
                log.error(
                    "stalled decode thread still wedged after %.1fs; "
                    "resetting device state under it", policy.join_grace_s,
                )
            self._stalled = False
        now = time.monotonic()
        self._reset_times = [
            t for t in self._reset_times if now - t < self.RESET_WINDOW_S
        ]
        if len(self._reset_times) >= self.MAX_RESETS_PER_WINDOW:
            self._give_up_restart(
                retry, gaveup, reason=reason, cause=cause,
                message="serving engine down: supervisor reset budget exhausted",
                outcome="reset-budget-exhausted",
            )
            log.error("engine supervisor giving up: %d resets within %.0fs",
                      self.MAX_RESETS_PER_WINDOW, self.RESET_WINDOW_S)
            return
        self._reset_times.append(now)
        try:
            await loop.run_in_executor(self._executor, self._reset_engine)
        except Exception as exc:  # noqa: BLE001 - rebuild failed: stay down
            log.exception("supervised engine reset failed; staying down")
            self._error = exc
            self._give_up_restart(
                retry, gaveup, reason=reason, cause=cause,
                message="serving engine down: device-state reset failed",
                outcome="reset-failed",
            )
            return
        leaks = self._audit_leaks()
        if leaks:
            self.generator.metrics.incr("supervisor_leak")
            log.error("post-reset leak audit failed: %s", leaks)
        self._error = None
        self._task = None
        await self.start()
        self._restarting = []
        for request in retry:
            request.requeues += 1
            self.generator.metrics.incr("supervisor_requeue")
            # requeues re-enter ABOVE the normal priority lanes (they were
            # already admitted once) and outside the bounded low lane (its
            # slot was released when the entry was first popped); their
            # deadline is an absolute instant, so the residual budget
            # carries through the restart automatically
            await self._queue.put(
                (-max(request.priority, 1), next(self._seq), request)
            )
        self.generator.metrics.incr("supervisor_restart")
        # restart-to-ready: device reset through loop restart + requeue.
        # With the AOT cache the reset's program rebuilds deserialize
        # instead of recompiling, which is what keeps this in seconds
        ready_s = time.monotonic() - restart_t0
        self.generator.metrics.set_gauge(
            "supervisor_restart_ready_seconds", round(ready_s, 3)
        )
        aot = getattr(self.generator, "_aot", None)
        self._dump_blackbox(reason, {
            "cause": cause,
            "requeued": len(retry),
            "gaveup": gaveup,
            "leaks": leaks,
            "resets_in_window": len(self._reset_times),
            "restart_ready_s": round(ready_s, 3),
            "aot_cache": aot.stats() if aot is not None else "off",
            **step_tail,
        })
        log.warning(
            "supervised engine restart (%s) ready in %.2fs: %d requeued, "
            "%d failed, leaks=%s",
            reason, ready_s, len(retry), gaveup, leaks or "none",
        )

    def _on_partial_from_worker(self, handed: list) -> None:
        """Generator hook (decode worker thread) -> event-loop callback:
        ONE ``call_soon_threadsafe`` carries every ``(key, token_ids)``
        pair of the commit that still has a live stream, in commit order.
        The hand-over carries the step clock's last stamp, where the
        commit that holds these tokens began: one stamp a commit, read
        here and not taken a row."""
        if self._loop is None:
            return
        live = []
        for key, token_ids in handed:
            entry = self._partial_cbs.get(key)
            if entry is None:
                continue
            callback, future = entry
            if future.done():  # streaming client cancelled; slot drains unheard
                continue
            live.append((key, callback, future, token_ids))
        if live:
            self._loop.call_soon_threadsafe(
                self._deliver_partials, live, self.generator.step_clock.mark
            )

    def _deliver_partials(self, live: list, committed_t: float) -> None:
        """Loop side of one hand-over: each snapshot in commit order.  A
        stream whose callback raises is logged, as the loop would log a
        callback of its own, and the others are still delivered."""
        for key, callback, future, token_ids in live:
            try:
                self._deliver_partial(key, callback, future, token_ids, committed_t)
            except Exception:
                log.exception("streaming delivery to %s failed", key)

    def _deliver_partial(
        self, key: int, callback: Any, future: "asyncio.Future",
        token_ids: list, committed_t: Optional[float] = None,
    ) -> None:
        """Loop-side partial delivery with a per-request order guard.

        The worker's ``future.done()`` check races cancellation, and a
        supervised restart can interleave a dead registration's queued
        snapshots with the requeued request's fresh ones (same wave-mode
        slot key).  Re-checking here — and delivering only snapshots
        that strictly EXTEND what this key's stream already saw — makes
        the stream per-request monotonic in token order regardless of
        how commits and cancellations interleave.  Whatever becomes of
        the snapshot, its way from the commit to this thread is over: the
        step clock takes its age (``StepRecord.deliver_lag_ms``)."""
        if committed_t is not None:
            self.generator.step_clock.delivered(committed_t)
        if future.done() or self._partial_cbs.get(key, (None, None))[1] is not future:
            return
        if len(token_ids) <= self._partial_sent.get(key, 0):
            return  # stale snapshot: would rewind the stream
        self._partial_sent[key] = len(token_ids)
        callback(token_ids)

    def load_report(self):
        """This replica's load, in the shape the data-plane router's shed
        decision reads (``operator_tpu/router/health.py: ReplicaLoad``):
        queue pressure, the admission roofline's own per-token estimate
        (so the router's residual-fit check agrees with what THIS replica
        would clamp a deadline to), and whether the supervisor gave up.
        Cheap loop-side reads — approximate under concurrent decode is
        fine, the router treats it as feedback, not truth.  Served on
        ``GET /healthz`` (serving/httpserver.py) next to the replica id."""
        from ..router.health import ReplicaLoad

        if self._sched is not None:
            # _pending holds EVERY handed-off request (admitted rows AND
            # scheduler-queued ones), so counting _pending next to
            # sched.queue_depth would tally queued requests twice and
            # make this replica look ~2x as loaded as a wave-mode twin
            queue_depth = self._queue.qsize() + self._sched.queue_depth
            inflight = len(self._inflight) + self._sched.num_active
        else:
            queue_depth = self._queue.qsize()
            inflight = len(self._inflight) + len(self._pending)
        # step-timing summary (obs/steptrace.py): the measured decode MFU,
        # host-gap fraction and occupancy the operator's /fleet view rolls
        # up across replicas — None until steps have been recorded
        summary = self.generator.step_clock.summary()
        fractions = summary.get("fractions") or {}
        # KV economy (serving/kvstore.py): page headroom + prefix hit
        # rate for the router's informed-affinity choice, plus a bounded
        # block-hash inventory so a failover can prefer a survivor that
        # already holds the prompt's blocks (the peer index)
        kv_pages_free = 0
        kv_pages_total = 0
        allocator = getattr(self.generator, "allocator", None)
        if allocator is not None:
            kv_pages_free = allocator.available
            kv_pages_total = allocator.num_pages - 1
        prefix_hit_rate = None
        prefix_lookups = 0
        kv_blocks = None
        kvstore = getattr(self._sched, "_kvstore", None)
        if kvstore is not None:
            prefix_hit_rate = kvstore.hit_rate()
            prefix_lookups = kvstore.lookups
            kv_blocks = kvstore.inventory()
        return ReplicaLoad(
            queue_depth=queue_depth,
            inflight=inflight,
            decode_token_s=self.generator.decode_token_estimate_s(),
            gave_up=self._gave_up,
            decode_mfu=summary.get("decode_mfu"),
            host_gap_frac=fractions.get("host"),
            occupancy=summary.get("occupancy_avg"),
            steps=summary.get("steps") or 0,
            slo_attainment=self._slo_board.attainment(),
            goodput_tokens_s=self._slo_board.goodput_tokens_s(),
            slo_completed=self._slo_board.completed,
            slo_classes=self._slo_board.per_class(),
            kv_pages_free=kv_pages_free,
            kv_pages_total=kv_pages_total,
            prefix_hit_rate=prefix_hit_rate,
            prefix_lookups=prefix_lookups,
            kv_blocks=kv_blocks,
            role=self.replica_role,
            device=self.device.to_dict() if self.device is not None else None,
            shed=(
                self.generator.metrics.labeled_total("shed")
                if hasattr(self.generator.metrics, "labeled_total") else 0
            ),
            degraded=(
                self.generator.metrics.labeled_total("degraded")
                if hasattr(self.generator.metrics, "labeled_total") else 0
            ),
        )

    def serving_features(self) -> dict:
        """Which engine runs and what it really does, as opposed to what
        the configuration asked for (``GET /healthz`` ``features``): the
        scheduler switches speculation and the prefix cache off for a
        model with recurrent state and says why here."""
        model = self.generator.config
        sched = self._sched
        return {
            "schedMode": "continuous" if sched is not None else "wave",
            "modelFamily": getattr(model, "family", "llama"),
            "recurrentState": bool(getattr(model, "recurrent_state", False)),
            "specDecode": sched is not None and sched.spec_k > 0,
            "kvPrefixCache": sched is not None and sched._kvstore is not None,
            "switchedOff": dict(sched.switched_off) if sched is not None else {},
        }

    def device_memory(self) -> list:
        """Per local device, what its runtime says about memory
        (``memory_stats()``: bytes in use now, the peak, the limit) —
        None for a backend that reports none (the CPU).  Shows which
        devices actually hold the arrays, e.g. that an unsharded engine
        on a four-chip host sits on device 0 alone."""
        keys = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
        return [
            {"id": dev.id, **{k: (dev.memory_stats() or {}).get(k) for k in keys}}
            for dev in self.generator._jax.local_devices()
        ]

    async def _fabric_prefetch(
        self,
        prompt: str,
        params: Optional[SamplingParams],
        resume_tokens: Optional[list],
    ) -> None:
        """Admission-time fabric prefetch (operator_tpu/fabric/fetch.py).

        Tokenizes exactly the way the scheduler's enqueue will (same
        truncation budget, same resume suffix) so the probed block
        hashes line up with the prefix match that follows.  The cheap
        gates run FIRST — no host pool to land pages in, or an index
        with no holders at all, must cost the request nothing (the
        tokenize is duplicate CPU work the enqueue repeats).  The
        tokenize itself and all store access run on the decode executor:
        the event loop never touches the store (the scheduler mutates it
        from that same thread), and long prompts never stall other
        connections here.  Never raises — every failure mode is a silent
        fall-through to the recompute the request was going to do
        anyway."""
        from .types import prompt_budget

        store = getattr(self._sched, "_kvstore", None)
        if store is None:
            return
        pool = getattr(store, "host_pool", None)
        if pool is None or getattr(pool, "capacity_bytes", 0) <= 0:
            return  # nowhere to land a fetched page
        try:
            if self.fabric.index.empty():
                return  # no holders anywhere: nothing to fetch
            g = self.generator
            p = params or SamplingParams()

            def tokenize() -> Optional[list]:
                ids = g.tokenizer.encode(prompt)
                budget = prompt_budget(g.max_seq, p.max_tokens)
                if resume_tokens:
                    if len(resume_tokens) >= budget:
                        return None  # enqueue will reject it
                    return g._truncate_prompt(
                        ids, budget - len(resume_tokens)
                    ) + list(resume_tokens)
                return g._truncate_prompt(ids, budget)

            tokens = await asyncio.get_running_loop().run_in_executor(
                self._executor, tokenize
            )
            if tokens is None:
                return
            residual = None
            if p.deadline is not None:
                residual = p.deadline - g._clock()
            await self.fabric.prefetch(
                tokens, store=store, budget_s=residual,
                executor=self._executor,
            )
        except asyncio.CancelledError:
            raise
        except Exception:
            log.debug("fabric prefetch failed; recompute covers it",
                      exc_info=True)

    def kv_block_bytes(self, hash_hex: str) -> Optional[bytes]:
        """Serve one KV block out of the host pool for a fabric peer
        (``GET /kv/blocks/{hash}`` — serving/httpserver.py).  Host numpy
        in, wire bytes out: no device touch, no scheduler involvement.
        Returns None when the block is not pooled here (the peer treats
        that 404 as index-eviction feedback)."""
        from ..fabric.wire import encode_block

        metrics = self.generator.metrics
        store = getattr(self._sched, "_kvstore", None)
        pool = getattr(store, "host_pool", None)
        try:
            block_hash = bytes.fromhex(hash_hex)
        except ValueError:
            return None
        entry = pool.get(block_hash) if pool is not None else None
        if entry is None:
            metrics.incr("fabric_serve_miss", exemplar=hash_hex)
            return None
        metrics.incr("fabric_serve_hit", exemplar=hash_hex)
        return encode_block(block_hash, entry[0], entry[1])

    async def start(self) -> None:
        if self._gc_watch is None:
            from .perf import GcWatch

            # what else the process does in a step's interval: the step
            # clock reads the collector's pauses and the compile log
            clock = self.generator.step_clock
            self._gc_watch = clock.gc_watch = GcWatch(
                self.generator._annotation
            ).install()
            clock.compile_watch = self.compile_watch
        if self._task is None:
            self._loop = asyncio.get_running_loop()
            self._task = asyncio.create_task(self._run(), name="serving-engine")
        if self._supervisor is not None and self._supervise_task is None:
            self._supervise_task = asyncio.create_task(
                self._supervise(), name="serving-supervisor"
            )
        if self.fabric_poller is not None and self._fabric_poll_task is None:
            self._fabric_poll_task = asyncio.create_task(
                self.fabric_poller.run(), name="fabric-peer-poll"
            )

    async def close(self) -> None:
        self._closed = True
        if self.compile_watch is not None:
            self.compile_watch.close()  # take the tap off the jax logger
        if self._gc_watch is not None:
            self._gc_watch.remove()  # and the hook off the collector
            self._gc_watch = None
        # the peer poller is pure index plumbing — first down, nothing
        # depends on it
        poll_task, self._fabric_poll_task = self._fabric_poll_task, None
        if poll_task is not None:
            poll_task.cancel()
            try:
                await poll_task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001 - already torn down
                pass
        # wake an idle watchdog so it observes _closed and exits.  A
        # watchdog MID-RESTART is awaited (bounded) rather than cancelled:
        # cancelling between survivor collection and the device-state
        # reset would leave slots/pages allocated forever and the
        # already-submitted reset racing this shutdown on the executor
        self._supervise_wakeup.set()
        supervise, self._supervise_task = self._supervise_task, None
        if supervise is not None:
            grace = 5.0 + (
                self._supervisor.join_grace_s
                if self._supervisor is not None else 0.0
            )
            try:
                await asyncio.wait_for(supervise, timeout=grace)
            except (asyncio.TimeoutError, asyncio.CancelledError):
                pass  # wedged restart: wait_for already cancelled it
        # AFTER the watchdog settles — a restart in flight during the
        # wait above re-creates self._task via start()
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
        self._task = None
        self._fail_outstanding(asyncio.CancelledError("serving engine closed"))
        self._executor.shutdown(wait=False)

    def _fail_outstanding(self, exc: BaseException) -> None:
        """Resolve every in-flight and queued future so callers never hang."""
        self._partial_cbs.clear()
        self._partial_sent.clear()
        self._partial_by_future.clear()
        for request in self._restarting:  # supervisor interrupted mid-recovery
            if not request.future.done():
                request.future.set_exception(exc)
        self._restarting = []
        for request in self._pending.values():
            if not request.future.done():
                request.future.set_exception(exc)
        self._pending.clear()
        for request in self._inflight:  # popped but not yet admitted
            if not request.future.done():
                request.future.set_exception(exc)
        self._inflight.clear()
        while not self._queue.empty():
            request = self._unwrap(self._queue.get_nowait())
            if not request.future.done():
                request.future.set_exception(exc)

    async def precompile(self, level: str = "serving") -> dict:
        """Run the warmup compile on the decode worker thread
        (single-threaded executor: serialised with every other generator
        op).  Call before serving traffic — readiness should gate on it
        (operator/app.py warmup).  In continuous-scheduler mode there is
        no program grid: exactly ONE mixed program compiles, whatever
        the workload (docs/SERVING.md)."""
        loop = asyncio.get_running_loop()
        if self._sched is not None:
            sched = self._sched

            def _warm() -> dict:
                if level == "off":
                    return {"level": level, "programs": 0, "seconds": 0.0}
                started = time.perf_counter()
                sched.precompile()
                out = {
                    "level": level, "programs": 1,
                    "seconds": round(time.perf_counter() - started, 2),
                }
                aot = getattr(self.generator, "_aot", None)
                if aot is not None:
                    out["aot"] = aot.stats()
                return out

            return await loop.run_in_executor(self._executor, _warm)
        return await loop.run_in_executor(
            self._executor, lambda: self.generator.precompile_grid(level)
        )

    async def add_prefix(self, text: str) -> int:
        """Register a shared prompt prefix (generator.add_shared_prefix)
        on the decode worker: safe while serving — registration only
        allocates pages and updates the cache functionally.  Programs for
        the new prefix's buckets compile in-band on their first waves
        (restart to fold them into the warmup grid).

        Under the continuous scheduler nothing is registered (0): the
        mixed program reads no registered prefix, and the block-hash
        store already reuses any template's preamble."""
        if self._sched is not None:
            return 0
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            self._executor, lambda: self.generator.add_shared_prefix(text)
        )

    def _refused_by_scheduler(self) -> ValueError:
        """What a guided or LoRA request is told under the continuous
        scheduler, whose mixed program has neither path."""
        model = self.generator.config
        return ValueError(
            "guided decoding and LoRA adapters are not supported in "
            "continuous scheduler mode (sched_mode=continuous)"
            + (
                f", the only mode that serves model {model.name!r} "
                f"({model.family} family: {model.continuous_only})"
                if getattr(model, "continuous_only", None) else ""
            )
        )

    async def ensure_guided(self, spec: tuple) -> None:
        """Build (and cache) the automaton for a guided spec; raises
        ValueError on bad specs or unsupported engine configs.

        The build (regex NFA + subset construction, seconds for a novel
        spec) runs on the loop's default executor — NOT inline (it would
        stall every HTTP connection) and NOT on the dedicated decode
        thread (it would delay decode steps queued behind it);
        ``_guided_lock`` makes the cache safe across threads.  The inline
        probe keeps cache-hit submits (the common case: validation
        already built the spec) off the shared executor, where one slow
        novel build would queue them.  Concurrent callers with the same
        novel spec piggyback on ONE in-flight build (shielded, so a
        cancelled waiter never kills the build for the others) instead of
        occupying one executor thread each.  The single entry point for
        both submit (generate) and HTTP validate paths, so build
        scheduling can never diverge between them."""
        if self._sched is not None:
            raise self._refused_by_scheduler()
        if self.generator._automaton_cached(spec):
            return
        build = self._guided_builds.get(spec)
        if build is None:
            build = asyncio.get_running_loop().run_in_executor(
                None, self.generator._ensure_automaton, spec
            )
            self._guided_builds[spec] = build

            def _done(fut: "asyncio.Future") -> None:
                self._guided_builds.pop(spec, None)
                if not fut.cancelled():
                    fut.exception()  # retrieved even with zero waiters left

            build.add_done_callback(_done)
        await asyncio.shield(build)

    async def generate(
        self,
        prompt: str,
        params: Optional[SamplingParams] = None,
        *,
        on_partial: Optional[Any] = None,
        priority: int = 0,
        resume_tokens: Optional[list] = None,
    ) -> GenerationResult:
        """Generate; ``on_partial(token_ids_so_far)`` (if given) fires on the
        event loop after each decode block while the request is generating —
        the streaming feed for the completion API (serving/httpserver.py).

        ``priority`` orders ADMISSION only (higher first, FIFO within a
        class): the operator pipeline uses 10 so external API callers on the
        shared engine can never starve incident analysis.  Already-admitted
        and backpressured-in-hand requests are not preempted.

        ``resume_tokens`` resumes a failed-over stream mid-token: the
        already-generated ids are re-prefilled verbatim after the prompt
        (cheap under the prefix cache) and the result carries ONLY the
        continuation — the caller owns stitching checkpoint + result.
        Continuous scheduler mode only."""
        if self._closed:
            raise RuntimeError("serving engine is closed")
        if self._gave_up:
            # the reset budget is a RATE limit, not a death sentence: once
            # the window has drained, the next caller may revive the engine
            # (the unsupervised path already recovers this way via lazy
            # _try_recover).  Staying _gave_up forever with green probes
            # would brick the AI leg until a human deletes the pod.
            now = time.monotonic()
            in_window = [
                t for t in self._reset_times
                if now - t < self.RESET_WINDOW_S
            ]
            if len(in_window) < self.MAX_RESETS_PER_WINDOW and not self._closed:
                if self._error is not None:
                    await self._try_recover()
                # revalidate after the recovery await: a concurrent failure
                # may have re-armed _error/_gave_up while we suspended —
                # only revive from a state observed AFTER the await
                if self._gave_up and self._error is None:
                    self._gave_up = False
            if self._gave_up:
                raise RuntimeError(
                    "serving engine is down (supervisor reset budget exhausted)"
                ) from self._error
        if self._supervisor is None:
            # unsupervised: lazy recovery on the next caller (pre-supervisor
            # semantics).  Supervised engines restart proactively — a death
            # observed here is mid-restart, and the queue survives it.
            if self._error is not None:
                await self._try_recover()
            if self._error is not None:
                raise RuntimeError("serving engine loop died") from self._error
        if params is not None and params.guided_choice is not None \
                and params.guided_regex is not None:
            raise ValueError("guided_choice and guided_regex are mutually exclusive")
        if params is not None:
            # refused at SUBMIT, to this caller, as the scheduler would
            check_denoise(params, getattr(self.generator.config, "block_length", 0))
        if self._sched is not None:
            if params is not None and (
                params.guided_choice is not None
                or params.guided_regex is not None
                or params.adapter is not None
            ):
                # refused at SUBMIT (to this caller) rather than inside
                # the serve loop
                raise self._refused_by_scheduler()
        else:
            # reject unknown adapters at SUBMIT time: a bad name surfacing
            # as a ValueError inside the serve loop's admit would fail the
            # whole co-batched wave and kill the loop — one misconfigured
            # AIProvider CR must never take down serving for everyone
            adapter = (params.adapter if params is not None else None)
            if adapter is not None and adapter not in self.generator._adapter_ids:
                raise ValueError(
                    f"unknown LoRA adapter {adapter!r}; registered: "
                    f"{self.generator.adapter_names}"
                )
            if resume_tokens:
                raise ValueError(
                    "token-level streaming resume requires the continuous "
                    "scheduler (sched_mode=continuous)"
                )
        if params is not None and params.deadline is not None:
            # fail-fast at submit: a budget that cannot fit ONE decoded
            # token must not consume a queue slot, a prefill, or KV pages.
            # Truncation is NOT applied here — admission re-runs the policy
            # with post-queue-wait residue and owns the clamp.
            _, outcome = self.generator.deadline_policy(params)
            if outcome == "rejected":
                self.generator.metrics.incr("admission_deadline_rejected")
                raise DeadlineExceeded(
                    "deadline budget cannot fit any decoded output "
                    f"(remaining {max(0.0, params.deadline - self.generator._clock()):.3f}s)"
                )
        if self._sched is None:
            guided_spec = self.generator._guided_spec(params)
            if guided_spec is not None:
                # builds+caches the automaton; raises ValueError here (to
                # THIS caller) on bad specs or unsupported engine configs
                await self.ensure_guided(guided_spec)
        if self.fabric is not None and self._sched is not None:
            # fleet KV fabric: pull the prompt's missing prefix blocks
            # from a peer's host pool BEFORE admission so the scheduler's
            # prefix match restores them instead of recomputing.  Best
            # effort, residual-budget clamped — a failed fetch degrades
            # to the ordinary recompute with at most the fetch budget
            # spent, never an error to this caller.
            await self._fabric_prefetch(prompt, params, resume_tokens)
        if self._task is None:
            await self.start()
        future: asyncio.Future = asyncio.get_running_loop().create_future()
        if on_partial is not None:
            self._partial_by_future[future] = on_partial
        # one obs span per engine request (joins the ambient analysis /
        # HTTP trace; detached no-op outside one): the queue-wait vs
        # compute split below is how a decode stall becomes attributable
        # — the result's prefill/decode times are chip-side, the rest of
        # the wall time was spent waiting for a slot/pages/the low lane
        submitted = time.perf_counter()
        # per-class SLO accounting (obs/sloledger.py SLOBoard): every
        # submit is counted, and the finally guarantees exactly one
        # settle per submit — a cancelled/errored request is a miss, so
        # /healthz attainment can never read better than reality
        slo_cls = (params.slo_class if params is not None else None) or "default"
        self._slo_board.submitted(slo_cls)
        slo_settled = False
        try:
            with obs_span("engine.generate", priority=priority) as span_:
                if priority <= 0:
                    await self._low_lane.acquire()  # released when the entry is popped
                await self._queue.put((
                    -priority, next(self._seq),
                    _Request(
                        prompt, params or SamplingParams(), future, priority,
                        submitted=submitted,
                        resume_tokens=(
                            list(resume_tokens) if resume_tokens else None
                        ),
                    ),
                ))
                # the put may have landed after close()/loop-death drained the
                # queue; _closed/_error were set before the drain, so re-checking
                # here closes that window.  A supervised engine's queue SURVIVES
                # a loop death (the supervisor requeues, new arrivals wait), so
                # only _gave_up is terminal there.
                dead = self._closed or self._gave_up or (
                    self._error is not None and self._supervisor is None
                )
                if dead and not future.done():
                    self._partial_by_future.pop(future, None)
                    future.set_exception(RuntimeError("serving engine is closed"))
                result = await future
                # span timings are COPIED from the result, whose decode/queue
                # numbers are derived from the step clock + measured admission
                # wait — the span and the step records share one source of
                # truth and cannot disagree (the old wall-minus-compute
                # inference could).  The same values feed the latency
                # histograms (docs/METRICS.md "Histograms").
                metrics = self.generator.metrics
                metrics.observe("queue_wait_milliseconds", result.queue_wait_ms)
                metrics.observe(
                    "ttft_milliseconds", result.queue_wait_ms + result.prefill_ms
                )
                if result.completion_tokens > 0:
                    metrics.observe(
                        "token_latency_milliseconds",
                        result.decode_ms / result.completion_tokens,
                    )
                # attained = finished with output inside its own deadline;
                # deadline-free requests attain by completing at all
                attained = result.finish_reason != "deadline" and (
                    params is None or params.deadline is None
                    or self.generator._clock() <= params.deadline
                )
                self._slo_board.finished(
                    slo_cls, attained=attained,
                    tokens=result.completion_tokens,
                )
                slo_settled = True
                span_.set(
                    queue_wait_ms=round(result.queue_wait_ms, 3),
                    prefill_ms=round(result.prefill_ms, 3),
                    decode_ms=round(result.decode_ms, 3),
                    prompt_tokens=result.prompt_tokens,
                    completion_tokens=result.completion_tokens,
                    finish_reason=result.finish_reason,
                )
                return result
        finally:
            if not slo_settled:
                self._slo_board.finished(slo_cls, attained=False, tokens=0)

    # ------------------------------------------------------------------
    async def _run(self) -> None:
        try:
            await self._serve()
        except asyncio.CancelledError:
            raise
        except Exception as exc:  # generator/device failure: fail fast, loudly
            log.exception("serving engine loop died")
            self._error = exc
            if self._supervisor is not None and not self._closed:
                # keep the in-flight requests: the supervisor resets the
                # engine and requeues them (once) instead of failing them
                self._supervise_wakeup.set()
            else:
                self._fail_outstanding(exc)

    def _sweep_batch(self, batch: "list[_Request]") -> None:
        """Drop requests whose callers vanished while QUEUED — no point
        tokenizing, granting pages, and prefilling a dead request ahead
        of live ones.  Deadline-carrying entries that EXPIRED while
        queued are failed here for the same reason: their budget is gone
        before any chip time was spent.  In-place (batch aliases
        ``_inflight``)."""
        now = self.generator._clock()
        live = []
        for request in batch:
            future = request.future
            if future.done():
                self._partial_by_future.pop(future, None)
                continue
            deadline = request.params.deadline
            if deadline is not None and deadline <= now:
                self._partial_by_future.pop(future, None)
                self.generator.metrics.incr("admission_deadline_rejected")
                future.set_exception(DeadlineExceeded(
                    "deadline expired while queued for admission"
                ))
                continue
            live.append(request)
        batch[:] = live

    async def _serve_sched(self) -> None:
        """The continuous-batching serve loop (serving/sched/): every
        popped request is handed to the scheduler immediately — admission
        is token-level inside :meth:`Scheduler.step`, so there is no
        admission window, no wave formation, and no backpressure retry
        machinery here; ``_pending`` is keyed by scheduler req id."""
        loop = asyncio.get_running_loop()
        sched = self._sched
        assert sched is not None
        annotate = self.generator._annotation
        # the scheduler's host queue is unbounded: cap the handoff so
        # overflow stays in THIS bounded priority queue (max_queue via
        # the low lane keeps gating external callers, and a late
        # high-priority arrival can still jump the un-drained tail)
        handoff = max(2 * self.generator.max_slots, 16)
        while not self._closed:
            batch = self._inflight
            if not batch and sched.total_work == 0 and self._queue.empty():
                # fully idle: block until a request arrives
                batch.append(self._unwrap(await self._queue.get()))
            while (
                not self._queue.empty()
                and sched.queue_depth + len(batch) < handoff
            ):
                batch.append(self._unwrap(self._queue.get_nowait()))
            if batch:
                self._sweep_batch(batch)
            if batch:
                requests = list(batch)

                def _enqueue_all(requests=requests):
                    out = []
                    # on the worker thread, between two steps: tokenising
                    # lands in the step record's turn_ms
                    with annotate("podmortem.sched.enqueue", n=len(requests)):
                        for request in requests:
                            try:
                                out.append((request, sched.enqueue(
                                    request.prompt, request.params,
                                    submitted=request.submitted or None,
                                    priority=request.priority,
                                    resume_tokens=request.resume_tokens,
                                ), None))
                            except Exception as exc:  # noqa: BLE001 - per-request verdict
                                out.append((request, None, exc))
                    return out
                # the hand-off as the event loop sees it: over to the
                # worker thread, its ``podmortem.sched.enqueue``, back, and
                # the bookkeeping here
                with annotate("podmortem.serve.enqueue", n=len(requests)):
                    enqueued = await loop.run_in_executor(
                        self._executor, _enqueue_all
                    )
                    batch.clear()
                    for request, req_id, exc in enqueued:
                        if exc is not None:
                            self._partial_by_future.pop(request.future, None)
                            if not request.future.done():
                                request.future.set_exception(exc)
                            continue
                        self._pending[req_id] = request
                        callback = self._partial_by_future.pop(
                            request.future, None
                        )
                        if callback is not None:
                            self._partial_cbs[req_id] = (
                                callback, request.future,
                            )
                            self._partial_sent.pop(req_id, None)
            if sched.total_work:
                # reclaim rows whose callers are gone (disconnects):
                # per-token recycling frees their slot + pages THIS step
                cancelled = [
                    (req_id, request)
                    for req_id, request in self._pending.items()
                    if request.future.cancelled()
                ]
                if cancelled:
                    await loop.run_in_executor(
                        self._executor,
                        lambda: [sched.cancel(r) for r, _ in cancelled],
                    )
                    for req_id, request in cancelled:
                        # identity revalidation after the executor await:
                        # only reap the entry we observed — the id may have
                        # been reaped elsewhere while the cancel ran
                        if self._pending.get(req_id) is not request:
                            continue
                        self._pending.pop(req_id, None)
                        self._partial_cbs.pop(req_id, None)
                        self._partial_sent.pop(req_id, None)
            if sched.total_work:
                # the whole call as the event loop sees it: the worker
                # thread's phase spans lie inside, so what this one alone
                # covers is the hand-over to the worker and the hand-back
                with annotate("podmortem.serve.step"):
                    step_call = loop.run_in_executor(
                        self._executor, sched.step
                    )
                    if self._supervisor is not None:
                        # same stall watchdog as the wave loop: one mixed
                        # dispatch making no progress within the budget
                        # means the device is wedged, not merely slow
                        try:
                            outcomes = await asyncio.wait_for(
                                step_call, self._supervisor.stall_timeout_s
                            )
                        except asyncio.TimeoutError:
                            self._stalled = True
                            raise EngineStalled(
                                f"mixed dispatch made no progress in "
                                f"{self._supervisor.stall_timeout_s:.1f}s"
                            ) from None
                    else:
                        outcomes = await step_call
                with annotate("podmortem.serve.outcomes", n=len(outcomes)):
                    for outcome in outcomes:
                        self._partial_cbs.pop(outcome.req_id, None)
                        self._partial_sent.pop(outcome.req_id, None)
                        request = self._pending.pop(outcome.req_id, None)
                        if request is None or request.future.done():
                            continue
                        if outcome.error is not None:
                            request.future.set_exception(outcome.error)
                        else:
                            request.future.set_result(outcome.result)
            # the loop's turn: stream deliveries and whoever else is due
            # run here, between two steps
            with annotate("podmortem.serve.turn"):
                await asyncio.sleep(0)

    async def _serve(self) -> None:
        if self._sched is not None:
            return await self._serve_sched()
        loop = asyncio.get_running_loop()
        while not self._closed:
            # requests live in self._inflight between queue pop and slot
            # admission so cancellation/crash cleanup can always see them
            batch = self._inflight
            leftover = bool(batch)  # backpressured from an earlier round
            if not batch and self.generator.num_active == 0 and self._queue.empty():
                # fully idle: block until a request arrives (never while
                # backpressured requests are already waiting in hand)
                batch.append(self._unwrap(await self._queue.get()))
            total_free = len(self.generator.free_slots())
            stalled = self._page_stalled(batch)
            if (
                len(batch) < total_free
                and not stalled
                and (not self._queue.empty() or (batch and not leftover))
            ):
                # tiny window lets concurrent arrivals share one prefill
                # (32 events -> one prefill, BASELINE config 4).  Skipped
                # when the batch is page-stalled leftovers with no fresh
                # arrivals: sleeping then would throttle decode for every
                # active sequence exactly when the engine is most loaded
                await asyncio.sleep(self.admission_wait_s)
                while len(batch) < total_free and not self._queue.empty():
                    batch.append(self._unwrap(self._queue.get_nowait()))
            if batch:
                self._sweep_batch(batch)
            if batch and not stalled:
                admitted = await self._admit(batch)
                # paged backpressure: requests beyond the KV free list stay
                # in _inflight and retry as decode frees pages
                # graftlint: disable=GL011 reason=_serve is the engine's sole consumer task; _inflight is its working set and the cleanup paths (close/crash) only run after this loop has exited
                self._inflight = batch[admitted:]
                allocator = getattr(self.generator, "allocator", None)
                # record a stall only while active sequences hold pages —
                # their release is the retry trigger; with nothing active
                # (e.g. after an oversized head was failed) retry freely
                self._stalled_avail = (
                    allocator.available
                    if (self._inflight and allocator is not None
                        and self.generator.num_active > 0)
                    else None
                )

            if self.generator.num_active:
                # reclaim slots whose callers are gone (disconnects /
                # timeouts): an abandoned request must not decode to
                # max_tokens holding a slot and its KV pages
                cancelled = [
                    (slot_id, request)
                    for slot_id, request in self._pending.items()
                    if request.future.cancelled()
                ]
                if cancelled:
                    freed = await loop.run_in_executor(
                        self._executor,
                        lambda: [self.generator.cancel(s) for s, _ in cancelled],
                    )
                    for (slot_id, request), reclaimed in zip(cancelled, freed):
                        # a chunk-prefilling (reserved) slot can't be
                        # cancelled mid-job: KEEP its future so the sweep
                        # catches it once the wave activates.  Identity
                        # revalidation after the executor await: slots are
                        # reused, so only reap the entry we observed — a
                        # freed slot re-admitted while cancel ran must not
                        # lose its fresh future
                        if reclaimed and self._pending.get(slot_id) is request:
                            self._pending.pop(slot_id, None)
                            self._partial_cbs.pop(slot_id, None)
                            self._partial_sent.pop(slot_id, None)
            if self.generator.num_active:
                step_call = loop.run_in_executor(
                    self._executor, self.generator.step
                )
                if self._supervisor is not None:
                    # stall watchdog: a step that outlives the budget means
                    # the device (not the host) is wedged.  The worker
                    # thread cannot be interrupted — it is ABANDONED (the
                    # supervisor swaps executors) and the loop dies into
                    # the supervised-restart path.
                    try:
                        finished = await asyncio.wait_for(
                            step_call, self._supervisor.stall_timeout_s
                        )
                    except asyncio.TimeoutError:
                        self._stalled = True
                        raise EngineStalled(
                            f"decode step made no progress in "
                            f"{self._supervisor.stall_timeout_s:.1f}s"
                        ) from None
                else:
                    finished = await step_call
                for slot_id, result in finished:
                    self._partial_cbs.pop(slot_id, None)
                    self._partial_sent.pop(slot_id, None)
                    request = self._pending.pop(slot_id, None)
                    if request is not None and not request.future.done():
                        result.queue_wait_ms = request.queue_wait_ms
                        request.future.set_result(result)
            await asyncio.sleep(0)

    async def _admit(self, batch: "list[_Request]") -> int:
        """Admit as much of ``batch`` as fits; returns the admitted count."""
        prompts = [request.prompt for request in batch]
        params = [request.params for request in batch]
        # queue wait ends when admission (prefill included) begins
        admitted_t = time.perf_counter()
        try:
            admit_call = asyncio.get_running_loop().run_in_executor(
                self._executor, lambda: self.generator.admit(prompts, params)
            )
            if self._supervisor is not None:
                # the batched prefill is device work too — a wedge here is
                # the same fault class the step watchdog guards, and the
                # largest single dispatch; without a bound it would hang
                # the serve loop (and every caller) forever
                try:
                    slot_ids = await asyncio.wait_for(
                        admit_call, self._supervisor.stall_timeout_s
                    )
                except asyncio.TimeoutError:
                    self._stalled = True
                    raise EngineStalled(
                        f"batched prefill made no progress in "
                        f"{self._supervisor.stall_timeout_s:.1f}s"
                    ) from None
            else:
                slot_ids = await admit_call
        except OversizedRequest as exc:
            # only the head request is impossible; fail it alone and let
            # the rest retry next round
            future = batch[0].future
            self._partial_by_future.pop(future, None)
            if not future.done():
                future.set_exception(exc)
            return 1
        except BaseException as exc:
            if self._supervisor is not None and not isinstance(
                exc, asyncio.CancelledError
            ):
                # leave the batch in _inflight: the loop death this raise
                # becomes is supervised, and the restart requeues them
                raise
            # the batch futures are out of the queue but not yet in
            # _pending — fail them here or their callers hang forever
            for request in batch:
                self._partial_by_future.pop(request.future, None)
                if not request.future.done():
                    request.future.set_exception(exc)
            raise
        for slot_id, request in zip(slot_ids, batch):
            if request.submitted:
                request.queue_wait_ms = max(
                    0.0, (admitted_t - request.submitted) * 1e3
                )
            self._pending[slot_id] = request
            callback = self._partial_by_future.pop(request.future, None)
            if callback is not None:
                # future travels with the callback so the worker-side hook
                # can drop deltas once the streaming client is gone
                self._partial_cbs[slot_id] = (callback, request.future)
                self._partial_sent.pop(slot_id, None)
        return len(slot_ids)
