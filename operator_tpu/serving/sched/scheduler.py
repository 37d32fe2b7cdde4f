"""The continuous-batching scheduler: schedule → dispatch → commit.

An explicit per-step loop over ONE ragged mixed-phase program, in place
of the wave engine's implicit phase machinery (batched prefill dispatches
+ fixed decode blocks), standing on the device state of a
``serving/runtime.py`` ``Runtime``:

- **schedule** (:meth:`Scheduler._schedule`) — form this step's ragged
  wave: every decode row contributes its next token (or a prompt-lookup
  speculation verify chunk, below), every prefill row contributes its
  next chunk (Sarathi-style: at most ``chunk`` tokens, so a prompt
  storm stalls in-flight decodes for at most one chunk's compute per
  step), and queued requests are admitted into the RUNNING wave the
  moment a slot + pages free up — token-level admission, no block
  boundary, no admission window;
- **dispatch** (:meth:`Scheduler._dispatch`) — pack the wave onto the
  flat token axis and run the one compiled mixed program
  (``sched/mixed.py`` + ``ops/ragged_attention.py``), WITHOUT waiting
  for it;
- **commit** (:meth:`Scheduler._commit_oldest`) — fetch a dispatched
  step's sampled tokens (the step's ONE host sync), advance rows, and
  recycle a finished row's slot and KV pages THIS step — not
  ``decode_block - 1`` junk tokens later — so the next step's admission
  can reuse them.

**Decode-ahead pipelining** (``pipeline_depth`` > 1, the wave engine's
in-flight-blocks discipline transplanted): dispatch and commit are
decoupled through a bounded in-flight queue, so step N+1 is planned
from PREDICTED row state (``_Row.pred_*``: authoritative + in-flight
deltas) and dispatched while step N's sampled tokens are still on
device.  A chained decode row's input id never visits the host — the
program substitutes its carried per-slot ``latest`` sample buffer
(``from_prev``) — so only accepted token ids ever cross the host
boundary, at commit, asynchronously.  The replan path is conservative:
a commit that invalidates a prediction (finish, cancel) releases the
row immediately, later in-flight work for it commits as a no-op
(``podmortem_sched_pipeline_voided_total``), and admission only ever
consumes authoritatively-freed slots and pages.  Stale KV writes from
voided work are safe by construction: device execution is serialised by
the donated paged-cache dependency, so a re-granted page's new owner
writes every position it will ever read AFTER the voided write lands.

**Prompt-lookup self-speculation** (``spec_decode``, sched/draft.py): a
greedy decode row with no in-flight work proposes up to
``spec_lookup_k`` draft tokens from its own prompt+generated context
and verifies them as ONE ``q_count = k + 1`` row; the commit accepts
the longest sample-confirmed prefix (``accept + 1`` tokens per host
round-trip), byte-identical to one-token greedy decoding by
construction.

**Block-hash prefix caching** (``kvstore=``, serving/kvstore.py): at
admission the request's longest cached block chain is matched and those
STORE-OWNED device pages are mapped into the row's page table read-only
(refcounted); the row's ``pos`` starts at ``cached_len``, so only the
uncached suffix prefills — the ragged program already handles arbitrary
per-row q_count, a hit is just a shorter chunk.  At prefill completion
the row donates its full prompt blocks' pages to the store (ownership
transfer, no copy).  When admission needs pages, LRU refcount-zero
blocks are evicted; with a host pool (ops/kv_transfer.py) the page's KV
is gathered on device at eviction (no sync) and fetched to host inside
the commit step's existing sync window, restorable later with one DMA.
Greedy output is byte-identical cache-on vs cache-off: KV vectors are
per-token projections, independent of how the prompt was chunked.

**Rows that denoise blocks** (a model with ``block_length``, models/
sdar.py): a generating row is in step ``s`` of a block of ``N`` positions.
Its step packs the block's ``N`` query tokens (after, in a block's first
step, the ``N`` clean ids of the block before, which write that block's
final keys), samples all of them and keeps a few (``sched/mixed.py``); the
prompt's whole blocks are prefilled in chunks that end on block boundaries
and its tail opens the first generated block.  With ``denoise_steps``
fixed a request, every count the host packs is known ahead
(``sched/types.py BlockSchedule``), so decode-ahead pipelining stays: the
per-slot carry is the block as the last step left it, and ``from_prev``
tokens read it.  The commit takes what a step kept, streams a position
once everything left of it is kept, and cuts the answer at ``max_tokens``
and at EOS.  Speculation and the prefix store are switched off for such a
model by the scheduler itself.

Counters (docs/METRICS.md): ``podmortem_sched_admitted_midwave_total``,
``podmortem_sched_chunked_prefill_total``,
``podmortem_sched_recycled_slot_total``,
``podmortem_sched_stall_free_step_total``,
``podmortem_sched_stall_step_total``,
``podmortem_sched_pipeline_dispatch_ahead_total``,
``podmortem_sched_pipeline_voided_total``,
``podmortem_spec_rounds_total``, ``podmortem_spec_proposed_total``,
``podmortem_spec_accepted_total``, ``podmortem_spec_rest_total``,
``podmortem_sample_wide_steps_total``,
``podmortem_kv_hit_total``, ``podmortem_kv_miss_total``,
``podmortem_kv_evict_total``, ``podmortem_kv_offload_total``,
``podmortem_kv_restore_total``,
``podmortem_kv_prefill_tokens_saved_total``,
``podmortem_unmasked_tokens_total``.
"""

from __future__ import annotations

import dataclasses
import itertools
import logging
import time
from collections import deque
from typing import Any, Optional

import numpy as np

from ...ops import latent_attention
from ...ops.ragged_attention import (
    kv_block_pages,
    kv_blocks_walked,
    query_tile_rows,
    query_tiles,
)
from ..runtime import Runtime
from ..types import (
    DeadlineExceeded,
    GenerationResult,
    OversizedRequest,
    SamplingParams,
    ShedLowValue,
    _Slot,
    check_denoise,
    pages_needed,
    prompt_budget,
)
from .draft import PromptLookupDraft
from .types import BlockSchedule, RowWork, StepOutcome, StepPlan, _Row

log = logging.getLogger(__name__)

__all__ = ["Scheduler"]


def _kv_walk(
    kv_len: np.ndarray, q_count: np.ndarray, page_size: int,
    window: Optional[int] = None,
) -> tuple[np.ndarray, int]:
    """What ONE layer's ragged-attention call does with these per-slot
    arrays, by the kernel's own rule (``ops/ragged_attention.py``
    ``_ragged_attn_kernel``): a slot with ``q_count > 0`` walks pages
    ``first .. cdiv(kv_len, page_size)``, the others none, and a sliding
    window skips the pages wholly before the earliest position any of
    the row's queries can see.  Returns (KV pages each slot's walk
    takes, query-key pairs scored = ``q_count`` x the positions on the
    walked pages up to ``kv_len``)."""
    kv = kv_len.astype(np.int64)
    count = q_count.astype(np.int64)
    first = np.zeros_like(kv)
    if window is not None:
        first = np.maximum(kv - count - window + 1, 0) // page_size
    pages = np.where(count > 0, np.maximum(-(-kv // page_size) - first, 0), 0)
    seen = np.maximum(kv - first * page_size, 0)
    return pages, int((count * seen).sum())


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-uncommitted step: the plan and the device-side
    result references (token samples + per-slot accepted-draft counts)
    the commit will fetch."""

    plan: StepPlan
    toks: Any  # device [B, W] sampled token ids
    accept: Any  # device [B] accepted-draft counts
    counts: dict  # the step record's work counts (_Packed.counts)
    #: the ``seq`` this step's record will get (commits are in order and
    #: each appends one record) — what its host spans carry as ``step``
    seq: int = 0
    held_rows: int = 0
    #: device [2] of a model with experts: experts given a token (summed
    #: over layers), the fullest expert's tokens; None for any other
    moe: Any = None


@dataclasses.dataclass
class _Packed:
    """A plan packed onto the program's host-side input arrays, with the
    work counts the step record and the dispatch span carry — counted
    HERE, from the very ``kv_len`` / ``q_count`` the kernel is given."""

    # the flat token axis, [t_budget] each
    ids: np.ndarray
    rows: np.ndarray
    pos: np.ndarray
    valid: np.ndarray
    in_row: np.ndarray
    from_prev: np.ndarray
    # per slot, [slots] each
    q_start: np.ndarray
    q_count: np.ndarray
    sample_start: np.ndarray
    spec_len: np.ndarray
    temp: np.ndarray
    top_p: np.ndarray
    kv_len: np.ndarray
    #: per slot, for a model that denoises blocks (None for any other):
    #: ``keep``, ``limit``, ``low_confidence`` (``sched/mixed.py``)
    denoise: Optional[dict]
    #: some row carries a draft (``spec_len.any()``, the predicate the
    #: compiled step branches on): the head and the sampler run wide
    wide: bool
    #: the step record's work counts (``StepRecord`` field names)
    counts: dict
    #: query-key pairs the attention scores: the sum over the slots it
    #: walks of ``q_count x (kv positions walked)`` — on the dispatch
    #: span, for the roofline's operation count
    qk_pairs: int


class Scheduler:
    """Continuous-batching scheduler over a :class:`Runtime`
    (serving/runtime.py): every name it reads off ``self.generator`` is
    one that class defines.  Guided decoding and LoRA requests are
    refused at submit.
    """

    def __init__(
        self,
        generator: Runtime,
        *,
        chunk: int = 64,
        token_budget: int = 0,
        pipeline_depth: int = 1,
        spec_decode: bool = False,
        spec_lookup_k: int = 4,
        kvstore: Optional[Any] = None,
        queue_limit: int = 0,
        overload_policy: Optional[Any] = None,
        fabric_mirror: bool = False,
        audit_hook: Optional[Any] = None,
    ) -> None:
        if kvstore is not None and kvstore.page_size != generator.page_size:
            raise ValueError(
                f"kvstore page_size={kvstore.page_size} != generator "
                f"page_size={generator.page_size}: block hashes would not "
                f"align with KV pages"
            )
        self.generator = generator
        #: what this model cannot have, switched off HERE whatever the
        #: configuration asked for, with the reason (``/healthz``
        #: ``features``): a recurrent state per slot can be neither
        #: restored by a prefix hit (the pages map, the recurrence does
        #: not) nor rolled back past a rejected draft
        self.switched_off: dict[str, str] = {}
        model = generator.config
        self._recurrent = bool(getattr(model, "recurrent_state", False))
        #: times a token takes the layer stack in the mixed step
        #: (``StepRecord.passes``, the dispatch span's ``passes``)
        self._passes = int(getattr(model, "total_ut_steps", 1))
        #: positions of a block a row denoises a step (models/sdar.py); 0
        #: for a model whose step commits one token a row
        self._block = int(getattr(model, "block_length", 0))
        #: a model with experts: its steps count ``moe_tokens`` and bring
        #: two expert counters back
        self._moe = bool(getattr(model, "num_experts", 0))
        #: layers of the gated delta rule (models/olmo_hybrid.py): the
        #: depth the delta kernel's cost is counted over; 0 for the others
        self._delta_layers = int(getattr(model, "delta_layers", 0))
        #: a model with a latent cache (models/deepseek_v2.py): its steps
        #: walk the latent attention kernel's pages, not the ragged one's
        self._latent = getattr(model, "latent_dims", None) is not None
        #: layers with experts, the depth the expert kernel's cost is
        #: counted over (every layer of SDAR; DeepSeek-V2's dense layers
        #: have none); 0 for a model without experts
        self._moe_layers = (
            int(getattr(model, "moe_layers", model.num_layers)) if self._moe else 0
        )
        if self._block:
            if spec_decode:
                self.switched_off["spec_decode"] = (
                    "a row that denoises a block of positions a step has no "
                    "next token to draft"
                )
                spec_decode = False
            if kvstore is not None:
                self.switched_off["kv_prefix_cache"] = (
                    "under the block-causal mask a cached page's keys depend "
                    "on where the prompt's blocks end"
                )
                kvstore = None
        if self._recurrent:
            if spec_decode:
                self.switched_off["spec_decode"] = (
                    "a rejected draft shrinks the KV lengths but cannot roll "
                    "a recurrent state back"
                )
                spec_decode = False
            if kvstore is not None:
                self.switched_off["kv_prefix_cache"] = (
                    "a prefix hit maps KV pages but cannot restore a "
                    "recurrent state"
                )
                kvstore = None
        for feature, why in self.switched_off.items():
            log.warning(
                "%s is OFF for model %r (%s family): %s",
                feature, model.name, model.family, why,
            )
        self.chunk = max(1, min(chunk, generator.max_seq))
        #: pages a flash update of the ragged kernel folds in, by query
        #: tile: the kernel's own rule on the static shapes it is given
        #: (the latent attention kernel's, for a model with a latent cache)
        if self._latent:
            self._kv_block_of = {
                tile: latent_attention.block_pages(
                    tile, heads=model.num_heads, page_size=generator.page_size,
                )
                for tile in latent_attention.query_tiles(self.chunk)
            }
        else:
            self._kv_block_of = {
                tile: kv_block_pages(
                    tile, q_per_kv=model.num_heads // model.num_kv_heads,
                    kv_heads=model.num_kv_heads, head_dim=model.head_dim,
                    page_size=generator.page_size,
                    itemsize=np.dtype(generator.cache_dtype).itemsize,
                )
                for tile in query_tiles(self.chunk)
            }
        #: the most query tokens a generating row packs a step: one, or a
        #: block led by the block before it
        row_tokens = 2 * self._block or 1
        if self._block and (self.chunk % self._block or self.chunk < row_tokens):
            raise ValueError(
                f"sched chunk={self.chunk} must be a multiple of the model's "
                f"block of {self._block} positions and hold two blocks"
            )
        self.t_budget = token_budget or max(
            self.chunk, generator.max_slots * row_tokens
        )
        if self.t_budget < generator.max_slots * row_tokens:
            # a full decode batch must always fit one step, or decode
            # rows would be starved by construction
            raise ValueError(
                f"sched token_budget={self.t_budget} < max_slots="
                f"{generator.max_slots} x {row_tokens}: a full decode batch "
                "would not fit"
            )
        if self.chunk > self.t_budget:
            raise ValueError(
                f"sched chunk={self.chunk} > token_budget={self.t_budget}"
            )
        #: bounded in-flight dispatch queue; 1 = synchronous (each step
        #: commits the dispatch it just issued, the pre-pipelining loop)
        self.depth = max(1, int(pipeline_depth))
        # a verify row is one q_count = 1 + k chunk: it must fit the
        # attention re-pack ([B, chunk]) and leave budget for peers
        k = int(spec_lookup_k) if spec_decode else 0
        self.spec_k = max(0, min(k, self.chunk - 1, self.t_budget - 1))
        #: sampled positions per slot in the mixed program (static)
        self.width = 1 + self.spec_k
        self._draft = PromptLookupDraft() if self.spec_k else None
        self._draft_ms = 0.0
        #: dispatched steps whose tokens are still on device, oldest
        #: first; bounded by ``depth``
        self._inflight: deque = deque()
        #: device [B] carry of each slot's freshest sampled token — the
        #: chaining buffer ``from_prev`` decode rows read in-program
        self._latest = None
        self._host_syncs = 0
        self._decode_committed = 0
        self.metrics = generator.metrics
        #: ``hook([(req_id, token_ids_so_far), ...])`` once a commit, with
        #: every row still generating that streamed in it, in commit order
        #: — the streaming feed (ServingEngine marshals it onto the event
        #: loop).  Called from the decode worker; never with an empty list.
        self.partial_hook: Optional[Any] = None
        # (req_id, tokens, params, submitted, priority) — admission order
        # is priority class first, then earliest deadline (EDF) within a
        # class, then FIFO (_edf_head)
        self._queue: deque = deque()
        self._rows: dict[int, _Row] = {}  # req_id -> row, insertion order
        self._next_req = itertools.count(1)
        self._kv_shadow = np.zeros((generator.max_slots,), np.int32)
        self._staged_tables: list[tuple[int, np.ndarray]] = []
        #: block-hash prefix cache (serving/kvstore.py); None = off
        self._kvstore = kvstore
        #: evicted blocks gathered on device but not yet fetched to the
        #: host pool: (hash, k_dev, v_dev) — drained inside the commit
        #: step's existing host-sync window (_drain_offload)
        self._pending_offload: list[tuple[bytes, Any, Any]] = []
        #: KV fabric mirror (operator_tpu/fabric/): copy newly-donated
        #: prompt blocks into the host pool at prefill completion so
        #: peers can fetch them over GET /kv/blocks/{hash} before
        #: eviction would have spilled them.  Gathers are eager device
        #: slices at registration; the fetch drains inside the commit
        #: step's host-sync window next to _drain_offload.
        self._fabric_mirror = bool(fabric_mirror)
        self._pending_mirror: list[tuple[bytes, Any, Any]] = []
        self._fn = None
        # host-side stats the bench reads (stats())
        self.steps = 0
        self.occupancy_sum = 0.0
        self.stall_steps = 0
        #: set to a list to record every step's ``StepPlan.trace()`` —
        #: the determinism test replays a fixed arrival trace and
        #: asserts the schedule is byte-identical
        self.plan_log: Optional[list] = None
        #: ``hook(self)`` after each step's commit window — the game-day
        #: invariant auditor's commit-barrier probe point (chaos/
        #: invariants.py checks page conservation against
        #: :meth:`page_accounting` here, while rows still hold pages)
        self.audit_hook: Optional[Any] = audit_hook
        #: queue eviction (router/value.py): when the submit queue holds
        #: ``queue_limit`` entries, enqueue sheds the LOWEST-VALUE
        #: non-protected request instead of growing without bound.
        #: 0 = unbounded (the pre-overload-control behaviour).
        self.queue_limit = max(0, int(queue_limit))
        self.overload_policy = overload_policy
        # queued requests evicted by value between steps; drained into
        # the next step()'s outcomes so callers get a terminal error
        self._evicted: list[StepOutcome] = []

    # ------------------------------------------------------------------
    # submit side
    # ------------------------------------------------------------------

    def enqueue(
        self,
        prompt: str,
        params: Optional[SamplingParams] = None,
        *,
        submitted: Optional[float] = None,
        priority: int = 0,
        resume_tokens: Optional[list[int]] = None,
    ) -> int:
        """Tokenise + queue one request; returns its req id.  Raises
        :class:`OversizedRequest` when the request can never fit the KV
        pool, ``ValueError`` for features the mixed program does not
        serve (guided decoding, LoRA).  ``submitted`` carries the
        caller's original perf_counter submit stamp (ServingEngine), so
        queue wait covers the engine handoff too, not just this queue.
        ``priority`` orders admission (higher class first); WITHIN a
        class the queue is earliest-deadline-first, so an urgent late
        arrival overtakes an earlier request with slack (_edf_head).
        ``resume_tokens`` is the token-level failover path (streaming
        resume, router/resume.py): already-generated token ids appended
        VERBATIM after the prompt, so the survivor re-prefills
        prompt+generated-so-far — cheap under the prefix cache — and the
        result's token_ids carry only the continuation."""
        g = self.generator
        params = params or SamplingParams()
        if params.guided_choice is not None or params.guided_regex is not None:
            raise ValueError(
                "guided decoding is not supported by the continuous "
                "scheduler (sched_mode=continuous); use the wave engine"
            )
        if params.adapter is not None:
            raise ValueError(
                "LoRA adapters are not supported by the continuous "
                "scheduler (sched_mode=continuous); use the wave engine"
            )
        check_denoise(params, self._block)
        ids = g.tokenizer.encode(prompt)
        # the budget formula and the runtime's truncation both engines share
        budget = prompt_budget(g.max_seq, params.max_tokens)
        if resume_tokens:
            # resumed stream: the generated suffix must survive VERBATIM
            # (the caller already streamed those tokens), so truncation
            # may only eat the prompt part
            if len(resume_tokens) >= budget:
                raise OversizedRequest(
                    f"resume checkpoint of {len(resume_tokens)} tokens "
                    f"leaves no prompt budget (budget {budget})"
                )
            tokens = (
                g._truncate_prompt(ids, budget - len(resume_tokens))
                + list(resume_tokens)
            )
        else:
            tokens = g._truncate_prompt(ids, budget)
        pool = g.allocator.num_pages - 1 - g.prefix_held_pages
        if self._pages_needed(tokens, params) > pool:
            raise OversizedRequest(
                f"request needs {self._pages_needed(tokens, params)} KV "
                f"pages, cache holds {pool}"
            )
        req_id = next(self._next_req)
        if (
            self.queue_limit
            and self.overload_policy is not None
            and len(self._queue) >= self.queue_limit
        ):
            # queue at its limit: shed the lowest-value request — which
            # may be the arrival itself — instead of growing unboundedly
            self._evict_lowest_value(req_id, params)
        self._queue.append((
            req_id, tokens, params,
            submitted if submitted is not None else time.perf_counter(),
            priority,
        ))
        return req_id

    def _request_value(self, params: SamplingParams, now: float):
        """Score one request with the shared value model (residual
        deadline on the generator's injectable clock — no wall clock,
        GL007)."""
        residual = (
            None if params.deadline is None else params.deadline - now
        )
        return self.overload_policy.model.value(
            slo_class=params.slo_class,
            residual_s=residual,
            recall_p=params.recall_p,
        )

    def _evict_lowest_value(
        self, incoming_id: int, incoming: SamplingParams
    ) -> None:
        """Shed-lowest-value-first queue eviction: score every queued
        request plus the arrival, drop the minimum non-protected one.
        A queued victim surfaces as a :class:`ShedLowValue` StepOutcome
        at the next step; the arrival itself losing raises straight to
        the caller.  All-protected queues grow instead (the ladder never
        sheds a class below its attainment target)."""
        now = self.generator._clock()
        pressure = len(self._queue) + len(self._rows)
        candidates = [(str(incoming_id), self._request_value(incoming, now))]
        by_id = {}
        for entry in self._queue:
            value = self._request_value(entry[2], now)
            candidates.append((str(entry[0]), value))
            by_id[str(entry[0])] = entry
        victim = self.overload_policy.pick_eviction(candidates)
        if victim is None:
            return  # every candidate protected: let the queue grow
        rid, value = victim
        self.overload_policy.record_eviction(
            rid, value, pressure=pressure, site="sched",
        )
        self.metrics.incr("sched_queue_evicted")
        if rid == str(incoming_id):
            raise ShedLowValue(
                f"request shed at enqueue: value score "
                f"{round(value.score, 6)} is the queue minimum at "
                f"pressure {pressure}"
            )
        entry = by_id[rid]
        self._queue.remove(entry)
        self._evicted.append(StepOutcome(entry[0], error=ShedLowValue(
            f"queued request evicted by higher-value arrival at "
            f"pressure {pressure}"
        )))

    def cancel(self, req_id: int) -> bool:
        """Drop a queued request or reclaim a live row's slot/pages now."""
        for i, entry in enumerate(self._queue):
            if entry[0] == req_id:
                del self._queue[i]
                return True
        row = self._rows.get(req_id)
        if row is None:
            return False
        self._release_row(row)
        return True

    @property
    def num_active(self) -> int:
        return len(self._rows)

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def total_work(self) -> int:
        return len(self._rows) + len(self._queue)

    def stats(self) -> dict:
        """Step-level occupancy/stall/pipelining stats."""
        proposed = self.metrics.counter("spec_proposed")
        accepted = self.metrics.counter("spec_accepted")
        rounds = self.metrics.counter("spec_rounds")
        return {
            "steps": self.steps,
            "batch_occupancy_avg": round(
                self.occupancy_sum / self.steps, 4
            ) if self.steps else None,
            "decode_stall_steps": self.stall_steps,
            "admitted_midwave": self.metrics.counter("sched_admitted_midwave"),
            "chunked_prefills": self.metrics.counter("sched_chunked_prefill"),
            "recycled_slots": self.metrics.counter("sched_recycled_slot"),
            # decode-ahead + speculation: the headline is generated
            # tokens committed per host round-trip — 1.0 is the old
            # synchronous one-token loop's ceiling
            "pipeline_depth": self.depth,
            "dispatch_ahead": self.metrics.counter(
                "sched_pipeline_dispatch_ahead"
            ),
            "voided_work": self.metrics.counter("sched_pipeline_voided"),
            "host_syncs": self._host_syncs,
            "decode_tokens_committed": self._decode_committed,
            "decode_tokens_per_host_sync": round(
                self._decode_committed / self._host_syncs, 4
            ) if self._host_syncs else None,
            "spec_decode": {
                "enabled": self._draft is not None,
                "lookup_k": self.spec_k,
                "rest_rounds": self.metrics.counter("spec_rest"),
                "verify_rounds": rounds,
                "drafts_proposed": proposed,
                "drafts_accepted": accepted,
                "acceptance_rate": round(accepted / proposed, 4)
                if proposed else None,
                "mean_accepted_per_round": round(
                    accepted / rounds, 4
                ) if rounds else None,
                "draft_overhead_ms": round(self._draft_ms, 3),
            },
            "kv_economy": (
                {
                    **self._kvstore.stats(),
                    "evictions": self.metrics.counter("kv_evict"),
                    "offloads": self.metrics.counter("kv_offload"),
                    "restores": self.metrics.counter("kv_restore"),
                    "prefill_tokens_saved": self.metrics.counter(
                        "kv_prefill_tokens_saved"
                    ),
                    "offload_pending": len(self._pending_offload),
                    "mirrored": self.metrics.counter("fabric_mirror"),
                    "mirror_pending": len(self._pending_mirror),
                }
                if self._kvstore is not None else None
            ),
        }

    def reset(self) -> None:
        """Drop every row and queued request (the supervised-restart /
        recovery path: the generator rebuilds device state separately
        and the engine has already collected the in-flight futures).
        In-flight dispatches are abandoned unfetched — their device
        buffers died with the reset device state."""
        self._queue.clear()
        self._rows.clear()
        self._kv_shadow[:] = 0
        self._staged_tables.clear()
        self._inflight.clear()
        self._latest = None
        self._pending_offload.clear()  # gathered buffers died with the device state
        self._pending_mirror.clear()
        if self._kvstore is not None:
            # every device page is gone (the generator rebuilds its
            # allocator); host-pool copies survive and stay restorable
            self._kvstore.reset()

    def spill_cache(self) -> int:
        """Evict every refcount-zero cached block off device — to the
        host pool when one is configured, else dropped.  Returns the
        number of blocks spilled.  The deterministic hook the bench and
        tests use to exercise the restored-from-host lane, and an
        operator's pre-burst page reclaim."""
        if self._kvstore is None:
            return 0
        count = len(self._kvstore.evictable())
        if count:
            self._evict_blocks(count)
        return count

    def precompile(self) -> None:
        """Compile the one mixed program before serving (an empty wave
        drives the full trace: the program's shapes are workload-
        independent by construction)."""
        plan = StepPlan()
        entry = self._dispatch(plan, self._pack(plan))
        np.asarray(entry.toks)  # block: precompile must finish warm

    # ------------------------------------------------------------------
    # the loop
    # ------------------------------------------------------------------

    def step(self) -> list[StepOutcome]:
        """One scheduler round: plan + dispatch the next ragged wave
        from predicted row state, then commit dispatched steps down to
        the pipeline bound.  Returns every request that reached a
        terminal state (result or admission error).

        ``depth == 1`` degenerates to the original synchronous loop —
        the dispatch just issued commits before the call returns.  At
        ``depth >= 2`` the dispatch for step N+1 is issued BEFORE step
        N's commit, so the chip always has a queued wave and the host's
        work hides under the device's (the step record's ``wait_ms`` is
        what is left of that slack)."""
        clock = self.generator.step_clock
        clock.enter()
        try:
            return self._step()
        finally:
            clock.leave(busy=bool(self._inflight) or self.total_work > 0)

    def _step(self) -> list[StepOutcome]:
        g = self.generator
        clock = g.step_clock
        if g.fault_plan is not None:
            # chaos seam, same site as the wave engine's step so stall /
            # device-error scenarios drive both loops identically
            g.fault_plan.apply("engine.step", active=self.num_active)
        outcomes: list[StepOutcome] = []
        if self._evicted:
            # value-based queue evictions since the last step surface as
            # terminal ShedLowValue outcomes here
            outcomes.extend(self._evicted)
            self._evicted.clear()
        # the record this plan's step will write: commits land in order,
        # one record each
        seq = clock.ring.next_seq + len(self._inflight)
        with g._annotation("podmortem.sched.plan", step=seq):
            plan = self._schedule(outcomes)
        held_rows = len(self._rows)  # snapshot BEFORE commit recycles
        if self.plan_log is not None:
            self.plan_log.append(plan.trace())
        if plan.work:
            clock.begin("pack")
            with g._annotation("podmortem.sched.pack", step=seq):
                packed = self._pack(plan)
            clock.begin("put")
            with g._annotation(
                "podmortem.sched.dispatch",
                [row.params for row in self._rows.values()],
                step=seq,
                kv_pages=packed.counts["kv_pages_walked"],
                kv_blocks=packed.counts["kv_blocks_walked"],
                qk_pairs=packed.qk_pairs,
                tokens=plan.tokens_planned,
                q_tile_rows=packed.counts["q_tile_rows"],
                # the pool planes the step writes and walks: one a pass
                # and layer
                passes=self._passes,
                kv_planes=int(g.paged_cache.k_pages.shape[0]),
                **(
                    {"state_rows": packed.counts["state_rows"]}
                    if self._recurrent else {}  # no such argument without the state
                ),
                **(
                    {"delta_layers": self._delta_layers}
                    if self._delta_layers else {}
                ),
                # the planes the latent attention kernel reads a step, and
                # the layers with experts: the depths their costs are
                # counted over
                **({"latent_layers": int(g.config.kv_planes)} if self._latent else {}),
                **({"moe_layers": self._moe_layers} if self._moe else {}),
                # a model that denoises blocks, a model with experts: the
                # same rule
                **{
                    name: packed.counts[name]
                    for name in ("block_rows", "commit_tokens", "moe_tokens")
                    if packed.counts[name] is not None
                },
            ):
                entry = self._dispatch(plan, packed, seq)
            entry.seq = seq
            entry.held_rows = held_rows
            if self._inflight:
                self.metrics.incr("sched_pipeline_dispatch_ahead")
            self._inflight.append(entry)
            # step accounting at dispatch: occupancy is HELD slots over
            # capacity (rows at any phase — the same "slots occupied"
            # definition the wave engine's batch_occupancy stage uses,
            # so the two compare like with like); a stall step is one
            # where a decode-ready row got NO token — the schedule never
            # defers decodes while token_budget >= max_slots, so the
            # counter is the proof of the property, not a mechanism
            self.steps += 1
            occupancy = held_rows / g.max_slots
            self.occupancy_sum += occupancy
            self.metrics.record("sched_occupancy", occupancy * 100.0)
            if plan.deferred_decode:
                self.stall_steps += 1
                self.metrics.incr("sched_stall_step")
            else:
                self.metrics.incr("sched_stall_free_step")
            if packed.wide:
                self.metrics.incr("sample_wide_steps")
        elif not self._inflight:
            return outcomes
        # commit down to the pipeline bound (depth - 1 stays in flight
        # across calls); with nothing to dispatch, drain one entry per
        # round — progress is guaranteed (a plan can only be empty while
        # rows/queue exist if their work is already in flight) and the
        # serve loop stays responsive to cancellation between commits
        while len(self._inflight) > self.depth - 1 or (
            self._inflight and not plan.work
        ):
            self._commit_oldest(outcomes)
            if not plan.work:
                break
        if self.audit_hook is not None:
            # commit barrier: every page granted, cached, offloaded or
            # freed this step has settled — the point where fleet-wide
            # conservation invariants must hold exactly
            self.audit_hook(self)
        return outcomes

    # -- audit ---------------------------------------------------------

    def page_accounting(self) -> dict:
        """Snapshot of where every KV page is right now — the terms of
        the page-conservation invariant the game-day auditor checks at
        commit barriers:

        ``available + row_pages + store_pages + prefix_pages == total``

        (page 0 is the reserved trash page, hence ``num_pages - 1``).
        ``row_pages`` are grants held by live rows, ``store_pages`` are
        device pages pinned by the prefix cache, ``prefix_pages`` are
        the generator's system-prefix hold."""
        g = self.generator
        return {
            "available": g.allocator.available,
            "row_pages": sum(len(row.pages) for row in self._rows.values()),
            "store_pages": (
                self._kvstore.device_pages_held
                if self._kvstore is not None
                else 0
            ),
            "prefix_pages": g.prefix_held_pages,
            "total": g.allocator.num_pages - 1,
        }

    # -- schedule ------------------------------------------------------

    def _pages_needed(self, tokens: list, params: SamplingParams) -> int:
        g = self.generator
        return pages_needed(
            len(tokens), params.max_tokens, g.max_seq, g.page_size
        )

    # -- prefix cache (serving/kvstore.py) -----------------------------

    def _match_prefix(self, tokens: list, need: int) -> list:
        """Match + acquire the longest AFFORDABLE cached block chain for
        ``tokens``.  Host-resident blocks are restored into fresh
        store-owned pages (one DMA each); LRU refcount-zero blocks are
        evicted when the row grant + restores would not fit.  Returns
        device-resident blocks with refs held; the chain shrinks from
        the tail until it fits, possibly to nothing."""
        g = self.generator
        store = self._kvstore
        chain = store.match(tokens)
        if not chain:
            return []
        store.acquire(chain)
        # a chain entry that lost both its device page and its host copy
        # ends the usable prefix (match() already breaks on those; this
        # guards the race where the host pool dropped it since)
        usable = []
        for blk in chain:
            if blk.page >= 0 or store.restorable(blk.hash):
                usable.append(blk)
            else:
                break
        if len(usable) < len(chain):
            store.release([b.hash for b in chain[len(usable) :]])
        while usable:
            restores = sum(1 for b in usable if b.page < 0)
            required = (need - len(usable)) + restores
            deficit = required - g.allocator.available
            if deficit > 0:
                self._evict_blocks(deficit)
            if required <= g.allocator.available:
                break
            dropped = usable.pop()
            store.release([dropped.hash])
        for blk in usable:
            if blk.page < 0:
                self._restore_block(blk)
        return usable

    def _evict_blocks(self, count: int) -> None:
        """Evict up to ``count`` LRU refcount-zero blocks from device.
        With a host pool, each victim's page is GATHERED into fresh
        device buffers first (an enqueued device-side copy, no sync —
        ordering guarantees the gather reads the page before any new
        owner's writes land) and queued for the commit-side offload
        drain; without one the block is simply forgotten."""
        from ...ops import kv_transfer

        g = self.generator
        store = self._kvstore
        pool = store.host_pool
        for blk in store.evict_lru(count):
            # capture the page BEFORE mark_offloaded/forget clear it on
            # the shared entry — releasing after would return -1 to the
            # free list (a leak plus a poisoned allocation)
            page = blk.page
            if pool is not None and pool.has(blk.hash):
                store.mark_offloaded(blk.hash)  # host copy already there
            elif pool is not None and pool.capacity_bytes > 0:
                k_dev, v_dev = kv_transfer.gather_page(g.paged_cache, page)
                self._pending_offload.append((blk.hash, k_dev, v_dev))
                store.pending_offload.add(blk.hash)
                store.mark_offloaded(blk.hash)
            else:
                store.forget(blk.hash)
            g.allocator.release([page])

    def _restore_block(self, blk: Any) -> None:
        """Bring an off-device block back: one freshly-allocated
        store-owned page + one DMA (from the pending-offload device
        buffers when the drain hasn't run yet, else from the host
        pool) — table writes + a page copy, never recompute."""
        from ...ops import kv_transfer

        g = self.generator
        store = self._kvstore
        page = g.allocator.allocate(1)[0]
        entry = None
        if blk.hash in store.pending_offload:
            for i, (h, k_dev, v_dev) in enumerate(self._pending_offload):
                if h == blk.hash:
                    entry = (k_dev, v_dev)
                    del self._pending_offload[i]
                    break
            store.pending_offload.discard(blk.hash)
        if entry is None:
            entry = store.host_pool.get(blk.hash)
        g.paged_cache = kv_transfer.restore_page(
            g.paged_cache, page, entry[0], entry[1]
        )
        blk.page = page
        self.metrics.incr("kv_restore")

    def _drain_offload(self) -> None:
        """Fetch gathered eviction buffers to the host pool — called
        ONLY inside the commit step's existing host-sync window, so the
        device→host readback overlaps the sync the loop already pays."""
        from ...ops import kv_transfer

        store = self._kvstore
        pool = store.host_pool
        for h, k_dev, v_dev in self._pending_offload:
            if h not in store.pending_offload:
                continue  # restored from these buffers meanwhile
            store.pending_offload.discard(h)
            dropped = pool.put(h, *kv_transfer.fetch_page(k_dev, v_dev))
            if dropped is None:
                store.forget(h)  # pool refused: the block is gone
                continue
            self.metrics.incr("kv_offload")
            for old in dropped:
                # LRU-dropped host copies: forget any index entry that
                # has no device page left either
                entry = store.get(old)
                if entry is not None and entry.page < 0:
                    store.forget(old)
        self._pending_offload.clear()

    def _drain_mirror(self) -> None:
        """Fetch mirror-gathered prompt blocks to the host pool — same
        discipline as _drain_offload: called ONLY inside the commit
        step's host-sync window.  Unlike offload the device page stays
        resident; a refused put just means peers cannot fetch it."""
        from ...ops import kv_transfer

        store = self._kvstore
        pool = store.host_pool
        for h, k_dev, v_dev in self._pending_mirror:
            if pool.has(h):
                continue  # offload drain or a peer fetch beat us to it
            dropped = pool.put(h, *kv_transfer.fetch_page(k_dev, v_dev))
            if dropped is None:
                continue  # pool refused; the block stays device-only
            self.metrics.incr("fabric_mirror")
            for old in dropped:
                entry = store.get(old)
                if entry is not None and entry.page < 0:
                    store.forget(old)
        self._pending_mirror.clear()

    def _register_row_blocks(self, row: _Row) -> None:
        """Prefill completed: donate the row's FULL prompt blocks to the
        store (ownership transfer of the device pages — no copy).  Only
        full blocks are immutable by construction (generation writes at
        positions >= prompt_len, past the last full prompt block), and
        the row keeps a reference on each donated block until release."""
        from ...ops import kv_transfer
        from ..kvstore import block_hashes

        g = self.generator
        store = self._kvstore
        ps = g.page_size
        pool = store.host_pool
        mirror = (
            self._fabric_mirror
            and pool is not None
            and pool.capacity_bytes > 0
        )
        k_full = row.prompt_len // ps
        c0 = row.cached_len // ps
        if k_full <= c0:
            return
        hashes = block_hashes(row.tokens[: k_full * ps], ps)
        transferred: set[int] = set()
        for j in range(c0, k_full):
            h = hashes[j]
            entry = store.get(h)
            page = row.pages[j - c0]
            if entry is not None and entry.page >= 0:
                # a concurrent identical prompt registered first: keep
                # the row-owned duplicate page, no transfer
                continue
            store.insert(
                h,
                hashes[j - 1] if j else None,
                row.tokens[j * ps : (j + 1) * ps],
                page,
                refs=1,
            )
            store.pending_offload.discard(h)
            transferred.add(j - c0)
            row.cached_hashes.append(h)
            if mirror and not pool.has(h):
                # eager device slice now (no sync); the host fetch waits
                # for the commit window's _drain_mirror
                k_dev, v_dev = kv_transfer.gather_page(g.paged_cache, page)
                self._pending_mirror.append((h, k_dev, v_dev))
        if transferred:
            row.pages = [
                p for i, p in enumerate(row.pages) if i not in transferred
            ]

    def _sweep_expired(self, outcomes: list[StepOutcome]) -> None:
        """Fail EVERY queued request whose deadline already expired —
        the whole queue, every step, regardless of capacity.  Checking
        only at admission would leave an expired caller hanging until a
        slot (and the head's pages) freed, where the wave path's sweep
        fails it on every loop round."""
        if not self._queue:
            return
        now = self.generator._clock()
        live = deque()
        for entry in self._queue:
            params = entry[2]
            if params.deadline is not None and params.deadline <= now:
                self.metrics.incr("admission_deadline_rejected")
                outcomes.append(StepOutcome(entry[0], error=DeadlineExceeded(
                    "deadline expired while queued for admission"
                )))
            else:
                live.append(entry)
        self._queue = live

    def _edf_head(self) -> int:
        """Index of the next request to admit: highest priority class
        first, earliest deadline within the class (EDF), FIFO among
        deadline-free peers.  Deadline-free requests sort AFTER any
        deadline in their class but are never skipped past — admission
        still stops (does not skip ahead) when the chosen head's pages
        don't fit, so a starved large request keeps its turn."""
        best = 0
        best_key = None
        for i, entry in enumerate(self._queue):
            params, priority = entry[2], entry[4]
            deadline = (
                params.deadline if params.deadline is not None
                else float("inf")
            )
            key = (-priority, deadline, i)
            if best_key is None or key < best_key:
                best, best_key = i, key
        return best

    def _admit_queued(
        self, outcomes: list[StepOutcome]
    ) -> tuple[list[int], int]:
        """Token-level admission: pull queued requests into free slots
        while pages last.  Runs at the top of EVERY step, so an arrival
        joins the running wave at the next step boundary — never waits
        for a decode block or an admission window.  Returns the admitted
        req ids and the total prompt tokens they reused from the prefix
        cache (StepPlan.cached_tokens)."""
        g = self.generator
        self._sweep_expired(outcomes)
        admitted: list[int] = []
        cached_total = 0
        while self._queue:
            free = g.free_slots()
            if not free:
                break
            head = self._edf_head()
            req_id, tokens, params, submitted, _ = self._queue[head]
            clamped, outcome = g.deadline_policy(
                params, pressure=len(self._queue) + len(self._rows)
            )
            if outcome == "shed":
                # overload ladder: lowest value at admission under storm
                del self._queue[head]
                self.metrics.incr("admission_shed")
                outcomes.append(StepOutcome(req_id, error=ShedLowValue(
                    "request shed at admission: lowest value under "
                    "overload (router/value.py ladder)"
                )))
                continue
            if outcome == "rejected":
                # expired between the check above and the policy's clock
                # read: minimal one-token clamp, as the wave path does
                clamped = dataclasses.replace(
                    params, max_tokens=1, deadline_clamped=True
                )
                outcome = "truncated"
            if outcome == "truncated":
                self.metrics.incr("admission_deadline_truncated")
            need = self._pages_needed(tokens, clamped)
            # prefix-cache match: the longest affordable cached block
            # chain replaces the head of the row's grant (store-owned
            # read-only pages; refs held until the row releases)
            picked: list = []
            if self._kvstore is not None:
                picked = self._match_prefix(tokens, need)
            grant_need = need - len(picked)
            if grant_need > g.allocator.available and self._kvstore is not None:
                # the free list is short but the store may be sitting on
                # refcount-zero cached pages — reclaim those first (LRU,
                # spilled to host when a pool exists).  Without this an
                # idle engine whose pool is fully cached would deadlock:
                # nothing decoding means nothing ever frees a page.
                self._evict_blocks(grant_need - g.allocator.available)
            if grant_need > g.allocator.available:
                # backpressure: decode frees pages, retry next step
                if picked:
                    self._kvstore.release([b.hash for b in picked])
                break
            del self._queue[head]
            grant = g.allocator.allocate(grant_need)
            slot = free[0]
            row = _Row(
                req_id=req_id, slot=slot, tokens=tokens, params=clamped,
                pages=grant, submitted=submitted,
            )
            if self._block:
                row.blocks = BlockSchedule(
                    size=self._block,
                    per_step=self._block // (clamped.denoise_steps or self._block),
                    prompt_len=len(tokens), max_tokens=clamped.max_tokens,
                    low_confidence=clamped.remask == "low_confidence",
                    answer=[None] * clamped.max_tokens,
                )
            if picked:
                # cached blocks ARE the prompt head: prefill starts at
                # cached_len (always inside a row-owned page — the match
                # is capped one token short of the prompt, so no row
                # ever appends into a shared page: the no-CoW rule)
                row.cached_len = len(picked) * g.page_size
                row.cached_hashes = [b.hash for b in picked]
                row.pos = row.cached_len
                self._kv_shadow[slot] = row.cached_len
                cached_total += row.cached_len
                self.metrics.incr(
                    "kv_prefill_tokens_saved", row.cached_len
                )
            self._rows[req_id] = row
            # measured submit -> admission wall: the span's queue_wait_ms
            # and the sched_queue_wait gauge read the SAME number
            row.queue_wait_ms = max(
                0.0, (time.perf_counter() - submitted) * 1e3
            )
            self.metrics.record("sched_queue_wait", row.queue_wait_ms)
            # mirror into the generator's slot table so free_slots /
            # num_active / the supervisor's leak audit see one truth
            slot_obj = _Slot()
            slot_obj.active = True
            slot_obj.prompt_len = len(tokens)
            slot_obj.params = clamped
            slot_obj.pages = grant
            g.slots[slot] = slot_obj
            # stage the row's page table for the next dispatch: cached
            # store-owned pages first, then the row's own grant
            row_table = np.zeros((g.pages_per_seq,), np.int32)
            if picked:
                row_table[: len(picked)] = [b.page for b in picked]
            row_table[len(picked) : len(picked) + len(grant)] = grant
            self._staged_tables.append((slot, row_table))
            admitted.append(req_id)
            if len(self._rows) > 1:
                self.metrics.incr("sched_admitted_midwave")
        return admitted, cached_total

    def _schedule(self, outcomes: list[StepOutcome]) -> StepPlan:
        """Plan the next ragged wave from PREDICTED row state (``pred_*``
        = authoritative + in-flight deltas), so a plan can be built while
        earlier dispatches are still on device.  The conservative-replan
        rule is structural: a row with an in-flight verify round
        (``pend_spec``) is skipped entirely — its true length is
        unknowable until commit — and commit-side voiding (_commit skips
        work whose row vanished) covers finish/cancel races."""
        g = self.generator
        plan = StepPlan()
        plan.admitted, plan.cached_tokens = self._admit_queued(outcomes)
        budget = self.t_budget
        cursor = 0
        # decode rows first — one token each (plus drafts), NEVER
        # deferred (the whole point: a prefill storm cannot starve an
        # in-flight decode).  A row predicted to have hit max_tokens or
        # the sequence cap sits out: its in-flight tokens already cover
        # the request, and commit will finish it.
        if self._block:
            cursor = self._plan_block_rows(plan)
        decode_ready = [] if self._block else [
            (req_id, row) for req_id, row in self._rows.items()
            if not row.pend_spec
            and row.pred_decoding
            and row.pred_gen < row.params.max_tokens
            and row.pred_kv + 1 < g.max_seq
        ]
        for i, (req_id, row) in enumerate(decode_ready):
            if cursor >= budget:  # unreachable while budget >= max_slots
                plan.deferred_decode += 1
                continue
            greedy = (
                self._draft is not None and row.params.temperature <= 0.0
            )
            # speculation REST (how speculation composes with depth >= 2
            # pipelining): a greedy row with a chained token in flight
            # can never draft — the proposal needs its committed text —
            # so when a probe of the STALE context finds an n-gram hit,
            # the row sits this round out; its in-flight commit lands
            # meanwhile and the NEXT round verifies k drafts in one
            # dispatch.  Rest is bounded (the in-flight queue drains
            # within ``depth`` rounds) and taken only on a probe hit, so
            # draft-miss rows keep the 1-token/step pipelined chain.
            if (
                greedy
                and row.pend_gen > 0
                and row.pend_pos == 0
                and row.decoding
            ):
                t0 = time.perf_counter()
                probe = self._draft.propose(
                    row.tokens + row.generated, self.spec_k
                )
                dms = (time.perf_counter() - t0) * 1e3
                self._draft_ms += dms
                self.metrics.observe("spec_draft_milliseconds", dms)
                if probe:
                    self.metrics.incr("spec_rest")
                    continue
            # speculation: greedy rows with NO in-flight work (the draft
            # needs the committed text, and the verify row needs the
            # committed last token as its input id) try a prompt-lookup
            # proposal.  Draft width is capped so the row cannot overrun
            # max_tokens, the sequence cap, or the peers' reserved
            # one-token budget slots (rows_after).
            k_eff = 0
            drafts: tuple = ()
            rows_after = len(decode_ready) - i - 1
            if (
                greedy
                and row.pend_gen == 0
                and row.pend_pos == 0
                and row.decoding
                and row.generated
            ):
                cap = min(
                    self.spec_k,
                    row.params.max_tokens - len(row.generated) - 1,
                    g.max_seq - 1 - row.kv_len,
                    budget - cursor - 1 - rows_after,
                )
                if cap > 0:
                    t0 = time.perf_counter()
                    proposed = self._draft.propose(
                        row.tokens + row.generated, cap
                    )
                    dms = (time.perf_counter() - t0) * 1e3
                    self._draft_ms += dms
                    self.metrics.observe("spec_draft_milliseconds", dms)
                    if proposed:
                        drafts = tuple(proposed)
                        k_eff = len(drafts)
            plan.work.append(RowWork(
                row.slot, req_id, cursor, 1 + k_eff,
                "verify" if k_eff else "decode",
                pos0=row.pred_kv, spec_len=k_eff, drafts=drafts,
                from_prev=row.pend_gen > 0,
            ))
            cursor += 1 + k_eff
            plan.decode_rows += 1
        # prefill chunks fill the remaining budget, FIFO by admission
        for req_id, row in self._rows.items():
            if row.pend_spec or row.pred_decoding:
                continue
            remaining = budget - cursor
            count = min(self.chunk, row.prefill_len - row.pred_pos, remaining)
            if self._block:
                count -= count % self._block  # chunks end where blocks do
            if count <= 0:
                continue
            # a denoising row's last chunk samples nothing: its first
            # tokens come of its first block's steps
            kind = (
                "finish"
                if row.blocks is None and row.pred_pos + count >= row.prompt_len
                else "prefill"
            )
            plan.work.append(RowWork(
                row.slot, req_id, cursor, count, kind, pos0=row.pred_pos,
            ))
            cursor += count
            plan.prefill_rows += 1
        plan.tokens_planned = cursor
        return plan

    def _plan_block_rows(self, plan: StepPlan) -> int:
        """The generating rows of a model that denoises blocks: each its
        next step by its schedule (``BlockSchedule.next``: past what is
        committed or in flight), never deferred while ``token_budget``
        holds two blocks a slot.  A row whose last step is in flight sits
        out: its commit will finish it.  Returns the flat tokens taken."""
        cursor = 0
        for req_id, row in self._rows.items():
            if not row.pred_decoding:
                continue
            step = row.blocks.next
            if step is None:
                continue
            if cursor + step.count > self.t_budget:
                plan.deferred_decode += 1
                continue
            plan.work.append(RowWork(
                row.slot, req_id, cursor, step.count, "block",
                pos0=step.start - step.commit, block=step,
            ))
            cursor += step.count
            plan.decode_rows += 1
        return cursor

    # -- dispatch ------------------------------------------------------

    def _get_fn(self):
        if self._fn is None:
            from .mixed import make_mixed_fn

            log.info(
                "compiling mixed-step program t_budget=%d chunk=%d slots=%d"
                " width=%d pipeline_depth=%d",
                self.t_budget, self.chunk, self.generator.max_slots,
                self.width, self.depth,
            )
            self._fn = self.generator._aot_wrap(
                f"mixed_t{self.t_budget}_c{self.chunk}_w{self.width}",
                make_mixed_fn(
                    self.generator, self.t_budget, self.chunk,
                    spec_width=self.width,
                ),
            )
        return self._fn

    def _pack(self, plan: StepPlan) -> _Packed:
        """Pack the plan onto the flat token axis (host arrays only) and
        count the step's work from the packed arrays."""
        g = self.generator
        t, b = self.t_budget, g.max_slots
        ids = np.zeros((t,), np.int32)
        rows = np.zeros((t,), np.int32)
        pos = np.zeros((t,), np.int32)
        valid = np.zeros((t,), bool)
        in_row = np.zeros((t,), np.int32)
        from_prev = np.zeros((t,), bool)
        q_start = np.zeros((b,), np.int32)
        q_count = np.zeros((b,), np.int32)
        sample_start = np.zeros((b,), np.int32)
        spec_len = np.zeros((b,), np.int32)
        temp = np.zeros((b,), np.float32)
        top_p = np.ones((b,), np.float32)
        kv_len = self._kv_shadow.copy()
        denoise = None
        if self._block:
            denoise = {
                "keep": np.zeros((b,), np.int32),
                "limit": np.zeros((b,), np.int32),
                "low_confidence": np.zeros((b,), bool),
            }
        prefill_tokens = commit_tokens = 0
        for work in plan.work:
            row = self._rows[work.req_id]
            span = slice(work.start, work.start + work.count)
            if work.kind == "decode":
                # a chained row's input id is the PREVIOUS dispatch's
                # on-device sample: pack a placeholder, the program
                # substitutes its carried latest[slot]
                ids[work.start] = 0 if work.from_prev else row.generated[-1]
                pos[work.start] = work.pos0
                from_prev[work.start] = work.from_prev
            elif work.kind == "verify":
                # committed last token + k prompt-lookup drafts, one
                # contiguous chunk of absolute positions
                ids[span] = [row.generated[-1], *work.drafts]
                pos[span] = np.arange(
                    work.pos0, work.pos0 + work.count, dtype=np.int32
                )
            elif work.kind == "block":
                step = work.block
                lead = work.start + step.commit
                pos[span] = np.arange(
                    work.pos0, work.pos0 + work.count, dtype=np.int32
                )
                if step.step:
                    # the block as the step before left it, on the device
                    from_prev[span] = True
                else:
                    # a new block: masks, after the prompt's tail in the
                    # first; led, in any other, by the block before it
                    # (clean, on the device), whose keys this step writes
                    tail = row.tokens[step.start :]
                    ids[lead : lead + len(tail)] = tail
                    ids[lead + len(tail) : lead + step.size] = g.config.mask_token_id
                    from_prev[work.start : lead] = True
                commit_tokens += step.commit
                denoise["keep"][work.slot] = step.keep
                denoise["limit"][work.slot] = step.limit
                denoise["low_confidence"][work.slot] = row.blocks.low_confidence
            else:  # prefill / finish
                ids[span] = row.tokens[work.pos0 : work.pos0 + work.count]
                pos[span] = np.arange(
                    work.pos0, work.pos0 + work.count, dtype=np.int32
                )
                prefill_tokens += work.count
            rows[span] = work.slot
            valid[span] = True
            in_row[span] = np.arange(work.count, dtype=np.int32)
            q_start[work.slot] = work.start
            q_count[work.slot] = work.count
            # first sampled position: the last NON-draft token (a verify
            # row samples it and every draft after it), or the first
            # position of a denoising row's block
            sample_start[work.slot] = (
                work.start + work.block.commit if work.kind == "block"
                else work.start + work.count - 1 - work.spec_len
            )
            spec_len[work.slot] = work.spec_len
            # optimistic: every draft accepted; the program corrects the
            # committed lengths on device (kv_len - (spec_len - accept))
            kv_len[work.slot] = work.pos0 + work.count
            temp[work.slot] = row.params.temperature
            top_p[work.slot] = row.params.top_p
        pages, pairs = _kv_walk(
            kv_len, q_count, g.page_size, g.config.sliding_window
        )
        if self._latent:
            tile_rows = latent_attention.query_tile_rows(q_count, self.chunk)
        else:
            tile_rows = query_tile_rows(q_count, self.chunk)
        wide = bool(spec_len.any())
        return _Packed(
            ids=ids, rows=rows, pos=pos, valid=valid, in_row=in_row,
            from_prev=from_prev, q_start=q_start, q_count=q_count,
            sample_start=sample_start, spec_len=spec_len, temp=temp,
            top_p=top_p, kv_len=kv_len, denoise=denoise, wide=wide,
            counts={
                "prefill_tokens": prefill_tokens,
                "kv_pages_walked": int(pages.sum()),
                # the latent pages one layer's latent attention call copies
                # in (the ragged kernel's rule: every live page of a slot
                # with queries); None for a model with a K/V cache
                "latent_pages_walked": int(pages.sum()) if self._latent else None,
                # flash updates one layer's kernel call makes: each slot's
                # pages in blocks of what its rung walks at a time
                "kv_blocks_walked": kv_blocks_walked(
                    pages, tile_rows, self._kv_block_of
                ),
                # the query-tile rows one layer's kernel call works, by
                # the kernel's own rule: nothing for a slot without
                # queries, else the smallest rung that holds them (one
                # for a row of one query, the small tile, the chunk)
                "q_tile_rows": int(tile_rows.sum()),
                # slots whose recurrent state a layer's scan call reads
                # and rewrites (the others it skips); no such state, no count
                "state_rows": (
                    int((q_count > 0).sum()) if self._recurrent else None
                ),
                # logit rows the head and the sampler are ASKED for: one
                # a slot, or the verify width a slot in a step that
                # carries a draft.  sched/mixed.py branches on the same
                # spec_len; this restates the predicate on the host and
                # does not observe which branch the device took
                # (a model that denoises blocks: the block's positions a
                # slot, every step)
                "sampled_rows": b * (
                    self._block or (self.width if wide else 1)
                ),
                "passes": self._passes,
                # rows in a denoising step, and the query tokens among
                # theirs that only rewrite a finished block's keys; None
                # for a model that does not denoise
                "block_rows": plan.decode_rows if self._block else None,
                "commit_tokens": commit_tokens if self._block else None,
                # valid tokens the step's layers route to experts
                "moe_tokens": plan.tokens_planned if self._moe else None,
            },
            qk_pairs=pairs,
        )

    def _dispatch(
        self, plan: StepPlan, packed: _Packed, seq: int = 0
    ) -> _InFlight:
        """ISSUE the one mixed program on the packed plan; commits the
        returned cache/rng/latest handles and returns the in-flight
        entry WITHOUT syncing — the sampled tokens stay on device until
        ``_commit_oldest`` fetches them (the pipelining point: at depth
        >= 2 the next plan is dispatched before this fetch happens).
        Two parts of the step clock, a span each: ``put`` (the staged
        page tables and every packed array go to the device) and
        ``launch`` (the call of the compiled step until it returns its
        handles, and the bookkeeping after it)."""
        g = self.generator
        clock = g.step_clock
        jnp = g._jnp
        p = packed
        paged = g.paged_cache
        with g._annotation("podmortem.sched.put", step=seq):
            if self._staged_tables:
                idx = jnp.asarray(
                    [slot for slot, _ in self._staged_tables], jnp.int32
                )
                tables = jnp.asarray(
                    np.stack([tab for _, tab in self._staged_tables]), jnp.int32
                )
                paged = dataclasses.replace(
                    paged, page_table=paged.page_table.at[idx].set(tables)
                )
                self._staged_tables.clear()
            if self._latest is None:
                # each slot's freshest token, or for a model that denoises
                # blocks its block as the last step left it
                self._latest = jnp.zeros(
                    (g.max_slots, self._block) if self._block else (g.max_slots,),
                    jnp.int32,
                )
            extra = ()
            if p.denoise is not None:
                extra = ({k: jnp.asarray(v) for k, v in p.denoise.items()},)
            args = (
                g.params, paged,
                jnp.asarray(p.ids), jnp.asarray(p.rows), jnp.asarray(p.pos),
                jnp.asarray(p.valid), jnp.asarray(p.in_row),
                jnp.asarray(p.q_start), jnp.asarray(p.q_count),
                jnp.asarray(p.kv_len),
                self._latest, jnp.asarray(p.from_prev),
                jnp.asarray(p.sample_start), jnp.asarray(p.spec_len),
                g._rng, jnp.asarray(p.temp), jnp.asarray(p.top_p), *extra,
            )
        clock.begin("launch")
        with g._annotation("podmortem.sched.launch", step=seq):
            new_paged, toks, accept, latest, rng, *moe = self._get_fn()(*args)
            g.paged_cache = new_paged
            g._rng = rng
            self._latest = latest
            # shadow holds the OPTIMISTIC lengths (all drafts accepted) so
            # the next plan's packing is consistent with pred_kv; a verify
            # commit re-anchors the slot from the row's authoritative state
            # when drafts were rejected.  A copy: on the CPU ``jnp.asarray``
            # may share ``p.kv_len``'s memory with the step's argument, and
            # the shadow is written in place (a recycled slot's 0)
            self._kv_shadow = p.kv_len.copy()
            # NO block/fetch here: the commit side owns the step's one host
            # sync (GL001: host loop code, not jit-reachable).  Record the
            # in-flight deltas planning reads as pred_* until commit.
            for work in plan.work:
                row = self._rows[work.req_id]
                if work.kind == "decode":
                    row.pend_gen += 1
                elif work.kind == "verify":
                    row.pend_spec = True
                elif work.kind == "block":
                    row.blocks.pend += 1
                elif work.kind == "finish":
                    row.pend_pos += work.count
                    row.pend_gen += 1  # the chunk's first sampled token
                else:  # prefill
                    row.pend_pos += work.count
        return _InFlight(
            plan=plan, toks=toks, accept=accept,
            counts=packed.counts, moe=moe[0] if moe else None,
        )

    # -- commit --------------------------------------------------------

    def _release_row(self, row: _Row) -> None:
        """Recycle the row's slot + pages NOW.  The freed pages may be
        granted to a new row this very step: the dead row's stale page
        table entries are never read again (its shadow kv length is 0,
        so the ragged kernel walks zero pages) and are overwritten by
        staging at the slot's next admission — no trash-page indirection
        needed, unlike the wave engine's always-dispatch-all-slots
        decode block."""
        g = self.generator
        if self._kvstore is not None and row.cached_hashes:
            # drop the row's references on shared/donated blocks (the
            # pages themselves stay with the store until LRU eviction)
            self._kvstore.release(row.cached_hashes)
            row.cached_hashes = []
        g.allocator.release(row.pages)
        g.slots[row.slot] = _Slot()
        self._kv_shadow[row.slot] = 0
        self._rows.pop(row.req_id, None)
        self.metrics.incr("sched_recycled_slot")

    def _finish(self, row: _Row, reason: str) -> GenerationResult:
        g = self.generator
        eos = g.tokenizer.eos_id
        ids = [t for t in row.generated if t != eos]
        if reason == "length" and row.params.deadline_clamped:
            reason = "deadline"
        elif reason == "length" and row.params.degraded:
            # overload-truncated depth, not a deadline miss: the ladder
            # reduced max_tokens, so hitting it IS the degraded outcome
            reason = "degraded"
        # decode wall from the step clock's monotonic cumulative, not a
        # wall-clock delta: the SAME records /metrics and black-box dumps
        # carry, so the span and the step timeline cannot disagree.  Read
        # before this commit's record lands, as decode_cum0 was
        # (_commit_oldest): the two offsets cancel
        decode_ms = 0.0
        if row.started:
            decode_ms = max(
                0.0, g.step_clock.decode_cum_ms - row.decode_cum0
            )
        result = GenerationResult(
            text=g.tokenizer.decode(ids),
            token_ids=ids,
            prompt_tokens=row.prompt_len,
            completion_tokens=len(ids),
            finish_reason=reason,
            prefill_ms=row.prefill_ms,
            decode_ms=decode_ms,
            queue_wait_ms=row.queue_wait_ms,
        )
        self._release_row(row)
        return result

    def _commit_oldest(self, outcomes: list[StepOutcome]) -> None:
        """Fetch + commit the oldest in-flight dispatch: the step's ONE
        host sync.  The step-clock record lands at the END of the commit
        and closes the interval that began at the previous commit's end,
        so records tile the wall.  Rows therefore stamp ``decode_cum0``
        (and ``_finish`` reads ``decode_cum_ms``) one record early on
        both sides: a request's decode window is the intervals from its
        first token's commit up to its last's, equal to last-token time
        less first-token time to within the difference of two steps."""
        g = self.generator
        clock = g.step_clock
        entry = self._inflight.popleft()
        plan = entry.plan
        # the sync was always here (np.asarray); block_until_ready in
        # front only SPLITS it into device wait vs token-id transfer
        # — no new sync point (GL001: host loop code, not jit-reachable)
        clock.begin("wait")
        with g._annotation("podmortem.sched.wait", step=entry.seq):
            try:
                entry.toks.block_until_ready()
            except AttributeError:
                pass  # already a host array (fake-jax tests)
        clock.begin("xfer")
        with g._annotation("podmortem.sched.commit", step=entry.seq) as span:
            toks = np.asarray(entry.toks)
            accept = np.asarray(entry.accept)
            # the commit runs on past the record's end, to the next stamp
            # (the next plan, or the step's return): no glue is left over
            clock.begin("commit")
            if self._pending_offload:
                # the step just paid its host sync: piggyback the offload
                # fetches on it (device→host page copies overlap the token
                # readback window instead of opening a new sync point)
                self._drain_offload()
            if self._pending_mirror:
                self._drain_mirror()
            self._host_syncs += 1
            if plan.decode_rows and plan.prefill_rows:
                kind = "mixed"
            elif plan.decode_rows:
                kind = "decode"
            else:
                kind = "prefill"
            # prospective accepted-token count so MFU attribution stays
            # honest under speculation: a verify row lands accept+1 tokens,
            # not the q_count it was billed for (voided rows land zero)
            accepted = 0
            for work in plan.work:
                if work.req_id not in self._rows:
                    continue
                if work.kind == "verify":
                    accepted += int(accept[work.slot]) + 1
                elif work.kind == "block":
                    # the positions the step kept, a bit each
                    accepted += int(accept[work.slot]).bit_count()
                elif work.kind in ("decode", "finish"):
                    accepted += 1
            # rows are charged the interval so far (their own commit, a
            # per cent of a step, is still to come)
            elapsed_ms = clock.elapsed_ms()
            outcomes.extend(self._commit(plan, toks, accept, elapsed_ms))
            experts = {"moe_experts_hit": None, "moe_assign_max": None}
            if entry.moe is not None:
                hit, fullest = np.asarray(entry.moe)
                experts = {"moe_experts_hit": int(hit), "moe_assign_max": int(fullest)}
            if self._block:
                self.metrics.incr("unmasked_tokens", accepted)
            if span is not None and span.is_enabled():
                # what the hand-over to the event loop took of the commit
                span.set_metadata(
                    wake_us=int(clock.wake_ms * 1e3), wakeups=clock.wakeups,
                    wake_rows=clock.wake_rows,
                )
        record = clock.observe(
            kind=kind,
            tokens=plan.tokens_planned,
            slots=entry.held_rows,
            accepted=accepted,
            cached_tokens=(
                plan.cached_tokens if self._kvstore is not None else None
            ),
            # answer tokens the step's denoising rows kept
            unmasked_tokens=accepted if self._block else None,
            **experts,
            **entry.counts,
        )
        if plan.decode_rows and not plan.prefill_rows:
            # wall time per pure-decode round only: the admission
            # roofline reads p50(decode_step) as seconds-per-token
            # (decode_token_estimate_s), and a mixed step's wall includes
            # up to `chunk` prefill tokens' compute — folding that in
            # would make deadline clamping over-truncate every admission.
            # The record's wall: dispatch -> fetch spans two device steps
            # at depth 2.  A denoising step keeps several tokens a row:
            # its wall is spread over what a row of it kept
            per_row = accepted / plan.decode_rows if self._block and accepted else 1.0
            self.metrics.record("decode_step", record.wall_ms / per_row)

    def _push_token(self, row: _Row, token: int) -> Optional[str]:
        """Append one committed token; returns the finish reason when
        the row just reached a terminal state."""
        g = self.generator
        eos = g.tokenizer.eos_id
        row.generated.append(token)
        if row.params.stop_on_eos and eos is not None and token == eos:
            return "stop"
        if len(row.generated) >= row.params.max_tokens:
            return "length"
        if row.kv_len + 1 >= g.max_seq:
            # the NEXT decode token would write past the sequence cap
            return "length"
        return None

    def _commit_block(
        self, row: _Row, work: RowWork, toks: np.ndarray, accept: np.ndarray,
    ) -> Optional[str]:
        """Commit one denoising step of ``row``: the positions whose bit
        ``accept`` has set take their token of ``toks`` (the block after
        the step) and never change again; a position is streamed once
        everything left of it is kept.  Returns the finish reason when the
        answer is whole (``max_tokens`` positions: the last block's
        positions past it were never unmasked) or met EOS."""
        g = self.generator
        blocks, step = row.blocks, work.block
        blocks.pend -= 1
        blocks.done += 1
        if not row.started:
            # the row's first step: the prompt is in the pool
            row.started = time.perf_counter()
            row.decode_cum0 = g.step_clock.decode_cum_ms
            self.metrics.record("prefill", row.prefill_ms)
        first = step.start - row.prompt_len  # the block's place in the answer
        bits = int(accept[work.slot])
        for j in range(step.size):
            if bits >> j & 1:
                blocks.answer[first + j] = int(toks[work.slot, j])
        finished = None
        while (
            finished is None
            and len(row.generated) < len(blocks.answer)
            and blocks.answer[len(row.generated)] is not None
        ):
            finished = self._push_token(row, blocks.answer[len(row.generated)])
            self._decode_committed += 1
        return finished

    def _commit(
        self, plan: StepPlan, toks: np.ndarray, accept: np.ndarray,
        elapsed_ms: float,
    ) -> list[StepOutcome]:
        outcomes: list[StepOutcome] = []
        g = self.generator
        # the rows that stream, handed to the event loop in ONE call
        handed: list[tuple[int, list]] = []
        # the step's wall is attributed to its rows by token share —
        # good enough for the prefill/decode split the spans surface
        share = elapsed_ms / max(1, plan.tokens_planned)
        for work in plan.work:
            row = self._rows.get(work.req_id)
            if row is None:
                # cancelled/finished between dispatch and commit: the
                # prediction this work was planned from is void.  Slot
                # and pages were reclaimed at release; the stale KV
                # writes land in pages whose next owner overwrites its
                # own positions before reading them.
                self.metrics.incr("sched_pipeline_voided")
                continue
            finished: Optional[str] = None
            if work.kind in ("prefill", "finish"):
                row.pos += work.count
                row.pend_pos -= work.count
                row.prefill_ms += share * work.count
                if not row.decoding:
                    # mid-prompt chunk: more prefill next step
                    if not row.chunked:
                        row.chunked = True
                        self.metrics.incr("sched_chunked_prefill")
                    continue
                if row.blocks is not None:
                    # the prompt's whole blocks are written; the chunk
                    # sampled nothing: the first block's steps follow
                    continue
                # prompt completed THIS step: the sampled token is the
                # row's first generated token (wave-engine semantics:
                # the prefill-sampled token counts toward max_tokens)
                row.started = time.perf_counter()
                row.decode_cum0 = g.step_clock.decode_cum_ms
                row.pend_gen -= 1
                row.generated = []
                if self._kvstore is not None:
                    # the prompt's KV is complete and immutable: donate
                    # its full blocks' pages to the prefix cache
                    self._register_row_blocks(row)
                self.metrics.record("prefill", row.prefill_ms)
                finished = self._push_token(row, int(toks[work.slot, 0]))
                self._decode_committed += 1
            elif work.kind == "decode":
                row.pend_gen -= 1
                finished = self._push_token(row, int(toks[work.slot, 0]))
                self._decode_committed += 1
            elif work.kind == "block":
                streamed = len(row.generated)
                finished = self._commit_block(row, work, toks, accept)
                if finished is None and len(row.generated) == streamed:
                    continue  # nothing new to stream
            else:  # verify
                row.pend_spec = False
                a = int(accept[work.slot])
                self.metrics.incr("spec_rounds")
                self.metrics.incr("spec_proposed", work.spec_len)
                self.metrics.incr("spec_accepted", a)
                for j in range(a + 1):
                    finished = self._push_token(row, int(toks[work.slot, j]))
                    self._decode_committed += 1
                    if finished is not None:
                        break
                if finished is None:
                    # rejected drafts left the shadow optimistic: re-
                    # anchor the slot to the row's authoritative length
                    # so the next dispatch packs true positions
                    self._kv_shadow[row.slot] = row.kv_len
            if finished is not None:
                outcomes.append(
                    StepOutcome(work.req_id, result=self._finish(row, finished))
                )
            elif (
                self.partial_hook is not None
                and row.decoding
                and row.generated
            ):
                # list COPY: the hook crosses into the event-loop thread
                handed.append((row.req_id, list(row.generated)))
        if handed:
            clock = g.step_clock
            t0 = clock.now()
            self.partial_hook(handed)
            clock.woke((clock.now() - t0) * 1e3, len(handed))
        return outcomes
