"""Scheduler data types: row state and the per-step ragged wave plan.

Split from :mod:`.scheduler` so tests (and the determinism assertion:
a fixed arrival trace must produce a byte-identical plan sequence) can
inspect plans without importing the dispatch machinery.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

from ..types import SamplingParams


@dataclass(frozen=True)
class SchedConfig:
    """Continuous-scheduler knobs (OperatorConfig ``sched_*``).

    ``chunk`` bounds the prefill tokens ONE row may contribute to a step
    (Sarathi-style chunking: a storm of long prompts can stall in-flight
    decodes for at most one chunk's compute per step).  ``token_budget``
    is the flat token axis of the mixed program — decode rows take one
    token each off the top, prefill chunks fill the remainder; it must
    be >= ``max_slots`` so a full decode batch can never be starved
    (enforced at construction)."""

    chunk: int = 64
    token_budget: int = 0  # 0 = auto: max(chunk, max_slots)
    #: bounded in-flight dispatch queue (decode-ahead pipelining): step
    #: N+1 is planned from predicted row state and dispatched while step
    #: N's sampled tokens are still on device; 1 = synchronous commit
    pipeline_depth: int = 1
    #: prompt-lookup self-speculation (sched/draft.py): greedy rows
    #: verify up to ``spec_lookup_k`` draft tokens per step as one
    #: q_count=k+1 row; 0 / spec_decode off = plain one-token decode
    spec_decode: bool = False
    spec_lookup_k: int = 4


@dataclass(frozen=True)
class BlockStep:
    """One denoising step of a row, as the schedule fixes it: step
    ``step`` of block ``index``, whose ``size`` positions begin at
    ``start``.  The step keeps ``keep`` of the block's masked positions
    below ``limit`` (those at or past it lie beyond ``max_tokens`` and
    stay masked).  A block's first step is led by ``commit`` query tokens,
    the clean ids of the block before, which only write that block's
    final keys."""

    index: int
    step: int
    start: int
    size: int
    keep: int
    limit: int
    commit: int

    @property
    def count(self) -> int:
        """Query tokens the step packs."""
        return self.commit + self.size


@dataclass
class BlockSchedule:
    """A request of a model that denoises blocks (models/sdar.py), as a
    function of the prompt's length, ``max_tokens``, the block's length
    and the request's ``denoise_steps`` alone: the whole blocks of the
    prompt are prefilled (``prefill_len``), its tail opens the first
    generated block already clean, and block ``k`` takes ``ceil(masked /
    per_step)`` steps, where a whole block has ``size`` masked positions,
    the first ``size`` less the tail's, and the last only those below
    ``prompt_len + max_tokens``.  Every count the host packs follows, so a
    step can be planned while the ones before it are still on the device.

    ``done`` / ``pend`` are the row's place in it (steps committed, steps
    in flight); ``answer`` holds the answer positions kept so far (None
    where one is still masked): a position is streamed once everything to
    its left is kept."""

    size: int
    per_step: int
    prompt_len: int
    max_tokens: int
    low_confidence: bool
    done: int = 0
    pend: int = 0
    answer: list = field(default_factory=list)

    @property
    def prefill_len(self) -> int:
        return self.prompt_len - self.prompt_len % self.size

    def _block(self, index: int) -> tuple[int, int, int]:
        """``(start, clean positions at its start, limit)`` of a block."""
        start = self.prefill_len + index * self.size
        clean = self.prompt_len - self.prefill_len if index == 0 else 0
        limit = min(self.size, self.prompt_len + self.max_tokens - start)
        return start, clean, limit

    def at(self, number: int) -> Optional[BlockStep]:
        """Step ``number`` of the request (0-based), or None past its end."""
        _, clean, limit = self._block(0)
        first = -(-(limit - clean) // self.per_step)  # the first block's steps
        index, step = 0, number
        if number >= first:
            whole = self.size // self.per_step
            index = 1 + (number - first) // whole
            step = (number - first) % whole
        start, clean, limit = self._block(index)
        left = limit - clean - step * self.per_step  # masked when the step begins
        if left <= 0:
            return None
        return BlockStep(
            index=index, step=step, start=start, size=self.size,
            keep=min(self.per_step, left), limit=limit,
            commit=self.size if step == 0 and index > 0 else 0,
        )

    @property
    def next(self) -> Optional[BlockStep]:
        """The next step to plan: past everything committed or in flight."""
        return self.at(self.done + self.pend)


@dataclass
class _Row:
    """One live row of the running wave: a request at an arbitrary
    prefill-chunk or decode position."""

    req_id: int
    slot: int
    tokens: list[int]  # full (truncated) prompt token ids
    params: SamplingParams
    pages: list[int]
    pos: int = 0  # prompt tokens already written to the KV pages
    generated: list[int] = field(default_factory=list)
    submitted: float = 0.0  # perf_counter at admission
    started: float = 0.0  # perf_counter when the prompt completed
    prefill_ms: float = 0.0  # accumulated chunk compute share
    chunked: bool = False  # took more than one step of prefill
    queue_wait_ms: float = 0.0  # measured submit -> admission wall
    #: step-clock decode cumulative (StepRing.decode_cum_ms) when the
    #: prompt completed — _finish derives decode_ms as the delta, so the
    #: span timing and the step records share one source of truth
    decode_cum0: float = 0.0
    # --- decode-ahead pipelining: uncommitted in-flight deltas.  The
    # authoritative fields above advance only at commit; planning reads
    # the PREDICTED state (authoritative + pending) so step N+1 can be
    # dispatched while step N's tokens are still on device. ---
    #: prompt tokens dispatched but not yet committed (prefill chunks)
    pend_pos: int = 0
    #: tokens sampled on device but not yet committed (chained decodes
    #: + a finishing chunk's first sample); their ids never left the
    #: device — the next dispatch chains them via ``from_prev``
    pend_gen: int = 0
    #: a speculation verify round is in flight: the row must not be
    #: re-planned until its commit lands (the accepted count — and so
    #: the row's true length — is unknowable on the host until then)
    pend_spec: bool = False
    # --- prefix cache (serving/kvstore.py) ---
    #: prompt tokens served from cached blocks at admission: the row's
    #: first ``cached_len // page_size`` table entries are STORE-OWNED
    #: read-only pages (never in ``pages``, never written — suffix
    #: prefill starts at ``pos = cached_len`` in a row-owned page)
    cached_len: int = 0
    #: block hashes this row holds references on (acquired at admission
    #: + blocks it donated at prefill completion); released on finish
    cached_hashes: list[bytes] = field(default_factory=list)
    #: the request's denoising schedule, for a model that denoises blocks
    #: (models/sdar.py); None for a row that commits a token a step
    blocks: Optional[BlockSchedule] = None

    @property
    def prompt_len(self) -> int:
        return len(self.tokens)

    @property
    def prefill_len(self) -> int:
        """Prompt tokens the prefill writes: all of them, or for a row
        that denoises blocks the prompt's whole blocks (its tail opens
        the first generated block)."""
        return self.prompt_len if self.blocks is None else self.blocks.prefill_len

    @property
    def decoding(self) -> bool:
        return self.pos >= self.prefill_len

    @property
    def kv_len(self) -> int:
        """Tokens currently valid in this row's pages."""
        if not self.decoding:
            return self.pos
        # the freshest sampled token has not been written yet; every
        # earlier one has (prompt + generated[:-1])
        return self.prompt_len + max(0, len(self.generated) - 1)

    # -- predicted state (authoritative + in-flight deltas) ------------

    @property
    def pred_pos(self) -> int:
        return self.pos + self.pend_pos

    @property
    def pred_decoding(self) -> bool:
        return self.pred_pos >= self.prefill_len

    @property
    def pred_gen(self) -> int:
        return len(self.generated) + self.pend_gen

    @property
    def pred_kv(self) -> int:
        """Pages' valid length once every in-flight dispatch lands."""
        if not self.pred_decoding:
            return self.pred_pos
        return self.prompt_len + max(0, self.pred_gen - 1)


@dataclass
class RowWork:
    """One row's share of a step: ``count`` tokens starting at flat
    offset ``start``.  ``kind`` distinguishes a speculation verify row
    ("verify") from plain work; otherwise it is forensics only — the
    program does not distinguish phases.  Positions are FROZEN at plan
    time (``pos0``): under pipelining the row's authoritative state may
    advance between this plan's dispatch and its commit, so the work
    item must carry everything dispatch packs."""

    slot: int
    req_id: int
    start: int  # flat offset of the row's first token this step
    count: int
    kind: str  # "prefill" | "finish" | "decode" | "verify" | "block"
    #: absolute position of the row's first token this step (prefill:
    #: the predicted prompt offset; decode/verify: the predicted kv len)
    pos0: int = 0
    #: draft tokens riding a verify row (count == 1 + spec_len)
    spec_len: int = 0
    drafts: tuple = ()
    #: the row's input token is the previous dispatch's on-device sample
    #: (chained decode) — the packed id is a placeholder the program
    #: replaces with its carried ``latest`` buffer
    from_prev: bool = False
    #: a "block" row's denoising step (its ``start`` - ``commit`` is
    #: ``pos0``, its ``count`` is ``count``)
    block: Optional[BlockStep] = None


@dataclass
class StepPlan:
    """The ragged wave one dispatch serves; ``trace()`` is the stable
    serialisation the determinism test replays."""

    work: list[RowWork] = field(default_factory=list)
    tokens_planned: int = 0
    decode_rows: int = 0
    prefill_rows: int = 0
    deferred_decode: int = 0  # decode-ready rows left out (stall signal)
    admitted: list[int] = field(default_factory=list)  # req ids admitted NOW
    #: prompt tokens rows admitted THIS step reused from the prefix
    #: cache (spared prefill compute; rides into StepRecord.cached_tokens)
    cached_tokens: int = 0

    def trace(self) -> tuple:
        return tuple(
            (w.slot, w.req_id, w.start, w.count, w.kind, w.pos0,
             w.spec_len, w.drafts, w.from_prev)
            for w in self.work
        )


@dataclass
class StepOutcome:
    """One finished request: the result (or the admission-time error)
    the engine resolves its future with."""

    req_id: int
    result: Optional[Any] = None  # GenerationResult
    error: Optional[BaseException] = None
