"""Serving data types shared by the engine, admission, and program layers.

Split out of serving/engine.py (round 5) so the admission-policy and
program-builder modules can import them without a cycle; the public import
surface is unchanged (serving.engine re-exports everything here).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional


@dataclass(frozen=True)
class SamplingParams:
    max_tokens: int = 256
    temperature: float = 0.3  # reference default, aiprovider-crd.yaml:56-58
    top_p: float = 0.95
    stop_on_eos: bool = True
    #: LoRA adapter name for this request (multi-LoRA serving: every slot
    #: picks its own adapter from the generator's stacked registry; None =
    #: base model).  Unknown names are rejected at admission.
    adapter: Optional[str] = None
    #: constrain the output to one of these strings (serving/guided.py):
    #: a token-trie automaton rides the decode scan as device state and
    #: masks the sampler every step.  None = unconstrained.
    guided_choice: Optional[tuple] = None
    #: constrain the output to match this regex (serving/regex_dfa.py:
    #: byte-level DFA, token closure, same device-state machinery).
    #: Mutually exclusive with guided_choice.
    guided_regex: Optional[str] = None
    #: absolute time.monotonic() deadline for this request (deadline
    #: budget, utils/deadline.py).  Admission rejects a request whose
    #: roofline decode estimate cannot fit the residue, or clamps
    #: max_tokens to what does fit (admission.deadline_policy); an entry
    #: that expires while queued fails with DeadlineExceeded.  None = no
    #: budget.
    deadline: Optional[float] = None
    #: set by admission when max_tokens was clamped to fit the deadline —
    #: the finish reason then reads "deadline" instead of "length"
    deadline_clamped: bool = False
    #: set when the overload ladder truncated analysis depth (max_tokens
    #: scaled down under pressure, admission.deadline_policy): the finish
    #: reason then reads "degraded" — degrade-before-reject, distinct
    #: from deadline clamping
    degraded: bool = False
    #: recall-hit probability from memory/recall.py's predictor: a
    #: recalled incident costs ~4% of a cold analysis, so this rides into
    #: the request's overload value (router/value.py) — recalled work is
    #: shed only after all cold work of equal-or-lower class
    recall_p: float = 0.0
    #: obs trace id of the request's analysis (operator_tpu/obs/): the
    #: engine stamps it into its jax.profiler prefill/decode annotations
    #: so an xplane capture joins the flight recorder's timeline.  None =
    #: untraced (external API caller without a traceparent).
    trace_tag: Optional[str] = None
    #: SLO class this request is accounted under (obs/sloledger.py): the
    #: engine's per-class SLOBoard buckets attainment + goodput by it and
    #: /healthz carries the rollup.  None = the board's "default" bucket.
    slo_class: Optional[str] = None
    #: for a model that denoises blocks of positions (``block_length``,
    #: models/sdar.py): steps a block takes, each keeping ``block_length /
    #: denoise_steps`` of its positions (a divisor of the block's length;
    #: None = one position a step), and which a step keeps (``REMASK_RULES``:
    #: those whose token had the highest probability among the sampler's
    #: candidates, or the leftmost).  Refused at submit for a model that
    #: does not denoise (:func:`check_denoise`)
    denoise_steps: Optional[int] = None
    remask: str = "low_confidence"


#: which of a block's masked positions a denoising step keeps
REMASK_RULES = ("low_confidence", "sequential")


def check_denoise(params: SamplingParams, block_length: int) -> None:
    """Raise ``ValueError`` unless ``params``' denoising fields suit a
    model of ``block_length`` positions a block (0: a model that commits a
    token a row a step, which takes neither field)."""
    if not block_length:
        if params.denoise_steps is not None or params.remask != REMASK_RULES[0]:
            raise ValueError(
                "denoise_steps and remask are for a model that denoises "
                "blocks of positions; this one commits one token a row a step"
            )
        return
    steps = block_length if params.denoise_steps is None else params.denoise_steps
    if steps < 1 or block_length % steps:
        raise ValueError(
            f"denoise_steps={steps} does not divide the model's block of "
            f"{block_length} positions"
        )
    if params.remask not in REMASK_RULES:
        raise ValueError(f"remask={params.remask!r}: expected one of {REMASK_RULES}")


@dataclass
class GenerationResult:
    text: str
    token_ids: list[int]
    prompt_tokens: int
    completion_tokens: int
    finish_reason: str  # "stop" | "length" | "deadline" (budget-clamped) | "degraded" (overload-truncated)
    prefill_ms: float = 0.0
    #: decode wall DERIVED FROM THE STEP CLOCK (obs/steptrace.py): the
    #: cumulative attributed wall of decode-bearing steps this request
    #: lived through — the same records /metrics histograms and black-box
    #: dumps carry, so span timings and step records cannot disagree
    decode_ms: float = 0.0
    #: submit -> admission wall (measured, not inferred as wall minus
    #: compute — the coarse delta the engine.generate span used to carry)
    queue_wait_ms: float = 0.0

    @property
    def total_ms(self) -> float:
        return self.prefill_ms + self.decode_ms


@dataclass
class _Slot:
    active: bool = False
    prompt_len: int = 0
    generated: list[int] = field(default_factory=list)
    params: SamplingParams = field(default_factory=SamplingParams)
    started: float = 0.0
    prefill_ms: float = 0.0
    pages: list[int] = field(default_factory=list)  # paged mode only
    #: step-clock decode cumulative (StepRing.decode_cum_ms) when the slot
    #: went live — _finish derives decode_ms as the delta, eviction-proof
    decode_cum0: float = 0.0
    queue_wait_ms: float = 0.0


@dataclass
class _PrefillJob:
    """An in-progress chunked prefill (engine.prefill_chunk).

    Device state (the bucket mini cache and the running last-token logits)
    carries across chunk calls; host arrays describe the admitted wave the
    same way _admit_batch's one-shot path does."""

    key: tuple  # (n_pad, t_pad)
    ids: Any  # [n_pad, t_pad] device tokens
    lengths_np: Any
    lengths: Any  # device
    temp: Any
    top_p: Any
    slot_ids_np: Any  # padded rows duplicate row 0
    taken: list
    params_list: list
    page_grants: list
    adapter_idx: Any  # device or None
    mini: Any  # KVCache carry
    last_logits: Any  # [n_pad, vocab] carry
    written: int
    chunk_ms: float = 0.0  # accumulated chunk compute (not interleaved wall)


class OversizedRequest(ValueError):
    """A single request needs more KV pages than the whole cache holds."""


class DeadlineExceeded(RuntimeError):
    """The request's deadline budget cannot fit even one decoded token
    (rejected at submit), or expired while the request was queued."""


class ShedLowValue(RuntimeError):
    """The overload ladder shed this request: under storm its value score
    (router/value.py) fell below the rising cutoff and its SLO class was
    not protected — shed-lowest-value-first, after degradation already
    fired."""


def _bucket(n: int, floor: int, cap: int) -> int:
    """Smallest power-of-two >= n, clamped to [floor, cap]."""
    size = floor
    while size < n and size < cap:
        size *= 2
    return min(size, cap)


def prompt_budget(max_seq: int, max_tokens: int) -> int:
    """Prompt-token budget for truncation: leave room for at least one
    generated token, and never let the generation reservation eat more
    than half the sequence.  The ONE formula both admission paths use
    (AdmissionMixin.admit and the continuous Scheduler.enqueue) — a
    drift here would make the two modes truncate the same prompt
    differently."""
    return max_seq - max(1, min(max_tokens, max_seq // 2))


def pages_needed(
    prompt_tokens: int, max_tokens: int, max_seq: int, page_size: int
) -> int:
    """Worst-case KV pages a request needs (prompt + full generation,
    clamped to the sequence cap) — the grant both admission paths make
    up front so the page table stays static for the row's lifetime."""
    total = min(prompt_tokens + max_tokens, max_seq)
    return -(-total // page_size)


class PageAllocator:
    """Host-side free list for the paged KV cache (ops/paged_attention.py).

    Page 0 is reserved as the trash page: padded prefill rows and released
    slots write there, so a page handed to a live sequence is never touched
    by anyone else.  Allocation is worst-case up front (prompt + max new
    tokens), which keeps the device page table static for a sequence's
    whole lifetime — no mid-decode growth, no host sync in the decode loop.
    """

    def __init__(self, num_pages: int) -> None:
        assert num_pages >= 2, "need at least one real page beyond the trash page"
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, 0, -1))  # pop() yields low ids first

    @property
    def available(self) -> int:
        return len(self._free)

    def allocate(self, count: int) -> list[int]:
        if count > len(self._free):
            raise MemoryError(f"KV pages exhausted: want {count}, have {len(self._free)}")
        return [self._free.pop() for _ in range(count)]

    def release(self, pages: list[int]) -> None:
        self._free.extend(pages)
