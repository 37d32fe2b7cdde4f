"""Step clock: bounded per-step records for the serving decode loops.

A single opaque MFU number cannot say *where* a step's wall time goes.
Both engine loops (the wave engine's ``step()`` and the
continuous scheduler's ``Scheduler.step()``) record one
:class:`StepRecord` per COMMITTED step into a bounded :class:`StepRing`.
A record is of an INTERVAL of the worker thread's wall clock: from the
end of the previous commit to the end of this one (or, after an idle
engine, from the start of the ``step()`` that found work again), so
records never overlap and their ``wall_ms`` sum to the wall time the
engine had work.  The interval splits three ways:

- ``wait_ms``  — blocked in ``block_until_ready`` on the committed
  step's token array (the ONE sync the loop was about to perform anyway,
  so the clock adds zero new host syncs — GL001-gated): the host's slack
- ``xfer_ms``  — the sampled-token device→host fetch
- ``host_ms``  — the rest: what the host needed.  Its named parts
  (:data:`HOST_PARTS`) TILE it — every instant of the interval belongs
  to the part the worker was in: ``plan_ms`` (scheduling + admission),
  ``pack_ms`` (packing the flat token axis), ``put_ms`` (the packed
  arrays' host-to-device puts), ``launch_ms`` (the call of the compiled
  step and the bookkeeping after it), ``commit_ms`` (row commits, offload
  drains, outcomes, up to the next stamp; ``wake_ms`` of it inside the
  ``wakeups`` hand-overs to the event loop) and ``turn_ms`` (between two
  ``step()`` calls while work was pending: the event loop's turn)

``host_ms + wait_ms + xfer_ms == wall_ms`` by construction, so the
attribution fractions always total 1.0; the analytic flops-per-token
model (serving/perf.py) turns the same records into per-step achieved
TFLOPs and a measured, attributed decode MFU.  Under decode-ahead
pipelining (depth 2) the phases inside one interval belong to two step
numbers — the commit of step N and the plan + dispatch of step N+2's
predecessor — the record is of the interval, not of one dispatch.

Beside the split a record says what else the PROCESS did in its
interval, as differences of four monotonic cumulatives read at every
commit: ``cpu_ms`` (the worker thread's own CPU time, less what it used
inside the device wait), ``proc_cpu_ms`` (every thread's), ``gc_ms`` /
``gc_gen2`` (the collector's pauses, and how many were of the oldest
generation) and ``compile_ms`` (XLA compiles); and ``delivered`` /
``deliver_lag_ms`` / ``deliver_lag_max_ms``: the streamed snapshots that
reached the event loop in the interval and how long after their commit.
An interval far longer than its neighbours is a STALL (:func:`stall_over_ms`):
its record is marked and kept apart, where ordinary steps do not evict it.

The ring is host-side bookkeeping only and is never reachable from a
compiled program; ``STEP_RING_CAPACITY`` bounds it (default 512 steps).
"""

from __future__ import annotations

import os
import threading
import statistics
from dataclasses import dataclass
from typing import Iterable, Optional, Sequence

#: record kinds: a pure-prefill step, a pure-decode step, or the
#: continuous scheduler's ragged mixed step (both phases in one program)
STEP_KINDS = ("prefill", "decode", "mixed")

_DEFAULT_CAPACITY = 512

#: the named parts of ``host_ms``, in the order the worker passes through
#: them; they tile it.  The ONE list the clock, the record's fields, the
#: attribution summary and the rendering read
HOST_PARTS = ("plan", "pack", "put", "launch", "commit", "turn")

#: a stall is an interval over BOTH this wall and this many times the
#: median of recent walls
STALL_MIN_MS = 50.0
STALL_TIMES_MEDIAN = 3.0


def _env_capacity(default: int = _DEFAULT_CAPACITY) -> int:
    try:
        return int(os.environ.get("STEP_RING_CAPACITY", "") or default)
    except ValueError:  # garbage env must not fail every importer
        return default


@dataclass(frozen=True)
class StepRecord:
    """One committed step's interval of the worker thread's wall clock
    (immutable once recorded): previous commit's end → this commit's
    end.  ``kind`` / ``tokens`` / the work counts describe the step that
    COMMITTED in it; at pipeline depth 2 the ``plan_ms`` / ``pack_ms``
    inside the same interval were spent on a later step's dispatch."""

    seq: int
    kind: str  # "prefill" | "decode" | "mixed"
    tokens: int  # tokens processed this step (decode rows / prefill chunk)
    slots: int  # live slots at dispatch
    occupancy: float  # slots / max_slots
    wall_ms: float  # the interval: host_ms + wait_ms + xfer_ms
    host_ms: float  # wall_ms less wait_ms and xfer_ms
    wait_ms: float  # blocked on the device (block_until_ready)
    xfer_ms: float  # sampled-token device->host fetch
    #: named parts of ``host_ms`` (``HOST_PARTS``): a loop that stamps
    #: through ``StepClock.begin`` makes them sum to it
    plan_ms: float = 0.0
    pack_ms: float = 0.0
    put_ms: float = 0.0
    launch_ms: float = 0.0
    commit_ms: float = 0.0
    turn_ms: float = 0.0
    #: of ``commit_ms``: inside the ``wakeups`` calls that hand a row's
    #: tokens to the event loop
    wake_ms: float = 0.0
    #: what the process did in the interval (0.0 where nobody measured):
    #: the worker thread's CPU time less its CPU time inside the device
    #: wait, every thread's CPU time, the collector's pauses, XLA compiles
    cpu_ms: float = 0.0
    proc_cpu_ms: float = 0.0
    gc_ms: float = 0.0
    compile_ms: float = 0.0
    #: streamed snapshots that reached the event loop in the interval:
    #: the sum and the largest of their ages since their commit began
    deliver_lag_ms: float = 0.0
    deliver_lag_max_ms: float = 0.0
    #: the interval was a stall by the clock's rule when it was recorded
    stall: bool = False
    #: work counts taken where the step's arrays are packed; None on
    #: engines that do not distinguish.  ``prefill_tokens`` of ``tokens``
    #: were prompt tokens (the rest decode and verify tokens)
    prefill_tokens: Optional[int] = None
    #: KV pages ONE layer's ragged-attention call walks this step: the
    #: sum over slots with ``q_count > 0`` of ``cdiv(kv_len, page_size)``
    #: less the pages a sliding window skips — ``num_live - first`` of
    #: ``ops/ragged_attention._ragged_attn_kernel``
    kv_pages_walked: Optional[int] = None
    #: flash updates ONE layer's ragged-attention call makes this step:
    #: each walking slot's pages in blocks of what its rung folds into
    #: one update (``ops/ragged_attention.kv_blocks_walked``);
    #: ``kv_pages_walked`` over it is how full the blocks ran
    kv_blocks_walked: Optional[int] = None
    #: query-tile rows ONE layer's ragged-attention call computes this
    #: step: the sum over slots with ``q_count > 0`` of the tile the
    #: kernel chooses for them (``ops/ragged_attention.query_tile_rows``);
    #: ``tokens`` over it is how full the tiles were
    q_tile_rows: Optional[int] = None
    #: latent pages ONE layer's latent attention call copies in this step
    #: (a model with a latent cache, ``ops/latent_attention.py``: one
    #: ``[page_size, rank]`` latent and one rope copy a live page of a
    #: slot with queries); None for a model with a K/V cache
    latent_pages_walked: Optional[int] = None
    #: slots whose recurrent state ONE layer's scan call reads and
    #: rewrites this step (``q_count > 0``; ``ops/ssm_scan.py`` skips the
    #: others); None for a model without recurrent state
    state_rows: Optional[int] = None
    #: logit rows the step's head and sampler were asked for: one a slot,
    #: or the verify width a slot in a step that carries a draft (the
    #: host's count of what ``sched/mixed.py`` branches on, not of what
    #: ran); None on engines that do not count them
    sampled_rows: Optional[int] = None
    #: times every token of the step took the layer stack: 1, or a looped
    #: model's ``total_ut_steps`` (``sched/mixed.py`` runs every pass for
    #: every row); None on engines that do not say
    passes: Optional[int] = None
    #: of a model that denoises blocks of positions (models/sdar.py; None
    #: for any other): rows in a denoising step, the answer tokens those
    #: rows KEPT this step (``unmasked_tokens / block_rows`` is
    #: ``block_length / denoise_steps`` but for a request's first and last
    #: block), and the query tokens among ``tokens`` that only rewrite a
    #: finished block's keys (a block's first step is led by the block
    #: before it)
    block_rows: Optional[int] = None
    unmasked_tokens: Optional[int] = None
    commit_tokens: Optional[int] = None
    #: of a model with experts (None for any other): valid tokens the
    #: step's layers routed (each to ``num_experts_per_tok`` experts),
    #: experts given at least one token SUMMED OVER LAYERS, and the tokens
    #: of the fullest expert, the LARGEST over layers: the last two come
    #: back from the device beside the step's tokens
    moe_tokens: Optional[int] = None
    moe_experts_hit: Optional[int] = None
    moe_assign_max: Optional[int] = None
    #: per-step achieved MFU when the ring's owner knows the model's
    #: flops/token (serving/perf.py StepClock); None on bare rings
    mfu: Optional[float] = None
    #: generated tokens actually COMMITTED this step — differs from
    #: ``tokens`` under speculation (a verify row is billed q_count
    #: tokens of compute but lands accept+1) and under pipelining
    #: (voided work lands zero); None on engines that don't distinguish
    accepted: Optional[int] = None
    #: prompt tokens served from the prefix KV cache by rows admitted at
    #: this step (serving/kvstore.py) — kept off the billed ``tokens``
    #: so MFU stays honest on compute actually performed; None on
    #: engines without a prefix cache
    cached_tokens: Optional[int] = None
    #: hand-overs to the event loop in the commit, snapshots the loop
    #: took in the interval, collections of the oldest generation that
    #: ended in it; None on a bare ring
    wakeups: Optional[int] = None
    delivered: Optional[int] = None
    gc_gen2: Optional[int] = None

    @property
    def total_ms(self) -> float:
        return self.wall_ms

    def to_dict(self) -> dict:
        out = {
            "seq": self.seq,
            "kind": self.kind,
            "tokens": self.tokens,
            "slots": self.slots,
            "occupancy": round(self.occupancy, 4),
        }
        for name in _MS_FIELDS:
            out[name] = round(getattr(self, name), 4)
        if self.mfu is not None:
            out["mfu"] = round(self.mfu, 6)
        if self.stall:
            out["stall"] = True
        for name in _COUNT_FIELDS:
            if getattr(self, name) is not None:
                out[name] = getattr(self, name)
        return out

    @classmethod
    def from_dict(cls, data: dict) -> "StepRecord":
        return cls(
            seq=int(data.get("seq", 0)),
            kind=str(data.get("kind", "decode")),
            tokens=int(data.get("tokens", 0)),
            slots=int(data.get("slots", 0)),
            occupancy=float(data.get("occupancy", 0.0)),
            mfu=(float(data["mfu"]) if data.get("mfu") is not None else None),
            stall=bool(data.get("stall", False)),
            **{name: float(data.get(name, 0.0)) for name in _MS_FIELDS},
            **{
                name: (int(data[name]) if data.get(name) is not None else None)
                for name in _COUNT_FIELDS
            },
        )


_MS_FIELDS = (
    "wall_ms", "host_ms", "wait_ms", "xfer_ms",
    *(f"{part}_ms" for part in HOST_PARTS),
    "wake_ms", "cpu_ms", "proc_cpu_ms", "gc_ms", "compile_ms",
    "deliver_lag_ms", "deliver_lag_max_ms",
)
_COUNT_FIELDS = (
    "accepted", "cached_tokens", "prefill_tokens", "kv_pages_walked",
    "kv_blocks_walked", "q_tile_rows", "latent_pages_walked", "state_rows",
    "sampled_rows", "passes",
    "block_rows", "unmasked_tokens", "commit_tokens",
    "moe_tokens", "moe_experts_hit", "moe_assign_max",
    "wakeups", "delivered", "gc_gen2",
)


class StepRing:
    """Bounded, thread-safe ring of step records.

    Recorded from the decode worker thread, read from the event loop
    (``/healthz`` summaries, black-box dumps) — hence the lock.  Besides
    the bounded window it keeps MONOTONIC cumulative totals per kind:
    eviction-proof running sums the engines use to derive a request's
    decode wall time from the clock itself (so span timings and step
    records can never disagree, however long the generation ran).
    """

    def __init__(self, capacity: Optional[int] = None) -> None:
        self.capacity = (
            int(capacity) if capacity and int(capacity) > 0 else _env_capacity()
        )
        self._lock = threading.Lock()
        self._records: list[StepRecord] = []
        self._seq = 0
        self.evicted = 0
        #: cumulative attributed ms per kind since construction (never
        #: reset by eviction; reset() zeroes them with the ring)
        self.cum_ms = {kind: 0.0 for kind in STEP_KINDS}
        self.cum_tokens = {kind: 0 for kind in STEP_KINDS}

    def append(
        self,
        *,
        kind: str,
        tokens: int,
        slots: int,
        occupancy: float,
        wall_ms: float,
        wait_ms: float = 0.0,
        xfer_ms: float = 0.0,
        mfu: Optional[float] = None,
        **fields,
    ) -> StepRecord:
        """Append one interval's record.  ``host_ms`` is never passed: it
        is ``wall_ms`` less the two waits, so the three always sum to the
        wall.  ``fields`` are the record's optional parts and counts by
        name."""
        if kind not in STEP_KINDS:
            raise ValueError(f"unknown step kind {kind!r} (one of {STEP_KINDS})")
        wall_ms = max(0.0, float(wall_ms))
        wait_ms = min(max(0.0, float(wait_ms)), wall_ms)
        xfer_ms = min(max(0.0, float(xfer_ms)), wall_ms - wait_ms)
        with self._lock:
            record = StepRecord(
                seq=self._seq,
                kind=kind,
                tokens=int(tokens),
                slots=int(slots),
                occupancy=float(occupancy),
                wall_ms=wall_ms,
                host_ms=wall_ms - wait_ms - xfer_ms,
                wait_ms=wait_ms,
                xfer_ms=xfer_ms,
                mfu=mfu,
                **fields,
            )
            self._seq += 1
            self._records.append(record)
            if len(self._records) > self.capacity:
                del self._records[0]
                self.evicted += 1
            self.cum_ms[kind] += record.wall_ms
            self.cum_tokens[kind] += record.tokens
            return record

    @property
    def next_seq(self) -> int:
        """The ``seq`` the next appended record will get."""
        with self._lock:
            return self._seq

    def records(self, last: Optional[int] = None) -> "list[StepRecord]":
        with self._lock:
            if last is not None and last >= 0:
                return self._records[-last:] if last else []
            return list(self._records)

    def __len__(self) -> int:
        with self._lock:
            return len(self._records)

    @property
    def decode_cum_ms(self) -> float:
        """Cumulative attributed wall of every decode-bearing step (pure
        decode + mixed) — the monotonic clock request decode times are
        derived from."""
        with self._lock:
            return self.cum_ms["decode"] + self.cum_ms["mixed"]

    def reset(self) -> None:
        with self._lock:
            self._records.clear()
            self._seq = 0
            self.evicted = 0
            for kind in STEP_KINDS:
                self.cum_ms[kind] = 0.0
                self.cum_tokens[kind] = 0


def attribution(
    records: "Sequence[StepRecord]",
    *,
    flops_per_token: Optional[float] = None,
    peak_tflops: Optional[float] = None,
) -> dict:
    """Stall-attribution summary over a window of step records.

    Fractions are shares of the summed wall (``host`` + ``wait`` +
    ``xfer`` over all records), so they total 1.0 by construction.
    With a flops model, ``decode_mfu`` is the measured MFU over
    decode-bearing steps (pure decode + mixed): tokens they produced x
    flops/token against peak over their wall.  Without a peak (a device
    serving/perf.py has no row for) the MFU stays None — not measured —
    and only ``achieved_tflops`` is given."""
    host = sum(r.host_ms for r in records)
    wait = sum(r.wait_ms for r in records)
    xfer = sum(r.xfer_ms for r in records)
    total = host + wait + xfer
    decode_records = [r for r in records if r.kind in ("decode", "mixed")]
    decode_ms = sum(r.wall_ms for r in decode_records)
    decode_tokens = sum(r.tokens for r in decode_records)
    # committed generated tokens: billed tokens unless the engine
    # reported a per-step accepted count (speculation / voided work)
    accepted_tokens = sum(
        r.accepted if r.accepted is not None else r.tokens
        for r in decode_records
    )
    out = {
        "steps": len(records),
        "prefill_steps": sum(1 for r in records if r.kind == "prefill"),
        "decode_steps": sum(1 for r in records if r.kind == "decode"),
        "mixed_steps": sum(1 for r in records if r.kind == "mixed"),
        "tokens": sum(r.tokens for r in records),
        "wall_ms": round(total, 3),
        "host_ms": round(host, 3),
        "wait_ms": round(wait, 3),
        "xfer_ms": round(xfer, 3),
        # the host's named parts, summed
        "host_parts_ms": {
            name: round(sum(getattr(r, f"{name}_ms") for r in records), 3)
            for name in HOST_PARTS
        },
        "stalls": sum(1 for r in records if r.stall),
        "accepted_tokens": accepted_tokens,
        # prompt tokens the prefix cache spared from prefill compute
        "cached_tokens": sum(r.cached_tokens or 0 for r in records),
        "occupancy_avg": (
            round(sum(r.occupancy for r in records) / len(records), 4)
            if records else None
        ),
        "fractions": {
            "host": round(host / total, 4) if total else None,
            "wait": round(wait / total, 4) if total else None,
            "xfer": round(xfer / total, 4) if total else None,
        },
        "decode_mfu": None,
        "achieved_tflops": None,
    }
    if flops_per_token and decode_ms > 0 and decode_tokens:
        flops = decode_tokens * flops_per_token
        achieved = flops / (decode_ms / 1e3) / 1e12  # TFLOP/s
        out["achieved_tflops"] = round(achieved, 6)
        if peak_tflops:  # None = device not in the peak table: no MFU
            out["decode_mfu"] = round(achieved / peak_tflops, 6)
    return out


def stall_over_ms(walls: "Sequence[float]") -> float:
    """The wall above which an interval is a stall, from recent walls:
    over ``STALL_MIN_MS`` and ``STALL_TIMES_MEDIAN`` times their median
    (infinite with no walls to judge by)."""
    if not walls:
        return float("inf")
    return max(STALL_MIN_MS, STALL_TIMES_MEDIAN * statistics.median(walls))


def grown_part(record: StepRecord, typical: "Sequence[StepRecord]") -> str:
    """The part of a stalled interval that grew: of ``HOST_PARTS`` and
    the two waits, the one whose excess over its own median among
    ``typical`` records is largest."""
    def excess(part: str) -> float:
        usual = [getattr(r, f"{part}_ms") for r in typical if r is not record]
        return getattr(record, f"{part}_ms") - (
            statistics.median(usual) if usual else 0.0
        )

    return max(HOST_PARTS + ("wait", "xfer"), key=excess)


def render_stalls(records: "Sequence[StepRecord]") -> str:
    """The stalls among ``records`` alone (``obs.view --stalls``): each
    with the part that grew against the ordinary records beside it, and
    what the process did meanwhile."""
    ordinary = [r for r in records if not r.stall]
    header = (
        f"{'seq':>6}  {'kind':<7} {'wall_ms':>9} {'grew':<7} {'part_ms':>9} "
        f"{'cpu_ms':>8} {'proc_cpu':>9} {'gc_ms':>8} {'gen2':>4} "
        f"{'compile':>8} {'dlv':>4} {'lag_max':>8}"
    )
    lines = [header, "-" * len(header)]
    for r in records:
        if not r.stall:
            continue
        part = grown_part(r, ordinary)
        lines.append(
            f"{r.seq:>6}  {r.kind:<7} {r.wall_ms:>9.3f} {part:<7} "
            f"{getattr(r, part + '_ms'):>9.3f} {r.cpu_ms:>8.3f} "
            f"{r.proc_cpu_ms:>9.3f} {r.gc_ms:>8.3f} {r.gc_gen2 or 0:>4} "
            f"{r.compile_ms:>8.3f} {r.delivered or 0:>4} "
            f"{r.deliver_lag_max_ms:>8.3f}"
        )
    return "\n".join(lines)


def render_steps(records: "Iterable[StepRecord]") -> str:
    """Compact fixed-width per-step timeline table (the ``obs.view
    --steps`` rendering; also readable when pasted from a black-box
    dump).  A stall's row wears a ``*`` after its number."""
    header = (
        f"{'seq':>6}  {'kind':<7} {'tok':>5} {'pf_tok':>6} {'slots':>5} {'occ':>5} "
        f"{'wall_ms':>8} {'host_ms':>8} {'wait_ms':>8} {'xfer_ms':>8} "
        + "".join(f"{part:>7} " for part in HOST_PARTS)
        + f"{'wake':>7} {'cpu':>7} {'proc':>7} {'gc':>6} {'comp':>6} {'lag':>6} "
        f"{'blk_rows':>8} {'unmask':>6} {'cmt_tok':>7} {'moe_tok':>7} {'exp_hit':>7} "
        f"{'exp_max':>7} {'passes':>6} "
        f"{'st_rows':>7} {'smp_rows':>8} {'kv_pg':>6} {'pg_blk':>6} {'q_fill':>6} "
        f"{'mfu':>8}"
    )

    def shown(value):
        return "-" if value is None else value

    lines = [header, "-" * len(header)]
    for r in records:
        mfu = f"{r.mfu:.4f}" if r.mfu is not None else "-"
        # pages a flash update folded in, of the block its rung could take
        block = (
            f"{r.kv_pages_walked / r.kv_blocks_walked:.2f}"
            if r.kv_pages_walked and r.kv_blocks_walked else "-"
        )
        fill = f"{r.tokens / r.q_tile_rows:.3f}" if r.q_tile_rows else "-"
        pages, prompt = shown(r.kv_pages_walked), shown(r.prefill_tokens)
        state, sampled, passes = shown(r.state_rows), shown(r.sampled_rows), shown(r.passes)
        # mean age of the snapshots the event loop took in the interval
        lag = f"{r.deliver_lag_ms / r.delivered:.2f}" if r.delivered else "-"
        lines.append(
            f"{r.seq:>5}{'*' if r.stall else ' '}  {r.kind:<7} {r.tokens:>5} "
            f"{prompt:>6} {r.slots:>5} "
            f"{r.occupancy:>5.2f} {r.wall_ms:>8.3f} {r.host_ms:>8.3f} "
            f"{r.wait_ms:>8.3f} {r.xfer_ms:>8.3f} "
            + "".join(f"{getattr(r, part + '_ms'):>7.3f} " for part in HOST_PARTS)
            + f"{r.wake_ms:>7.3f} {r.cpu_ms:>7.3f} {r.proc_cpu_ms:>7.2f} "
            f"{r.gc_ms:>6.2f} {r.compile_ms:>6.1f} {lag:>6} "
            # a denoising step's rows, what they kept, the tokens that only
            # rewrite a finished block's keys; the tokens routed, experts
            # given one (over the layers), the fullest expert's
            f"{shown(r.block_rows):>8} {shown(r.unmasked_tokens):>6} "
            f"{shown(r.commit_tokens):>7} {shown(r.moe_tokens):>7} "
            f"{shown(r.moe_experts_hit):>7} {shown(r.moe_assign_max):>7} {passes:>6} "
            f"{state:>7} {sampled:>8} {pages:>6} {block:>6} {fill:>6} {mfu:>8}"
        )
    return "\n".join(lines)
