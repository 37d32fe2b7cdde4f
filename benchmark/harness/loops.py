"""The load loops: open (arrivals on a schedule) and closed (clients that
wait for each reply), one asyncio thread, no worker threads of their own.

Every request is timed on ``time.perf_counter`` from the moment it was
**due** (open loop) or sent (closed loop).  The streaming callback does
nothing but stamp times and count tokens.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Any, Optional

#: after the window, requests that have no first token yet get this long
DRAIN_S = 5.0
#: a request's token gap is judged only with this many tokens
MIN_GAP_TOKENS = 8


def annotate(name: str) -> Any:
    """A host span in the profiler's trace (free while none is taken)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Request:
    """One request's record; times are ``perf_counter`` seconds."""

    index: int
    prompt: str
    max_tokens: int
    due_t: float = 0.0
    sent_t: Optional[float] = None
    first_t: Optional[float] = None
    last_t: Optional[float] = None
    tokens: int = 0  # streamed so far, EOS ids included
    eos_seen: int = 0
    finished: bool = False
    error: Optional[str] = None
    prompt_tokens: Optional[int] = None
    completion_tokens: Optional[int] = None
    finish_reason: Optional[str] = None
    queue_wait_ms: Optional[float] = None
    ids_in_vocab: Optional[bool] = None
    #: this request's own sampling, where it is not the mix's: a greedy
    #: request (``Spec.greedy``), whose served ids are kept for the reference
    sampling: Optional[dict] = None
    token_ids: Optional[list] = None  # served ids, kept for greedy requests only

    @property
    def ttft_ms(self) -> Optional[float]:
        if self.first_t is None:
            return None
        return (self.first_t - self.due_t) * 1e3

    @property
    def gap_ms(self) -> Optional[float]:
        if self.first_t is None or self.tokens < MIN_GAP_TOKENS:
            return None
        return (self.last_t - self.first_t) * 1e3 / (self.tokens - 1)

    @property
    def failed(self) -> bool:
        return self.error is not None or self.first_t is None


class TokenMeter:
    """Answer tokens delivered so far, over all requests."""

    def __init__(self) -> None:
        self.delivered = 0


async def send(handle: Any, req: Request, sampling: dict, meter: TokenMeter) -> None:
    """Send one request, at its own sampling where it has one, and fill in
    its record; never raises except cancellation."""
    eos = handle.eos_id
    keep_ids = req.sampling is not None

    def on_partial(ids: list) -> None:
        now = time.perf_counter()
        if req.first_t is None:
            req.first_t = now
        new = len(ids) - req.tokens
        if new > 0:
            if eos is not None:
                req.eos_seen += ids[req.tokens:].count(eos)
            meter.delivered += new
            req.tokens = len(ids)
            req.last_t = now
            if keep_ids:
                req.token_ids = list(ids)  # as streamed: EOS ids included

    req.sent_t = time.perf_counter()
    try:
        result = await handle.generate(
            req.prompt, req.max_tokens, req.sampling or sampling, on_partial
        )
    except asyncio.CancelledError:
        raise
    except Exception as exc:  # noqa: BLE001 - a refused or failed request is a result
        req.error = f"{type(exc).__name__}: {exc}"[:200]
        return
    now = time.perf_counter()
    if req.first_t is None:
        req.first_t = now
    # the last step's tokens come with the result, not through the stream;
    # the result's ids have EOS ids filtered out (Scheduler._finish)
    total = max(req.tokens, int(result.completion_tokens) + req.eos_seen)
    meter.delivered += total - req.tokens
    if total > req.tokens:
        req.tokens, req.last_t = total, now
    req.finished = True
    req.prompt_tokens = int(result.prompt_tokens)
    req.completion_tokens = int(result.completion_tokens)
    req.finish_reason = result.finish_reason
    req.queue_wait_ms = float(result.queue_wait_ms)
    req.ids_in_vocab = all(0 <= t < handle.vocab_size for t in result.token_ids)
    if keep_ids and len(result.token_ids) == req.max_tokens:
        # the answer as the user got it; where the program filtered an EOS id
        # out of it, the ids streamed so far stand, whose positions are known
        req.token_ids = list(result.token_ids)


@dataclasses.dataclass
class Window:
    """What one measured window produced."""

    t0: float
    t1: float
    attempted: list  # requests due (open) or started (closed) inside it
    tokens_delivered: int
    first_step: int
    end_step: int
    compiles: list
    trace_dir: Optional[str] = None  # the profiler's log directory, traced runs
    pool: list = dataclasses.field(default_factory=list)  # Handle.pool_pages() samples, traced runs
    sent: list = dataclasses.field(default_factory=list)  # every request sent, a closed loop's ramp included

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


async def _sleep_until(t: float) -> float:
    delay = t - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)
    return time.perf_counter()


async def _trace_slice(out_dir: str, start_t: float, seconds: float) -> None:
    """Trace ``seconds`` from ``start_t`` with ``jax.profiler``; start and
    stop run off the event loop, so the load keeps its schedule."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # no per-call Python hook on the host
    options.enable_hlo_proto = False
    loop = asyncio.get_running_loop()
    await _sleep_until(start_t)
    await loop.run_in_executor(
        None, lambda: jax.profiler.start_trace(out_dir, profiler_options=options)
    )
    with annotate("bench.trace_slice"):
        await asyncio.sleep(seconds)
    await loop.run_in_executor(None, jax.profiler.stop_trace)


async def _sample_pool(handle: Any, samples: list, every_s: float = 0.25) -> None:
    """The KV pool's page counts, four times a second, until cancelled."""
    while True:
        pages = handle.pool_pages()
        if pages is not None:
            samples.append(pages)
        await asyncio.sleep(every_s)


def _start_tracer(
    handle: Any, trace_dir: Optional[str], t0: float, seconds: float,
    trace_seconds: float, pool: list,
) -> list:
    """The tasks of a traced run: one traces ``trace_seconds`` from the
    middle of the window, one samples the KV pool into ``pool`` all through
    it.  An untraced run has neither."""
    if not trace_dir:
        return []
    middle = t0 + max(0.0, (seconds - trace_seconds) / 2)
    return [
        asyncio.create_task(_trace_slice(trace_dir, middle, trace_seconds)),
        asyncio.create_task(_sample_pool(handle, pool)),
    ]


async def _close_window(
    handle: Any, tasks: list, tracer: list, t0: float, t1: float,
    attempted: list, sent: list, delivered: int, first_step: int,
    trace_dir: Optional[str], pool: list,
) -> Window:
    """Read the counters at the window's end, stop the pool sampler,
    drain, cancel, and wait for the tracer."""
    end_step = handle.steps_recorded()
    compiles = [e for e in handle.compiles_since_mark() if e[0] <= t1 - t0]
    for sampler in tracer[1:]:
        sampler.cancel()  # its samples end with the window
    await _drain_and_cancel(tasks + tracer[1:], attempted)
    if tracer:
        await tracer[0]
    return Window(
        t0, t1, attempted, delivered, first_step, end_step, compiles, trace_dir, pool, sent
    )


async def _drain_and_cancel(tasks: list, attempted: list) -> None:
    """Give requests without a first token ``DRAIN_S`` to get one, then
    cancel whatever still runs.  A request still decoding is not failed."""
    deadline = time.perf_counter() + DRAIN_S
    while time.perf_counter() < deadline and any(
        r.first_t is None and r.error is None for r in attempted
    ):
        await asyncio.sleep(0.05)
    for task in tasks:
        task.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


async def open_loop(
    handle: Any, requests: list, seconds: float, sampling: dict,
    trace_dir: Optional[str] = None, trace_seconds: float = 4.0,
) -> Window:
    """Send each request at ``due_t`` (seconds from the window's start),
    whether or not anything earlier has completed."""
    meter = TokenMeter()
    tasks: list = []
    handle.mark_compiles()
    first_step = handle.steps_recorded()
    t0 = time.perf_counter()
    pool_samples: list = []
    tracer = _start_tracer(handle, trace_dir, t0, seconds, trace_seconds, pool_samples)
    for req in requests:
        req.due_t += t0
    with annotate("bench.window"):
        for req in requests:
            await _sleep_until(req.due_t)
            with annotate("bench.submit"):
                tasks.append(asyncio.create_task(send(handle, req, sampling, meter)))
        t1 = await _sleep_until(t0 + seconds)
    return await _close_window(
        handle, tasks, tracer, t0, t1, requests, requests, meter.delivered, first_step,
        trace_dir, pool_samples,
    )


async def closed_loop(
    handle: Any, pools: list, seconds: float, sampling: dict, ramp_s: float,
    trace_dir: Optional[str] = None, trace_seconds: float = 4.0,
) -> Window:
    """``len(pools)`` clients, each sending the next request of its pool
    when the last completes.  The window opens ``ramp_s`` after they start."""
    meter = TokenMeter()
    sent: list = []

    async def client(pool: list) -> None:
        k = 0
        while True:
            req = dataclasses.replace(pool[k % len(pool)], index=len(sent))
            req.due_t = time.perf_counter()
            sent.append(req)
            with annotate("bench.submit"):
                await send(handle, req, sampling, meter)
            if req.error is not None:
                await asyncio.sleep(0.1)  # a refusing engine is not spun on
            k += 1

    start = time.perf_counter()
    tasks = [asyncio.create_task(client(pool)) for pool in pools]
    t0 = await _sleep_until(start + ramp_s)
    handle.mark_compiles()
    first_step = handle.steps_recorded()
    delivered0 = meter.delivered
    pool_samples: list = []
    tracer = _start_tracer(handle, trace_dir, t0, seconds, trace_seconds, pool_samples)
    with annotate("bench.window"):
        t1 = await _sleep_until(t0 + seconds)
    attempted = [r for r in sent if t0 <= r.due_t < t1]
    return await _close_window(
        handle, tasks, tracer, t0, t1, attempted, sent, meter.delivered - delivered0,
        first_step, trace_dir, pool_samples,
    )
