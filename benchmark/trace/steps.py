"""The program's own step spans in a profiler trace, joined to the device's
runs of the step program.

The scheduler wraps every dispatch in a host span
``podmortem.sched.dispatch`` whose arguments say what the step's kernel
calls will walk (``kv_pages``, ``qk_pairs``, ``tokens``, ``step``); the
profiler hands them back as the event's ``stats``.  The device's ``XLA
Modules`` line holds one event per run of the step program, and its ``XLA
Ops`` line the kernel's events inside each run.  The k-th dispatch span
of the traced slice is joined to the k-th run **by order**, so nothing is
computed across the host's clock and the device's.  Under decode-ahead
pipelining the first run of the slice may have been dispatched just
before the slice began: the join is then off by one step throughout,
which moves a sum over the slice by its two end terms only (a decoding
row's walk grows by one page in ``page_size`` steps), under 1% of a
roofline share.

A trace of a program that writes no such span, or no arguments on it,
gives an empty join, and the metrics that read it are left out.
"""

from __future__ import annotations

import re
from typing import Optional

from benchmark.trace import reduce as trace_reduce

DISPATCH_SPAN = "podmortem.sched.dispatch"
SPAN_PREFIX = "podmortem."


def load(path: str) -> dict:
    """``reduce.load_xplane(path)`` with ``"spans"``: the program's own
    host spans as ``(thread, name, start_ns, dur_ns, stats)``."""
    from jax.profiler import ProfileData

    events = trace_reduce.load_xplane(path)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX) and e.duration_ns > 0:
                    spans.append((
                        line.name, e.name, float(e.start_ns),
                        float(e.duration_ns), dict(e.stats),
                    ))
    events["spans"] = sorted(spans, key=lambda s: s[2])
    return events


def window_of(events: dict) -> Optional[tuple]:
    """The traced slice as ``(start_ns, end_ns)``: the harness's
    ``bench.trace_slice`` span, else first device event to last."""
    for _, name, start, dur in events.get("host", []):
        if name == trace_reduce.WINDOW_SPAN:
            return start, start + dur
    ops = [e for plane in events["device"].values() for e in plane]
    if not ops:
        return None
    return min(e[1] for e in ops), max(e[1] + e[2] for e in ops)


def step_runs(events: dict, window: tuple) -> list:
    """``(plane, start_ns, dur_ns)`` of each run, wholly inside the window,
    of the program that took most of it (the serving step), in order."""
    inside = [
        (re.sub(r"\(\d+\)$", "", name), plane, start, dur)
        for plane, runs in events.get("modules", {}).items()
        for name, start, dur in runs
        if start >= window[0] and start + dur <= window[1]
    ]
    seconds: dict = {}
    for name, _, _, dur in inside:
        seconds[name] = seconds.get(name, 0.0) + dur
    if not seconds:
        return []
    step = max(seconds, key=seconds.get)
    return sorted(
        ((plane, start, dur) for name, plane, start, dur in inside if name == step),
        key=lambda r: r[1],
    )


def kernel_steps(events: dict, pattern: "re.Pattern") -> list:
    """One dict per joined step of the slice: the dispatch span's
    ``kv_pages``, ``qk_pairs``, ``tokens`` and ``step``, and ``kernel_s``,
    the seconds of the device events matching ``pattern`` inside the run
    it was joined to.  Empty where the trace has no such spans or runs."""
    window = window_of(events)
    if window is None:
        return []
    spans = [
        stats for _, name, start, _, stats in events.get("spans", [])
        if name == DISPATCH_SPAN and "kv_pages" in stats
        and window[0] <= start <= window[1]
    ]
    joined = []
    for stats, (plane, start, dur) in zip(spans, step_runs(events, window)):
        kernel_ns = sum(
            d for name, s, d in events["device"].get(plane, [])
            if start <= s <= start + dur and pattern.search(name)
        )
        joined.append({
            "step": stats.get("step"),
            "kv_pages": int(stats["kv_pages"]),
            "qk_pairs": int(stats.get("qk_pairs", 0)),
            "tokens": int(stats.get("tokens", 0)),
            "kernel_s": kernel_ns / 1e9,
        })
    return joined
