"""From a profiler trace (xplane) to numbers: the benchmark's own reducer.

Two stages, so that the arithmetic can be checked on a small recorded
trace (``recorded_*.json`` beside this file) without a chip:

- :func:`load_xplane` reads an ``.xplane.pb`` with nothing but
  ``jax.profiler.ProfileData`` into plain lists of events;
- :func:`reduce` turns those lists into the busy union, the idle share,
  the device operations by summed self time under the names the trace
  prints, and the idle gaps put down to what the host was doing.

Device planes are those named ``/device:TPU:<n>``; on each, the line
``XLA Ops`` holds one event per executed HLO operation and the line
``XLA Modules`` one event per executed program (a serving step is one).  Operations nest
(a ``while`` holds its body's operations), so an operation's *self* time
is its duration less its children's, and the busy time is the union of
the intervals.  The window is the host span ``bench.trace_slice`` (the
harness wraps the traced seconds in it) where the trace has it and it
overlaps the device's events, else first device event to last.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Iterable, Optional

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.trace_slice"
#: host spans that say nothing about what the host was doing in a gap
_ENCLOSING = frozenset({WINDOW_SPAN, "bench.window"})
#: host threads whose spans explain a device gap: the event loop (load
#: generator) and the engine's one worker thread
_HOST_LINES = ("python", "tpu-decode")
#: gaps shorter than this are launch overhead between operations
MIN_GAP_NS = 20_000.0


_HLO = re.compile(r"^%?(\S+) = \(?([a-z0-9]+\[[0-9,]*\])")


def short_name(text: str) -> str:
    """An operation's name as the breakdown prints it.  The trace names an
    operation by its whole HLO line; the instruction's name and its (first)
    result type say which it is: ``copy.95 bf16[28,2049,64,2,128]``."""
    match = _HLO.match(text)
    return f"{match.group(1)} {match.group(2)}" if match else text[:120]


def load_xplane(path: str) -> dict:
    """``{"device": {plane: [(name, start_ns, dur_ns), ...]}, "modules":
    {plane: [(name, start_ns, dur_ns), ...]}, "host": [(thread, name,
    start_ns, dur_ns), ...], "lines": {plane: [line names]}}`` from one
    ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: dict = {}
    modules: dict = {}
    host: list = []
    lines: dict = {}
    for plane in data.planes:
        names = []
        for line in plane.lines:
            names.append(line.name)
            if plane.name.startswith(DEVICE_PLANE_PREFIX):
                if line.name == OPS_LINE:
                    short: dict = {}
                    device[plane.name] = [
                        (short.setdefault(e.name, short_name(e.name)),
                         float(e.start_ns), float(e.duration_ns))
                        for e in line.events
                    ]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events
                    ]
            elif plane.name.startswith("/host:") and line.name.startswith(_HOST_LINES):
                host.extend(
                    (line.name, e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                    if e.duration_ns > 0
                )
        lines[plane.name] = names
    return {"device": device, "modules": modules, "host": host, "lines": lines}


def newest_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime,
    )
    return found[-1] if found else None


def cut(events: dict, start_ns: float, end_ns: float) -> dict:
    """The events that lie wholly inside ``[start_ns, end_ns]`` (for
    recording a small trace), the enclosing window span kept clipped."""
    def inside(start: float, dur: float) -> bool:
        return start >= start_ns and start + dur <= end_ns

    host = [h for h in events["host"] if inside(h[2], h[3])]
    host.append(("python", WINDOW_SPAN, start_ns, end_ns - start_ns))
    return {
        "device": {
            plane: [e for e in ops if inside(e[1], e[2])]
            for plane, ops in events["device"].items()
        },
        "modules": {
            plane: [e for e in runs if inside(e[1], e[2])]
            for plane, runs in events.get("modules", {}).items()
        },
        "host": host,
    }


def _union_ns(intervals: Iterable[tuple]) -> tuple[float, list]:
    """Total length of the union of ``(start, end)`` intervals, and the
    gaps between its pieces as ``(start, end)``."""
    total = 0.0
    gaps = []
    cur_start = cur_end = None
    for start, end in sorted(intervals):
        if cur_end is None:
            cur_start, cur_end = start, end
        elif start <= cur_end:
            cur_end = max(cur_end, end)
        else:
            total += cur_end - cur_start
            gaps.append((cur_end, start))
            cur_start, cur_end = start, end
    if cur_end is not None:
        total += cur_end - cur_start
    return total, gaps


def _self_times(ops: list) -> dict:
    """Summed self time by operation name: duration less the children's.
    Events of one line nest properly, so a stack suffices."""
    totals: dict = {}
    stack: list = []  # [name, end, self_ns]

    def close(item: list) -> None:
        totals[item[0]] = totals.get(item[0], 0.0) + item[2]

    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            close(stack.pop())
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    while stack:
        close(stack.pop())
    return totals


def _blame(gap: tuple, host: list) -> str:
    """What the host was doing in an idle gap: the shortest (innermost)
    host span that covers the gap's middle; where none does, the span that
    overlaps the gap most."""
    middle = (gap[0] + gap[1]) / 2
    inner, inner_dur = None, float("inf")
    widest, widest_overlap = None, 0.0
    for thread, name, start, dur in host:
        if name in _ENCLOSING:
            continue
        overlap = min(gap[1], start + dur) - max(gap[0], start)
        if overlap <= 0:
            continue
        label = f"{thread.split('/')[0]}:{name}"
        if start <= middle <= start + dur and dur < inner_dur:
            inner, inner_dur = label, dur
        if overlap > widest_overlap:
            widest, widest_overlap = label, overlap
    return inner or widest or "no host span"


def _programs(modules: dict, window: tuple) -> list:
    """``[name, runs, seconds]`` of each program, over the runs that lie
    wholly inside the window, summed over the planes; most seconds first.
    A program's name as the trace prints it ends in its run's number in
    parentheses, which is dropped."""
    table: dict = {}
    for runs in modules.values():
        for name, start, dur in runs:
            if start >= window[0] and start + dur <= window[1]:
                entry = table.setdefault(re.sub(r"\(\d+\)$", "", name), [0, 0.0])
                entry[0] += 1
                entry[1] += dur / 1e9
    return sorted(
        ([name, n, seconds] for name, (n, seconds) in table.items()),
        key=lambda row: -row[2],
    )


def reduce(events: dict) -> dict:
    """Busy seconds (averaged over the device planes that ran anything),
    window seconds, idle share, ``device_ops`` and ``idle_gaps`` as lists
    of ``[name, seconds]``, largest first, ``op_self_s`` by name, and
    ``programs`` as ``[name, runs, seconds]`` (empty where the trace has no
    ``XLA Modules`` line)."""
    planes = {p: ops for p, ops in events["device"].items() if ops}
    if not planes:
        raise ValueError("no operation ran on a device in this trace")
    first = min(e[1] for ops in planes.values() for e in ops)
    last = max(e[1] + e[2] for ops in planes.values() for e in ops)
    window = (first, last)
    for _, name, start, dur in events["host"]:
        if name == WINDOW_SPAN and start < last and start + dur > first:
            window = (start, start + dur)
            break
    busy = []
    op_self: dict = {}
    gap_blame: dict = {}
    for ops in planes.values():
        clipped = [
            (max(s, window[0]), min(s + d, window[1]))
            for _, s, d in ops if s < window[1] and s + d > window[0]
        ]
        total, gaps = _union_ns(clipped)
        busy.append(total)
        if clipped:
            ends = sorted(clipped)
            gaps = [(window[0], ends[0][0])] + gaps + [
                (max(e for _, e in clipped), window[1])
            ]
        for name, ns in _self_times(ops).items():
            op_self[name] = op_self.get(name, 0.0) + ns
        for gap in gaps:
            if gap[1] - gap[0] >= MIN_GAP_NS:
                who = _blame(gap, events["host"])
                gap_blame[who] = gap_blame.get(who, 0.0) + (gap[1] - gap[0])
    n = len(planes)
    busy_s = sum(busy) / n / 1e9
    window_s = (window[1] - window[0]) / 1e9

    def ranked(table: dict) -> list:
        return [
            [name, ns / n / 1e9]
            for name, ns in sorted(table.items(), key=lambda kv: -kv[1])
        ]

    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": ranked(op_self),
        "idle_gaps": ranked(gap_blame),
        "op_self_s": {name: ns / n / 1e9 for name, ns in op_self.items()},
        "programs": _programs(events.get("modules", {}), window),
        "planes": sorted(planes),
    }


def reduce_dir(trace_dir: str) -> dict:
    """Reduce the newest trace under a ``jax.profiler`` log directory."""
    path = newest_xplane(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(load_xplane(path))
