"""One cell, once: set-up, warm-up, the measured window, the reduction to
the contract's last line and, once the engine is closed and freed, the
reference over a sample of what the window served, which decides
``correct``.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import math
import os
import random
import statistics
import sys
import time
from typing import Any, Optional

from . import loops, stats
from .manifest import Manifest, load_json

#: requests a closed-loop client has to draw from before it wraps
POOL_PER_CLIENT = 64
#: seconds of the window a traced run traces, from its middle
TRACE_SLICE_S = 4.0


def log(*parts: Any) -> None:
    """Progress goes to stderr; standard output carries the last line."""
    print("[benchmark]", *parts, file=sys.stderr, flush=True)


def configure_jax() -> None:
    """Let JAX's persistent cache keep every program, however small.  By
    default it keeps only those that took a second to compile, and a cell
    makes some 150 small ones (15 s on the chip) in every new process; the
    directory is the program's own rule (``utils/platform.py``)."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


@dataclasses.dataclass
class Spec:
    """A cell with its files read."""

    manifest: Manifest
    cell: dict
    config: dict
    traffic: dict

    @classmethod
    def load(cls, manifest: Manifest, workload: str) -> "Spec":
        cell = manifest.cell(workload)
        return cls(
            manifest, cell, manifest.config(cell["config"]),
            manifest.traffic(cell["traffic"]),
        )

    def structure_seed(self, seed: Any) -> Any:
        """The seed of the traffic's shape: arrival times, answer lengths,
        and which arrival gets which prompt.  A mix that names a
        ``structure_seed`` sends the same shape under every ``--seed``, and
        the seed then makes only the prompts' words."""
        return self.traffic.get("structure_seed", seed)

    def greedy(self) -> tuple:
        """``(every, sampling)`` of the mix's ``greedy`` group: every
        ``every``-th request (open loop) or client (closed loop) is sent at
        that sampling, and what it is served is what the reference reads.
        A mix without the group has no such request, and its cells can
        never read ``correct: true``."""
        group = self.traffic.get("greedy")
        if not group:
            return 0, None
        return int(group["every"]), dict(group["sampling"])

    def prompts(self, seed: Any, at_s: list) -> list:
        params = self.traffic["prompts"]
        if "structure_seed" in self.traffic:
            params = {**params, "structure_seed": self.traffic["structure_seed"]}
        module = self.manifest.module("generators", params["generator"])
        return module.make(seed, params, at_s)


@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader is given."""

    spec: Spec
    handle: Any
    window: loops.Window
    steps: list  # the step clock's records of the window
    device: dict  # platform, kind, count, memory_stats
    peaks: Optional[dict]  # the peaks.json row of this device kind, or None off-chip
    trace: Optional[dict] = None  # benchmark/trace/reduce.py reduce(), traced runs


# -- set-up ---------------------------------------------------------------


class SetupClock:
    """Seconds of each part of set-up, in the order they ran: a part ends
    where ``lap`` names it, and the next begins there."""

    def __init__(self, started: float) -> None:
        self.parts: dict = {}
        self._at = started

    def lap(self, name: str, now: Optional[float] = None) -> None:
        now = time.perf_counter() if now is None else now
        self.parts[name] = self.parts.get(name, 0.0) + now - self._at
        self._at = now


def probe_group(spec: Spec) -> tuple:
    """``(reference module, its weights module, the probe group)`` of the
    cell's configuration file.  A file that names no reference is an
    error: what decides ``correct`` is never a default."""
    name = spec.config.get("reference")
    if not name:
        raise ValueError(
            f"configuration {spec.cell['config']!r} names no reference: its file "
            'needs "reference": "<module under <path>/reference/>" and a "probe" '
            "group (benchmark/README.md, 'A reference')"
        )
    group = spec.config.get("probe")
    if not isinstance(group, dict) or not {"requests", "limit"} <= set(group):
        raise ValueError(
            f"configuration {spec.cell['config']!r} needs a \"probe\" group with "
            "requests and limit (and why, origin, readings)"
        )
    reference = spec.manifest.module("reference", name)
    own = spec.manifest.module("reference", reference.WEIGHTS)
    return reference, own, group


def judge(gaps: list, group: dict) -> dict:
    """The numbers compared, each beside its limit: the largest gap
    against ``limit`` and, where the group has a ``soft`` rule, that
    percentile of the gaps against its limit.  Nothing where no sequence
    came back: the count of missing requests then says so."""
    flat = [g for row in gaps for g in row]
    if not flat:
        return {}
    compared = {"served_gap_max": {"value": max(flat), "limit": float(group["limit"])}}
    soft = group.get("soft")
    if soft:
        compared[f"served_gap_p{soft['percentile']:g}"] = {
            "value": stats.percentile(flat, float(soft["percentile"])),
            "limit": float(soft["limit"]),
        }
    return compared


def served_sample(spec: Spec, handle: Any, window: loops.Window, seed: int) -> list:
    """Correctness (a), its first half, while the engine lives: a sample,
    drawn from the seed, of the greedy requests that the window finished,
    the longest among them, as ``[(prompt ids, served ids), ...]``."""
    _, _, group = probe_group(spec)
    done = [
        r for r in window.sent
        if r.sampling is not None and r.finished and r.token_ids
        and window.t0 <= r.last_t <= window.t1
    ]
    done.sort(key=lambda r: (-(r.prompt_tokens + len(r.token_ids)), r.index))
    count = int(group["requests"])
    rest = done[1:]
    random.Random(f"served:{seed}").shuffle(rest)
    sample = sorted(done[:1] + rest[:count - 1], key=lambda r: r.index)
    log(
        f"served sample: {len(sample)} of the {len(done)} greedy requests the window "
        f"finished, {sum(len(r.token_ids) for r in sample)} served tokens"
    )
    return [(handle.prompt_ids(r.prompt, r.max_tokens), r.token_ids) for r in sample]


def compare_served(spec: Spec, sequences: list, clock: Optional[SetupClock] = None) -> dict:
    """Correctness (a), its second half, once the window has closed, the
    memory peak has been read and the program's state is freed: the
    configuration's own reference, on weights of its own, teacher-forced
    over each sampled prompt with its served tokens; each number beside
    its limit."""
    reference, own, group = probe_group(spec)
    clock = clock or SetupClock(time.perf_counter())
    gaps = []
    if sequences:
        weights = own.make(spec.config)
        clock.lap("reference_weights")
        gaps = reference.greedy_gaps(spec.config, weights, sequences)
        clock.lap("reference_forward")
    compared = judge(gaps, group)
    compared["served_requests_missing"] = {
        "value": max(0, int(group["requests"]) - len(sequences)), "limit": 0,
    }
    flat = [g for row in gaps for g in row]
    log(
        f"served tokens against the reference: {len(flat)} of {len(sequences)} requests, "
        f"largest gap to the reference's maximum {max(flat, default=math.nan):.4f} (limit "
        f"{group['limit']}: {group.get('origin', 'no origin given')}), "
        f"{sum(1 for g in flat if g > 0)} not the reference's own choice"
    )
    return compared


async def warm_up(spec: Spec, handle: Any, seed: int) -> None:
    """Two prompts of the mix, one of them greedy, then one of them again,
    so that long prompts, both samplings and the prefix-hit path have run;
    then admit 1, 2, 3, ... rows at once: the scheduler's page-table update
    compiles once per number of rows staged in a step."""
    warm = spec.traffic.get("warmup", {})
    rows = min(int(warm.get("rows_at_once", 8)), handle.slots)
    sampling = dict(spec.traffic["sampling"])
    meter = loops.TokenMeter()
    _, greedy = spec.greedy()
    first = [
        loops.Request(index=i, prompt=p, max_tokens=8, sampling=greedy if i == 0 else None)
        for i, p in enumerate(spec.prompts(f"warm-up:{seed}", [0.0, 0.0]))
    ]
    line = "warm-up row {k}.{j}: status: container app terminated exit code 137 reason=OOMKilled"
    batches = [first, [dataclasses.replace(first[1], max_tokens=2)]] + [
        [loops.Request(index=j, prompt=line.format(k=k, j=j), max_tokens=2) for j in range(k)]
        for k in range(1, rows + 1)
    ]
    for batch in batches:
        await asyncio.gather(*(loops.send(handle, r, sampling, meter) for r in batch))
        errors = [r.error for r in batch if r.error]
        if errors:
            raise RuntimeError(f"warm-up request failed: {errors[0]}")


# -- the window -----------------------------------------------------------


def build_open(spec: Spec, seed: int, seconds: float, rate: Optional[float] = None) -> list:
    arrivals = dict(spec.traffic["arrivals"])
    if rate is not None:
        arrivals["rate_per_s"] = rate
    module = spec.manifest.module("generators", arrivals["generator"])
    shape = spec.structure_seed(seed)
    due = module.make(shape, arrivals, seconds)
    prompts = spec.prompts(seed, due)
    rng = random.Random(f"max_tokens:{shape}")
    max_tokens = stats.draw_ints(rng, spec.traffic["max_tokens"], len(due))
    every, greedy = spec.greedy()
    return [
        loops.Request(
            index=i, prompt=p, due_t=t, max_tokens=m,
            sampling=greedy if every and i % every == 0 else None,
        )
        for i, (t, p, m) in enumerate(zip(due, prompts, max_tokens))
    ]


def build_closed(spec: Spec, seed: int, slots: int) -> list:
    clients = spec.traffic["clients"]
    clients = slots if clients == "slots" else int(clients)
    prompts = spec.prompts(seed, [0.0] * (clients * POOL_PER_CLIENT))
    rng = random.Random(f"max_tokens:{seed}")
    first = spec.traffic.get("first_max_tokens", spec.traffic["max_tokens"])
    # stratified over the clients: each round of requests asks for the same work
    rounds = [stats.draw_ints(rng, first, clients)] + [
        stats.draw_ints(rng, spec.traffic["max_tokens"], clients)
        for _ in range(POOL_PER_CLIENT - 1)
    ]
    every, greedy = spec.greedy()
    return [
        [
            loops.Request(
                index=0, prompt=prompts[c * POOL_PER_CLIENT + k], max_tokens=rounds[k][c],
                sampling=greedy if every and c % every == 0 else None,
            )
            for k in range(POOL_PER_CLIENT)
        ]
        for c in range(clients)
    ]


async def measure(
    spec: Spec, handle: Any, seed: int, seconds: float,
    trace_dir: Optional[str] = None, rate: Optional[float] = None,
    clock: Optional[SetupClock] = None,
) -> loops.Window:
    sampling = dict(spec.traffic["sampling"])
    clock = clock or SetupClock(time.perf_counter())
    if spec.traffic["loop"] == "open":
        requests = build_open(spec, seed, seconds, rate)
        clock.lap("traffic")
        return await loops.open_loop(
            handle, requests, seconds, sampling, trace_dir, TRACE_SLICE_S,
        )
    if spec.traffic["loop"] == "closed":
        pools = build_closed(spec, seed, handle.slots)
        clock.lap("traffic")
        # the first prompts of all the clients are prefilled before the window
        ramp_s = max(
            float(spec.traffic.get("ramp_s", 0.0)),
            float(spec.traffic.get("ramp_s_per_client", 0.0)) * len(pools),
        )
        return await loops.closed_loop(
            handle, pools, seconds, sampling, ramp_s, trace_dir, TRACE_SLICE_S,
        )
    raise ValueError(f"unknown loop {spec.traffic['loop']!r}")


# -- reduction --------------------------------------------------------------


def window_correct(handle: Any, window: loops.Window) -> tuple[bool, list]:
    """Correctness (b): every finished request returned exactly its
    ``max_tokens`` ids below the vocabulary size with reason ``length``,
    and the supervisor never reset the engine.  The program filters EOS
    ids out of a result, so the ids returned plus the EOS ids seen in the
    stream must make ``max_tokens`` (the last token is not streamed, so it
    may be one more EOS)."""
    problems = []
    for r in window.attempted:
        if not r.finished:
            continue
        missing = r.max_tokens - r.completion_tokens - r.eos_seen
        if r.finish_reason != "length" or missing not in (0, 1) or not r.ids_in_vocab:
            problems.append(
                f"request {r.index}: {r.completion_tokens}+{r.eos_seen} eos of "
                f"{r.max_tokens} tokens, reason {r.finish_reason}, "
                f"ids in vocabulary {r.ids_in_vocab}"
            )
    resets = handle.engine_resets()
    if resets:
        problems.append(f"the supervisor reset the engine {resets} times")
    return not problems, problems


def end_to_end(window: loops.Window, setup_s: float) -> dict:
    """Every end-to-end number this window supports, by metric name."""
    attempted = window.attempted
    ttft = [r.ttft_ms if not r.failed else math.inf for r in attempted]
    gaps = [r.gap_ms for r in attempted if not r.failed and r.gap_ms is not None]
    streamed = [r for r in attempted if not r.failed and r.tokens >= 2]
    return {
        "setup_s": setup_s,
        "ttft_p50_ms": stats.percentile(ttft, 50),
        # over every request, a failed one infinite, so that the wait a
        # burst imposes counts in full (read by layer_metrics/ttft_mean_ms.py
        # too: recorded, not judged)
        "ttft_mean_ms": sum(ttft) / len(ttft) if ttft else None,
        "token_gap_p50_ms": stats.percentile(gaps, 50),
        # over all the window's tokens at once: some thousands of gaps, where
        # the median over requests has some tens of values to stand on
        "token_gap_mean_ms": (
            sum(r.last_t - r.first_t for r in streamed) * 1e3
            / sum(r.tokens - 1 for r in streamed)
        ) if streamed else None,
        "out_tokens_per_s": window.tokens_delivered / window.seconds,
    }


def device_info() -> dict:
    """The device as JAX reports it, with the peak memory of the fullest chip."""
    import jax

    devices = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") or 0 for d in devices]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(max(peaks)),
    }


def load_peaks(manifest: Manifest, device: dict) -> Optional[dict]:
    """This device's row of ``peaks.json``.  A TPU without a row is an
    error; off the chip there are no peaks and no device metrics."""
    if device["platform"] != "tpu":
        return None
    table = load_json(manifest.find("", "peaks.json"))
    if device["kind"] not in table:
        raise KeyError(
            f"device kind {device['kind']!r} has no row in peaks.json; add one "
            "with its source"
        )
    return table[device["kind"]]


def describe(window: loops.Window) -> dict:
    """Facts about the window for the log: counts and prompt lengths."""
    attempted = window.attempted
    prompt_tokens = sorted(r.prompt_tokens for r in attempted if r.prompt_tokens)
    out = {
        "seconds": round(window.seconds, 3),
        "attempted": len(attempted),
        "finished": sum(1 for r in attempted if r.finished),
        "failed": sum(1 for r in attempted if r.failed),
        "distinct_prompts": len({r.prompt for r in attempted}),
        "steps": window.end_step - window.first_step,
        "tokens_delivered": window.tokens_delivered,
    }
    if prompt_tokens:
        out["prompt_tokens_min_p50_max"] = [
            prompt_tokens[0], statistics.median(prompt_tokens), prompt_tokens[-1]
        ]
    return out


def save_setup(manifest: Manifest, workload: str, seed: int, setup_s: float, parts: dict) -> None:
    """The seconds of each part of the run, to the log and under ``out/``
    (no metric: ``setup_s`` is the sum of those up to ``ramp``, and the one
    that is judged; ``window`` and what follows it are in none)."""
    log("set-up parts (s):", json.dumps({k: round(v, 3) for k, v in parts.items()}))
    out_dir = os.path.join(manifest.paths[0], "out", "setup")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{workload}.seed{seed}.json"), "w") as f:
        json.dump({"setup_s": setup_s, "parts": parts}, f)


def save_requests(manifest: Manifest, workload: str, seed: int, window: loops.Window) -> None:
    """The window's request records, under ``out/``: what another statistic
    would have read, without another run."""
    out_dir = os.path.join(manifest.paths[0], "out", "requests")
    os.makedirs(out_dir, exist_ok=True)
    rows = [
        {
            "due_s": r.due_t - window.t0, "ttft_ms": r.ttft_ms, "gap_ms": r.gap_ms,
            "tokens": r.tokens, "max_tokens": r.max_tokens,
            "prompt_tokens": r.prompt_tokens, "queue_wait_ms": r.queue_wait_ms,
            "finished": r.finished, "error": r.error,
            "decode_s": None if r.first_t is None else r.last_t - r.first_t,
        }
        for r in window.attempted
    ]
    with open(os.path.join(out_dir, f"{workload}.seed{seed}.json"), "w") as f:
        json.dump(rows, f)


async def run_cell(
    manifest: Manifest, workload: str, seed: int, seconds: float, trace: bool,
    started: float,
) -> dict:
    """Run one cell and return the contract's last line as a dict."""
    spec = Spec.load(manifest, workload)
    entry = manifest.module("entries", spec.config.get("entry", "engine"))
    configure_jax()
    clock = SetupClock(started)
    handle = entry.build(spec.config)
    closed = False
    try:
        device = device_info()
        if device["count"] < int(spec.cell["chips"]):
            raise RuntimeError(
                f"the cell asks for {spec.cell['chips']} chips, JAX found {device['count']}"
            )
        peaks = load_peaks(manifest, device)
        log(f"engine built in {time.perf_counter() - started:.1f}s on", device)
        clock.lap("engine_build")
        await warm_up(spec, handle, seed)
        clock.lap("warm_up")
        trace_dir = None
        if trace:
            trace_dir = os.path.join(manifest.paths[0], "out", "trace", workload)
            os.makedirs(trace_dir, exist_ok=True)
        log(f"warm after {time.perf_counter() - started:.1f}s; measuring {seconds}s")
        window = await measure(spec, handle, seed, seconds, trace_dir, clock=clock)
        setup_s = window.t0 - started  # a closed loop's ramp is set-up too
        clock.lap("ramp", window.t0)
        clock.lap("window")  # the drain included; from here on, in no metric
        steps = handle.step_records(window.first_step, window.end_step)
        in_window_ok, problems = window_correct(handle, window)
        for problem in problems[:10]:
            log("incorrect:", problem)
        device = device_info()
        log("window:", json.dumps(describe(window)))
        log("compiles in the window:", window.compiles)
        numbers = end_to_end(window, setup_s)
        log("end to end:", json.dumps(numbers))
        save_requests(manifest, workload, seed, window)
        line = {
            "correct": False,  # decided last, below
            "attempted": len(window.attempted),
            "failed": sum(1 for r in window.attempted if r.failed),
            "metrics": {},
            "device": device,
        }
        if not trace:
            for metric in manifest.metrics_for("end_to_end", workload):
                value = numbers.get(metric["name"])
                if value is not None and math.isfinite(value):
                    line["metrics"][metric["name"]] = {
                        "value": value, "unit": metric["unit"],
                    }
        else:
            run = Run(spec, handle, window, steps, device, peaks)
            if window.trace_dir and device["platform"] == "tpu":
                from benchmark.trace import reduce as trace_reduce

                run.trace = trace_reduce.reduce_dir(window.trace_dir)
                device["busy_s"] = run.trace["busy_s"]
                device["window_s"] = run.trace["window_s"]
                line["breakdown"] = {
                    "device_ops": run.trace["device_ops"][:10],
                    "idle_gaps": run.trace["idle_gaps"][:10],
                }
            for metric in manifest.metrics_for("per_layer", workload):
                reader = manifest.module("layer_metrics", metric["name"])
                value = reader.read(run)
                if value is not None and math.isfinite(value):
                    line["metrics"][metric["name"]] = {
                        "value": value, "unit": metric["unit"],
                    }
        # the reference comes last: the window has closed, the memory peak has
        # been read, and the program's state is freed before its weights are made
        sequences = served_sample(spec, handle, window, seed)
        await handle.close()
        closed = True
        clock.lap("reduce_and_close")
        compared = compare_served(spec, sequences, clock)
        save_setup(manifest, workload, seed, setup_s, clock.parts)
        compared["window_requests_wrong"] = {"value": len(problems), "limit": 0}
        line["correct"] = bool(
            in_window_ok
            and all(entry["value"] <= entry["limit"] for entry in compared.values())
        )
        line["compared"] = compared  # last, each number beside its limit
        return line
    finally:
        if not closed:
            await handle.close()
