"""One cell, once: set-up, the correctness probe, warm-up, the measured
window, and the reduction to the contract's last line.
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

#: greedy tokens decoded per probe prompt, and how many prompts
PROBE_TOKENS = 16
PROBE_PROMPTS = 2
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


async def probe(spec: Spec, handle: Any, seed: int) -> tuple[bool, list]:
    """Correctness (a): greedy tokens through the engine against the
    float32 reference on the engine's own parameters.  Returns the verdict
    and the prompts used (the warm-up re-asks one)."""
    from benchmark.reference import decoder_f32

    arch = spec.config["architecture"]
    prompts = spec.prompts(f"probe:{seed}", [0.0] * PROBE_PROMPTS)
    meter = loops.TokenMeter()
    weights = handle.reference_weights()
    gaps: list = []
    ok = True
    for i, prompt in enumerate(prompts):
        req = loops.Request(index=i, prompt=prompt, max_tokens=PROBE_TOKENS)
        req.due_t = time.perf_counter()
        await loops.send(
            handle, req, {"temperature": 0.0, "top_p": 1.0, "stop_on_eos": False},
            meter, keep_ids=True,
        )
        if req.error is not None or len(req.token_ids or []) != PROBE_TOKENS:
            log("probe request failed:", req.error, req.token_ids)
            ok = False
            continue
        prompt_ids = handle.prompt_ids(prompt, PROBE_TOKENS)
        gaps.extend(decoder_f32.greedy_gaps(weights, arch, prompt_ids, req.token_ids))
    worst = max(gaps) if gaps else math.inf
    ok = ok and worst <= decoder_f32.LOGIT_TOLERANCE
    log(
        f"probe: {len(gaps)} greedy tokens, largest gap to the reference's "
        f"maximum {worst:.4f} (tolerance {decoder_f32.LOGIT_TOLERANCE}), "
        f"{sum(1 for g in gaps if g > 0)} not the reference's own choice"
    )
    return ok, prompts


async def warm_up(spec: Spec, handle: Any, probe_prompts: list) -> None:
    """Admit 1, 2, 3, ... rows at once: the scheduler's page-table update
    compiles once per number of rows staged in a step.  Then re-ask a probe
    prompt, so that the prefix-hit path has run too."""
    warm = spec.traffic.get("warmup", {})
    rows = int(warm.get("rows_at_once", 8))
    rows = min(rows, handle.slots)
    sampling = dict(spec.traffic["sampling"])
    meter = loops.TokenMeter()
    line = "warm-up row {k}.{j}: status: container app terminated exit code 137 reason=OOMKilled"
    for k in range(1, rows + 1):
        batch = [
            loops.Request(index=j, prompt=line.format(k=k, j=j), max_tokens=2)
            for j in range(k)
        ]
        await asyncio.gather(*(loops.send(handle, r, sampling, meter) for r in batch))
        errors = [r.error for r in batch if r.error]
        if errors:
            raise RuntimeError(f"warm-up request failed: {errors[0]}")
    again = loops.Request(index=0, prompt=probe_prompts[0], max_tokens=2)
    await loops.send(handle, again, sampling, meter)


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
    return [
        loops.Request(index=i, prompt=p, due_t=t, max_tokens=m)
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
    return [
        [
            loops.Request(
                index=0, prompt=prompts[c * POOL_PER_CLIENT + k], max_tokens=rounds[k][c]
            )
            for k in range(POOL_PER_CLIENT)
        ]
        for c in range(clients)
    ]


async def measure(
    spec: Spec, handle: Any, seed: int, seconds: float,
    trace_dir: Optional[str] = None, rate: Optional[float] = None,
) -> loops.Window:
    sampling = dict(spec.traffic["sampling"])
    if spec.traffic["loop"] == "open":
        return await loops.open_loop(
            handle, build_open(spec, seed, seconds, rate), seconds, sampling,
            trace_dir, TRACE_SLICE_S,
        )
    if spec.traffic["loop"] == "closed":
        pools = build_closed(spec, seed, handle.slots)
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
    handle = entry.build(spec.config)
    try:
        device = device_info()
        if device["count"] < int(spec.cell["chips"]):
            raise RuntimeError(
                f"the cell asks for {spec.cell['chips']} chips, JAX found {device['count']}"
            )
        peaks = load_peaks(manifest, device)
        log(f"engine built in {time.perf_counter() - started:.1f}s on", device)
        probe_ok, probe_prompts = await probe(spec, handle, seed)
        await warm_up(spec, handle, probe_prompts)
        trace_dir = None
        if trace:
            trace_dir = os.path.join(manifest.paths[0], "out", "trace", workload)
            os.makedirs(trace_dir, exist_ok=True)
        log(f"warm after {time.perf_counter() - started:.1f}s; measuring {seconds}s")
        window = await measure(spec, handle, seed, seconds, trace_dir)
        setup_s = window.t0 - started  # a closed loop's ramp is set-up too
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
            "correct": bool(probe_ok and in_window_ok),
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
            return line
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
        return line
    finally:
        await handle.close()
