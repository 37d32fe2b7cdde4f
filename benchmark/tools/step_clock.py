#!/usr/bin/env python3
"""Hold the program's step clock against the harness's own clock: one
untraced window of a cell, then the step records of the window beside
what the harness measured from outside.

    python3 benchmark/tools/step_clock.py --workload <cell> --seed <n> --seconds 51

Prints one JSON object: the window's seconds against the sum of the
records' ``wall_ms``; ``step_ms_mean`` (window over records) against the
mean of ``host_ms + wait_ms + xfer_ms``; the harness's
``token_gap_mean_ms`` (over the requests that started in the window)
against the program's own ``decode_ms / completion_tokens`` over the
requests that finished in it, and, like for like, against those same
requests' last token time less first over their tokens; and the host's
parts.  The records themselves go to
``benchmark/out/steps/<cell>.seed<n>.jsonl`` (one a line, as
``python -m operator_tpu.obs.view --steps`` reads them).  Reads every field with a
default, so it runs on a program from before the fields too (and then
says what that program's records sum to).  Not a cell run, and never read
by the driver.
"""

from __future__ import annotations

import time

_STARTED = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

PARTS = ("host_ms", "wait_ms", "xfer_ms", "plan_ms", "pack_ms", "commit_ms", "turn_ms")


class Tap:
    """The entry's handle, keeping each result's own timings."""

    def __init__(self, handle) -> None:
        self._handle = handle
        self.results: list = []

    def __getattr__(self, name):
        return getattr(self._handle, name)

    async def generate(self, prompt, max_tokens, sampling, on_partial=None):
        seen = {"first_t": None, "last_t": None, "tokens": 0}

        def stamped(ids: list) -> None:
            now = time.perf_counter()
            if seen["first_t"] is None:
                seen["first_t"] = now
            if len(ids) > seen["tokens"]:
                seen["tokens"], seen["last_t"] = len(ids), now
            if on_partial is not None:
                on_partial(ids)

        result = await self._handle.generate(prompt, max_tokens, sampling, stamped)
        now = time.perf_counter()
        # the last step's tokens come with the result, as the harness counts them
        if result.completion_tokens > seen["tokens"]:
            seen["tokens"], seen["last_t"] = int(result.completion_tokens), now
        self.results.append((now, result, seen))
        return result


def mean(values: list):
    return statistics.fmean(values) if values else None


def compare(window, steps: list, results: list, gap_ms) -> dict:
    """The records against the outside."""
    def column(name: str) -> list:
        return [getattr(s, name) for s in steps if getattr(s, name, None) is not None]

    wall = column("wall_ms") or [s.total_ms for s in steps]
    finished = [
        (r, seen) for t, r, seen in results
        if window.t0 <= t <= window.t1 and r.completion_tokens > 1
        and seen["first_t"] is not None
    ]
    per_token = [r.decode_ms / r.completion_tokens for r, _ in finished]
    # the same requests from outside: last token's time less the first's,
    # over the tokens between them
    outside = [
        (seen["last_t"] - seen["first_t"]) * 1e3 / (seen["tokens"] - 1)
        for _, seen in finished if seen["tokens"] > 1
    ]
    out = {
        "window_s": window.seconds,
        "steps": len(steps),
        "sum_wall_s": sum(wall) / 1e3,
        "sum_wall_over_window": sum(wall) / 1e3 / window.seconds,
        "step_ms_mean": window.seconds * 1e3 / len(steps) if steps else None,
        "token_gap_mean_ms": gap_ms,
        "requests_finished_in_window": len(per_token),
        "decode_ms_per_token_mean": mean(per_token),
        "same_requests_gap_mean_ms": mean(outside),
    }
    for name in PARTS:
        out[f"{name}_mean"] = mean(column(name))
    if out["host_ms_mean"] is not None:
        out["host_wait_xfer_mean_ms"] = sum(out[f"{n}_mean"] for n in PARTS[:3])
        out["host_wait_xfer_over_step_ms_mean"] = (
            out["host_wait_xfer_mean_ms"] / out["step_ms_mean"]
        )
    if gap_ms and per_token:
        out["decode_ms_per_token_over_gap"] = out["decode_ms_per_token_mean"] / gap_ms
        out["decode_ms_per_token_over_same_requests_gap"] = (
            out["decode_ms_per_token_mean"] / out["same_requests_gap_mean_ms"]
        )
    if wall:
        ordered = sorted(wall)
        out["wall_ms_p10_p50_p90_max"] = [
            ordered[int(q * (len(ordered) - 1))] for q in (0.1, 0.5, 0.9, 1.0)
        ]
    return out


async def run(manifest, workload: str, seed: int, seconds: float) -> dict:
    from benchmark.harness import cell

    spec = cell.Spec.load(manifest, workload)
    entry = manifest.module("entries", spec.config.get("entry", "engine"))
    cell.configure_jax()
    handle = Tap(entry.build(spec.config))
    try:
        await cell.warm_up(spec, handle, seed)
        cell.log(f"set-up {time.perf_counter() - _STARTED:.1f}s")
        window = await cell.measure(spec, handle, seed, seconds)
        steps = handle.step_records(window.first_step, window.end_step)
        numbers = cell.end_to_end(window, window.t0 - _STARTED)
        out = compare(window, steps, handle.results, numbers["token_gap_mean_ms"])
        out["out_tokens_per_s"] = numbers["out_tokens_per_s"]
        out["device"] = cell.device_info()
        out_dir = os.path.join(manifest.paths[0], "out", "steps")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"{workload}.seed{seed}.jsonl"), "w") as f:
            for record in steps:  # what `python -m operator_tpu.obs.view --steps` reads
                f.write(json.dumps(record.to_dict()) + "\n")
        with open(os.path.join(out_dir, f"{workload}.seed{seed}.requests.json"), "w") as f:
            json.dump([
                {"finished_s": t - window.t0, "decode_ms": r.decode_ms,
                 "prefill_ms": r.prefill_ms, "queue_wait_ms": r.queue_wait_ms,
                 "completion_tokens": r.completion_tokens,
                 "prompt_tokens": r.prompt_tokens,
                 "outside_decode_ms": (
                     None if seen["first_t"] is None
                     else (seen["last_t"] - seen["first_t"]) * 1e3
                 )}
                for t, r, seen in handle.results
            ], f)
        return out
    finally:
        await handle.close()


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=51.0)
    parser.add_argument("--manifest", default="BENCHMARK.json")
    args = parser.parse_args()
    root = os.getcwd()
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.harness.manifest import Manifest

    manifest = Manifest(os.path.join(root, args.manifest))
    out = asyncio.run(run(manifest, args.workload, args.seed, args.seconds))
    print(json.dumps({"workload": args.workload, "seed": args.seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
