#!/usr/bin/env python3
"""Find an open-loop cell's knee: one process, one set-up, several windows
at rising mean rates.

    python3 benchmark/tools/knee.py --workload <cell> --seconds 40 --rates 1,2,3,4

A rate is *sustained* when at least 95% of the requests due in the window
had their first token by its end (finished or decoding) and no more
requests were waiting for a first token at the end than at the middle.
The knee is the highest sustained rate; the cell's traffic file then takes
0.8 x the knee as a number.  Beside the verdict each window says what a
reader needs to tell whose knee it is: the generator's lateness (95th
percentile; over ~20 ms the knee is the generator's), the most of the KV
pool that live rows held (a pool that sheds rows sets a knee of its own),
requests that failed, tokens a step.  Prints one JSON object per window and
a last line with the verdicts, and keeps each window's step records
(``tokens``, ``kv_pages_walked``, ``wall_ms``: what ``storm_model.py``'s
constants are fitted to) under ``out/knee/``; not a cell run, and never
read by the driver.
"""

from __future__ import annotations

import time

_STARTED = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def waiting_at(requests: list, t: float) -> int:
    """Requests due by ``t`` that had no first token at ``t``."""
    return sum(
        1 for r in requests
        if r.due_t <= t and r.error is None and (r.first_t is None or r.first_t > t)
    )


async def sample_pool(handle, samples: list, every_s: float = 0.25) -> None:
    """``(time, Handle.pool_pages())`` four times a second, until cancelled."""
    while True:
        pages = handle.pool_pages()
        if pages is not None:
            samples.append((time.perf_counter(), pages))
        await asyncio.sleep(every_s)


#: the cells' own per-layer readers that say whose knee it is: the
#: generator's, the pool's or the step's
FACTS = ("gen_lateness_p95_ms", "kv_pool_rows_share", "step_tokens_mean")


def load_facts(manifest, run) -> dict:
    """The window through the cells' own arithmetic (``cell.end_to_end``
    and the readers under ``layer_metrics/``), so that the sweep's table
    and a cell's line say the same thing."""
    from benchmark.harness import cell

    reqs = run.window.attempted
    return {
        "failed": sum(1 for r in reqs if r.failed),
        "token_gap_mean_ms": cell.end_to_end(run.window, 0.0)["token_gap_mean_ms"],
        **{name: manifest.module("layer_metrics", name).read(run) for name in FACTS},
    }


def judge(window) -> dict:
    from benchmark.harness import stats

    reqs = window.attempted
    served = sum(1 for r in reqs if r.first_t is not None and r.first_t <= window.t1)
    mid = waiting_at(reqs, (window.t0 + window.t1) / 2)
    end = waiting_at(reqs, window.t1)
    share = served / len(reqs) if reqs else 0.0
    ttft = [r.ttft_ms if not r.failed else math.inf for r in reqs]
    gaps = [r.gap_ms for r in reqs if r.gap_ms is not None]
    steps = window.end_step - window.first_step
    return {
        "attempted": len(reqs),
        "offered_per_s": len(reqs) / window.seconds,
        "served_share": share,
        "waiting_mid": mid,
        "waiting_end": end,
        "sustained": bool(reqs) and share >= 0.95 and end <= max(mid, 1),
        "errors": sum(1 for r in reqs if r.error is not None),
        "ttft_p50_ms": stats.percentile(ttft, 50),
        "ttft_p90_ms": stats.percentile(ttft, 90),
        "token_gap_p50_ms": stats.percentile(gaps, 50),
        "out_tokens_per_s": window.tokens_delivered / window.seconds,
        "step_ms_mean": window.seconds * 1e3 / steps if steps else None,
        "compiles": len(window.compiles),
    }


async def sweep(manifest, workload: str, seed: int, seconds: float, rates: list) -> list:
    from benchmark.harness import cell

    spec = cell.Spec.load(manifest, workload)
    if spec.traffic["loop"] != "open":
        raise SystemExit("the knee is a property of an open-loop cell")
    entry = manifest.module("entries", spec.config.get("entry", "engine"))
    cell.configure_jax()
    handle = entry.build(spec.config)
    out = []
    try:
        await cell.warm_up(spec, handle, seed)
        cell.log(f"set-up {time.perf_counter() - _STARTED:.1f}s")
        steps_dir = os.path.join(manifest.paths[0], "out", "knee")
        os.makedirs(steps_dir, exist_ok=True)
        for i, rate in enumerate(rates):
            pool: list = []
            sampler = asyncio.create_task(sample_pool(handle, pool))
            window = await cell.measure(spec, handle, seed + i, seconds, rate=rate)
            sampler.cancel()
            window.pool = [pages for t, pages in pool if window.t0 <= t <= window.t1]
            steps = handle.step_records(window.first_step, window.end_step)
            run = cell.Run(spec, handle, window, steps, {}, None)
            verdict = {"rate_per_s": rate, **judge(window), **load_facts(manifest, run)}
            out.append(verdict)
            with open(os.path.join(steps_dir, f"{workload}.rate{rate:g}.steps.json"), "w") as f:
                json.dump([
                    [s.tokens, getattr(s, "prefill_tokens", None),
                     getattr(s, "kv_pages_walked", None), s.wall_ms]
                    for s in steps
                ], f)
            print(json.dumps(verdict), flush=True)
            await asyncio.sleep(1.0)  # cancelled rows leave the engine
    finally:
        await handle.close()
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=40.0)
    parser.add_argument("--rates", required=True, help="comma-separated, rising")
    parser.add_argument("--manifest", default="BENCHMARK.json")
    args = parser.parse_args()
    root = os.getcwd()
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.harness.manifest import Manifest

    manifest = Manifest(os.path.join(root, args.manifest))
    rates = [float(r) for r in args.rates.split(",")]
    verdicts = asyncio.run(sweep(manifest, args.workload, args.seed, args.seconds, rates))
    sustained = [v["rate_per_s"] for v in verdicts if v["sustained"]]
    summary = {
        "workload": args.workload, "seconds": args.seconds,
        "knee_per_s": max(sustained) if sustained else None, "windows": verdicts,
    }
    out_dir = os.path.join(manifest.paths[0], "out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"knee-{args.workload}.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
