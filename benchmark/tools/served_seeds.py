#!/usr/bin/env python3
"""Read what decides ``correct`` on many seeds in one process: one set-up,
then for each seed a window of the cell's own traffic at the cell's own
load (``harness/cell.py measure``) and the sample of served greedy requests
a run would compare (``served_sample``).  Once every window has closed and
the engine is freed, the reference reads each seed's sample
(``compare_served``'s own calls and ``judge``) and, for the first
``--control`` seeds, its control reads the same prompts and tokens.

    python3 benchmark/tools/served_seeds.py --workload <cell> --seeds 1,2,3 \
        --seconds 30 [--control 3]

Prints one JSON object a seed (``seed``, ``ok``, ``count``, ``max``,
``p95``, ``p99``, ``nonzero``; with the control ``control_ok``, which has
to be false by the harness's own comparison, ``control_max``,
``control_p95``, ``control_nonzero``), then a summary.  What a
configuration's ``probe`` limits are set from: the largest ``max`` is the
lower reading, the smallest ``control_max`` the upper (``PERF.md`` section
6).  ``--seconds`` has to be long enough to finish the mix's longest
requests.  Not a cell run, and never read by the driver.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time


def reading(cell, stats, gaps: list, group: dict, prefix: str = "") -> dict:
    flat = [g for row in gaps for g in row]
    compared = cell.judge(gaps, group)
    return {
        prefix + "ok": bool(compared) and all(
            entry["value"] <= entry["limit"] for entry in compared.values()
        ),
        prefix + "count": len(flat),
        prefix + "max": max(flat, default=None),
        prefix + "p95": stats.percentile(flat, 95),
        prefix + "p99": stats.percentile(flat, 99),
        prefix + "nonzero": sum(1 for g in flat if g > 0),
    }


async def read_seeds(manifest, workload: str, seeds: list, seconds: float, control: int) -> list:
    from benchmark.harness import cell, stats

    spec = cell.Spec.load(manifest, workload)
    entry = manifest.module("entries", spec.config.get("entry", "engine"))
    cell.configure_jax()
    handle = entry.build(spec.config)
    samples = []
    try:
        cell.log("device:", cell.device_info())
        reference, own, group = cell.probe_group(spec)
        await cell.warm_up(spec, handle, seeds[0])
        for seed in seeds:
            window = await cell.measure(spec, handle, seed, seconds)
            samples.append(cell.served_sample(spec, handle, window, seed))
            await asyncio.sleep(1.0)  # cancelled rows leave the engine
    finally:
        await handle.close()
    started = time.perf_counter()
    weights = own.make(spec.config)
    cell.log(f"reference weights made in {time.perf_counter() - started:.1f}s")
    rows = []
    for seed, sequences in zip(seeds, samples):
        started = time.perf_counter()
        gaps = reference.greedy_gaps(spec.config, weights, sequences) if sequences else []
        row = {"seed": seed, "requests": len(sequences), **reading(cell, stats, gaps, group)}
        row["reference_s"] = time.perf_counter() - started
        rows.append(row)
    for row, sequences in list(zip(rows, samples))[:control]:
        lowered = reference.control_gaps(spec.config, weights, sequences)
        row.update(reading(cell, stats, lowered, group, "control_"))
    for row in rows:
        print(json.dumps(row), flush=True)
    return rows


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--control", type=int, default=0)
    parser.add_argument("--manifest", default="BENCHMARK.json")
    args = parser.parse_args()
    root = os.getcwd()
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.harness.manifest import Manifest

    seeds = [int(s) for s in args.seeds.split(",")]
    rows = asyncio.run(read_seeds(
        Manifest(os.path.join(root, args.manifest)), args.workload, seeds,
        args.seconds, args.control,
    ))
    controls = [r for r in rows if "control_max" in r]
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "all_ok": all(r["ok"] for r in rows),
        "largest": max(r["max"] for r in rows),
        "largest_p95": max(r["p95"] for r in rows),
        "control_any_ok": any(r["control_ok"] for r in controls),
        "control_smallest_max": min((r["control_max"] for r in controls), default=None),
        "control_smallest_p95": min((r["control_p95"] for r in controls), default=None),
    }), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
