#!/usr/bin/env python3
"""A step-by-step model of the continuous scheduler under an open-loop mix,
on the CPU, in a second: what a storm's *arrangement* does to its numbers.

    python3 benchmark/tools/storm_model.py --workload <cell> --seeds 1-40
    python3 benchmark/tools/storm_model.py --workload <cell> --seeds 1 --structures 0-119
        (its last lines name the typical structure: the mix's ``structure_seed``)
    python3 benchmark/tools/storm_model.py --workload <cell> --seeds 0-239 --structures seed

It replays the cell's own requests (``harness/cell.py build_open``) through
the budget rule of ``serving/sched/scheduler.py _schedule`` as it stands
since PR 22: every decoding row takes one token of the step's budget, the
rest goes to prompts in order of admission, ``sched_chunk`` tokens a row at
most; a prompt's full pages are served from the prefix cache once a row with
the same pages has finished its prefill, one token short of the whole prompt
at most.  A step takes ``base + per_page x`` the KV pages its rows walk ``+
per_token x`` the tokens it processes: the three constants of the cell's
configuration in ``STEP_MS``, fitted by least squares (``--fit``) to the
step records (``tokens``, ``kv_pages_walked``, ``wall_ms``) that
``tools/knee.py`` keeps of its windows.  They belong to that configuration
and that day's program: PR 22's were 90 ms + 0.11 ms a page, three kernels
ago.

    python3 benchmark/tools/storm_model.py --fit benchmark/out/knee/<cell>.rate*.steps.json

What it is for: choosing a mix's ``structure_seed`` (the typical storm, not a
lucky one) and saying how much of a spread between runs is the arrangement's.
What it is not: a measurement.  Its milliseconds are a model's; nothing it
prints is a metric, and the driver never runs it.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys

#: ``(base ms, ms a KV page walked, ms a token)`` of a step, by configuration:
#: fitted on TPU v5 lite to the knee sweeps' step records (my chip runs, PR 33)
STEP_MS = {
    "qwen2.5-1.5b-int8": (11.97, 0.0175, 0.0102),  # 4,106 steps at 4 and 6 requests/s, rmse 0.45 ms
    "qwen2.5-7b-int8": (13.90, 0.0249, 0.0189),  # 10,308 steps at 1.5 to 3.5 requests/s, rmse 0.39 ms
}
#: as ``harness/loops.py DRAIN_S``: a first token counts this long after the window
DRAIN_S = 5.0


def replay(
    requests: list, page: int, chunk: int, budget: int, seconds: float, step_ms: tuple,
) -> dict:
    """``requests``: ``(due_s, token ids, max_tokens)`` by due time;
    ``step_ms``: the configuration's row of ``STEP_MS``.  Returns the model's
    mean TTFT and mean token gap (ms) and tokens/s of the window."""
    base_ms, ms_per_page, ms_per_token = step_ms
    cached: set = set()
    rows: list = []
    first: dict = {}
    last: dict = {}
    tokens: dict = {}
    t, nxt = 0.0, 0
    while t < seconds + DRAIN_S:
        while nxt < len(requests) and requests[nxt][0] <= t:
            due, ids, max_tokens = requests[nxt]
            hit = 0
            while hit < (len(ids) - 1) // page and hash(ids[: (hit + 1) * page]) in cached:
                hit += 1
            rows.append({"i": nxt, "ids": ids, "pos": hit * page, "gen": 0, "max": max_tokens})
            nxt += 1
        if not rows:
            if nxt == len(requests):
                break
            t = requests[nxt][0]
            continue
        used = pages = 0
        work = []
        for row in rows:  # decoding rows first, one token each, never deferred
            if row["gen"]:
                work.append((row, 1))
                used += 1
                pages += math.ceil((len(row["ids"]) + row["gen"] + 1) / page)
        for row in rows:  # prompts share what is left, in order of admission
            count = 0 if row["gen"] else min(chunk, len(row["ids"]) - row["pos"], budget - used)
            if count > 0:
                work.append((row, count))
                used += count
                pages += math.ceil((row["pos"] + count) / page)
        t += (base_ms + ms_per_page * pages + ms_per_token * used) / 1e3
        for row, count in work:
            if row["gen"]:
                row["gen"] += 1
            else:
                row["pos"] += count
                if row["pos"] < len(row["ids"]):
                    continue
                row["gen"] = 1  # the step that ends the prompt samples the first token
                first[row["i"]] = t
                cached.update(
                    hash(row["ids"][: n * page]) for n in range(1, len(row["ids"]) // page + 1)
                )
            if t <= seconds:
                tokens[row["i"]], last[row["i"]] = row["gen"], t
        rows = [row for row in rows if row["gen"] < row["max"]]
    streamed = [i for i, n in tokens.items() if n >= 2]
    waits = [(first[i] - requests[i][0]) * 1e3 for i in first]
    return {
        "first_tokens": len(first),
        "ttft_mean_ms": statistics.fmean(waits) if waits else None,
        "token_gap_mean_ms": (
            sum(last[i] - first[i] for i in streamed) * 1e3
            / sum(tokens[i] - 1 for i in streamed)
        ) if streamed else None,
        "out_tokens_per_s": sum(tokens.values()) / seconds,
    }


def model_cell(manifest, workload: str, seed: int, seconds: float, structure=None) -> dict:
    """The model's numbers for one seed of an open-loop cell; ``structure``
    overrides the mix's ``structure_seed`` (None leaves the file's)."""
    from operator_tpu.models.tokenizer import load_tokenizer
    from operator_tpu.utils.config import OperatorConfig

    from benchmark.harness import cell

    spec = cell.Spec.load(manifest, workload)
    if structure is not None:
        spec.traffic = {**spec.traffic, "structure_seed": structure}
    engine = spec.config["engine"]
    chunk = int(engine.get("sched_chunk", OperatorConfig.sched_chunk))
    budget = int(engine.get("sched_token_budget") or max(chunk, engine["max_batch_size"]))
    tokenizer = load_tokenizer("builtin-bpe")
    ids: dict = {}
    requests = []
    for r in cell.build_open(spec, seed, seconds):
        if r.prompt not in ids:
            ids[r.prompt] = tuple(tokenizer.encode(r.prompt))
        requests.append((r.due_t, ids[r.prompt], r.max_tokens))
    return replay(
        requests, OperatorConfig.kv_page_size, chunk, budget, seconds,
        STEP_MS[spec.cell["config"]],
    )


def fit(paths: list) -> dict:
    """Least squares of ``wall_ms ~ base + per_page x pages + per_token x
    tokens`` over the step records in ``paths`` (``tools/knee.py``'s dumps:
    ``[tokens, prefill_tokens, kv_pages_walked, wall_ms]`` a step).  Steps
    over three times the median wall (a stall, a drain) are left out."""
    import numpy as np

    rows = []
    for path in paths:
        with open(path, encoding="utf-8") as f:
            rows += [row for row in json.load(f) if row[2] is not None]
    wall = np.array([row[3] for row in rows])
    keep = wall < 3 * np.median(wall)
    x = np.array([[1.0, row[2], row[0]] for row in rows])[keep]
    coef, *_ = np.linalg.lstsq(x, wall[keep], rcond=None)
    residual = x @ coef - wall[keep]
    return {
        "steps": int(keep.sum()), "left_out": int((~keep).sum()),
        "base_ms": float(coef[0]), "ms_per_page": float(coef[1]),
        "ms_per_token": float(coef[2]), "rmse_ms": float(np.sqrt(np.mean(residual ** 2))),
        "wall_ms_mean": float(wall[keep].mean()),
    }


def spread(values: list) -> float:
    """Distance between the quartiles over the median, as the driver reads it."""
    q1, _, q3 = statistics.quantiles(values, n=4, method="inclusive")
    return (q3 - q1) / statistics.median(values)


NAMES = ("ttft_mean_ms", "token_gap_mean_ms", "out_tokens_per_s")


def typical(rows: list) -> dict:
    """Of the structures tried, the one whose modelled numbers lie nearest
    the medians over all of them (distances as shares of each median,
    summed): the typical storm, not a lucky one.  What a mix's
    ``structure_seed`` is chosen by."""
    medians = {name: statistics.median(r[name] for r in rows) for name in NAMES}

    def distance(row: dict) -> float:
        return sum(abs(row[name] - medians[name]) / medians[name] for name in NAMES)

    best = min(rows, key=distance)
    return {
        "typical_structure": best["structure"], "distance": distance(best),
        "its": {name: best[name] for name in NAMES}, "medians": medians,
        "ranges": {name: [min(r[name] for r in rows), max(r[name] for r in rows)] for name in NAMES},
    }


def _numbers(text: str) -> list:
    low, _, high = text.partition("-")
    return list(range(int(low), int(high or low) + 1))


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload")
    parser.add_argument("--fit", nargs="+", metavar="STEPS_JSON", help="fit STEP_MS and stop")
    parser.add_argument("--seeds", default="1-12", help="a number or low-high")
    parser.add_argument(
        "--structures",
        help="structure seeds in place of the mix's own, or 'seed': the shape follows --seeds",
    )
    parser.add_argument("--seconds", type=float, default=51.0)
    parser.add_argument("--manifest", default="BENCHMARK.json")
    args = parser.parse_args()
    if args.fit:
        print(json.dumps(fit(args.fit)))
        return 0
    if not args.workload:
        parser.error("--workload or --fit")
    root = os.getcwd()
    if root not in sys.path:
        sys.path.insert(0, root)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # the tokenizer's package imports jax
    from benchmark.harness.manifest import Manifest

    manifest = Manifest(os.path.join(root, args.manifest))
    follow = args.structures == "seed"
    structures = _numbers(args.structures) if args.structures and not follow else [None]
    rows = []
    for structure in structures:
        for seed in _numbers(args.seeds):
            structure = seed if follow else structure
            row = model_cell(manifest, args.workload, seed, args.seconds, structure)
            rows.append({"structure": structure, "seed": seed, **row})
            print(json.dumps(rows[-1]), flush=True)
    if len(structures) >= 4:
        print(json.dumps(typical(rows)))
    if len(rows) >= 4:
        print(json.dumps({
            "model_spread": {
                name: spread([r[name] for r in rows]) for name in NAMES
            },
            "runs": len(rows),
        }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
