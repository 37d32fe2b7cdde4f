#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once and print the contract's line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the checkout's root, one new process per run.  ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics
with a profiler trace of a slice of the window.  The last line of standard
output is one JSON object; everything else goes to standard error or under
``benchmark/out/``.  It fails (non-zero, no line) where the program's own
``resolve_device`` fails: no chip, no numbers.
"""

from __future__ import annotations

import time

_STARTED = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def scrub_environment() -> None:
    """Only the files decide what runs: no model override, no bench knob."""
    for key in list(os.environ):
        if key == "OPERATOR_TPU_MODEL" or key.startswith("BENCH_"):
            del os.environ[key]


def main(argv: "list[str] | None" = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--manifest", default="BENCHMARK.json",
        help="another manifest of the same shape (the tests' rehearsal)",
    )
    args = parser.parse_args(argv)
    scrub_environment()
    root = os.getcwd()
    if root not in sys.path:
        sys.path.insert(0, root)

    from benchmark.harness.cell import run_cell
    from benchmark.harness.manifest import Manifest

    manifest = Manifest(os.path.join(root, args.manifest))
    line = asyncio.run(run_cell(
        manifest, args.workload, args.seed, args.seconds, bool(args.trace), _STARTED
    ))
    for name, entry in line["compared"].items():  # the last lines on stderr
        print(
            f"[benchmark] compared {name}: {entry['value']} (limit {entry['limit']})",
            file=sys.stderr,
        )
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    # the engine's worker thread and the profiler hold nothing worth a
    # slow interpreter teardown; every task was awaited and the engine closed
    os._exit(code)
