#!/usr/bin/env python3
"""Cut a few milliseconds out of a traced run into a small JSON trace, and
print what the trace holds (planes, lines, top operations, gaps).

    python3 benchmark/tools/record_trace.py <trace dir> <out.json> [--ms 50]

The JSON has the shape ``benchmark/trace/reduce.py load_xplane`` returns,
so ``reduce`` reads it as it reads a whole trace; the test of the reducer
runs on such a file committed beside it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("trace_dir")
    parser.add_argument("out")
    parser.add_argument("--ms", type=float, default=50.0)
    args = parser.parse_args()
    sys.path.insert(0, os.getcwd())
    from benchmark.trace import reduce as trace_reduce

    path = trace_reduce.newest_xplane(args.trace_dir)
    if path is None:
        raise SystemExit(f"no .xplane.pb under {args.trace_dir}")
    events = trace_reduce.load_xplane(path)
    whole = trace_reduce.reduce(events)
    print(json.dumps({
        "path": path, "lines": events["lines"],
        "busy_s": whole["busy_s"], "window_s": whole["window_s"],
        "idle_share": whole["idle_share"],
        "device_ops": whole["device_ops"][:25], "idle_gaps": whole["idle_gaps"][:15],
        "host_spans": sorted({h[1] for h in events["host"]})[:60],
    }, indent=1))
    ops = [e for plane in events["device"].values() for e in plane]
    middle = (min(e[1] for e in ops) + max(e[1] + e[2] for e in ops)) / 2
    small = trace_reduce.cut(events, middle, middle + args.ms * 1e6)
    with open(args.out, "w") as f:
        json.dump(small, f, separators=(",", ":"))
    print("recorded", sum(len(v) for v in small["device"].values()), "device events,",
          len(small["host"]), "host spans ->", args.out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
