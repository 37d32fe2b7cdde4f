#!/usr/bin/env python3
"""The one-off check that the reference's own weights are the program's,
bit for bit: build the engine, make the reference's weights from the
configuration's recipe (``<reference>.WEIGHTS`` ``make``), and compare
every leaf with the program's tree, mapped by the same file's ``adapt``
(layout only).  One stacked leaf of each at a time is copied to the host.

    python3 benchmark/tools/weights_check.py --workload <cell>

Prints one JSON object: for every leaf how many elements differ, and
``identical``.  It is not what decides ``correct`` (a run never reads the
program's weights); it says that a gap read in a run is the program's
arithmetic and not another model.  Never read by the driver.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys


def differing(mine, theirs) -> int:
    import numpy as np

    mine, theirs = np.asarray(mine), np.asarray(theirs)
    if mine.shape != theirs.shape or mine.dtype != theirs.dtype:
        return -1
    return int(np.count_nonzero(mine != theirs))


async def check(manifest, workload: str) -> dict:
    import jax
    import numpy as np

    from benchmark.harness import cell

    spec = cell.Spec.load(manifest, workload)
    entry = manifest.module("entries", spec.config.get("entry", "engine"))
    cell.configure_jax()
    handle = entry.build(spec.config)
    try:
        reference, own, _ = cell.probe_group(spec)
        adapted = own.adapt(handle.parameters(), spec.config)
        # to the host first: two 7B trees do not fit one chip beside each other
        theirs = jax.tree_util.tree_map(np.asarray, adapted.leaves)
    finally:
        await handle.close()
    mine = own.make(spec.config)
    out = {}
    for name in ("embed", "ln_final", "lm_head"):
        if name in mine.leaves or name in theirs:
            out[name] = differing(mine.leaves[name], theirs[name])
    for name, leaf in mine.layers.items():
        other = theirs["layers"][name]
        if isinstance(leaf, dict):
            out[name + ".q"] = differing(leaf["q"], other["q"])
            out[name + ".s"] = differing(leaf["s"], other["s"])
        else:
            out[name] = differing(leaf, other)
    for name in sorted(set(theirs["layers"]) - set(mine.layers)):
        # leaves the recipe does not make: q/k/v biases, which it states as zeros
        out[name + ".nonzero"] = int(np.count_nonzero(theirs["layers"][name]))
    return {"workload": workload, "identical": not any(out.values()), "differing": out}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--manifest", default="BENCHMARK.json")
    args = parser.parse_args()
    root = os.getcwd()
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.harness.manifest import Manifest

    print(json.dumps(asyncio.run(
        check(Manifest(os.path.join(root, args.manifest)), args.workload)
    )), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
