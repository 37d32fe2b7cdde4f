"""Worker for the two-process jax.distributed test (test_distributed.py).

Run as: python tests/_dcn_worker.py <coordinator_addr> <process_id> <n_procs>

Each process contributes 4 virtual CPU devices (XLA_FLAGS set by the
parent); the pair forms one 8-device dp mesh over the coordination
service — the DCN topology of parallel/mesh.py's docstring, minus real
NICs.  Prints one DIST-OK line with the value of a cross-process
reduction; the parent asserts the value proves BOTH processes'
contributions landed.
"""

from __future__ import annotations

import sys

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from operator_tpu.parallel.mesh import (
    MeshPlan,
    initialize_distributed,
    make_mesh,
)


def main() -> None:
    addr, pid, n_procs = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    # the wrapper under test: must run BEFORE anything touches the backend
    initialize_distributed(
        coordinator_address=addr, num_processes=n_procs, process_id=pid
    )
    assert jax.process_count() == n_procs, jax.process_count()
    n_local = jax.local_device_count()
    n_global = jax.device_count()
    assert n_global == n_procs * n_local, (n_global, n_local)

    # dp over hosts (the layout initialize_distributed documents): each
    # process feeds its local shard, the reduction must cross processes
    mesh = make_mesh(MeshPlan(dp=n_global))
    local = np.full((n_local,), float(pid + 1), np.float32)
    arr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), local, (n_global,)
    )
    total = jax.jit(lambda x: x.sum())(arr)
    # process 0 contributes 4x1, process 1 contributes 4x2 -> 12: any
    # single-process value (4 or 8) means the collective never left home
    print(f"DIST-OK pid={pid} procs={jax.process_count()} "
          f"devices={n_global} total={float(total)}", flush=True)


if __name__ == "__main__":
    main()
