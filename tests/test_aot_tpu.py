"""AOT cross-compilation of every Pallas kernel and the serving step for a
real v5e target.

Mosaic lowering failures (layout/window asserts, "cannot be automatically
partitioned") surface at COMPILE time, so compiling against an abstract
v5e topology on the CPU host finds the on-chip crash without a chip — it
caught flash prefill's bf16 K/V head slice breaking (8,128)x2 tiling, the
ragged kernel's head_dim-64 page DMA, and the unpartitionable paged
kernel under ``SERVING_MESH``.  The child never opens a chip.  Skips
cleanly on jax installs without the TPU compiler (plain CI wheels).
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from operator_tpu.models.configs import _REGISTRY
from operator_tpu.utils.config import OperatorConfig

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def record():
    """One cross-compile of everything, in a child (the TPU compiler's
    library belongs to one process at a time)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "aot_tpu_check.py")],
        capture_output=True, text=True, timeout=900, env=env, cwd=str(REPO),
    )
    if out.returncode == 42:
        pytest.skip("this jax install has no TPU compiler")
    assert out.returncode == 0, out.stdout + out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_the_ragged_kernel_keeps_its_name_in_the_compiled_step(record):
    """A chip trace names a device event after its HLO instruction; the
    benchmark's kernel metrics find the ragged kernel by that name."""
    import re

    from operator_tpu.ops.ragged_attention import KERNEL_NAME

    sys.path.insert(0, str(REPO))
    from benchmark.layer_metrics import attn_kernel_share

    calls = record["kernels"]["mixed_step_default_model"]["pallas_calls"]
    ragged = [
        call for call in calls
        if re.fullmatch(re.escape(KERNEL_NAME) + r"(\.\d+)?", call)
    ]
    assert ragged, calls
    assert "ragged_attention" in KERNEL_NAME
    assert all(attn_kernel_share.PATTERN.search(call) for call in ragged)


def test_all_kernels_aot_compile_for_v5e(record):
    assert record["failed"] == 0, record
    kernels = record["kernels"]
    assert all(k["ok"] for k in kernels.values()), record
    # both production dtypes of every serving kernel must be present
    for name in (
        "paged_attention_v1_bf16", "paged_attention_v2_bf16",
        "flash_prefill_bf16", "similarity_best_window",
    ):
        assert name in kernels, record
    # the default path's only attention: for EVERY registered config the
    # ragged kernel either compiled at both chunk widths (verify rows and
    # prefill chunks) or was refused by name — never anything in between
    for name, config in _REGISTRY.items():
        if config.head_dim % 128:
            assert "head_dim" in kernels[f"ragged_{name}"]["refused"], record
        else:
            assert f"ragged_{name}_c5" in kernels, record
            assert f"ragged_{name}_c64" in kernels, record
    assert "ragged_window" in kernels, record
    # the kernel alone at the benchmark's two cells: a rung of the query
    # tile that cannot lower at 7B's 7 queries a kv head is found here
    assert "ragged_cell_qwen2.5-1.5b_b128" in kernels, record
    assert "ragged_cell_qwen2.5-7b_b32" in kernels, record
    # the whole mixed step at the server's default shape, for the default
    # model — which must therefore be one the kernel serves
    assert _REGISTRY[OperatorConfig().model_id].head_dim % 128 == 0
    assert kernels["mixed_step_default_model"]["argument_bytes"] > 1e9, record
    # SERVING_MESH=dp=1,tp=4: the paged kernel inside a shard_map
    assert "mesh_tp4_paged_decode" in kernels, record


def test_the_recurrent_models_step_compiles_with_its_state_pool_held_once(record):
    """The benchmark cell of a model with recurrent state: the scan kernel
    alone and the whole mixed step at 128 slots.  The 3.2 GB state pool is
    donated, carried through the layer loop and aliased by the kernel, so
    it is an argument that comes back as an output and never a temporary:
    what is not aliased of the outputs is a few vectors, and the
    temporaries hold no second pool of that size."""
    from operator_tpu.ops.ssm_scan import KERNEL_NAME

    kernels = record["kernels"]
    assert kernels["ssm_scan_cell_falcon-h1-34b_b128"]["ok"], record
    step = kernels["mixed_step_falcon-h1-34b-6l_b128"]
    assert any(call.startswith(KERNEL_NAME) for call in step["pallas_calls"]), step
    assert any(call.startswith("ragged_attention_kernel") for call in step["pallas_calls"])
    state_bytes = 6 * 128 * 32 * 256 * 128 * 4
    assert step["alias_bytes"] > state_bytes  # the state and the KV pool, in place
    assert step["output_bytes"] - step["alias_bytes"] < 1e6
    assert step["temp_bytes"] < 0.75 * state_bytes, step
    total = step["argument_bytes"] + step["output_bytes"] - step["alias_bytes"] + step["temp_bytes"]
    assert total < 15.75 * 2**30  # fits the chip
