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
        if getattr(config, "latent_dims", None) is not None:
            # a latent cache: the latent attention kernel serves it (its
            # cell's case below), never the ragged one
            assert f"ragged_{name}" not in kernels and f"ragged_{name}_c64" not in kernels
            continue
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
    assert "ragged_cell_falcon-h1-34b_b128" in kernels, record
    # one query head a kv head: sixteen [tile, 1, D] slabs
    assert "ragged_cell_ouro-2.6b_b10" in kernels, record
    # the whole mixed step at the server's default shape, for the default
    # model — which must therefore be one the kernel serves
    assert _REGISTRY[OperatorConfig().model_id].head_dim % 128 == 0
    assert kernels["mixed_step_default_model"]["argument_bytes"] > 1e9, record
    # SERVING_MESH=dp=1,tp=4: the paged kernel inside a shard_map
    assert "mesh_tp4_paged_decode" in kernels, record


def test_every_ragged_case_names_the_kv_block_of_each_rung(record):
    """The kernel compiled for v5e with the KV blocks its VMEM rule gave
    it, and the record says which: a block too large for the chip's VMEM
    at any served geometry fails the compile above; a rule that stopped
    folding pages at the cells' decode rows shows here."""
    from operator_tpu.ops.ragged_attention import KV_BLOCK_PAGES

    kernels = record["kernels"]
    ragged = {
        name: k for name, k in kernels.items()
        if name.startswith("ragged_") and "refused" not in k
    }
    assert len(ragged) >= 8, sorted(ragged)
    for name, k in ragged.items():
        assert k["ok"], (name, k)
        tiles, blocks = k["query_tiles"], k["kv_block_pages"]
        # one rung at a verify width under the small tile, two at a chunk,
        # and at one query head a kv head the rung of one query before them
        mha = tiles[0] == 1
        assert tiles[mha:] == ([5] if name.endswith("_c5") else [8, 64]), (name, tiles)
        assert len(blocks) == len(tiles), (name, blocks)
        assert set(blocks) <= set(KV_BLOCK_PAGES), (name, blocks)
        # the small tile never walks narrower than the chunk, and the rung
        # of one query walks the small tile's block
        assert blocks == sorted(blocks, reverse=True), (name, blocks)
        assert blocks[0] == blocks[mha], (name, blocks)
    cells = {
        name: (k["query_tiles"], k["kv_block_pages"]) for name, k in ragged.items()
        if name.startswith("ragged_cell_")
    }
    assert cells == {
        "ragged_cell_qwen2.5-1.5b_b128": ([8, 64], [8, 8]),
        "ragged_cell_qwen2.5-7b_b32": ([8, 64], [8, 4]),
        "ragged_cell_falcon-h1-34b_b128": ([8, 64], [8, 4]),
        # one query head a kv head: a decode row takes the rung of one
        "ragged_cell_ouro-2.6b_b10": ([1, 8, 64], [2, 2, 2]),
        # eight query heads a kv head, under the block-causal mask
        "ragged_cell_sdar-30b-a3b_b128": ([8, 64], [8, 4]),
        # 32 heads a page (the hybrid's 30, held as the tile holds them):
        # K and V in two slots are the 2 MiB budget at one page
        "ragged_cell_olmo-hybrid-7b_b48": ([1, 8, 64], [1, 1, 1]),
    }, cells
    # the window at one query head a kv head lowers on every rung
    assert ragged["ragged_window_mha"]["query_tiles"] == [1, 8, 64]


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
    # 270 MB as found: the float32 logits and the sampler's one re-laid-out
    # copy of them (134 MB each; serving/sampler.py); no pool of either
    # kind (the smallest, one KV pool, is 604 MB) is held a second time
    # while the step runs
    assert step["temp_bytes"] < 300e6, step
    total = step["argument_bytes"] + step["output_bytes"] - step["alias_bytes"] + step["temp_bytes"]
    assert total < 15.75 * 2**30  # fits the chip


def test_the_looped_models_pool_has_a_plane_a_pass_and_layer_and_fits(record):
    """The benchmark cell of the model whose stack runs four times a
    token: the pool's first axis is passes x layers, it is most of the
    step's arguments, comes back aliased, and the whole step leaves the
    chip over a GB."""
    step = record["kernels"]["mixed_step_ouro-2.6b_b10"]
    assert step["kv_pool"]["shape"] == [4 * 48, 112, 64, 16, 128]
    assert step["kv_pool"]["bytes"] > 11e9
    assert step["alias_bytes"] >= step["kv_pool"]["bytes"]
    assert step["output_bytes"] - step["alias_bytes"] < 1e6
    assert step["temp_bytes"] < 1e9, step
    total = step["argument_bytes"] + step["output_bytes"] - step["alias_bytes"] + step["temp_bytes"]
    assert total < 15.75 * 2**30 - 1e9


def test_the_sparse_expert_models_step_holds_its_stacks_and_its_pool_once(record):
    """The benchmark cell of the model with experts: the grouped product
    alone and the whole mixed step at 128 slots of two blocks of 4.  The
    expert stacks (7.25 GB int8) go into the kernel whole and the layer is
    a prefetched scalar, so no layer of 604 MB is sliced out: the
    temporaries stay far under one; the worst-case pool comes back aliased;
    the tail has no conditional; and the step leaves the chip a GB."""
    from operator_tpu.ops.moe_experts import KERNEL_NAME

    kernels = record["kernels"]
    alone = kernels["moe_experts_cell_sdar-30b-a3b_t1024"]
    assert alone["ok"] and any(c.startswith(KERNEL_NAME) for c in alone["pallas_calls"]), alone
    assert alone["temp_bytes"] < 200e6, alone  # the row gathers, no widened stack
    step = kernels["mixed_step_sdar-30b-a3b-12l_b128"]
    assert any(call.startswith(KERNEL_NAME) for call in step["pallas_calls"]), step
    assert any(call.startswith("ragged_attention_kernel") for call in step["pallas_calls"])
    assert step["kv_pool"]["shape"] == [12, 4096, 64, 4, 128]
    assert step["kv_pool"]["moved_by"] == [] and step["conditionals"] == 0
    assert step["alias_bytes"] >= step["kv_pool"]["bytes"]
    assert step["argument_bytes"] > 15e9 and step["temp_bytes"] < 700e6, step
    total = step["argument_bytes"] + step["output_bytes"] - step["alias_bytes"] + step["temp_bytes"]
    assert total < 15.75 * 2**30 - 1e9


def test_the_hybrid_models_step_sizes_its_pools_by_layer_kind_and_fits(record):
    """The benchmark cell of the model whose layers are of two kinds: the
    delta-rule kernel alone and the whole mixed step at 48 slots.  The KV
    pool has a plane for each of the 8 full-attention layers (32 heads a
    page: the 30 the model has, as the chip's tile holds them), the state
    pool 24 linear layers of 15 head pairs x 96 x 384 float32; both come
    back aliased.  The loop makes a step a period and reads every layer
    from the whole stacks, so no period's weights are copied out (a
    scanned period's slice was: 683 MB of temporaries); the step leaves
    the chip over a GB."""
    from operator_tpu.ops.gated_delta import KERNEL_NAME

    kernels = record["kernels"]
    assert kernels["gated_delta_cell_olmo-hybrid-7b_b48"]["ok"], record
    step = kernels["mixed_step_olmo-hybrid-7b_b48"]
    assert sum(call.startswith(KERNEL_NAME) for call in step["pallas_calls"]) == 3, step
    assert step["kv_pool"]["shape"] == [8, 544, 64, 32, 128]
    state_bytes = 24 * 48 * 15 * 96 * 384 * 4
    assert step["alias_bytes"] >= state_bytes + step["kv_pool"]["bytes"], step
    assert step["output_bytes"] - step["alias_bytes"] < 1e6
    assert step["temp_bytes"] < 100e6, step
    total = step["argument_bytes"] + step["output_bytes"] - step["alias_bytes"] + step["temp_bytes"]
    assert total < 15.75 * 2**30 - 1e9


def test_the_latent_models_step_holds_an_unpadded_latent_pool_once_and_fits(record):
    """The benchmark cell of the model with latent attention: the latent
    kernel alone and the whole mixed step at 128 slots, 1,024 tokens and
    4,096 pages.  The pool is 14 planes of latent rows [64, 512] and rope
    rows [32, 128], two tokens' 64-lane rotary keys to a row: 1,152 B a
    token and layer, 4.23 GB, nothing padded and no V, written in place and
    back aliased.  The dense layer runs ahead of the loop over the 13
    expert layers and its queries are read as held, so no int8 matrix is
    copied; the kernel keeps a name the attention share counts; the step
    leaves the chip over a GB."""
    import re

    from operator_tpu.ops.latent_attention import KERNEL_NAME

    sys.path.insert(0, str(REPO))
    from benchmark.layer_metrics import attn_kernel_share

    kernels = record["kernels"]
    alone = kernels["latent_attention_cell_deepseek-v2-lite_b128"]
    assert alone["ok"] and alone["query_tiles"] == [1, 64], alone
    step = kernels["mixed_step_deepseek-v2-lite-14l_b128"]
    latent = [
        call for call in step["pallas_calls"]
        if re.fullmatch(re.escape(KERNEL_NAME) + r"(\.\d+)?", call)
    ]
    # the dense layer's call ahead of the loop, the loop body's
    assert len(latent) == 2 and all(attn_kernel_share.PATTERN.search(c) for c in latent), step
    assert any(call.startswith("moe_experts_kernel") for call in step["pallas_calls"])
    pool = step["kv_pool"]
    assert pool["shape"] == [14, 4096, 64, 512] and pool["rope_shape"] == [14, 4096, 32, 128]
    assert pool["bytes"] == 14 * 4096 * 64 * 1152 == 4_227_858_432
    assert pool["moved_by"] == [] and step["weight_moves"] == [] and step["conditionals"] == 0
    assert step["alias_bytes"] >= pool["bytes"]
    assert step["temp_bytes"] < 100e6, step
    total = step["argument_bytes"] + step["output_bytes"] - step["alias_bytes"] + step["temp_bytes"]
    assert total < 15.75 * 2**30 - 1e9


@pytest.mark.parametrize("case, conditionals", [
    ("mixed_step_default_model", 1),
    ("mixed_step_qwen2.5-1.5b_b128", 1),
    ("mixed_step_qwen2.5-7b_b32", 1),
    ("mixed_step_falcon-h1-34b-6l_b128", 0),
    # 192 planes, through the pass loop and the layer loop inside it
    ("mixed_step_ouro-2.6b_b10", 1),
    # planes for the 8 full-attention layers alone, a period a loop step
    ("mixed_step_olmo-hybrid-7b_b48", 0),
    # a latent pool: latent and rope rows, the dense layer ahead of the loop
    ("mixed_step_deepseek-v2-lite-14l_b128", 0),
])
def test_the_kv_pool_is_held_once_and_never_copied(record, case, conditionals):
    """The stacked KV pools ride the layer loop's carry and are written in
    place (``serving/sched/mixed.py``): in the step's optimised HLO nothing
    copies a pool, slices a layer out of one or stacks a layer back; the
    donated pools come back aliased; and the temporaries could not hold a
    second copy of even one of them.  That holds with the tail's one
    conditional in the step (the head and the sampler at one row a slot,
    or at the verify width): the pools are no operand of it.  A step
    compiled at width 1 has no conditional."""
    step = record["kernels"][case]
    pool = step["kv_pool"]
    assert pool["moved_by"] == [], pool
    assert step["temp_bytes"] < pool["bytes"] / 2, step
    assert step["alias_bytes"] >= pool["bytes"], step
    assert step["conditionals"] == conditionals, step


#: the parent's way through the layer loop, cut from the optimised HLO of
#: its 7B step: a layer sliced out of the pool and stacked back (two
#: fusions), a whole-pool copy, and beside them what must NOT be named:
#: the in-place scatter and an instruction of another shape
_OLD_WAY_HLO = """\
%fused_computation.23.clone.clone (param_0.985: bf16[28,800,64,4,128], param_1.1206: s32[]) -> bf16[800,64,4,128] {
  %param_0.985 = bf16[28,800,64,4,128]{4,3,2,1,0:T(4,128)(2,1)} parameter(0)
  %dynamic_slice.202 = bf16[1,800,64,4,128]{4,3,2,1,0:T(4,128)(2,1)} dynamic-slice(%param_0.985, %param_1.1206), dynamic_slice_sizes={1,800,64,4,128}
  ROOT %bitcast.196 = bf16[800,64,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} bitcast(%dynamic_slice.202)
}

%fused_computation.21.clone.clone (param_0.987: bf16[28,800,64,4,128], param_1.1208: s32[], param_2.1037: bf16[800,64,4,128]) -> bf16[28,800,64,4,128] {
  %param_0.987 = bf16[28,800,64,4,128]{4,3,2,1,0:T(4,128)(2,1)} parameter(0)
  ROOT %dynamic_update_slice.16 = bf16[28,800,64,4,128]{4,3,2,1,0:T(4,128)(2,1)} dynamic-update-slice(%param_0.987, %bitcast.197, %param_1.1208)
}

%fused_computation.8.clone (param_0.915: bf16[28,800,64,4,128], param_1.1131: s32[64]) -> bf16[28,800,64,4,128] {
  ROOT %scatter.21 = bf16[28,800,64,4,128]{4,3,2,1,0:T(4,128)(2,1)} scatter(%param_0.915, %custom-call.30, %transpose.190), to_apply=%region
}

ENTRY %main.48 (paged_0_.1: bf16[28,800,64,4,128]) -> bf16[28,800,64,4,128] {
  %dynamic-slice_bitcast_fusion.5 = bf16[800,64,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} fusion(%get-tuple-element.1102, %get-tuple-element.1059), kind=kLoop, calls=%fused_computation.23.clone.clone, metadata={op_name="squeeze"}
  %bitcast_dynamic-update-slice_fusion.5 = bf16[28,800,64,4,128]{4,3,2,1,0:T(4,128)(2,1)} fusion(%get-tuple-element.1062, %fusion.234), kind=kLoop, calls=%fused_computation.21.clone.clone
  %fusion.221 = bf16[28,800,64,4,128]{4,3,2,1,0:T(4,128)(2,1)} fusion(%get-tuple-element.1002, %bitcast.217), kind=kCustom, calls=%fused_computation.8.clone
  %copy.7 = bf16[64,3584]{1,0:T(8,128)(2,1)} copy(%fusion.3)
  ROOT %copy.99 = bf16[28,800,64,4,128]{4,3,2,1,0:T(4,128)(2,1)} copy(%get-tuple-element.1135)
}
"""


def test_the_pool_scan_names_the_old_ways_slice_stack_back_and_copy():
    """What ``kv_pool.moved_by`` is read from: on the parent's HLO the scan
    names the slice, the stack-back and the copy as a chip trace would, and
    neither the in-place scatter nor a copy of another shape."""
    sys.path.insert(0, str(REPO / "scripts"))
    import aot_tpu_check

    assert aot_tpu_check._pool_moves(_OLD_WAY_HLO, (28, 800, 64, 4, 128)) == [
        "bitcast_dynamic-update-slice_fusion.5", "copy.99",
        "dynamic-slice_bitcast_fusion.5",
    ]
    assert aot_tpu_check._pool_moves(_OLD_WAY_HLO, (28, 3456, 64, 2, 128)) == []


@pytest.mark.parametrize("case", [
    "mixed_step_default_model",
    "mixed_step_qwen2.5-1.5b_b128",
    "mixed_step_qwen2.5-7b_b32",
    "mixed_step_falcon-h1-34b-6l_b128",
    "mixed_step_ouro-2.6b_b10",
    "mixed_step_sdar-30b-a3b-12l_b128",
    # the dense layer's queries too (``quant.LEAD_HEAD_PROJECTIONS``)
    "mixed_step_deepseek-v2-lite-14l_b128",
])
def test_no_layer_matrix_is_copied_in_the_mixed_step(record, case):
    """The step is compiled with the weights as the continuous path holds
    them (``models/quant.py hold_head_projections``): q, k and v are read
    from ``[L, out, in]`` in the layout their heads-major product asks
    for, so nothing copies an int8 layer matrix or a stack of them.  Held
    ``[L, in, out]``, each case had three such copies (PR 39): one a layer
    a step, or at Ouro the three 201 MB stacks at every step's head."""
    step = record["kernels"][case]
    assert step["weight_moves"] == [], step
    if case == "mixed_step_ouro-2.6b_b10":
        assert step["temp_bytes"] < 0.01e9, step  # 0.606 GB with the copies


#: the parent's 7B step, cut from its optimised HLO: the layer's ``wq``
#: sliced out of the stack into VMEM (the weight stream: not named) and
#: copied a second time into the transposed layout the heads-major dot
#: reads through a bitcast (named), and beside them a copy of another dtype
_OLD_WEIGHT_HLO = """\
%fused_computation.344.clone.clone (param_0.1492: s8[28,3584,3584], param_1.1892: s32[]) -> s8[1,3584,3584] {
  %param_0.1492 = s8[28,3584,3584]{2,1,0:T(8,128)(4,1)} parameter(0)
  %param_1.1892 = s32[]{:T(128)} parameter(1)
  %constant.1570 = s32[]{:T(128)} constant(0)
  ROOT %dynamic_slice.201 = s8[1,3584,3584]{2,1,0:T(8,128)(4,1)S(1)} dynamic-slice(%param_0.1492, %param_1.1892, %constant.1570, %constant.1570), dynamic_slice_sizes={1,3584,3584}
}

ENTRY %main.48 (wq: s8[28,3584,3584]) -> bf16[64,3584] {
  %constant_dynamic-slice_fusion.10 = s8[1,3584,3584]{2,1,0:T(8,128)(4,1)S(1)} fusion(%get-tuple-element.1106, %get-tuple-element.1046), kind=kLoop, calls=%fused_computation.344.clone.clone
  %copy.110 = s8[1,3584,3584]{1,2,0:T(8,128)(4,1)S(1)} copy(%constant_dynamic-slice_fusion.10)
  %bitcast.290 = s8[28,128,3584]{2,1,0:T(8,128)(4,1)S(1)} bitcast(%copy.110)
  ROOT %copy.7 = bf16[64,3584]{1,0:T(8,128)(2,1)} copy(%fusion.3)
}
"""


def test_the_weight_scan_names_the_transposing_copy_and_not_the_slice():
    """What ``weight_moves`` is read from: on the parent's HLO the scan
    names the transposing copy of the layer's ``wq`` as a chip trace
    would, and neither the slice that stages it nor a copy of another
    dtype; for a stack it is not given, nothing."""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, str(REPO / "scripts"))
    import aot_tpu_check

    def tree(*shapes):
        return {"layers": {
            f"w{i}": {
                "q": jax.ShapeDtypeStruct(shape, jnp.int8),
                "s": jax.ShapeDtypeStruct((shape[0], shape[2]), jnp.float32),
            }
            for i, shape in enumerate(shapes)
        }}

    assert aot_tpu_check._weight_moves(_OLD_WEIGHT_HLO, tree((28, 3584, 3584))) == ["copy.110"]
    assert aot_tpu_check._weight_moves(_OLD_WEIGHT_HLO, tree((28, 3584, 512))) == []
