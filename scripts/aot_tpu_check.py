#!/usr/bin/env python
"""AOT-compile every Pallas kernel and the serving step for a real v5e
target — no chip needed.

Mosaic lowering failures (layout/window asserts, VMEM overflow, "cannot
be automatically partitioned") surface at COMPILE time, so cross-compiling
against an abstract v5e topology (``jax.experimental.topologies``) on a
CPU host finds them before chip time is spent.  Covered:

- the similarity, paged-attention (v1/v2) and flash-prefill kernels;
- the ragged mixed-phase kernel — the continuous scheduler's ONLY
  attention on a TPU — at the ``(QH, KH, D)`` of every registered model
  config, bf16, page 64, chunk widths 5 (verify) and 64 (prefill), plus a
  sliding-window case at 8 and at 1 query head a kv head, and alone at
  the benchmark's cells (12/2 heads x 128 slots, 28/4 x 32, 20/4 x 128,
  16/16 x 10, 30/30 x 48): every rung of its query tile, each with the
  KV block ``kv_block_pages`` gives it (the record's ``query_tiles``,
  smallest first: the rung of one query leads; and ``kv_block_pages``:
  pages a flash update folds in at each).
  A config the kernel cannot serve must be REFUSED
  by ``require_ragged_kernel_support`` (a named error at engine build),
  never silently routed elsewhere — the check asserts which of the two
  happens for each config;
- the whole mixed step (``serving/sched/mixed.py``) for the default model
  at the server's default shape, at the 1.5B benchmark cell's (128
  slots, 3,456 pages, 256 tokens a step) and at the 7B cell's (32 slots,
  800 pages), int8 weights, with XLA's memory analysis, lowered as the
  scheduler calls it (the jitted step itself, so that the cache's donation
  counts), and ``conditionals``: the optimised HLO's ``conditional``
  instructions — one at a verify width (the step's tail), none at width 1;
- the state-space scan kernel (``ops/ssm_scan.py``) alone and the whole
  mixed step of a model with recurrent state, at the benchmark cell's
  shape (``falcon-h1-34b-6l``, 128 slots, 1,536 pages): the memory
  analysis shows the 3.2 GB state pool aliased, held once;
- the whole mixed step of a model whose stack runs several times a token
  (``ouro-2.6b``, 10 slots, 112 pages of 192 planes: the pass loop round
  the layer loop), and the ragged kernel alone at its 16 MHA heads;
- the grouped expert product (``ops/moe_experts.py``) alone at the
  sparse-expert cell's sizes, the ragged kernel at its 32 / 4 heads under
  the block-causal mask, and the whole mixed step of ``sdar-30b-a3b-12l``
  (128 slots, 1,024 tokens, 4,096 pages, the denoising tail): the expert
  stacks go into the kernel whole, so the temporaries hold no layer's
  copy of one;
- the latent attention kernel (``ops/latent_attention.py``) alone at the
  DeepSeek-V2-Lite cell's sizes and the whole mixed step of
  ``deepseek-v2-lite-14l`` (128 slots, 1,024 tokens, a LATENT pool of 14
  planes x 4,096 pages: ``kv_pool.shape`` the latent rows, ``rope_shape``
  the rope rows, no V), its dense layer ahead of the loop over 13 expert
  layers;
- for each whole mixed step, ``kv_pool``: the stacked KV pools' bytes and
  the names of the optimised HLO's instructions that MOVE a pool — a
  ``copy``, ``dynamic-slice`` or ``dynamic-update-slice`` (or a fusion that
  holds one) whose result has the pool's shape or one layer's slice of it.  The pools ride the layer loop's carry and are written in place:
  the list is empty;
- for each whole mixed step, compiled with the weights as the continuous
  path holds them (``models/quant.py hold_head_projections``),
  ``weight_moves``: the optimised HLO's ``copy`` instructions (or fusions
  that hold one) whose result is an int8 layer matrix, one layer's slice
  of a stack or a stack whole.  q, k and v are read from ``[L, out, in]``
  as their heads-major product asks: the list is empty (three a step when
  they were held ``[in, out]``: PR 39);
- the sharded wave decode step over the 4-device topology (``tp=4``): the
  program ``SERVING_MESH=dp=1,tp=4`` runs, whose paged-attention kernel
  must sit inside a ``shard_map``.

Numerical parity still needs the chip (``chip_smoke.py``'s kernel leg).

Prints one line per case and a final JSON summary; exits 1 on any
failure, 42 when the jax install has no TPU compiler (plain CI wheels) —
callers treat 42 (and only 42: CPython itself exits 2 on a missing
script) as skip.
"""

from __future__ import annotations

import functools
import json
import math
import os
import re
import sys
from unittest import mock

# the host side of a cross-compile is the CPU; never open a chip from here
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

TOPOLOGY = os.environ.get("AOT_TPU_TOPOLOGY", "v5e:2x2x1")

#: the server's default engine shape (utils/config.py OperatorConfig)
_SLOTS, _PAGE, _CHUNK, _MAX_SEQ, _SPEC_WIDTH = 32, 64, 64, 2048, 5


def _memory(compiled) -> dict:
    mem = compiled.memory_analysis()
    if mem is None:
        return {}
    return {
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
    }


#: what moves a buffer whole: the opcodes a pool's instruction must not be
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")


def _moves(hlo: str, shapes: set, opcodes: tuple) -> list[str]:
    """Names, as a trace of the chip would print them, of the optimised
    HLO's instructions whose opcode is one of ``opcodes`` and whose result
    has one of ``shapes`` (``"s8[1,3584,3584]"``, layout aside) — or of the
    fusion that holds one."""
    header = re.compile(r"(?:ENTRY )?%?(\S+) \(.*\{$")
    instruction = re.compile(r"\s*(?:ROOT )?%?(\S+) = (\w+\[[\d,]*\])\S* ([\w-]+)\(")
    fusion_of = dict(  # fused computation -> the fusion instruction that calls it
        (called, name) for name, called in re.findall(
            r"^\s*(?:ROOT )?%?(\S+) = [^\n]* fusion\([^\n]*calls=%?([\w.-]+)", hlo, re.M,
        )
    )
    found, computation = set(), None
    for line in hlo.splitlines():
        opened = header.match(line)
        if opened:
            computation = opened.group(1)
            continue
        parsed = instruction.match(line)
        if parsed and parsed.group(2) in shapes and parsed.group(3) in opcodes:
            found.add(fusion_of.get(computation, parsed.group(1)))
    return sorted(found)


def _stack_shapes(dtype: str, stack: tuple) -> set:
    """A stack's shape, one layer's slice of it, and the slice with its
    leading 1, as the HLO writes them."""
    return {
        dtype + "[" + ",".join(str(d) for d in shape) + "]"
        for shape in (stack, stack[1:], (1, *stack[1:]))
    }


def _pool_moves(hlo: str, pool_shape: tuple) -> list[str]:
    """The ``copy``, ``dynamic-slice`` or ``dynamic-update-slice``
    instructions (or fusions holding one) whose result is the stacked
    pool or one layer's slice of it."""
    return _moves(hlo, _stack_shapes("bf16", tuple(pool_shape)), _MOVES)


def _weight_moves(hlo: str, params) -> list[str]:
    """The ``copy`` instructions (or fusions holding one) whose result is
    an int8 layer matrix of ``params`` (abstract), one layer's slice of its
    stack, or the stack whole.  The slices a layer's product reads its
    weight through are not named: they are the weight stream."""
    shapes = set()
    for leaf in jax.tree_util.tree_leaves(params["layers"]):
        if leaf.dtype == jnp.int8:
            shapes |= _stack_shapes("s8", tuple(leaf.shape))
            if leaf.ndim == 4:
                # stacked by period and layer (models/olmo_hybrid.py), or
                # by layer and expert: one layer's (one expert's) slice too
                shapes |= _stack_shapes("s8", tuple(leaf.shape[1:]))
    return _moves(hlo, shapes, ("copy",))


def _abstract_params(config, sharding_for, held=False):
    """The int8 serving tree as ShapeDtypeStructs (``jax.eval_shape``: no
    weight is ever allocated); ``held``: as the continuous path holds it
    (``models/quant.py hold_head_projections``)."""
    from operator_tpu.models.quant import hold_head_projections, init_params_quantized

    def tree(key):
        params = init_params_quantized(config, key, dtype=jnp.bfloat16)
        return hold_head_projections(params) if held else params

    shapes = jax.eval_shape(tree, jax.ShapeDtypeStruct((2,), jnp.uint32))
    return jax.tree_util.tree_map(
        lambda leaf, sh: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sh),
        shapes, sharding_for(shapes),
    )


def _mixed_step_case(topo_device, model_id=None, slots=_SLOTS, t_budget=None,
                     kv_pages=None, spec_width=_SPEC_WIDTH):
    """(fn, args, the stacked pools' shapes: K and V, or a latent pool's
    latent and rope rows) for the continuous scheduler's
    one program: by default at the server's default shape for the default
    model, else at a benchmark cell's (its slots, token budget, pool pages
    and sampled width)."""
    from jax.sharding import SingleDeviceSharding

    from operator_tpu.models import get_config
    from operator_tpu.ops.paged_attention import PagedKVCache
    from operator_tpu.ops.ragged_attention import pool_heads
    from operator_tpu.serving import sampler
    from operator_tpu.serving.sched.mixed import make_mixed_fn
    from operator_tpu.utils.config import OperatorConfig

    config = get_config(model_id or OperatorConfig().model_id)
    sharding = SingleDeviceSharding(topo_device)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    class _Shapes:
        """The attributes ``make_mixed_fn`` reads off a runtime."""

        _jax, _jnp = jax, jnp
        max_slots = slots
        sample = staticmethod(
            functools.partial(sampler.sample, top_k=sampler.SAMPLE_TOP_K)
        )
        sample_confident = staticmethod(functools.partial(
            sampler.sample_with_confidence, top_k=sampler.SAMPLE_TOP_K
        ))

    generator = _Shapes()
    generator.config = config
    pages_per_seq = _MAX_SEQ // _PAGE
    num_pages = kv_pages or slots * pages_per_seq + 1
    recurrent = PagedKVCache.recurrent_shapes(config, slots)
    latent = getattr(config, "latent_dims", None)
    if latent is not None:
        # a latent pool (ops/latent_attention.py): latent rows and rope
        # rows, no V
        from operator_tpu.ops.latent_attention import pool_shapes

        pool_shape, rope_shape = pool_shapes(config.kv_planes, num_pages, _PAGE, *latent)
        pools = (pool_shape, rope_shape)
    else:
        pool_shape = (
            config.kv_planes, num_pages, _PAGE, pool_heads(config.num_kv_heads),
            config.head_dim,
        )
        pools = (pool_shape, pool_shape)
    paged = PagedKVCache(
        k_pages=shaped(pool_shape, jnp.bfloat16),
        v_pages=None if latent is not None else shaped(pool_shape, jnp.bfloat16),
        page_table=shaped((slots, pages_per_seq), jnp.int32),
        lengths=shaped((slots,), jnp.int32),
        ssm_state=None if recurrent is None else shaped(recurrent[0], jnp.float32),
        conv_state=None if recurrent is None else shaped(recurrent[1], jnp.bfloat16),
        rope_pages=None if latent is None else shaped(pools[1], jnp.bfloat16),
    )
    t = t_budget or max(_CHUNK, slots)
    params = _abstract_params(
        config, lambda tree: jax.tree_util.tree_map(lambda _: sharding, tree),
        held=True,
    )
    flat_i, flat_b = shaped((t,), jnp.int32), shaped((t,), jnp.bool_)
    slot_i, slot_f = shaped((slots,), jnp.int32), shaped((slots,), jnp.float32)
    # a model that denoises blocks carries each slot's block, and is told
    # per slot what its step keeps (sched/mixed.py)
    block = int(getattr(config, "block_length", 0))
    latest = shaped((slots, block), jnp.int32) if block else slot_i
    denoise = ()
    if block:
        denoise = ({
            "keep": slot_i, "limit": slot_i,
            "low_confidence": shaped((slots,), jnp.bool_),
        },)
    args = (
        params, paged,
        flat_i, flat_i, flat_i, flat_b, flat_i,  # ids rows pos valid in_row
        slot_i, slot_i, slot_i, latest, flat_b,  # q_start q_count kv_len latest from_prev
        slot_i, slot_i,  # sample_start spec_len
        shaped((2,), jnp.uint32), slot_f, slot_f,  # rng temp top_p
        *denoise,
    )
    return make_mixed_fn(generator, t, _CHUNK, spec_width=spec_width), args, pools


def _mesh_decode_case(topo_devices):
    """(fn, args) for the wave engine's paged decode step under
    ``SERVING_MESH=dp=1,tp=4`` — GSPMD over the 4-device topology with the
    paged-attention kernel shard_mapped over ``tp`` kv heads."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from operator_tpu.models import get_config
    from operator_tpu.models.llama import decode_step_paged
    from operator_tpu.ops.paged_attention import PagedKVCache
    from operator_tpu.parallel.mesh import (
        MeshPlan, make_mesh, paged_cache_specs, param_shardings,
    )

    config = get_config("qwen2.5-7b")
    mesh = make_mesh(MeshPlan(dp=1, fsdp=1, tp=4), list(topo_devices))

    def ns(spec):
        return NamedSharding(mesh, spec)

    def shaped(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=ns(spec))

    params = _abstract_params(
        config, lambda _: param_shardings(mesh, config, quantized=True)
    )
    pages_per_seq = _MAX_SEQ // _PAGE
    num_pages = _SLOTS * pages_per_seq + 1
    specs = paged_cache_specs()
    pool = (config.num_layers, num_pages, _PAGE, config.num_kv_heads,
            config.head_dim)
    paged = PagedKVCache(
        k_pages=shaped(pool, jnp.bfloat16, specs.k_pages),
        v_pages=shaped(pool, jnp.bfloat16, specs.v_pages),
        page_table=shaped((_SLOTS, pages_per_seq), jnp.int32, P(None, None)),
        lengths=shaped((_SLOTS,), jnp.int32, P(None)),
    )
    tokens = shaped((_SLOTS, 1), jnp.int32, P(("dp", "fsdp"), None))

    def decode(params, paged, tokens):
        logits, new_paged = decode_step_paged(
            params, config, tokens, paged, mesh=mesh
        )
        return jnp.argmax(logits, axis=-1), new_paged

    return decode, (params, paged, tokens)


def main() -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name=TOPOLOGY
        )
    except Exception as exc:
        if os.environ.get("AOT_TPU_TOPOLOGY"):
            # an explicitly requested topology failing is an ERROR, not a
            # missing-compiler skip — surfacing typos/format drift
            raise
        print(f"SKIP: no TPU topology support here ({exc})", file=sys.stderr)
        return 42
    sharding = SingleDeviceSharding(topo.devices[0])

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    from operator_tpu.models.configs import _REGISTRY
    from operator_tpu.ops.flash_prefill import _flash_prefill_pallas
    from operator_tpu.ops.paged_attention import (
        PagedKVCache,
        _paged_attention_pallas,
        _paged_attention_pallas_v2,
    )
    from operator_tpu.ops.ragged_attention import (
        UnsupportedHeadDim,
        _ragged_attention_pallas,
        kv_block_pages,
        pool_heads,
        query_tiles,
        require_ragged_kernel_support,
    )
    from operator_tpu.ops.similarity import _best_window_pallas

    b, qh, kh, d, page, pps = 4, 32, 8, 128, 16, 8
    fb, ft = 2, 256

    def paged_args(dtype):
        return (
            shaped((b, qh, d), dtype),
            shaped((b * pps, page, kh, d), dtype),
            shaped((b * pps, page, kh, d), dtype),
            shaped((b, pps), jnp.int32),
            shaped((b,), jnp.int32),
        )

    def flash_args(dtype):
        return (
            shaped((fb, ft, qh, d), dtype),
            shaped((fb, ft, kh, d), dtype),
            shaped((fb, ft, kh, d), dtype),
            shaped((fb,), jnp.int32),
        )

    def ragged_args(heads, kv_heads, head_dim, chunk, rows=4):
        pages = _MAX_SEQ // _PAGE
        # two layers, each page of the heads the pool holds for kv_heads
        pool = (2, rows * pages + 1, _PAGE, pool_heads(kv_heads), head_dim)
        return (
            shaped((rows, chunk, heads, head_dim), jnp.bfloat16),
            shaped(pool, jnp.bfloat16),
            shaped(pool, jnp.bfloat16),
            shaped((rows, pages), jnp.int32),
            shaped((rows,), jnp.int32),
            shaped((rows,), jnp.int32),
            shaped((), jnp.int32),
        )

    cases = [
        ("similarity_best_window", _best_window_pallas,
         (shaped((1000, 384), jnp.float32), shaped((300, 384), jnp.float32))),
    ]
    for dtype, tag in ((jnp.float32, "f32"), (jnp.bfloat16, "bf16")):
        cases.append((f"paged_attention_v1_{tag}",
                      _paged_attention_pallas, paged_args(dtype)))
        cases.append((f"paged_attention_v2_{tag}",
                      _paged_attention_pallas_v2, paged_args(dtype)))
        cases.append((f"flash_prefill_{tag}",
                      _flash_prefill_pallas, flash_args(dtype)))
    # the windowed variants lower DIFFERENT Mosaic code (first-block
    # computation + extra mask term): sliding-window models would hit
    # them first on-chip otherwise
    cases.append((
        "paged_attention_v2_bf16_window",
        functools.partial(_paged_attention_pallas_v2, sliding_window=64),
        paged_args(jnp.bfloat16),
    ))
    cases.append((
        "flash_prefill_bf16_window",
        functools.partial(_flash_prefill_pallas, sliding_window=128),
        flash_args(jnp.bfloat16),
    ))

    results, failed = {}, 0
    #: ragged cases: each rung's query tile, and the KV block it walks
    rungs = {}

    def ragged_case(name, fn, heads, kv_heads, head_dim, chunk, rows=4):
        """A case of the ragged kernel alone, its rungs' query tiles and
        the pages a flash update folds in at each (smallest tile first),
        by the module's own rules from the very shapes compiled."""
        args = ragged_args(heads, kv_heads, head_dim, chunk, rows=rows)
        tiles = query_tiles(chunk)
        rungs[name] = list(tiles), [
            kv_block_pages(
                tile, q_per_kv=heads // kv_heads, kv_heads=kv_heads,
                head_dim=head_dim, page_size=_PAGE,
                itemsize=args[1].dtype.itemsize,
            )
            for tile in tiles
        ]
        cases.append((name, functools.partial(fn, kv_heads=kv_heads), args))

    # ragged kernel x every registered config: compile, or a NAMED refusal
    for name, config in sorted(_REGISTRY.items()):
        if getattr(config, "latent_dims", None) is not None:
            continue  # a latent cache: the latent attention kernel's (below)
        geometry = (config.num_heads, config.num_kv_heads, config.head_dim)
        try:
            require_ragged_kernel_support(config)
        except UnsupportedHeadDim as exc:
            results[f"ragged_{name}"] = {"ok": True, "refused": str(exc)}
            print(f"REFUSED ragged_{name}: {exc}", file=sys.stderr)
            continue
        for chunk in (_SPEC_WIDTH, _CHUNK):
            fn = _ragged_attention_pallas
            if config.sliding_window is not None:
                fn = functools.partial(fn, sliding_window=config.sliding_window)
            if getattr(config, "block_length", 0):
                fn = functools.partial(fn, attend_block=config.block_length)
            ragged_case(f"ragged_{name}_c{chunk}", fn, *geometry, chunk)
    # the kernel alone at the benchmark's cells (BENCHMARK.json: heads
    # x slots, bf16 pool, page 64, chunk 64): every rung of the query tile
    # at 6, 7, 5 and 1 queries a kv head, which the 4-row cases above lower
    # too, at the slot counts the cells run, each rung with the KV block
    # its VMEM budget gives it
    for tag, heads, kv_heads, slots in (
        ("qwen2.5-1.5b", 12, 2, 128), ("qwen2.5-7b", 28, 4, 32),
        ("falcon-h1-34b", 20, 4, 128),
        # one query head a kv head: a chunk or verify row takes sixteen
        # [tile, 1, D] slabs
        ("ouro-2.6b", 16, 16, 10),
        # the hybrid's 30 heads, a page holding 32 (pool_heads)
        ("olmo-hybrid-7b", 30, 30, 48),
    ):
        ragged_case(
            f"ragged_cell_{tag}_b{slots}", _ragged_attention_pallas,
            heads, kv_heads, 128, _CHUNK, rows=slots,
        )
    # eight query heads a kv head under the block-causal mask (blocks of 4)
    ragged_case(
        "ragged_cell_sdar-30b-a3b_b128",
        functools.partial(_ragged_attention_pallas, attend_block=4),
        32, 4, 128, _CHUNK, rows=128,
    )
    # a window that actually bites inside max_seq (Mistral's 4096 is wider
    # than the serving cap, so its first-page term folds to zero above)
    ragged_case(
        "ragged_window",
        functools.partial(_ragged_attention_pallas, sliding_window=256),
        32, 8, 128, _CHUNK,
    )
    # the same window at one query head a kv head: the rung of one query
    # masks by it too
    ragged_case(
        "ragged_window_mha",
        functools.partial(_ragged_attention_pallas, sliding_window=256),
        16, 16, 128, _CHUNK,
    )

    # the state-space scan kernel alone at the benchmark's cell: 32 heads x
    # 128 x 256 float32 a slot and layer, 128 slots, 256 flat tokens
    from operator_tpu.ops.ssm_scan import _ssm_scan_pallas

    falcon = _REGISTRY["falcon-h1-34b-6l"]
    f_slots, f_tokens = 128, 256
    state_shape, _ = PagedKVCache.recurrent_shapes(falcon, f_slots)
    cases.append((
        "ssm_scan_cell_falcon-h1-34b_b128", _ssm_scan_pallas,
        (
            shaped((f_tokens, falcon.mamba_n_heads, falcon.mamba_d_head), jnp.bfloat16),
            shaped((f_tokens, falcon.mamba_n_heads), jnp.float32),
            shaped((falcon.mamba_n_heads,), jnp.float32),
            shaped((f_tokens, falcon.mamba_n_groups, falcon.mamba_d_state), jnp.bfloat16),
            shaped((f_tokens, falcon.mamba_n_groups, falcon.mamba_d_state), jnp.bfloat16),
            shaped(state_shape, jnp.float32), shaped((), jnp.int32),
            shaped((f_slots,), jnp.int32), shaped((f_slots,), jnp.int32),
            shaped((f_slots,), jnp.bool_),
        ),
    ))

    # the gated delta-rule kernel alone at the benchmark's cell: 30 heads of
    # 96 x 192 float32 held in pairs, 24 linear layers, 48 slots, 96 tokens
    from operator_tpu.ops.gated_delta import _gated_delta_pallas

    olmo = _REGISTRY["olmo-hybrid-7b"]
    o_slots, o_tokens = 48, 96
    delta_state, _ = PagedKVCache.recurrent_shapes(olmo, o_slots)
    o_heads = olmo.linear_num_value_heads
    cases.append((
        "gated_delta_cell_olmo-hybrid-7b_b48", _gated_delta_pallas,
        (
            shaped((o_tokens, o_heads, olmo.linear_key_head_dim), jnp.float32),
            shaped((o_tokens, o_heads, olmo.linear_key_head_dim), jnp.float32),
            shaped((o_tokens, o_heads, olmo.linear_value_head_dim), jnp.bfloat16),
            shaped((o_tokens, o_heads), jnp.float32), shaped((o_tokens, o_heads), jnp.float32),
            shaped(delta_state, jnp.float32), shaped((), jnp.int32),
            shaped((o_slots,), jnp.int32), shaped((o_slots,), jnp.int32),
            shaped((o_slots,), jnp.bool_),
        ),
    ))

    # the grouped expert product alone at the benchmark's cell: 1,024 flat
    # tokens routed 8 ways over a layer's 128 int8 experts of 2048 x 768,
    # the stacks whole (12 layers) and the layer a prefetched scalar
    from operator_tpu.ops.moe_experts import _moe_experts_pallas

    sdar = _REGISTRY["sdar-30b-a3b-12l"]
    m_tokens, m_top = 1024, sdar.num_experts_per_tok

    def expert_stack(rows, cols):
        lead = (sdar.num_layers, sdar.num_experts)
        return {"q": shaped((*lead, rows, cols), jnp.int8), "s": shaped((*lead, cols), jnp.float32)}

    cases.append((
        "moe_experts_cell_sdar-30b-a3b_t1024", _moe_experts_pallas,
        (
            shaped((m_tokens, sdar.hidden_size), jnp.bfloat16),
            shaped((m_tokens, m_top), jnp.int32), shaped((m_tokens, m_top), jnp.float32),
            expert_stack(sdar.hidden_size, sdar.moe_intermediate_size),
            expert_stack(sdar.hidden_size, sdar.moe_intermediate_size),
            expert_stack(sdar.moe_intermediate_size, sdar.hidden_size),
            shaped((), jnp.int32),
        ),
    ))

    # the latent attention kernel alone at the benchmark's cell: 1,024 flat
    # tokens over 128 slots of 16 heads, latent 512 and rope 64 in pages of
    # 64 over 14 planes of 4,096 pages (4.23 GB), chunk 64
    from operator_tpu.ops.latent_attention import (
        _latent_attention_pallas,
        block_pages as latent_block_pages,
        pool_shapes as latent_pool_shapes,
        query_tiles as latent_query_tiles,
    )

    deepseek = _REGISTRY["deepseek-v2-lite-14l"]
    d_slots, d_tokens, d_pages = 128, 1024, 4096
    d_latent, d_rope = latent_pool_shapes(
        deepseek.kv_planes, d_pages, _PAGE, *deepseek.latent_dims,
    )
    d_heads, d_rank = deepseek.num_heads, deepseek.kv_lora_rank
    d_name = "latent_attention_cell_deepseek-v2-lite_b128"
    rungs[d_name] = list(latent_query_tiles(_CHUNK)), [
        latent_block_pages(tile, heads=d_heads, page_size=_PAGE)
        for tile in latent_query_tiles(_CHUNK)
    ]
    cases.append((
        d_name,
        functools.partial(
            _latent_attention_pallas, scale=deepseek.softmax_scale, chunk=_CHUNK,
        ),
        (
            shaped((d_tokens, d_heads, d_rank), jnp.bfloat16),
            shaped((d_rope[-1] // deepseek.qk_rope_head_dim, d_tokens, d_heads, d_rope[-1]),
                   jnp.bfloat16),
            shaped(d_latent, jnp.bfloat16), shaped(d_rope, jnp.bfloat16),
            shaped((d_slots, _MAX_SEQ // _PAGE), jnp.int32),
            shaped((d_slots,), jnp.int32), shaped((d_slots,), jnp.int32),
            shaped((d_slots,), jnp.int32), shaped((), jnp.int32),
        ),
    ))

    # whole programs: the dispatchers must pick the kernels although the
    # HOST backend is the CPU — the compile target is the TPU topology
    from operator_tpu.ops import _dispatch

    with mock.patch.object(_dispatch, "on_tpu", lambda: True):
        pools = {}  # whole mixed steps: the stacked KV pool's shape
        for name, cell in (
            ("mixed_step_default_model", {}),
            # the 1.5B cells (BENCHMARK.json: storm and decode)
            ("mixed_step_qwen2.5-1.5b_b128", dict(
                model_id="qwen2.5-1.5b", slots=128, t_budget=256, kv_pages=3456,
            )),
            # the 7B cell (its token budget is the default: the chunk)
            ("mixed_step_qwen2.5-7b_b32", dict(
                model_id="qwen2.5-7b", slots=32, kv_pages=800,
            )),
            ("mixed_step_falcon-h1-34b-6l_b128", dict(
                model_id="falcon-h1-34b-6l", slots=f_slots, t_budget=f_tokens,
                kv_pages=1536, spec_width=1,
            )),
            # the looped model's cell: 4 passes x 48 layers = 192 planes
            # of 112 pages, 11.3 GB, through both loops and held once
            ("mixed_step_ouro-2.6b_b10", dict(
                model_id="ouro-2.6b", slots=10, kv_pages=112,
            )),
            # the sparse-expert cell: 12 layers of 128 int8 experts (7.25
            # GB), 128 slots of two blocks of 4, the denoising tail, the
            # pool at its worst case (AOT_SDAR_KV_PAGES sizes another)
            ("mixed_step_sdar-30b-a3b-12l_b128", dict(
                model_id="sdar-30b-a3b-12l", slots=128, t_budget=m_tokens,
                kv_pages=int(os.environ.get("AOT_SDAR_KV_PAGES", "4096")),
                spec_width=1,
            )),
            # the hybrid's cell: a period a scan step (3 delta-rule layers,
            # 1 full), 8 KV planes of 544 pages, 2.55 GB of state in 24 layers
            ("mixed_step_olmo-hybrid-7b_b48", dict(
                model_id="olmo-hybrid-7b", slots=o_slots, t_budget=o_tokens,
                kv_pages=544,
                spec_width=1,
            )),
            # the latent-attention cell: one dense layer ahead of the loop,
            # 13 expert layers of 64 int8 experts scanned, a latent pool of
            # 14 planes x 4,096 pages (4.23 GB, no lane padded)
            ("mixed_step_deepseek-v2-lite-14l_b128", dict(
                model_id="deepseek-v2-lite-14l", slots=d_slots, t_budget=d_tokens,
                kv_pages=d_pages, spec_width=1,
            )),
        ):
            fn, args, pools[name] = _mixed_step_case(topo.devices[0], **cell)
            cases.append((name, fn, args))
        if len(topo.devices) >= 4:
            cases.append(("mesh_tp4_paged_decode", *_mesh_decode_case(topo.devices[:4])))

        only = os.environ.get("AOT_TPU_ONLY")  # a substring: those cases alone
        for name, fn, args in cases:
            if only and only not in name:
                continue
            try:
                # a jitted step is lowered as it is called: a second
                # jax.jit around it would drop its donation
                lowered = fn.lower(*args) if hasattr(fn, "lower") else jax.jit(fn).lower(*args)
                compiled = lowered.compile()
                results[name] = {
                    "ok": True, **_memory(compiled),
                    # the instruction names a profiler trace of the chip
                    # prints for the program's Pallas kernels
                    "pallas_calls": sorted(set(re.findall(
                        r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
                        compiled.as_text(),
                    ))),
                }
                if name in rungs:
                    (results[name]["query_tiles"],
                     results[name]["kv_block_pages"]) = rungs[name]
                if name in pools:
                    shape, second = pools[name]
                    # the tail's one conditional (sched/mixed.py: the head
                    # and the sampler at one row a slot, or at the verify
                    # width); a step compiled at width 1 has none
                    results[name]["conditionals"] = len(re.findall(
                        r" conditional\(", compiled.as_text()
                    ))
                    results[name]["kv_pool"] = {
                        "shape": list(shape),
                        # K and V, or a latent pool's latent and rope rows: bf16
                        "bytes": 2 * (math.prod(shape) + math.prod(second)),
                        "moved_by": sorted(set(
                            _pool_moves(compiled.as_text(), shape)
                            + _pool_moves(compiled.as_text(), second)
                        )),
                    }
                    if second != shape:
                        results[name]["kv_pool"]["rope_shape"] = list(second)
                    results[name]["weight_moves"] = _weight_moves(
                        compiled.as_text(), args[0]
                    )
                print(f"OK   {name}", file=sys.stderr)
            except Exception as exc:  # noqa: BLE001 - record and continue
                failed += 1
                results[name] = {
                    "ok": False, "error": f"{type(exc).__name__}: {exc}"[:400],
                }
                print(f"FAIL {name}: {exc}", file=sys.stderr)
    print(json.dumps({
        "metric": "aot_tpu_kernel_compile",
        "topology": TOPOLOGY,
        "device_kind": topo.devices[0].device_kind,
        "kernels": results,
        "failed": failed,
    }))
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
