"""Continuous-batching scheduler (serving/sched/) + the ragged
mixed-phase kernel (ops/ragged_attention.py).

Covers the ISSUE 7 acceptance surface: ragged-kernel parity against the
dense reference (prefill-only / decode-only / mixed rows, interpret
mode), greedy parity of the mixed program against the wave engine,
token-level admission into a RUNNING wave, per-token slot+page recycling
with a leak audit, the seeded engine-stall chaos scenario under the new
loop (supervisor requeue, replayed byte-identically twice), and schedule
determinism for a fixed arrival trace.
"""

import asyncio
import dataclasses
import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from operator_tpu.models import TINY_TEST, init_params  # noqa: E402
from operator_tpu.models.tokenizer import ByteTokenizer  # noqa: E402
from operator_tpu.ops.ragged_attention import (  # noqa: E402
    _ragged_attention_pallas,
    ragged_attention_reference,
)
from operator_tpu.serving.engine import (  # noqa: E402
    BatchedGenerator,
    OversizedRequest,
    SamplingParams,
    ServingEngine,
    SupervisorPolicy,
)
from operator_tpu.serving.sched import Scheduler  # noqa: E402
from operator_tpu.utils.timing import MetricsRegistry  # noqa: E402


@pytest.fixture(scope="module")
def params():
    return init_params(TINY_TEST, jax.random.PRNGKey(0), dtype=jnp.float32)


def make_generator(params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", 128)
    kw.setdefault("page_size", 16)
    return BatchedGenerator(
        params, TINY_TEST, ByteTokenizer(), paged=True,
        cache_dtype=jnp.float32, metrics=MetricsRegistry(), **kw,
    )


def drain(sched, want, limit=300):
    """Step until ``want`` requests finished; returns {req_id: outcome}."""
    done = {}
    for _ in range(limit):
        for outcome in sched.step():
            done[outcome.req_id] = outcome
        if len(done) >= want:
            return done
    raise AssertionError(f"only {len(done)}/{want} finished in {limit} steps")


def assert_no_leaks(generator):
    assert len(generator.free_slots()) == generator.max_slots
    assert generator.allocator.available == generator.allocator.num_pages - 1


# ---------------------------------------------------------------------------
# ragged kernel parity (interpret mode vs dense reference)
# ---------------------------------------------------------------------------


class TestRaggedKernel:
    #: the stacked pool's layers, every one with contents of its own
    LAYERS = 3

    def _setup(self, rng, b=4, c=8, qh=4, kh=2, d=16, ps=8, pps=6):
        num_pages = b * pps + 1
        k_pages = jnp.asarray(
            rng.normal(size=(self.LAYERS, num_pages, ps, kh, d)), jnp.float32
        )
        v_pages = jnp.asarray(
            rng.normal(size=(self.LAYERS, num_pages, ps, kh, d)), jnp.float32
        )
        table = np.zeros((b, pps), np.int32)
        free = list(range(1, num_pages))
        for row in range(b):
            for j in range(pps):
                table[row, j] = free.pop(0)
        q = jnp.asarray(rng.normal(size=(b, c, qh, d)), jnp.float32)
        return q, k_pages, v_pages, jnp.asarray(table)

    def _check(self, q, k_pages, v_pages, table, kv_len, q_count, window=None,
               layer=1):
        layer = jnp.int32(layer)
        ref = ragged_attention_reference(
            q, k_pages, v_pages, table, kv_len, q_count, layer,
            sliding_window=window,
        )
        got = _ragged_attention_pallas(
            q, k_pages, v_pages, table, kv_len, q_count, layer,
            interpret=True, sliding_window=window,
        )
        for row in range(q.shape[0]):
            n = int(q_count[row])
            if n == 0:
                continue  # padding rows are garbage in both by contract
            np.testing.assert_allclose(
                np.asarray(got[row, :n]), np.asarray(ref[row, :n]),
                rtol=2e-5, atol=2e-5,
            )

    #: chunk 16 over the small tile of 8: both rungs, with q_count at
    #: their edges (0, 1, 5, small, small + 1, chunk) and idle slots
    #: between the live ones
    _EDGES = dict(
        b=8, c=16, q_count=[0, 1, 0, 5, 8, 0, 9, 16],
        kv_len=[12, 33, 0, 21, 8, 40, 30, 48],
    )

    #: pages of 16 keys, 8 a row, so that a row's walk ends at a block's
    #: edges on BOTH rungs with idle slots between (slots 1, 4, 8, 11):
    #: at 4 pages a block, rows of 1 page (slot 0: one key in it), of
    #: exactly N (3, 5), of N + 1 with one key on the last page (6, 7) and
    #: of 2N - 1 (9, 10); slots 0, 3, 6, 9 take the small tile
    _BLOCK_ROWS = dict(
        b=12, c=16, ps=16, pps=8, qh=4, kh=2,
        q_count=[1, 0, 16, 5, 0, 16, 8, 16, 0, 1, 9, 0],
        kv_len=[1, 40, 16, 64, 0, 64, 65, 65, 30, 100, 112, 7],
    )
    #: the same at 2 pages a block: 1, N (32 keys), N + 1 = 2N - 1 (33, 48)
    _BLOCK_ROWS_2 = dict(
        _BLOCK_ROWS, kv_len=[1, 40, 16, 32, 0, 32, 33, 33, 30, 48, 48, 7],
    )

    @pytest.mark.parametrize("case", [
        dict(id="edges-4x2", qh=4, kh=2, **_EDGES),
        # group sizes that are no power of two: 6 and 7 queries a kv head
        dict(id="edges-6x1", qh=6, kh=1, **_EDGES),
        dict(id="edges-7x1", qh=7, kh=1, **_EDGES),
        dict(id="all-idle", qh=4, kh=2, b=4, c=16, q_count=[0] * 4,
             kv_len=[9, 0, 17, 3]),
        dict(id="bf16-pool", qh=6, kh=1, dtype=jnp.bfloat16, **_EDGES),
        # the window bites on small-tile rows (decode and verify) and on
        # a chunk, several pages back
        dict(id="window-small-tile", qh=4, kh=2, b=4, c=16, window=7,
             q_count=[1, 5, 0, 16], kv_len=[41, 30, 12, 37]),
        # the first, a middle and the last layer of the stacked pool: a
        # kernel that ignored ``layer`` would read another layer's pages
        dict(id="layer-first", qh=4, kh=2, layer=0, **_EDGES),
        dict(id="layer-middle", qh=4, kh=2, layer=1, **_EDGES),
        dict(id="layer-last", qh=4, kh=2, layer=2, **_EDGES),
        # the KV block's edges, on both rungs (blocks: pages a flash
        # update folds in, small tile then chunk)
        dict(id="block-edges-4x4", blocks=(4, 4), **_BLOCK_ROWS),
        dict(id="block-edges-2x2", blocks=(2, 2), **_BLOCK_ROWS_2),
        # each rung its own block; 7 pages are one partial block of 8
        dict(id="block-edges-8x2", blocks=(8, 2), **_BLOCK_ROWS),
        # a partial last block is folded at the narrowest width that
        # holds it: rows of 2, 3, 6 and 8 pages at 8 a block fold 2, 4, 8
        # and a whole block, on both rungs
        dict(id="block-last-widths", blocks=(8, 8), b=8, c=16, ps=16, pps=8,
             qh=4, kh=2, q_count=[1, 16, 1, 16, 5, 16, 8, 9],
             kv_len=[32, 30, 40, 48, 90, 96, 128, 128]),
        dict(id="block-bf16-pool", blocks=(4, 2), dtype=jnp.bfloat16,
             **_BLOCK_ROWS),
        # windows whose first page (5, 5 and 3) is no multiple of the
        # block: the walk starts there, not at the block's edge below
        dict(id="block-window-off-edge", blocks=(2, 2), window=20,
             b=4, c=16, ps=16, pps=8, qh=4, kh=2,
             q_count=[1, 16, 0, 5], kv_len=[100, 120, 50, 77]),
        dict(id="block-window-off-edge-4", blocks=(4, 4), window=20,
             b=4, c=16, ps=16, pps=8, qh=4, kh=2,
             q_count=[1, 16, 0, 5], kv_len=[100, 120, 50, 77]),
        # NaN in every page no row's table names and in the keys past
        # kv_len of every last page: a probability of 0 times what an
        # unfetched or dead buffer row holds must not reach the output
        dict(id="block-nan-poison", blocks=(4, 4), poison=True, **_BLOCK_ROWS),
        dict(id="block-nan-poison-1", blocks=(1, 1), poison=True, **_BLOCK_ROWS),
        # one page a turn and four serve the same rows alike
        dict(id="block-1-and-4-agree", blocks=(4, 4), agree_with=(1, 1),
             **_BLOCK_ROWS),
        # pages that hold more heads than the model has (ten held as
        # sixteen: ragged_attention.pool_heads), NaN in the six past them:
        # one query head a kv head, and two
        dict(id="heads-held-wider", qh=10, kh=10, held=16, **_EDGES),
        dict(_BLOCK_ROWS, id="heads-held-wider-gqa", qh=20, kh=10, held=16,
             blocks=(2, 2)),
    ], ids=lambda case: case["id"])
    def test_parity_by_rung(self, case):
        """The kernel against the reference where what it works follows
        ``q_count``: no slot's output depends on the tile it was given,
        and a slot without queries leaves the others alone."""
        from operator_tpu.ops.ragged_attention import SMALL_TILE, query_tile_rows

        dtype = case.get("dtype", jnp.float32)
        rng = np.random.default_rng(len(case["id"]))
        ps, pps = case.get("ps", 8), case.get("pps", 6)
        q, k, v, table = self._setup(
            rng, b=case["b"], c=case["c"], qh=case["qh"], kh=case["kh"],
            ps=ps, pps=pps,
        )
        q, k, v = (x.astype(dtype) for x in (q, k, v))
        kv_heads = case["kh"]
        if "held" in case:
            wide = ((0, 0),) * 3 + ((0, case["held"] - kv_heads), (0, 0))
            k, v = (jnp.pad(x, wide, constant_values=jnp.nan) for x in (k, v))
        kv_len = jnp.asarray(case["kv_len"], jnp.int32)
        q_count = jnp.asarray(case["q_count"], jnp.int32)
        assert max(case["kv_len"]) <= ps * pps
        tiles = set(query_tile_rows(np.asarray(q_count), case["c"]).tolist())
        assert tiles <= {0, SMALL_TILE, case["c"]}
        if case["id"].startswith("edges"):
            assert tiles == {0, SMALL_TILE, case["c"]}
        window = case.get("window")
        layer = case.get("layer", self.LAYERS - 1)
        given = (k, v)
        if case.get("poison"):
            given = self._poisoned(k, v, table, case["kv_len"], ps)
        run = functools.partial(
            _ragged_attention_pallas, q, *given, table, kv_len, q_count,
            jnp.int32(layer), interpret=True, sliding_window=window,
            kv_heads=kv_heads,
        )
        got = run(block_pages=case.get("blocks"))
        assert got.shape == q.shape and got.dtype == dtype
        if "agree_with" in case:
            other = run(block_pages=case["agree_with"])
            for row, n in enumerate(case["q_count"]):
                np.testing.assert_allclose(
                    np.asarray(got[row, :n]), np.asarray(other[row, :n]),
                    rtol=2e-6, atol=2e-6,
                )
        # the oracle is handed the one layer alone, as a pool of one: it
        # cannot read another layer's pages whatever it does with ``layer``
        want = ragged_attention_reference(
            q.astype(jnp.float32),
            *(x[layer][None].astype(jnp.float32) for x in (k, v)),
            table, kv_len, q_count, jnp.int32(0), sliding_window=window,
            kv_heads=kv_heads,
        )
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        others = [
            ragged_attention_reference(
                *(x.astype(jnp.float32) for x in (q, k, v)), table, kv_len,
                q_count, jnp.int32(other), sliding_window=window,
                kv_heads=kv_heads,
            )
            for other in range(self.LAYERS)
        ]
        for row, n in enumerate(case["q_count"]):
            np.testing.assert_allclose(
                np.asarray(got[row, :n], np.float32), np.asarray(want[row, :n]),
                rtol=tol, atol=tol,
            )
            # the reference indexes the same layer, and no other layer's
            # pages would have given this answer
            np.testing.assert_array_equal(
                np.asarray(others[layer][row, :n]), np.asarray(want[row, :n])
            )
            for other in set(range(self.LAYERS)) - {layer}:
                if n:
                    assert np.abs(
                        np.asarray(others[other][row, :n] - want[row, :n])
                    ).max() > 1e-2, (row, other)

    @staticmethod
    def _poisoned(k, v, table, kv_len, ps):
        """The pools with NaN wherever no row may look: every page no
        row's table names up to its ``kv_len``, and the keys past
        ``kv_len`` of each row's last page."""
        dead = np.ones(k.shape[1:3], bool)  # [page, key]
        for row, kv in enumerate(kv_len):
            live = -(-kv // ps)
            dead[np.asarray(table)[row, :live]] = False
            if kv % ps:
                dead[int(table[row, live - 1]), kv % ps:] = True
        bad = jnp.asarray(dead)[None, :, :, None, None]
        assert bool(bad.any())
        return tuple(jnp.where(bad, jnp.nan, x) for x in (k, v))

    def test_both_rungs_serve_the_same_queries_alike(self):
        """One row's last five queries, once as the tail of a 9-query row
        (the chunk's tile) and once as a 5-query row padded out to the
        chunk (the small tile), over the same pages: equal outputs."""
        rng = np.random.default_rng(7)
        q, k, v, table = self._setup(rng, b=2, c=16)
        table = jnp.stack([table[0], table[0]])  # both slots read one row's pages
        q = q.at[1, :5].set(q[0, 4:9])
        kv_len = jnp.asarray([29, 29], jnp.int32)
        q_count = jnp.asarray([9, 5], jnp.int32)
        got = _ragged_attention_pallas(
            q, k, v, table, kv_len, q_count, jnp.int32(1), interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(got[1, :5]), np.asarray(got[0, 4:9]), rtol=2e-6, atol=2e-6
        )
        self._check(q, k, v, table, kv_len, q_count)

    def test_prefill_only_rows(self):
        rng = np.random.default_rng(0)
        q, k, v, table = self._setup(rng)
        # whole-prompt prefill: kv_len == q_count (q positions 0..n-1)
        kv_len = jnp.asarray([8, 5, 8, 3], jnp.int32)
        q_count = kv_len
        self._check(q, k, v, table, kv_len, q_count)

    def test_decode_only_rows(self):
        rng = np.random.default_rng(1)
        q, k, v, table = self._setup(rng)
        kv_len = jnp.asarray([17, 30, 9, 1], jnp.int32)
        q_count = jnp.asarray([1, 1, 1, 1], jnp.int32)
        self._check(q, k, v, table, kv_len, q_count)

    def test_mixed_rows(self):
        """One wave: a decode row, a mid-prompt chunk, a whole-prompt
        prefill, and an inactive row — the shape the scheduler
        dispatches every step."""
        rng = np.random.default_rng(2)
        q, k, v, table = self._setup(rng)
        kv_len = jnp.asarray([17, 20, 8, 0], jnp.int32)
        q_count = jnp.asarray([1, 6, 8, 0], jnp.int32)
        self._check(q, k, v, table, kv_len, q_count)

    def test_mixed_rows_sliding_window(self):
        rng = np.random.default_rng(3)
        q, k, v, table = self._setup(rng)
        kv_len = jnp.asarray([33, 20, 8, 12], jnp.int32)
        q_count = jnp.asarray([1, 6, 8, 1], jnp.int32)
        self._check(q, k, v, table, kv_len, q_count, window=7)

    def test_decode_matches_paged_attention_kernel_semantics(self):
        """A q_count==1 ragged row must equal the dedicated decode
        kernel's oracle for the same cache — decode really is the
        special case of the one program."""
        from operator_tpu.ops.paged_attention import paged_attention_reference

        rng = np.random.default_rng(4)
        q, k, v, table = self._setup(rng)
        kv_len = jnp.asarray([17, 30, 9, 2], jnp.int32)
        q_count = jnp.asarray([1, 1, 1, 1], jnp.int32)
        ragged = ragged_attention_reference(
            q, k, v, table, kv_len, q_count, jnp.int32(2)
        )
        decode = paged_attention_reference(q[:, 0], k[2], v[2], table, kv_len)
        np.testing.assert_allclose(
            np.asarray(ragged[:, 0]), np.asarray(decode), rtol=2e-5, atol=2e-5
        )


# ---------------------------------------------------------------------------
# the mixed step: the pools on the layer loop's carry, written in place
# ---------------------------------------------------------------------------


def step_built_the_old_way(generator, params, paged, step):
    """One mixed step as it was built before the pools rode the carry: a
    loop over the layers in the test, each cutting its pages out of the
    pool, scattering the step's K/V into that slice, attending over the
    slice alone and stacking it back.  Returns the greedy tokens, the
    pools and every layer's K/V as written (after RoPE)."""
    from operator_tpu.models import family_of
    from operator_tpu.models.llama import apply_rope, rms_norm, rope_frequencies
    from operator_tpu.serving.sched.mixed import StepView

    config, inv_freq = generator.config, rope_frequencies(generator.config)
    t, chunk, page = step["ids"].shape[0], step["chunk"], paged.page_size
    rows, pos, valid = step["rows"], step["pos"], step["valid"]
    pack_idx = jnp.clip(
        step["q_start"][:, None] + jnp.arange(chunk)[None], 0, t - 1
    )
    page_ids = jnp.where(valid, paged.page_table[rows, pos // page], 0)
    page_slots = jnp.where(valid, pos % page, 0)
    written = []

    def attend(q, k, v, pools, layer):
        q = apply_rope(q.reshape(1, t, config.num_heads, -1), pos[None], inv_freq)
        k = apply_rope(k.reshape(1, t, config.num_kv_heads, -1), pos[None], inv_freq)
        v = v.reshape(1, t, config.num_kv_heads, -1)
        written.append((np.asarray(k[0]), np.asarray(v[0])))
        k_layer = pools["k"][layer].at[page_ids, page_slots].set(k[0])
        v_layer = pools["v"][layer].at[page_ids, page_slots].set(v[0])
        attn_pack = ragged_attention_reference(
            q[0][pack_idx], k_layer[None], v_layer[None], paged.page_table,
            step["kv_len"], step["q_count"], jnp.int32(0),
        )
        attn = jnp.where(valid[:, None, None], attn_pack[rows, step["in_row"]], 0)
        return attn.reshape(1, t, -1), {
            "k": pools["k"].at[layer].set(k_layer),
            "v": pools["v"].at[layer].set(v_layer),
        }

    layer_step = family_of(config).mixed_layer(config, StepView(
        t_budget=t, chunk=chunk, rows=rows, in_row=step["in_row"], pos=pos,
        valid=valid, q_start=step["q_start"], q_count=step["q_count"],
        attend=attend,
    ))
    x = jnp.take(params["embed"], step["ids"], axis=0)[None]
    x = x * getattr(config, "embedding_multiplier", 1.0)
    recurrent = None
    if paged.ssm_state is not None:
        recurrent = {"ssm": paged.ssm_state, "conv": paged.conv_state}
    carry = (x, {"k": paged.k_pages, "v": paged.v_pages}, recurrent)
    for layer in range(config.num_layers):
        carry, _ = layer_step(carry, {
            "w": jax.tree_util.tree_map(lambda leaf: leaf[layer], params["layers"]),
            "layer": jnp.int32(layer),
        })
    x, pools, _ = carry
    x = rms_norm(x, params["ln_final"], config.rms_norm_eps)
    head = params["embed"].T if config.tie_embeddings else params["lm_head"]
    last = x[0][step["q_start"] + step["q_count"] - 1]
    return np.asarray(jnp.argmax(last @ head, axis=-1)), pools, written


class TestMixedStepPool:
    @pytest.mark.parametrize("name", ["tiny-test", "tiny-falcon-h1"])
    def test_a_step_writes_each_layers_rows_in_place_and_nothing_else(self, name):
        """One step of a three-layer model over a pool full of noise: a
        decode row, a prefill chunk across a page boundary, an idle slot,
        a fresh prompt and six padding tokens.  Afterwards every layer
        holds ITS K/V at the step's ``(page, slot)``s, every other entry
        of both pools is the bit it was (the trash page excepted), and the
        tokens are those of the step built the old way."""
        from operator_tpu.models import family_of, get_config
        from operator_tpu.serving.sched.mixed import make_mixed_fn

        config = get_config(name)
        weights = family_of(config).init_params(
            config, jax.random.PRNGKey(0), dtype=jnp.float32
        )
        generator = BatchedGenerator(
            weights, config, ByteTokenizer(), paged=True, max_slots=4,
            max_seq=128, page_size=16, cache_dtype=jnp.float32,
            metrics=MetricsRegistry(),
        )
        rng = np.random.default_rng(3)
        paged = generator.paged_cache
        noise = {
            field.name: jnp.asarray(
                rng.normal(size=getattr(paged, field.name).shape), jnp.float32
            )
            for field in dataclasses.fields(paged)
            if field.name.endswith(("_pages", "_state"))
            and getattr(paged, field.name) is not None
        }
        pages_per_seq = paged.page_table.shape[1]
        paged = dataclasses.replace(
            paged, **noise,
            page_table=1 + jnp.arange(4 * pages_per_seq, dtype=jnp.int32).reshape(4, -1),
            lengths=jnp.asarray([20, 13, 9, 0], jnp.int32),
        )
        assert paged.k_pages.shape[:2] == (3, 4 * pages_per_seq + 1)
        t_budget, chunk = 16, 8
        q_count = np.asarray([1, 6, 0, 3], np.int32)  # decode, chunk, idle, fresh
        first = np.asarray([20, 13, 0, 0], np.int32)  # 13..18 crosses a page
        q_start = np.asarray([0, 1, 0, 7], np.int32)
        live = int(q_count.sum())
        rows = np.zeros(t_budget, np.int32)
        rows[:live] = np.repeat(np.arange(4), q_count)
        in_row = np.zeros(t_budget, np.int32)
        in_row[:live] = np.concatenate([np.arange(n) for n in q_count])
        valid = np.arange(t_budget) < live
        step = {
            "chunk": chunk,
            "ids": jnp.asarray(rng.integers(0, config.vocab_size, t_budget), jnp.int32),
            "rows": jnp.asarray(rows), "in_row": jnp.asarray(in_row),
            "pos": jnp.asarray(np.where(valid, first[rows] + in_row, 0), jnp.int32),
            "valid": jnp.asarray(valid),
            "q_start": jnp.asarray(q_start), "q_count": jnp.asarray(q_count),
            "kv_len": jnp.asarray(np.where(q_count > 0, first + q_count, [0, 0, 9, 0]), jnp.int32),
        }
        # the step donates the cache: what is compared is copied out first
        before = {key: np.asarray(getattr(paged, key)) for key in ("k_pages", "v_pages")}
        table = np.asarray(paged.page_table)
        want_toks, want_pools, written = step_built_the_old_way(
            generator, weights, paged, step
        )
        zeros = jnp.zeros((4,), jnp.int32)
        new_paged, toks, _, _, _ = make_mixed_fn(generator, t_budget, chunk)(
            weights, paged, step["ids"], step["rows"], step["pos"], step["valid"],
            step["in_row"], step["q_start"], step["q_count"], step["kv_len"],
            zeros, jnp.zeros((t_budget,), bool),
            step["q_start"] + step["q_count"] - 1, zeros,
            jax.random.PRNGKey(0), jnp.zeros((4,), jnp.float32),
            jnp.ones((4,), jnp.float32),
        )
        scheduled = q_count > 0
        assert np.asarray(toks)[scheduled, 0].tolist() == want_toks[scheduled].tolist()
        page_of = table[rows, np.asarray(step["pos"]) // 16][:live]
        slot_of = (np.asarray(step["pos"]) % 16)[:live]
        assert len(set(page_of.tolist())) == 4  # four pages written, none the trash page
        for which, key in enumerate(("k_pages", "v_pages")):
            got = np.asarray(getattr(new_paged, key))
            for layer in range(config.num_layers):
                np.testing.assert_allclose(
                    got[layer, page_of, slot_of], written[layer][which][:live],
                    rtol=1e-5, atol=1e-5,
                )
                if layer:  # and not the layer before's
                    assert np.abs(
                        got[layer, page_of, slot_of] - written[layer - 1][which][:live]
                    ).max() > 1e-2
            untouched = np.ones(got.shape[:3], bool)
            untouched[:, page_of, slot_of] = False
            untouched[:, 0] = False  # the trash page takes the padding tokens
            np.testing.assert_array_equal(got[untouched], before[key][untouched])
            assert untouched.sum() == got[..., 0, 0].size - 3 * (live + 16)
            np.testing.assert_allclose(
                got[:, 1:], np.asarray(want_pools[key[0]])[:, 1:], rtol=1e-5, atol=1e-5
            )
        assert np.asarray(new_paged.lengths).tolist() == np.asarray(step["kv_len"]).tolist()


# ---------------------------------------------------------------------------
# scheduler: parity, admission, recycling
# ---------------------------------------------------------------------------


class TestSchedulerParity:
    def test_greedy_matches_wave_engine(self, params):
        prompt = "pod crashed with exit code 137"
        sampling = SamplingParams(max_tokens=8, temperature=0.0)
        wave = make_generator(params).generate(prompt, sampling)

        generator = make_generator(params)
        sched = Scheduler(generator, chunk=16, token_budget=32)
        req_id = sched.enqueue(prompt, sampling)
        outcome = drain(sched, 1)[req_id]
        assert outcome.error is None
        assert outcome.result.token_ids == wave.token_ids
        assert outcome.result.prompt_tokens == wave.prompt_tokens
        assert_no_leaks(generator)

    def test_cobatched_mixed_wave_matches_solo(self, params):
        """Rows co-batched at DIFFERENT phases (one decoding, one
        chunk-prefilling) must each produce their solo greedy tokens —
        the ragged program's cross-row isolation proof."""
        prompts = [
            "pod crashed with exit code 137",
            "a much longer prompt " * 8,  # chunked over several steps
            "OOMKilled",
        ]
        sampling = SamplingParams(max_tokens=6, temperature=0.0)
        solo = {}
        for prompt in prompts:
            solo[prompt] = make_generator(params).generate(
                prompt, sampling
            ).token_ids

        generator = make_generator(params)
        sched = Scheduler(generator, chunk=16, token_budget=32)
        ids = {sched.enqueue(p, sampling): p for p in prompts}
        done = drain(sched, len(prompts))
        for req_id, prompt in ids.items():
            assert done[req_id].result.token_ids == solo[prompt], prompt
        assert_no_leaks(generator)


class TestTokenLevelAdmission:
    def test_admitted_into_running_wave(self, params):
        """A request queued while another row is mid-generation joins at
        the NEXT step — no block boundary, no wave drain."""
        generator = make_generator(params)
        sched = Scheduler(generator, chunk=16, token_budget=32)
        sampling = SamplingParams(max_tokens=12, temperature=0.0,
                                  stop_on_eos=False)
        first = sched.enqueue("long running request " * 4, sampling)
        for _ in range(4):
            sched.step()
        assert sched.num_active == 1  # first is mid-generation
        mid = sched.enqueue("late arrival", sampling)
        sched.step()
        assert sched.num_active == 2  # joined the RUNNING wave
        assert generator.metrics.counter("sched_admitted_midwave") == 1
        done = drain(sched, 2)
        assert done[first].error is None and done[mid].error is None
        assert_no_leaks(generator)

    def test_chunked_prefill_never_starves_decodes(self, params):
        """While a long prompt chunk-prefills, decoding rows get a token
        EVERY step (zero stall steps) — the Sarathi property, asserted
        end to end."""
        generator = make_generator(params, max_seq=256)
        sched = Scheduler(generator, chunk=16, token_budget=32)
        short = sched.enqueue(
            "short", SamplingParams(max_tokens=20, temperature=0.0,
                                    stop_on_eos=False),
        )
        sched.step()  # short is decoding now
        long_prompt = "a very long prompt that needs many chunks " * 4
        long = sched.enqueue(
            long_prompt, SamplingParams(max_tokens=4, temperature=0.0,
                                        stop_on_eos=False),
        )
        done = drain(sched, 2)
        assert sched.stall_steps == 0
        assert generator.metrics.counter("sched_stall_step") == 0
        assert generator.metrics.counter("sched_chunked_prefill") >= 1
        assert generator.metrics.counter("sched_stall_free_step") == sched.steps
        assert done[short].result.completion_tokens == 20
        assert done[long].result.completion_tokens == 4
        assert_no_leaks(generator)


class TestPerTokenRecycling:
    def test_finished_row_recycles_slot_and_pages_immediately(self, params):
        """When a row hits its token budget, its slot AND pages are free
        for the very next step's admission — not decode_block-1 junk
        tokens later."""
        generator = make_generator(params)
        sched = Scheduler(generator, chunk=16, token_budget=32)
        available_before = generator.allocator.available
        sampling = SamplingParams(max_tokens=2, temperature=0.0,
                                  stop_on_eos=False)
        first = sched.enqueue("finishes fast", sampling)
        done = drain(sched, 1)
        assert done[first].result.completion_tokens == 2
        # the moment the outcome is returned, everything is back
        assert generator.allocator.available == available_before
        assert len(generator.free_slots()) == generator.max_slots
        assert generator.metrics.counter("sched_recycled_slot") == 1

    def test_freed_capacity_admits_backpressured_request_next_step(self, params):
        """Queue more work than the pool can hold: the backpressured
        request must be admitted on the first step after a finishing row
        releases its pages (per-token recycling feeds admission)."""
        # page pool sized so only ONE request fits at a time
        generator = make_generator(
            params, max_slots=2, kv_pages=6, page_size=16, max_seq=96
        )
        sched = Scheduler(generator, chunk=16, token_budget=32)
        sampling = SamplingParams(max_tokens=3, temperature=0.0,
                                  stop_on_eos=False)
        hog = sched.enqueue("a prompt that hogs the kv pool " * 2, sampling)
        sched.step()
        waiter = sched.enqueue("waits for pages", sampling)
        assert sched.queue_depth == 1  # backpressured, not dropped
        done = drain(sched, 2)
        assert done[hog].error is None and done[waiter].error is None
        assert_no_leaks(generator)

    def test_cancel_live_row_reclaims_now(self, params):
        generator = make_generator(params)
        sched = Scheduler(generator, chunk=16, token_budget=32)
        req = sched.enqueue(
            "cancelled mid-flight",
            SamplingParams(max_tokens=50, temperature=0.0, stop_on_eos=False),
        )
        sched.step()
        sched.step()
        assert sched.num_active == 1
        assert sched.cancel(req) is True
        assert sched.num_active == 0
        assert_no_leaks(generator)

    def test_oversized_request_refused_at_enqueue(self, params):
        generator = make_generator(
            params, max_slots=2, kv_pages=3, page_size=16, max_seq=96
        )
        sched = Scheduler(generator, chunk=16, token_budget=32)
        with pytest.raises(OversizedRequest):
            sched.enqueue(
                "x" * 300,
                SamplingParams(max_tokens=64, temperature=0.0),
            )


class TestEDFAdmission:
    def test_urgent_late_arrival_overtakes_slack_earlier_request(self, params):
        """Queue order under pressure: a later arrival with a tight
        deadline (and a higher-priority class) is admitted before an
        earlier deadline-free request — and the slack request still
        completes afterwards (no starvation, no skip-ahead drop)."""
        generator = make_generator(params, max_slots=1)
        sched = Scheduler(generator, chunk=16, token_budget=32)
        sampling = SamplingParams(max_tokens=3, temperature=0.0,
                                  stop_on_eos=False)
        hog = sched.enqueue("holds the only slot", sampling)
        sched.step()  # hog occupies the slot; everything below queues
        slack = sched.enqueue("queued first, no deadline", sampling)
        tight = sched.enqueue(
            "queued later, tight deadline",
            SamplingParams(max_tokens=3, temperature=0.0, stop_on_eos=False,
                           deadline=generator._clock() + 60.0),
        )
        urgent = sched.enqueue("priority class beats deadline", sampling,
                               priority=10)
        assert sched.queue_depth == 3
        order: list[int] = []
        done = {}
        for _ in range(300):
            for outcome in sched.step():
                order.append(outcome.req_id)
                done[outcome.req_id] = outcome
            if len(done) == 4:
                break
        # one slot -> completion order IS admission order
        assert order == [hog, urgent, tight, slack]
        assert all(o.error is None for o in done.values())
        assert_no_leaks(generator)

    def test_fifo_among_deadline_free_peers(self, params):
        """Without deadlines or priorities the EDF head degenerates to
        FIFO — the plan-determinism contract existing traces rely on."""
        generator = make_generator(params, max_slots=1)
        sched = Scheduler(generator, chunk=16, token_budget=32)
        sampling = SamplingParams(max_tokens=2, temperature=0.0,
                                  stop_on_eos=False)
        ids = [sched.enqueue(f"request {i}", sampling) for i in range(3)]
        order: list[int] = []
        for _ in range(300):
            for outcome in sched.step():
                order.append(outcome.req_id)
            if len(order) == 3:
                break
        assert order == ids


# ---------------------------------------------------------------------------
# value-aware overload: queue eviction, admission ladder, degraded finish
# ---------------------------------------------------------------------------


from operator_tpu.router.value import OverloadPolicy, ValueModel  # noqa: E402
from operator_tpu.serving.types import ShedLowValue  # noqa: E402

SLO_CLASSES = {"interactive": 2.0, "standard": 30.0, "batch": 120.0}


def make_policy(**kw):
    model = ValueModel(SLO_CLASSES, attainment=kw.pop("attainment", None))
    kw.setdefault("shed_pressure", 8.0)
    return OverloadPolicy(model, **kw)


class TestValueEviction:
    def test_full_queue_evicts_lowest_value_for_higher_value_arrival(
        self, params
    ):
        """Queue at its limit: a high-class arrival displaces the
        lowest-value QUEUED request, which surfaces as a ShedLowValue
        StepOutcome at the next step — shed-lowest-value-first, not
        tail-drop."""
        generator = make_generator(params, max_slots=1)
        policy = make_policy()
        sched = Scheduler(generator, chunk=16, token_budget=32,
                          queue_limit=2, overload_policy=policy)
        sampling = SamplingParams(max_tokens=2, temperature=0.0,
                                  stop_on_eos=False)
        hog = sched.enqueue("holds the only slot", sampling)
        sched.step()  # hog occupies the slot; everything below queues
        cheap = sched.enqueue(
            "batch class, lowest value",
            dataclasses.replace(sampling, slo_class="batch"),
        )
        mid = sched.enqueue(
            "standard class",
            dataclasses.replace(sampling, slo_class="standard"),
        )
        assert sched.queue_depth == 2  # at the limit
        urgent = sched.enqueue(
            "interactive arrival displaces the batch request",
            dataclasses.replace(sampling, slo_class="interactive"),
        )
        assert sched.queue_depth == 2  # evicted, not grown
        done = drain(sched, 4)
        assert isinstance(done[cheap].error, ShedLowValue)
        for rid in (hog, mid, urgent):
            assert done[rid].error is None, rid
        assert generator.metrics.counter("sched_queue_evicted") == 1
        line = policy.log.lines()[-1]
        assert "site=sched" in line and "action=shed" in line
        assert "reason=queue-evict" in line and "cls=batch" in line
        assert_no_leaks(generator)

    def test_lowest_value_arrival_is_shed_at_enqueue(self, params):
        """When the ARRIVAL is the queue minimum, it is refused straight
        at enqueue (ShedLowValue raised to the caller) and the queued
        higher-value work is untouched."""
        generator = make_generator(params, max_slots=1)
        sched = Scheduler(generator, chunk=16, token_budget=32,
                          queue_limit=2, overload_policy=make_policy())
        sampling = SamplingParams(max_tokens=2, temperature=0.0,
                                  stop_on_eos=False,
                                  slo_class="interactive")
        hog = sched.enqueue("holds the only slot", sampling)
        sched.step()
        queued = [sched.enqueue(f"interactive {i}", sampling)
                  for i in range(2)]
        with pytest.raises(ShedLowValue):
            sched.enqueue(
                "batch arrival loses to the interactive queue",
                dataclasses.replace(sampling, slo_class="batch"),
            )
        assert sched.queue_depth == 2
        done = drain(sched, 3)
        assert all(done[r].error is None for r in [hog, *queued])
        assert_no_leaks(generator)

    def test_all_protected_queue_grows_instead_of_shedding(self, params):
        """Every candidate in a class below its attainment target: the
        ladder refuses to pick a victim and the queue grows past its
        limit — 'never shed the SLO class already below target'."""
        generator = make_generator(params, max_slots=1)
        policy = make_policy(attainment=lambda: {"batch": 0.1})
        sched = Scheduler(generator, chunk=16, token_budget=32,
                          queue_limit=1, overload_policy=policy)
        sampling = SamplingParams(max_tokens=2, temperature=0.0,
                                  stop_on_eos=False, slo_class="batch")
        hog = sched.enqueue("holds the only slot", sampling)
        sched.step()
        first = sched.enqueue("queued batch, protected", sampling)
        second = sched.enqueue("another protected batch", sampling)
        assert sched.queue_depth == 2  # grew past queue_limit=1
        assert generator.metrics.counter("sched_queue_evicted") == 0
        done = drain(sched, 3)
        assert all(done[r].error is None for r in (hog, first, second))
        assert_no_leaks(generator)

    def test_degraded_request_finishes_with_degraded_reason(self, params):
        """A ladder-truncated request that exhausts its reduced budget
        reports finish_reason 'degraded' — the distinct terminal outcome
        the SLO ledger counts as attained when it lands in target."""
        generator = make_generator(params)
        sched = Scheduler(generator, chunk=16, token_budget=32)
        req = sched.enqueue(
            "depth-truncated analysis",
            SamplingParams(max_tokens=2, temperature=0.0,
                           stop_on_eos=False, degraded=True),
        )
        outcome = drain(sched, 1)[req]
        assert outcome.error is None
        assert outcome.result.finish_reason == "degraded"
        assert_no_leaks(generator)


class TestAdmissionLadder:
    def test_pressure_band_truncates_analysis_depth(self, params):
        """deadline_policy consults the ladder before the deadline math:
        in the degrade band max_tokens shrinks and the params are stamped
        degraded — degrade-before-reject at the admission clamp."""
        generator = make_generator(params)
        generator.overload_policy = make_policy(degrade_tokens_frac=0.25)
        sampling = SamplingParams(max_tokens=40, temperature=0.0)
        clamped, outcome = generator.deadline_policy(sampling, pressure=5.0)
        assert outcome == "degraded"
        assert clamped.max_tokens == 10
        assert clamped.degraded is True
        # idempotent: an already-degraded request is not re-truncated
        again, outcome2 = generator.deadline_policy(clamped, pressure=5.0)
        assert outcome2 == "ok"
        assert again.max_tokens == 10

    def test_deep_overload_sheds_low_value_class(self, params):
        generator = make_generator(params)
        generator.overload_policy = make_policy(shed_value_floor=4.0)
        sampling = SamplingParams(max_tokens=8, temperature=0.0,
                                  slo_class="batch")
        # cutoff at pressure 16 = 4 * 16/8 = 8 > batch weight 1 -> shed
        _, outcome = generator.deadline_policy(sampling, pressure=16.0)
        assert outcome == "shed"
        # same pressure, interactive (16 >= 8) degrades instead
        clamped, outcome = generator.deadline_policy(
            SamplingParams(max_tokens=8, temperature=0.0,
                           slo_class="interactive"),
            pressure=16.0,
        )
        assert outcome == "degraded" and clamped.degraded

    def test_no_pressure_signal_leaves_request_untouched(self, params):
        generator = make_generator(params)
        generator.overload_policy = make_policy()
        sampling = SamplingParams(max_tokens=8, temperature=0.0)
        same, outcome = generator.deadline_policy(sampling)
        assert outcome == "ok" and same == sampling
        assert_no_leaks(generator)


class TestDeterminism:
    def test_fixed_arrival_trace_yields_identical_schedule(self, params):
        """Same arrival script, two fresh schedulers: the per-step plan
        sequence (slots, offsets, counts, kinds) and every result must
        be byte-identical — the property the chaos replay harness
        builds on."""

        def run_once():
            generator = make_generator(params)
            sched = Scheduler(generator, chunk=16, token_budget=32)
            sched.plan_log = []
            sampling = SamplingParams(max_tokens=6, temperature=0.0,
                                      stop_on_eos=False)
            arrivals = {
                0: [("pod crashed with exit code 137", sampling)],
                2: [("a longer second prompt " * 3, sampling),
                    ("third", sampling)],
                5: [("fourth arrival", sampling)],
            }
            results = {}
            for step_i in range(60):
                for prompt, params_ in arrivals.get(step_i, ()):
                    sched.enqueue(prompt, params_)
                for outcome in sched.step():
                    results[outcome.req_id] = outcome.result.token_ids
                if len(results) == 4:
                    break
            return sched.plan_log, results

        plans_a, results_a = run_once()
        plans_b, results_b = run_once()
        assert plans_a == plans_b
        assert results_a == results_b


# ---------------------------------------------------------------------------
# engine integration: deadlines, streaming, supervisor chaos
# ---------------------------------------------------------------------------


def _sched_engine(params, *, supervisor=None, **gen_kw):
    generator = make_generator(params, **gen_kw)
    sched = Scheduler(generator, chunk=16, token_budget=32)
    engine = ServingEngine(generator, scheduler=sched, supervisor=supervisor)
    return engine, generator, sched


def run(coro):
    return asyncio.run(coro)


class TestEngineIntegration:
    def test_concurrent_generate_and_streaming(self, params):
        engine, generator, _sched = _sched_engine(params)

        async def scenario():
            await engine.start()
            sampling = SamplingParams(max_tokens=5, temperature=0.0)
            parts = []
            results = await asyncio.gather(
                engine.generate("one", sampling),
                engine.generate("two", sampling,
                                on_partial=lambda ids: parts.append(len(ids))),
                engine.generate("three", sampling, priority=10),
            )
            await asyncio.sleep(0.05)
            assert all(r.completion_tokens > 0 for r in results)
            assert parts and parts == sorted(parts)
            await engine.close()

        run(scenario())
        assert_no_leaks(generator)

    def test_guided_and_lora_refused_at_submit(self, params):
        engine, generator, _sched = _sched_engine(params)

        async def scenario():
            await engine.start()
            with pytest.raises(ValueError, match="continuous"):
                await engine.generate(
                    "x", SamplingParams(guided_choice=("a", "b"))
                )
            with pytest.raises(ValueError, match="continuous|adapter"):
                await engine.generate(
                    "x", SamplingParams(adapter="nope")
                )
            await engine.close()

        run(scenario())

    def test_expired_deadline_fails_in_scheduler_queue(self, params):
        engine, generator, sched = _sched_engine(params)
        from operator_tpu.serving.engine import DeadlineExceeded

        async def scenario():
            await engine.start()
            # warm the roofline estimate so submit passes, then expire
            await engine.generate(
                "warm", SamplingParams(max_tokens=2, temperature=0.0,
                                       stop_on_eos=False),
            )
            clock = generator._clock
            expired = SamplingParams(
                max_tokens=4, temperature=0.0, deadline=clock() + 0.0005
            )
            with pytest.raises(DeadlineExceeded):
                await engine.generate("too late" * 40, expired)
            await engine.close()

        run(scenario())
        assert_no_leaks(generator)


class TestSupervisorChaos:
    def _stall_scenario(self, params, seed):
        """Seeded engine-stall chaos under the continuous loop: warm,
        wedge the second step past the watchdog budget, assert the
        supervisor requeues and the request completes.  Returns the
        replay-identity record."""
        from operator_tpu.utils.faultinject import OK, FaultPlan, sleep_

        generator = make_generator(params)
        sched = Scheduler(generator, chunk=16, token_budget=32)
        policy = SupervisorPolicy(stall_timeout_s=120.0, join_grace_s=2.0)
        engine = ServingEngine(generator, scheduler=sched, supervisor=policy)

        async def scenario():
            await engine.start()
            await engine.generate(
                "warm", SamplingParams(max_tokens=2, temperature=0.0,
                                       stop_on_eos=False),
            )
            policy.stall_timeout_s = 0.4
            plan = FaultPlan(seed=seed)
            plan.rule("engine.step", [OK, sleep_(1.5)])
            generator.fault_plan = plan
            result = await asyncio.wait_for(
                engine.generate(
                    "stalled mid-decode then requeued",
                    SamplingParams(max_tokens=12, temperature=0.0,
                                   stop_on_eos=False),
                ),
                30,
            )
            generator.fault_plan = None
            assert plan.pending() == {}, plan.pending()
            await engine.close()
            return result

        result = run(scenario())
        assert_no_leaks(generator)
        counters = generator.metrics.snapshot()["counters"]
        assert counters.get("supervisor_restart") == 1
        assert counters.get("supervisor_requeue") == 1
        assert not counters.get("supervisor_gaveup")
        assert not counters.get("supervisor_leak")
        return {
            "token_ids": result.token_ids,
            "finish_reason": result.finish_reason,
            "completion_tokens": result.completion_tokens,
            "restarts": counters.get("supervisor_restart"),
            "requeues": counters.get("supervisor_requeue"),
        }

    def test_engine_stall_requeues_and_replays_byte_identically(self, params):
        first = self._stall_scenario(params, seed=11)
        second = self._stall_scenario(params, seed=11)
        assert first == second


def test_expired_queued_request_fails_even_with_all_slots_busy(params):
    """The expiry sweep covers the WHOLE scheduler queue every step,
    regardless of capacity — an expired caller must not hang until a
    slot frees (the wave path's sweep fires on every loop round)."""
    from operator_tpu.serving.engine import DeadlineExceeded

    generator = make_generator(params, max_slots=1)
    sched = Scheduler(generator, chunk=16, token_budget=32)
    busy = sched.enqueue(
        "holds the only slot",
        SamplingParams(max_tokens=30, temperature=0.0, stop_on_eos=False),
    )
    sched.step()  # the only slot is now occupied
    fake_now = [generator._clock()]
    generator._clock = lambda: fake_now[0]
    doomed = sched.enqueue(
        "expires while queued",
        SamplingParams(max_tokens=4, temperature=0.0,
                       deadline=fake_now[0] + 0.5),
    )
    fake_now[0] += 1.0  # deadline passes with zero free slots
    outcomes = {o.req_id: o for o in sched.step()}
    assert doomed in outcomes, "expired entry not swept without capacity"
    assert isinstance(outcomes[doomed].error, DeadlineExceeded)
    assert sched.cancel(busy)
