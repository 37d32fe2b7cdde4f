"""The ragged kernel's rung of one query: a row's one query against
every KV head of a KV block at once, in the page's own ``[keys, heads,
D]`` layout, each query head keeping its own KV head's keys, at one
query head a KV head (MHA) and at several (GQA).  The Pallas kernel in
interpret mode against ``ragged_attention_reference``, with the rung's
rows beside rows of the other rungs and idle slots in one call, and NaN
wherever no row may look: every page no table names, the keys past
``kv_len`` of every last page, and the heads a page holds past the
model's (``pool_heads``).
"""

import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from operator_tpu.ops.ragged_attention import (  # noqa: E402
    _ragged_attention_pallas,
    pool_heads,
    query_tile_rows,
    ragged_attention_reference,
)

LAYERS, PAGE, D, CHUNK, PAGES_A_ROW = 2, 8, 16, 16, 6

#: one call's rows, pages of 8 keys: decode rows of 1 page (5 keys and
#: one key), of 2, 3, 5 and 6 pages (6 ends 3 keys into its last page),
#: a whole chunk, a verify row of 5 queries and idle slots, one with keys
ROWS = dict(
    q_count=[1, 0, 1, 16, 1, 5, 1, 0, 1, 1],
    kv_len=[5, 20, 16, 30, 24, 21, 43, 0, 1, 40],
)

#: the model's query and KV heads: sixteen of sixteen, thirty held as
#: thirty-two a page, and the GQA cells' 12 / 2, 28 / 4 and 20 / 4
GEOMETRIES = {
    "16 of 16": (16, 16), "30 in a 32-head pool": (30, 30),
    "12 / 2": (12, 2), "28 / 4": (28, 4), "20 / 4": (20, 4),
}


def case(rng, heads, kv_heads, dtype):
    """Queries, a stacked pool with contents of its own in every layer
    (pages handed out in no order), its table, and the pool poisoned with
    NaN wherever no row may look."""
    rows = len(ROWS["q_count"])
    held = pool_heads(kv_heads)
    num_pages = rows * PAGES_A_ROW + 1
    shape = (LAYERS, num_pages, PAGE, held, D)
    k = jnp.asarray(rng.normal(size=shape), dtype)
    v = jnp.asarray(rng.normal(size=shape), dtype)
    table = rng.permutation(np.arange(1, num_pages)).reshape(rows, PAGES_A_ROW)
    q = jnp.asarray(rng.normal(size=(rows, CHUNK, heads, D)), dtype)
    dead = np.ones((num_pages, PAGE), bool)  # [page, key]
    for row, kv in enumerate(ROWS["kv_len"]):
        live = -(-kv // PAGE)
        dead[table[row, :live]] = False
        if kv % PAGE:
            dead[table[row, live - 1], kv % PAGE:] = True
    bad = (jnp.asarray(dead)[None, :, :, None, None]
           | (jnp.arange(held) >= kv_heads)[None, None, None, :, None])
    poisoned = tuple(jnp.where(bad, jnp.nan, x) for x in (k, v))
    return q, (k, v), poisoned, jnp.asarray(table, jnp.int32)


@pytest.mark.parametrize("blocks", [1, 2, 4], ids=lambda n: f"{n}-pages-a-block")
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_the_rung_of_one_query_equals_the_reference(geometry, blocks):
    heads, kv_heads = GEOMETRIES[geometry]
    rng = np.random.default_rng(heads + kv_heads + blocks)
    q, clean, poisoned, table = case(rng, heads, kv_heads, jnp.float32)
    kv_len = jnp.asarray(ROWS["kv_len"], jnp.int32)
    q_count = jnp.asarray(ROWS["q_count"], jnp.int32)
    # every rung is in the call, the one-query rung with its decode rows
    tiles = query_tile_rows(np.asarray(ROWS["q_count"]), CHUNK)
    assert set(tiles.tolist()) == {0, 1, 8, CHUNK}
    layer = jnp.int32(1)
    got = _ragged_attention_pallas(
        q, *poisoned, table, kv_len, q_count, layer, interpret=True,
        block_pages=(blocks,) * 3, kv_heads=kv_heads,
    )
    assert got.shape == q.shape
    want = ragged_attention_reference(
        q, *clean, table, kv_len, q_count, layer, kv_heads=kv_heads,
    )
    for row, n in enumerate(ROWS["q_count"]):
        if not n:
            continue  # an idle slot's output is not read
        np.testing.assert_allclose(
            np.asarray(got[row, :n]), np.asarray(want[row, :n]), rtol=2e-5, atol=2e-5,
        )


@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_the_rung_keeps_the_pools_precision(geometry):
    """A bfloat16 pool and queries: scores from bfloat16 keys and queries
    summed in float32, the output in the queries' dtype."""
    heads, kv_heads = GEOMETRIES[geometry]
    rng = np.random.default_rng(3 * heads + kv_heads)
    q, clean, poisoned, table = case(rng, heads, kv_heads, jnp.bfloat16)
    kv_len = jnp.asarray(ROWS["kv_len"], jnp.int32)
    q_count = jnp.asarray(ROWS["q_count"], jnp.int32)
    got = _ragged_attention_pallas(
        q, *poisoned, table, kv_len, q_count, jnp.int32(0), interpret=True,
        block_pages=(2, 2, 2), kv_heads=kv_heads,
    )
    assert got.dtype == jnp.bfloat16
    want = ragged_attention_reference(
        *(x.astype(jnp.float32) for x in (q, *clean)), table, kv_len, q_count,
        jnp.int32(0), kv_heads=kv_heads,
    )
    for row, n in enumerate(ROWS["q_count"]):
        if n:
            np.testing.assert_allclose(
                np.asarray(got[row, :n], np.float32), np.asarray(want[row, :n]),
                rtol=2e-2, atol=2e-2,
            )


@pytest.mark.parametrize("geometry, window", [
    ("16 of 16", 7), ("16 of 16", 20), ("28 / 4", 7), ("28 / 4", 20),
], ids=["7", "20", "28 / 4-7", "28 / 4-20"])
def test_a_window_bites_on_the_rung(geometry, window):
    """A sliding window, whose first page is no block's edge: the walk
    starts there and the rung masks the keys before the window."""
    heads, kv_heads = GEOMETRIES[geometry]
    rng = np.random.default_rng(window)
    q, clean, poisoned, table = case(rng, heads, kv_heads, jnp.float32)
    kv_len = jnp.asarray(ROWS["kv_len"], jnp.int32)
    q_count = jnp.asarray(ROWS["q_count"], jnp.int32)
    got = _ragged_attention_pallas(
        q, *clean, table, kv_len, q_count, jnp.int32(1), interpret=True,
        sliding_window=window, block_pages=(2, 2, 2),
    )
    want = ragged_attention_reference(
        q, *clean, table, kv_len, q_count, jnp.int32(1), sliding_window=window,
    )
    unwindowed = ragged_attention_reference(
        q, *clean, table, kv_len, q_count, jnp.int32(1),
    )
    for row, n in enumerate(ROWS["q_count"]):
        if n:
            np.testing.assert_allclose(
                np.asarray(got[row, :n]), np.asarray(want[row, :n]),
                rtol=2e-5, atol=2e-5,
            )
    # the window changed the longest decode row's answer
    assert not np.allclose(np.asarray(want[6, 0]), np.asarray(unwindowed[6, 0]), atol=1e-3)


@pytest.mark.parametrize("block", [4, 8])
def test_the_rung_serves_the_block_mask_at_32_of_4(block):
    """The block-causal mask (models/sdar.py) at SDAR's 32 / 4 heads: a
    row of one query at position ``p`` sees every key up to ``p | (block
    - 1)``, written by the step like the rest, on the rung of one query
    beside rows of a block and of a whole chunk; NaN wherever no row may
    look."""
    heads, kv_heads = 32, 4
    rng = np.random.default_rng(block)
    q, clean, poisoned, table = case(rng, heads, kv_heads, jnp.float32)
    # every decode row's position lies inside its block, so it sees keys
    # after its own: the verify row stands for a block's later step
    kv_len = jnp.asarray(ROWS["kv_len"], jnp.int32)
    q_count = jnp.asarray(ROWS["q_count"], jnp.int32)
    positions = np.asarray(ROWS["kv_len"]) - 1
    assert (positions[np.asarray(ROWS["q_count"]) == 1] % block < block - 1).any()
    got = _ragged_attention_pallas(
        q, *poisoned, table, kv_len, q_count, jnp.int32(1), interpret=True,
        attend_block=block, block_pages=(2, 2, 2), kv_heads=kv_heads,
    )
    want = ragged_attention_reference(
        q, *clean, table, kv_len, q_count, jnp.int32(1), attend_block=block,
        kv_heads=kv_heads,
    )
    for row, n in enumerate(ROWS["q_count"]):
        if n:
            np.testing.assert_allclose(
                np.asarray(got[row, :n]), np.asarray(want[row, :n]),
                rtol=2e-5, atol=2e-5,
            )
