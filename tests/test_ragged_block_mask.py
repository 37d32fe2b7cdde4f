"""The ragged kernel's block-causal mask (``attend_block``: a query sees
every position up to the end of its own block of that many, models/
sdar.py): the Pallas kernel in interpret mode against
``ragged_attention_reference``, the reference against a mask written out
by hand, and ``attend_block=1`` lowering to the text of a call that does
not name the argument.
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
    ragged_attention_reference,
)

LAYERS = 2


def pool(rng, rows, chunk, *, qh=8, kh=2, d=16, page=8, pages_a_row=10):
    """A stacked pool with contents of its own in every layer, a page table
    whose rows take their pages in another order than the pool's, and
    queries."""
    num_pages = rows * pages_a_row + 1
    k = jnp.asarray(rng.normal(size=(LAYERS, num_pages, page, kh, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(LAYERS, num_pages, page, kh, d)), jnp.float32)
    table = rng.permutation(np.arange(1, num_pages)).reshape(rows, pages_a_row)
    q = jnp.asarray(rng.normal(size=(rows, chunk, qh, d)), jnp.float32)
    return q, k, v, jnp.asarray(table, jnp.int32)


#: rows of 4 queries (a block's later steps), 8 (its first step: the block
#: before it, then the block), 64 (a prefill chunk), an idle slot, and a
#: chunk that ends inside a page; pages of 8 keys, so a block of 4 spans
#: two pages where a row's positions start off a block's edge is
#: impossible, and a block of 16 always does
CASES = {
    "blocks of 4": dict(
        block=4, chunk=64, q_count=[4, 8, 64, 0, 8, 4, 20],
        kv_len=[12, 24, 64, 30, 72, 4, 44],
    ),
    "blocks of 16 span two pages": dict(
        block=16, chunk=32, q_count=[16, 32, 0, 16],
        kv_len=[48, 32, 16, 80],
    ),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_equals_the_reference_under_the_block_mask(case):
    spec = CASES[case]
    rng = np.random.default_rng(7)
    rows = len(spec["q_count"])
    q, k, v, table = pool(rng, rows, spec["chunk"])
    kv_len = jnp.asarray(spec["kv_len"], jnp.int32)
    q_count = jnp.asarray(spec["q_count"], jnp.int32)
    layer = jnp.int32(1)
    want = ragged_attention_reference(
        q, k, v, table, kv_len, q_count, layer, attend_block=spec["block"],
    )
    got = _ragged_attention_pallas(
        q, k, v, table, kv_len, q_count, layer, interpret=True,
        attend_block=spec["block"],
    )
    causal = ragged_attention_reference(q, k, v, table, kv_len, q_count, layer)
    for row, n in enumerate(spec["q_count"]):
        if not n:
            continue
        np.testing.assert_allclose(
            np.asarray(got[row, :n]), np.asarray(want[row, :n]), rtol=2e-5, atol=2e-5,
        )
        # the last query of a block sees what a causal query sees; the
        # first sees more
        assert np.allclose(np.asarray(want[row, n - 1]), np.asarray(causal[row, n - 1]), atol=2e-5)
        assert not np.allclose(np.asarray(want[row, 0]), np.asarray(causal[row, 0]), atol=1e-3)


def test_the_reference_is_the_mask_written_out():
    """``j // B <= i // B`` by hand, one row, softmax in float64."""
    rng = np.random.default_rng(3)
    block, page, d = 4, 8, 16
    q, k, v, table = pool(rng, 1, 8, qh=2, kh=1, d=d, page=page, pages_a_row=3)
    kv_len, count = 20, 8  # the row's queries are positions 12 .. 19
    got = np.asarray(ragged_attention_reference(
        q, k, v, table, jnp.asarray([kv_len]), jnp.asarray([count]), jnp.int32(0),
        attend_block=block,
    ))
    keys = np.asarray(k[0, table[0]]).reshape(-1, d)[:kv_len].astype(np.float64)
    values = np.asarray(v[0, table[0]]).reshape(-1, d)[:kv_len].astype(np.float64)
    for i in range(count):
        position = kv_len - count + i
        seen = [j for j in range(kv_len) if j // block <= position // block]
        for head in range(2):
            scores = keys[seen] @ np.asarray(q[0, i, head], np.float64) * d ** -0.5
            probs = np.exp(scores - scores.max())
            want = (probs / probs.sum()) @ values[seen]
            assert np.abs(got[0, i, head] - want).max() < 1e-5


def test_a_block_of_one_lowers_to_the_causal_kernels_text():
    rng = np.random.default_rng(1)
    q, k, v, table = pool(rng, 3, 16)
    args = (q, k, v, table, jnp.asarray([16, 9, 30]), jnp.asarray([16, 1, 5]), jnp.int32(0))

    def text(**kw):
        return jax.jit(
            lambda *a: _ragged_attention_pallas(*a, interpret=True, **kw)
        ).lower(*args).as_text()

    assert text(attend_block=1) == text()
    assert text(attend_block=4) != text()
    reference = jax.jit(ragged_attention_reference, static_argnames=("attend_block",))
    assert (
        reference.lower(*args, attend_block=1).as_text() == reference.lower(*args).as_text()
    )
