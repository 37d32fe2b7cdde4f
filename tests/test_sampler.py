"""The sampler's selection is exact: ``_nucleus`` hands back, bit for
bit, what ``jax.lax.top_k`` over the whole row handed back, whichever
form the row's static shape sends it to, ties included."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from operator_tpu.serving import sampler  # noqa: E402

LANE = sampler._LANE

#: the cells' vocabularies (Ouro, Qwen2.5-1.5B and SDAR, Qwen2.5-7B,
#: Falcon-H1), the tiny test model's, and one that is no whole number of
#: blocks
CELL_WIDTHS = (49_152, 151_936, 152_064, 261_120)
PLAIN_WIDTHS = (512, 151_936 + 64)


def takes_pruned_form(vocab, k):
    return vocab % LANE == 0 and vocab // LANE >= sampler._MIN_BLOCKS_PER_K * k


def planted_rows(vocab, k, seed):
    """Rows of normal logits with the tie patterns that would show a
    selection that is not ``lax.top_k``'s."""
    rng = np.random.default_rng(seed)
    rows = rng.normal(size=(10, vocab)).astype(np.float32)
    # 0, 1: nothing planted, 1 with two-decimal values (ties everywhere)
    rows[1] = np.round(rows[1], 2)
    # 2: a row of one value
    rows[2] = 0.25
    # 3: more than k equal maxima, spread over two blocks
    rows[3, 5 * LANE - k: 5 * LANE + k] = 9.0
    # 4: the mask id's column, and whole blocks, at minus infinity
    rows[4, min(151_669, vocab - 3)] = -np.inf
    rows[4, 2 * LANE: 4 * LANE] = -np.inf
    # 5: equal values straddling a block edge at the k-th place: k - 1
    # larger ones far apart, then a run of equals over the edge
    rows[5, rng.choice(vocab // 2, size=max(k - 1, 0), replace=False)] = 12.0
    rows[5, vocab - 3 * LANE - 2: vocab - 3 * LANE + 2] = 11.0
    # 6: everything minus infinity but fewer than k columns
    rows[6] = -np.inf
    rows[6, rng.choice(vocab, size=max(k // 2, 1), replace=False)] = 1.0
    # 7: k blocks whose maxima tie, each holding smaller equals too:
    # the k-th place falls among equal block maxima
    rows[7] = -1.0
    rows[7, LANE // 2:: LANE] = 3.0
    rows[7, 7:: 2 * LANE] = 3.0
    # 8: one block holds the whole top k
    rows[8, vocab - LANE: vocab] = 20.0 + np.arange(LANE, dtype=np.float32)
    # 9: descending by id, so the winners are the first columns
    rows[9] = -np.arange(vocab, dtype=np.float32) / 1024.0
    return rows


@pytest.mark.parametrize("k", (1, 7, 64, 256))
@pytest.mark.parametrize("vocab", CELL_WIDTHS + PLAIN_WIDTHS)
def test_nucleus_is_bitwise_lax_top_k(monkeypatch, vocab, k):
    logits = jnp.asarray(planted_rows(vocab, k, seed=vocab + k))
    rows = logits.shape[0]
    temp = jnp.asarray(
        np.resize(np.array([0.3, 1.0, 1e-4, 0.0, 0.7], np.float32), rows)
    )
    top_p = jnp.asarray(
        np.resize(np.array([0.95, 1.0, 1e-6, 0.5], np.float32), rows)
    )
    # the case is of the form its width was chosen for
    text = jax.jit(sampler._nucleus, static_argnames="top_k").lower(
        logits, temp, top_p, top_k=k
    ).as_text()
    assert (f"{vocab // LANE}x{LANE}" in text) == takes_pruned_form(vocab, k)

    got_idx, got_filtered = sampler._nucleus(logits, temp, top_p, k)
    monkeypatch.setattr(sampler, "_top_k", jax.lax.top_k)  # the whole row
    want_idx, want_filtered = sampler._nucleus(logits, temp, top_p, k)
    np.testing.assert_array_equal(np.asarray(got_idx), np.asarray(want_idx))
    np.testing.assert_array_equal(
        np.asarray(got_filtered).view(np.uint32),
        np.asarray(want_filtered).view(np.uint32),
    )


def test_the_cells_take_the_pruned_form_and_the_tiny_models_the_plain():
    for vocab in CELL_WIDTHS:
        assert takes_pruned_form(vocab, sampler.SAMPLE_TOP_K), vocab
    for vocab in PLAIN_WIDTHS:
        assert not takes_pruned_form(vocab, sampler.SAMPLE_TOP_K), vocab
    # sample_top_k near the block count: sorted whole
    assert not takes_pruned_form(49_152, 256)


@pytest.mark.parametrize("temps", ((0.3, 0.0, 1.0, 0.3), (0.0, 0.0, 0.0, 0.0)))
def test_sample_and_confidence_are_what_they_were(monkeypatch, temps):
    """The same tokens, confidences and rng for a fixed key at 151,936
    columns as with the whole-row ``lax.top_k``."""
    vocab = 151_936
    logits = jnp.asarray(planted_rows(vocab, 64, seed=37)[:4])
    temp = jnp.asarray(temps, jnp.float32)
    top_p = jnp.asarray([0.95, 0.95, 1.0, 0.5], jnp.float32)
    key = jax.random.PRNGKey(20261004)

    def run():
        picked, rng = sampler.sample(logits, key, temp, top_p, top_k=64)
        conf_picked, conf, conf_rng = sampler.sample_with_confidence(
            logits, key, temp, top_p, top_k=64
        )
        return [np.asarray(x) for x in (picked, rng, conf_picked, conf, conf_rng)]

    got = run()
    monkeypatch.setattr(sampler, "_top_k", jax.lax.top_k)
    want = run()
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g.view(np.uint32), w.view(np.uint32))
