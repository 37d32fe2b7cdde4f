"""Program construction: every jitted XLA program the generator runs.

The compile layer split out of serving/engine.py (VERDICT r4 item 8): the
decode step/block variants (plain/paged x unguided/guided), the
prefill-bucket factories (plain, paged, shared-prefix suffix), and the
chunked-prefill chunk/finish programs.  Pure construction — program CACHES
(_prefill_fns/_prefix_fns/_chunk_fns/_finish_fns) and all mutable state
stay on the generator; these methods close over `self` only for static
configuration (config, mesh, shardings) and the runtime's sampler
(``self.sample``, serving/sampler.py).

Mixed into :class:`serving.engine.BatchedGenerator`.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

from ..models.llama import KVCache, forward

log = logging.getLogger(__name__)


class ProgramBuilderMixin:
    """Builders for the generator's compiled programs (see module doc)."""

    #: unroll the K-step decode block into straight-line XLA instead of a
    #: lax.scan whose carry holds the whole KV cache/page pool.  Experiment
    #: knob (ROADMAP D2); compile time grows ~K-fold.
    DECODE_UNROLL = os.environ.get("OPERATOR_TPU_DECODE_UNROLL", "0") == "1"

    def _decode_step(self, params, cache, tokens, offsets, rng, temp, top_p, active,
                     lora=None, lora_idx=None,
                     gtables=None, gaut=None, gstate=None):
        """[B,1] tokens at per-slot offsets -> next token per slot."""
        jnp = self._jnp
        positions = offsets[:, None]
        logits, cache = forward(
            params, self.config, tokens, positions, cache=cache, cache_offset=offsets,
            lora=lora, lora_alpha=self.lora_alpha, lora_indices=lora_idx,
        )
        last = logits[:, -1, :]
        if gtables is not None:
            row = gtables[gaut, gstate]
            last = jnp.where(row >= 0, last, -jnp.inf)
        next_tokens, rng = self.sample(last, rng, temp, top_p)
        # inactive slots keep decoding garbage into their own slot space;
        # offsets only advance for active ones so their state is untouched
        offsets = jnp.where(active, offsets + 1, offsets)
        if gtables is None:
            return cache, next_tokens, offsets, rng
        stepped = jnp.take_along_axis(row, next_tokens[:, None], axis=1)[:, 0]
        gstate = jnp.where(active & (stepped >= 0), stepped, gstate)
        return cache, next_tokens, offsets, rng, gstate

    def _decode_step_paged(self, params, paged, tokens, rng, temp, top_p, active,
                           lora=None, lora_idx=None,
                           gtables=None, gaut=None, gstate=None):
        """Paged twin of :meth:`_decode_step` (released slots write to the
        trash page via their zeroed page-table row; their lengths stay put).
        With guided args, the sampler is masked by the automaton row and the
        per-slot DFA state advances — returned as an extra carry."""
        from ..models.llama import decode_step_paged
        from ..ops.paged_attention import PagedKVCache

        jnp = self._jnp
        logits, new_paged = decode_step_paged(
            params, self.config, tokens, paged,
            lora=lora, lora_alpha=self.lora_alpha, lora_indices=lora_idx,
            mesh=self.mesh,
        )
        if gtables is not None:
            row = gtables[gaut, gstate]  # [B, vocab] allowed-transition rows
            logits = jnp.where(row >= 0, logits, -jnp.inf)
        next_tokens, rng = self.sample(logits, rng, temp, top_p)
        lengths = jnp.where(active, new_paged.lengths, paged.lengths)
        new_paged = PagedKVCache(
            k_pages=new_paged.k_pages, v_pages=new_paged.v_pages,
            page_table=new_paged.page_table, lengths=lengths,
        )
        if gtables is None:
            return new_paged, next_tokens, rng
        stepped = jnp.take_along_axis(row, next_tokens[:, None], axis=1)[:, 0]
        gstate = jnp.where(active & (stepped >= 0), stepped, gstate)
        return new_paged, next_tokens, rng, gstate

    def _decode_block(self, params, cache, tokens, offsets, rng, temp, top_p, active,
                      lora=None, lora_idx=None):
        """K chained decode steps in one program; returns the [K, B] token
        matrix plus final carry state.  lax.scan by default, straight-line
        unrolled under OPERATOR_TPU_DECODE_UNROLL=1 (see DECODE_UNROLL)."""
        jax, jnp = self._jax, self._jnp

        if self.DECODE_UNROLL:
            toks = []
            for _ in range(self.decode_block):
                cache, next_tokens, offsets, rng = self._decode_step(
                    params, cache, tokens, offsets, rng, temp, top_p, active,
                    lora, lora_idx,
                )
                tokens = next_tokens[:, None]
                toks.append(next_tokens)
            return cache, jnp.stack(toks), tokens, offsets, rng

        def body(carry, _):
            cache, tokens, offsets, rng = carry
            cache, next_tokens, offsets, rng = self._decode_step(
                params, cache, tokens, offsets, rng, temp, top_p, active,
                lora, lora_idx,
            )
            return (cache, next_tokens[:, None], offsets, rng), next_tokens

        (cache, last, offsets, rng), toks = jax.lax.scan(
            body, (cache, tokens, offsets, rng), None, length=self.decode_block
        )
        return cache, toks, last, offsets, rng

    def _decode_block_paged(self, params, paged, tokens, rng, temp, top_p, active,
                            lora=None, lora_idx=None):
        jax, jnp = self._jax, self._jnp

        if self.DECODE_UNROLL:
            toks = []
            for _ in range(self.decode_block):
                paged, next_tokens, rng = self._decode_step_paged(
                    params, paged, tokens, rng, temp, top_p, active,
                    lora, lora_idx,
                )
                tokens = next_tokens[:, None]
                toks.append(next_tokens)
            return paged, jnp.stack(toks), tokens, rng

        def body(carry, _):
            paged, tokens, rng = carry
            paged, next_tokens, rng = self._decode_step_paged(
                params, paged, tokens, rng, temp, top_p, active,
                lora, lora_idx,
            )
            return (paged, next_tokens[:, None], rng), next_tokens

        (paged, last, rng), toks = jax.lax.scan(
            body, (paged, tokens, rng), None, length=self.decode_block
        )
        return paged, toks, last, rng

    def _decode_block_guided(self, params, cache, tokens, offsets, rng, temp,
                             top_p, active, lora, lora_idx,
                             gtables, gaut, gstate):
        """Guided twin of :meth:`_decode_block`: the DFA state joins the
        scan carry, so masking and stepping never leave the device."""
        jax, jnp = self._jax, self._jnp

        if self.DECODE_UNROLL:
            toks = []
            for _ in range(self.decode_block):
                cache, next_tokens, offsets, rng, gstate = self._decode_step(
                    params, cache, tokens, offsets, rng, temp, top_p, active,
                    lora, lora_idx, gtables, gaut, gstate,
                )
                tokens = next_tokens[:, None]
                toks.append(next_tokens)
            return cache, jnp.stack(toks), tokens, offsets, rng, gstate

        def body(carry, _):
            cache, tokens, offsets, rng, gstate = carry
            cache, next_tokens, offsets, rng, gstate = self._decode_step(
                params, cache, tokens, offsets, rng, temp, top_p, active,
                lora, lora_idx, gtables, gaut, gstate,
            )
            return (cache, next_tokens[:, None], offsets, rng, gstate), next_tokens

        (cache, last, offsets, rng, gstate), toks = jax.lax.scan(
            body, (cache, tokens, offsets, rng, gstate), None,
            length=self.decode_block,
        )
        return cache, toks, last, offsets, rng, gstate

    def _decode_block_paged_guided(self, params, paged, tokens, rng, temp,
                                   top_p, active, lora, lora_idx,
                                   gtables, gaut, gstate):
        jax, jnp = self._jax, self._jnp

        if self.DECODE_UNROLL:
            toks = []
            for _ in range(self.decode_block):
                paged, next_tokens, rng, gstate = self._decode_step_paged(
                    params, paged, tokens, rng, temp, top_p, active,
                    lora, lora_idx, gtables, gaut, gstate,
                )
                tokens = next_tokens[:, None]
                toks.append(next_tokens)
            return paged, jnp.stack(toks), tokens, rng, gstate

        def body(carry, _):
            paged, tokens, rng, gstate = carry
            paged, next_tokens, rng, gstate = self._decode_step_paged(
                params, paged, tokens, rng, temp, top_p, active,
                lora, lora_idx, gtables, gaut, gstate,
            )
            return (paged, next_tokens[:, None], rng, gstate), next_tokens

        (paged, last, rng, gstate), toks = jax.lax.scan(
            body, (paged, tokens, rng, gstate), None, length=self.decode_block
        )
        return paged, toks, last, rng, gstate

    def _get_guided_decode_fn(self):
        if self._decode_fn_guided is None:
            jax = self._jax
            body = (
                self._decode_block_paged_guided if self.paged
                else self._decode_block_guided
            )
            if self.mesh is None:
                self._decode_fn_guided = jax.jit(body, donate_argnums=(1,))
            else:
                # mirrors the unguided mesh programs: automaton tables
                # replicate (tens of MB, read-only), per-slot aut/state
                # shard over the data axes with the other [B] vectors
                from jax.sharding import NamedSharding, PartitionSpec as P

                s = self._shardings
                block_tokens = NamedSharding(self.mesh, P(None, ("dp", "fsdp")))
                if self.paged:
                    self._decode_fn_guided = jax.jit(
                        body,
                        in_shardings=(
                            self._param_shardings, s["paged"], s["tokens"],
                            s["repl"], s["batch"], s["batch"], s["batch"],
                            s["repl"], s["batch"],  # lora stack, idx
                            s["repl"], s["batch"], s["batch"],  # tables, aut, state
                        ),
                        out_shardings=(
                            s["paged"], block_tokens, s["tokens"], s["repl"],
                            s["batch"],
                        ),
                        donate_argnums=(1,),
                    )
                else:
                    self._decode_fn_guided = jax.jit(
                        body,
                        in_shardings=(
                            self._param_shardings, s["cache"], s["tokens"],
                            s["batch"], s["repl"], s["batch"], s["batch"],
                            s["batch"], s["repl"], s["batch"],
                            s["repl"], s["batch"], s["batch"],
                        ),
                        out_shardings=(
                            s["cache"], block_tokens, s["tokens"], s["batch"],
                            s["repl"], s["batch"],
                        ),
                        donate_argnums=(1,),
                    )
            self._decode_fn_guided = self._aot_wrap(
                "decode_guided", self._decode_fn_guided
            )
        return self._decode_fn_guided

    def _prefill_shardings(self, n_pad: int):
        """(row, vec) shardings for a prefill bucket.  dp-aware admission
        (_admit_batch) always pads the bucket to a multiple of dp*fsdp, so
        rows shard over the data axes unconditionally."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        assert n_pad % self._dp_total == 0, (n_pad, self._dp_total)
        return (
            NamedSharding(self.mesh, P(("dp", "fsdp"), None)),
            NamedSharding(self.mesh, P(("dp", "fsdp"))),
        )

    def _prefill_score_shards(self) -> int:
        """Devices the prefill batch axis is sharded over — the
        chunked-attention budget is per-device (models/llama.py)."""
        return self._dp_total if self.mesh is not None else 1

    def _make_prefill(self, n_pad: int, t_pad: int, guided: bool = False):
        """Compile a prefill program for the (n_pad, t_pad) bucket."""
        jax, jnp = self._jax, self._jnp
        config = self.config
        score_shards = self._prefill_score_shards()

        def prefill_fn(params, cache, token_ids, lengths, slot_ids, rng, temp, top_p,
                       lora=None, lora_idx=None, gtables=None, gaut=None):
            # fresh contiguous mini-cache for the prompt tokens
            mini = KVCache.create(config, n_pad, t_pad, dtype=cache.k.dtype)
            positions = jnp.broadcast_to(
                jnp.arange(t_pad, dtype=jnp.int32)[None], (n_pad, t_pad)
            )
            kv_valid = positions < lengths[:, None]
            # kv_valid (not a materialised mask) so long buckets take the
            # chunked-prefill path in models/llama.py — no [T, S] f32 scores
            logits, mini = forward(
                params, config, token_ids, positions, cache=mini,
                cache_offset=0, kv_valid=kv_valid, score_shards=score_shards,
                prefill_lengths=lengths,
                lora=lora, lora_alpha=self.lora_alpha, lora_indices=lora_idx,
            )
            # scatter the prompt KV into the big cache rows for these slots
            # (slot axis is axis 1 of [L, B, S, KH, D])
            k = cache.k.at[:, slot_ids, :t_pad].set(mini.k.astype(cache.k.dtype))
            v = cache.v.at[:, slot_ids, :t_pad].set(mini.v.astype(cache.v.dtype))
            last = jnp.take_along_axis(
                logits, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1
            )[:, 0, :]
            if guided:
                row = gtables[gaut, jnp.zeros_like(gaut)]  # DFA start state
                last = jnp.where(row >= 0, last, -jnp.inf)
            first_tokens, rng = self.sample(last, rng, temp, top_p)
            if guided:
                first_state = jnp.take_along_axis(
                    row, first_tokens[:, None], axis=1
                )[:, 0]
                return KVCache(k=k, v=v), first_tokens, rng, jnp.maximum(first_state, 0)
            return KVCache(k=k, v=v), first_tokens, rng

        if self.mesh is None:
            return jax.jit(prefill_fn)
        s = self._shardings
        rows, vec = self._prefill_shardings(n_pad)
        in_shardings = (
            self._param_shardings, s["cache"], rows, vec, vec,
            s["repl"], vec, vec, s["repl"], vec,
        )
        out_shardings = (s["cache"], vec, s["repl"])
        if guided:
            in_shardings += (s["repl"], vec)   # tables, row automaton ids
            out_shardings += (vec,)            # first DFA state per row
        return jax.jit(
            prefill_fn, in_shardings=in_shardings, out_shardings=out_shardings
        )

    def _make_prefill_paged(self, n_pad: int, t_pad: int, guided: bool = False):
        """Prefill for the paged cache: same mini-cache forward, then the
        prompt KV scatters into each sequence's pages (write_tokens with
        valid_len so padded rows land in the trash page)."""
        jax, jnp = self._jax, self._jnp
        config = self.config
        score_shards = self._prefill_score_shards()

        def prefill_fn(params, paged, token_ids, lengths, row_tables, rng, temp, top_p,
                       lora=None, lora_idx=None, gtables=None, gaut=None):
            from ..ops.paged_attention import PagedKVCache, write_tokens

            mini = KVCache.create(config, n_pad, t_pad, dtype=paged.k_pages.dtype)
            positions = jnp.broadcast_to(
                jnp.arange(t_pad, dtype=jnp.int32)[None], (n_pad, t_pad)
            )
            kv_valid = positions < lengths[:, None]
            logits, mini = forward(
                params, config, token_ids, positions, cache=mini,
                cache_offset=0, kv_valid=kv_valid, score_shards=score_shards,
                prefill_lengths=lengths,
                lora=lora, lora_alpha=self.lora_alpha, lora_indices=lora_idx,
            )
            zero = jnp.zeros((n_pad,), jnp.int32)
            scatter = jax.vmap(write_tokens, in_axes=(0, None, 0, None, None))
            k_pages = scatter(paged.k_pages, row_tables, mini.k, zero, lengths)
            v_pages = scatter(paged.v_pages, row_tables, mini.v, zero, lengths)
            last = jnp.take_along_axis(
                logits, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1
            )[:, 0, :]
            if guided:
                row = gtables[gaut, jnp.zeros_like(gaut)]  # DFA start state
                last = jnp.where(row >= 0, last, -jnp.inf)
            first_tokens, rng = self.sample(last, rng, temp, top_p)
            new_paged = PagedKVCache(
                k_pages=k_pages, v_pages=v_pages,
                page_table=paged.page_table, lengths=paged.lengths,
            )
            if guided:
                first_state = jnp.take_along_axis(
                    row, first_tokens[:, None], axis=1
                )[:, 0]
                return new_paged, first_tokens, rng, jnp.maximum(first_state, 0)
            return new_paged, first_tokens, rng

        if self.mesh is None:
            return jax.jit(prefill_fn)
        s = self._shardings
        rows, vec = self._prefill_shardings(n_pad)
        in_shardings = (
            self._param_shardings, s["paged"], rows, vec, rows,
            s["repl"], vec, vec, s["repl"], vec,
        )
        out_shardings = (s["paged"], vec, s["repl"])
        if guided:
            in_shardings += (s["repl"], vec)
            out_shardings += (vec,)
        return jax.jit(
            prefill_fn, in_shardings=in_shardings, out_shardings=out_shardings
        )

    def _make_prefill_paged_prefixed(
        self, n_pad: int, t_sfx: int, shared: int, guided: bool = False
    ):
        """Suffix-only prefill: the first ``shared`` tokens' KV is gathered
        from the cached prefix pages into the mini cache (read-only reuse),
        and only ``t_sfx`` suffix tokens run through the model."""
        jax, jnp = self._jax, self._jnp
        config = self.config
        score_shards = self._prefill_score_shards()
        n_prefix_pages = shared // self.page_size
        t_total = shared + t_sfx

        def prefill_fn(params, paged, prefix_table, token_ids, lengths,
                       row_tables, rng, temp, top_p,
                       lora=None, lora_idx=None, gtables=None, gaut=None):
            from ..ops.paged_attention import PagedKVCache, write_tokens

            # prefix KV: pages -> contiguous [L, shared, KH, D], shared by
            # every row of the mini cache (broadcast, not per-row copies)
            def gather(pages):
                picked = pages[:, prefix_table]  # [L, n_pp, ps, KH, D]
                return picked.reshape(
                    pages.shape[0], shared, *pages.shape[3:]
                )

            mini = KVCache.create(config, n_pad, t_total, dtype=paged.k_pages.dtype)
            mini = KVCache(
                k=mini.k.at[:, :, :shared].set(
                    gather(paged.k_pages).astype(mini.k.dtype)[:, None]
                ),
                v=mini.v.at[:, :, :shared].set(
                    gather(paged.v_pages).astype(mini.v.dtype)[:, None]
                ),
            )
            positions = shared + jnp.broadcast_to(
                jnp.arange(t_sfx, dtype=jnp.int32)[None], (n_pad, t_sfx)
            )
            kv_positions = jnp.broadcast_to(
                jnp.arange(t_total, dtype=jnp.int32)[None], (n_pad, t_total)
            )
            kv_valid = kv_positions < lengths[:, None]
            logits, mini = forward(
                params, config, token_ids, positions, cache=mini,
                cache_offset=jnp.full((n_pad,), shared, jnp.int32),
                kv_valid=kv_valid, score_shards=score_shards,
                lora=lora, lora_alpha=self.lora_alpha, lora_indices=lora_idx,
            )
            # scatter ONLY the suffix into this wave's own pages — the
            # prefix pages are shared and must never be rewritten
            start = jnp.full((n_pad,), shared, jnp.int32)
            suffix_len = lengths - shared
            suffix_k = jax.lax.slice_in_dim(mini.k, shared, t_total, axis=2)
            suffix_v = jax.lax.slice_in_dim(mini.v, shared, t_total, axis=2)
            zero_scatter = jax.vmap(write_tokens, in_axes=(0, None, 0, None, None))
            k_pages = zero_scatter(paged.k_pages, row_tables, suffix_k, start, suffix_len)
            v_pages = zero_scatter(paged.v_pages, row_tables, suffix_v, start, suffix_len)
            last = jnp.take_along_axis(
                logits, (lengths - 1 - shared)[:, None, None].astype(jnp.int32),
                axis=1,
            )[:, 0, :]
            if guided:
                row = gtables[gaut, jnp.zeros_like(gaut)]
                last = jnp.where(row >= 0, last, -jnp.inf)
            first_tokens, rng = self.sample(last, rng, temp, top_p)
            new_paged = PagedKVCache(
                k_pages=k_pages, v_pages=v_pages,
                page_table=paged.page_table, lengths=paged.lengths,
            )
            if guided:
                first_state = jnp.take_along_axis(
                    row, first_tokens[:, None], axis=1
                )[:, 0]
                return new_paged, first_tokens, rng, jnp.maximum(first_state, 0)
            return new_paged, first_tokens, rng

        if self.mesh is None:
            return jax.jit(prefill_fn)
        s = self._shardings
        rows, vec = self._prefill_shardings(n_pad)
        in_shardings = (
            self._param_shardings, s["paged"], s["repl"], rows, vec, rows,
            s["repl"], vec, vec, s["repl"], vec,
        )
        out_shardings = (s["paged"], vec, s["repl"])
        if guided:
            in_shardings += (s["repl"], vec)
            out_shardings += (vec,)
        return jax.jit(
            prefill_fn, in_shardings=in_shardings, out_shardings=out_shardings
        )

    def _make_chunk_fn(self, n_pad: int, t_pad: int, chunk: int):
        """One prefill chunk: forward ``chunk`` tokens at a dynamic offset
        into the job's mini cache, carrying last-token logits for rows whose
        prompt ends inside this chunk."""
        jax, jnp = self._jax, self._jnp
        config = self.config
        score_shards = self._prefill_score_shards()

        def chunk_fn(params, mini, ids_chunk, lengths, offset, last_logits,
                     lora=None, lora_idx=None):
            positions = offset + jnp.broadcast_to(
                jnp.arange(chunk, dtype=jnp.int32)[None], (n_pad, chunk)
            )
            kv_positions = jnp.broadcast_to(
                jnp.arange(t_pad, dtype=jnp.int32)[None], (n_pad, t_pad)
            )
            # valid cache slots: written so far (incl. this chunk) AND real
            kv_valid = kv_positions < jnp.minimum(lengths, offset + chunk)[:, None]
            logits, mini = forward(
                params, config, ids_chunk, positions, cache=mini,
                cache_offset=jnp.broadcast_to(offset, (n_pad,)),
                kv_valid=kv_valid, score_shards=score_shards,
                lora=lora, lora_alpha=self.lora_alpha, lora_indices=lora_idx,
            )
            rel = lengths - 1 - offset  # last-token position, chunk-relative
            in_chunk = (rel >= 0) & (rel < chunk)
            gathered = jnp.take_along_axis(
                logits, jnp.clip(rel, 0, chunk - 1)[:, None, None].astype(jnp.int32),
                axis=1,
            )[:, 0, :]
            last_logits = jnp.where(in_chunk[:, None], gathered, last_logits)
            return mini, last_logits

        if self.mesh is None:
            return jax.jit(chunk_fn)
        # mesh: same layout as the one-shot prefill programs — rows shard
        # over the data axes (dp-aware admission pads the bucket), the
        # mini cache shards like the big cache (batch over dp, heads over
        # tp), and the chunk offset is a replicated scalar
        s = self._shardings
        rows, vec = self._prefill_shardings(n_pad)
        return jax.jit(
            chunk_fn,
            in_shardings=(
                self._param_shardings, s["cache"], rows, vec,
                s["repl"], rows, s["repl"], vec,
            ),
            out_shardings=(s["cache"], rows),
        )

    def _make_finish_fn(self, n_pad: int, t_pad: int, guided: bool = False):
        """Scatter the completed mini cache into the big cache / pages and
        sample each row's first token from the carried last logits (masked
        by the automaton start-state rows for guided waves)."""
        jax, jnp = self._jax, self._jnp

        def sample_first(last_logits, rng, temp, top_p, gtables, gaut):
            if guided:
                row = gtables[gaut, jnp.zeros_like(gaut)]
                last_logits = jnp.where(row >= 0, last_logits, -jnp.inf)
            first_tokens, rng = self.sample(last_logits, rng, temp, top_p)
            if guided:
                first_state = jnp.take_along_axis(
                    row, first_tokens[:, None], axis=1
                )[:, 0]
                return first_tokens, rng, (jnp.maximum(first_state, 0),)
            return first_tokens, rng, ()

        if self.paged:
            def finish_fn(paged, mini, lengths, row_tables, last_logits,
                          rng, temp, top_p, gtables=None, gaut=None):
                from ..ops.paged_attention import PagedKVCache, write_tokens

                zero = jnp.zeros((n_pad,), jnp.int32)
                scatter = jax.vmap(write_tokens, in_axes=(0, None, 0, None, None))
                k_pages = scatter(paged.k_pages, row_tables, mini.k, zero, lengths)
                v_pages = scatter(paged.v_pages, row_tables, mini.v, zero, lengths)
                first_tokens, rng, extra = sample_first(
                    last_logits, rng, temp, top_p, gtables, gaut
                )
                return (
                    PagedKVCache(
                        k_pages=k_pages, v_pages=v_pages,
                        page_table=paged.page_table, lengths=paged.lengths,
                    ),
                    first_tokens, rng, *extra,
                )
        else:
            def finish_fn(cache, mini, lengths, slot_ids, last_logits,
                          rng, temp, top_p, gtables=None, gaut=None):
                k = cache.k.at[:, slot_ids, :t_pad].set(mini.k.astype(cache.k.dtype))
                v = cache.v.at[:, slot_ids, :t_pad].set(mini.v.astype(cache.v.dtype))
                first_tokens, rng, extra = sample_first(
                    last_logits, rng, temp, top_p, gtables, gaut
                )
                return KVCache(k=k, v=v), first_tokens, rng, *extra

        if self.mesh is None:
            return jax.jit(finish_fn)
        s = self._shardings
        rows, vec = self._prefill_shardings(n_pad)
        if self.paged:
            # (paged, mini, lengths, row_tables, last_logits, rng, temp, top_p)
            in_shardings = (
                s["paged"], s["cache"], vec, rows, rows,
                s["repl"], vec, vec,
            )
            out_shardings = (s["paged"], vec, s["repl"])
        else:
            # (cache, mini, lengths, slot_ids, last_logits, rng, temp, top_p)
            in_shardings = (
                s["cache"], s["cache"], vec, vec, rows,
                s["repl"], vec, vec,
            )
            out_shardings = (s["cache"], vec, s["repl"])
        if guided:
            in_shardings += (s["repl"], vec)
            out_shardings += (vec,)
        return jax.jit(
            finish_fn, in_shardings=in_shardings, out_shardings=out_shardings
        )
