"""The mixed-phase dispatch program: ONE compiled step for the whole
ragged wave.

The scheduler packs every row's work for a step — one token per decode
row, up to ``chunk`` prompt tokens per prefill row — into a FLAT token
axis of static length ``t_budget`` (right-padded with trash tokens), so
the transformer trunk (projections, MLP, norms: all per-token) runs at
exactly the wave's token count regardless of how it splits between
phases.  Attention is the only op that needs row structure: the flat
q tokens are re-packed per row into ``[B, chunk]`` and handed to the
ragged paged-attention kernel (``ops/ragged_attention.py``), whose
causal mask makes a decode row the ``q_count == 1`` special case of a
prefill chunk and whose work follows each row's ``q_count``: a slot the
step gives no token costs it nothing, and what it leaves unwritten there
is masked off when the rows are gathered back.  KV for the step is
scattered into the paged cache BEFORE attention, so the kernel is a pure
page read.

The stacked KV pools ``[L, pages, page, KH, D]`` ride the layer loop's
CARRY, whole, beside the residual stream (and a recurrent model's state
pools): a layer scatters its step's rows at ``(layer, page, slot)`` and
the kernel fetches its pages from ``(layer, page)``, so the donated pool
is updated in place and held once — no layer's slice is cut out, none is
stacked back, and no step copies the pool.  A model that runs its stack
``passes`` times a token (``config.total_ut_steps``: models/ouro.py) has
``passes x L`` planes, and the layer loop runs inside a loop over passes:
pass ``t``'s layer ``l`` writes and reads plane ``t * L + l``, the final
norm ends every pass, and the carry goes through both loops.

Exactly ONE program compiles per engine (static ``t_budget`` / ``chunk``
/ ``max_slots``): there is no bucket grid to warm, no per-shape compile
to hit mid-run — the property the warmup-grid machinery exists to
approximate for the wave engine, the mixed program has by construction.

Unsupported here: guided decoding and LoRA adapters are refused at
submit (``ServingEngine.generate`` + ``Scheduler.enqueue``), and a
serving mesh with ``sched_mode=continuous`` is a start-up error
(``build_serving_engine``): the program has no sharded path.  Prefix
reuse is the scheduler's block-hash cache (serving/kvstore.py): a hit
maps the cached pages into the row's table and its first chunk starts at
``cached_len`` — to this program just a row whose ``kv_len`` runs ahead
of its ``q_count``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

from ...models import family_of
from ...models.llama import apply_rope, rms_norm, rope_frequencies

__all__ = ["StepView", "make_mixed_fn"]


@dataclasses.dataclass
class StepView:
    """What a family's layer body (``models.family_of(config).mixed_layer``)
    is given of one dispatch: the flat tokens' packing and ``attend``, the
    attention every family shares.  The body is a ``lax.scan`` step over
    ``{"w": the layer's weights, "layer": its index}`` (for a family with
    ``scan_steps``, a period's index, its weights read from ``stacks``)
    whose carry is
    ``(x, pools, recurrent)``: the residual stream, the KV pools (through
    ``attend``) and the family's recurrent pools, or ``None``."""

    t_budget: int
    chunk: int
    rows: Any  # [T] owning slot per flat token
    in_row: Any  # [T] index within the row's tokens of this step
    pos: Any  # [T] absolute positions
    valid: Any  # [T] live mask
    q_start: Any  # [S] flat offset of the slot's first token
    q_count: Any  # [S] the slot's tokens this step
    #: ``attend(q, k, v, pools, layer) -> (attn [1, T, QH * D], pools)``
    #: with ``pools = {"k", "v"}`` the WHOLE stacked KV pools
    #: ``[planes, pages, page, KH, D]`` off the layer loop's carry: RoPE,
    #: the step's K/V into plane ``layer``'s pages in place, the ragged
    #: kernel over that plane, the rows back on the flat axis.  ``layer``
    #: is the scanned index the body was given: the layer, or for a model
    #: of several passes ``pass * L + layer``.  The pools it returns go
    #: back into the carry
    attend: Callable
    #: the layer leaves the loop does NOT scan (the family's
    #: ``WHOLE_STACKS``, e.g. the expert stacks of models/sdar.py), whole:
    #: the body indexes them with ``scanned["layer"]``.  Empty for a family
    #: that names none
    stacks: Any = None
    #: a model with a LATENT cache (models/deepseek_v2.py): ``attend_latent(
    #: q_lat [T, H, R], q_rope [T, H, dr], c [T, R], k_rope [T, dr], pools,
    #: plane) -> (o_lat [T, H, R], pools)``, the queries and the rotary key
    #: rotated already: the step's rows into plane ``plane`` of the latent
    #: pool (``pools = {"k": latent, "v": None, "rope": rope}``) in place,
    #: the latent attention kernel over it, the outputs on the flat axis
    #: (zero at padding tokens).  None for every other model
    attend_latent: Any = None


def make_mixed_fn(runtime: Any, t_budget: int, chunk: int,
                  spec_width: int = 1):
    """Compile the mixed-step program for ``runtime`` (serving/runtime.py:
    its ``config``, ``max_slots`` and ``sample``).

    Signature of the returned jitted function::

        fn(params, paged, ids, rows, pos, valid, in_row,
           q_start, q_count, kv_len, latest, from_prev,
           sample_start, spec_len, rng, temp, top_p, denoise=None)
        -> (new_paged, toks [B, W], accept [B], latest_out [B], rng)

    Flat inputs (length ``t_budget``): ``ids`` token ids, ``rows`` the
    owning slot per token, ``pos`` absolute positions, ``valid`` live
    mask (padding tokens write to the trash page), ``in_row`` each
    token's index within its row's chunk, ``from_prev`` tokens whose id
    is the PREVIOUS dispatch's on-device sample for that slot (decode-
    ahead chaining: the host dispatched this step before the last step's
    token ever crossed to it, so the program substitutes its own carried
    ``latest`` buffer).  Per-slot inputs (length ``max_slots``):
    ``q_start`` the flat offset of the slot's first token, ``q_count``
    its token count this step (0 = not scheduled), ``kv_len`` the pages'
    valid length AFTER this step's writes assuming every draft is
    accepted (rows not scheduled keep their current length),
    ``sample_start`` the flat offset of the slot's first SAMPLED
    position, ``spec_len`` the slot's draft-token count this step
    (0 = plain row).

    Up to ``W = spec_width`` positions are sampled per slot, starting at
    ``sample_start``: a plain row samples only its last valid logit
    (``toks[b, 0]``); a speculation verify row of ``q_count = 1 + k``
    tokens (committed last token + k prompt-lookup drafts) samples ALL
    ``k + 1`` of them, and ``accept[b]`` is the length of the longest
    draft prefix the samples confirm — standard speculative-decoding
    acceptance, so the commit takes ``accept[b] + 1`` tokens
    (``toks[b, :accept[b] + 1]``) and greedy output is byte-identical to
    one-token decoding by construction.  The head and the sampler cost
    rows x vocabulary, so the step's tail runs at the width THIS step's
    drafts need: when ``W > 1`` it is one ``lax.cond`` on
    ``spec_len.any()`` — no draft anywhere, one logit row a slot
    (``toks[:, 1:]`` zero, ``accept`` zero); a draft somewhere, ``W`` rows
    a slot.  Both branches advance the rng once.  The returned cache's
    lengths are corrected on device to ``kv_len - (spec_len - accept)``:
    the rejected drafts' KV writes land but are never readable.
    ``latest_out[b]`` carries each slot's freshest sampled token for the
    next dispatch's chaining (passthrough when the slot sat this step
    out).

    **A model that denoises blocks** (``config.block_length``, models/
    sdar.py) has another tail and no draft.  A generating row's step runs
    the ``N = block_length`` positions of its current block (after, in a
    block's first step, the ``N`` clean positions of the block before,
    which only write that block's final keys) under the block-causal mask;
    ``sample_start`` is the flat offset of the block's first position.
    The carried buffer is ``latest [B, N]``, the slot's block as the last
    step left it, and a ``from_prev`` token takes ``latest[slot, pos %
    N]``.  ``denoise`` (None for every other model) holds per slot ``keep``
    (positions this step keeps; 0 = no block row), ``limit`` (positions of
    the block at or past it are never kept: they lie past ``max_tokens``)
    and ``low_confidence`` (the row's remask rule).  The tail samples all
    ``N`` positions a slot, always (no conditional), with each token's
    confidence (``runtime.sample_confident``; the mask id's logit is taken
    out first, so no position is ever denoised into a mask), and keeps
    ``keep`` of the positions that still hold the mask id: the most
    confident, or under the sequential rule the leftmost, ties to the
    left.  ``toks [B, N]`` is the block after the step, ``accept [B]`` the
    bits of the positions this step kept, and ``latest_out`` the block of
    every slot that had a block row.

    A model with experts (``config.num_experts``: models/sdar.py,
    models/deepseek_v2.py), whichever tail it has, returns a sixth value,
    ``[2]``: experts given a token, summed over the loop's layers
    (``StepRecord.moe_experts_hit``), and the fullest expert's tokens, the
    largest over them (``moe_assign_max``).

    **A model with a latent cache** (``config.latent_dims``: models/
    deepseek_v2.py) has ``paged.rope_pages`` beside ``paged.k_pages`` and
    no ``v_pages``; its layers attend through ``StepView.attend_latent``
    (``ops/latent_attention.py``), and the layers its family runs ahead of
    the loop (``mixed_lead``: the dense layers) take the carry first.
    """
    jax, jnp = runtime._jax, runtime._jnp
    config = runtime.config
    b_slots = runtime.max_slots
    # a model without positional rotation (``rope_theta`` None:
    # models/olmo_hybrid.py) is traced without it: decided here, statically
    rope = config.rope_theta is not None
    inv_freq = rope_frequencies(config) if rope else None
    lax = jax.lax
    width = max(1, int(spec_width))
    # muP models scale the embedding and the logits (models/falcon_h1.py)
    embedding_multiplier = float(getattr(config, "embedding_multiplier", 1.0))
    lm_head_multiplier = float(getattr(config, "lm_head_multiplier", 1.0))
    # how many times a token takes the layer stack (models/ouro.py); the
    # exit gate of such a model is not evaluated here: every row takes
    # every pass
    passes = int(getattr(config, "total_ut_steps", 1))
    # a family may carry the residual stream in another dtype than its
    # parameters' (models/ouro.py: float32 through 384 additions a token)
    family = family_of(config)
    stream_dtype = getattr(family, "STREAM_DTYPE", None)
    # leaves of the layers the loop does not scan (a family's expert stacks,
    # or every leaf of a family whose body reads its layers itself)
    whole = tuple(getattr(family, "WHOLE_STACKS", ()))
    # steps of the layer loop: one a layer, or one a PERIOD for a family
    # whose stack repeats a period of layers of several kinds
    # (models/olmo_hybrid.py: its body is a period, and it maps a step's
    # index to its layers' weights, planes and states itself)
    steps = family.scan_steps(config) if hasattr(family, "scan_steps") else config.num_layers
    # positions of a block a model denoises a step (models/sdar.py); 0 for
    # a model that commits one token a row
    block = int(getattr(config, "block_length", 0))
    assert not (block and width > 1), "a denoising row carries no draft"
    # a model with experts brings two expert counters back beside its tokens
    experts = bool(getattr(config, "num_experts", 0))
    # layers a family runs ahead of its loop, each with leaves of its own
    # (models/deepseek_v2.py: the dense layers before the expert ones)
    lead = getattr(family, "mixed_lead", None)

    def mixed_fn(params, paged, ids, rows, pos, valid, in_row,
                 q_start, q_count, kv_len, latest, from_prev,
                 sample_start, spec_len, rng, temp, top_p, denoise=None):
        from ...ops.ragged_attention import ragged_paged_attention, write_rows

        page_size = paged.page_size
        # decode-ahead chaining: a token flagged from_prev takes its id
        # from the carried per-slot latest-sample buffer instead of the
        # host-packed placeholder — the sampled id never visits the host
        if block:
            # the carry is each slot's block as its last step left it
            eff_ids = jnp.where(from_prev, latest[rows, pos % block], ids)
        else:
            eff_ids = jnp.where(from_prev, latest[rows], ids)
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], eff_ids, axis=0)[None]  # [1, T, H]
            if embedding_multiplier != 1.0:
                x = (x.astype(jnp.float32) * embedding_multiplier).astype(x.dtype)
            if stream_dtype is not None:
                x = x.astype(stream_dtype)
        positions = pos[None]  # [1, T]
        # flat -> per-row packing indices for the attention re-pack
        pack_idx = jnp.clip(
            q_start[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None, :],
            0, t_budget - 1,
        )  # [B, chunk]
        # per-token page/slot targets (invalid tokens -> trash page 0)
        page_ids = jnp.where(
            valid, paged.page_table[rows, pos // page_size], 0
        )
        page_slots = jnp.where(valid, pos % page_size, 0)

        def attend(q, k, v, pools, layer):
            dtype = q.dtype  # the projections': what comes back
            q = q.reshape(1, t_budget, config.num_heads, config.head_dim)
            k = k.reshape(1, t_budget, config.num_kv_heads, config.head_dim)
            v = v.reshape(1, t_budget, config.num_kv_heads, config.head_dim)
            if rope:
                q = apply_rope(q, positions, inv_freq)
                k = apply_rope(k, positions, inv_freq)
            # scatter this step's K/V into the layer's pages FIRST — the
            # ragged kernel then reads a cache that already holds every
            # token a causal query may attend to (its own included).  The
            # pools are the loop's carry: the rows land in place
            with jax.named_scope("kv_write"):
                k_pages = write_rows(pools["k"], layer, page_ids, page_slots, k[0])
                v_pages = write_rows(pools["v"], layer, page_ids, page_slots, v[0])
            with jax.named_scope("attn"):
                q_pack = q[0][pack_idx]  # [B, chunk, QH, D]
                attn_pack = ragged_paged_attention(
                    q_pack.astype(k_pages.dtype), k_pages, v_pages,
                    paged.page_table, kv_len, q_count, layer,
                    sliding_window=config.sliding_window,
                    attend_block=max(1, block), kv_heads=config.num_kv_heads,
                )
                # back to flat [T, QH, D].  The kernel leaves what it was
                # not asked for unwritten (idle slots, rows past q_count):
                # a padding token would gather whatever the buffer held
                attn = jnp.where(
                    valid[:, None, None], attn_pack[rows, in_row], 0
                )
            return (
                attn.astype(dtype).reshape(1, t_budget, -1),
                {"k": k_pages, "v": v_pages},
            )

        def attend_latent(q_lat, q_rope, c, k_rope, pools, plane):
            from ...ops.latent_attention import latent_attention, write_latent

            with jax.named_scope("kv_write"):
                latent, rope_rows = write_latent(
                    pools["k"], pools["rope"], plane, page_ids, page_slots, c, k_rope,
                    rows, pos, valid,
                )
            with jax.named_scope("latent_attn"):
                o_lat = latent_attention(
                    q_lat.astype(latent.dtype), q_rope, latent, rope_rows,
                    paged.page_table, kv_len, q_start, q_count, plane,
                    scale=config.softmax_scale, chunk=chunk,
                )
                # the kernel leaves tokens of no live slot unwritten
                o_lat = jnp.where(valid[:, None, None], o_lat, 0)
            return o_lat, {**pools, "k": latent, "rope": rope_rows}

        # what differs by family is the layer body; the model's config
        # selects it, no option does
        view = StepView(
            t_budget=t_budget, chunk=chunk, rows=rows, in_row=in_row, pos=pos,
            valid=valid, q_start=q_start, q_count=q_count, attend=attend,
            stacks={name: params["layers"][name] for name in whole},
            attend_latent=attend_latent if paged.rope_pages is not None else None,
        )
        layer_step = family.mixed_layer(config, view)
        # the pools ride the layer loop's carry WHOLE, beside the residual
        # stream: the KV pools every model has, and the recurrent pools of
        # a model with state-space layers (updated in place,
        # ops/ssm_scan.py).  A layer gets its weights and its index;
        # nothing slices a layer's pages out of a pool or stacks them back
        pools = {"k": paged.k_pages, "v": paged.v_pages}
        if paged.rope_pages is not None:
            pools["rope"] = paged.rope_pages
        recurrent = None
        if paged.ssm_state is not None:
            recurrent = {"ssm": paged.ssm_state, "conv": paged.conv_state}
        if lead is not None:
            x, pools, recurrent = lead(config, view, (x, pools, recurrent))

        def one_pass(carry, planes):
            """The layer stack once, over the pool planes ``planes [L]``,
            and the norm that ends a pass: the head's input after the
            last pass, the next pass's after any other."""
            scanned = {k: v for k, v in params["layers"].items() if k not in whole}
            (x, pools, recurrent), per_layer = lax.scan(
                layer_step, carry, {"w": scanned, "layer": planes},
            )
            with jax.named_scope("pass_norm"):
                x = rms_norm(x, params["ln_final"], config.rms_norm_eps)
            return (x, pools, recurrent), per_layer

        # a model whose stack runs several times a token (models/ouro.py)
        # has a plane of the pool for every pass and layer, pass-major: the
        # same weights, each pass writing and reading its own planes.  The
        # pools stay in the carry through both loops.  One pass is the
        # plain layer loop, under no second loop
        planes = jnp.arange(passes * steps, dtype=jnp.int32)
        if passes == 1:
            (x, pools, recurrent), per_layer = one_pass((x, pools, recurrent), planes)
        else:
            (x, pools, recurrent), per_layer = lax.scan(
                one_pass, (x, pools, recurrent), planes.reshape(passes, steps),
            )

        def logits_at(samp_idx):
            """The logit rows of the flat positions ``samp_idx [B,
            rows_a_slot]``.  Only the sampled positions get logit rows:
            they are gathered before the head matmul, so the [vocab]
            projection runs at [B * rows_a_slot], not [T]"""
            with jax.named_scope("head"):
                head = (
                    params["embed"].T if config.tie_embeddings
                    else params["lm_head"]
                )
                # [B, rows_a_slot, H], in the head's dtype whatever the
                # stream's
                x_samp = x[0][samp_idx].astype(head.dtype)
                logits = jnp.einsum(
                    "bwh,hv->bwv", x_samp, head,
                    preferred_element_type=jnp.float32,
                )
                if lm_head_multiplier != 1.0:
                    logits = logits * lm_head_multiplier
            return logits

        def sample_at(rows_a_slot, rng):
            """``rows_a_slot`` positions a slot from ``sample_start`` on,
            through the head and the sampler: (toks [B, rows_a_slot], rng)"""
            samp_idx = jnp.clip(
                sample_start[:, None]
                + jnp.arange(rows_a_slot, dtype=jnp.int32)[None],
                0, t_budget - 1,
            )  # [B, rows_a_slot]
            logits = logits_at(samp_idx)
            with jax.named_scope("sample"):
                flat_toks, rng = runtime.sample(
                    logits.reshape(b_slots * rows_a_slot, -1), rng,
                    jnp.repeat(temp, rows_a_slot),
                    jnp.repeat(top_p, rows_a_slot),
                )
            return flat_toks.reshape(b_slots, rows_a_slot), rng

        def narrow(rng):
            # no row carries a draft: a plain row samples only its last
            # valid logit, so one row a slot is all the step needs
            toks, rng = sample_at(1, rng)
            if width > 1:
                toks = jnp.pad(toks, ((0, 0), (0, width - 1)))
            return toks, jnp.zeros((b_slots,), jnp.int32), rng

        def wide(rng):
            # a verify row samples its committed token AND every draft,
            # in chunk order; every slot pays the row's width
            toks, rng = sample_at(width, rng)
            # longest matching draft prefix: draft j (flat position
            # sample_start + 1 + j) is confirmed iff the sample AT the
            # position BEFORE it predicted exactly it, and every earlier
            # draft was confirmed (cumprod)
            draft_idx = jnp.clip(
                sample_start[:, None] + 1
                + jnp.arange(width - 1, dtype=jnp.int32)[None],
                0, t_budget - 1,
            )  # [B, W-1]
            drafts = eff_ids[draft_idx]
            confirmed = (toks[:, : width - 1] == drafts) & (
                jnp.arange(width - 1, dtype=jnp.int32)[None]
                < spec_len[:, None]
            )
            accept = jnp.sum(
                jnp.cumprod(confirmed.astype(jnp.int32), axis=1), axis=1
            )
            return toks, accept, rng

        def denoise_block(rng):
            """The tail of a model that denoises blocks: every slot's
            ``block`` positions through the head and the sampler, and of
            those that hold the mask id the ``keep`` best kept."""
            place = jnp.arange(block, dtype=jnp.int32)[None]  # [1, N]
            samp_idx = jnp.clip(sample_start[:, None] + place, 0, t_budget - 1)
            state = eff_ids[samp_idx]  # [B, N]: the block as the step found it
            # a position is never denoised into the mask id
            logits = logits_at(samp_idx).at[..., config.mask_token_id].set(-jnp.inf)
            with jax.named_scope("sample"):
                flat_toks, flat_conf, rng = runtime.sample_confident(
                    logits.reshape(b_slots * block, -1), rng,
                    jnp.repeat(temp, block), jnp.repeat(top_p, block),
                )
            with jax.named_scope("unmask"):
                drawn = flat_toks.reshape(b_slots, block)
                conf = flat_conf.reshape(b_slots, block)
                open_ = (state == config.mask_token_id) & (
                    place < denoise["limit"][:, None]
                )
                score = jnp.where(
                    denoise["low_confidence"][:, None], conf,
                    -place.astype(jnp.float32),
                )
                score = jnp.where(open_, score, -jnp.inf)
                # ahead[b, j, i]: position i is kept before position j
                ahead = (score[:, None, :] > score[:, :, None]) | (
                    (score[:, None, :] == score[:, :, None])
                    & (place[:, None, :] < place[:, :, None])
                )
                rank = jnp.sum(ahead, axis=-1, dtype=jnp.int32)
                kept = open_ & (rank < denoise["keep"][:, None])
                after = jnp.where(kept, drawn, state)
                bits = jnp.sum(kept.astype(jnp.int32) << place, axis=-1)
            return after, bits, rng

        def expert_stats():
            """``[2]``: experts given a token summed over the layers, the
            fullest expert's tokens the largest over them, from the
            loop's ``[layers, E]`` counts"""
            return jnp.stack([
                jnp.sum(per_layer > 0, dtype=jnp.int32),
                jnp.max(per_layer).astype(jnp.int32),
            ])

        if block:
            toks, accept, rng = denoise_block(rng)
            block_row = denoise["keep"] > 0
            new_paged = dataclasses.replace(
                paged, k_pages=pools["k"], v_pages=pools["v"], lengths=kv_len,
            )
            return (
                new_paged, toks, accept,
                jnp.where(block_row[:, None], toks, latest), rng, expert_stats(),
            )
        if width > 1:
            # the head and the sampler cost rows x vocabulary: they run
            # at the width THIS step's drafts need.  The branches take
            # the normed x, the head and the per-slot vectors; the pools
            # stay outside, in the carry they left the layer loop in
            toks, accept, rng = lax.cond(
                jnp.any(spec_len > 0), wide, narrow, rng
            )
        else:
            toks, accept, rng = narrow(rng)
        # rejected drafts wrote KV the row must never read again: shrink
        # the committed lengths on device (spec_len - accept positions)
        new_lengths = kv_len - (spec_len - accept)
        # per-slot freshest sample for the next dispatch's chaining:
        # toks[b, accept[b]] is the last ACCEPTED token (== toks[b, 0]
        # for plain rows); slots that sat out keep their carried value
        fresh = jnp.take_along_axis(
            toks, jnp.clip(accept, 0, width - 1)[:, None], axis=1
        )[:, 0]
        latest_out = jnp.where(q_count > 0, fresh, latest)
        new_paged = dataclasses.replace(
            paged, k_pages=pools["k"], v_pages=pools["v"],
            lengths=new_lengths,
        )
        if recurrent is not None:
            new_paged = dataclasses.replace(
                new_paged, ssm_state=recurrent["ssm"], conv_state=recurrent["conv"],
            )
        if "rope" in pools:
            new_paged = dataclasses.replace(new_paged, rope_pages=pools["rope"])
        if experts:
            return new_paged, toks, accept, latest_out, rng, expert_stats()
        return new_paged, toks, accept, latest_out, rng

    assert b_slots <= t_budget, (b_slots, t_budget)
    return jax.jit(mixed_fn, donate_argnums=(1,))
