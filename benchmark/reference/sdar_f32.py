"""The plain reference for SDAR (``model_type: sdar_moe``): a sparse-expert
decoder under a block-causal mask that generates by denoising blocks of
positions -- in float32.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision(
"highest")``: no kernel, no cache, no page, no batching of requests into a
step, nothing of the program imported.  It holds its weights as the
recipe's int8 groups (``sdar_f32_weights.make``: 7.5 GB at 12 layers of
128 experts) and widens what it multiplies: a layer's attention matrices,
and ONE expert's three matrices at a time -- all of the model in float32
is 30 GB.  An expert is computed for the tokens routed to it, gathered by
plain index; no product runs over all 128.

The layer, sizes from the configuration's ``architecture`` group, ``x``
the stream, ``N`` an RMSNorm (``rms_norm_eps``), ``B = block_length``::

    a = N(x; ln_attn);  q, k, v = a Wq, a Wk, a Wv                (no bias)
    q = N(q; q_norm),  k = N(k; k_norm)     per head, over head_dim
    RoPE (half rotation, rope_theta) on q and k; softmax at head_dim^-0.5,
    num_attention_heads / num_key_value_heads queries a KV head, over the
    keys j with  j // B <= i // B
    x = x + attn Wo
    m = N(x; ln_mlp);  p = softmax(m Wr) over all num_experts
    the num_experts_per_tok largest p_e, divided by their sum
    x = x + sum_e p_e (silu(m Wg_e) * (m Wu_e)) Wd_e
    logits = N(x; ln_final) W_head;  position i's logits predict position i

**What a served answer is compared with.**  The harness hands over
``(prompt ids, served ids)`` of requests sent with ``remask:
"sequential"`` and the ``generation`` group's ``denoise_steps`` ``T``, and
this file rebuilds what that schedule saw, a function of the prompt's
length ``P``, the answer's length ``M``, ``B`` and ``T`` alone.  The
sequence is cut in blocks of ``B`` by absolute position; the prompt's
whole blocks are context, its tail (``P mod B`` tokens) opens the first
generated block already clean.  A generated block's masked positions
(those below ``P + M``) are unmasked left to right, ``B / T`` a step, so
in its step ``s`` the block held clean ids left of ``clean + s B / T``
and ``mask_token_id`` from there on (past ``P + M`` always).  The
reference runs ``1 + T`` streams over the whole sequence, layer by layer
side by side: the CLEAN stream (every block as it ended; the keys and
values every later block saw) and, for each ``s``, the stream of every
block in its step-``s`` state, whose queries see the clean stream's keys
in earlier blocks and their own stream's in their own block.  A served
token's gap -- the position's largest logit, the mask id's left out (the
program never denoises a position into a mask), less the served token's --
is read in the stream of the step that kept it.

Departures from the published description, each of them the
configuration's ``assumed``: the blocks' length and the schedule are not
keys of the published config (the family's ``generate.py`` takes them as
arguments; its default threshold rule is not what is served); a block's
final keys come of one clean forward over the whole sequence (the family
runs one more forward a finished block; the same arithmetic under the
block-causal mask); every sequence is right-padded with mask ids to one
power of two, which no scored position can see; the QK norms are the
Qwen3-MoE layer's, the catalog's config having no key for them.

**The interface** is ``decoder_f32``'s (``benchmark/README.md``, "A
reference"): ``WEIGHTS``, ``greedy_gaps(config_doc, weights, sequences)``
and ``control_gaps`` (the attention matrices and the expert stacks at int4
in place of int8); ``logits``, ``step_logits`` and ``kept_positions``
(the confidence ranking of ``remask: "low_confidence"``, which no run can
judge: the harness hands over no order) serve the tests.
"""

from __future__ import annotations

import functools
import importlib
from typing import Any

from . import decoder_f32
from .sdar_f32_weights import ATTENTION, EXPERTS, widen

#: the weights module beside this file
WEIGHTS = "sdar_f32_weights"


def schedule(prompt_len: int, answer_len: int, block: int, steps: int) -> dict:
    """What the sequential schedule fixes for one request: ``prefill`` (the
    prompt's whole blocks), ``length`` (the sequence's, whole blocks), and
    for every answer position ``(step, position)``: the step that keeps it
    and where it lies."""
    per_step = block // steps
    prefill = prompt_len - prompt_len % block
    end = prompt_len + answer_len
    kept = []
    for position in range(prompt_len, end):
        start = position - position % block
        clean = prompt_len - start if start == prefill else 0
        kept.append(((position - start - clean) // per_step, position))
    return {"prefill": prefill, "length": -(-end // block) * block, "kept": kept}


def stream_ids(prompt: list, served: list, block: int, steps: int, mask: int, padded: int) -> list:
    """The ``1 + steps`` streams of one request, each ``padded`` ids: the
    clean one, then every generated block in its step-``s`` state."""
    plan = schedule(len(prompt), len(served), block, steps)
    clean = list(prompt) + list(served)
    clean += [mask] * (padded - len(clean))
    out = [clean]
    for s in range(steps):
        ids = list(clean)
        for step, position in plan["kept"]:
            if step >= s:
                ids[position] = mask
        out.append(ids)
    return out


@functools.lru_cache(maxsize=None)
def _attention_fn(heads: int, kv_heads: int, head_dim: int, eps: float, theta: float,
                  block: int, padded: int) -> Any:
    """The jitted attention half of a layer for one geometry: ``x [rows,
    padded, hidden]``, ``clean [rows]`` the row whose keys and values a
    row's queries see in earlier blocks (itself, for a clean stream)."""
    import jax
    import jax.numpy as jnp

    def rms_norm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale

    def rope(x, cos, sin):  # [T, heads, D]
        half = head_dim // 2
        x1, x2 = x[..., :half], x[..., half:]
        c, s = cos[:, None, :], sin[:, None, :]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)

    @jax.jit
    def attention(x, clean, w):
        with jax.default_matmul_precision("highest"):
            exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
            angles = (
                jnp.arange(padded, dtype=jnp.float32)[:, None]
                * (1.0 / (theta ** exponents))[None, :]
            )
            cos, sin = jnp.cos(angles), jnp.sin(angles)
            a = rms_norm(x, w["ln_attn"])
            rows = x.shape[0]
            q = rms_norm((a @ w["wq"]).reshape(rows, padded, heads, head_dim), w["q_norm"])
            k = rms_norm((a @ w["wk"]).reshape(rows, padded, kv_heads, head_dim), w["k_norm"])
            v = (a @ w["wv"]).reshape(rows, padded, kv_heads, head_dim)
            q = jax.vmap(lambda one: rope(one, cos, sin))(q)
            k = jax.vmap(lambda one: rope(one, cos, sin))(k)
            k = jnp.repeat(k, heads // kv_heads, axis=2)
            v = jnp.repeat(v, heads // kv_heads, axis=2)
            blocks = jnp.arange(padded) // block
            own = blocks[None, :] == blocks[:, None]  # [query, key]
            earlier = blocks[None, :] < blocks[:, None]

            def one_row(args):
                q_r, k_r, v_r, k_c, v_c = args
                scale = head_dim ** -0.5
                s_own = jnp.einsum("thd,shd->hts", q_r, k_r) * scale
                s_clean = jnp.einsum("thd,shd->hts", q_r, k_c) * scale
                scores = jnp.where(
                    own[None], s_own, jnp.where(earlier[None], s_clean, -jnp.inf)
                )
                probs = jax.nn.softmax(scores, axis=-1)
                out = jnp.einsum("hts,shd->thd", jnp.where(own[None], probs, 0.0), v_r)
                out += jnp.einsum("hts,shd->thd", jnp.where(earlier[None], probs, 0.0), v_c)
                return out.reshape(padded, heads * head_dim)

            attn = jax.lax.map(one_row, (q, k, v, k[clean], v[clean]))
            x = x + attn @ w["wo"]
            return x, rms_norm(x, w["ln_mlp"])

    return attention, rms_norm


@functools.lru_cache(maxsize=None)
def _expert_fn() -> Any:
    import jax

    @functools.partial(jax.jit, donate_argnums=(0,))
    def expert(out, m, index, gate, w_gate, w_up, w_down, layer, e):
        """``out`` with one expert's part added: the rows ``index`` of ``m``
        through the expert's gated MLP, times their gates.  The three
        matrices are widened here from the stacks as they are stored, and
        the layer and the expert are operands, not constants: one program
        a count of rows, for every expert of every layer."""
        with jax.default_matmul_precision("highest"):
            wg, wu, wd = (widen(w, layer, e) for w in (w_gate, w_up, w_down))
            rows = m[index]
            part = ((jax.nn.silu(rows @ wg) * (rows @ wu)) @ wd) * gate[:, None]
        return out.at[index].add(part)

    return expert


@functools.lru_cache(maxsize=None)
def _router_fn(top: int, norm: bool) -> Any:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def router(m, w_router, layer):
        with jax.default_matmul_precision("highest"):
            probs = jax.nn.softmax(m @ widen(w_router, layer), axis=-1)
        gates, chosen = jax.lax.top_k(probs, top)
        if norm:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        return gates, chosen

    return router


def _experts(weights: Any, arch: dict, layer: int, m: Any) -> Any:
    """The sparse-expert half of a layer on ``m [tokens, hidden]`` (normed
    already): the router's float32 softmax over all experts, the largest
    ``num_experts_per_tok`` divided by their sum, and each expert computed
    for the tokens routed to it."""
    import jax.numpy as jnp
    import numpy as np

    layers = weights.layers
    at = jnp.asarray(layer, jnp.int32)
    gates, chosen = _router_fn(int(arch["num_experts_per_tok"]), bool(arch["norm_topk_prob"]))(
        m, layers["w_router"], at
    )
    chosen, gates_host = np.asarray(chosen), np.asarray(gates)
    out = jnp.zeros_like(m)
    expert = _expert_fn()
    for e in range(int(arch["num_experts"])):
        tokens, part = np.nonzero(chosen == e)
        if not len(tokens):
            continue
        # few shapes to compile: the rows padded to a power of two, 64 at
        # least, with token 0 at gate 0
        size = 64
        while size < len(tokens):
            size *= 2
        index = np.zeros(size, np.int32)
        index[: len(tokens)] = tokens
        gate = np.zeros(size, np.float32)
        gate[: len(tokens)] = gates_host[tokens, part]
        out = expert(
            out, m, jnp.asarray(index), jnp.asarray(gate),
            *(layers[name] for name in EXPERTS), at, jnp.asarray(e, jnp.int32),
        )
    return out


def hidden_states(weights: Any, arch: dict, ids: Any, clean: Any) -> Any:
    """The final-normed stream ``[rows, padded, hidden]`` of ``ids [rows,
    padded]``, row ``r``'s queries seeing row ``clean[r]``'s keys in
    earlier blocks and their own row's in their own."""
    import jax.numpy as jnp

    rows, padded = ids.shape
    attention, rms_norm = _attention_fn(
        int(arch["num_attention_heads"]), int(arch["num_key_value_heads"]),
        int(arch["head_dim"]), float(arch["rms_norm_eps"]), float(arch["rope_theta"]),
        int(arch["block_length"]), padded,
    )
    x = jnp.take(weights.embed, ids, axis=0).astype(jnp.float32)
    clean = jnp.asarray(clean, jnp.int32)
    for layer in range(int(arch["num_hidden_layers"])):
        w = {
            name: widen(weights.layers[name], jnp.asarray(layer, jnp.int32))
            for name in ATTENTION + ("ln_attn", "q_norm", "k_norm", "ln_mlp")
        }
        x, m = attention(x, clean, w)
        x = x + _experts(weights, arch, layer, m.reshape(rows * padded, -1)).reshape(x.shape)
    return rms_norm(x, weights.ln_final.astype(jnp.float32))


def _scored_rows(config_doc: dict, weights: Any, sequences: list) -> tuple:
    """``(rows [scored, hidden], per sequence the row of each served
    token)``: every served token's final-normed state in the stream of the
    step that kept it."""
    import jax.numpy as jnp

    arch, steps = config_doc["architecture"], int(config_doc["generation"]["denoise_steps"])
    block, mask = int(arch["block_length"]), int(arch["mask_token_id"])
    longest = max(len(p) + len(c) for p, c in sequences)
    padded = decoder_f32._pad_length(-(-longest // block) * block)
    ids, clean, where = [], [], []
    for prompt, served in sequences:
        base = len(ids)
        ids += stream_ids(prompt, served, block, steps, mask, padded)
        clean += [base] * (1 + steps)
        plan = schedule(len(prompt), len(served), block, steps)
        where.append([(base + 1 + step, position) for step, position in plan["kept"]])
    hidden = hidden_states(weights, arch, jnp.asarray(ids, jnp.int32), clean)
    flat = [pair for row in where for pair in row]
    rows = hidden[jnp.asarray([r for r, _ in flat]), jnp.asarray([p for _, p in flat])]
    index, at = [], 0
    for row in where:
        index.append(list(range(at, at + len(row))))
        at += len(row)
    return rows, index


@functools.lru_cache(maxsize=None)
def _reduce_fn() -> Any:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def reduce(h, block, index, banned):
        """One block of the head: each row's largest logit in it (the
        banned column left out), where it lies, and the row's logit at
        ``index`` (relative to the block; minus infinity outside it)."""
        with jax.default_matmul_precision("highest"):
            part = h @ block.astype(jnp.float32)
        width = part.shape[-1]
        part = jnp.where(jnp.arange(width)[None] == banned, -jnp.inf, part)
        at = jnp.take_along_axis(part, jnp.clip(index, 0, width - 1)[:, None], axis=1)[:, 0]
        return (
            part.max(axis=-1), part.argmax(axis=-1).astype(jnp.int32),
            jnp.where((index >= 0) & (index < width), at, -jnp.inf),
        )

    return reduce


def head_reduce(weights: Any, arch: dict, rows: Any, index: Any, blocks: int = 8) -> tuple:
    """``rows [n, hidden]`` through the head a block of the vocabulary at a
    time, the mask id's column left out: each row's largest logit, the
    token that has it, and the row's logit of the token ``index[row]``."""
    import jax.numpy as jnp
    import numpy as np

    vocab, mask = int(arch["vocab_size"]), int(arch["mask_token_id"])
    edges = [vocab * i // blocks for i in range(blocks + 1)]
    reduce = _reduce_fn()
    index = np.asarray(index, np.int32)
    largest = first = picked = None
    for lo, hi in zip(edges, edges[1:]):
        part = reduce(rows, weights.head[:, lo:hi], jnp.asarray(index - lo), mask - lo)
        if largest is None:
            largest, first, picked = part[0], part[1] + lo, part[2]
            continue
        first = jnp.where(part[0] > largest, part[1] + lo, first)
        largest = jnp.maximum(largest, part[0])
        picked = jnp.maximum(picked, part[2])
    return np.asarray(largest), np.asarray(first), np.asarray(picked)


def logits(config_doc: dict, weights: Any, ids: list) -> Any:
    """``[len(ids), vocab]`` float32 logits of one clean sequence under the
    block-causal mask (the tests' whole-vocabulary view)."""
    import jax
    import jax.numpy as jnp

    arch = config_doc["architecture"]
    block = int(arch["block_length"])
    padded = decoder_f32._pad_length(-(-len(ids) // block) * block)
    row = list(ids) + [int(arch["mask_token_id"])] * (padded - len(ids))
    hidden = hidden_states(weights, arch, jnp.asarray([row], jnp.int32), [0])
    with jax.default_matmul_precision("highest"):
        return (hidden[0] @ weights.head.astype(jnp.float32))[: len(ids)]


def step_logits(config_doc: dict, weights: Any, prompt: list, served: list) -> Any:
    """``[len(served), vocab]``: each served token's position in the stream
    of the step that kept it, through the whole head (the tests' view of
    what :func:`greedy_gaps` reduces)."""
    import jax
    import jax.numpy as jnp

    rows, _ = _scored_rows(config_doc, weights, [(list(prompt), list(served))])
    with jax.default_matmul_precision("highest"):
        return rows @ weights.head.astype(jnp.float32)


def kept_positions(
    logits: Any, drawn: list, open_positions: list, count: int,
    temperature: float, top_p: float, top_k: int,
) -> list:
    """Which of a block's ``open_positions`` a step keeps under ``remask:
    "low_confidence"``, in plain numpy: ``logits [block, vocab]`` (the mask
    id's column already out), ``drawn`` the token sampled at each position.
    A position's confidence is its token's probability among the sampler's
    candidates -- the ``top_k`` largest logits at ``temperature``, cut to
    the nucleus ``top_p`` (a candidate stays while the candidates before it
    hold less than ``top_p``) and renormalised; the ``count`` most confident
    are kept, ties to the left."""
    import numpy as np

    confidence = {}
    for position in open_positions:
        row = np.asarray(logits[position], np.float64) / max(temperature, 1e-4)
        order = np.argsort(-row, kind="stable")[:top_k]
        probs = np.exp(row[order] - row[order].max())
        probs /= probs.sum()
        inside = np.cumsum(probs) - probs < top_p
        among = np.where(inside, probs, 0.0) / probs[inside].sum()
        at = int(np.nonzero(order == drawn[position])[0][0])
        confidence[position] = among[at]
    ranked = sorted(open_positions, key=lambda position: (-confidence[position], position))
    return sorted(ranked[:count])


def greedy_gaps(config_doc: dict, weights: Any, sequences: list) -> list:
    """Per sequence, for each served token: the largest reference logit of
    its position in the step that kept it (the mask id's left out) minus
    the served token's."""
    rows, where = _scored_rows(config_doc, weights, sequences)
    index = [token for _, served in sequences for token in served]
    largest, _, picked = head_reduce(weights, config_doc["architecture"], rows, index)
    return [[float(largest[r] - picked[r]) for r in row] for row in where]


def control_gaps(config_doc: dict, weights: Any, sequences: list) -> list:
    """The control: this reference with its int8 matrices at the nearest
    precision below the configuration's (int4 a column for int8), put in
    the program's place: at each scored position of the same streams, the
    float32 gap of the token the lower precision puts first."""
    own = importlib.import_module("." + WEIGHTS, __package__)
    arch = config_doc["architecture"]
    rows, where = _scored_rows(config_doc, weights, sequences)
    weights.release_layers()
    bits = decoder_f32.LOWER_BITS[int(config_doc["weights"].get("bits") or 0)]
    low = own.make(config_doc, bits, like=weights)
    low_rows, _ = _scored_rows(config_doc, low, sequences)
    low.release_layers()
    _, first, _ = head_reduce(weights, arch, low_rows, [0] * low_rows.shape[0])
    largest, _, picked = head_reduce(weights, arch, rows, first)
    return [[float(largest[r] - picked[r]) for r in row] for row in where]
