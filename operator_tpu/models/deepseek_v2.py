"""DeepSeek-V2 (``model_type: deepseek_v2``): multi-head latent attention
and a feed-forward layer that is dense in the leading layers and sparse,
with shared experts beside the routed ones, after them.

deepseek-ai/DeepSeek-V2-Lite (``config.json`` keys in ``configs.
DeepseekV2Config``; the layer is the public ``modeling_deepseek.py``'s,
arXiv:2405.04434).  With ``x`` the residual stream, ``N`` an RMSNorm
(``rms_norm_eps``), ``H`` heads, ``dn``/``dr``/``dv`` the nope, rope and
value head widths and ``R`` the latent (``kv_lora_rank``), layer ``l``::

    a = N(x; ln_attn)
    q = a Wq                      [H x (dn | dr)]   (q_lora_rank null: no
                                                     q compression)
    [c | k_r] = a Wkva            [R | dr];  c = N(c; kv_norm)
    per head h:  [k_nope_h | v_h] = c Wkvb_h  (dn and dv wide; held as
                 W_uk[h], W_uv[h]  [R, dn], [R, dv])
    q_rope_h, k_r rotated by YaRN RoPE over the dr lanes (the published
        convention: the dr lanes de-interleaved, even lanes then odd, then
        half rotation; cos and sin unscaled, configs.YarnScaling)
    score_hs = (q_nope_h . k_nope_h,s + q_rope_h . k_r,s) x scale,
        scale = (dn + dr)^-0.5 x mscale(factor, mscale_all_dim)^2
    causal softmax;  o_h = sum_s p_hs v_h,s;  x = x + concat_h(o_h) Wo
    layer < first_k_dense_replace:  x = x + down(silu(gate(m)) * up(m)),
        m = N(x; ln_mlp)                          (intermediate_size wide)
    after:  m = N(x; ln_mlp);  p = softmax_float32(m Wr) over all E experts;
        the K largest p_e as they are (norm_topk_prob false; divided by
        their sum where true), times routed_scaling_factor;
        x = x + sum_e p_e E_e(m) + S(m)
        E_e a gated MLP of moe_intermediate_size, S the n_shared_experts
        as ONE gated MLP of n_shared_experts x moe_intermediate_size
    after the last layer:  logits = N(x; ln_final) W_head   (untied)

**Absorbed**, the form the serving step runs (``ops/latent_attention.py``):
``q_lat_h = q_nope_h W_uk[h]^T`` (R wide), the score is ``(q_lat_h . c_s
+ q_rope_h . k_r,s) x scale`` and the output ``o_h = (sum_s p_hs c_s)
W_uv[h]``: the same function, so the cache holds ``[c | k_r]`` a token and
layer and nothing a head.  :func:`forward` runs the EXPANDED form (per-head
keys and values decompressed from ``c``), the tests' path.

**The leaves.**  The dense layers' are stacked apart from the expert
layers', under a ``dense_`` prefix (``[first_k_dense_replace, ...]``): the
mixed step runs them ahead of its loop (:func:`mixed_lead`) and scans the
expert layers' (:func:`scan_steps` of them).  The expert stacks ``w_gate
w_up w_down [expert layers, E, in, out]`` go whole into
``ops/moe_experts.py``, as SDAR's do.  ``W_uk`` and ``W_uv`` are held per
head ``[layers, H, R, dn]`` (``[in, out]`` a head: int8 with a scale an
output column, as every other matrix).

Three entry points share :func:`attention` and the two feed-forward
kinds: :func:`init_params` (the seeded recipe), :func:`forward` (no cache)
and :func:`mixed_layer` / :func:`mixed_lead` (the continuous scheduler's).
No loader maps the published names yet (models/loader.py refuses the
family): the serving path runs the seeded recipe.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..ops.moe_experts import expert_counts, moe_experts
from .configs import DeepseekV2Config
from .llama import apply_rope, dense_init, make_causal_mask, rms_norm, rope_frequencies
from .quant import mm
from .sdar import route as sdar_route

Params = dict[str, Any]

#: a dense layer's leaves carry this prefix
DENSE = "dense_"
#: the attention matrices of a layer of either kind
ATTENTION = ("wq", "w_kva", "w_uk", "w_uv", "wo")
#: the matrices, in the order the init's key split follows: the dense
#: layers' then the expert layers' (the router among them, float)
MATRICES = (
    *(DENSE + name for name in ATTENTION + ("w_gate", "w_up", "w_down")),
    *ATTENTION, "w_router", "sh_gate", "sh_up", "sh_down", "w_gate", "w_up", "w_down",
)
#: what int8 holds a column (and, in an expert stack, an expert): every
#: matrix but the router, whose logits order the experts
LAYER_MATRICES = tuple(name for name in MATRICES if name != "w_router")
#: the expert stacks ``[expert layers, E, in, out]``
EXPERT_STACKS = ("w_gate", "w_up", "w_down")
#: the norm vectors, in the order the init draws them
LAYER_NORMS = (
    DENSE + "ln_attn", DENSE + "kv_norm", DENSE + "ln_mlp", "ln_attn", "kv_norm", "ln_mlp",
)
#: the leaves the mixed step's loop does not scan: the expert stacks (the
#: kernel fetches an expert from ``(layer, expert)``) and every leaf of the
#: dense layers (:func:`mixed_lead` runs them before the loop)
WHOLE_STACKS = EXPERT_STACKS + tuple(
    name for name in MATRICES + LAYER_NORMS if name.startswith(DENSE)
)

__all__ = [
    "LAYER_MATRICES", "LAYER_NORMS", "WHOLE_STACKS", "attention", "forward",
    "init_params", "layer_matrix_shapes", "matmul_params_per_token", "mixed_layer",
    "mixed_lead", "route", "scan_steps",
]


def layer_matrix_shapes(config: DeepseekV2Config) -> dict[str, tuple[int, ...]]:
    """Stacked shapes of the matrices, in the order of ``MATRICES``."""
    h, heads = config.hidden_size, config.num_heads
    rank, dn, dr, dv = (
        config.kv_lora_rank, config.qk_nope_head_dim, config.qk_rope_head_dim,
        config.v_head_dim,
    )
    shapes = {}
    for prefix, n in ((DENSE, config.first_k_dense_replace), ("", config.moe_layers)):
        shapes.update({
            prefix + "wq": (n, h, heads * (dn + dr)),
            prefix + "w_kva": (n, h, rank + dr),
            prefix + "w_uk": (n, heads, rank, dn),
            prefix + "w_uv": (n, heads, rank, dv),
            prefix + "wo": (n, heads * dv, h),
        })
    f, e, m = config.moe_intermediate_size, config.num_experts, config.moe_layers
    shared = config.shared_intermediate_size
    shapes.update({
        DENSE + "w_gate": (config.first_k_dense_replace, h, config.intermediate_size),
        DENSE + "w_up": (config.first_k_dense_replace, h, config.intermediate_size),
        DENSE + "w_down": (config.first_k_dense_replace, config.intermediate_size, h),
        "w_router": (m, h, e),
        "sh_gate": (m, h, shared), "sh_up": (m, h, shared), "sh_down": (m, shared, h),
        "w_gate": (m, e, h, f), "w_up": (m, e, h, f), "w_down": (m, e, f, h),
    })
    return {name: shapes[name] for name in MATRICES}


def matmul_params_per_token(config: DeepseekV2Config) -> int:
    """Layer weights that multiply ONE token (``serving/perf.py``): every
    attention matrix whole (absorbed, ``W_uk`` and ``W_uv`` multiply a
    token's heads once each), the dense layers' MLP, and in an expert
    layer the router, ``num_experts_per_tok`` experts and the shared
    MLP."""
    total = 0
    for name, shape in layer_matrix_shapes(config).items():
        count = 1
        for size in shape:
            count *= size
        if name in EXPERT_STACKS:
            count = count // config.num_experts * config.num_experts_per_tok
        total += count
    return total


def scan_steps(config: DeepseekV2Config) -> int:
    """Steps of the mixed step's layer loop: one an expert layer
    (``serving/sched/mixed.py``); the dense layers run before it."""
    return config.moe_layers


def init_params(
    config: DeepseekV2Config,
    key: jax.Array,
    dtype: jnp.dtype = jnp.bfloat16,
    *,
    layer_matrix_init: Optional[Callable] = None,
) -> Params:
    """Seeded random init.  A recipe two sides can follow: ``key`` split in
    four (embedding, matrices, head, vectors).  The matrices' key is split
    in ``len(MATRICES)``, in that order: every matrix normal x fan-in^-0.5
    (``llama.dense_init``: the fan-in is the shape's second-to-last axis),
    drawn, scaled and cast in one compiled program -- a stacked leaf whole,
    the router too, an expert stack A LAYER AT A TIME from its key split in
    the expert layers, each layer's ``[E, in, out]`` through
    ``layer_matrix_init`` (which is where int8 is made) and stacked.  The
    vectors' key is split in ``len(LAYER_NORMS) + 1``: the norms, then the
    final norm, each ``1 + 0.1 x normal`` (ones would hide a dropped or a
    swapped norm: ``kv_norm`` above all)."""
    k_embed, k_layers, k_head, k_vectors = jax.random.split(key, 4)
    h = config.hidden_size
    draw = jax.jit(
        lambda k, shape: dense_init(k, shape, h, dtype), static_argnames=("shape",)
    )
    if layer_matrix_init is None:
        def layer_matrix_init(k, shape):
            return jax.block_until_ready(draw(k, shape=shape))

    params: Params = {
        "embed": jax.block_until_ready(draw(k_embed, shape=(config.vocab_size, h))),
        "lm_head": jax.block_until_ready(draw(k_head, shape=(h, config.vocab_size))),
    }
    shapes = layer_matrix_shapes(config)
    layers: dict[str, Any] = {}
    for name, k in zip(MATRICES, jax.random.split(k_layers, len(MATRICES))):
        shape = shapes[name]
        if name == "w_router":
            layers[name] = jax.block_until_ready(draw(k, shape=shape))
        elif name in EXPERT_STACKS:
            each = [
                layer_matrix_init(k_layer, shape[1:])
                for k_layer in jax.random.split(k, config.moe_layers)
            ]
            layers[name] = jax.block_until_ready(
                jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *each)
            )
            del each
        else:
            layers[name] = layer_matrix_init(k, shape)

    def norm_scale(k, shape):
        return (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    *k_norms, k_final = jax.random.split(k_vectors, len(LAYER_NORMS) + 1)
    for name, k in zip(LAYER_NORMS, k_norms):
        n = config.first_k_dense_replace if name.startswith(DENSE) else config.moe_layers
        width = config.kv_lora_rank if name.endswith("kv_norm") else h
        layers[name] = norm_scale(k, (n, width))
    params["layers"] = layers
    params["ln_final"] = norm_scale(k_final, (h,))
    return params


# --------------------------------------------------------------------------
# the pieces every path shares
# --------------------------------------------------------------------------


def _split(leaf: Any) -> tuple:
    """``(matrix, output-column scale or None)`` of a plain or int8 leaf."""
    if isinstance(leaf, dict):
        return leaf["q"], leaf["s"]
    return leaf, None


def per_head_up(c: jax.Array, leaf: Any, heads_in: bool = False) -> jax.Array:
    """``c [..., R]`` through every head's ``[R, d]`` of ``leaf [H, R, d]``:
    ``[..., H, d]`` in ``c``'s dtype (the expanded form's keys and values).
    With ``heads_in``, ``c [..., H, R]`` holds a latent a head, each through
    its own head's matrix (the absorbed form's outputs)."""
    w, s = _split(leaf)
    # as ``quant.mm``: the product in the activations' dtype, then the
    # column scale
    y = jnp.einsum(
        "...hr,hrd->...hd" if heads_in else "...r,hrd->...hd", c, w.astype(c.dtype),
    )
    if s is not None:
        y = y * s.astype(y.dtype)
    return y


def absorb(q_nope: jax.Array, leaf: Any) -> jax.Array:
    """``q_nope [..., H, dn]`` into the latent space: ``q_nope_h W_uk[h]^T``,
    ``[..., H, R]`` in ``q_nope``'s dtype (an int8 ``W_uk``'s column scale
    multiplies the query, the side its columns meet)."""
    w, s = _split(leaf)
    if s is not None:
        q_nope = (q_nope.astype(jnp.float32) * s.astype(jnp.float32)).astype(q_nope.dtype)
    return jnp.einsum(
        "...hd,hrd->...hr", q_nope, w.astype(q_nope.dtype),
        preferred_element_type=jnp.float32,
    ).astype(q_nope.dtype)


def rotate(config: DeepseekV2Config, x: jax.Array, positions: jax.Array,
           inv_freq: jax.Array) -> jax.Array:
    """RoPE over the last ``qk_rope_head_dim`` lanes of ``x [B, T, ...,
    dr]`` in the published convention: the lanes de-interleaved (even then
    odd), then half rotation."""
    dr = config.qk_rope_head_dim
    order = jnp.concatenate([jnp.arange(0, dr, 2), jnp.arange(1, dr, 2)])
    return apply_rope(x[..., order], positions, inv_freq)


def attention(config: DeepseekV2Config, w: Params, x: jax.Array, attend: Callable) -> jax.Array:
    """The attention half of a layer on ``x [B, T, hidden]``: the
    projections, ``c`` normed, and ``attend(w, q_nope [B, T, H, dn],
    q_rope [B, T, H, dr], c [B, T, R], k_rope [B, T, dr])``, unrotated,
    returning ``[B, T, H, dv]``: RoPE, the cache and the attention itself
    are the caller's."""
    eps, heads = config.rms_norm_eps, config.num_heads
    dn, rank = config.qk_nope_head_dim, config.kv_lora_rank
    a = rms_norm(x, w["ln_attn"], eps)
    q = mm(a, w["wq"]).reshape(*x.shape[:-1], heads, -1)
    kv = mm(a, w["w_kva"])
    c = rms_norm(kv[..., :rank], w["kv_norm"], eps)
    o = attend(w, q[..., :dn], q[..., dn:], c, kv[..., rank:])
    return x + mm(o.reshape(*x.shape[:-1], -1), w["wo"])


def mlp(w: Params, m: jax.Array, prefix: str = "") -> jax.Array:
    gate = jax.nn.silu(mm(m, w[prefix + "gate"]))
    return mm(gate * mm(m, w[prefix + "up"]), w[prefix + "down"])


def dense_ffn(config: DeepseekV2Config, w: Params, x: jax.Array) -> jax.Array:
    with jax.named_scope("dense_ffn"):
        return x + mlp(w, rms_norm(x, w["ln_mlp"], config.rms_norm_eps), "w_")


def route(config: DeepseekV2Config, m: jax.Array, w_router: jax.Array,
          valid: Optional[jax.Array] = None) -> tuple[jax.Array, jax.Array]:
    """SDAR's router (``models/sdar.py route``: the float32 softmax over
    all experts, the top ``num_experts_per_tok``, divided by their sum only
    where ``norm_topk_prob``), the gates times ``routed_scaling_factor``."""
    expert_ids, gates = sdar_route(config, m, w_router, valid)
    if config.routed_scaling_factor != 1.0:
        gates = gates * config.routed_scaling_factor
    return expert_ids, gates


def moe_ffn(config: DeepseekV2Config, w: Params, stacks: Params, layer: jax.Array,
            x: jax.Array, valid: Optional[jax.Array] = None):
    """The expert half of a layer on ``x [1, T, hidden]``: the routed
    experts from the whole ``stacks`` at ``layer`` and the shared ones.
    Returns ``(x, counts [E])``, the tokens each expert was given."""
    with jax.named_scope("moe_route"):
        m = rms_norm(x, w["ln_mlp"], config.rms_norm_eps)[0]  # [T, H]
        expert_ids, gates = route(config, m, w["w_router"], valid)
        counts = expert_counts(expert_ids, config.num_experts)
    with jax.named_scope("moe_experts"):
        y = moe_experts(
            m, expert_ids, gates, stacks["w_gate"], stacks["w_up"], stacks["w_down"], layer,
        )
    with jax.named_scope("shared_expert"):
        y = y + mlp(w, m, "sh_").astype(jnp.float32)
    return x + y.astype(x.dtype)[None], counts


def layer_of(leaves: Params, prefix: str, index: Any) -> Params:
    """One layer's leaves of a kind, its prefix dropped: ``index`` into
    each leaf that carries ``prefix`` (and, for the expert layers' empty
    prefix, no ``dense_``)."""
    return {
        name[len(prefix):]: jax.tree_util.tree_map(lambda a: a[index], leaf)
        for name, leaf in leaves.items()
        if name.startswith(prefix) and (prefix or not name.startswith(DENSE))
    }


# --------------------------------------------------------------------------
# the whole sequence at once (no cache): the expanded form
# --------------------------------------------------------------------------


def forward(
    params: Params, config: DeepseekV2Config, token_ids: jax.Array, positions: jax.Array,
) -> tuple[jax.Array, None]:
    """Logits ``[1, T, vocab]`` (float32) of ONE sequence with the EXPANDED
    attention (per-head keys and values decompressed from ``c``) and plain
    causal softmax, and None, as ``llama.forward`` returns without a cache:
    the program's own numerics (bfloat16 activations, int8 matrices)
    without cache, kernel or scheduler."""
    assert token_ids.shape[0] == 1, "one sequence: the expert op's token axis is flat"
    inv_freq = rope_frequencies(config, config.qk_rope_head_dim)
    x = jnp.take(params["embed"], token_ids, axis=0)
    t = x.shape[1]
    mask = make_causal_mask(positions, positions, jnp.ones((1, t), bool))
    layers = params["layers"]

    def attend(w, q_nope, q_rope, c, k_rope):
        k_nope = per_head_up(c, w["w_uk"])  # [1, T, H, dn]
        v = per_head_up(c, w["w_uv"])  # [1, T, H, dv]
        q_rope = rotate(config, q_rope, positions, inv_freq)
        k_rope = rotate(config, k_rope, positions, inv_freq)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None], (*k_nope.shape[:3], k_rope.shape[-1]))],
            axis=-1,
        )
        scores = jnp.einsum("bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32)
        scores = jnp.where(mask[:, None], scores * config.softmax_scale, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhts,bshd->bthd", probs, v)

    for i in range(config.first_k_dense_replace):
        w = layer_of(layers, DENSE, i)
        x = dense_ffn(config, w, attention(config, w, x, attend))
    stacks = {name: layers[name] for name in EXPERT_STACKS}
    for i in range(config.moe_layers):
        w = layer_of({k: v for k, v in layers.items() if k not in EXPERT_STACKS}, "", i)
        x = attention(config, w, x, attend)
        x, _ = moe_ffn(config, w, stacks, jnp.int32(i), x)
    x = rms_norm(x, params["ln_final"], config.rms_norm_eps)
    logits = jnp.einsum("bth,hv->btv", x, params["lm_head"], preferred_element_type=jnp.float32)
    return logits, None


# --------------------------------------------------------------------------
# the continuous scheduler's bodies (serving/sched/mixed.py): absorbed
# --------------------------------------------------------------------------


def _absorbed(config: DeepseekV2Config, step: Any, held: dict, plane: Any) -> Callable:
    """The absorbed attention of one layer of the mixed step: queries into
    the latent space, the step's rows into plane ``plane`` of the latent
    pool (``held["pools"]``, rebound), ``step.attend_latent`` over it, and
    the latent outputs up through ``W_uv``."""
    inv_freq = rope_frequencies(config, config.qk_rope_head_dim)
    positions = step.pos[None]

    def attend(w, q_nope, q_rope, c, k_rope):
        with jax.named_scope("mla_absorb"):
            q_lat = absorb(q_nope[0], w["w_uk"])  # [T, H, R]
            q_rope = rotate(config, q_rope, positions, inv_freq)[0]
            k_rope = rotate(config, k_rope, positions, inv_freq)[0]
        o_lat, held["pools"] = step.attend_latent(
            q_lat, q_rope, c[0], k_rope, held["pools"], plane,
        )
        with jax.named_scope("mla_up"):
            return per_head_up(o_lat.astype(c.dtype), w["w_uv"], heads_in=True)[None]

    return attend


def mixed_lead(config: DeepseekV2Config, step: Any, carry: tuple) -> tuple:
    """The dense layers, ahead of the mixed step's loop: layer ``i`` reads
    its leaves at ``i`` of the whole ``dense_`` stacks (``step.stacks``)
    and writes and reads plane ``i`` of the latent pool."""
    x, pools, recurrent = carry
    held = {"pools": pools}
    for i in range(config.first_k_dense_replace):
        w = layer_of(step.stacks, DENSE, i)
        x = attention(config, w, x, _absorbed(config, step, held, i))
        x = dense_ffn(config, w, x)
    return x, held["pools"], recurrent


def mixed_layer(config: DeepseekV2Config, step: Any) -> Callable:
    """The scan body of the mixed step for this family: ONE EXPERT LAYER,
    the loop making :func:`scan_steps` of them.  ``scanned["layer"]`` is
    the expert layer's index ``i`` (into the expert stacks); its plane of
    the latent pool is ``first_k_dense_replace + i``.  Padding tokens
    (``step.valid`` false) are routed nowhere.  Each layer also yields the
    tokens its experts were given, ``[E]``."""

    def layer_step(carry, scanned):
        x, pools, recurrent = carry
        index = scanned["layer"]
        held = {"pools": pools}
        w = scanned["w"]
        plane = config.first_k_dense_replace + index
        x = attention(config, w, x, _absorbed(config, step, held, plane))
        x, counts = moe_ffn(config, w, step.stacks, index, x, step.valid)
        return (x, held["pools"], recurrent), counts

    return layer_step
