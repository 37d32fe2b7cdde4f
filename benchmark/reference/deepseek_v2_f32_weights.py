"""The weights of ``deepseek_v2_f32``, made here and nowhere else.

**``make(config_doc)`` makes the reference's own weights** from the recipe
the configuration's file states (its ``weights`` group), with nothing of
the program imported and nothing the program made read; the quantiser and
the compiled draw of a matrix are ``decoder_f32_weights``' (a file of the
benchmark, beside this one).

The recipe (``"weights": {"seed", "init": "deepseek_v2_fan_in", "dtype",
"bits"}``; it is the program's ``allow_random_weights`` recipe for this
family, ``operator_tpu/models/deepseek_v2.py init_params``, written down
here as a published checkpoint's would be).  ``D`` is
``first_k_dense_replace`` (the dense layers), ``M`` the layers after them
(each with experts), ``H`` heads, ``R`` the latent:

- ``key = PRNGKey(seed)``, split in FOUR: embedding, layer matrices, head,
  vectors.  The layers' key is split in twenty, in the order of
  ``MATRICES``: the dense layers' ``wq w_kva w_uk w_uv wo w_gate w_up
  w_down`` (``dense_`` before each name, stacked ``[D, ...]``), then the
  expert layers' ``wq w_kva w_uk w_uv wo w_router sh_gate sh_up sh_down
  w_gate w_up w_down`` (stacked ``[M, ...]``).  Every matrix is
  ``normal(key, shape, float32) * shape[-2] ** -0.5`` cast to ``dtype``,
  drawn, scaled and cast in one compiled program: a stacked leaf whole,
  the router too; an expert stack ``[M, E, in, out]`` A LAYER AT A TIME,
  its key split in ``M`` and layer ``l``'s ``[E, in, out]`` drawn from key
  ``l``.  ``wq [hidden, H x (dn + dr)]``, ``w_kva [hidden, R + dr]``,
  ``w_uk [H, R, dn]`` and ``w_uv [H, R, dv]`` (the published ``kv_b_proj``
  split by head, ``[in, out]`` a head), ``wo [H x dv, hidden]``, the
  shared experts as ONE gated MLP of ``n_shared_experts x
  moe_intermediate_size``.  The embedding ``[vocab, hidden]`` and the head
  ``[hidden, vocab]`` are the same draw (the head is never tied);
- the vectors' key is split in seven: ``dense_ln_attn dense_kv_norm
  dense_ln_mlp ln_attn kv_norm ln_mlp`` (``[layers, hidden]``, the
  ``kv_norm`` ``[layers, R]``) and the final norm (``[hidden]``), each ``1
  + 0.1 * normal`` cast to ``dtype``: ones would hide a dropped norm;
- ``bits`` 8: every matrix but the router is held as whole numbers of
  ``-127..127`` with one scale an output column (and, in a stack, an
  expert; in ``w_uk`` and ``w_uv`` a head); the router, the embedding, the
  head and the norms stay as drawn.  ``make(config_doc, bits=4)`` is the
  control's.

**``adapt(params, config_doc)`` maps layout only** (the program's tree is
already this one, ``wq`` and ``dense_wq`` held transposed for the serving
step put back), for ``tools/weights_check.py`` and the tests.
``PROGRAM_CONFIG`` ties every ``architecture`` key of a configuration's
file to the program's model configuration (``operator_tpu.models.
get_config``).
"""

from __future__ import annotations

from typing import Any, Optional

from .decoder_f32_weights import Weights, _dense_fn, _quantise_fn

#: ``architecture`` key -> attribute of the program's ``DeepseekV2Config``
PROGRAM_CONFIG = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "kv_lora_rank": "kv_lora_rank",
    "q_lora_rank": "q_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "first_k_dense_replace": "first_k_dense_replace",
    "moe_layer_freq": "moe_layer_freq",
    "n_routed_experts": "n_routed_experts",
    "n_shared_experts": "n_shared_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "scoring_func": "scoring_func",
    "topk_method": "topk_method",
    "n_group": "n_group",
    "topk_group": "topk_group",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "attention_bias",
    "rope_theta": "rope_theta",
    "rope_scaling": "rope_scaling_published",
    "rms_norm_eps": "rms_norm_eps",
    "hidden_act": "hidden_act",
}

DENSE = "dense_"
ATTENTION = ("wq", "w_kva", "w_uk", "w_uv", "wo")
EXPERTS = ("w_gate", "w_up", "w_down")
#: the key split's order
MATRICES = (
    *(DENSE + name for name in ATTENTION + EXPERTS),
    *ATTENTION, "w_router", "sh_gate", "sh_up", "sh_down", *EXPERTS,
)
NORMS = (
    DENSE + "ln_attn", DENSE + "kv_norm", DENSE + "ln_mlp", "ln_attn", "kv_norm", "ln_mlp",
)


def depths(arch: dict) -> tuple:
    """``(dense layers, expert layers)``."""
    dense = int(arch["first_k_dense_replace"])
    return dense, int(arch["num_hidden_layers"]) - dense


def matrix_shapes(arch: dict) -> dict:
    """Stacked shapes, in the order the layers' key is split."""
    dense, moe = depths(arch)
    h, heads = int(arch["hidden_size"]), int(arch["num_attention_heads"])
    rank, dn, dr, dv = (
        int(arch["kv_lora_rank"]), int(arch["qk_nope_head_dim"]),
        int(arch["qk_rope_head_dim"]), int(arch["v_head_dim"]),
    )
    f, e = int(arch["moe_intermediate_size"]), int(arch["n_routed_experts"])
    inner, shared = int(arch["intermediate_size"]), int(arch["n_shared_experts"]) * f
    shapes = {}
    for prefix, n in ((DENSE, dense), ("", moe)):
        shapes.update({
            prefix + "wq": (n, h, heads * (dn + dr)), prefix + "w_kva": (n, h, rank + dr),
            prefix + "w_uk": (n, heads, rank, dn), prefix + "w_uv": (n, heads, rank, dv),
            prefix + "wo": (n, heads * dv, h),
        })
    shapes.update({
        DENSE + "w_gate": (dense, h, inner), DENSE + "w_up": (dense, h, inner),
        DENSE + "w_down": (dense, inner, h),
        "w_router": (moe, h, e),
        "sh_gate": (moe, h, shared), "sh_up": (moe, h, shared), "sh_down": (moe, shared, h),
        "w_gate": (moe, e, h, f), "w_up": (moe, e, h, f), "w_down": (moe, e, f, h),
    })
    return {name: shapes[name] for name in MATRICES}


def widen(leaf: Any, *index: Any) -> Any:
    """``leaf[index]`` in float32: a stored matrix, or an int8 group's
    whole numbers times its column scales."""
    import jax.numpy as jnp

    if isinstance(leaf, dict):
        q, s = leaf["q"][index], leaf["s"][index]
        return q.astype(jnp.float32) * s.astype(jnp.float32)[..., None, :]
    return leaf[index].astype(jnp.float32)


def adapt(params: Any, config_doc: dict) -> Weights:
    """The program's tree under the reference's interface: layout only."""
    import jax.numpy as jnp

    del config_doc  # nothing is cut: the program holds every layer it runs
    layers = {}
    for name in MATRICES + NORMS:
        leaf = params["layers"][name]
        if isinstance(leaf, dict) and "qt" in leaf:
            # held [out, in] for the serving step: the canonical [in, out]
            leaf = {"q": jnp.swapaxes(leaf["qt"], -1, -2), "s": leaf["s"]}
        layers[name] = leaf
    leaves = {k: v for k, v in params.items() if k != "layers"}
    leaves["layers"] = layers
    return Weights(leaves)


def make(config_doc: dict, bits: Optional[int] = None, like: Optional[Weights] = None) -> Weights:
    """The reference's own weights for this configuration, at ``bits``
    (the file's unless given: the control asks for fewer, and shares the
    leaves outside the layers of ``like``)."""
    import jax
    import jax.numpy as jnp

    arch, recipe = config_doc["architecture"], config_doc["weights"]
    if recipe.get("init") != "deepseek_v2_fan_in":
        raise ValueError(f"deepseek_v2_f32_weights knows no init {recipe.get('init')!r}")
    if arch["tie_word_embeddings"]:
        raise ValueError("deepseek_v2_f32_weights makes an untied head only")
    dtype = jnp.dtype(str(recipe["dtype"]))
    bits = int(recipe.get("bits") or 0) if bits is None else int(bits)
    h, vocab = int(arch["hidden_size"]), int(arch["vocab_size"])
    dense, moe = depths(arch)
    k_embed, k_layers, k_head, k_vectors = jax.random.split(
        jax.random.PRNGKey(int(recipe["seed"])), 4
    )
    *k_norms, k_final = jax.random.split(k_vectors, len(NORMS) + 1)

    def norm_scale(key, shape):
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    def matrix(key, shape):
        leaf = _dense_fn(shape, dtype.name)(key)
        if bits:
            leaf = _quantise_fn(2 ** (bits - 1) - 1)(leaf)
        return jax.block_until_ready(leaf)

    def make_layers() -> dict:
        shapes = matrix_shapes(arch)
        layers = {}
        for key, (name, shape) in zip(jax.random.split(k_layers, len(shapes)), shapes.items()):
            if name == "w_router":
                layers[name] = jax.block_until_ready(_dense_fn(shape, dtype.name)(key))
            elif name in EXPERTS:
                each = [matrix(k, shape[1:]) for k in jax.random.split(key, moe)]
                layers[name] = jax.block_until_ready(
                    jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *each)
                )
                del each
            else:
                layers[name] = matrix(key, shape)
        for name, key in zip(NORMS, k_norms):
            n = dense if name.startswith(DENSE) else moe
            width = int(arch["kv_lora_rank"]) if name.endswith("kv_norm") else h
            layers[name] = norm_scale(key, (n, width))
        return layers

    if like is not None:
        leaves = {k: v for k, v in like.leaves.items() if k != "layers"}
    else:
        leaves = {
            "embed": jax.block_until_ready(_dense_fn((vocab, h), dtype.name)(k_embed)),
            "lm_head": jax.block_until_ready(_dense_fn((h, vocab), dtype.name)(k_head)),
            "ln_final": norm_scale(k_final, (h,)),
        }
    made = Weights(leaves, make_layers)
    made.layers  # noqa: B018 - made now; again after a release_layers()
    return made
