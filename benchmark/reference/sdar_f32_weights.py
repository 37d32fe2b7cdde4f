"""The weights of ``sdar_f32``, made here and nowhere else.

**``make(config_doc)`` makes the reference's own weights** from the recipe
the configuration's file states (its ``weights`` group), with nothing of
the program imported and nothing the program made read; the quantiser and
the compiled draw of a matrix are ``decoder_f32_weights``' (a file of the
benchmark, beside this one).

The recipe (``"weights": {"seed", "init": "sdar_fan_in", "dtype",
"bits"}``; it is the program's ``allow_random_weights`` recipe for this
family, ``operator_tpu/models/sdar.py init_params``, written down here as
a published checkpoint's would be):

- ``key = PRNGKey(seed)``, split in FOUR: embedding, layer matrices, head,
  vectors.  The layers' key is split in eight, in the order ``wq wk wv wo
  w_router w_gate w_up w_down``; every matrix is ``normal(key, shape,
  float32) * shape[-2] ** -0.5`` cast to ``dtype``, drawn, scaled and cast
  in one compiled program: the four attention matrices and the router
  stacked ``[layer, in, out]``; an expert stack ``[layer, expert, in,
  out]`` A LAYER AT A TIME, its key split in ``num_hidden_layers`` and
  layer ``l``'s ``[expert, in, out]`` drawn from key ``l`` (2.4 G elements
  do not fit the chip as one float32 draw).  The embedding ``[vocab,
  hidden]`` and the head ``[hidden, vocab]`` are the same draw (the head
  is never tied);
- the vectors' key is split in five: ``ln_attn`` (``[layer, hidden]``),
  ``q_norm``, ``k_norm`` (``[layer, head_dim]``), ``ln_mlp`` and the final
  norm (``[hidden]``), each ``1 + 0.1 * normal`` cast to ``dtype``: ones
  would hide a swapped or a dropped norm;
- ``bits`` 8: the four attention matrices and the three expert stacks are
  held as whole numbers of ``-127..127`` with one scale an output column
  (and, in a stack, an expert); the router, the embedding, the head and
  the norms stay as drawn.  ``make(config_doc, bits=4)`` is the control's.

**``adapt(params, config_doc)`` maps layout only** (the program's tree is
already this one), for ``tools/weights_check.py`` and the tests.
``PROGRAM_CONFIG`` ties every ``architecture`` key of a configuration's
file to the program's model configuration (``operator_tpu.models.
get_config``).
"""

from __future__ import annotations

from typing import Any, Optional

from .decoder_f32_weights import Weights, _dense_fn, _quantise_fn

#: ``architecture`` key -> attribute of the program's ``SdarConfig``
PROGRAM_CONFIG = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "norm_topk_prob": "norm_topk_prob",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_norm_eps",
    "hidden_act": "hidden_act",
    "block_length": "block_length",
    "mask_token_id": "mask_token_id",
}

ATTENTION = ("wq", "wk", "wv", "wo")
EXPERTS = ("w_gate", "w_up", "w_down")
#: the key split's order
MATRICES = ATTENTION + ("w_router",) + EXPERTS
VECTORS = ("ln_attn", "q_norm", "k_norm", "ln_mlp")


def matrix_shapes(arch: dict) -> dict:
    """Stacked shapes, in the order the layers' key is split."""
    n, h = int(arch["num_hidden_layers"]), int(arch["hidden_size"])
    f, e = int(arch["moe_intermediate_size"]), int(arch["num_experts"])
    heads, kv, d = (
        int(arch["num_attention_heads"]), int(arch["num_key_value_heads"]),
        int(arch["head_dim"]),
    )
    return {
        "wq": (n, h, heads * d), "wk": (n, h, kv * d), "wv": (n, h, kv * d),
        "wo": (n, heads * d, h), "w_router": (n, h, e),
        "w_gate": (n, e, h, f), "w_up": (n, e, h, f), "w_down": (n, e, f, h),
    }


def widen(leaf: Any, *index: int) -> Any:
    """``leaf[index]`` in float32: a stored matrix, or an int8 group's
    whole numbers times its column scales."""
    import jax.numpy as jnp

    if isinstance(leaf, dict):
        q, s = leaf["q"][index], leaf["s"][index]
        return q.astype(jnp.float32) * s.astype(jnp.float32)[..., None, :]
    return leaf[index].astype(jnp.float32)


def adapt(params: Any, config_doc: dict) -> Weights:
    """The program's tree under the reference's interface: layout only."""
    del config_doc  # nothing is cut: the program holds every layer it runs
    leaves = {k: v for k, v in params.items() if k != "layers"}
    leaves["layers"] = {name: params["layers"][name] for name in MATRICES + VECTORS}
    return Weights(leaves)


def make(config_doc: dict, bits: Optional[int] = None, like: Optional[Weights] = None) -> Weights:
    """The reference's own weights for this configuration, at ``bits``
    (the file's unless given: the control asks for fewer, and shares the
    leaves outside the layers of ``like``)."""
    import jax
    import jax.numpy as jnp

    arch, recipe = config_doc["architecture"], config_doc["weights"]
    if recipe.get("init") != "sdar_fan_in":
        raise ValueError(f"sdar_f32_weights knows no init {recipe.get('init')!r}")
    if arch["tie_word_embeddings"]:
        raise ValueError("sdar_f32_weights makes an untied head only")
    dtype = jnp.dtype(str(recipe["dtype"]))
    bits = int(recipe.get("bits") or 0) if bits is None else int(bits)
    n, h, vocab = (
        int(arch["num_hidden_layers"]), int(arch["hidden_size"]), int(arch["vocab_size"])
    )
    k_embed, k_layers, k_head, k_vectors = jax.random.split(
        jax.random.PRNGKey(int(recipe["seed"])), 4
    )
    *k_norms, k_final = jax.random.split(k_vectors, len(VECTORS) + 1)

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
                layers[name] = _dense_fn(shape, dtype.name)(key)
            elif name in EXPERTS:
                each = [matrix(k, shape[1:]) for k in jax.random.split(key, n)]
                layers[name] = jax.block_until_ready(
                    jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *each)
                )
                del each
            else:
                layers[name] = matrix(key, shape)
        for name, key in zip(VECTORS, k_norms):
            width = int(arch["head_dim"]) if name in ("q_norm", "k_norm") else h
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
