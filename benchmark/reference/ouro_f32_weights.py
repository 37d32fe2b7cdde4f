"""The weights of ``ouro_f32``, made here and nowhere else.

**``make(config_doc)`` makes the reference's own weights** from the recipe
the configuration's file states (its ``weights`` group), with nothing of
the program imported and nothing the program made read; the quantiser and
the compiled draw of a matrix are ``decoder_f32_weights``' (a file of the
benchmark, beside this one).

The recipe (``"weights": {"seed", "init": "ouro_fan_in", "dtype",
"bits"}``; it is the program's ``allow_random_weights`` recipe for this
family, ``operator_tpu/models/ouro.py init_params``, written down here as
a published checkpoint's would be):

- ``key = PRNGKey(seed)``, split in FOUR: embedding, layer matrices, head,
  vectors.  The layers' key is split in seven, in the order ``wq wk wv wo
  w_gate w_up w_down``; each is ``normal(key, shape, float32) * shape[-2]
  ** -0.5`` cast to ``dtype``, stacked ``[layer, in, out]`` and drawn,
  scaled and cast in one compiled program; the embedding ``[vocab,
  hidden]`` and the head ``[hidden, vocab]`` the same draw, one compiled
  program each (the head is never tied);
- the vectors' key is split in seven, each operation a program of its
  own: the four norms of a layer in the order ``ln_attn ln_attn_post
  ln_mlp ln_mlp_post`` (``[layer, hidden]``) and the final norm
  (``[hidden]``) are ``gain + 0.1 * gain * normal`` cast to ``dtype``,
  with ``gain`` 1 for the norm before a branch and the final norm and 0.5
  for the two ``_post`` norms after a branch: ones would hide a swapped or
  a dropped norm, and a branch that joins at the stream's own size makes
  the random looped stack spread rounding until no fault can be told from
  it; the exit gate's weight ``normal *
  hidden ** -0.5`` (``[hidden]``) and its bias one normal draw, cast to
  ``dtype``;
- ``bits`` 8: the seven layer matrices are held as whole numbers of
  ``-127..127`` with one scale an output column; everything else stays as
  drawn.  ``make(config_doc, bits=4)`` is the control's.

**``adapt(params, config_doc)`` maps layout only** (the program's tree is
already this one: stacked layers, int8 groups ``{"q", "s"}``), for
``tools/weights_check.py`` and the tests.  ``PROGRAM_CONFIG`` ties every
``architecture`` key of a configuration's file to the program's model
configuration (``operator_tpu.models.get_config``).
"""

from __future__ import annotations

from typing import Any, Optional

from .decoder_f32_weights import Weights, _dense_fn, _quantise_fn, layer_shapes

#: ``architecture`` key -> attribute of the program's ``OuroConfig``
PROGRAM_CONFIG = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_norm_eps",
    "hidden_act": "hidden_act",
    "total_ut_steps": "total_ut_steps",
    "early_exit_threshold": "early_exit_threshold",
}

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
VECTORS = ("ln_attn", "ln_attn_post", "ln_mlp", "ln_mlp_post")
#: leaves outside the layers, beside ``embed`` and ``lm_head``
TOP = ("ln_final", "exit_w", "exit_b")


def adapt(params: Any, config_doc: dict) -> Weights:
    """The program's tree under the reference's interface: layout only."""
    del config_doc  # nothing is cut: the program holds every layer
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
    if recipe.get("init") != "ouro_fan_in":
        raise ValueError(f"ouro_f32_weights knows no init {recipe.get('init')!r}")
    if arch["tie_word_embeddings"]:
        raise ValueError("ouro_f32_weights makes an untied head only")
    dtype = jnp.dtype(str(recipe["dtype"]))
    bits = int(recipe.get("bits") or 0) if bits is None else int(bits)
    n, h, vocab = (
        int(arch["num_hidden_layers"]), int(arch["hidden_size"]), int(arch["vocab_size"])
    )
    k_embed, k_layers, k_head, k_vectors = jax.random.split(
        jax.random.PRNGKey(int(recipe["seed"])), 4
    )
    *k_norms, k_final, k_gate_w, k_gate_b = jax.random.split(k_vectors, len(VECTORS) + 3)

    def norm_scale(key, shape, gain=1.0):
        return (gain + 0.1 * gain * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    def make_layers() -> dict:
        shapes = layer_shapes(arch)
        layers = {}
        for key, (name, shape) in zip(jax.random.split(k_layers, len(shapes)), shapes.items()):
            leaf = _dense_fn(shape, dtype.name)(key)
            if bits:
                leaf = jax.block_until_ready(_quantise_fn(2 ** (bits - 1) - 1)(leaf))
            layers[name] = leaf
        for name, key in zip(VECTORS, k_norms):
            layers[name] = norm_scale(key, (n, h), 0.5 if name.endswith("_post") else 1.0)
        return layers

    if like is not None:
        leaves = {k: v for k, v in like.leaves.items() if k != "layers"}
    else:
        leaves = {
            "embed": jax.block_until_ready(_dense_fn((vocab, h), dtype.name)(k_embed)),
            "lm_head": jax.block_until_ready(_dense_fn((h, vocab), dtype.name)(k_head)),
            "ln_final": norm_scale(k_final, (h,)),
            "exit_w": (
                jax.random.normal(k_gate_w, (h,), jnp.float32) * h ** -0.5
            ).astype(dtype),
            "exit_b": jax.random.normal(k_gate_b, (), jnp.float32).astype(dtype),
        }
    made = Weights(leaves, make_layers)
    made.layers  # noqa: B018 - made now; again after a release_layers()
    return made
