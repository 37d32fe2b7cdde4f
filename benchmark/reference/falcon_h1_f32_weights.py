"""The weights of ``falcon_h1_f32``, made here and nowhere else.

**``make(config_doc)`` makes the reference's own weights** from the recipe
the configuration's file states (its ``weights`` group), with nothing of
the program imported and nothing the program made read; the quantiser and
the two ways of drawing a matrix are ``decoder_f32_weights``' (a file of
the benchmark, beside this one).

The recipe (``"weights": {"seed", "init": "falcon_h1_fan_in", "dtype",
"bits"}``; it is the program's ``allow_random_weights`` recipe for this
family, ``operator_tpu/models/falcon_h1.py init_params``, written down
here as a published checkpoint's would be):

- ``key = PRNGKey(seed)``, split in FOUR: embedding, layer matrices, head,
  mixer vectors.  The layers' key is split in nine, in the order ``wq wk
  wv wo w_gate w_up w_down w_in w_out``; each is ``normal(key, shape,
  float32) * shape[-2] ** -0.5`` cast to ``dtype``, stacked ``[layer, in,
  out]`` and drawn, scaled and cast in one compiled program; the embedding
  ``[vocab, hidden]`` and the head ``[hidden, vocab]`` the same draw, in
  one compiled program each too (operation by operation a 1.3 B-element
  leaf is 5.3 GB in float32 twice over: 16.04 GB of the chip's 16.9 at the
  head's draw, my chip run, PR 29) and waited for;
- the mixer's key is split in four: ``A_log = log(uniform[1, 16])``,
  ``dt_bias = softplus^-1(exp(uniform[log 1e-3, log 1e-1]))``, both
  ``[layer, heads]`` float32; convolution weights ``normal x d_conv^-0.5``
  ``[layer, d_conv, conv_dim]`` and a non-zero convolution bias ``normal x
  0.02`` ``[layer, conv_dim]``, both cast to ``dtype``; ``D`` is ones
  (float32); the three norms are ones.  Each operation by operation;
- ``bits`` 8: the nine layer matrices are held as whole numbers of
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

from .decoder_f32_weights import Weights, _dense_fn, _quantise_fn

#: ``architecture`` key -> attribute of the program's ``FalconH1Config``
PROGRAM_CONFIG = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "attention_bias",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_norm_eps",
    "hidden_act": "hidden_act",
    "mlp_bias": "mlp_bias",
    "projectors_bias": "projectors_bias",
    "mamba_d_ssm": "mamba_d_ssm",
    "mamba_n_heads": "mamba_n_heads",
    "mamba_d_head": "mamba_d_head",
    "mamba_d_state": "mamba_d_state",
    "mamba_n_groups": "mamba_n_groups",
    "mamba_d_conv": "mamba_d_conv",
    "mamba_expand": "mamba_expand",
    "mamba_conv_bias": "mamba_conv_bias",
    "mamba_proj_bias": "mamba_proj_bias",
    "mamba_rms_norm": "mamba_rms_norm",
    "mamba_norm_before_gate": "mamba_norm_before_gate",
    "embedding_multiplier": "embedding_multiplier",
    "lm_head_multiplier": "lm_head_multiplier",
    "attention_in_multiplier": "attention_in_multiplier",
    "attention_out_multiplier": "attention_out_multiplier",
    "key_multiplier": "key_multiplier",
    "ssm_in_multiplier": "ssm_in_multiplier",
    "ssm_out_multiplier": "ssm_out_multiplier",
    "mlp_multipliers": "mlp_multipliers_list",
    "ssm_multipliers": "ssm_multipliers_list",
}

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "w_in", "w_out")
VECTORS = (
    "a_log", "dt_bias", "d_skip", "conv_w", "conv_b", "ln_attn", "ln_mlp", "ln_ssm",
)


def adapt(params: Any, config_doc: dict) -> Weights:
    """The program's tree under the reference's interface: layout only."""
    del config_doc  # the cut is in depth: the program holds exactly these layers
    leaves = {k: v for k, v in params.items() if k != "layers"}
    leaves["layers"] = {name: params["layers"][name] for name in MATRICES + VECTORS}
    return Weights(leaves)


def conv_dim(arch: dict) -> int:
    return int(arch["mamba_d_ssm"]) + 2 * int(arch["mamba_n_groups"]) * int(arch["mamba_d_state"])


def layer_shapes(arch: dict) -> dict:
    """Stacked ``[layer, in, out]`` shapes, in the order the keys are split."""
    n, h = int(arch["num_hidden_layers"]), int(arch["hidden_size"])
    f, heads = int(arch["intermediate_size"]), int(arch["num_attention_heads"])
    kv, d = int(arch["num_key_value_heads"]), int(arch["head_dim"])
    d_ssm = int(arch["mamba_d_ssm"])
    return {
        "wq": (n, h, heads * d), "wk": (n, h, kv * d), "wv": (n, h, kv * d),
        "wo": (n, heads * d, h), "w_gate": (n, h, f), "w_up": (n, h, f),
        "w_down": (n, f, h),
        "w_in": (n, h, d_ssm + conv_dim(arch) + int(arch["mamba_n_heads"])),
        "w_out": (n, d_ssm, h),
    }


def make(config_doc: dict, bits: Optional[int] = None, like: Optional[Weights] = None) -> Weights:
    """The reference's own weights for this configuration, at ``bits``
    (the file's unless given: the control asks for fewer, and shares the
    embedding, the head and the final norm of ``like``)."""
    import jax
    import jax.numpy as jnp

    arch, recipe = config_doc["architecture"], config_doc["weights"]
    if recipe.get("init") != "falcon_h1_fan_in":
        raise ValueError(f"falcon_h1_f32_weights knows no init {recipe.get('init')!r}")
    dtype = str(recipe["dtype"])
    bits = int(recipe.get("bits") or 0) if bits is None else int(bits)
    n, h, vocab = (
        int(arch["num_hidden_layers"]), int(arch["hidden_size"]), int(arch["vocab_size"])
    )
    heads, width = int(arch["mamba_n_heads"]), int(arch["mamba_d_conv"])
    k_embed, k_layers, k_head, k_mixer = jax.random.split(
        jax.random.PRNGKey(int(recipe["seed"])), 4
    )

    def make_layers() -> dict:
        shapes = layer_shapes(arch)
        layers = {}
        for key, (name, shape) in zip(jax.random.split(k_layers, len(shapes)), shapes.items()):
            leaf = _dense_fn(shape, dtype)(key)
            if bits:
                leaf = jax.block_until_ready(_quantise_fn(2 ** (bits - 1) - 1)(leaf))
            layers[name] = leaf
        k_a, k_dt, k_conv, k_bias = jax.random.split(k_mixer, 4)
        layers["a_log"] = jnp.log(jax.random.uniform(k_a, (n, heads), jnp.float32, 1.0, 16.0))
        steps = jnp.exp(jax.random.uniform(
            k_dt, (n, heads), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)
        ))
        layers["dt_bias"] = steps + jnp.log(-jnp.expm1(-steps))  # softplus^-1
        layers["d_skip"] = jnp.ones((n, heads), jnp.float32)
        layers["conv_w"] = (
            jax.random.normal(k_conv, (n, width, conv_dim(arch)), jnp.float32) * width ** -0.5
        ).astype(jnp.dtype(dtype))
        layers["conv_b"] = (
            jax.random.normal(k_bias, (n, conv_dim(arch)), jnp.float32) * 0.02
        ).astype(jnp.dtype(dtype))
        layers["ln_attn"] = layers["ln_mlp"] = jnp.ones((n, h), jnp.dtype(dtype))
        layers["ln_ssm"] = jnp.ones((n, int(arch["mamba_d_ssm"])), jnp.dtype(dtype))
        return layers

    if like is not None:
        leaves = {k: v for k, v in like.leaves.items() if k != "layers"}
    else:
        leaves = {"embed": jax.block_until_ready(_dense_fn((vocab, h), dtype)(k_embed))}
        if not arch["tie_word_embeddings"]:
            leaves["lm_head"] = jax.block_until_ready(_dense_fn((h, vocab), dtype)(k_head))
        leaves["ln_final"] = jnp.ones((h,), jnp.dtype(dtype))
    made = Weights(leaves, make_layers)
    made.layers  # noqa: B018 - made now; again after a release_layers()
    return made
