"""The weights of ``olmo_hybrid_f32``, made here and nowhere else.

**``make(config_doc)`` makes the reference's own weights** from the recipe
the configuration's file states (its ``weights`` group), with nothing of
the program imported and nothing the program made read; the quantiser and
the draw of a matrix are ``decoder_f32_weights``' (a file of the benchmark,
beside this one).

The recipe (``"weights": {"seed", "init": "olmo_hybrid_fan_in", "dtype",
"bits"}``; it is the program's ``allow_random_weights`` recipe for this
family, ``operator_tpu/models/olmo_hybrid.py init_params``, written down
here as a published checkpoint's would be).  ``P`` is the number of
periods of ``layer_types``, ``nl`` and ``nf`` its linear and full layers a
period:

- ``key = PRNGKey(seed)``, split in FOUR: embedding, layer matrices, head,
  vectors.  The embedding ``[vocab, hidden]`` and the head ``[hidden,
  vocab]`` are ``normal(key, shape, float32) * shape[-2] ** -0.5`` cast to
  ``dtype``, one compiled program each;
- the layers' key is split in seventeen, in the order ``lin_wq lin_wk
  lin_wv lin_wa lin_wb lin_wg lin_wo lin_w_gate lin_w_up lin_w_down wq wk
  wv wo w_gate w_up w_down``; the ``lin_`` matrices are stacked ``[P, nl,
  in, out]``, the others ``[P, nf, in, out]``; each is the same draw, in one
  compiled program;
- the vectors' key is split in eleven: ``lin_o_norm lin_ln_attn_post
  lin_ln_mlp_post q_norm k_norm ln_attn_post ln_mlp_post``, the final
  norm, ``A_log``, ``dt_bias`` and the convolution.  Each norm is ``1 + 0.1
  x normal(float32)`` cast to ``dtype``; ``A_log = log(uniform[1, 16])``
  and ``dt_bias = softplus^-1(exp(uniform[log 1e-3, log 1e-1]))``, both
  ``[P, nl, heads]`` float32; the convolution ``normal x K^-0.5`` ``[P, nl,
  K, 2 x key dim + value dim]`` cast to ``dtype``.  Each operation by
  operation;
- ``bits`` 8: the seventeen matrices are held as whole numbers of
  ``-127..127`` with one scale an output column; everything else stays as
  drawn.  ``make(config_doc, bits=4)`` is the control's.

**``adapt(params, config_doc)`` maps layout only** (the program's tree is
already this one), for ``tools/weights_check.py`` and the tests.
``PROGRAM_CONFIG`` ties every ``architecture`` key of a configuration's
file to the program's model configuration (``operator_tpu.models
.get_config``).
"""

from __future__ import annotations

from typing import Any, Optional

from .decoder_f32_weights import Weights, _dense_fn, _quantise_fn

#: ``architecture`` key -> attribute of the program's ``OlmoHybridConfig``
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
    "layer_types": "layer_types_list",
    "linear_num_key_heads": "linear_num_key_heads",
    "linear_num_value_heads": "linear_num_value_heads",
    "linear_key_head_dim": "linear_key_head_dim",
    "linear_value_head_dim": "linear_value_head_dim",
    "linear_conv_kernel_dim": "linear_conv_kernel_dim",
    "linear_allow_neg_eigval": "linear_allow_neg_eigval",
}

MATRICES = (
    "lin_wq", "lin_wk", "lin_wv", "lin_wa", "lin_wb", "lin_wg", "lin_wo",
    "lin_w_gate", "lin_w_up", "lin_w_down",
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
)
NORMS = (
    "lin_o_norm", "lin_ln_attn_post", "lin_ln_mlp_post",
    "q_norm", "k_norm", "ln_attn_post", "ln_mlp_post",
)
VECTORS = NORMS + ("lin_a_log", "lin_dt_bias", "lin_conv_w")


def period(arch: dict) -> list:
    """The shortest prefix of ``layer_types`` that repeats to make it."""
    types = list(arch["layer_types"])
    for size in range(1, len(types) + 1):
        if len(types) % size == 0 and types == types[:size] * (len(types) // size):
            return types[:size]
    return types


def placement(arch: dict) -> list:
    """Per layer ``(linear?, period, position among its kind in the
    period)``: where its leaves lie in the stacks."""
    one = period(arch)
    out = []
    for index, kind in enumerate(arch["layer_types"]):
        p, at = divmod(index, len(one))
        out.append((kind == "linear_attention", p, one[:at].count(kind)))
    return out


class HybridWeights(Weights):
    """``decoder_f32_weights.Weights`` whose layer leaves are stacked by
    period and kind: ``layer_at(linear, p, j)`` widens one layer's."""

    def layer_at(self, linear: bool, p: int, j: int) -> dict:
        import jax.numpy as jnp

        out = {}
        for name, leaf in self.layers.items():
            if name.startswith("lin_") != linear:
                continue
            key = name[4:] if linear else name
            if isinstance(leaf, dict):
                out[key] = (
                    leaf["q"][p, j].astype(jnp.float32)
                    * leaf["s"][p, j].astype(jnp.float32)[None, :]
                )
            else:
                out[key] = leaf[p, j].astype(jnp.float32)
        return out


def adapt(params: Any, config_doc: dict) -> HybridWeights:
    """The program's tree under the reference's interface: layout only.  A
    held ``{qt, s}`` (the continuous path's q, k and v of the full layers)
    is turned back to ``{q, s}``."""
    import jax.numpy as jnp

    del config_doc  # nothing is cut: the program holds exactly these layers
    layers = {}
    for name in MATRICES + VECTORS:
        leaf = params["layers"][name]
        if isinstance(leaf, dict) and "qt" in leaf:
            leaf = {"q": jnp.swapaxes(leaf["qt"], -1, -2), "s": leaf["s"]}
        layers[name] = leaf
    leaves = {k: v for k, v in params.items() if k != "layers"}
    leaves["layers"] = layers
    return HybridWeights(leaves)


def layer_shapes(arch: dict) -> dict:
    """Stacked shapes, in the order the keys are split."""
    one = period(arch)
    p = len(arch["layer_types"]) // len(one)
    lin = (p, one.count("linear_attention"))
    full = (p, one.count("full_attention"))
    h, f = int(arch["hidden_size"]), int(arch["intermediate_size"])
    heads = int(arch["linear_num_value_heads"])
    kd = int(arch["linear_num_key_heads"]) * int(arch["linear_key_head_dim"])
    vd = heads * int(arch["linear_value_head_dim"])
    qd = int(arch["num_attention_heads"]) * int(arch["head_dim"])
    kvd = int(arch["num_key_value_heads"]) * int(arch["head_dim"])
    return {
        "lin_wq": (*lin, h, kd), "lin_wk": (*lin, h, kd), "lin_wv": (*lin, h, vd),
        "lin_wa": (*lin, h, heads), "lin_wb": (*lin, h, heads), "lin_wg": (*lin, h, vd),
        "lin_wo": (*lin, vd, h), "lin_w_gate": (*lin, h, f), "lin_w_up": (*lin, h, f),
        "lin_w_down": (*lin, f, h),
        "wq": (*full, h, qd), "wk": (*full, h, kvd), "wv": (*full, h, kvd),
        "wo": (*full, qd, h), "w_gate": (*full, h, f), "w_up": (*full, h, f),
        "w_down": (*full, f, h),
    }


def make(config_doc: dict, bits: Optional[int] = None,
         like: Optional[Weights] = None) -> HybridWeights:
    """The reference's own weights for this configuration, at ``bits`` (the
    file's unless given: the control asks for fewer, and shares the
    embedding, the head and the final norm of ``like``)."""
    import jax
    import jax.numpy as jnp

    arch, recipe = config_doc["architecture"], config_doc["weights"]
    if recipe.get("init") != "olmo_hybrid_fan_in":
        raise ValueError(f"olmo_hybrid_f32_weights knows no init {recipe.get('init')!r}")
    dtype = jnp.dtype(str(recipe["dtype"]))
    bits = int(recipe.get("bits") or 0) if bits is None else int(bits)
    h, vocab = int(arch["hidden_size"]), int(arch["vocab_size"])
    shapes = layer_shapes(arch)
    lin, full = shapes["lin_wq"][:2], shapes["wq"][:2]
    heads, width = int(arch["linear_num_value_heads"]), int(arch["linear_conv_kernel_dim"])
    widths = {
        "lin_o_norm": (*lin, int(arch["linear_value_head_dim"])),
        "lin_ln_attn_post": (*lin, h), "lin_ln_mlp_post": (*lin, h),
        "q_norm": (*full, shapes["wq"][-1]), "k_norm": (*full, shapes["wk"][-1]),
        "ln_attn_post": (*full, h), "ln_mlp_post": (*full, h),
    }
    k_embed, k_layers, k_head, k_vectors = jax.random.split(
        jax.random.PRNGKey(int(recipe["seed"])), 4
    )
    *k_norms, k_final, k_a, k_dt, k_conv = jax.random.split(k_vectors, len(NORMS) + 4)

    def norm_scale(key, shape):
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    def make_layers() -> dict:
        layers = {}
        for key, (name, shape) in zip(jax.random.split(k_layers, len(shapes)), shapes.items()):
            leaf = _dense_fn(shape, str(dtype))(key)
            if bits:
                leaf = jax.block_until_ready(_quantise_fn(2 ** (bits - 1) - 1)(leaf))
            layers[name] = leaf
        for name, key in zip(NORMS, k_norms):
            layers[name] = norm_scale(key, widths[name])
        layers["lin_a_log"] = jnp.log(
            jax.random.uniform(k_a, (*lin, heads), jnp.float32, 1.0, 16.0)
        )
        steps = jnp.exp(jax.random.uniform(
            k_dt, (*lin, heads), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)
        ))
        layers["lin_dt_bias"] = steps + jnp.log(-jnp.expm1(-steps))  # softplus^-1
        channels = shapes["lin_wq"][-1] + shapes["lin_wk"][-1] + shapes["lin_wv"][-1]
        layers["lin_conv_w"] = (
            jax.random.normal(k_conv, (*lin, width, channels), jnp.float32) * width ** -0.5
        ).astype(dtype)
        return layers

    if like is not None:
        leaves = {k: v for k, v in like.leaves.items() if k != "layers"}
    else:
        leaves = {
            "embed": jax.block_until_ready(_dense_fn((vocab, h), str(dtype))(k_embed)),
            "lm_head": jax.block_until_ready(_dense_fn((h, vocab), str(dtype))(k_head)),
            "ln_final": norm_scale(k_final, (h,)),
        }
    made = HybridWeights(leaves, make_layers)
    made.layers  # noqa: B018 - made now; again after a release_layers()
    return made
