"""The weights of ``decoder_f32``, made here and nowhere else.

**``make(config_doc)`` makes the reference's own weights** from the recipe
the configuration's file states (its ``weights`` group), with nothing of
the program imported and nothing the program made read: a seeded normal
init scaled by fan-in, in bfloat16, and this file's own per-channel
quantiser.  What decides ``correct`` reads only these; a wrong scale, a
mis-quantised channel or another init in the program then shows as a gap,
where a reference fed the program's own ``q`` and ``s`` would read 0.

The recipe, as the configuration states it (``"weights": {"seed", "init",
"dtype", "bits"}``; it is the program's ``allow_random_weights`` recipe,
written down here as a published checkpoint's would be):

- ``key = PRNGKey(seed)``, split in three: embedding, layers, head; the
  layers' key split in seven, in the order ``wq wk wv wo w_gate w_up
  w_down``; each leaf is ``normal(key, shape, float32) * shape[-2] ** -0.5``
  cast to ``dtype`` -- layer matrices stacked ``[layer, in, out]``, the
  embedding ``[vocab, hidden]`` (so its factor is ``vocab ** -0.5``), the
  head ``[hidden, vocab]`` and absent where ``tie_word_embeddings``.  A
  stacked layer matrix is drawn, scaled and cast in one compiled program,
  the embedding and the head operation by operation: compiled together, the
  chip's compiler folds the factor into the draw, which moves one element in
  200,000 by one bfloat16 step (``tools/weights_check.py``, on the chip);
- norms are ones, q/k/v biases zeros;
- ``bits`` 8: every layer matrix is held as whole numbers of ``-127..127``
  with one scale an output column, ``scale = max|column| / 127``, ``q =
  round(w / scale)``; embedding, head and norms stay in ``dtype``.

``make(config_doc, bits=4)`` is the control's: the same init at the
nearest precision below (``-7..7``, ``scale = max|column| / 7``).

**``adapt(params, config_doc)`` maps layout only**: the program's tree
(stacked layers, int8 groups ``{"q", "s"}``) as the same interface.  It is
for ``tools/weights_check.py``, the one-off check that the program's
leaves equal these bit for bit, and for the tests, which set biases no
init makes; the harness never calls it.  ``PROGRAM_CONFIG`` is the table
that ties a configuration file's ``architecture`` keys to the program's
model configuration.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

#: ``architecture`` key of a configuration file -> attribute of the
#: program's ``ModelConfig`` (``operator_tpu.models.get_config``) that must
#: equal it; ``tests/benchmark/test_benchmark.py`` holds every
#: configuration that names this reference to this table
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
}

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
VECTORS = ("ln_attn", "ln_mlp", "bq", "bk", "bv")


class Weights:
    """What ``decoder_f32`` reads: ``embed``, ``head`` (None where tied),
    ``ln_final`` and ``layer(i)`` as float32.  ``leaves`` holds them as
    they are stored: a layer matrix is ``{"q", "s"}`` (whole numbers and a
    scale an output column) or a float array, stacked on axis 0."""

    def __init__(self, leaves: dict, make_layers: Any = None) -> None:
        self.leaves = leaves
        self._make_layers = make_layers

    @property
    def embed(self) -> Any:
        return self.leaves["embed"]

    @property
    def ln_final(self) -> Any:
        return self.leaves["ln_final"]

    @property
    def head(self) -> Optional[Any]:
        """``[hidden, vocab]``, or None where the head is the embedding."""
        return self.leaves.get("lm_head")

    @property
    def layers(self) -> dict:
        """The stacked layer leaves, made again where they were released."""
        if "layers" not in self.leaves:
            self.leaves["layers"] = self._make_layers()
        return self.leaves["layers"]

    def release_layers(self) -> None:
        """Free the layer leaves (the control makes a second set, and two
        7B sets do not fit one chip beside each other)."""
        self.leaves.pop("layers", None)

    def layer(self, index: int) -> dict:
        import jax.numpy as jnp

        out = {}
        for name, leaf in self.layers.items():
            if isinstance(leaf, dict):
                out[name] = (
                    leaf["q"][index].astype(jnp.float32)
                    * leaf["s"][index].astype(jnp.float32)[None, :]
                )
            else:
                out[name] = leaf[index].astype(jnp.float32)
        return out


def adapt(params: Any, config_doc: dict) -> Weights:
    """The program's tree under the same interface: layout only.
    ``config_doc`` is the configuration's whole file (a cut
    configuration's adapter reads its share from it)."""
    del config_doc  # nothing is cut in the configurations that name this one
    leaves = {k: v for k, v in params.items() if k != "layers"}
    leaves["layers"] = {
        name: params["layers"][name]
        for name in MATRICES + VECTORS if name in params["layers"]
    }
    return Weights(leaves)


# -- the reference's own weights ----------------------------------------------


@functools.lru_cache(maxsize=None)
def _dense_fn(shape: tuple, dtype: str) -> Any:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def dense(key):
        drawn = jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5
        return drawn.astype(jnp.dtype(dtype))

    return dense


@functools.lru_cache(maxsize=None)
def _quantise_fn(levels: int) -> Any:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def quantise(w):  # [layer, in, out]
        w32 = w.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(w32), axis=-2), 1e-8) / levels
        q = jnp.clip(jnp.round(w32 / scale[..., None, :]), -levels, levels)
        return {"q": q.astype(jnp.int8), "s": scale}

    return quantise


def _dense_by_steps(key: Any, shape: tuple, dtype: str) -> Any:
    """The same draw, each operation a program of its own (the embedding
    and the head: the module's text says why)."""
    import jax
    import jax.numpy as jnp

    drawn = jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5
    return drawn.astype(jnp.dtype(dtype))


def layer_shapes(arch: dict) -> dict:
    """Stacked ``[layer, in, out]`` shapes, in the order the keys are split."""
    n, h = int(arch["num_hidden_layers"]), int(arch["hidden_size"])
    f, heads = int(arch["intermediate_size"]), int(arch["num_attention_heads"])
    kv, d = int(arch["num_key_value_heads"]), int(arch.get("head_dim") or h // heads)
    return {
        "wq": (n, h, heads * d), "wk": (n, h, kv * d), "wv": (n, h, kv * d),
        "wo": (n, heads * d, h), "w_gate": (n, h, f), "w_up": (n, h, f),
        "w_down": (n, f, h),
    }


def make(config_doc: dict, bits: Optional[int] = None, like: Optional[Weights] = None) -> Weights:
    """The reference's own weights for this configuration: the recipe of
    its ``weights`` group, at ``bits`` (the file's unless given: the
    control asks for fewer, and shares the embedding, head and norms of
    ``like``).  Layer matrices are made one stacked matrix at a time, so
    that at most one exists in float."""
    import jax
    import jax.numpy as jnp

    arch, recipe = config_doc["architecture"], config_doc["weights"]
    if recipe.get("init") != "normal_fan_in":
        raise ValueError(f"decoder_f32_weights knows no init {recipe.get('init')!r}")
    dtype = str(recipe["dtype"])
    bits = int(recipe.get("bits") or 0) if bits is None else int(bits)
    n, h, vocab = (
        int(arch["num_hidden_layers"]), int(arch["hidden_size"]), int(arch["vocab_size"])
    )
    k_embed, k_layers, k_head = jax.random.split(jax.random.PRNGKey(int(recipe["seed"])), 3)

    def make_layers() -> dict:
        shapes = layer_shapes(arch)
        layers = {}
        for key, (name, shape) in zip(jax.random.split(k_layers, len(shapes)), shapes.items()):
            leaf = _dense_fn(shape, dtype)(key)
            if bits:
                leaf = jax.block_until_ready(_quantise_fn(2 ** (bits - 1) - 1)(leaf))
            layers[name] = leaf
        layers["ln_attn"] = layers["ln_mlp"] = jnp.ones((n, h), jnp.dtype(dtype))
        return layers

    if like is not None:
        leaves = {k: v for k, v in like.leaves.items() if k != "layers"}
    else:
        leaves = {
            "embed": _dense_by_steps(k_embed, (vocab, h), dtype),
            "ln_final": jnp.ones((h,), jnp.dtype(dtype)),
        }
        if not arch["tie_word_embeddings"]:
            leaves["lm_head"] = _dense_by_steps(k_head, (h, vocab), dtype)
    made = Weights(leaves, make_layers)
    made.layers  # noqa: B018 - made now; again after a release_layers()
    return made
