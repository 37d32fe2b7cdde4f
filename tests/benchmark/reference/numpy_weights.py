"""The weights of ``numpy_f64``.  ``make(config_doc)`` makes them from the
recipe of the file's ``weights`` group, written apart from
``benchmark/reference/decoder_f32_weights.py``: the draws come from
``jax.random`` (a library, as for any reader of the recipe), everything
after them is numpy, the quantiser included.  ``adapt`` maps the program's
Llama-family parameter tree to the same plain float64 (layout only: the
tests' parity checks use it, the harness never does), and
``PROGRAM_CONFIG`` holds a configuration file's ``architecture`` keys to
the program's ``ModelConfig``."""

from __future__ import annotations

import numpy as np

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

_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
_VECTORS = ("ln_attn", "ln_mlp", "bq", "bk", "bv")


def _plain(leaf, index=None) -> np.ndarray:
    if isinstance(leaf, dict):
        q = np.asarray(leaf["q"] if index is None else leaf["q"][index], np.float64)
        s = np.asarray(leaf["s"] if index is None else leaf["s"][index], np.float64)
        return q * s[None, :]
    return np.asarray(leaf if index is None else leaf[index], np.float64)


def adapt(params, config_doc: dict) -> dict:
    layers = params["layers"]
    count = int(config_doc["architecture"]["num_hidden_layers"])
    return {
        "embed": _plain(params["embed"]),
        "ln_final": _plain(params["ln_final"]),
        "head": _plain(params["lm_head"]) if "lm_head" in params else None,
        "layers": [
            {
                **{name: _plain(layers[name], i) for name in _MATRICES},
                **{name: _plain(layers[name], i) for name in _VECTORS if name in layers},
            }
            for i in range(count)
        ],
    }


def _draw(key, shape) -> np.ndarray:
    """``normal * fan_in ** -0.5`` rounded to bfloat16, as float32."""
    import jax
    import jax.numpy as jnp

    drawn = jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5
    return np.asarray(drawn.astype(jnp.bfloat16).astype(jnp.float32))


def _rounded(w: np.ndarray, levels: int) -> np.ndarray:
    """One scale an output column, whole numbers of ``-levels..levels``:
    float32 arithmetic, as the recipe states it, then float64."""
    scale = np.maximum(np.abs(w).max(axis=0), np.float32(1e-8)) / np.float32(levels)
    q = np.clip(np.round(w / scale[None, :]), -levels, levels)
    return q.astype(np.float64) * scale.astype(np.float64)[None, :]


def make(config_doc: dict) -> dict:
    import jax

    arch, recipe = config_doc["architecture"], config_doc["weights"]
    assert recipe["init"] == "normal_fan_in" and recipe["dtype"] == "bfloat16", recipe
    n, h, f = (int(arch[k]) for k in ("num_hidden_layers", "hidden_size", "intermediate_size"))
    heads, kv, d = (
        int(arch[k]) for k in ("num_attention_heads", "num_key_value_heads", "head_dim")
    )
    vocab, levels = int(arch["vocab_size"]), 2 ** (int(recipe["bits"]) - 1) - 1
    k_embed, k_layers, k_head = jax.random.split(jax.random.PRNGKey(int(recipe["seed"])), 3)
    shapes = {
        "wq": (n, h, heads * d), "wk": (n, h, kv * d), "wv": (n, h, kv * d),
        "wo": (n, heads * d, h), "w_gate": (n, h, f), "w_up": (n, h, f), "w_down": (n, f, h),
    }
    stacked = {
        name: _draw(key, shape)
        for key, (name, shape) in zip(jax.random.split(k_layers, len(shapes)), shapes.items())
    }
    ones = np.ones(h)
    return {
        "embed": _draw(k_embed, (vocab, h)).astype(np.float64),
        "ln_final": ones,
        "head": None if arch["tie_word_embeddings"]
        else _draw(k_head, (h, vocab)).astype(np.float64),
        "layers": [
            {
                **{name: _rounded(stacked[name][i], levels) for name in _MATRICES},
                "ln_attn": ones, "ln_mlp": ones,
            }
            for i in range(n)
        ],
    }
