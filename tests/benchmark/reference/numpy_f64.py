"""A second reference for the rehearsal, written apart from
``benchmark/reference/decoder_f32.py``: the same Llama-style decoder in
numpy float64, one sequence, one head and one layer at a time, nothing
padded, nothing jitted.  It is here to show that a configuration's
reference is found by the file's ``reference`` key under any of the
manifest's ``paths`` (``tests/benchmark/rehearsal-reference.json``), with
an adapter and an architecture table of its own and nothing edited under
``benchmark/``.  Far too slow for a cell; exact enough for a tiny one.
"""

from __future__ import annotations

import numpy as np

#: the adapter beside this file
WEIGHTS = "numpy_weights"


def half_rotation(x: np.ndarray, angles: np.ndarray) -> np.ndarray:
    """Rotary embedding as the published implementation applies it: the
    pairs are ``(x[i], x[i + D/2])``.  ``x [T, D]``, ``angles [T, D/2]``."""
    half = x.shape[-1] // 2
    x1, x2 = x[:, :half], x[:, half:]
    cos, sin = np.cos(angles), np.sin(angles)
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def rms_norm(x: np.ndarray, scale: np.ndarray, eps: float) -> np.ndarray:
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def forward(arch: dict, weights: dict, ids: list, rotate=half_rotation) -> np.ndarray:
    """Logits ``[len(ids), vocab]`` of one sequence."""
    heads, kv_heads = int(arch["num_attention_heads"]), int(arch["num_key_value_heads"])
    dim, eps = int(arch["head_dim"]), float(arch["rms_norm_eps"])
    length = len(ids)
    inv_freq = float(arch["rope_theta"]) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    angles = np.arange(length, dtype=np.float64)[:, None] * inv_freq[None, :]
    allowed = np.tril(np.ones((length, length), bool))
    x = weights["embed"][np.asarray(ids)]
    for layer in weights["layers"]:
        h = rms_norm(x, layer["ln_attn"], eps)
        q = h @ layer["wq"] + layer.get("bq", 0.0)
        k = h @ layer["wk"] + layer.get("bk", 0.0)
        v = h @ layer["wv"] + layer.get("bv", 0.0)
        mixed = np.zeros((length, heads * dim))
        for head in range(heads):
            shared = head // (heads // kv_heads)  # the KV head this query head reads
            q_h = rotate(q[:, head * dim:(head + 1) * dim], angles)
            k_h = rotate(k[:, shared * dim:(shared + 1) * dim], angles)
            scores = np.where(allowed, q_h @ k_h.T / np.sqrt(dim), -np.inf)
            weight = np.exp(scores - scores.max(axis=-1, keepdims=True))
            weight /= weight.sum(axis=-1, keepdims=True)
            mixed[:, head * dim:(head + 1) * dim] = weight @ v[:, shared * dim:(shared + 1) * dim]
        x = x + mixed @ layer["wo"]
        h = rms_norm(x, layer["ln_mlp"], eps)
        gate = h @ layer["w_gate"]
        x = x + (gate / (1.0 + np.exp(-gate)) * (h @ layer["w_up"])) @ layer["w_down"]
    x = rms_norm(x, weights["ln_final"], eps)
    head_matrix = weights["embed"].T if weights["head"] is None else weights["head"]
    return x @ head_matrix


def greedy_gaps(config_doc: dict, weights: dict, sequences: list, rotate=half_rotation) -> list:
    """The interface of ``benchmark/README.md``, "A reference"."""
    arch = config_doc["architecture"]
    out = []
    for prompt_ids, chosen in sequences:
        scored = forward(arch, weights, list(prompt_ids) + list(chosen), rotate)
        first = len(prompt_ids) - 1
        out.append([
            float(scored[first + i].max() - scored[first + i, token])
            for i, token in enumerate(chosen)
        ])
    return out
