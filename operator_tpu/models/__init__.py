"""JAX model implementations (SURVEY.md §7 stages 3-4): the Llama-family
decoder (TinyLlama-1.1B / Llama-3-8B / Mistral-7B) and, in ``encoder``, the
MiniLM-class sentence-embedding encoder for semantic pattern matching.

Import of this package must not require an accelerator; jax is imported at
module level but devices are only touched when arrays are created."""

from .configs import (
    FalconH1Config,
    LLAMA_3_8B,
    MISTRAL_7B,
    TINY_TEST,
    TINYLLAMA_1_1B,
    ModelConfig,
    get_config,
    register_config,
    scaled,
)
from .llama import (
    KVCache,
    decode_step,
    forward,
    init_params,
    param_count,
    rms_norm,
)
from .loader import convert_hf_state_dict, load_params, save_params


def family_of(config: ModelConfig):
    """The module that builds and runs ``config``'s family: its
    ``LAYER_MATRICES`` and ``layer_matrix_shapes(config)`` (what is
    quantised, counted and streamed), ``init_params``, ``forward`` and
    ``mixed_layer`` (the continuous scheduler's layer body).  Everything
    outside this package that once assumed the Llama layer asks here."""
    import importlib

    return importlib.import_module(f"{__name__}.{config.family}")


from .tokenizer import ByteTokenizer, HFTokenizer, Tokenizer, load_tokenizer

__all__ = [name for name in dir() if not name.startswith("_")]
