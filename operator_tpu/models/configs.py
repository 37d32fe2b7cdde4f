"""Model configurations for the Llama family.

One decoder architecture covers every model the system serves (BASELINE
configs 2/4/5): RMSNorm + RoPE + grouped-query attention + SiLU-gated MLP.
Mistral adds a sliding attention window; Llama-3 a larger vocab and RoPE
theta.  Sizes are from the public model cards / HF config.json files.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import ClassVar, Optional


@dataclass(frozen=True)
class RopeScaling:
    """Llama-3.1-style ("llama3") NTK-by-parts RoPE scaling: low-frequency
    bands are slowed by ``factor``, high-frequency bands kept, and the bands
    between interpolated — how 3.1/3.2 stretch an 8k-trained RoPE to 128k."""

    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_positions: int = 8192


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10_000.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 2048
    sliding_window: Optional[int] = None  # Mistral-style local attention
    tie_embeddings: bool = False
    rope_scaling: Optional[RopeScaling] = None  # Llama-3.1+ long context
    attention_bias: bool = False  # Qwen2-style bias on the q/k/v projections

    #: which module of this package builds and runs the model
    #: (``models.family_of``): init, layer matrices, the mixed step's layer
    family: ClassVar[str] = "llama"
    #: a recurrent state per slot beside the KV pages: prefix hits and
    #: draft roll-backs cannot restore it, so the serving path switches
    #: both off for such a model (serving/provider.py)
    recurrent_state: ClassVar[bool] = False
    #: what of the model only the unsharded continuous scheduler serves
    #: (``sched/mixed.py``), in words for the start-up error that refuses
    #: the wave engine, a mesh and LoRA for it (serving/provider.py); None
    #: for a model every engine serves
    continuous_only: ClassVar[Optional[str]] = None

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def kv_planes(self) -> int:
        """Planes of the paged KV pool (its first axis): one a layer for a
        model that runs its stack once a token."""
        return self.num_layers

    def __post_init__(self) -> None:
        assert self.num_heads % self.num_kv_heads == 0, "heads must divide evenly into kv groups"


@dataclass(frozen=True)
class OuroConfig(ModelConfig):
    """Ouro (LoopLM): one stack of sandwich-normed decoder layers run
    ``total_ut_steps`` times a token with the same weights, the final norm
    after every pass, and an exit gate read after each
    (``models/ouro.py`` has the equations).  A pass attends only to its
    own keys and values, so the cache holds a plane for every pass and
    layer: plane ``pass * num_layers + layer``.  A sibling of
    ``ModelConfig`` as ``FalconH1Config`` is; field names follow the
    published ``config.json``."""

    total_ut_steps: int = 4
    #: the cumulative exit probability at which a row would leave the loop;
    #: at the published 1.0 none does, and the serving path runs every
    #: pass for every token whatever is set here
    early_exit_threshold: float = 1.0
    hidden_act: str = "silu"

    family: ClassVar[str] = "ouro"
    continuous_only: ClassVar[Optional[str]] = (
        "runs its layer stack several times a token over a KV plane for "
        "every pass and layer"
    )

    @property
    def kv_planes(self) -> int:
        return self.total_ut_steps * self.num_layers

    def __post_init__(self) -> None:
        super().__post_init__()
        assert self.total_ut_steps >= 1
        assert (
            self.hidden_act == "silu" and not self.attention_bias
            and not self.tie_embeddings and self.sliding_window is None
            and self.rope_scaling is None
        ), f"{self.name}: an Ouro variant the layer body does not implement"


@dataclass(frozen=True)
class SdarConfig(ModelConfig):
    """SDAR (JetLM, ``model_type: sdar_moe``): the Qwen3-MoE decoder layer
    (per-head RMSNorm on q and k, a router over ``num_experts`` gated MLPs
    of which a token takes ``num_experts_per_tok``) under a block-causal
    mask, generating by denoising blocks of ``block_length`` positions
    (``models/sdar.py`` has the equations).  Every layer is sparse
    (``decoder_sparse_step`` 1, no dense layer, no shared expert), so
    ``intermediate_size`` is carried and unused.  A sibling of
    ``ModelConfig`` as ``FalconH1Config`` is; field names follow the
    published ``config.json`` where it has the key."""

    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    hidden_act: str = "silu"
    #: positions a block holds: position ``i`` attends to every ``j`` with
    #: ``j // block_length <= i // block_length``, and a step denoises one
    #: block a row.  Not a key of the published config (the family's
    #: ``generate.py`` takes it as an argument)
    block_length: int = 4
    #: the id a position holds until a step keeps a token for it
    mask_token_id: int = 151669

    family: ClassVar[str] = "sdar"
    continuous_only: ClassVar[Optional[str]] = (
        "routes every token to a few of its experts and denoises a block "
        "of positions a step"
    )

    def __post_init__(self) -> None:
        super().__post_init__()
        assert 1 <= self.num_experts_per_tok <= self.num_experts
        assert self.block_length >= 1 and (
            self.block_length & (self.block_length - 1) == 0
        ), "block_length must be a power of two (the mask ORs positions with it)"
        assert 0 <= self.mask_token_id < self.vocab_size
        assert (
            self.hidden_act == "silu" and not self.attention_bias
            and not self.tie_embeddings and self.sliding_window is None
            and self.rope_scaling is None
        ), f"{self.name}: an SDAR variant the layer body does not implement"


@dataclass(frozen=True)
class FalconH1Config(ModelConfig):
    """Falcon-H1: in every layer a Mamba-2 mixer in parallel with
    grouped-query attention, then a gated MLP; muP multipliers on nearly
    every branch (``models/falcon_h1.py`` has the equations).  A sibling
    of ``ModelConfig`` and not more fields on it: the Llama family's
    configurations, their fingerprints and every ``dataclasses.asdict`` of
    them stay what they were, and what reads only the shared sizes
    (heads, head_dim, vocabulary, depth) reads them from either.  Field
    names follow the published ``config.json`` where it has the key."""

    mamba_d_ssm: int = 0
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_rms_norm: bool = True
    mamba_norm_before_gate: bool = False
    mlp_bias: bool = False
    projectors_bias: bool = False
    hidden_act: str = "silu"
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    #: gate and down-projection multipliers
    mlp_multipliers: tuple = (1.0, 1.0)
    #: over the in-projection's segments z | x | B | C | dt
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)

    family: ClassVar[str] = "falcon_h1"
    recurrent_state: ClassVar[bool] = True
    continuous_only: ClassVar[Optional[str]] = "keeps a recurrent state per slot"

    @property
    def mamba_conv_dim(self) -> int:
        """Channels the causal convolution runs over: x | B | C."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def mamba_in_dim(self) -> int:
        """The in-projection's width: z | x | B | C | dt."""
        return self.mamba_d_ssm + self.mamba_conv_dim + self.mamba_n_heads

    @property
    def mlp_multipliers_list(self) -> list:
        """As JSON has it: a configuration file's ``architecture`` group
        is held to this (and to ``ssm_multipliers_list``)."""
        return list(self.mlp_multipliers)

    @property
    def ssm_multipliers_list(self) -> list:
        return list(self.ssm_multipliers)

    def __post_init__(self) -> None:
        super().__post_init__()
        assert self.mamba_d_ssm == self.mamba_n_heads * self.mamba_d_head
        assert self.mamba_n_heads % self.mamba_n_groups == 0
        assert len(self.mlp_multipliers) == 2 and len(self.ssm_multipliers) == 5
        # the flags are held so that a configuration's file can be checked
        # against them; the layer (models/falcon_h1.py) is written for
        # these values and a config that states another is refused here
        assert (
            self.mamba_conv_bias and self.mamba_rms_norm and self.hidden_act == "silu"
            and not (self.mamba_proj_bias or self.mamba_norm_before_gate
                     or self.mlp_bias or self.projectors_bias or self.attention_bias)
            and self.mamba_expand * self.hidden_size >= self.mamba_d_ssm
        ), f"{self.name}: a Falcon-H1 variant the layer body does not implement"


TINYLLAMA_1_1B = ModelConfig(
    name="tinyllama-1.1b",
    vocab_size=32000,
    hidden_size=2048,
    intermediate_size=5632,
    num_layers=22,
    num_heads=32,
    num_kv_heads=4,
    head_dim=64,
    rope_theta=10_000.0,
    max_seq_len=2048,
)

LLAMA_3_8B = ModelConfig(
    name="llama-3-8b",
    vocab_size=128256,
    hidden_size=4096,
    intermediate_size=14336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    rope_theta=500_000.0,
    max_seq_len=8192,
)

LLAMA_3_1_8B = ModelConfig(
    name="llama-3.1-8b",
    vocab_size=128256,
    hidden_size=4096,
    intermediate_size=14336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    rope_theta=500_000.0,
    max_seq_len=16384,  # serving cap; the model supports 128k
    rope_scaling=RopeScaling(factor=8.0),
)

# small modern targets: a 1B that outclasses TinyLlama at the same latency
# budget, and a 3B midpoint — both tie embeddings and use llama3 scaling
LLAMA_3_2_1B = ModelConfig(
    name="llama-3.2-1b",
    vocab_size=128256,
    hidden_size=2048,
    intermediate_size=8192,
    num_layers=16,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    rope_theta=500_000.0,
    max_seq_len=16384,
    tie_embeddings=True,
    rope_scaling=RopeScaling(factor=32.0),
)

LLAMA_3_2_3B = ModelConfig(
    name="llama-3.2-3b",
    vocab_size=128256,
    hidden_size=3072,
    intermediate_size=8192,
    num_layers=28,
    num_heads=24,
    num_kv_heads=8,
    head_dim=128,
    rope_theta=500_000.0,
    max_seq_len=16384,
    tie_embeddings=True,
    rope_scaling=RopeScaling(factor=32.0),
)

MISTRAL_7B = ModelConfig(
    name="mistral-7b",
    vocab_size=32000,
    hidden_size=4096,
    intermediate_size=14336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    rope_theta=10_000.0,
    sliding_window=4096,
    max_seq_len=8192,
)

# Qwen2 family: same decoder skeleton plus bias vectors on the q/k/v
# projections (HF Qwen2Config attention_bias); 2.5 generation sizes
QWEN2_5_7B = ModelConfig(
    name="qwen2.5-7b",
    vocab_size=152064,
    hidden_size=3584,
    intermediate_size=18944,
    num_layers=28,
    num_heads=28,
    num_kv_heads=4,
    head_dim=128,
    rope_theta=1_000_000.0,
    rms_norm_eps=1e-6,
    max_seq_len=16384,  # serving cap; the model supports 32k
    attention_bias=True,
)

QWEN2_5_1_5B = ModelConfig(
    name="qwen2.5-1.5b",
    vocab_size=151936,
    hidden_size=1536,
    intermediate_size=8960,
    num_layers=28,
    num_heads=12,
    num_kv_heads=2,
    head_dim=128,
    rope_theta=1_000_000.0,
    rms_norm_eps=1e-6,
    max_seq_len=16384,
    tie_embeddings=True,
    attention_bias=True,
)

#: small config for tests and the compile-check entry point: real arrays,
#: real architecture, laptop-sized
TINY_TEST = ModelConfig(
    name="tiny-test",
    vocab_size=512,
    hidden_size=128,
    intermediate_size=352,
    num_layers=3,
    num_heads=8,
    num_kv_heads=2,
    head_dim=16,
    rope_theta=10_000.0,
    max_seq_len=256,
)

# Falcon-H1-34B-Instruct as published (tiiuae/Falcon-H1-34B-Instruct,
# config.json): 72 identical layers.  ``falcon-h1-34b-6l`` is the same
# model cut in depth only, one pipeline stage's six layers with the
# embedding and the head (benchmark/configs/falcon-h1-34b-int8.json)
FALCON_H1_34B = FalconH1Config(
    name="falcon-h1-34b",
    vocab_size=261120,
    hidden_size=5120,
    intermediate_size=21504,
    num_layers=72,
    num_heads=20,
    num_kv_heads=4,
    head_dim=128,
    rope_theta=1e11,
    rms_norm_eps=1e-5,
    max_seq_len=16384,  # serving cap; the model supports 256k
    mamba_d_ssm=4096,
    mamba_n_heads=32,
    mamba_d_head=128,
    mamba_d_state=256,
    mamba_n_groups=2,
    mamba_d_conv=4,
    embedding_multiplier=5.656854249492381,
    lm_head_multiplier=0.0078125,
    attention_in_multiplier=1.0,
    attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804,
    ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
    ssm_multipliers=(
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738,
    ),
)
FALCON_H1_34B_6L = replace(FALCON_H1_34B, name="falcon-h1-34b-6l", num_layers=6)

#: the family's small config for tests: every multiplier != 1
TINY_FALCON_H1 = FalconH1Config(
    name="tiny-falcon-h1",
    vocab_size=512,
    hidden_size=128,
    intermediate_size=352,
    num_layers=3,
    num_heads=8,
    num_kv_heads=2,
    head_dim=16,
    rope_theta=10_000.0,
    max_seq_len=256,
    mamba_d_ssm=64,
    mamba_n_heads=4,
    mamba_d_head=16,
    mamba_d_state=16,
    mamba_n_groups=2,
    mamba_d_conv=4,
    embedding_multiplier=2.5,
    lm_head_multiplier=0.25,
    attention_in_multiplier=0.9,
    attention_out_multiplier=0.6,
    key_multiplier=0.7,
    ssm_in_multiplier=0.8,
    ssm_out_multiplier=0.5,
    mlp_multipliers=(0.75, 0.4),
    ssm_multipliers=(0.9, 0.8, 0.7, 1.2, 0.6),
)

# Ouro-2.6B as published (ByteDance/Ouro-2.6B, config.json): 48 layers run
# four times a token; the serving cap on positions is provider.py's
OURO_2_6B = OuroConfig(
    name="ouro-2.6b",
    vocab_size=49152,
    hidden_size=2048,
    intermediate_size=5632,
    num_layers=48,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,
    rope_theta=1_000_000.0,
    rms_norm_eps=1e-6,
    max_seq_len=16384,  # serving cap; the model supports 64k
    total_ut_steps=4,
    early_exit_threshold=1.0,
)

#: the family's small config for tests: MHA at the kernel's head size,
#: three passes over two layers
TINY_OURO = OuroConfig(
    name="tiny-ouro",
    vocab_size=512,
    hidden_size=128,
    intermediate_size=352,
    num_layers=2,
    num_heads=4,
    num_kv_heads=4,
    head_dim=128,
    rope_theta=10_000.0,
    rms_norm_eps=1e-6,
    max_seq_len=256,
    total_ut_steps=3,
)

# SDAR-30B-A3B-Chat as published (JetLM/SDAR-30B-A3B-Chat, config.json): 48
# identical sparse layers.  ``sdar-30b-a3b-12l`` is the same model cut in
# depth only, one pipeline stage's twelve layers with the embedding and the
# head (benchmark/configs/sdar-30b-a3b-int8.json)
SDAR_30B_A3B = SdarConfig(
    name="sdar-30b-a3b",
    vocab_size=151936,
    hidden_size=2048,
    intermediate_size=6144,
    num_layers=48,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    rope_theta=1_000_000.0,
    rms_norm_eps=1e-6,
    max_seq_len=16384,  # serving cap; the model supports 32k
    num_experts=128,
    num_experts_per_tok=8,
    moe_intermediate_size=768,
    block_length=4,
    mask_token_id=151669,
)
SDAR_30B_A3B_12L = replace(SDAR_30B_A3B, name="sdar-30b-a3b-12l", num_layers=12)

#: the family's small config for tests: four layers of sixteen experts, eight
#: a token (at two of eight, one expert chosen otherwise in bfloat16 moves a
#: logit by 1 to 2.5 and no limit tells sound from int4; at eight of sixteen
#: by under 0.03: tests/benchmark/configs/tiny-sdar.json), and a mask id
#: outside the byte tokenizer's
TINY_SDAR = SdarConfig(
    name="tiny-sdar",
    vocab_size=512,
    hidden_size=64,
    intermediate_size=192,
    num_layers=4,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    rope_theta=10_000.0,
    rms_norm_eps=1e-6,
    max_seq_len=256,
    num_experts=16,
    num_experts_per_tok=8,
    moe_intermediate_size=32,
    block_length=4,
    mask_token_id=511,
)

_REGISTRY = {
    cfg.name: cfg
    for cfg in (
        TINYLLAMA_1_1B,
        LLAMA_3_8B,
        LLAMA_3_1_8B,
        LLAMA_3_2_1B,
        LLAMA_3_2_3B,
        MISTRAL_7B,
        QWEN2_5_7B,
        QWEN2_5_1_5B,
        TINY_TEST,
        FALCON_H1_34B,
        FALCON_H1_34B_6L,
        TINY_FALCON_H1,
        OURO_2_6B,
        TINY_OURO,
        SDAR_30B_A3B,
        SDAR_30B_A3B_12L,
        TINY_SDAR,
    )
}


def get_config(name: str) -> ModelConfig:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown model {name!r}; known: {sorted(_REGISTRY)}") from None


def register_config(config: ModelConfig) -> None:
    _REGISTRY[config.name] = config


def scaled(config: ModelConfig, *, num_layers: Optional[int] = None,
           max_seq_len: Optional[int] = None) -> ModelConfig:
    """A reduced variant (fewer layers / shorter context) for smoke tests."""
    kwargs = {}
    if num_layers is not None:
        kwargs["num_layers"] = num_layers
    if max_seq_len is not None:
        kwargs["max_seq_len"] = max_seq_len
    return replace(config, name=f"{config.name}-scaled", **kwargs)
