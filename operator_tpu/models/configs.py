"""Model configurations for the Llama family.

One decoder architecture covers every model the system serves (BASELINE
configs 2/4/5): RMSNorm + RoPE + grouped-query attention + SiLU-gated MLP.
Mistral adds a sliding attention window; Llama-3 a larger vocab and RoPE
theta.  Sizes are from the public model cards / HF config.json files.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import ClassVar, Optional, Union


@dataclass(frozen=True)
class RopeScaling:
    """Llama-3.1-style ("llama3") NTK-by-parts RoPE scaling: low-frequency
    bands are slowed by ``factor``, high-frequency bands kept, and the bands
    between interpolated — how 3.1/3.2 stretch an 8k-trained RoPE to 128k."""

    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_positions: int = 8192

    rope_type: ClassVar[str] = "llama3"


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction for a context stretched ``factor`` times
    (``0.1 x mscale x ln(factor) + 1``; 1 for no stretch)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


@dataclass(frozen=True)
class YarnScaling:
    """YaRN RoPE scaling (Peng et al., arXiv:2309.00071; ``rope_scaling``
    ``type: yarn`` as DeepSeek-V2 publishes it): the rotary bands whose
    wavelength fits fewer than ``beta_slow`` turns in the original context
    are divided by ``factor``, those with more than ``beta_fast`` kept, a
    linear ramp between; cos and sin scaled by ``mscale(factor, mscale) /
    mscale(factor, mscale_all_dim)`` and the attention's softmax scale by
    ``mscale(factor, mscale_all_dim)`` squared (``models/llama.py
    rope_frequencies``, ``configs.DeepseekV2Config.softmax_scale``)."""

    factor: float = 40.0
    original_max_positions: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707

    rope_type: ClassVar[str] = "yarn"

    @property
    def rotary_magnitude(self) -> float:
        """What cos and sin are multiplied by."""
        return yarn_mscale(self.factor, self.mscale) / yarn_mscale(
            self.factor, self.mscale_all_dim
        )


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
    #: Llama-3.1+ long context, or YaRN (``YarnScaling``)
    rope_scaling: Optional[Union[RopeScaling, YarnScaling]] = None
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

    def recurrent_state_shapes(self, batch_size: int) -> tuple:
        """``(state shape, convolution tail shape)`` of the pools a slot's
        mixers carry (``ops/paged_attention.PagedKVCache``): per layer and
        slot, ``[heads, d_state, d_head]`` float32 and the last ``d_conv -
        1`` inputs of the convolution's ``x | B | C``."""
        n = self.num_layers
        return (
            (n, batch_size, self.mamba_n_heads, self.mamba_d_state, self.mamba_d_head),
            (n, batch_size, self.mamba_d_conv - 1, self.mamba_conv_dim),
        )

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


#: the two kinds of layer of ``OlmoHybridConfig.layer_types``
LINEAR_ATTENTION, FULL_ATTENTION = "linear_attention", "full_attention"


@dataclass(frozen=True)
class OlmoHybridConfig(ModelConfig):
    """Olmo Hybrid (allenai, ``model_type: olmo_hybrid``): a stack whose
    ``layer_types`` repeat a period of gated delta-rule layers (Gated
    DeltaNet, linear attention with a per-slot state) and full-attention
    layers without positional rotation, every branch normed after it and
    before its residual add (the Olmo 2 order); ``models/olmo_hybrid.py``
    has the equations.  The KV pool holds planes for the full-attention
    layers alone, the recurrent pools state for the linear ones alone.  A
    sibling of ``ModelConfig`` as ``FalconH1Config`` is; field names follow
    the published ``config.json`` (``rope_theta`` None: no RoPE)."""

    layer_types: tuple = ()
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    #: ``beta = 2 * sigmoid(.)``: the state's transition may have a negative
    #: eigenvalue (Grazzi et al., arXiv:2411.12537)
    linear_allow_neg_eigval: bool = True
    hidden_act: str = "silu"

    family: ClassVar[str] = "olmo_hybrid"
    recurrent_state: ClassVar[bool] = True
    continuous_only: ClassVar[Optional[str]] = (
        "keeps a recurrent state per slot in its linear-attention layers"
    )

    @property
    def layer_types_list(self) -> list:
        """As JSON has it: a configuration file's ``architecture`` group is
        held to this."""
        return list(self.layer_types)

    @property
    def period(self) -> tuple:
        """The shortest prefix of ``layer_types`` that repeats to make it."""
        types = tuple(self.layer_types)
        for size in range(1, len(types) + 1):
            if len(types) % size == 0 and types == types[:size] * (len(types) // size):
                return types[:size]
        return types

    @property
    def periods(self) -> int:
        return len(self.layer_types) // len(self.period)

    @property
    def linear_per_period(self) -> int:
        return self.period.count(LINEAR_ATTENTION)

    @property
    def full_per_period(self) -> int:
        return self.period.count(FULL_ATTENTION)

    @property
    def delta_layers(self) -> int:
        """Layers of the gated delta rule: what the state pool holds."""
        return self.periods * self.linear_per_period

    @property
    def kv_planes(self) -> int:
        """One plane a full-attention layer: the linear layers keep no KV."""
        return self.periods * self.full_per_period

    @property
    def linear_key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def linear_value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def linear_conv_dim(self) -> int:
        """Channels the causal convolution runs over: q | k | v."""
        return 2 * self.linear_key_dim + self.linear_value_dim

    def recurrent_state_shapes(self, batch_size: int) -> tuple:
        """``(state shape, convolution tail shape)``: per linear layer and
        slot, the heads' ``[key_dim, value_dim]`` float32 states two heads
        side by side, ``[heads / 2, key_dim, 2 x value_dim]`` (a minor axis
        of 2 x 192 = 384 lanes, where one head's 192 would be padded to
        256), and the last ``conv_kernel - 1`` inputs of q | k | v."""
        n, heads = self.delta_layers, self.linear_num_value_heads
        return (
            (n, batch_size, heads // 2, self.linear_key_head_dim,
             2 * self.linear_value_head_dim),
            (n, batch_size, self.linear_conv_kernel_dim - 1, self.linear_conv_dim),
        )

    def __post_init__(self) -> None:
        super().__post_init__()
        types = tuple(self.layer_types)
        assert len(types) == self.num_layers and set(types) <= {
            LINEAR_ATTENTION, FULL_ATTENTION,
        }, f"{self.name}: layer_types must name each of the {self.num_layers} layers"
        # the mixed step scans ONE period a step: a pattern that does not
        # repeat is refused, not unrolled layer by layer
        assert self.periods >= 2, f"{self.name}: layer_types repeat no period"
        assert self.linear_per_period and self.full_per_period, (
            f"{self.name}: a period of both kinds of layer"
        )
        assert self.linear_num_key_heads == self.linear_num_value_heads and (
            self.linear_num_value_heads % 2 == 0
        ), f"{self.name}: the state pool holds heads in pairs, one key head a value head"
        assert (
            self.hidden_act == "silu" and self.linear_allow_neg_eigval
            and self.rope_theta is None and self.rope_scaling is None
            and not self.attention_bias and not self.tie_embeddings
            and self.sliding_window is None and self.num_heads == self.num_kv_heads
        ), f"{self.name}: an Olmo Hybrid variant the layer body does not implement"


@dataclass(frozen=True)
class DeepseekV2Config(ModelConfig):
    """DeepSeek-V2 (``model_type: deepseek_v2``): multi-head latent
    attention (MLA: keys and values decompressed per head from one
    ``kv_lora_rank`` latent a token, a rotary key of ``qk_rope_head_dim``
    shared by the heads) and a feed-forward layer that is a dense gated MLP
    in the first ``first_k_dense_replace`` layers and, after them, a router
    over ``n_routed_experts`` gated MLPs beside ``n_shared_experts`` that
    every token takes (``models/deepseek_v2.py`` has the equations).  The
    cache holds the latent and the rotary key a token and layer and no
    per-head K or V (``ops/latent_attention.py``).  A sibling of
    ``ModelConfig`` as ``SdarConfig`` is; field names follow the published
    ``config.json``.  ``head_dim`` is a query head's width, ``qk_nope_head_dim
    + qk_rope_head_dim``: what the softmax scale is taken from."""

    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_routed_experts: int = 64
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1408
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    scoring_func: str = "softmax"
    topk_method: str = "greedy"
    n_group: int = 1
    topk_group: int = 1
    hidden_act: str = "silu"

    family: ClassVar[str] = "deepseek_v2"
    continuous_only: ClassVar[Optional[str]] = (
        "caches one latent a token and layer, read by the latent attention "
        "kernel, and routes every token to a few of its experts"
    )

    @property
    def num_experts(self) -> int:
        """The routed experts, under the name the expert counters read."""
        return self.n_routed_experts

    @property
    def moe_layers(self) -> int:
        """The layers after them, each with experts (``moe_layer_freq`` 1)."""
        return self.num_layers - self.first_k_dense_replace

    @property
    def latent_dims(self) -> tuple:
        """``(latent width, rotary key width)`` of a cached token."""
        return self.kv_lora_rank, self.qk_rope_head_dim

    @property
    def shared_intermediate_size(self) -> int:
        """The shared experts as ONE gated MLP, as the public code builds
        them: ``n_shared_experts x moe_intermediate_size`` wide."""
        return self.n_shared_experts * self.moe_intermediate_size

    @property
    def rope_scaling_published(self) -> Optional[dict]:
        """``rope_scaling`` as the published ``config.json`` writes it: a
        configuration file's ``architecture`` group is held to this."""
        scaling = self.rope_scaling
        if scaling is None:
            return None
        return {
            "beta_fast": scaling.beta_fast, "beta_slow": scaling.beta_slow,
            "factor": scaling.factor, "mscale": scaling.mscale,
            "mscale_all_dim": scaling.mscale_all_dim,
            "original_max_position_embeddings": scaling.original_max_positions,
            "type": scaling.rope_type,
        }

    @property
    def softmax_scale(self) -> float:
        """``head_dim^-0.5``, times YaRN's ``mscale(factor,
        mscale_all_dim)`` squared where the rotary is stretched."""
        scale = self.head_dim ** -0.5
        scaling = self.rope_scaling
        if isinstance(scaling, YarnScaling) and scaling.mscale_all_dim:
            scale *= yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2
        return scale

    def __post_init__(self) -> None:
        super().__post_init__()
        assert self.head_dim == self.qk_nope_head_dim + self.qk_rope_head_dim, (
            f"{self.name}: head_dim is a query head's nope + rope width"
        )
        # the values the layer body (models/deepseek_v2.py) is written for;
        # a config that states another is refused here, at build
        unsupported = [
            text for bad, text in (
                (self.q_lora_rank is not None,
                 f"q_lora_rank={self.q_lora_rank} (no q compression is implemented)"),
                (self.topk_method != "greedy", f"topk_method={self.topk_method!r}"),
                (self.scoring_func != "softmax", f"scoring_func={self.scoring_func!r}"),
                (self.n_group > 1, f"n_group={self.n_group} (group-limited routing)"),
                (self.moe_layer_freq != 1, f"moe_layer_freq={self.moe_layer_freq}"),
                (self.hidden_act != "silu", f"hidden_act={self.hidden_act!r}"),
                (self.attention_bias or self.tie_embeddings
                 or self.sliding_window is not None,
                 "attention bias, tied embeddings or a sliding window"),
                (self.rope_scaling is not None and not (
                    isinstance(self.rope_scaling, YarnScaling)
                    and abs(self.rope_scaling.rotary_magnitude - 1.0) < 1e-12
                ), "rope scaling other than YaRN with cos/sin unscaled"),
            ) if bad
        ]
        assert not unsupported, (
            f"{self.name}: a DeepSeek-V2 variant the layer body does not "
            f"implement: {'; '.join(unsupported)}"
        )
        assert 0 <= self.first_k_dense_replace < self.num_layers
        assert 1 <= self.num_experts_per_tok <= self.n_routed_experts
        assert 128 % self.qk_rope_head_dim == 0, (
            f"{self.name}: the cache packs {128 // self.qk_rope_head_dim} rotary keys "
            "to a 128-lane row"
        )


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

# Olmo-Hybrid-7B as published (allenai/Olmo-Hybrid-7B, config.json): 32
# layers, three gated delta-rule layers then one full-attention layer, eight
# times; head_dim is hidden / heads (the file gives none)
OLMO_HYBRID_7B = OlmoHybridConfig(
    name="olmo-hybrid-7b",
    vocab_size=100352,
    hidden_size=3840,
    intermediate_size=11008,
    num_layers=32,
    num_heads=30,
    num_kv_heads=30,
    head_dim=128,
    rope_theta=None,
    rms_norm_eps=1e-6,
    max_seq_len=16384,  # serving cap; the model supports 64k
    layer_types=((LINEAR_ATTENTION,) * 3 + (FULL_ATTENTION,)) * 8,
    linear_num_key_heads=30,
    linear_num_value_heads=30,
    linear_key_head_dim=96,
    linear_value_head_dim=192,
    linear_conv_kernel_dim=4,
)

#: the family's small config for tests: two periods, two linear heads of
#: key 16 and value 32
TINY_OLMO_HYBRID = OlmoHybridConfig(
    name="tiny-olmo-hybrid",
    vocab_size=512,
    hidden_size=128,
    intermediate_size=256,
    num_layers=8,
    num_heads=2,
    num_kv_heads=2,
    head_dim=64,
    rope_theta=None,
    rms_norm_eps=1e-6,
    max_seq_len=256,
    layer_types=((LINEAR_ATTENTION,) * 3 + (FULL_ATTENTION,)) * 2,
    linear_num_key_heads=2,
    linear_num_value_heads=2,
    linear_key_head_dim=16,
    linear_value_head_dim=32,
    linear_conv_kernel_dim=4,
)

# DeepSeek-V2-Lite as published (deepseek-ai/DeepSeek-V2-Lite, config.json):
# one dense layer, then 26 layers of 64 routed experts (6 a token) beside 2
# shared ones, MLA at a latent of 512 and no q compression.
# ``deepseek-v2-lite-14l`` is the same model cut in depth only, the first
# of two pipeline stages (the dense layer and 13 expert layers) with the
# embedding and the head (benchmark/configs/deepseek-v2-lite-int8.json)
DEEPSEEK_V2_LITE = DeepseekV2Config(
    name="deepseek-v2-lite",
    vocab_size=102400,
    hidden_size=2048,
    intermediate_size=10944,
    num_layers=27,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,
    rope_theta=10_000.0,
    rms_norm_eps=1e-6,
    max_seq_len=16384,  # serving cap; the model supports 163,840
    rope_scaling=YarnScaling(
        factor=40.0, original_max_positions=4096, beta_fast=32.0, beta_slow=1.0,
        mscale=0.707, mscale_all_dim=0.707,
    ),
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    first_k_dense_replace=1,
    n_routed_experts=64,
    n_shared_experts=2,
    num_experts_per_tok=6,
    moe_intermediate_size=1408,
    norm_topk_prob=False,
)
DEEPSEEK_V2_LITE_14L = replace(DEEPSEEK_V2_LITE, name="deepseek-v2-lite-14l", num_layers=14)

#: the family's small config for tests: one dense layer and two expert
#: layers, four heads, a latent of 32 and a rotary key of 16, eight experts
#: two a token beside one shared, YaRN as published
TINY_DEEPSEEK_V2 = DeepseekV2Config(
    name="tiny-deepseek-v2",
    vocab_size=512,
    hidden_size=64,
    intermediate_size=192,
    num_layers=3,
    num_heads=4,
    num_kv_heads=4,
    head_dim=48,
    rope_theta=10_000.0,
    rms_norm_eps=1e-6,
    max_seq_len=256,
    rope_scaling=YarnScaling(
        factor=40.0, original_max_positions=4096, beta_fast=32.0, beta_slow=1.0,
        mscale=0.707, mscale_all_dim=0.707,
    ),
    kv_lora_rank=32,
    qk_nope_head_dim=32,
    qk_rope_head_dim=16,
    v_head_dim=32,
    first_k_dense_replace=1,
    n_routed_experts=8,
    n_shared_experts=1,
    num_experts_per_tok=2,
    moe_intermediate_size=32,
    norm_topk_prob=False,
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
        OLMO_HYBRID_7B,
        TINY_OLMO_HYBRID,
        DEEPSEEK_V2_LITE,
        DEEPSEEK_V2_LITE_14L,
        TINY_DEEPSEEK_V2,
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
