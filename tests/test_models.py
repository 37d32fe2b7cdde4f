"""Model-stack tests: forward shapes, KV-cache == full-context equivalence,
sliding window, and logit parity against transformers' Llama implementation
(built locally with random weights — no network)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from operator_tpu.models import (
    TINY_TEST,
    ByteTokenizer,
    KVCache,
    ModelConfig,
    convert_hf_state_dict,
    decode_step,
    forward,
    get_config,
    init_params,
    param_count,
)


def make_tokens(key, config, batch=2, seq=16):
    return jax.random.randint(key, (batch, seq), 0, config.vocab_size, dtype=jnp.int32)


def positions_for(tokens):
    b, t = tokens.shape
    return jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))


# --- basics ---------------------------------------------------------------


def test_forward_shapes_and_dtype():
    config = TINY_TEST
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = make_tokens(jax.random.PRNGKey(1), config)
    logits, cache = forward(params, config, tokens, positions_for(tokens))
    assert logits.shape == (2, 16, config.vocab_size)
    assert logits.dtype == jnp.float32
    assert cache is None


def test_param_count_matches_formula():
    """init_params' actual tree must weigh exactly what the architecture
    formula says (exercised on TINY_TEST; same code path as the 1.1B)."""
    config = TINY_TEST
    params = init_params(config, jax.random.PRNGKey(0))
    h, f, v, n = (config.hidden_size, config.intermediate_size,
                  config.vocab_size, config.num_layers)
    qh, kvh, d = config.num_heads, config.num_kv_heads, config.head_dim
    expected = (
        v * h  # embed
        + n * (h * qh * d + 2 * h * kvh * d + qh * d * h)  # attn
        + n * (3 * h * f)  # mlp
        + n * 2 * h + h  # norms
        + h * v  # lm_head
    )
    assert param_count(params) == expected


def test_param_count_tinyllama_shape():
    # sanity: the real TinyLlama config should weigh in around 1.1B
    config = get_config("tinyllama-1.1b")
    h, f, v, n = (config.hidden_size, config.intermediate_size,
                  config.vocab_size, config.num_layers)
    qh, kvh, d = config.num_heads, config.num_kv_heads, config.head_dim
    expected = (
        v * h  # embed
        + n * (h * qh * d + 2 * h * kvh * d + qh * d * h)  # attn
        + n * (3 * h * f)  # mlp
        + n * 2 * h + h  # norms
        + h * v  # lm_head
    )
    assert 1.0e9 < expected < 1.2e9


def test_causal_masking_is_effective():
    """Changing a future token must not change past logits."""
    config = TINY_TEST
    params = init_params(config, jax.random.PRNGKey(0), dtype=jnp.float32)
    tokens = make_tokens(jax.random.PRNGKey(1), config, batch=1, seq=8)
    logits1, _ = forward(params, config, tokens, positions_for(tokens))
    modified = tokens.at[0, -1].set((tokens[0, -1] + 1) % config.vocab_size)
    logits2, _ = forward(params, config, modified, positions_for(modified))
    np.testing.assert_allclose(logits1[0, :-1], logits2[0, :-1], atol=1e-5)
    assert not np.allclose(logits1[0, -1], logits2[0, -1], atol=1e-3)


# --- KV cache -------------------------------------------------------------


def test_prefill_plus_decode_matches_full_forward():
    config = TINY_TEST
    params = init_params(config, jax.random.PRNGKey(0), dtype=jnp.float32)
    tokens = make_tokens(jax.random.PRNGKey(1), config, batch=2, seq=12)
    pos = positions_for(tokens)
    full_logits, _ = forward(params, config, tokens, pos)

    # prefill 8, then decode 4 one at a time
    cache = KVCache.create(config, batch_size=2, max_seq_len=32, dtype=jnp.float32)
    prefill, cache = forward(params, config, tokens[:, :8], pos[:, :8],
                             cache=cache, cache_offset=0)
    np.testing.assert_allclose(prefill, full_logits[:, :8], rtol=2e-4, atol=2e-4)
    for i in range(8, 12):
        step_logits, cache = decode_step(
            params, config, tokens[:, i : i + 1], pos[:, i : i + 1],
            cache, jnp.int32(i),
        )
        np.testing.assert_allclose(step_logits, full_logits[:, i], rtol=2e-4, atol=2e-4)


def test_kv_cache_pytree_roundtrip():
    cache = KVCache.create(TINY_TEST, batch_size=1, max_seq_len=8)
    leaves, treedef = jax.tree_util.tree_flatten(cache)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.k.shape == cache.k.shape


# --- sliding window (Mistral) ---------------------------------------------


def test_sliding_window_limits_attention():
    import dataclasses

    config = dataclasses.replace(TINY_TEST, name="tiny-sw", sliding_window=4)
    params = init_params(config, jax.random.PRNGKey(0), dtype=jnp.float32)
    tokens = make_tokens(jax.random.PRNGKey(1), config, batch=1, seq=12)
    pos = positions_for(tokens)
    logits1, _ = forward(params, config, tokens, pos)
    # a token far outside every query's window must not affect the tail
    modified = tokens.at[0, 0].set((tokens[0, 0] + 1) % config.vocab_size)
    logits2, _ = forward(params, config, modified, pos)
    np.testing.assert_allclose(logits1[0, -1], logits2[0, -1], atol=1e-5)
    # but within a window it must
    modified2 = tokens.at[0, -2].set((tokens[0, -2] + 1) % config.vocab_size)
    logits3, _ = forward(params, config, modified2, pos)
    assert not np.allclose(logits1[0, -1], logits3[0, -1], atol=1e-3)


# --- HF parity ------------------------------------------------------------


@pytest.fixture(scope="module")
def hf_tiny_model():
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig, LlamaForCausalLM

    hf_config = LlamaConfig(
        vocab_size=TINY_TEST.vocab_size,
        hidden_size=TINY_TEST.hidden_size,
        intermediate_size=TINY_TEST.intermediate_size,
        num_hidden_layers=TINY_TEST.num_layers,
        num_attention_heads=TINY_TEST.num_heads,
        num_key_value_heads=TINY_TEST.num_kv_heads,
        head_dim=TINY_TEST.head_dim,
        rope_theta=TINY_TEST.rope_theta,
        rms_norm_eps=TINY_TEST.rms_norm_eps,
        max_position_embeddings=TINY_TEST.max_seq_len,
        tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(7)
    model = LlamaForCausalLM(hf_config).eval()
    return model


def test_logit_parity_with_transformers(hf_tiny_model):
    """Our forward must reproduce HF Llama logits from the same weights —
    the numeric-parity bar SURVEY.md §7 sets for every model family."""
    torch = pytest.importorskip("torch")

    params = convert_hf_state_dict(hf_tiny_model.state_dict(), TINY_TEST, dtype=jnp.float32)
    rng = np.random.RandomState(0)
    tokens_np = rng.randint(0, TINY_TEST.vocab_size, size=(2, 24)).astype(np.int64)

    with torch.no_grad():
        hf_logits = hf_tiny_model(torch.from_numpy(tokens_np)).logits.numpy()

    tokens = jnp.asarray(tokens_np, jnp.int32)
    ours, _ = forward(params, TINY_TEST, tokens, positions_for(tokens))
    ours = np.asarray(ours)

    assert ours.shape == hf_logits.shape
    # float32 cross-framework tolerance: different accumulation orders (and
    # HF computing RoPE tables in f32) bound agreement around 1e-2 absolute;
    # the strict bit-level check runs in float64 below
    np.testing.assert_allclose(ours, hf_logits, rtol=1e-2, atol=1e-2)
    # and argmax agreement everywhere (the decisions, not just the numbers)
    assert (ours.argmax(-1) == hf_logits.argmax(-1)).mean() == 1.0


def test_logit_parity_rope_scaled_tied(tmp_path):
    """Llama-3.1/3.2 features — llama3 NTK-by-parts RoPE scaling and tied
    embeddings — must match HF exactly (the configs that use them:
    llama-3.1-8b, llama-3.2-1b/3b)."""
    import dataclasses

    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig, LlamaForCausalLM

    from operator_tpu.models.configs import RopeScaling

    config = dataclasses.replace(
        TINY_TEST,
        name="tiny-3.2",
        tie_embeddings=True,
        rope_theta=500_000.0,
        rope_scaling=RopeScaling(
            factor=32.0, low_freq_factor=1.0, high_freq_factor=4.0,
            original_max_positions=64,  # tiny so the test hits ALL 3 bands
        ),
    )
    hf_config = LlamaConfig(
        vocab_size=config.vocab_size,
        hidden_size=config.hidden_size,
        intermediate_size=config.intermediate_size,
        num_hidden_layers=config.num_layers,
        num_attention_heads=config.num_heads,
        num_key_value_heads=config.num_kv_heads,
        head_dim=config.head_dim,
        rope_theta=config.rope_theta,
        rms_norm_eps=config.rms_norm_eps,
        max_position_embeddings=config.max_seq_len,
        tie_word_embeddings=True,
        attn_implementation="eager",
        rope_scaling={
            "rope_type": "llama3",
            "factor": 32.0,
            "low_freq_factor": 1.0,
            "high_freq_factor": 4.0,
            "original_max_position_embeddings": 64,
        },
    )
    torch.manual_seed(11)
    model = LlamaForCausalLM(hf_config).eval()

    params = convert_hf_state_dict(model.state_dict(), config, dtype=jnp.float32)
    assert "lm_head" not in params  # tied: head reuses the embedding
    rng = np.random.RandomState(3)
    tokens_np = rng.randint(0, config.vocab_size, size=(2, 48)).astype(np.int64)
    with torch.no_grad():
        hf_logits = model(torch.from_numpy(tokens_np)).logits.numpy()
    tokens = jnp.asarray(tokens_np, jnp.int32)
    ours, _ = forward(params, config, tokens, positions_for(tokens))
    ours = np.asarray(ours)
    np.testing.assert_allclose(ours, hf_logits, rtol=1e-2, atol=1e-2)
    assert (ours.argmax(-1) == hf_logits.argmax(-1)).mean() == 1.0
    # the scaling actually changed the frequencies (guards a silent no-op)
    from operator_tpu.models.llama import rope_frequencies

    unscaled = rope_frequencies(dataclasses.replace(config, rope_scaling=None))
    scaled = rope_frequencies(config)
    assert not np.allclose(np.asarray(unscaled), np.asarray(scaled))


def test_new_model_configs_registered():
    for name in ("llama-3.1-8b", "llama-3.2-1b", "llama-3.2-3b"):
        config = get_config(name)
        assert config.rope_scaling is not None
        assert config.num_heads % config.num_kv_heads == 0
    assert get_config("llama-3.2-1b").tie_embeddings


def test_logit_parity_float64_strict(hf_tiny_model, tmp_path):
    """Exactness check: in float64 both implementations agree to ~1e-6
    (residual = HF's float32 RoPE tables).  x64 is a process-global jax flag,
    so this runs in a subprocess."""
    import subprocess
    import sys

    torch = pytest.importorskip("torch")
    state_path = tmp_path / "state.pt"
    torch.save(hf_tiny_model.state_dict(), state_path)
    script = f"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, torch, jax.numpy as jnp
import sys
sys.path.insert(0, {repr(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))})
from operator_tpu.models import TINY_TEST, convert_hf_state_dict, forward
from transformers import LlamaConfig, LlamaForCausalLM
cfg = TINY_TEST
hf_config = LlamaConfig(vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
    intermediate_size=cfg.intermediate_size, num_hidden_layers=cfg.num_layers,
    num_attention_heads=cfg.num_heads, num_key_value_heads=cfg.num_kv_heads,
    head_dim=cfg.head_dim, rope_theta=cfg.rope_theta, rms_norm_eps=cfg.rms_norm_eps,
    max_position_embeddings=cfg.max_seq_len, tie_word_embeddings=False,
    attn_implementation="eager")
model = LlamaForCausalLM(hf_config).eval()
model.load_state_dict(torch.load({repr(str(state_path))}))
model = model.double()
params = convert_hf_state_dict(model.state_dict(), cfg, dtype=jnp.float64)
rng = np.random.RandomState(0)
tokens_np = rng.randint(0, cfg.vocab_size, size=(2, 24)).astype(np.int64)
with torch.no_grad():
    hf = model(torch.from_numpy(tokens_np)).logits.numpy()
tokens = jnp.asarray(tokens_np, jnp.int32)
pos = jnp.broadcast_to(jnp.arange(24, dtype=jnp.int64)[None], (2, 24))
ours, _ = forward(params, cfg, tokens, pos)
diff = float(np.abs(np.asarray(ours) - hf).max())
assert diff < 1e-5, f"float64 parity broke: {{diff}}"
print("F64_PARITY_OK", diff)
"""
    result = subprocess.run([sys.executable, "-c", script], capture_output=True,
                            text=True, timeout=300)
    assert "F64_PARITY_OK" in result.stdout, result.stderr[-2000:]


def test_parity_survives_kv_cache_decode(hf_tiny_model):
    torch = pytest.importorskip("torch")

    params = convert_hf_state_dict(hf_tiny_model.state_dict(), TINY_TEST, dtype=jnp.float32)
    rng = np.random.RandomState(3)
    tokens_np = rng.randint(0, TINY_TEST.vocab_size, size=(1, 16)).astype(np.int64)
    with torch.no_grad():
        hf_logits = hf_tiny_model(torch.from_numpy(tokens_np)).logits.numpy()

    tokens = jnp.asarray(tokens_np, jnp.int32)
    pos = positions_for(tokens)
    cache = KVCache.create(TINY_TEST, batch_size=1, max_seq_len=32, dtype=jnp.float32)
    _, cache = forward(params, TINY_TEST, tokens[:, :15], pos[:, :15], cache=cache)
    last, _ = decode_step(params, TINY_TEST, tokens[:, 15:16], pos[:, 15:16],
                          cache, jnp.int32(15))
    np.testing.assert_allclose(np.asarray(last)[0], hf_logits[0, 15], rtol=1e-2, atol=1e-2)
    assert np.asarray(last)[0].argmax() == hf_logits[0, 15].argmax()


# --- loader validation ----------------------------------------------------


def write_hf_checkpoint(tmp_path, config, params):
    """Write our params back out as a sharded HF-layout checkpoint."""
    from safetensors.numpy import save_file

    state = {
        "model.embed_tokens.weight": np.asarray(params["embed"]),
        "model.norm.weight": np.asarray(params["ln_final"]),
        "lm_head.weight": np.ascontiguousarray(np.asarray(params["lm_head"]).T),
    }
    hf_names = {
        "wq": ("self_attn.q_proj", True), "wk": ("self_attn.k_proj", True),
        "wv": ("self_attn.v_proj", True), "wo": ("self_attn.o_proj", True),
        "w_gate": ("mlp.gate_proj", True), "w_up": ("mlp.up_proj", True),
        "w_down": ("mlp.down_proj", True),
        "ln_attn": ("input_layernorm", False), "ln_mlp": ("post_attention_layernorm", False),
    }
    for ours, (hf, transpose) in hf_names.items():
        stacked = np.asarray(params["layers"][ours])
        for i in range(config.num_layers):
            tensor = stacked[i].T if transpose else stacked[i]
            state[f"model.layers.{i}.{hf}.weight"] = np.ascontiguousarray(tensor)
    # split across two shard files to exercise multi-file iteration
    names = sorted(state)
    save_file({k: state[k] for k in names[::2]}, tmp_path / "model-00001.safetensors")
    save_file({k: state[k] for k in names[1::2]}, tmp_path / "model-00002.safetensors")


def test_safetensors_roundtrip(tmp_path):
    """init -> save HF-layout safetensors shards -> load_params -> same logits."""
    from operator_tpu.models import load_params

    config = TINY_TEST
    params = init_params(config, jax.random.PRNGKey(5), dtype=jnp.float32)
    write_hf_checkpoint(tmp_path, config, params)

    loaded = load_params(str(tmp_path), config, dtype=jnp.float32)
    tokens = make_tokens(jax.random.PRNGKey(6), config, batch=1, seq=8)
    ref, _ = forward(params, config, tokens, positions_for(tokens))
    got, _ = forward(loaded, config, tokens, positions_for(tokens))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6)


def test_loader_preserves_native_dtype():
    # a float64 state dict must not be bottlenecked through float32
    rng = np.random.RandomState(0)
    captured = {}

    def put(name, array):
        captured[name] = array.dtype
        return jnp.asarray(array, jnp.float32)

    state = {}
    cfg = TINY_TEST
    state["model.embed_tokens.weight"] = rng.randn(cfg.vocab_size, cfg.hidden_size)
    state["model.norm.weight"] = rng.randn(cfg.hidden_size)
    state["lm_head.weight"] = rng.randn(cfg.vocab_size, cfg.hidden_size)
    shapes = {
        "self_attn.q_proj": (cfg.num_heads * cfg.head_dim, cfg.hidden_size),
        "self_attn.k_proj": (cfg.num_kv_heads * cfg.head_dim, cfg.hidden_size),
        "self_attn.v_proj": (cfg.num_kv_heads * cfg.head_dim, cfg.hidden_size),
        "self_attn.o_proj": (cfg.hidden_size, cfg.num_heads * cfg.head_dim),
        "mlp.gate_proj": (cfg.intermediate_size, cfg.hidden_size),
        "mlp.up_proj": (cfg.intermediate_size, cfg.hidden_size),
        "mlp.down_proj": (cfg.hidden_size, cfg.intermediate_size),
        "input_layernorm": (cfg.hidden_size,),
        "post_attention_layernorm": (cfg.hidden_size,),
    }
    for i in range(cfg.num_layers):
        for hf, shape in shapes.items():
            state[f"model.layers.{i}.{hf}.weight"] = rng.randn(*shape)
    convert_hf_state_dict(state, cfg, put=put)
    assert captured["wq"] == np.float64  # stacked groups keep native dtype


def test_loader_rejects_incomplete_checkpoint():
    state = {"model.embed_tokens.weight": np.zeros((TINY_TEST.vocab_size,
                                                    TINY_TEST.hidden_size), np.float32)}
    with pytest.raises(ValueError, match="missing"):
        convert_hf_state_dict(state, TINY_TEST)


def test_byte_tokenizer_roundtrip():
    tok = ByteTokenizer()
    ids = tok.encode("hello ✨ world")
    assert ids[0] == tok.bos_id
    assert tok.decode(ids) == "hello ✨ world"


# --- chunked prefill (long-context, VERDICT r2 missing #1) ----------------


class TestChunkedPrefill:
    """The q-chunked attention path must be bit-for-bit loyal to the dense
    path: same mask semantics (causal, padding validity, sliding window),
    same cache writes — only peak memory differs."""

    def _params(self, config=TINY_TEST):
        return init_params(config, jax.random.PRNGKey(0))

    def test_matches_dense_no_cache(self):
        config = TINY_TEST
        params = self._params(config)
        tokens = make_tokens(jax.random.PRNGKey(1), config, batch=2, seq=32)
        pos = positions_for(tokens)
        dense, _ = forward(params, config, tokens, pos)
        chunked, _ = forward(params, config, tokens, pos, q_chunk=8)
        # bf16 activations: einsum batching differs between paths, so
        # accumulation order shifts logits by O(1e-2) at scale ~4
        np.testing.assert_allclose(np.asarray(dense), np.asarray(chunked),
                                   rtol=0, atol=0.05)
        assert (np.argmax(np.asarray(dense), -1) ==
                np.argmax(np.asarray(chunked), -1)).mean() > 0.98

    def test_matches_dense_with_cache_and_padding(self):
        """Batched-prefill shape: right-padded rows masked via kv_valid."""
        config = TINY_TEST
        params = self._params(config)
        b, t = 2, 32
        tokens = make_tokens(jax.random.PRNGKey(2), config, batch=b, seq=t)
        pos = positions_for(tokens)
        lengths = jnp.array([t, 17], jnp.int32)
        kv_valid = pos < lengths[:, None]

        cache_a = KVCache.create(config, b, t)
        dense, cache_a = forward(params, config, tokens, pos, cache=cache_a,
                                 cache_offset=0, kv_valid=kv_valid)
        cache_b = KVCache.create(config, b, t)
        chunked, cache_b = forward(params, config, tokens, pos, cache=cache_b,
                                   cache_offset=0, kv_valid=kv_valid, q_chunk=8)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(chunked),
                                   rtol=0, atol=0.05)
        np.testing.assert_allclose(np.asarray(cache_a.k), np.asarray(cache_b.k),
                                   rtol=1e-6, atol=1e-6)

    def test_matches_dense_sliding_window(self):
        from dataclasses import replace

        config = replace(TINY_TEST, sliding_window=9, name="tiny-swa")
        params = self._params(config)
        tokens = make_tokens(jax.random.PRNGKey(3), config, batch=2, seq=32)
        pos = positions_for(tokens)
        dense, _ = forward(params, config, tokens, pos)
        chunked, _ = forward(params, config, tokens, pos, q_chunk=4)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(chunked),
                                   rtol=0, atol=0.05)

    def test_policy_engages_for_8b_shapes(self):
        from operator_tpu.models.llama import _SCORE_BUDGET_BYTES, _pick_q_chunk

        # 8B prefill bucket (VERDICT r2 missing #1): n=8, t=s=4096, 32 heads
        chunk = _pick_q_chunk(8, 4096, 4096, 32)
        assert chunk is not None and 4096 % chunk == 0
        assert 8 * 32 * chunk * 4096 * 4 <= _SCORE_BUDGET_BYTES
        # bench-scale TinyLlama bucket stays dense (no scan overhead)
        assert _pick_q_chunk(16, 128, 1024, 32) is None

    def test_engine_prefill_hits_chunked_path(self, monkeypatch):
        """Force a tiny budget so the serving engine's prefill bucket takes
        the chunked path end-to-end, and generation still works."""
        import operator_tpu.models.llama as llama_mod
        from operator_tpu.models import ByteTokenizer
        from operator_tpu.serving.engine import BatchedGenerator, SamplingParams

        monkeypatch.setattr(llama_mod, "_SCORE_BUDGET_BYTES", 1 << 12)
        config = TINY_TEST
        params = self._params(config)
        gen = BatchedGenerator(params, config, ByteTokenizer(), max_slots=2,
                               max_seq=128)
        out = gen.generate("pod exited with code 137 after OOM",
                           SamplingParams(max_tokens=4, temperature=0.0))
        assert len(out.token_ids) >= 1


def test_quantize_at_load_matches_post_hoc(tmp_path):
    """load_params(quantize=True) must equal load-then-quantize_params —
    without ever holding the full float tree (the 8B-int8 OOM fix)."""
    from operator_tpu.models import load_params
    from operator_tpu.models.quant import quantize_params

    config = TINY_TEST
    params = init_params(config, jax.random.PRNGKey(7), dtype=jnp.float32)
    write_hf_checkpoint(tmp_path, config, params)

    fused = load_params(str(tmp_path), config, dtype=jnp.bfloat16, quantize=True)
    two_step = quantize_params(
        load_params(str(tmp_path), config, dtype=jnp.bfloat16), config
    )
    flat_a, tree_a = jax.tree_util.tree_flatten(fused)
    flat_b, tree_b = jax.tree_util.tree_flatten(two_step)
    assert tree_a == tree_b
    for a, b in zip(flat_a, flat_b):
        assert a.dtype == b.dtype
        af = np.asarray(a, np.float32)
        bf = np.asarray(b, np.float32)
        if a.dtype == jnp.int8:  # jit-boundary rounding: <=1 quantization level
            assert np.abs(af - bf).max() <= 1
            assert (af != bf).mean() < 0.05
        else:
            np.testing.assert_allclose(af, bf, rtol=1e-2, atol=1e-3)
    # and the quantized tree actually serves
    from operator_tpu.models.llama import forward as fwd
    tokens = make_tokens(jax.random.PRNGKey(8), config, batch=1, seq=8)
    logits, _ = fwd(fused, config, tokens, positions_for(tokens))
    assert np.isfinite(np.asarray(logits)).all()


def test_save_params_roundtrip_and_index(tmp_path):
    """save_params -> load_params identity; index + shard layout valid."""
    import json as json_mod

    from operator_tpu.models import load_params, save_params

    config = TINY_TEST
    params = init_params(config, jax.random.PRNGKey(9), dtype=jnp.float32)
    files = save_params(params, str(tmp_path), config, shard_bytes=200_000)
    assert len(files) > 1  # small shard budget forces multiple shards
    index = json_mod.load(open(tmp_path / "model.safetensors.index.json"))
    assert set(index["weight_map"].values()) == set(files)

    loaded = load_params(str(tmp_path), config, dtype=jnp.float32)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # quantized trees are refused — including PARTIALLY quantized ones
    # (merge_lora output keeps untargeted int8 groups) — and
    # dequantize_params makes them saveable
    from operator_tpu.models.quant import dequantize_params, quantize_params
    from operator_tpu.parallel import init_lora, merge_lora

    qparams = quantize_params(params, config)
    with pytest.raises(ValueError, match="dequantize"):
        save_params(qparams, str(tmp_path), config)
    merged = merge_lora(qparams, init_lora(config, jax.random.PRNGKey(1), rank=2))
    with pytest.raises(ValueError, match="dequantize"):
        save_params(merged, str(tmp_path), config)
    out = tmp_path / "dequant"
    save_params(dequantize_params(merged, dtype=jnp.float32), str(out), config)
    reloaded = load_params(str(out), config, dtype=jnp.float32)
    assert "lm_head" in reloaded


# --- Qwen2 family (q/k/v projection bias) ---------------------------------


def _qwen_tiny_config():
    import dataclasses

    return dataclasses.replace(
        TINY_TEST, name="tiny-qwen", attention_bias=True,
        rope_theta=1_000_000.0, rms_norm_eps=1e-6,
    )


def test_qwen2_bias_leaves_and_registry():
    """attention_bias adds stacked bq/bk/bv leaves; real Qwen2.5 configs are
    registered and shard cleanly (bias on the tp output axis)."""
    from operator_tpu.models import get_config

    config = _qwen_tiny_config()
    params = init_params(config, jax.random.PRNGKey(0), dtype=jnp.float32)
    n, d = config.num_layers, config.head_dim
    assert params["layers"]["bq"].shape == (n, config.num_heads * d)
    assert params["layers"]["bk"].shape == (n, config.num_kv_heads * d)
    assert params["layers"]["bv"].shape == (n, config.num_kv_heads * d)

    for name in ("qwen2.5-7b", "qwen2.5-1.5b"):
        cfg = get_config(name)
        assert cfg.attention_bias

    # the 7B factorisation divides over a tp=4 mesh, biases included
    from operator_tpu.parallel import MeshPlan, make_mesh, validate_param_shardings

    devices = jax.devices("cpu")
    if len(devices) >= 4:
        mesh = make_mesh(MeshPlan(dp=len(devices) // 4, fsdp=1, tp=4), devices)
        validate_param_shardings(mesh, get_config("qwen2.5-7b"), quantized=True)


def test_logit_parity_qwen2_bias():
    """Our bias path must reproduce HF Qwen2 logits from the same weights —
    with biases RANDOMISED (HF zero-inits them, which would hide a broken
    bias path entirely)."""
    torch = pytest.importorskip("torch")
    from transformers import Qwen2Config, Qwen2ForCausalLM

    config = _qwen_tiny_config()
    hf_config = Qwen2Config(
        vocab_size=config.vocab_size,
        hidden_size=config.hidden_size,
        intermediate_size=config.intermediate_size,
        num_hidden_layers=config.num_layers,
        num_attention_heads=config.num_heads,
        num_key_value_heads=config.num_kv_heads,
        rope_theta=config.rope_theta,
        rms_norm_eps=config.rms_norm_eps,
        max_position_embeddings=config.max_seq_len,
        tie_word_embeddings=False,
        use_sliding_window=False,
        attn_implementation="eager",
    )
    torch.manual_seed(13)
    model = Qwen2ForCausalLM(hf_config).eval()
    with torch.no_grad():
        for name, tensor in model.named_parameters():
            if name.endswith("_proj.bias"):
                tensor.normal_(0.0, 0.5)

    params = convert_hf_state_dict(model.state_dict(), config, dtype=jnp.float32)
    assert float(np.abs(np.asarray(params["layers"]["bq"])).max()) > 0.01

    rng = np.random.RandomState(5)
    tokens_np = rng.randint(0, config.vocab_size, size=(2, 24)).astype(np.int64)
    with torch.no_grad():
        hf_logits = model(torch.from_numpy(tokens_np)).logits.numpy()
    tokens = jnp.asarray(tokens_np, jnp.int32)
    ours, _ = forward(params, config, tokens, positions_for(tokens))
    ours = np.asarray(ours)
    np.testing.assert_allclose(ours, hf_logits, rtol=1e-2, atol=1e-2)
    assert (ours.argmax(-1) == hf_logits.argmax(-1)).mean() == 1.0

    # zero biases must change the logits (the path is live, not decorative)
    zeroed = {
        **params,
        "layers": {
            **params["layers"],
            "bq": jnp.zeros_like(params["layers"]["bq"]),
            "bk": jnp.zeros_like(params["layers"]["bk"]),
            "bv": jnp.zeros_like(params["layers"]["bv"]),
        },
    }
    no_bias, _ = forward(zeroed, config, tokens, positions_for(tokens))
    assert not np.allclose(np.asarray(no_bias), ours, atol=1e-3)


def test_qwen2_decode_parity_paths():
    """Contiguous decode AND paged decode must both apply the bias: decode a
    short sequence token-by-token through each cache and match the full
    forward's logits."""
    from operator_tpu.ops.paged_attention import PagedKVCache

    config = _qwen_tiny_config()
    params = init_params(config, jax.random.PRNGKey(2), dtype=jnp.float32)
    # randomise the biases so a dropped bias add cannot pass
    key_q, key_k, key_v = jax.random.split(jax.random.PRNGKey(3), 3)
    layers = dict(params["layers"])
    layers["bq"] = jax.random.normal(key_q, layers["bq"].shape, jnp.float32) * 0.5
    layers["bk"] = jax.random.normal(key_k, layers["bk"].shape, jnp.float32) * 0.5
    layers["bv"] = jax.random.normal(key_v, layers["bv"].shape, jnp.float32) * 0.5
    params = {**params, "layers": layers}

    tokens = make_tokens(jax.random.PRNGKey(4), config, batch=2, seq=10)
    pos = positions_for(tokens)
    full_logits, _ = forward(params, config, tokens, pos)

    # contiguous: prefill 6 + decode 4
    cache = KVCache.create(config, batch_size=2, max_seq_len=16, dtype=jnp.float32)
    prefill, cache = forward(params, config, tokens[:, :6], pos[:, :6],
                             cache=cache, cache_offset=0)
    np.testing.assert_allclose(prefill, full_logits[:, :6], rtol=2e-4, atol=2e-4)
    for i in range(6, 10):
        step_logits, cache = decode_step(
            params, config, tokens[:, i : i + 1], pos[:, i : i + 1],
            cache, jnp.int32(i),
        )
        np.testing.assert_allclose(step_logits, full_logits[:, i], rtol=2e-4, atol=2e-4)

    # paged: decode every token from an empty cache, one page table per row
    from operator_tpu.models.llama import decode_step_paged

    paged = PagedKVCache.create(
        num_layers=config.num_layers, num_pages=9, page_size=4,
        kv_heads=config.num_kv_heads, head_dim=config.head_dim,
        batch_size=2, pages_per_seq=4, dtype=jnp.float32,
    )
    paged = PagedKVCache(
        k_pages=paged.k_pages, v_pages=paged.v_pages,
        page_table=jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32),
        lengths=paged.lengths,
    )
    for i in range(10):
        step_logits, paged = decode_step_paged(
            params, config, tokens[:, i : i + 1], paged
        )
        np.testing.assert_allclose(
            step_logits, full_logits[:, i], rtol=2e-4, atol=2e-4,
            err_msg=f"paged decode step {i}",
        )


def test_qwen2_checkpoint_roundtrip(tmp_path):
    """save_params emits the HF bias names; load_params reads them back."""
    import json as json_mod

    from operator_tpu.models import load_params, save_params

    config = _qwen_tiny_config()
    params = init_params(config, jax.random.PRNGKey(6), dtype=jnp.float32)
    layers = dict(params["layers"])
    layers["bq"] = jnp.full_like(layers["bq"], 0.25)
    params = {**params, "layers": layers}

    save_params(params, str(tmp_path), config)
    index = json_mod.load(open(tmp_path / "model.safetensors.index.json"))
    assert "model.layers.0.self_attn.q_proj.bias" in index["weight_map"]

    loaded = load_params(str(tmp_path), config, dtype=jnp.float32)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
