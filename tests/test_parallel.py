"""Mesh/sharding tests on the 8-device virtual CPU mesh: plan selection,
sharded-vs-single-device forward equivalence, and the jitted train step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from operator_tpu.models import TINY_TEST, get_config, init_params
from operator_tpu.models.llama import forward
from operator_tpu.parallel import (
    MeshPlan,
    device_memory_bytes,
    make_mesh,
    make_train_step,
    mesh_summary,
    param_specs,
    plan_for,
    shard_params,
)


def cpu_devices(n=8):
    devices = jax.devices("cpu")
    if len(devices) < n:
        pytest.skip(f"need {n} cpu devices, have {len(devices)}")
    return devices[:n]


# --- planning -------------------------------------------------------------


def test_plan_defaults_to_dp():
    plan = plan_for(8)
    assert plan == MeshPlan(dp=8, fsdp=1, tp=1)


def test_plan_llama3_8b_needs_tp_on_v5e():
    # bf16 8B ≈ 16 GB > 7/8 of a 16 GB chip -> tp=2; kv_heads=8 divisible ✓
    plan = plan_for(4, config=get_config("llama-3-8b"), hbm_bytes=16 * 2**30)
    assert plan.tp >= 2
    assert plan.total == 4


def test_plan_small_model_stays_dp():
    plan = plan_for(8, config=get_config("qwen2.5-1.5b"), hbm_bytes=16 * 2**30)
    assert plan.tp == 1 and plan.dp == 8


def test_plan_from_config_needs_a_measured_memory_size():
    # no assumed chip: sizing tp from a model takes the device's own number,
    # and a backend that reports none (the cpu) says so
    with pytest.raises(ValueError, match="hbm_bytes"):
        plan_for(4, config=get_config("llama-3-8b"))
    with pytest.raises(ValueError, match="reports no memory size"):
        device_memory_bytes(jax.devices("cpu")[0])


def test_plan_rejects_oversubscription():
    with pytest.raises(ValueError):
        plan_for(4, tp=4, fsdp=2)


def test_param_specs_cover_all_params():
    params = init_params(TINY_TEST, jax.random.PRNGKey(0))
    specs = param_specs(TINY_TEST)
    # same tree structure -> every param has a placement rule
    jax.tree_util.tree_map(lambda p, s: None, params, specs)


# --- sharded execution ----------------------------------------------------


def test_sharded_forward_matches_single_device():
    devices = cpu_devices(8)
    mesh = make_mesh(MeshPlan(dp=2, fsdp=2, tp=2), devices)
    config = TINY_TEST
    params = init_params(config, jax.random.PRNGKey(0), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, config.vocab_size,
                                dtype=jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32)[None], (4, 16))

    ref_logits, _ = forward(params, config, tokens, positions)

    sharded = shard_params(params, mesh, config)
    # params are actually distributed
    wq_sharding = sharded["layers"]["wq"].sharding
    assert not wq_sharding.is_fully_replicated
    logits, _ = jax.jit(lambda p, t, pos: forward(p, config, t, pos))(sharded, tokens, positions)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4)
    print(mesh_summary(mesh))


def test_train_step_learns_and_stays_sharded():
    devices = cpu_devices(8)
    mesh = make_mesh(MeshPlan(dp=4, fsdp=1, tp=2), devices)
    config = TINY_TEST
    params = init_params(config, jax.random.PRNGKey(0), dtype=jnp.float32)
    params = shard_params(params, mesh, config)
    init_state, train_step = make_train_step(config, mesh)
    state = init_state(params)

    # a fixed tiny batch: loss must drop when repeatedly trained on it
    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0, config.vocab_size,
                                dtype=jnp.int32)
    mask = jnp.ones((4, 32), jnp.float32)
    losses = []
    for _ in range(5):
        state, loss = train_step(state, tokens, mask)
        losses.append(float(loss))
    assert losses[-1] < losses[0], f"loss did not drop: {losses}"
    wq_sharding = state.params["layers"]["wq"].sharding
    assert not wq_sharding.is_fully_replicated  # constraint kept placement


def test_dryrun_multichip_entry():
    cpu_devices(8)
    import __graft_entry__ as entrypoints

    entrypoints.dryrun_multichip(8)


def test_dryrun_multichip_16_devices():
    """The v5e-16 factorisations (dp4·tp4 serving, fsdp4·tp4 training) run
    end to end — a 16-virtual-device subprocess because the suite's own
    backend is pinned to 8 devices at conftest import."""
    import os
    import pathlib
    import subprocess
    import sys

    repo = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=16",
        PYTHONPATH=str(repo),
    )
    out = subprocess.run(
        [sys.executable, str(repo / "__graft_entry__.py"), "16"],
        capture_output=True, text=True, timeout=900, env=env, cwd=str(repo),
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert out.stdout.count("[dryrun_multichip] 16-device ok") == 2, out.stdout


def test_entry_compiles_tiny():
    import os

    os.environ["GRAFT_ENTRY_MODEL"] = "tiny-test"
    try:
        import __graft_entry__ as entrypoints

        fn, args = entrypoints.entry()
        lowered = jax.jit(fn).lower(*args)
        compiled = lowered.compile()
        out = compiled(*args)
        assert out.shape == (1, 128, 512)
    finally:
        os.environ.pop("GRAFT_ENTRY_MODEL", None)


class TestLora:
    """LoRA adapters: identity at init, adapter-only training, quantized
    base merge — the fine-tune flow that fits 8B adaptation on one chip."""

    def _mesh(self):
        from operator_tpu.parallel import MeshPlan, make_mesh

        return make_mesh(MeshPlan(dp=2, fsdp=2, tp=2), jax.devices("cpu")[:8])

    def test_zero_b_is_identity(self):
        from operator_tpu.parallel import apply_lora, init_lora

        config = TINY_TEST
        params = init_params(config, jax.random.PRNGKey(0), dtype=jnp.float32)
        adapters = init_lora(config, jax.random.PRNGKey(1), rank=4,
                             dtype=jnp.float32)
        merged = apply_lora(params, adapters)
        tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0,
                                    config.vocab_size, dtype=jnp.int32)
        positions = jnp.broadcast_to(jnp.arange(12, dtype=jnp.int32)[None], (2, 12))
        ref, _ = forward(params, config, tokens, positions)
        got, _ = forward(merged, config, tokens, positions)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)

    def test_adapter_training_reduces_loss_and_freezes_base(self):
        from operator_tpu.parallel import apply_lora as apply_lora_f32
        from operator_tpu.parallel import init_lora, make_lora_train_step
        from operator_tpu.parallel.lora import lora_param_count

        config = TINY_TEST
        mesh = self._mesh()
        params = init_params(config, jax.random.PRNGKey(0), dtype=jnp.float32)
        adapters = init_lora(config, jax.random.PRNGKey(1), rank=4,
                             dtype=jnp.float32)
        assert lora_param_count(adapters) < 0.1 * sum(
            x.size for x in jax.tree_util.tree_leaves(params))
        init_state, train_step = make_lora_train_step(config, mesh)
        state = init_state(adapters)
        tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0,
                                    config.vocab_size, dtype=jnp.int32)
        mask = jnp.ones((4, 16), jnp.float32)
        losses = []
        for _ in range(8):
            state, loss = train_step(state, params, tokens, mask)
            losses.append(float(loss))
        assert losses[-1] < losses[0] - 0.05, losses
        # deployment property: (frozen base + trained adapters) alone
        # reproduces the improvement — nothing leaked into base training
        from operator_tpu.parallel import next_token_loss

        reproduced = float(next_token_loss(
            params, config, tokens, mask, lora=state.params))
        assert reproduced < losses[0] - 0.05
        merged = float(next_token_loss(
            apply_lora_f32(params, state.params), config, tokens, mask))
        assert abs(merged - reproduced) < 0.05  # merge == low-rank path

    def test_merge_into_quantized_base(self):
        from operator_tpu.models.quant import quantize_params
        from operator_tpu.parallel import init_lora, merge_lora

        config = TINY_TEST
        params = quantize_params(
            init_params(config, jax.random.PRNGKey(0)), config)
        adapters = init_lora(config, jax.random.PRNGKey(1), rank=4)
        merged = merge_lora(params, adapters)
        # adapted matrices dequantized to float; others stay int8
        assert not isinstance(merged["layers"]["wq"], dict)
        assert isinstance(merged["layers"]["w_gate"], dict)
        tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0,
                                    config.vocab_size, dtype=jnp.int32)
        positions = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32)[None], (1, 8))
        logits, _ = forward(merged, config, tokens, positions)
        assert np.isfinite(np.asarray(logits)).all()

    def test_lora_shardings_divide_and_match_base_axes(self):
        from operator_tpu.parallel import init_lora, lora_shardings
        from operator_tpu.parallel.lora import lora_specs

        config = TINY_TEST
        mesh = self._mesh()
        targets = ("wq", "wk", "wv", "wo", "w_down")
        adapters = init_lora(config, jax.random.PRNGKey(1), rank=4,
                             targets=targets)
        shardings = lora_shardings(mesh, adapters, config)
        for name, pair in shardings.items():
            for leaf_name in ("a", "b"):
                pair[leaf_name].shard_shape(adapters[name][leaf_name].shape)
        # row-parallel wo: fan-in on tp, fan-out on fsdp — derived, not
        # hardcoded column-parallel
        specs = lora_specs(config, targets)
        assert specs["wo"]["a"] == jax.sharding.PartitionSpec(None, "tp", None)
        assert specs["wo"]["b"] == jax.sharding.PartitionSpec(None, None, "fsdp")
        assert specs["wq"]["a"][1] == "fsdp" and specs["wq"]["b"][2] == "tp"

    def test_lora_training_over_quantized_base(self):
        from operator_tpu.models.quant import quantize_params
        from operator_tpu.parallel import init_lora, make_lora_train_step

        config = TINY_TEST
        mesh = self._mesh()
        base = quantize_params(
            init_params(config, jax.random.PRNGKey(0), dtype=jnp.float32), config)
        adapters = init_lora(config, jax.random.PRNGKey(1), rank=4,
                             dtype=jnp.float32)
        init_state, train_step = make_lora_train_step(
            config, mesh, quantized_base=True)
        state = init_state(adapters)
        tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0,
                                    config.vocab_size, dtype=jnp.int32)
        mask = jnp.ones((4, 16), jnp.float32)
        first = last = None
        for _ in range(6):
            state, loss = train_step(state, base, tokens, mask)
            first = float(loss) if first is None else first
            last = float(loss)
        assert last < first, (first, last)


def test_train_state_checkpoint_roundtrip(tmp_path):
    """save_train_state/load_train_state: a sharded fine-tune resumes
    exactly — params, optimizer moments, and step all round-trip onto the
    reference's mesh placement (orbax under the hood)."""
    from operator_tpu.parallel import (
        MeshPlan, load_train_state, make_mesh, make_train_step,
        save_train_state, shard_params,
    )

    cpu_devices(8)
    mesh = make_mesh(MeshPlan(dp=2, fsdp=2, tp=2), jax.devices("cpu"))
    params = shard_params(
        init_params(TINY_TEST, jax.random.PRNGKey(0), dtype=jnp.float32),
        mesh, TINY_TEST,
    )
    init_state, train_step = make_train_step(TINY_TEST, mesh)
    state = init_state(params)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (4, 32), 0, TINY_TEST.vocab_size, dtype=jnp.int32
    )
    mask = jnp.ones((4, 32), jnp.float32)
    state, _ = train_step(state, tokens, mask)

    path = str(tmp_path / "ckpt")
    save_train_state(state, path)
    reference = init_state(
        shard_params(
            init_params(TINY_TEST, jax.random.PRNGKey(9), dtype=jnp.float32),
            mesh, TINY_TEST,
        )
    )
    restored = load_train_state(path, reference)
    assert int(restored.step) == int(state.step) == 1
    # EVERY leaf — params AND optimizer moments — round-trips exactly
    # (the moments are the thing a resume exists to preserve)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # spec normal forms may differ (P() vs P(None, None)): compare
        # placement semantics, not representation
        assert a.sharding.is_equivalent_to(b.sharding, max(a.ndim, 1))
    # resuming actually CONTINUES: one more step from the restored state
    # produces the same loss as one more step from the original (state
    # was train_step's fresh OUTPUT — only the initial state was donated)
    next_a, loss_a = train_step(restored, tokens, mask)
    _, loss_b = train_step(state, tokens, mask)
    assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-6)
    assert int(next_a.step) == 2
    # and overwriting the same path works (the fixed-path resume story)
    save_train_state(next_a, path)
