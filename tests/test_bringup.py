"""Bring-up rules (PR 21): nothing hides the device, the model, the kernel
or the cache a process really runs with.

- the device: a non-TPU backend is served only when asked for by name;
- the compile cache: ``JAX_COMPILATION_CACHE_DIR`` if set (then the code
  sets nothing), else ``<checkout>/.jax_cache``;
- the engine: a configuration the continuous scheduler cannot serve is an
  error naming the reason, and on a TPU a model whose head_dim the ragged
  kernel cannot lower is refused at build;
- the demo: exits non-zero when the analysis it ran errored or degraded;
- ``chip_smoke.py``: fails without a chip, and its explicit CPU dry run
  drives every leg.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from operator_tpu.models import get_config
from operator_tpu.utils import platform
from operator_tpu.utils.config import OperatorConfig

REPO = pathlib.Path(__file__).resolve().parent.parent


def _run(argv, env_overrides, timeout=600):
    env = dict(os.environ)
    env.pop("OPERATOR_TPU_PLATFORM", None)  # conftest's; each case sets its own
    env.update(env_overrides)
    return subprocess.run(
        [sys.executable, *argv], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=str(REPO),
    )


# --- the device ------------------------------------------------------------


def test_non_tpu_backend_needs_to_be_asked_for_by_name(monkeypatch):
    # an ambient JAX_PLATFORMS=cpu (the sandbox sets it for every process)
    # is not a request: only OPERATOR_TPU_PLATFORM is
    monkeypatch.delenv("OPERATOR_TPU_PLATFORM")
    with pytest.raises(platform.NoAccelerator, match="OPERATOR_TPU_PLATFORM=cpu"):
        platform.resolve_device()
    monkeypatch.setenv("OPERATOR_TPU_PLATFORM", "cpu")
    device = platform.resolve_device()
    assert (device.platform, device.kind) == ("cpu", "cpu")
    assert device.to_dict() == {
        "platform": "cpu", "kind": "cpu", "count": device.count
    }


def test_server_exits_non_zero_without_a_chip():
    done = _run(["-m", "operator_tpu.serving", "--port", "0"], {
        "JAX_PLATFORMS": "cpu", "OPERATOR_TPU_MODEL": "tiny-test",
        "ALLOW_RANDOM_WEIGHTS": "true",
    })
    assert done.returncode != 0
    assert "NoAccelerator" in done.stderr


# --- the compile cache -----------------------------------------------------


def test_cache_dir_env_set_means_no_config_update(monkeypatch, tmp_path):
    import jax

    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda *args: updates.append(args)
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert platform.enable_persistent_compilation_cache() == str(tmp_path)
    assert updates == []


def test_cache_dir_unset_is_a_fixed_path_in_the_checkout(monkeypatch):
    import jax

    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda *args: updates.append(args)
    )
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expected = str(REPO / ".jax_cache")
    # twice: never a temp name, a pid or a time — the path is part of the key
    assert platform.enable_persistent_compilation_cache() == expected
    assert platform.enable_persistent_compilation_cache() == expected
    assert updates == [("jax_compilation_cache_dir", expected)] * 2


# --- the engine ------------------------------------------------------------


def test_head_dim_the_ragged_kernel_cannot_lower_is_refused_by_name():
    from operator_tpu.ops.ragged_attention import (
        UnsupportedHeadDim,
        require_ragged_kernel_support,
    )

    for name in ("qwen2.5-1.5b", "qwen2.5-7b", "mistral-7b", "llama-3-8b"):
        require_ragged_kernel_support(get_config(name))  # head_dim 128
    for name in ("tinyllama-1.1b", "llama-3.2-1b"):
        with pytest.raises(UnsupportedHeadDim) as refusal:
            require_ragged_kernel_support(get_config(name))
        assert "head_dim=64" in str(refusal.value)
        assert "_ragged_attention_pallas" in str(refusal.value)
    # the default deployment must start on the chip
    require_ragged_kernel_support(get_config(OperatorConfig().model_id))


def test_engine_build_refuses_unsupported_head_dim_on_a_tpu(monkeypatch):
    from operator_tpu.ops.ragged_attention import UnsupportedHeadDim
    from operator_tpu.serving import provider

    tpu = platform.DeviceInfo(platform="tpu", kind="TPU v5 lite", count=1)
    monkeypatch.setattr(platform, "resolve_device", lambda: tpu)
    config = OperatorConfig(model_id="tinyllama-1.1b", allow_random_weights=True)
    # refused before a single weight is drawn — and never routed to the
    # reference attention instead
    with pytest.raises(UnsupportedHeadDim, match="head_dim=64"):
        provider.build_serving_engine(config)


@pytest.mark.parametrize("overrides, reason", [
    ({"serving_mesh": "dp=1,tp=2"}, "serving_mesh"),
    ({"kv_cache_mode": "contiguous"}, "kv_cache_mode"),
])
def test_continuous_scheduler_blocker_is_an_error_not_another_engine(
    overrides, reason
):
    from operator_tpu.serving.provider import build_serving_engine

    config = OperatorConfig(
        model_id="tiny-test", allow_random_weights=True, **overrides
    )
    with pytest.raises(ValueError) as refusal:
        build_serving_engine(config)
    assert "sched_mode=continuous cannot serve" in str(refusal.value)
    assert reason in str(refusal.value)
    assert "SCHED_MODE=wave" in str(refusal.value)


def test_engine_reports_the_device_it_was_built_on():
    from operator_tpu.serving.provider import build_serving_engine

    config = OperatorConfig(
        model_id="tiny-test", allow_random_weights=True, max_batch_size=2
    )
    engine, _ = build_serving_engine(config)
    try:
        load = engine.load_report()
        assert load.device == engine.device.to_dict()
        assert load.device["platform"] == "cpu"
        assert load.to_dict()["device"] == load.device
        # one entry per local device; the cpu reports no memory numbers
        assert [d["id"] for d in engine.device_memory()] == [
            d.id for d in __import__("jax").local_devices()
        ]
        assert engine.compile_watch.report()["count"] >= 0
    finally:
        engine.compile_watch.close()
        engine._executor.shutdown(wait=False)


# --- the demo ---------------------------------------------------------------


def test_demo_exits_non_zero_when_the_ai_leg_degraded():
    # tpu-native without weights: the operator degrades to a pattern-only
    # result (right in production) — the DEMO must say it did not work
    done = _run(
        ["-m", "operator_tpu.operator", "--demo", "--provider", "tpu-native"],
        {"OPERATOR_TPU_PLATFORM": "cpu", "MODEL_ID": "tiny-test"},
    )
    assert done.returncode == 1, done.stderr[-2000:]
    summary = json.loads(done.stdout)
    assert summary["engine"] is None
    assert "demo failed" in done.stderr
    assert any(e["reason"] == "PodmortemAnalysisError" for e in summary["events"])
    # ... and the plain template demo still passes
    assert _run(["-m", "operator_tpu.operator", "--demo"], {}).returncode == 0


# --- chip_smoke.py ----------------------------------------------------------


def test_chip_smoke_fails_without_a_chip_and_prints_no_result():
    done = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"})
    assert done.returncode != 0
    assert '"ok"' not in done.stdout


def test_chip_smoke_cpu_dry_run_drives_every_leg():
    # four virtual devices, so the multi-chip leg is debugged here too
    done = _run(["chip_smoke.py", "--dry-run-cpu"], {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    }, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["ok"] is True and result["dry_run"] is True
    assert result["device"]["platform"] == "cpu"
    report = json.loads(lines[-2])
    assert set(report["legs"]) == {"kernels", "server", "pipeline", "mesh"}
    for leg in ("server", "mesh"):
        assert report["legs"][leg]["storm"]["succeeded"] > 0
    assert report["legs"]["pipeline"]["completion_tokens"] > 0
