#!/usr/bin/env python
"""Sustained open-loop soak of the WHOLE pipeline: fake apiserver feeding
the real serving engine.

The north star (BASELINE.md) is ">=100 explanations/min sustained with
p50 < 2 s" — *sustained* is the half a 60 s bench window can't show.
This harness runs the operator control plane (watcher -> pattern engine
-> tpu-native provider -> storage -> events) against the in-memory fake
apiserver for SOAK_SECONDS, injecting pod failures as a Poisson process
at SOAK_RATE/min, and reports:

- arrival -> durable-annotation latency p50/p99 (the user-visible SLO,
  measured at the etcd-equivalent write, not at engine completion)
- completions, in-window throughput, stragglers at the deadline
- leak audit after drain: KV pages back on the free list, zero active or
  reserved slots, engine reset (auto-recovery) count

Knobs (env): SOAK_SECONDS (600), SOAK_RATE (100, arrivals/min),
SOAK_MODEL (OperatorConfig's default model),
SOAK_SLOTS (16), SOAK_MAX_TOKENS (96), SOAK_DRAIN_S (120).

Prints one JSON line; exit 1 when the leak audit fails.

Runs on a TPU (`chiprun -- python scripts/soak.py`); a CPU smoke soak
asks for both by name: OPERATOR_TPU_PLATFORM=cpu SOAK_MODEL=tiny-test.
"""

from __future__ import annotations

import asyncio
import json
import os
import pathlib
import random
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

FIXTURES = REPO / "tests" / "fixtures"


def _percentile(values: list, q: float) -> float:
    if not values:
        return float("nan")
    ordered = sorted(values)
    idx = min(len(ordered) - 1, max(0, round(q * (len(ordered) - 1))))
    return ordered[idx]


async def main() -> int:
    from operator_tpu.utils.platform import (
        enable_persistent_compilation_cache,
        resolve_device,
    )

    # a TPU, or the backend OPERATOR_TPU_PLATFORM names — nothing assumed
    platform = resolve_device().platform
    enable_persistent_compilation_cache()

    from operator_tpu.utils.compilewatch import CompileWatcher

    compile_watch = CompileWatcher()

    from operator_tpu.operator.app import Operator
    from operator_tpu.operator.kubeapi import FakeKubeApi
    from operator_tpu.operator.storage import ANNOTATION_ANALYZED_AT
    from operator_tpu.schema import (
        AIProvider,
        AIProviderRef,
        AIProviderSpec,
        ContainerState,
        ContainerStateTerminated,
        ContainerStatus,
        LabelSelector,
        ObjectMeta,
        Pod,
        Podmortem,
        PodmortemSpec,
        PodStatus,
    )
    from operator_tpu.utils.config import OperatorConfig

    default_model = OperatorConfig().model_id
    seconds = float(os.environ.get("SOAK_SECONDS", "600"))
    rate_per_min = float(os.environ.get("SOAK_RATE", "100"))
    model_id = os.environ.get("SOAK_MODEL", default_model)
    slots = int(os.environ.get("SOAK_SLOTS", "16"))
    max_tokens = int(os.environ.get("SOAK_MAX_TOKENS", "96"))
    drain_s = float(os.environ.get("SOAK_DRAIN_S", "120"))

    logs = sorted(FIXTURES.glob("*.log"))
    assert logs, f"no fixture logs under {FIXTURES}"
    corpus = [p.read_text()[-4096:] for p in logs]

    api = FakeKubeApi()
    config = OperatorConfig(
        pattern_cache_directory="/nonexistent",
        health_port=-1,
        completion_api_host="127.0.0.1",
        completion_api_port=0,  # builds + warms the shared engine
        model_id=model_id,
        allow_random_weights=True,
        max_batch_size=slots,
        watch_restart_delay_s=0.01,
        conflict_backoff_base_s=0.001,
    )
    app = Operator(api, config=config)
    await app.start()
    try:
        # wait out weight load + warmup compile BEFORE arrivals start: the
        # soak measures steady state, readiness covers the cold window
        await asyncio.wait_for(app.completion_task, timeout=1800)
        if app.completion_server is None:
            print(json.dumps({"metric": "soak", "error": "engine failed to build"}))
            return 1
        engine = app.completion_server.engine

        provider = AIProvider(
            metadata=ObjectMeta(name="soak-provider", namespace="podmortem-system"),
            spec=AIProviderSpec(provider_id="tpu-native", model_id=model_id,
                                max_tokens=max_tokens),
        )
        await api.create("AIProvider", provider.to_dict())
        pm = Podmortem(
            metadata=ObjectMeta(name="soak", namespace="podmortem-system"),
            spec=PodmortemSpec(
                pod_selector=LabelSelector(match_labels={"app": "soak"}),
                ai_provider_ref=AIProviderRef(name="soak-provider",
                                              namespace="podmortem-system"),
            ),
        )
        await api.create("Podmortem", pm.to_dict())
        await app.watcher.cache.prime()

        # everything compiled from here on is a mid-run compile: an SLO
        # violation (the p99 tail at 100/min), not just noise.  The soak
        # reports each one with its offset into the run and build time.
        compile_watch.mark()

        rng = random.Random(0)
        started = time.monotonic()
        deadline = started + seconds
        submitted: dict[str, float] = {}
        latencies: list[float] = []
        in_window = 0

        polling = True

        async def poll_completions() -> None:
            # runs until the main loop clears `polling` (NOT until
            # `submitted` drains: it starts before the first arrival)
            nonlocal in_window
            while polling:
                done = []
                for name, t0 in submitted.items():
                    try:
                        pod = await api.get("Pod", name, "soak-ns")
                    except Exception:
                        continue
                    annotations = (pod.get("metadata") or {}).get("annotations") or {}
                    if ANNOTATION_ANALYZED_AT in annotations:
                        dt = time.monotonic() - t0
                        latencies.append(dt)
                        if time.monotonic() < deadline:
                            in_window += 1
                        done.append(name)
                for name in done:
                    del submitted[name]
                await asyncio.sleep(0.25)

        poller = asyncio.create_task(poll_completions())

        i = 0
        while time.monotonic() < deadline:
            # Poisson process: exponential inter-arrival gaps
            await asyncio.sleep(rng.expovariate(rate_per_min / 60.0))
            if time.monotonic() >= deadline:
                break
            name = f"soak-{i}"
            i += 1
            pod = Pod(
                metadata=ObjectMeta(name=name, namespace="soak-ns",
                                    labels={"app": "soak"}),
                status=PodStatus(phase="Running", container_statuses=[
                    ContainerStatus(
                        name="app", restart_count=1,
                        state=ContainerState(terminated=ContainerStateTerminated(
                            exit_code=137,
                            finished_at=f"2026-07-30T00:00:{i % 60:02d}Z")),
                    )]),
            )
            await api.create("Pod", pod.to_dict())
            api.set_pod_log("soak-ns", name, corpus[i % len(corpus)])
            submitted[name] = time.monotonic()
            await app.watcher.handle_pod_event("MODIFIED", pod)

        arrivals = i
        # drain: stragglers get a bounded window, then count as incomplete
        try:
            await asyncio.wait_for(app.watcher.drain(), timeout=drain_s)
        except asyncio.TimeoutError:
            pass
        drain_deadline = time.monotonic() + 10
        while submitted and time.monotonic() < drain_deadline:
            await asyncio.sleep(0.5)
        stragglers = len(submitted)
        polling = False
        await poller

        # ---- leak audit ------------------------------------------------
        generator = engine.generator
        leaks = {}
        if generator.paged:
            allocator = generator.allocator
            free = len(allocator._free)
            # minus the trash page and the generator-owned shared-prefix
            # pages (held for the engine's lifetime by design)
            held = int(getattr(generator, "prefix_held_pages", 0))
            total = allocator.num_pages - 1 - held
            if free != total:
                leaks["kv_pages"] = {"free": free, "total": total,
                                     "prefix_held": held}
        if generator.num_active:
            leaks["active_slots"] = generator.num_active
        if generator._reserved:
            leaks["reserved_slots"] = sorted(generator._reserved)
        resets = len(engine._reset_times)

        wall = time.monotonic() - started
        midrun = compile_watch.events_since_mark()
        record = {
            "metric": "soak",
            "platform": platform,
            "model": model_id,
            "seconds": round(wall, 1),
            "rate_per_min": rate_per_min,
            "arrivals": arrivals,
            "completed": len(latencies),
            "completed_in_window": in_window,
            "stragglers_at_deadline": stragglers,
            "throughput_per_min": round(60.0 * len(latencies) / wall, 1),
            "p50_s": round(_percentile(latencies, 0.50), 3),
            "p90_s": round(_percentile(latencies, 0.90), 3),
            "p99_s": round(_percentile(latencies, 0.99), 3),
            "engine_resets": resets,
            "midrun_compiles": len(midrun),
            "midrun_compile_events": [
                {"t_s": round(t, 1), "name": n,
                 "build_s": round(d, 2) if d is not None else None}
                for t, n, d in midrun[:40]
            ],
            "leaks": leaks or None,
            "slo_p50_under_2s": (
                bool(latencies) and _percentile(latencies, 0.50) < 2.0
            ),
        }
        print(json.dumps(record), flush=True)
        return 1 if leaks else 0
    finally:
        await app.stop()


if __name__ == "__main__":
    raise SystemExit(asyncio.run(main()))
