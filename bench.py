#!/usr/bin/env python
"""End-to-end benchmark: pod-failure explanations per minute on one chip.

Replays recorded failure logs through the REAL pipeline — pattern match
(CPU) -> prompt build -> continuous-batching LLM generation on the TPU
(operator_tpu.serving.engine) — and measures sustained throughput and p50
arrival->completion latency for BENCH_REQUESTS concurrent failure events.

The reference system publishes no benchmarks (BASELINE.md); the driver's
north star is >=100 explanations/min sustained with p50 < 2 s.  The primary
JSON metric is explanations/min, vs_baseline = value / 100.

Weights are random-init bf16 (no network egress to fetch checkpoints);
generation speed is weight-value independent, so throughput/latency numbers
are honest.  EOS stopping is disabled so every request generates exactly
BENCH_MAX_TOKENS tokens — deterministic work per request.

Two phases:

1. **closed batch** — BENCH_REQUESTS submitted at t=0 and drained: peak
   batched throughput (the headline expl/min metric).
2. **open loop** — a seeded failure storm at BENCH_RATE/min for
   BENCH_OPEN_SECONDS through the FULL operator->router->serving stack
   (operator_tpu/loadgen/), with SLO accounting from the ledger
   (obs/sloledger.py): offered vs achieved, per-class attainment,
   goodput-under-SLO, shed/deadline-exceeded breakdown, and the
   two-replay determinism gate (``replay_identical``).  The closed
   batch's p50 ~= wall time is a queueing artifact;
   this phase is the honest number.  Set BENCH_OPEN=0 to skip,
   BENCH_SWEEP="60,100,150" for a rate sweep.

The bench runs on a TPU and exits non-zero when JAX finds none: it never
substitutes a backend or a model.  Running it on another backend takes
``OPERATOR_TPU_PLATFORM=<name>`` (utils/platform.py) and an explicit
``BENCH_MODEL`` small enough for it; the record names the device it ran
on, and no MFU is computed for a device without a row in the peak table
(serving/perf.py).  Its lanes still drive the WAVE engine directly — they
are ROADMAP S1/S2's to rebuild as cells on the default path.

Knobs (env): BENCH_MODEL (qwen2.5-1.5b), BENCH_REQUESTS (32),
BENCH_SLOTS (16), BENCH_MAX_TOKENS (96), BENCH_MAX_SEQ (1024),
BENCH_RATE (100), BENCH_OPEN_SECONDS (60), BENCH_TOKENIZER (builtin-bpe).
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def build_requests(n: int) -> list:
    """n AnalysisRequests from the recorded failure fixtures."""
    from operator_tpu.patterns.engine import PatternEngine
    from operator_tpu.schema.analysis import AnalysisRequest, PodFailureData

    fixture_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests", "fixtures")
    fixtures = []
    for name in sorted(os.listdir(fixture_dir)):
        if name.endswith(".log"):
            with open(os.path.join(fixture_dir, name)) as f:
                fixtures.append(f.read())
    assert fixtures, "no .log fixtures found"

    engine = PatternEngine()
    requests = []
    for i in range(n):
        failure = PodFailureData(logs=fixtures[i % len(fixtures)])
        result = engine.analyze(failure)
        requests.append(AnalysisRequest(analysis_result=result, failure_data=failure))
    return requests


async def run_open_loop(
    replicas,
    *,
    rate_per_min: float,
    duration_s: float,
    seed: int = 0,
    time_scale: float = 1.0,
    drain_s: float = 60.0,
) -> dict:
    """One seeded open-loop failure storm through the FULL stack —
    operator pipeline -> router -> serving replicas (operator_tpu/loadgen/)
    — with SLO accounting from the ledger (obs/sloledger.py).

    Arrivals are a seeded storm schedule materialised up front and fired
    whether or not the system keeps up (arrivals never wait in line);
    the record reports offered vs achieved, per-class latency
    percentiles, attainment, goodput-under-SLO, and the shed /
    deadline-exceeded breakdown.  The schedule is materialised TWICE
    independently and the record carries ``replay_identical`` — the
    two-replay determinism gate — plus a zero-torn-lines audit of the
    ledger journal."""
    import tempfile

    from operator_tpu.loadgen import ArrivalProcess, ArrivalSpec
    from operator_tpu.loadgen.storm import build_storm_stack, run_storm

    spec = ArrivalSpec(
        name="storm", rate_per_min=rate_per_min, duration_s=duration_s,
    )
    process = ArrivalProcess(spec, seed=seed)
    replay = ArrivalProcess(spec, seed=seed)
    replay_identical = (
        process.fingerprint() == replay.fingerprint()
        and [e.to_dict() for e in process.materialize()]
        == [e.to_dict() for e in replay.materialize()]
    )
    with tempfile.TemporaryDirectory(prefix="bench-slo-") as tmp:
        ledger_path = os.path.join(tmp, "slo-ledger.jsonl")
        stack = await build_storm_stack(
            replicas=replicas, time_scale=time_scale,
            ledger_path=ledger_path,
        )
        report = await run_storm(stack, process, drain_s=drain_s)
        stack.close()
        torn = 0
        journaled = 0
        with open(ledger_path) as fh:
            for line in fh:
                if not line.strip():
                    continue
                journaled += 1
                try:
                    json.loads(line)
                except ValueError:
                    torn += 1
    total = report["slo"]["total"]
    classes = {
        cls: {
            "target_s": row.get("target_s"),
            "admitted": row["admitted"],
            "attainment": row["attainment"],
            "p50_s": row["p50_s"],
            "p95_s": row["p95_s"],
            "p99_s": row["p99_s"],
            "goodput_analyses_per_min": row["goodput_analyses_per_min"],
            "goodput_tokens_s": row["goodput_tokens_s"],
        }
        for cls, row in report["slo"]["classes"].items()
    }
    # the headline p50: the 2s-target interactive class when present
    # (that is the class the >=100/min SLO gate judges), else the total
    interactive = report["slo"]["classes"].get("interactive") or {}
    return {
        "rate_per_min": rate_per_min,
        "offered": report["arrivals"],
        "offered_per_min": report["offered_per_min"],
        "achieved_per_min": report["achieved_per_min"],
        "completed": total["completed"],
        "attainment": total["attainment"],
        "degraded": total.get("degraded", 0),
        "shed": total["shed"],
        "deadline_exceeded": total["deadline_exceeded"],
        "failed": total["failed"],
        "overload": report.get("overload"),
        "goodput_tokens_s": total["goodput_tokens_s"],
        "goodput_analyses_per_min": total["goodput_analyses_per_min"],
        "p50_s": (interactive.get("p50_s")
                  if interactive.get("p50_s") is not None else total["p50_s"]),
        "p99_s": (interactive.get("p99_s")
                  if interactive.get("p99_s") is not None else total["p99_s"]),
        "classes": classes,
        "fleet": report["fleet"]["fleet"],
        "seed": seed,
        "fingerprint": report["fingerprint"],
        "replay_identical": replay_identical,
        "ledger_lines": journaled,
        "ledger_torn_lines": torn,
    }


async def run_mixed_scenario(engine, long_prompts, short_prompts,
                             long_sampling, short_sampling) -> dict:
    """Mixed long-prefill + short-decode traffic: short requests are
    decoding when the long prompts arrive, so a phase-separated engine
    stalls them behind the batched prefill while the continuous
    scheduler (serving/sched/) keeps their tokens flowing.  Returns
    latency stats; occupancy/stall numbers are read from the engine's
    own metrics by the caller."""
    await engine.start()
    latencies: list[float] = []

    async def one(prompt: str, sampling) -> None:
        started = time.perf_counter()
        await engine.generate(prompt, sampling)
        latencies.append(time.perf_counter() - started)

    tasks = []
    # shorts first: they must be mid-decode when the long prefills land
    for prompt in short_prompts[: len(short_prompts) // 2]:
        tasks.append(asyncio.ensure_future(one(prompt, short_sampling)))
    await asyncio.sleep(0.05)
    for prompt in long_prompts:
        tasks.append(asyncio.ensure_future(one(prompt, long_sampling)))
    for prompt in short_prompts[len(short_prompts) // 2:]:
        await asyncio.sleep(0.01)
        tasks.append(asyncio.ensure_future(one(prompt, short_sampling)))
    wall_start = time.perf_counter()
    await asyncio.gather(*tasks)
    wall = time.perf_counter() - wall_start
    await engine.close()
    latencies.sort()
    n = len(latencies)
    return {
        "completed": n,
        "wall_s": round(wall, 3),
        "p50_s": round(latencies[n // 2], 3) if n else None,
        "p99_s": round(latencies[min(n - 1, int(n * 0.99))], 3) if n else None,
    }


def bench_mixed(params, config, tokenizer, *, slots: int, max_seq: int,
                page_size: int, decode_block: int) -> dict:
    """Run the mixed-traffic scenario under BOTH serving modes on fresh
    engines (fresh metrics registries, shared weights) and report batch
    occupancy + decode-stall alongside latency — the CPU-measurable face
    of the continuous scheduler's win (no TPU in the loop needed).

    The continuous side runs a ``sched_pipeline_depth`` sweep (the
    decode-ahead story: the host's work hides under the running step at
    depth >= 2, so the step's wall falls to the device's time) plus one speculation run on TEMPLATED greedy prompts
    (the repetitive-text case prompt-lookup drafting exists for); its
    ``spec_decode`` block carries acceptance rate, mean accepted
    tokens/round and the measured host-side draft overhead, and
    ``decode_tokens_per_host_sync`` is the headline — 1.0 is the old
    synchronous one-token loop's ceiling."""
    from operator_tpu.serving.engine import (
        BatchedGenerator, SamplingParams, ServingEngine,
    )
    from operator_tpu.serving.sched import Scheduler
    from operator_tpu.utils.timing import MetricsRegistry

    filler = "the pod was OOMKilled after its memory limit was exceeded "
    long_prompts = [filler * (max_seq // (len(filler) // 4)) for _ in range(2)]
    short_prompts = [f"pod crash {i}: exit code 137" for i in range(6)]
    long_sampling = SamplingParams(max_tokens=8, temperature=0.3,
                                   stop_on_eos=False)
    short_sampling = SamplingParams(max_tokens=24, temperature=0.3,
                                    stop_on_eos=False)

    def run_engine(*, mode, depth=1, spec=False, greedy=False):
        metrics = MetricsRegistry()
        generator = BatchedGenerator(
            params, config, tokenizer, max_slots=slots, max_seq=max_seq,
            paged=True, page_size=page_size, metrics=metrics,
            decode_block=decode_block if mode == "wave" else 1,
        )
        scheduler = None
        if mode == "continuous":
            scheduler = Scheduler(
                generator, chunk=64, pipeline_depth=depth,
                spec_decode=spec, spec_lookup_k=4,
            )
        engine = ServingEngine(
            generator, admission_wait_s=0.002, scheduler=scheduler
        )
        # speculation only drafts for greedy rows (byte-identical
        # acceptance needs argmax); the sweep keeps the sampled traffic
        long_s, short_s = long_sampling, short_sampling
        if greedy:
            long_s = SamplingParams(max_tokens=8, temperature=0.0,
                                    stop_on_eos=False)
            short_s = SamplingParams(max_tokens=24, temperature=0.0,
                                     stop_on_eos=False)
        result = asyncio.run(run_mixed_scenario(
            engine, long_prompts, short_prompts, long_s, short_s
        ))
        return result, generator, scheduler

    out: dict = {}
    result, generator, _ = run_engine(mode="wave")
    occupancy = generator.metrics.stage("batch_occupancy")
    stall = generator.metrics.stage("decode_stall")
    result["batch_occupancy_avg"] = (
        round(occupancy.mean_ms / 100.0, 4) if occupancy.count else None
    )
    result["decode_stall_steps"] = stall.count
    result["decode_stall_ms_total"] = round(stall.mean_ms * stall.count, 1)
    out["wave"] = result
    log(f"mixed[wave]: occupancy={result['batch_occupancy_avg']} "
        f"stall_steps={result['decode_stall_steps']} "
        f"stall_ms={result['decode_stall_ms_total']} "
        f"p50={result['p50_s']}s wall={result['wall_s']}s")

    # decode-ahead sweep: spec off so the depth axis is isolated; the
    # host's share of the step records' wall (step-clock attribution)
    # rides along — what the host needs a step, hidden or not
    out["sched_pipeline_depth_sweep"] = {}
    for depth in (1, 2, 4):
        result, generator, scheduler = run_engine(
            mode="continuous", depth=depth
        )
        stats = scheduler.stats()
        summary = generator.step_clock.summary()
        fractions = summary.get("fractions") or {}
        result["batch_occupancy_avg"] = stats["batch_occupancy_avg"]
        result["decode_stall_steps"] = stats["decode_stall_steps"]
        result["decode_stall_ms_total"] = 0.0
        result["admitted_midwave"] = stats["admitted_midwave"]
        result["chunked_prefills"] = stats["chunked_prefills"]
        result["host_gap_fraction"] = fractions.get("host")
        result["decode_tokens_per_host_sync"] = (
            stats["decode_tokens_per_host_sync"]
        )
        result["dispatch_ahead_steps"] = stats["dispatch_ahead"]
        out["sched_pipeline_depth_sweep"][str(depth)] = result
        if depth == 2:
            out["continuous"] = result  # the shipping default depth
        log(f"mixed[continuous,depth={depth}]: "
            f"occupancy={result['batch_occupancy_avg']} "
            f"host_gap_frac={result['host_gap_fraction']} "
            f"tok/sync={result['decode_tokens_per_host_sync']} "
            f"p50={result['p50_s']}s wall={result['wall_s']}s")

    # prompt-lookup speculation on templated greedy traffic (depth 2 =
    # the serving default, so rest rounds + verify rounds both appear)
    result, generator, scheduler = run_engine(
        mode="continuous", depth=2, spec=True, greedy=True,
    )
    stats = scheduler.stats()
    spec_stats = dict(stats["spec_decode"])
    spec_stats["decode_tokens_per_host_sync"] = (
        stats["decode_tokens_per_host_sync"]
    )
    spec_stats["wall_s"] = result["wall_s"]
    out["spec_decode"] = spec_stats
    log(f"mixed[spec_decode]: acceptance={spec_stats['acceptance_rate']} "
        f"mean_accepted/round={spec_stats['mean_accepted_per_round']} "
        f"draft_overhead_ms={spec_stats['draft_overhead_ms']} "
        f"tok/sync={spec_stats['decode_tokens_per_host_sync']}")
    return out


def bench_kv_economy(params, config, tokenizer, *, slots: int, max_seq: int,
                     page_size: int) -> dict:
    """Measure the KV-economy win (serving/kvstore.py + ops/kv_transfer.py)
    on a fresh continuous engine: TTFT cold (full prefill) vs warm-hit
    (block-hash prefix match) vs restored-from-host (blocks spilled via
    ``Scheduler.spill_cache()``, restored by DMA), the prefill-tokens-saved
    fraction over a templated storm, and resume-vs-restart latency for an
    injected mid-stream kill (token-level streaming resume: the survivor
    re-prefills prompt+generated and decodes only the continuation).

    All lanes run greedy on the same templated prompt set, so the
    byte-identity contract holds and the TTFT deltas are pure KV effects
    (no sampling noise, no recompiles after the first lane warms)."""
    from operator_tpu.ops.kv_transfer import HostKVPool
    from operator_tpu.serving.engine import BatchedGenerator, SamplingParams
    from operator_tpu.serving.kvstore import PrefixKVStore
    from operator_tpu.serving.sched import Scheduler
    from operator_tpu.utils.timing import MetricsRegistry

    metrics = MetricsRegistry()
    generator = BatchedGenerator(
        params, config, tokenizer, max_slots=slots, max_seq=max_seq,
        paged=True, page_size=page_size, metrics=metrics,
    )
    pool_mb = int(os.environ.get("KV_HOST_POOL_MB", "64"))
    store = PrefixKVStore(
        generator.page_size, host_pool=HostKVPool(pool_mb), metrics=metrics,
    )
    sched = Scheduler(generator, kvstore=store)
    template = ("analyse this pod failure: the container was OOMKilled "
                "after exceeding its memory limit; ")
    prompt = template * max(1, (max_seq // 2) // max(1, len(template) // 3))
    one_tok = SamplingParams(max_tokens=1, temperature=0.0, stop_on_eos=False)

    def drain(req_id: int, limit: int = 2000):
        for _ in range(limit):
            for outcome in sched.step():
                if outcome.req_id == req_id:
                    return outcome
        raise RuntimeError("kv bench request never finished")

    def ttft(sampling) -> tuple[float, "object"]:
        started = time.perf_counter()
        outcome = drain(sched.enqueue(prompt, sampling))
        return time.perf_counter() - started, outcome

    # compile the programs OUTSIDE the timed lanes (the cold lane measures
    # prefill work, not XLA) — a throwaway prompt with a distinct head so
    # its blocks never collide with the measured prompt's chain
    drain(sched.enqueue("warmup " + prompt[: len(prompt) // 2], one_tok))

    cold_s, cold = ttft(one_tok)
    warm_s, warm = ttft(one_tok)
    spilled = sched.spill_cache()
    restored_s, restored = ttft(one_tok)
    assert (list(cold.result.token_ids) == list(warm.result.token_ids)
            == list(restored.result.token_ids)), "kv lanes diverged"

    # templated storm: N suffix-varied prompts over the shared template —
    # the saved fraction is the economy headline (prompt tokens the fleet
    # never re-prefills)
    storm_n = int(os.environ.get("BENCH_KV_STORM", "8"))
    saved0 = metrics.counter("kv_prefill_tokens_saved")
    for i in range(storm_n):
        drain(sched.enqueue(prompt + f" incident {i}", one_tok))
    saved = metrics.counter("kv_prefill_tokens_saved") - saved0
    lookups = store.lookups
    storm_prompt_tokens = storm_n * len(tokenizer.encode(prompt))
    saved_frac = round(saved / storm_prompt_tokens, 4) if storm_prompt_tokens else 0.0

    # injected kill: generate the reference stream, then compare resuming
    # from a mid-stream checkpoint against restarting from scratch
    gen_tokens = 16
    reference = drain(sched.enqueue(
        prompt, SamplingParams(max_tokens=gen_tokens, temperature=0.0,
                               stop_on_eos=False),
    ))
    ref_ids = list(reference.result.token_ids)
    kill_at = gen_tokens // 2
    started = time.perf_counter()
    resumed = drain(sched.enqueue(
        prompt,
        SamplingParams(max_tokens=gen_tokens - kill_at, temperature=0.0,
                       stop_on_eos=False),
        resume_tokens=ref_ids[:kill_at],
    ))
    resume_s = time.perf_counter() - started
    started = time.perf_counter()
    restarted = drain(sched.enqueue(
        prompt, SamplingParams(max_tokens=gen_tokens, temperature=0.0,
                               stop_on_eos=False),
    ))
    restart_s = time.perf_counter() - started
    assert ref_ids[:kill_at] + list(resumed.result.token_ids) == ref_ids, \
        "resume lane diverged from the reference stream"
    assert list(restarted.result.token_ids) == ref_ids

    kv = sched.stats()["kv_economy"]
    out = {
        "ttft_cold_s": round(cold_s, 4),
        "ttft_warm_hit_s": round(warm_s, 4),
        "ttft_restored_s": round(restored_s, 4),
        "warm_speedup": round(cold_s / warm_s, 2) if warm_s > 0 else None,
        "restored_speedup": (
            round(cold_s / restored_s, 2) if restored_s > 0 else None
        ),
        "spilled_blocks": spilled,
        "storm_requests": storm_n,
        "prefill_tokens_saved": saved,
        "prefill_saved_frac": saved_frac,
        "prefix_lookups": lookups,
        "hit_rate": kv["hit_rate"],
        "offloads": kv["offloads"],
        "restores": kv["restores"],
        "resume_s": round(resume_s, 4),
        "restart_s": round(restart_s, 4),
        "resume_vs_restart": (
            round(restart_s / resume_s, 2) if resume_s > 0 else None
        ),
    }
    log(f"kv_economy: ttft cold={out['ttft_cold_s']}s "
        f"warm={out['ttft_warm_hit_s']}s (x{out['warm_speedup']}) "
        f"restored={out['ttft_restored_s']}s saved_frac={saved_frac} "
        f"resume={out['resume_s']}s vs restart={out['restart_s']}s")
    return out


def bench_kv_fabric(params, config, tokenizer, *, slots: int, max_seq: int,
                    page_size: int) -> dict:
    """Price the fleet KV fabric (operator_tpu/fabric/, docs/FABRIC.md)
    on CPU smoke:

    - **fetch vs recompute TTFT**: replica A computes a >=8-block prompt
      and mirrors its pages; replica B's cold lane prefills the same
      prompt from scratch, then (cache reset) its warm-peer lane pulls
      A's pages through the real wire format + fetch client and restores
      them by DMA.  The warm-peer time INCLUDES the fetch itself — the
      honest arrival-to-token-one comparison — and both lanes must stay
      greedy byte-identical;
    - **disaggregated vs mixed storm goodput**: the same seeded arrival
      schedule against a 3-mixed fleet and a 1-prefill + 2-decode fleet
      in disaggregated dispatch, goodput-under-SLO each.
    """
    from operator_tpu.fabric import FabricFetcher, FabricIndex, encode_block
    from operator_tpu.loadgen import ArrivalProcess, ArrivalSpec
    from operator_tpu.loadgen.storm import (
        SyntheticReplica, build_storm_stack, run_storm,
    )
    from operator_tpu.ops.kv_transfer import HostKVPool
    from operator_tpu.serving.engine import BatchedGenerator, SamplingParams
    from operator_tpu.serving.kvstore import PrefixKVStore, block_hashes
    from operator_tpu.serving.sched import Scheduler
    from operator_tpu.serving.types import prompt_budget
    from operator_tpu.utils.timing import MetricsRegistry

    # The warm-peer claim is judged on an >=8-block prompt, and the
    # prompt must FIT the truncation budget — or enqueue tail-truncates
    # it and every block hash changes out from under the mirror.  Two
    # traps: the generator clamps max_seq to config.max_seq_len (256
    # for tiny-test), and at the default page_size=64 with max_seq=512
    # the two constraints cannot both hold (8 blocks = 512 tokens > 511
    # budget).  So size the lane's OWN page off the effective budget.
    eff_seq = min(max_seq, config.max_seq_len)
    budget = prompt_budget(eff_seq, 2)
    fabric_page = min(page_size, 32)
    while fabric_page > 8 and 9 * fabric_page > budget:
        fabric_page //= 2

    def make_replica(*, mirror):
        metrics = MetricsRegistry()
        generator = BatchedGenerator(
            params, config, tokenizer, max_slots=slots, max_seq=max_seq,
            paged=True, page_size=fabric_page, metrics=metrics,
        )
        store = PrefixKVStore(
            generator.page_size, host_pool=HostKVPool(64), metrics=metrics,
        )
        return Scheduler(generator, kvstore=store, fabric_mirror=mirror), \
            generator, store

    def drain(sched, req_id, limit=2000):
        for _ in range(limit):
            for outcome in sched.step():
                if outcome.req_id == req_id:
                    return outcome
        raise RuntimeError("kv fabric bench request never finished")

    # two tokens, not one: mirroring piggybacks on the NEXT commit
    # window's host sync (scheduler._drain_mirror), so a 1-token request
    # would finish with its blocks still queued; token two opens exactly
    # one more window.  All three lanes use the same params, so the
    # cold/warm comparison stays equal-footing.
    one_tok = SamplingParams(max_tokens=2, temperature=0.0, stop_on_eos=False)
    template = ("analyse this pod failure: the container was OOMKilled "
                "after exceeding its memory limit; ")
    # grow the prompt in token space, not char space: stop once it spans
    # >8 full blocks, and never cross the truncation budget
    prompt = template
    while (len(tokenizer.encode(prompt)) < 9 * fabric_page
           and len(tokenizer.encode(prompt + template)) <= budget):
        prompt += template
    tokens = tokenizer.encode(prompt)
    hashes = block_hashes(tokens, fabric_page)
    assert len(hashes) >= 8, (
        f"fabric bench prompt spans only {len(hashes)} blocks "
        f"({len(tokens)} tokens at page {fabric_page}, budget {budget}); "
        "the warm-peer claim is judged on >= 8"
    )

    # replica A: the holder — compute + mirror (compile outside the lane)
    sched_a, _gen_a, store_a = make_replica(mirror=True)
    drain(sched_a, sched_a.enqueue("warmup " + prompt[: len(prompt) // 2],
                                   one_tok))
    ref = drain(sched_a, sched_a.enqueue(prompt, one_tok))
    assert all(store_a.host_pool.has(h) for h in hashes), \
        "holder failed to mirror the prompt's blocks"

    index = FabricIndex()
    index.update("bench-a", [h.hex() for h in hashes], url="http://bench-a")

    async def transport(url, budget_s):
        hash_hex = url.rsplit("/", 1)[-1]
        page = store_a.host_pool.get(bytes.fromhex(hash_hex))
        if page is None:
            return 404, b""
        return 200, encode_block(bytes.fromhex(hash_hex), *page)

    # replica B: cold lane (full prefill), then warm-peer lane (fetch +
    # adopt + DMA restore) after a cache reset — same compiled programs
    sched_b, gen_b, store_b = make_replica(mirror=False)
    drain(sched_b, sched_b.enqueue("warmup " + prompt[: len(prompt) // 2],
                                   one_tok))
    started = time.perf_counter()
    cold = drain(sched_b, sched_b.enqueue(prompt, one_tok))
    cold_s = time.perf_counter() - started
    sched_b.reset()

    fetcher = FabricFetcher(
        index, transport=transport, self_id="bench-b",
        metrics=gen_b.metrics,
    )
    started = time.perf_counter()
    adopted = asyncio.run(fetcher.prefetch(tokens, store=store_b))
    warm = drain(sched_b, sched_b.enqueue(prompt, one_tok))
    warm_s = time.perf_counter() - started
    assert adopted == len(hashes), \
        f"adopted {adopted}/{len(hashes)} fetched blocks"
    assert (list(cold.result.token_ids) == list(warm.result.token_ids)
            == list(ref.result.token_ids)), "fabric lanes diverged"

    # disagg vs mixed: one seeded schedule, two fleet shapes
    async def storm_goodput(fleet, disaggregate):
        spec = ArrivalSpec(
            name="fabric-storm",
            rate_per_min=float(os.environ.get(
                "BENCH_FABRIC_RATE_PER_MIN", "240")),
            duration_s=float(os.environ.get(
                "BENCH_FABRIC_DURATION_S", "3")),
        )
        process = ArrivalProcess(spec, seed=11)
        stack = await build_storm_stack(
            replicas=fleet, time_scale=0.2, disaggregate=disaggregate,
        )
        report = await run_storm(stack, process, drain_s=20.0)
        stack.close()
        total = report["slo"]["total"]
        return {
            "goodput_per_min": total["goodput_analyses_per_min"],
            "attainment": total["attainment"],
            "handoffs": stack.metrics.counter("fabric_disagg_handoff"),
        }

    mixed = asyncio.run(storm_goodput(
        [SyntheticReplica(f"fabric-mixed-{i}", concurrency=2,
                          time_scale=0.2) for i in range(3)],
        False,
    ))
    disagg = asyncio.run(storm_goodput(
        [SyntheticReplica("fabric-prefill-0", concurrency=2,
                          time_scale=0.2, role="prefill"),
         SyntheticReplica("fabric-decode-0", concurrency=2,
                          time_scale=0.2, role="decode"),
         SyntheticReplica("fabric-decode-1", concurrency=2,
                          time_scale=0.2, role="decode")],
        True,
    ))

    out = {
        "prompt_blocks": len(hashes),
        "ttft_cold_s": round(cold_s, 4),
        "ttft_warm_peer_s": round(warm_s, 4),
        "warm_peer_speedup": round(cold_s / warm_s, 2) if warm_s > 0 else None,
        "warm_peer_faster": bool(warm_s < cold_s),
        "fetched_ok": gen_b.metrics.counter("fabric_fetch_ok"),
        "adopted": adopted,
        "restores": gen_b.metrics.counter("kv_restore"),
        "byte_identical": True,  # asserted above; a divergence raises
        "storm_mixed": mixed,
        "storm_disagg": disagg,
        "disagg_vs_mixed_goodput": (
            round(disagg["goodput_per_min"] / mixed["goodput_per_min"], 3)
            if mixed["goodput_per_min"] else None
        ),
    }
    log(f"kv_fabric: ttft cold={out['ttft_cold_s']}s "
        f"warm-peer={out['ttft_warm_peer_s']}s "
        f"(x{out['warm_peer_speedup']}, {len(hashes)} blocks) "
        f"goodput mixed={mixed['goodput_per_min']:.0f}/min "
        f"disagg={disagg['goodput_per_min']:.0f}/min")
    return out


def bench_cold_start(params, config, tokenizer, *, slots: int, max_seq: int,
                     page_size: int, decode_block: int) -> dict:
    """Token-one latency from replica-does-not-exist (docs/SCALING.md):
    the serverless wake path the autoscaler creates when the first arrival
    lands on a fleet scaled to zero.  Each lane builds a FRESH
    BatchedGenerator (the pod-boot stand-in — params are assumed resident,
    so the number isolates program bring-up + prefill, not weight load)
    and times prompt -> first token:

    - AOT-cold: empty AOT cache directory, every serving program compiles
      live inside the measured window — the first-ever wake on a
      fingerprint;
    - AOT-warm: a second fresh generator over the now-populated cache —
      the wake the fleet actually pays once the image ships its programs.

    The split is the case for shipping the cache with the image: the
    autoscaler can only scale to zero as aggressively as
    token-one-from-zero is cheap."""
    import tempfile

    from operator_tpu.serving.engine import BatchedGenerator, SamplingParams

    prompt = ("analyse this pod failure: probe timeout after node drain; "
              "the serving fleet was scaled to zero when it arrived")
    one_tok = SamplingParams(max_tokens=1, temperature=0.0, stop_on_eos=False)

    with tempfile.TemporaryDirectory(prefix="bench-coldstart-") as aot_dir:
        def wake() -> tuple:
            started = time.perf_counter()
            generator = BatchedGenerator(
                params, config, tokenizer, max_slots=slots, max_seq=max_seq,
                paged=True, page_size=page_size, decode_block=decode_block,
                aot_cache=aot_dir,
            )
            result = generator.generate(prompt, one_tok)
            return (time.perf_counter() - started, result,
                    generator._aot.stats())

        cold_s, cold_result, cold_stats = wake()
        warm_s, warm_result, warm_stats = wake()
    assert list(cold_result.token_ids) == list(warm_result.token_ids), \
        "cold-start lanes diverged"

    return {
        # the headline: token-one from a fleet that did not exist, with
        # the image's AOT cache warm (the steady-state wake)
        "token_one_s": round(warm_s, 3),
        # first-ever wake on this fingerprint: live XLA compiles inside
        "token_one_cold_s": round(cold_s, 3),
        "aot_warm_speedup": (round(cold_s / warm_s, 2) if warm_s > 0
                             else None),
        "aot_cold": {k: cold_stats[k] for k in ("stored", "live_compiles")},
        "aot_warm": {k: warm_stats[k]
                     for k in ("hits", "live_compiles", "symbol_errors")},
    }


def main() -> None:
    model_name = os.environ.get("BENCH_MODEL", "qwen2.5-1.5b")
    n_requests = int(os.environ.get("BENCH_REQUESTS", "32"))
    slots = int(os.environ.get("BENCH_SLOTS", "16"))
    max_tokens = int(os.environ.get("BENCH_MAX_TOKENS", "96"))
    max_seq = int(os.environ.get("BENCH_MAX_SEQ", "1024"))

    import jax
    import jax.numpy as jnp

    from operator_tpu.models import get_config, init_params
    from operator_tpu.models.tokenizer import load_tokenizer
    from operator_tpu.serving.engine import (
        BatchedGenerator, SamplingParams, ServingEngine,
    )
    from operator_tpu.serving.prompts import build_prompt

    from operator_tpu.utils.platform import (
        enable_persistent_compilation_cache,
        resolve_device,
    )

    # no chip and no backend asked for by name -> NoAccelerator, exit 1
    device = resolve_device()
    log(f"device: {device}")
    log(f"persistent XLA cache: {enable_persistent_compilation_cache()}")
    log(f"model={model_name} requests={n_requests} slots={slots} "
        f"max_tokens={max_tokens} max_seq={max_seq}")

    config = get_config(model_name)
    t0 = time.perf_counter()
    # int8 is the default bench dtype (PR 10, behind the parity gate in
    # tests/test_quant_parity.py); BENCH_QUANT stays as the legacy alias
    quant = os.environ.get(
        "BENCH_INT8", os.environ.get("BENCH_QUANT", "1")
    ) == "1"
    if quant:
        # per-matrix init+quantize: never materialises the float tree, so
        # an 8B int8 bench fits the 16 GB chip (bf16 init alone would OOM)
        from operator_tpu.models.quant import init_params_quantized

        params = jax.block_until_ready(
            init_params_quantized(config, jax.random.PRNGKey(0))
        )
    else:
        # one jitted program, not dozens of eagerly compiled tiny ones
        init = jax.jit(lambda key: init_params(config, key, dtype=jnp.bfloat16))
        params = jax.block_until_ready(init(jax.random.PRNGKey(0)))
    params_init_s = time.perf_counter() - t0
    log(f"params initialised in {params_init_s:.1f}s (int8={quant})")

    paged = os.environ.get("BENCH_PAGED", "1") == "1"
    decode_block = int(os.environ.get("BENCH_DECODE_BLOCK", "8"))
    # real subword tokenizer by default (byte-level token
    # counts inflate prompts ~4x vs production BPE); BENCH_TOKENIZER may name
    # a local HF tokenizer dir, "builtin-bpe", or "byte"
    tok_spec = os.environ.get("BENCH_TOKENIZER", "builtin-bpe")
    tokenizer = load_tokenizer(tok_spec)
    if tokenizer.vocab_size > config.vocab_size:
        log(f"tokenizer vocab {tokenizer.vocab_size} exceeds model vocab "
            f"{config.vocab_size}; falling back to byte tokenizer")
        tok_spec = "byte"
        tokenizer = load_tokenizer(tok_spec)
    log(f"tokenizer: {tok_spec} (vocab {tokenizer.vocab_size})")
    # decode-ahead depth 2: one block stays in flight while the host
    # processes the previous block's tokens — hides the host<->device round
    # trip
    pipeline_depth = int(os.environ.get("BENCH_PIPELINE", "2"))
    # chunked prefill: bound the decode stall per admission wave
    # (BENCH_PREFILL_CHUNK=256 is the interesting open-loop comparison row)
    prefill_chunk = int(os.environ.get("BENCH_PREFILL_CHUNK", "0")) or None
    # persisted AOT executables (serving/aotcache.py): with a cache path
    # set, the bench measures bring-up TWICE — cold (compile + persist)
    # then warm on a fresh generator (deserialize only) — and serves the
    # timed phases on the warm engine, so the record carries the cold→warm
    # trajectory the autoscaling arc needs
    aot_path = os.environ.get("BENCH_AOT_CACHE", "").strip() or None
    page_size = int(os.environ.get("BENCH_PAGE_SIZE", "64"))
    prompts = [build_prompt(r) for r in build_requests(n_requests)]
    sampling = SamplingParams(max_tokens=max_tokens, temperature=0.3, stop_on_eos=False)

    open_enabled = os.environ.get("BENCH_OPEN", "1") == "1"

    # warmup: compile the decode step and every prefill bucket the timed run
    # can hit, so no XLA compile lands in the timed region.  Warm with the
    # TIMED sampling params: max_tokens feeds the truncation budget, and
    # with prefix caching the budget decides the suffix bucket — a
    # max_tokens mismatch would warm the wrong program.  One decode block
    # suffices, then cancel (slots/pages reclaimed).
    def warm_wave(generator, wave: list) -> None:
        warm_slots = generator.admit(wave, [sampling] * len(wave))
        if len(warm_slots) < len(wave):
            # page backpressure shrank the wave: the intended bucket was
            # NOT compiled — surface it instead of reporting a clean warmup
            log(f"warmup wave admitted {len(warm_slots)}/{len(wave)} rows "
                "(KV pool backpressure); its bucket stays cold")
        generator.step()  # compiles the decode block (first wave)
        # cancel-and-drain: chunk-prefilling slots are RESERVED (not yet
        # cancellable), so keep stepping the job and cancelling as slots
        # activate — leaving anything reserved would starve the next
        # admit()'s free-slot budget
        for slot in warm_slots:
            generator.cancel(slot)
        while generator.num_active:
            generator.step()
            for slot in warm_slots:
                generator.cancel(slot)

    def bring_up() -> tuple:
        """Build a generator and warm it; returns (generator,
        prefix_cached, bringup-record) — the timed unit the AOT cache
        exists to shrink."""
        t_start = time.perf_counter()
        generator = BatchedGenerator(
            params, config, tokenizer, max_slots=slots, max_seq=max_seq,
            paged=paged, page_size=page_size,
            decode_block=decode_block, pipeline_depth=pipeline_depth,
            prefill_chunk=prefill_chunk, aot_cache=aot_path,
        )
        # shared-prefix KV caching: bench prompts use the real template, so
        # its static preamble prefills once and every admission forwards
        # only its suffix — the production default (BENCH_PREFIX_CACHE=0
        # disables for A/B attribution of the win)
        prefix_cached = 0
        if paged and os.environ.get("BENCH_PREFIX_CACHE", "1") == "1":
            from operator_tpu.serving.prompts import DEFAULT_TEMPLATE

            prefix_cached = generator.set_shared_prefix(
                DEFAULT_TEMPLATE.split("{", 1)[0]
            )
            log(f"shared prefix cached: {prefix_cached} tokens")
        t_compile = time.perf_counter()
        # closed phase: full waves of `slots`, plus the remainder wave when
        # requests is not a multiple of slots
        warm_sizes = {slots}
        if n_requests % slots:
            warm_sizes.add(n_requests % slots)
        for size in sorted(warm_sizes):
            warm_wave(generator, prompts[:size])
        if open_enabled and os.environ.get("BENCH_GRID", "1") == "1":
            # open-loop phase: Poisson arrivals form waves of ANY size over
            # any prompt subset, so every (n_pad, bucket) combo — and the
            # per-size host glue — must be warm or it compiles inside a
            # measured request's latency (the r2 on-chip p99 tail).  The
            # engine's own grid precompile drives it through the real
            # admission path, restricted to the buckets THIS prompt set can
            # actually produce (chip time is the budget; all wave sizes
            # stay covered).
            grid = generator.precompile_grid(
                "serving", workload_prompts=prompts, workload_params=sampling
            )
            log(f"warmup grid: {grid}")
        now = time.perf_counter()
        aot = getattr(generator, "_aot", None)
        record = {
            "params_init_s": round(params_init_s, 2),
            "compile_s": round(now - t_compile, 2),
            "ready_s": round(now - t_start, 2),
            "aot_cache": aot.stats() if aot is not None else "off",
        }
        return generator, prefix_cached, record

    generator, prefix_cached, bringup = bring_up()
    log(f"bring-up (cold): {bringup}")
    if aot_path:
        # tear down and bring up AGAIN against the now-populated cache:
        # the warm generator (the one that serves the timed phases below)
        # should restore every program instead of compiling
        del generator
        cold = bringup
        generator, prefix_cached, bringup = bring_up()
        bringup["cold"] = cold
        log(f"bring-up (warm): ready={bringup['ready_s']}s "
            f"vs cold {cold['ready_s']}s")

    # from here on, every XLA compile is a mid-run compile: a direct,
    # multi-second p99 contribution the warmup above exists to prevent —
    # counted and reported so the discipline is visible in the record
    from operator_tpu.utils.compilewatch import CompileWatcher

    compile_watch = CompileWatcher()
    compile_watch.mark()
    open_seconds = float(os.environ.get("BENCH_OPEN_SECONDS", "60"))
    # compresses the arrival schedule; 1.0 = real time
    open_time_scale = float(os.environ.get("BENCH_OPEN_TIME_SCALE", "1.0"))
    loadgen_seed = int(os.environ.get("LOADGEN_SEED", "1"))
    rates = [
        float(r) for r in os.environ.get(
            "BENCH_SWEEP", os.environ.get("BENCH_RATE", "100")
        ).split(",")
    ]

    async def run() -> tuple[float, list[float], list[dict]]:
        # generous admission window -> full waves, so only warmed prefill
        # buckets are hit (any stray compile is logged by the engine)
        serving = ServingEngine(generator, admission_wait_s=0.05)
        await serving.start()
        latencies: list[float] = []

        async def one(prompt: str) -> None:
            started = time.perf_counter()
            await serving.generate(prompt, sampling)
            latencies.append(time.perf_counter() - started)

        wall_start = time.perf_counter()
        await asyncio.gather(*(one(p) for p in prompts))
        wall = time.perf_counter() - wall_start

        open_results: list[dict] = []
        if open_enabled:
            from operator_tpu.loadgen.storm import EngineReplica

            for rate in rates:
                log(f"open-loop storm: {rate:.0f} arrivals/min for "
                    f"{open_seconds:.0f}s (time x{open_time_scale})")
                storm_replicas = [
                    EngineReplica("bench-engine", serving,
                                  max_tokens=max_tokens),
                ]
                try:
                    result = await run_open_loop(
                        storm_replicas,
                        rate_per_min=rate, duration_s=open_seconds,
                        seed=loadgen_seed, time_scale=open_time_scale,
                        drain_s=max(30.0, open_seconds),
                    )
                except Exception as exc:
                    # a broken storm lane must FAIL LOUDLY in the record,
                    # never leave a null SLO headline with no reason
                    msg = (f"open-loop storm @{rate:.0f}/min raised "
                           f"{type(exc).__name__}: {exc}")
                    log(f"OPEN-LOOP LANE FAILED: {msg}")
                    open_results.append(
                        {"rate_per_min": rate, "error": msg}
                    )
                    continue
                log(f"open-loop @{rate:.0f}/min: "
                    f"attainment={result['attainment']} "
                    f"p50={result['p50_s']}s shed={result['shed']} "
                    f"deadline_exceeded={result['deadline_exceeded']} "
                    f"goodput={result['goodput_analyses_per_min']:.1f}/min "
                    f"replay_identical={result['replay_identical']}")
                open_results.append(result)
        await serving.close()
        return wall, latencies, open_results

    profile_dir = os.environ.get("BENCH_PROFILE", "").strip()
    if profile_dir:
        log(f"profiling timed region -> {profile_dir}")
        with generator.trace(profile_dir):
            wall, latencies, open_results = asyncio.run(run())
    else:
        wall, latencies, open_results = asyncio.run(run())
    latencies.sort()
    p50 = latencies[len(latencies) // 2]
    p99 = latencies[min(len(latencies) - 1, int(len(latencies) * 0.99))]
    per_min = n_requests / wall * 60.0
    tokens_s = n_requests * max_tokens / wall

    # mixed long-prefill + short-decode scenario, both serving modes on
    # fresh engines: the continuous scheduler's win (higher occupancy,
    # zero decode-stall steps) is measurable here without a TPU
    mixed = None
    if os.environ.get("BENCH_MIXED", "1") == "1":
        log("mixed-traffic scenario (wave vs continuous)")
        mixed = bench_mixed(
            params, config, tokenizer,
            slots=min(slots, 8), max_seq=min(max_seq, 512),
            page_size=page_size,
            decode_block=decode_block,
        )

    # KV economy: prefix-cache TTFT lanes + offload/restore + streaming
    # resume on a fresh continuous engine (CPU-measurable, like mixed)
    kv_economy = None
    if os.environ.get("BENCH_KV", "1") == "1":
        log("kv-economy scenario (prefix cache / offload / resume)")
        kv_economy = bench_kv_economy(
            params, config, tokenizer,
            slots=min(slots, 8), max_seq=min(max_seq, 512),
            page_size=page_size,
        )

    # fleet KV fabric: peer fetch vs recompute TTFT + disaggregated vs
    # mixed storm goodput (docs/FABRIC.md), CPU-measurable like kv/mixed
    kv_fabric = None
    if os.environ.get("BENCH_KV_FABRIC", "1") == "1":
        log("kv-fabric scenario (peer fetch vs recompute / disagg vs mixed)")
        kv_fabric = bench_kv_fabric(
            params, config, tokenizer,
            slots=min(slots, 8), max_seq=min(max_seq, 512),
            page_size=page_size,
        )

    # cold-start: token-one from replica-does-not-exist — the serverless
    # wake the autoscaler's scale-to-zero bets on (docs/SCALING.md)
    cold_start = None
    if os.environ.get("BENCH_COLD_START", "1") == "1":
        log("cold-start scenario (token-one from zero, AOT cold vs warm)")
        cold_start = bench_cold_start(
            params, config, tokenizer,
            slots=min(slots, 4), max_seq=min(max_seq, 512),
            page_size=page_size, decode_block=decode_block,
        )
        log(f"cold-start: token_one={cold_start['token_one_s']}s "
            f"(aot-cold {cold_start['token_one_cold_s']}s, "
            f"x{cold_start['aot_warm_speedup']})")

    # wave-engine occupancy/stall over the MAIN timed phases (the mixed
    # scenario above reports per-mode numbers on fresh engines)
    from operator_tpu.utils.timing import METRICS as _METRICS

    occupancy_stage = _METRICS.stage("batch_occupancy")
    stall_stage = _METRICS.stage("decode_stall")

    # decode MFU: ~2 FLOPs per weight per generated token (matmul-dominated,
    # attention FLOPs negligible at these sequence lengths) against THIS
    # device's peak for the dtype its matmuls run in (serving/perf.py's
    # table, keyed by device_kind); a device without a row has no MFU
    from operator_tpu.models.llama import param_count
    from operator_tpu.serving.perf import peak_tflops as peak_for

    n_params = param_count(params)
    peak_tflops = peak_for(device.kind, "int8" if quant else "bf16")
    mfu = (
        None if peak_tflops is None
        else round(tokens_s * 2.0 * n_params / (peak_tflops * 1e12), 4)
    )

    log(f"wall={wall:.2f}s  p50={p50:.2f}s  p99={p99:.2f}s  "
        f"decode~{tokens_s:.0f} tok/s  throughput={per_min:.1f} expl/min")
    # SLO verdict from the OPEN-loop phase (the honest p50 under sustained
    # arrivals); closed-batch p50 is a queueing artifact kept for continuity.
    # A null verdict must carry its gating reason (open_loop_gate below)
    slo = None
    slo_gate_reason = None
    judged = [
        r for r in sorted(open_results, key=lambda r: r["rate_per_min"])
        if r["rate_per_min"] >= 100
    ]
    for result in judged:
        if "error" not in result and result.get("p50_s") is not None:
            slo = bool(result["p50_s"] < 2.0)
            break  # the lowest swept rate >= 100/min, regardless of input order
    if slo is None:
        if not open_enabled:
            slo_gate_reason = "BENCH_OPEN=0: storm lane disabled by env"
        elif not judged:
            slo_gate_reason = (
                f"no swept rate >= 100/min to judge "
                f"(BENCH_SWEEP/BENCH_RATE gave {rates})"
            )
        elif "error" in judged[0]:
            slo_gate_reason = judged[0]["error"]
        else:
            slo_gate_reason = (
                "zero completed analyses at >= 100/min "
                "(p50 null in every judged storm)"
            )
        log(f"open-loop SLO headline is null: {slo_gate_reason}")
    # every per-rate record carries its own judging verdict, so a reader
    # of ONE record knows whether (and why not) it fed the SLO headline
    for result in open_results:
        if "error" in result:
            result["gate"] = {"judged": False, "reason": result["error"]}
        elif result["rate_per_min"] < 100:
            result["gate"] = {
                "judged": False,
                "reason": "rate below the 100/min SLO judging floor",
            }
        elif result.get("p50_s") is None:
            result["gate"] = {
                "judged": False,
                "reason": "zero completed analyses (p50 null)",
            }
        else:
            result["gate"] = {"judged": True, "reason": None}
    # a lane that was ENABLED but produced neither records nor a gate
    # reason is silently dead — refuse to publish it at all
    if open_enabled and not open_results and slo_gate_reason is None:
        raise SystemExit(
            "bench: open-loop lane enabled but open_loop is empty with a "
            "null open_loop_gate.reason — a silently-dead storm lane; "
            "fix the lane or disable it explicitly with BENCH_OPEN=0"
        )
    print(json.dumps({
        "metric": "explanations_per_min",
        "value": round(per_min, 1),
        "unit": "explanations/min",
        "vs_baseline": round(per_min / 100.0, 3),
        "p50_latency_s": round(p50, 3),
        "p99_latency_s": round(p99, 3),
        "open_loop": open_results,
        "open_loop_p50_under_2s_at_100pm": slo,
        # why the headline above is null, when it is (never silently null)
        "open_loop_gate": {"ran": slo is not None, "reason": slo_gate_reason},
        "decode_tokens_per_s": round(tokens_s, 1),
        # end-to-end MFU incl. host/queueing time — a decode-only step MFU
        # would be higher; this is the honest number for the whole pipeline
        "decode_mfu": mfu,
        # live decode rows / max_slots per step, and time decode rows
        # spent stalled behind phase-separated prefill dispatches —
        # the two numbers the continuous scheduler moves (docs/SERVING.md)
        "batch_occupancy_avg": (
            round(occupancy_stage.mean_ms / 100.0, 4)
            if occupancy_stage.count else None
        ),
        "decode_stall_ms_total": round(
            stall_stage.mean_ms * stall_stage.count, 1
        ),
        "mixed": mixed,
        "kv_economy": kv_economy,
        "kv_fabric": kv_fabric,
        # token-one-from-zero, AOT-warm vs AOT-cold split — the number
        # SCALE_TO_ZERO_IDLE_S trades against (docs/SCALING.md)
        "cold_start": cold_start,
        # step-clock attribution (serving/perf.py): the MEASURED decode
        # MFU decomposed per step — host-gap / device / sample-xfer
        # fractions sum to 1.0 by construction; decode_mfu here counts
        # only decode-bearing steps' attributed wall, so it upper-bounds
        # the end-to-end number above and the GAP between them is the
        # pipeline overhead the fractions attribute
        "step_attribution": generator.step_clock.summary(),
        "params_b": round(n_params / 1e9, 3),
        "peak_tflops": peak_tflops,
        "model": model_name,
        "requests": n_requests,
        "max_tokens": max_tokens,
        "decode_block": decode_block,
        "pipeline_depth": pipeline_depth,
        "tokenizer": tok_spec,
        "weight_dtype": "int8" if quant else "bf16",
        # structured bring-up record (cold→warm trajectory when
        # BENCH_AOT_CACHE is set; "off" aot_cache otherwise)
        "bringup": bringup,
        "prefix_cached_tokens": prefix_cached,
        "midrun_compiles": compile_watch.count_since_mark(),
        # the device as JAX reports it: platform, device_kind, count
        "device": device.to_dict(),
    }))


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # never leave the driver with an unparseable traceback
        import traceback

        traceback.print_exc(file=sys.stderr)
        print(json.dumps({
            "metric": "explanations_per_min",
            "value": 0.0,
            "unit": "explanations/min",
            "vs_baseline": 0.0,
            "error": f"{type(e).__name__}: {e}",
        }))
        sys.exit(1)
