#!/usr/bin/env python3
"""Drive a Qwen configuration's engine with rows at temperature 0, which
draft, so that steps take the WIDE branch of the mixed step's tail on the
chip (no cell's traffic does: every cell sends ``temperature > 0``).

    python3 benchmark/tools/wide_probe.py <out.json>
    PROBE_CONFIG=benchmark/configs/<name>.json python3 benchmark/tools/wide_probe.py <out.json>

Phase A: 32 greedy rows whose prompts repeat themselves (prompt-lookup
drafts), 96 tokens each: the served ids, and the step records split by
whether the packed step carried a draft (spied on ``Scheduler._pack``, so
it runs on a program without ``StepRecord.sampled_rows`` too).  Phase B:
the same 32 prompts at temperature 0.3, which never draft.  Prints one
JSON object (the ids go to the file alone).  A probe of 32 requests, not a
cell run, and never read by the driver: ``qwen2.5-1.5b-int8.greedy``
(ROADMAP Queue 2) is the cell that would measure this.
"""

import asyncio
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.getcwd())  # run from the root of a checkout


def main() -> None:
    out = sys.argv[1]
    from benchmark.entries import engine as entry
    from benchmark.harness import cell
    from benchmark.harness.manifest import load_json
    from operator_tpu.serving.sched import scheduler as sched_mod

    cell.configure_jax()
    doc = load_json(os.environ.get("PROBE_CONFIG", "benchmark/configs/qwen2.5-1.5b-int8.json"))
    handle = entry.build(doc)

    wide_flags = []
    real_pack = sched_mod.Scheduler._pack

    def spy(self, *a, **k):
        packed = real_pack(self, *a, **k)
        wide_flags.append(bool(packed.spec_len.any()))
        return packed

    sched_mod.Scheduler._pack = spy

    line = ("pod web-{i} crashed: container app terminated exit code 137 "
            "reason=OOMKilled; pod web-{i} crashed: container app terminated "
            "exit code 137 reason=OOMKilled; pod web-{i} crashed: container app "
            "terminated exit code 137 reason=OOMKilled; explain why pod web-{i} ")
    prompts = [line.format(i=i) for i in range(32)]

    async def phase(sampling, max_tokens):
        first = handle.steps_recorded()
        flags0 = len(wide_flags)
        t0 = time.perf_counter()
        results = await asyncio.gather(*(
            handle.generate(p, max_tokens, sampling) for p in prompts
        ))
        seconds = time.perf_counter() - t0
        end = handle.steps_recorded()
        records = handle.step_records(first, end)
        flags = wide_flags[flags0:flags0 + len(records)]
        return results, records, flags, seconds

    def split(records, flags):
        rows = {}
        for name, want in (("wide", True), ("narrow", False)):
            chosen = [r for r, f in zip(records, flags) if f is want]
            # decode-only steps, so that the two kinds compare like with like
            decode = [r for r in chosen if not (getattr(r, "prefill_tokens", 0) or 0)]
            rows[name] = {
                "steps": len(chosen),
                "decode_steps": len(decode),
                "decode_wall_ms_mean": statistics.fmean(r.wall_ms for r in decode) if decode else None,
                "decode_wall_ms_median": statistics.median(r.wall_ms for r in decode) if decode else None,
                "decode_tokens_mean": statistics.fmean(r.tokens for r in decode) if decode else None,
                "sampled_rows": sorted({getattr(r, "sampled_rows", None) for r in chosen}, key=str),
            }
        return rows

    async def run():
        greedy = {"temperature": 0.0, "top_p": 1.0, "stop_on_eos": False}
        warm = {"temperature": 0.3, "top_p": 0.95, "stop_on_eos": False}
        # warm both samplings and the staging shapes
        await asyncio.gather(*(handle.generate(p, 4, greedy) for p in prompts))
        await asyncio.gather(*(handle.generate(p, 4, warm) for p in prompts))
        handle.mark_compiles()
        a_results, a_records, a_flags, a_s = await phase(greedy, 96)
        b_results, b_records, b_flags, b_s = await phase(warm, 96)
        sched = handle.engine._sched
        doc_out = {
            "greedy_ids": [list(map(int, r.token_ids)) for r in a_results],
            "greedy": split(a_records, a_flags),
            "greedy_seconds": a_s,
            "warm": split(b_records, b_flags),
            "warm_seconds": b_s,
            "spec": sched.stats().get("spec_decode"),
            "wide_counter": sched.metrics.counter("sample_wide_steps"),
            "compiles_after_warm": len(handle.compiles_since_mark()),
        }
        await handle.close()
        return doc_out

    doc_out = asyncio.run(run())
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(doc_out, f)
    short = {k: v for k, v in doc_out.items() if k != "greedy_ids"}
    print(json.dumps(short))


if __name__ == "__main__":
    main()
