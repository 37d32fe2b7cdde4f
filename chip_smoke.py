#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py              # on a machine with a TPU
    python3 chip_smoke.py --dry-run-cpu  # debug the command here, on the CPU

Drives the default serving path once, through the entry points a user
would call, at the full published width AND depth of the default model
(``OperatorConfig.model_id``) with seeded random weights and every other
setting at its default: continuous scheduler, int8 weights, 32 slots,
page 64, chunk 64, decode-ahead depth 2, prompt-lookup speculation,
prefix cache.  Each leg is ONE child process that holds the chip,
started only after the previous one has exited:

1. *kernels* — this file again (``--leg kernels``): prints the device and
   the jax / jaxlib / libtpu versions, then checks every Pallas kernel
   against its dense reference on the chip, at the default model's
   geometry: the ragged paged-attention kernel (the default path's only
   attention), the similarity kernel, and the wave engine's paged-decode
   and flash-prefill kernels.
2. *server* — ``python -m operator_tpu.serving``: one plain completion,
   one streamed, a storm of concurrent completions built by
   ``serving/prompts.build_prompt`` over ``tests/fixtures/*.log``, and one
   ``POST /api/v1/analysis/analyze``; then ``/healthz`` must name the
   device, count steps, and list the process's compiles.
3. *pipeline* — ``python -m operator_tpu.operator --demo --provider
   tpu-native``: one CrashLoopBackOff pod through collect -> parse ->
   recall -> explain on the in-process engine.  Its mixed-program compile
   should be a persistent-cache hit from leg 2 (utils/platform.py).
4. *mesh* — only where JAX finds four or more devices: the server again
   as the shipped manifests run it (``SERVING_MESH=dp=1,tp=4
   SCHED_MODE=wave``, ``qwen2.5-7b``), same request set, and every device
   of the mesh must hold its shard of the parameters and KV pages.

THE PARENT NEVER IMPORTS JAX.  A chip belongs to one process at a time: a
parent that has touched JAX would hold it, and every child would fail or
hang.  The parent only builds prompts, speaks HTTP and reads JSON.

Exit code 0 and a last stdout line ``{"ok": true, "device": {...}}`` (the
device as JAX reports it inside the children) only if every leg and every
request succeeded on a TPU.  No accelerator -> non-zero, no result line.
``--dry-run-cpu`` is explicit and never automatic: the tiny test model,
kernels in interpret mode, the result marked ``"dry_run": true`` with
``"platform": "cpu"`` — it proves the command, not the system.

What the legs report are BRING-UP FACTS (seconds to first answer with
compilation included, which programs compiled and whether the cache
served them, device memory), not benchmark metrics.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
#: logs and report.json land here (the dry run's in chip_smoke_dry_run/,
#: so that debugging the command never overwrites a chip run's record)
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
#: the whole run must end inside the driver's 1200 s, compilation included
BUDGET_S = 1150.0
#: the storm: the default engine's slot count, at the answer length the
#: analysis path asks for most (BASELINE config 4: 32 events, one wave)
STORM_REQUESTS, STORM_MAX_TOKENS = 32, 96
#: the multi-chip leg (only where JAX finds >= 4 devices): what
#: deploy/serving-deployment.yaml ships, on the model that needs it
MESH_SPEC, MESH_MODEL = "dp=1,tp=4", "qwen2.5-7b"


class SmokeFailure(Exception):
    """A leg did not do what the contract says; the message is the report."""


def check(condition: bool, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


# ---------------------------------------------------------------------------
# leg 1 (child, holds the chip): device + kernel parity
# ---------------------------------------------------------------------------


def kernels_leg(interpret: bool) -> dict:
    """Runs INSIDE the child: the only function here that imports jax."""
    import importlib.metadata

    import jax
    import jax.numpy as jnp
    import numpy as np

    from operator_tpu.models import get_config
    from operator_tpu.ops.ragged_attention import (
        _ragged_attention_pallas,
        ragged_attention_reference,
    )
    from operator_tpu.ops.similarity import (
        _best_window_pallas,
        best_window_scores_reference,
    )
    from operator_tpu.serving.perf import peak_tflops
    from operator_tpu.utils.config import OperatorConfig
    from operator_tpu.utils.platform import (
        enable_persistent_compilation_cache,
        resolve_device,
    )

    device = resolve_device()  # raises unless TPU (or the dry run's cpu)
    enable_persistent_compilation_cache()
    report: dict = {
        "device": device.to_dict(),
        "versions": {
            name: importlib.metadata.version(name)
            for name in ("jax", "jaxlib", "libtpu")
        },
        "peak_bf16_tflops": peak_tflops(device.kind, "bf16"),
    }
    if not interpret:
        # the smoke's MFU-bearing consumers need a peak for this chip: an
        # unknown device_kind is an error here, never another chip's number
        check(
            report["peak_bf16_tflops"] is not None,
            f"device_kind {device.kind!r} has no row in serving/perf.py "
            "_PEAK_TFLOPS",
        )

    page, pages_per_seq = 64, 8  # 512-token rows: several pages per walk
    layers = 3  # a stacked pool, every layer's pages different

    def ragged_case(name, heads, kv_heads, head_dim, chunk, kv_len, q_count,
                    layer, window=None, attend_block=1):
        """One kernel-vs-reference comparison over rows of mixed phases,
        at one layer of the stacked pool."""
        rows = len(kv_len)
        keys = jax.random.split(jax.random.PRNGKey(len(name)), 3)
        pool = (layers, rows * pages_per_seq + 1, page, kv_heads, head_dim)
        layer = jnp.int32(layer)
        q = jax.random.normal(keys[0], (rows, chunk, heads, head_dim), jnp.bfloat16)
        k_pages = jax.random.normal(keys[1], pool, jnp.bfloat16)
        v_pages = jax.random.normal(keys[2], pool, jnp.bfloat16)
        # page 0 is the allocator's trash page; rows own disjoint pages
        table = 1 + jnp.arange(rows * pages_per_seq, dtype=jnp.int32).reshape(
            rows, pages_per_seq
        )
        kv = jnp.asarray(kv_len, jnp.int32)
        count = jnp.asarray(q_count, jnp.int32)
        got = _ragged_attention_pallas(
            q, k_pages, v_pages, table, kv, count, layer,
            interpret=interpret, sliding_window=window, attend_block=attend_block,
        )
        with jax.default_matmul_precision("highest"):
            want = ragged_attention_reference(
                q.astype(jnp.float32), k_pages.astype(jnp.float32),
                v_pages.astype(jnp.float32), table, kv, count, layer,
                sliding_window=window, attend_block=attend_block,
            )
        # rows past q_count (and whole inactive rows) are garbage by
        # contract on both sides: compare the live query rows only
        live = np.arange(chunk)[None, :] < np.asarray(q_count)[:, None]
        return close(name, np.asarray(got, np.float32)[live], np.asarray(want)[live])

    def close(name, got, want):
        """Tolerance |got - want| <= 1e-2 + 1e-2 |want|, for bf16 data
        against a float32 reference.  bf16 keeps 8 mantissa bits (relative
        step 2^-8 = 4e-3): the kernels accumulate in f32, but the MXU
        multiplies the probabilities in bf16 and the output is rounded to
        bf16 once more, so a value carries up to ~2 * 2^-8 of its own
        magnitude plus 2^-8 of the mean |v| it averaged.  A kernel that
        skipped a page, mis-masked one position or read the wrong row is
        off by O(0.1 - 1) on these unit-variance inputs."""
        check(np.isfinite(got).all(), f"{name}: non-finite output")
        excess = np.abs(got - want) - (1e-2 + 1e-2 * np.abs(want))
        err = float(np.max(np.abs(got - want)))
        check(float(excess.max()) <= 0.0,
              f"{name}: max |kernel - reference| = {err:.4f} is out of tolerance")
        return {"max_abs_err": round(err, 5), "values": int(got.size)}

    config = get_config(OperatorConfig().model_id)
    geometry = (config.num_heads, config.num_kv_heads, config.head_dim)
    if interpret:
        geometry = (4, 2, 128)  # the interpreter is slow; same code path
    mistral = get_config("mistral-7b")
    width = 1 + OperatorConfig().spec_lookup_k
    report["ragged"] = {
        # one wave of every phase the scheduler packs together: decode rows,
        # a whole-prompt prefill, a mid-prompt chunk, rows on either side
        # of the small query tile's edge (8 and 17 queries), and inactive
        # slots between and after the live ones (q_count 0 runs nothing
        # and moves no block)
        "mixed_rows_c64": ragged_case(
            "mixed", *geometry, chunk=64,
            kv_len=[1, 200, 0, 64, 448, 333, 300, 0],
            q_count=[1, 1, 0, 64, 64, 17, 8, 0], layer=1,
        ),
        f"verify_rows_c{width}": ragged_case(
            "verify", *geometry, chunk=width,
            kv_len=[130, 5, 512, 0], q_count=[width, width, 3, 0],
            layer=layers - 1,
        ),
        # one query head a KV head (ouro-2.6b: 16 x 128): sixteen
        # [tile, 1, D] slabs a page, both rungs of the query tile
        "mha_rows_c64": ragged_case(
            "mha", *((16, 16, 128) if not interpret else (4, 4, 128)), chunk=64,
            kv_len=[1, 200, 0, 64, 448, 333, 300, 0],
            q_count=[1, 1, 0, 64, 64, 17, 8, 0], layer=2,
        ),
        # eight query heads a KV head under the block-causal mask
        # (sdar-30b-a3b: 32 / 4 x 128, blocks of 4): rows of a block, of a
        # block led by the one before it, and a prompt chunk
        "block_rows_c64": ragged_case(
            "block", *((32, 4, 128) if not interpret else (8, 2, 128)), chunk=64,
            kv_len=[4, 200, 0, 64, 448, 336, 300, 0],
            q_count=[4, 8, 0, 64, 64, 8, 4, 0], layer=1, attend_block=4,
        ),
        # Mistral's geometry with a window that bites inside 512 tokens
        # (its published 4096 never does below the serving cap)
        "sliding_window": ragged_case(
            "window", mistral.num_heads if not interpret else 4,
            mistral.num_kv_heads if not interpret else 2, mistral.head_dim,
            chunk=64, kv_len=[500, 130, 64, 0], q_count=[1, 64, 64, 0],
            layer=0, window=100,
        ),
    }

    # the wave engine's kernels (SCHED_MODE=wave, and every SERVING_MESH):
    # paged decode v1 (its default) and v2, and flash prefill
    from operator_tpu.ops.flash_prefill import (
        _flash_prefill_pallas,
        flash_prefill_reference,
    )
    from operator_tpu.ops.paged_attention import (
        _paged_attention_pallas,
        _paged_attention_pallas_v2,
        paged_attention_reference,
    )

    heads, kv_heads, head_dim = geometry
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    rows = 4
    pool = (rows * pages_per_seq + 1, page, kv_heads, head_dim)
    q = jax.random.normal(keys[0], (rows, heads, head_dim), jnp.bfloat16)
    k_pages = jax.random.normal(keys[1], pool, jnp.bfloat16)
    v_pages = jax.random.normal(keys[2], pool, jnp.bfloat16)
    table = 1 + jnp.arange(rows * pages_per_seq, dtype=jnp.int32).reshape(rows, -1)
    lengths = jnp.asarray([5, 77, 512, 333], jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(paged_attention_reference(
            q.astype(jnp.float32), k_pages.astype(jnp.float32),
            v_pages.astype(jnp.float32), table, lengths,
        ))
    report["paged_decode"] = {
        name: close(name, np.asarray(
            fn(q, k_pages, v_pages, table, lengths, interpret=interpret),
            np.float32), want)
        for name, fn in (("v1", _paged_attention_pallas),
                         ("v2", _paged_attention_pallas_v2))
    }
    t = 256
    fq = jax.random.normal(keys[3], (2, t, heads, head_dim), jnp.bfloat16)
    fk = jax.random.normal(keys[4], (2, t, kv_heads, head_dim), jnp.bfloat16)
    fv = jax.random.normal(keys[5], (2, t, kv_heads, head_dim), jnp.bfloat16)
    flens = jnp.asarray([t, 131], jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(flash_prefill_reference(
            fq.astype(jnp.float32), fk.astype(jnp.float32),
            fv.astype(jnp.float32), flens,
        ))
    valid = np.arange(t)[None, :] < np.asarray(flens)[:, None]
    got = np.asarray(
        _flash_prefill_pallas(fq, fk, fv, flens, interpret=interpret), np.float32
    )
    report["flash_prefill"] = close("flash_prefill", got[valid], want[valid])

    # the state-space scan (a model with recurrent state, ops/ssm_scan.py)
    # at the Falcon-H1 geometry: idle slots between live ones, decode rows,
    # a full chunk, a part chunk, fresh and carried states; everything is
    # float32 inside, so kernel and token-by-token reference differ by the
    # order of a sum alone
    from operator_tpu.ops.ssm_scan import _ssm_scan_pallas, ssm_scan_reference

    falcon = get_config("falcon-h1-34b-6l")
    s_heads, s_dim, s_state, s_groups = (
        (falcon.mamba_n_heads, falcon.mamba_d_head, falcon.mamba_d_state,
         falcon.mamba_n_groups) if not interpret else (4, 16, 16, 2)
    )
    counts = np.asarray([1, 0, 64, 1, 0, 0, 5, 1], np.int32)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int32)
    tokens = 128
    sk = jax.random.split(jax.random.PRNGKey(13), 6)
    sx = jax.random.normal(sk[0], (tokens, s_heads, s_dim), jnp.bfloat16)
    sdt = jax.nn.softplus(jax.random.normal(sk[1], (tokens, s_heads)) - 3.0)
    sa = -jnp.exp(jax.random.uniform(sk[2], (s_heads,), minval=0.0, maxval=2.7))
    sb = jax.random.normal(sk[3], (tokens, s_groups, s_state), jnp.bfloat16)
    sc = jax.random.normal(sk[4], (tokens, s_groups, s_state), jnp.bfloat16)
    state = jax.random.normal(sk[5], (2, len(counts), s_heads, s_state, s_dim))
    fresh = jnp.asarray([0, 1, 1, 0, 0, 1, 0, 1], bool)
    scan_args = (sx, sdt, sa, sb, sc, state, jnp.int32(1), jnp.asarray(starts),
                 jnp.asarray(counts), fresh)
    want_y, want_state = jax.jit(ssm_scan_reference, static_argnames=("chunk",))(
        *scan_args, chunk=64
    )
    got_y, got_state = _ssm_scan_pallas(
        *scan_args, interpret=interpret,
        heads_per_block=2 if interpret else 0,
    )
    live = np.zeros(tokens, bool)
    for start, count in zip(starts, counts):
        live[start:start + count] = True
    idle = counts == 0
    check(
        bool(np.array_equal(np.asarray(got_state)[:, idle], np.asarray(state)[:, idle]))
        and bool(np.array_equal(np.asarray(got_state)[0], np.asarray(state)[0])),
        "ssm_scan: an idle slot's state, or another layer's, was touched",
    )
    report["ssm_scan"] = {
        "y": close("ssm_scan y", np.asarray(got_y)[live], np.asarray(want_y)[live]),
        "state": close("ssm_scan state", np.asarray(got_state), np.asarray(want_state)),
    }

    # the grouped expert product (a model with sparse experts,
    # ops/moe_experts.py) against a plain product over every expert: int8
    # stacks of two layers, a quarter of the tokens routed nowhere, one
    # expert that no token chose
    from operator_tpu.models.quant import quantize_matrix
    from operator_tpu.ops.moe_experts import _moe_experts_pallas, moe_experts_reference

    m_tokens, m_experts, m_top, m_hidden, m_inner = (
        (64, 8, 2, 128, 128) if interpret else (256, 32, 8, 2048, 768)
    )
    keys = jax.random.split(jax.random.PRNGKey(31), 5)

    def expert_stack(key, rows, cols):
        drawn = jax.random.normal(key, (2, m_experts, rows, cols), jnp.float32) * rows ** -0.5
        return quantize_matrix(drawn.astype(jnp.bfloat16))

    stacks = (
        expert_stack(keys[0], m_hidden, m_inner), expert_stack(keys[1], m_hidden, m_inner),
        expert_stack(keys[2], m_inner, m_hidden),
    )
    x = jax.random.normal(keys[3], (m_tokens, m_hidden), jnp.bfloat16)
    gates, chosen = jax.lax.top_k(
        jax.nn.softmax(jax.random.normal(keys[4], (m_tokens, m_experts))), m_top
    )
    chosen = jnp.where(chosen == 3, 4, chosen).astype(jnp.int32)  # expert 3 idles
    chosen = jnp.where((jnp.arange(m_tokens) % 4 == 3)[:, None], m_experts, chosen)
    got = _moe_experts_pallas(x, chosen, gates, *stacks, jnp.int32(1), interpret=interpret)
    want = moe_experts_reference(x, chosen, gates, *stacks, jnp.int32(1))
    check(
        float(jnp.abs(got[3::4]).max()) == 0.0,
        "moe_experts: a token routed nowhere was given an expert's output",
    )
    report["moe_experts"] = close("moe_experts", np.asarray(got), np.asarray(want))

    # similarity: the semantic matcher's shape (1000 windows x 300 patterns)
    # and incident recall's (one query row x a handful of incidents)
    def unit(key, shape):
        x = jax.random.normal(key, shape, jnp.float32)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    report["similarity"] = {}
    for name, n_windows, n_patterns in (("matcher", 1000, 300), ("recall", 1, 3)):
        windows = unit(jax.random.PRNGKey(11), (n_windows, 384))
        patterns = unit(jax.random.PRNGKey(12), (n_patterns, 384))
        scores, idx = _best_window_pallas(windows, patterns, interpret=interpret)
        with jax.default_matmul_precision("highest"):
            want, _ = best_window_scores_reference(windows, patterns)
            dense = np.asarray(windows @ patterns.T)
        scores, idx, want = np.asarray(scores), np.asarray(idx), np.asarray(want)
        err = float(np.max(np.abs(scores - want)))
        # Tolerance 1e-2 absolute on a cosine of unit vectors.  Mosaic runs
        # the f32 dot at the MXU's default precision (bf16 passes): one
        # bf16 rounding of each operand bounds the error by 2 * 2^-8 *
        # sum|w_i p_i| <= 8e-3.  The winning INDEX may differ between two
        # near-tied windows, so the index is checked by the score it earns.
        check(err < 1e-2, f"similarity {name}: max |d score| = {err:.5f}")
        earned = dense[idx, np.arange(n_patterns)]
        check(
            float(np.max(np.abs(earned - want))) < 1e-2,
            f"similarity {name}: argmax window does not earn the best score",
        )
        report["similarity"][name] = {"max_abs_err": round(err, 6)}
    return report


# ---------------------------------------------------------------------------
# parent side: prompts, HTTP, children
# ---------------------------------------------------------------------------


def storm_requests(n: int) -> list:
    """n AnalysisRequests over the recorded failure logs (no jax)."""
    from operator_tpu.patterns.engine import PatternEngine
    from operator_tpu.schema.analysis import AnalysisRequest, PodFailureData

    fixture_dir = os.path.join(REPO, "tests", "fixtures")
    logs = []
    for name in sorted(os.listdir(fixture_dir)):
        if name.endswith(".log"):
            with open(os.path.join(fixture_dir, name), encoding="utf-8") as f:
                logs.append(f.read())
    check(bool(logs), f"no .log fixtures under {fixture_dir}")
    engine = PatternEngine()
    requests = []
    for i in range(n):
        failure = PodFailureData(logs=logs[i % len(logs)])
        requests.append(AnalysisRequest(
            analysis_result=engine.analyze(failure), failure_data=failure,
        ))
    return requests


def http_json(url: str, payload=None, timeout: float = 300.0):
    """-> (status, parsed JSON or raw text)."""
    data = None if payload is None else json.dumps(payload).encode()
    request = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(request, timeout=timeout) as response:
            status, body = response.status, response.read().decode()
    except urllib.error.HTTPError as exc:
        status, body = exc.code, exc.read().decode()
    try:
        return status, json.loads(body)
    except ValueError:
        return status, body


def stop_process(proc: subprocess.Popen, grace_s: float = 30.0) -> None:
    """SIGINT (the server's clean shutdown: drain, close the engine, let
    go of the chip), wait, SIGKILL — the whole process group, so nothing
    this script started outlives it."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGINT)
            proc.wait(timeout=grace_s)
        except (subprocess.TimeoutExpired, ProcessLookupError):
            pass
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait(timeout=10)


def child_env(dry_run: bool, model: str, **extra: str) -> dict:
    """The children's environment: the model and random weights, NOTHING
    else — every other setting stays at OperatorConfig's default."""
    env = dict(os.environ)
    # only the dry run may name a non-TPU backend, whatever the caller's
    # environment says
    env.pop("OPERATOR_TPU_PLATFORM", None)
    env.update(OPERATOR_TPU_MODEL=model, ALLOW_RANDOM_WEIGHTS="true", **extra)
    if dry_run:
        env["OPERATOR_TPU_PLATFORM"] = "cpu"  # asked for by name
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def serving_compiles(events: list) -> list:
    from operator_tpu.serving.aotcache import SERVING_PROGRAM_MARKERS

    return [
        e for e in events
        if any(marker in e["name"] for marker in SERVING_PROGRAM_MARKERS)
    ]


def server_leg(dry_run: bool, model: str, deadline: float, out_dir: str,
               mesh: str = "") -> dict:
    """One server process through the whole request set.  ``mesh`` (a
    SERVING_MESH spec) turns it into the multi-chip leg: the shipped
    manifests' configuration, which is the WAVE engine — the continuous
    scheduler has no sharded program yet (build_serving_engine says so)."""
    from operator_tpu.schema.analysis import AIProviderConfig
    from operator_tpu.serving.prompts import build_prompt

    n_storm, storm_tokens = (8, 16) if dry_run else (STORM_REQUESTS, STORM_MAX_TOKENS)
    requests = storm_requests(n_storm)
    prompts = [build_prompt(r) for r in requests]

    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
    base = f"http://127.0.0.1:{port}"
    log_path = os.path.join(out_dir, "mesh_server.log" if mesh else "server.log")
    report: dict = {"log": os.path.relpath(log_path, REPO)}
    extra = {"SERVING_MESH": mesh, "SCHED_MODE": "wave"} if mesh else {}
    started = time.monotonic()
    with open(log_path, "wb") as log_file:
        proc = subprocess.Popen(
            [sys.executable, "-m", "operator_tpu.serving",
             "--host", "127.0.0.1", "--port", str(port)],
            cwd=REPO, env=child_env(dry_run, model, **extra),
            stdout=log_file, stderr=subprocess.STDOUT, start_new_session=True,
        )
    try:
        # weights are drawn on the device before the listener opens
        while True:
            check(proc.poll() is None,
                  f"server exited with code {proc.returncode} before "
                  f"listening; see {report['log']}")
            check(time.monotonic() < deadline, "server never opened /healthz")
            try:
                status, health = http_json(f"{base}/healthz", timeout=5)
                if status == 200:
                    break
            except OSError:
                pass
            time.sleep(0.5)
        report["listening_s"] = round(time.monotonic() - started, 1)
        report["device"] = health["load"]["device"]

        def completion(prompt, max_tokens):
            status, body = http_json(f"{base}/v1/completions", {
                "prompt": prompt, "max_tokens": max_tokens, "temperature": 0.3,
            }, timeout=max(5.0, deadline - time.monotonic()))
            check(status == 200, f"/v1/completions -> {status}: {str(body)[:300]}")
            tokens = body["usage"]["completion_tokens"]
            check(tokens > 0, f"completion_tokens = {tokens}")
            return tokens

        # 1. the first request compiles the one mixed program (the wave
        # engine: its first prefill bucket and its decode block)
        t0 = time.monotonic()
        completion(prompts[0], 16)
        report["first_request_s"] = round(time.monotonic() - t0, 1)
        _, health = http_json(f"{base}/healthz")
        compiles_before = health["compiles"]["count"]
        report["serving_program_compiles"] = serving_compiles(
            health["compiles"]["events"]
        )
        first_program = "_decode_block" if mesh else "mixed_fn"
        check(
            any(first_program in e["name"]
                for e in report["serving_program_compiles"]),
            f"no {first_program} compile was recorded (utils/compilewatch.py "
            "no longer matches jax's compile log?)",
        )

        # 2. one streamed completion must end in [DONE]
        request = urllib.request.Request(
            f"{base}/v1/completions",
            data=json.dumps({"prompt": prompts[1], "max_tokens": 16,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(request, timeout=300) as response:
            check(response.status == 200, f"stream -> {response.status}")
            frames = [
                line[len("data: "):] for line in
                response.read().decode().splitlines() if line.startswith("data: ")
            ]
        check(len(frames) >= 2 and frames[-1] == "[DONE]",
              f"stream did not end in [DONE]: {frames[-2:]}")
        report["stream_frames"] = len(frames)

        # 3. the storm: every slot's worth of prompts at once
        t0 = time.monotonic()
        with concurrent.futures.ThreadPoolExecutor(n_storm) as pool:
            tokens = list(pool.map(lambda p: completion(p, storm_tokens), prompts))
        report["storm"] = {
            "requests": n_storm, "succeeded": len(tokens),
            "completion_tokens": sum(tokens),
            "wall_s": round(time.monotonic() - t0, 1),
        }

        # 4. the reference's ai-interface route, served by the same engine
        requests[2].provider_config = AIProviderConfig(
            provider_id="tpu-native", model_id=model, max_tokens=storm_tokens,
        )
        status, body = http_json(
            f"{base}/api/v1/analysis/analyze", requests[2].to_dict()
        )
        check(status == 200, f"analyze -> {status}: {str(body)[:300]}")
        check(not body.get("error") and (body.get("completionTokens") or 0) > 0,
              f"analyze answered {str(body)[:300]}")
        report["analyze_completion_tokens"] = body["completionTokens"]

        _, health = http_json(f"{base}/healthz")
        check(health["load"]["device"] == report["device"],
              "device changed mid-run")
        check(health["load"]["steps"] > 0, "/healthz reports steps = 0")
        report["steps"] = health["load"]["steps"]
        report["step_attribution"] = {
            k: health["load"].get(k)
            for k in ("decodeMfu", "hostGapFrac", "occupancy", "prefixHitRate")
        }
        report["device_memory"] = health["deviceMemory"]
        late = health["compiles"]["events"][
            len(health["compiles"]["events"])
            - (health["compiles"]["count"] - compiles_before):
        ]
        report["compiles_after_first_request"] = {
            "count": health["compiles"]["count"] - compiles_before,
            "seconds": round(sum(e["seconds"] or 0.0 for e in late), 2),
            "names": sorted({e["name"] for e in late}),
        }
        if mesh:
            # the wave engine compiles one prefill program per (rows,
            # tokens) bucket as traffic first hits it: reported, not refused
            report["compiles_after_first_request"]["serving_programs"] = [
                {k: e[k] for k in ("name", "seconds")}
                for e in serving_compiles(late)
            ]
            # parameter and KV-page shards on every device of the mesh: an
            # engine that quietly sat on device 0 would leave the rest empty
            in_use = [d["bytes_in_use"] for d in health["deviceMemory"]]
            check(len(in_use) >= 4 and (dry_run or min(in_use) > 0.5 * max(in_use)),
                  f"the mesh does not hold shards on every device: {in_use}")
        else:
            check(
                not serving_compiles(late),
                f"a serving program compiled after the first request: "
                f"{serving_compiles(late)}",
            )
    finally:
        stop_process(proc)
    with open(log_path, encoding="utf-8", errors="replace") as f:
        log_text = f.read()
    mode = "serving mode: WAVE" if mesh else "serving mode: CONTINUOUS"
    check(mode in log_text, f"the server did not log {mode!r}")
    check(not mesh or "sharded serving: mesh" in log_text,
          "the server did not log its mesh")
    check("falling back" not in log_text.lower(),
          "the server log mentions 'falling back'")
    return report


def seed_incident_journal(path: str) -> str:
    """A one-incident memory journal (MEMORY_PATH) from a DIFFERENT failure
    than the demo's, so the demo's recall stage has something to score:
    an empty index returns before the similarity kernel is ever called."""
    from operator_tpu.memory import build_incident_memory, failure_fingerprint
    from operator_tpu.schema.analysis import AIResponse
    from operator_tpu.utils.config import OperatorConfig

    prior = storm_requests(2)[1]  # fixture #2; the demo's pod crashes with fixture #1
    memory = build_incident_memory(OperatorConfig(memory_path=path))
    fingerprint = failure_fingerprint(prior.analysis_result, None)
    check(not fingerprint.is_weak, "seed incident has a weak fingerprint")
    memory.insert(
        fingerprint, prior.analysis_result, None,
        AIResponse(explanation="Root Cause: seeded prior incident.",
                   provider_id="seed", model_id="seed"),
    )
    memory.close()
    return fingerprint.digest


def pipeline_leg(dry_run: bool, model: str, deadline: float, out_dir: str) -> dict:
    journal = os.path.join(out_dir, "incidents.jsonl")
    if os.path.exists(journal):
        os.remove(journal)
    seed_digest = seed_incident_journal(journal)
    log_path = os.path.join(out_dir, "pipeline.log")
    report: dict = {"log": os.path.relpath(log_path, REPO)}
    started = time.monotonic()
    with open(log_path, "wb") as log_file:
        proc = subprocess.Popen(
            [sys.executable, "-m", "operator_tpu.operator", "--demo",
             "--provider", "tpu-native"],
            cwd=REPO, env=child_env(dry_run, model, MEMORY_PATH=journal),
            stdout=subprocess.PIPE, stderr=log_file, start_new_session=True,
        )
    try:
        stdout, _ = proc.communicate(timeout=max(5.0, deadline - time.monotonic()))
    except subprocess.TimeoutExpired:
        raise SmokeFailure("the demo did not finish in time") from None
    finally:
        stop_process(proc)
    report["wall_s"] = round(time.monotonic() - started, 1)
    try:
        summary = json.loads(stdout)
    except ValueError:
        raise SmokeFailure(
            f"demo exited {proc.returncode} without a JSON summary; see "
            f"{report['log']}"
        ) from None
    check(proc.returncode == 0, f"demo exited {proc.returncode}; see {report['log']}")
    spans = {span["name"]: span for span in summary["trace"]}
    for stage in ("collect", "parse", "recall", "explain", "ai_generate",
                  "engine.generate", "store"):
        check(stage in spans, f"stage {stage!r} never ran")
        check(spans[stage]["status"] == "ok",
              f"stage {stage!r}: {spans[stage].get('error')}")
    [stored] = summary["podmortem_status"]["recentFailures"]
    check(stored["analysisStatus"] == "Analyzed",
          f"analysisStatus = {stored['analysisStatus']!r}")
    check(not any(e["reason"] == "PodmortemAnalysisError" for e in summary["events"]),
          "a PodmortemAnalysisError event was emitted")
    generate = spans["engine.generate"]["attributes"]
    check(spans["ai_generate"]["attributes"]["provider"] == "tpu-native"
          and generate["completion_tokens"] > 0,
          f"the explanation did not come from tpu-native: {generate}")
    report["completion_tokens"] = generate["completion_tokens"]
    report["recall"] = spans["recall"]["attributes"]["kind"]
    # recall scored against the seeded incident only if the demo really
    # opened the journal: it must now hold the seed AND the demo's failure
    with open(journal, encoding="utf-8") as f:
        journaled = f.read()
    digest = stored["recurrence"]["fingerprint"]
    check(seed_digest in journaled and digest in journaled
          and digest != seed_digest,
          "the demo did not recall against the seeded incident journal")
    engine = summary["engine"]
    report["device"] = engine["load"]["device"]
    events = engine["compiles"]["events"]
    # incident recall scored its query with the Pallas kernel iff the jitted
    # kernel entry point compiled in this process (the reference path is
    # never jitted under that name)
    recall_kernel = [e for e in events if "_best_window_pallas" in e["name"]]
    if not dry_run:
        check(bool(recall_kernel),
              "incident recall never compiled _best_window_pallas: the "
              "similarity kernel did not run")
    report["recall_kernel_compiles"] = recall_kernel
    report["serving_program_compiles"] = serving_compiles(events)
    report["native_scanner"] = summary["native_scanner"]
    return report


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--dry-run-cpu", action="store_true",
                        help="debug the command on the CPU: tiny model, "
                             "kernels interpreted, result marked dry_run")
    parser.add_argument("--leg", choices=["kernels"], help=argparse.SUPPRESS)
    args = parser.parse_args()
    if not os.path.isdir(os.path.join(REPO, "operator_tpu")):
        print("chip_smoke.py must sit at the root of the repository it "
              "drives", file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)

    if args.leg == "kernels":  # the child side of leg 1
        try:
            print(json.dumps(kernels_leg(interpret=args.dry_run_cpu)))
        except SmokeFailure as exc:
            print(f"FAILED kernels: {exc}", file=sys.stderr)
            return 1
        return 0

    from operator_tpu.utils.config import OperatorConfig

    dry_run = args.dry_run_cpu
    out_dir = OUT_DIR + ("_dry_run" if dry_run else "")
    model = "tiny-test" if dry_run else OperatorConfig().model_id
    deadline = time.monotonic() + BUDGET_S
    os.makedirs(out_dir, exist_ok=True)
    report: dict = {"dry_run": dry_run, "model": model, "legs": {}}
    failure = None
    try:
        # leg 1: device + kernels (fails first, and fast, without a chip)
        argv = [sys.executable, os.path.abspath(__file__), "--leg", "kernels"]
        done = subprocess.run(
            argv + (["--dry-run-cpu"] if dry_run else []),
            cwd=REPO, env=child_env(dry_run, model), capture_output=True,
            text=True, timeout=BUDGET_S / 2,
        )
        sys.stderr.write(done.stderr[-4000:])
        check(done.returncode == 0, f"kernels leg exited {done.returncode}")
        report["legs"]["kernels"] = json.loads(done.stdout.strip().splitlines()[-1])
        device = report["legs"]["kernels"]["device"]
        print(f"device: {json.dumps(device)} "
              f"versions: {json.dumps(report['legs']['kernels']['versions'])}",
              flush=True)
        report["legs"]["server"] = server_leg(dry_run, model, deadline, out_dir)
        print(f"server leg: {json.dumps(report['legs']['server'])}", flush=True)
        report["legs"]["pipeline"] = pipeline_leg(dry_run, model, deadline, out_dir)
        print(f"pipeline leg: {json.dumps(report['legs']['pipeline'])}", flush=True)
        if device["count"] >= 4:
            # a multi-chip host: the shipped manifests' tp=4 configuration
            # (deploy/*.yaml) on the 7B-class model it exists for
            report["legs"]["mesh"] = server_leg(
                dry_run, "tiny-test" if dry_run else MESH_MODEL, deadline,
                out_dir, mesh="dp=2,tp=2" if dry_run else MESH_SPEC,
            )
            print(f"mesh leg: {json.dumps(report['legs']['mesh'])}", flush=True)
        for leg in ("server", "pipeline"):
            check(report["legs"][leg]["device"] == device,
                  f"{leg} leg ran on {report['legs'][leg]['device']}, "
                  f"kernels leg on {device}")
        check(device["platform"] == ("cpu" if dry_run else "tpu"),
              f"ran on {device['platform']!r}")
        mixed = [e for e in report["legs"]["pipeline"]["serving_program_compiles"]
                 if "mixed_fn" in e["name"]]
        report["second_process_cache_hit"] = bool(mixed) and all(
            e["cache_hit"] for e in mixed
        )
        # (a sub-second CPU compile is below jax's threshold for caching)
        check(dry_run or report["second_process_cache_hit"],
              f"the pipeline leg recompiled the mixed program the server "
              f"leg had compiled: {mixed}")
    except (SmokeFailure, subprocess.TimeoutExpired) as exc:
        failure = str(exc)
    assert "jax" not in sys.modules, "the parent imported jax"
    report["ok"] = failure is None
    report["wall_s"] = round(BUDGET_S - (deadline - time.monotonic()), 1)
    with open(os.path.join(out_dir, "report.json"), "w", encoding="utf-8") as f:
        json.dump(report, f, indent=2)
    if failure is not None:
        print(f"FAILED: {failure}", file=sys.stderr)
        return 1
    print(json.dumps(report))
    result = {"ok": True, "device": device}
    if dry_run:
        result["dry_run"] = True
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
