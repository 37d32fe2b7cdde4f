"""The ``tpu-native`` AI provider: in-tree TPU inference, zero external calls.

This is the leg of the reference the rebuild replaces outright — the
operator no longer POSTs to an ai-interface pod that fronts a GPU/OpenAI
backend (reference AIInterfaceRestClient.java:37-39); ``providerId:
tpu-native`` routes straight into the local serving engine (BASELINE north
star: "0 external AI calls").

Configuration comes from the same AIProvider CR fields the reference
honours (promptTemplate / maxTokens / temperature,
aiprovider-crd.yaml:36-62): the prompt builder applies the template, and
each request carries its own SamplingParams into the shared batch
(per-slot sampling, serving/engine.py).

Model selection: ``modelId`` in the CR (must name a registered config);
weights from ``OperatorConfig.checkpoint_dir`` (HF safetensors). Without a
checkpoint the factory REFUSES to build (:class:`MissingCheckpoint`) so the
pipeline degrades to the pattern-only/template path — the reference emits a
degradation event rather than storing garbage (PodFailureWatcher.java:385-420),
and random-weight text is garbage.  Benches/tests that genuinely want a
random-init engine set ``allow_random_weights`` (they construct prompts
whose THROUGHPUT is weight-independent, so the measurement is honest).
"""

from __future__ import annotations

import asyncio
import logging
import os
from typing import Optional

from ..obs import annotate_root, current_trace_id
from ..schema.analysis import AIResponse, AnalysisRequest
from ..utils.config import OperatorConfig
from .engine import (
    BatchedGenerator,
    DeadlineExceeded,
    SamplingParams,
    ServingEngine,
    SupervisorPolicy,
)
from .prompts import build_prompt

log = logging.getLogger(__name__)


class MissingCheckpoint(RuntimeError):
    """tpu-native is configured but no model weights are mounted."""


def _parse_mesh_plan(spec: str, devices: list, model_config):
    """'auto' or 'dp=2,tp=4[,fsdp=1]' -> MeshPlan."""
    from ..parallel.mesh import MeshPlan, device_memory_bytes, plan_for

    if spec == "auto":
        return plan_for(
            len(devices), config=model_config,
            hbm_bytes=device_memory_bytes(devices[0]),
        )
    sizes = {"dp": 1, "fsdp": 1, "tp": 1}
    for part in spec.split(","):
        axis, _, value = part.strip().partition("=")
        if axis not in sizes or not value.isdigit():
            raise ValueError(
                f"bad serving_mesh {spec!r}: expected 'auto' or 'dp=N,tp=N[,fsdp=N]'"
            )
        sizes[axis] = int(value)
    plan = MeshPlan(**sizes)
    if plan.total > len(devices):
        raise ValueError(
            f"serving_mesh {spec!r} needs {plan.total} devices, found {len(devices)}"
        )
    return plan


class TPUNativeProvider:
    """AIProviderBackend serving explanations from the in-process engine."""

    def __init__(
        self,
        engine: ServingEngine,
        *,
        model_id: str,
        register_template_prefixes: bool = True,
    ) -> None:
        self.engine = engine
        self.model_id = model_id
        #: gate for lazy promptTemplate prefix registration — follows the
        #: operator's PREFIX_CACHE config (a disabled cache must not grow
        #: a registry through the side door)
        self.register_template_prefixes = register_template_prefixes
        # custom promptTemplate preambles already registered (or refused)
        # as shared prefixes — one attempt per distinct template
        self._registered_templates: set[str] = set()

    async def _ensure_template_prefix(self, template: Optional[str]) -> None:
        """Register a custom template's static preamble as a shared KV
        prefix, once: later waves of this CR's requests then prefill only
        their variable remainder (the default template was registered at
        engine build, serving/provider.py build_serving_engine)."""
        if not self.register_template_prefixes:
            return
        if not template or template in self._registered_templates:
            return
        self._registered_templates.add(template)
        from .prompts import template_preamble

        preamble = template_preamble(template)
        if not preamble:
            # build_prompt will fall back to DEFAULT_TEMPLATE for this
            # broken template; registering its preamble would hold pages
            # and a registry slot for a prefix no prompt ever starts with
            log.warning("promptTemplate does not render; prefix not cached")
            return
        try:
            cached = await self.engine.add_prefix(preamble)
            if cached:
                log.info("custom template preamble cached: %d tokens", cached)
        except Exception:  # noqa: BLE001 - an optimisation must never fail a request
            log.warning("custom template prefix registration failed",
                        exc_info=True)

    async def generate(self, request: AnalysisRequest) -> AIResponse:
        config = request.provider_config
        await self._ensure_template_prefix(
            config.prompt_template if config else None
        )
        prompt = build_prompt(request)
        # per-CR LoRA adapter (multi-LoRA serving): AIProvider
        # spec.additionalConfig.lora_adapter names a registered adapter;
        # different CRs then share one batch with different adapters
        extra = (config.additional_config or {}) if config else {}
        adapter = extra.get("lora_adapter") or None
        # per-CR constrained decoding: additionalConfig may carry a
        # guided_regex pattern or a guided_json schema (JSON text, lowered
        # onto the same regex automaton) — reference parity: the CR's
        # additionalConfig flows verbatim to the AI backend
        # (AIInterfaceClient.java:71-105); here it reaches the sampler.
        # A bad pattern/schema is a CONFIG error: fail this provider's
        # generation (pipeline stores the pattern-only result) rather than
        # silently dropping the constraint the CR asked for.
        guided_regex = extra.get("guided_regex") or None
        guided_schema = extra.get("guided_json") or None
        if guided_regex is not None and (
            not isinstance(guided_regex, str) or len(guided_regex) > 1024
        ):
            # same bound the HTTP entry point enforces: DFA compilation
            # runs synchronously at submit time, so an unbounded pattern
            # from one misconfigured CR could stall the serving thread
            return AIResponse(
                error="additionalConfig.guided_regex must be a string of "
                      "<=1024 chars",
                provider_id="tpu-native", model_id=self.model_id,
            )
        if guided_schema is not None:
            if guided_regex is not None:
                return AIResponse(
                    error="additionalConfig guided_json and guided_regex are "
                          "mutually exclusive",
                    provider_id="tpu-native", model_id=self.model_id,
                )
            from .json_schema import lower_guided_json

            try:
                guided_regex = lower_guided_json(guided_schema)
            except ValueError as exc:
                return AIResponse(
                    error=f"additionalConfig.guided_json: {exc}",
                    provider_id="tpu-native", model_id=self.model_id,
                )
        # deadline budget: the pipeline's residual envelope becomes an
        # absolute admission deadline — the engine clamps max_tokens to the
        # roofline fit or rejects outright (serving/admission.py)
        abs_deadline = None
        if request.deadline_s is not None:
            abs_deadline = (
                self.engine.generator._clock() + max(0.0, request.deadline_s)
            )
        params = SamplingParams(
            max_tokens=(config.max_tokens if config and config.max_tokens else 500),
            temperature=(
                config.temperature if config and config.temperature is not None else 0.3
            ),
            adapter=adapter,
            guided_regex=guided_regex,
            deadline=abs_deadline,
            # the analysis trace rides into the engine's profiler
            # annotations (podmortem.prefill/decode TraceMe tags), so an
            # xplane capture joins the flight-recorder timeline
            trace_tag=current_trace_id(),
        )
        try:
            # priority 10: pod-failure explanations admit ahead of external
            # completion-API callers sharing the engine (engine.generate)
            result = await self.engine.generate(prompt, params, priority=10)
        except asyncio.CancelledError:
            raise
        except DeadlineExceeded as exc:
            # no chip time was spent: admission refused the residue
            return AIResponse(
                error=f"deadline exceeded before generation: {exc}",
                provider_id="tpu-native", model_id=self.model_id,
                deadline_outcome="deadline-exceeded",
            )
        except Exception as exc:  # noqa: BLE001 - pipeline degrades to pattern-only
            log.exception("tpu-native generation failed")
            # a dead serve loop / device error is exactly the moment the
            # per-request timeline matters: flag the ambient trace for a
            # black-box dump (operator/pipeline.py reads the root attr)
            annotate_root("blackbox", "engine-error", overwrite=False)
            return AIResponse(error=str(exc), provider_id="tpu-native", model_id=self.model_id)
        outcome = None
        if abs_deadline is not None:
            outcome = (
                "truncated" if result.finish_reason == "deadline" else "completed"
            )
        return AIResponse(
            explanation=result.text,
            provider_id="tpu-native",
            model_id=self.model_id,
            prompt_tokens=result.prompt_tokens,
            completion_tokens=result.completion_tokens,
            deadline_outcome=outcome,
        )


def build_serving_engine(
    config: Optional[OperatorConfig] = None,
) -> "tuple[ServingEngine, str]":
    """Build the shared batching engine from operator config.

    Loads weights (checkpoint if configured, random init otherwise when
    ``allow_random_weights``), applies the serving mesh, and wraps the
    generator in a ``ServingEngine``.  Shared by the in-process
    ``tpu-native`` provider and the OpenAI-compatible HTTP server
    (serving/httpserver.py).  Returns ``(engine, model_id)``.
    """
    import jax
    import jax.numpy as jnp

    from ..models import get_config, init_params
    from ..models.loader import load_params_async
    from ..models.tokenizer import load_tokenizer
    from ..utils.compilewatch import CompileWatcher
    from ..utils.platform import (
        enable_persistent_compilation_cache,
        resolve_device,
    )

    # the ONE place the serving stack opens its backend: a non-TPU device
    # nobody asked for by name raises here (utils/platform.py)
    device = resolve_device()
    log.info(
        "serving device: platform=%s device_kind=%s count=%d",
        device.platform, device.kind, device.count,
    )
    log.info(
        "persistent XLA compilation cache: %s",
        enable_persistent_compilation_cache(),
    )
    # every compile from here on is attributed (GET /healthz "compiles")
    compile_watch = CompileWatcher()

    config = config or OperatorConfig.from_env()
    model_id = os.environ.get("OPERATOR_TPU_MODEL", config.model_id)
    model_config = get_config(model_id)

    checkpoint_dir = config.checkpoint_dir
    if checkpoint_dir:
        tokenizer = load_tokenizer(checkpoint_dir)
    else:
        # no checkpoint, so no tokenizer of its own: the committed
        # log-trained BPE (models/bpe_vocab, vocab 4096) wherever the
        # model's vocabulary can hold its ids — every served config; the
        # tiny test config (vocab 512) takes bytes
        tokenizer = load_tokenizer("builtin-bpe")
        if tokenizer.vocab_size > model_config.vocab_size:
            tokenizer = load_tokenizer("byte")
    log.info(
        "tokenizer: %s (vocab %d)", type(tokenizer).__name__, tokenizer.vocab_size
    )
    # legacy WEIGHT_DTYPE (when set) wins over the serving_dtype default —
    # int8 since PR 10, behind the tests/test_quant_parity.py gate
    serving_dtype = (config.weight_dtype or config.serving_dtype or "bf16").lower()
    quantize = serving_dtype == "int8"
    if quantize:
        log.info("int8 weight-only serving (per-output-channel)")
    elif serving_dtype not in ("bf16", "bfloat16"):
        raise ValueError(f"unknown serving dtype {serving_dtype!r}")

    # AOT executable cache: fingerprint from the SAME knobs the generator
    # construction below uses, built BEFORE the weight load finishes —
    # executable deserialization needs disk + host only, so it overlaps
    # the HBM weight transfer (the whole point of the warm-start path)
    mesh = None
    if config.serving_mesh:
        from ..parallel.mesh import make_mesh, mesh_summary

        devices = jax.devices()
        plan = _parse_mesh_plan(config.serving_mesh, devices, model_config)
        mesh = make_mesh(plan, devices)
        log.info("sharded serving: %s", mesh_summary(mesh))

    # multi-LoRA registry: every `<name>.safetensors` under lora_dir becomes
    # a selectable adapter; a bad file disables ONLY that adapter.  Loaded
    # before the AOT cache so the adapter names fold into its fingerprint
    # (the stacked-adapter axis changes every serving program's shape)
    lora_adapters = None
    if config.lora_dir and os.path.isdir(config.lora_dir):
        from ..parallel.lora import load_lora

        lora_adapters = {}
        for fname in sorted(os.listdir(config.lora_dir)):
            if not fname.endswith(".safetensors"):
                continue
            name = fname[: -len(".safetensors")]
            try:
                lora_adapters[name] = load_lora(os.path.join(config.lora_dir, fname))
            except Exception:  # noqa: BLE001 - optional per-adapter surface
                log.warning("LoRA adapter %s unusable; skipping", fname, exc_info=True)
        # one compiled program serves the whole set, so every adapter must
        # share targets and FULL factor shapes (stack_adapters); drop
        # empty/mismatched/name-colliding ones instead of letting the stack
        # (or API routing) break
        signature = None
        for name in sorted(lora_adapters):
            adapter = lora_adapters[name]
            sig = tuple(
                (target, adapter[target]["a"].shape, adapter[target]["b"].shape)
                for target in sorted(adapter)
            )
            if not sig:
                log.warning("LoRA adapter %r is empty; skipping", name)
                del lora_adapters[name]
            elif name == model_id:
                log.warning(
                    "LoRA adapter %r collides with the base model id and "
                    "would be unroutable over the API; skipping", name,
                )
                del lora_adapters[name]
            elif signature is None:
                signature = sig
            elif sig != signature:
                log.warning(
                    "LoRA adapter %r has targets/shapes %s != %s of the first "
                    "adapter; skipping (adapters must match to share one "
                    "compiled program)", name, sig, signature,
                )
                del lora_adapters[name]
        log.info("multi-LoRA serving: %s", sorted(lora_adapters) or "none loaded")
        lora_adapters = lora_adapters or None
    elif config.lora_dir:
        log.warning(
            "lora_dir %r does not exist or is not a directory; "
            "multi-LoRA serving disabled", config.lora_dir,
        )

    # continuous-batching scheduler (serving/sched/, docs/SERVING.md): the
    # default.  A configuration it cannot serve is an ERROR naming the
    # reason — never a warning and a different engine: which engine a
    # deployment runs must be readable from its configuration.  Checked
    # before any weight is loaded.
    if config.sched_mode not in ("continuous", "wave"):
        raise ValueError(
            f"unknown sched_mode {config.sched_mode!r}: expected "
            "'wave' or 'continuous'"
        )
    if model_config.continuous_only and (
        config.sched_mode != "continuous" or mesh is not None or lora_adapters
    ):
        # a recurrent state per slot lives only in the continuous path's
        # cache (ops/paged_attention.PagedKVCache.ssm_state), and only its
        # step loops over a looped model's passes: the wave engine's
        # programs, a sharded pool and the LoRA path know nothing of
        # either, and serving without them would be wrong, not slow
        asked = ", ".join(
            text for on, text in (
                (config.sched_mode != "continuous", f"sched_mode={config.sched_mode!r}"),
                (mesh is not None, f"serving_mesh={config.serving_mesh!r}"),
                (bool(lora_adapters), "lora_dir adapters"),
            ) if on
        )
        raise ValueError(
            f"model {model_id!r} ({model_config.family} family) "
            f"{model_config.continuous_only}, which only the unsharded "
            f"continuous scheduler serves (sched_mode=continuous, no "
            f"serving_mesh, no LoRA); this configuration asks for {asked}"
        )
    if config.sched_mode == "continuous":
        blockers = [
            reason for blocked, reason in (
                (config.kv_cache_mode != "paged",
                 f"kv_cache_mode={config.kv_cache_mode!r} (needs paged KV)"),
                (mesh is not None,
                 f"serving_mesh={config.serving_mesh!r} (the mixed program "
                 "has no sharded path)"),
                (bool(lora_adapters),
                 "lora_dir adapters (the mixed program has no LoRA path)"),
            ) if blocked
        ]
        if blockers:
            raise ValueError(
                "sched_mode=continuous cannot serve this configuration: "
                + "; ".join(blockers)
                + ". Set SCHED_MODE=wave to run the wave engine, which can."
            )
        # a model with a latent cache is served by the latent attention
        # kernel (ops/latent_attention.py), never the ragged one
        if device.platform == "tpu" and getattr(model_config, "latent_dims", None) is None:
            from ..ops.ragged_attention import require_ragged_kernel_support

            require_ragged_kernel_support(model_config)

    prefill_chunk = config.prefill_chunk or None
    max_slots = config.max_batch_size
    max_seq = min(model_config.max_seq_len, 2048)
    aot = None
    if config.aot_cache_path:
        from .aotcache import AotCache, generator_fingerprint

        # what the scheduler will really run: it switches speculation and
        # the prefix store off for a model with recurrent state and for one
        # that denoises blocks (sched/scheduler.py)
        plain_rows_only = bool(
            model_config.recurrent_state or getattr(model_config, "block_length", 0)
        )
        try:
            aot = AotCache(config.aot_cache_path, generator_fingerprint(
                config=model_config,
                weight_dtype="int8" if quantize else "bfloat16",
                max_slots=max_slots,
                max_seq=max_seq,
                paged=config.kv_cache_mode == "paged",
                page_size=config.kv_page_size,
                kv_pages=config.kv_pages or None,
                mesh=mesh,
                decode_block=config.decode_block,
                sample_top_k=config.sample_top_k,
                pipeline_depth=config.pipeline_depth,
                prefill_chunk=prefill_chunk,
                sched_pipeline_depth=config.sched_pipeline_depth,
                spec_width=1 + (
                    config.spec_lookup_k
                    if config.spec_decode and not plain_rows_only
                    else 0
                ),
                kv_prefix_cache=config.kv_prefix_cache and not plain_rows_only,
                lora_names=sorted(lora_adapters) if lora_adapters else (),
            ))
        except Exception:  # noqa: BLE001 - cache is an optimisation only
            log.warning("AOT executable cache disabled", exc_info=True)

    if checkpoint_dir and os.path.isdir(checkpoint_dir):
        log.info("loading %s weights from %s", model_id, checkpoint_dir)
        # quantize-at-load: each layer group quantizes as it is placed, so
        # an 8B int8 load peaks at int8 tree + one bf16 group, never the
        # full float tree (models/loader.py).  The load STREAMS on a
        # background thread while the AOT cache deserializes executables —
        # compile/restore needs shapes, not values, so the two bring-up
        # legs run concurrently instead of serially
        handle = load_params_async(
            checkpoint_dir, model_config, dtype=jnp.bfloat16, quantize=quantize
        )
        if aot is not None:
            preloaded = aot.preload()
            if preloaded:
                log.info(
                    "AOT cache: %d executables restored while weights "
                    "streamed", preloaded,
                )
        params = handle.result()
        log.info("weight stream finished in %.1fs", handle.seconds or 0.0)
    elif config.allow_random_weights:
        log.warning(
            "no checkpoint for %s (checkpoint_dir=%r); using random init — "
            "explanations will be non-linguistic (allow_random_weights set)",
            model_id, checkpoint_dir,
        )
        if quantize:
            from ..models.quant import init_params_quantized

            params = init_params_quantized(
                model_config, jax.random.PRNGKey(0), dtype=jnp.bfloat16
            )
        else:
            from ..models import family_of

            params = family_of(model_config).init_params(
                model_config, jax.random.PRNGKey(0), dtype=jnp.bfloat16
            )
    else:
        # refusing keeps random-weight noise out of pod annotations: the
        # pipeline catches the ProviderError and stores the pattern-only
        # result + degradation event instead (reference behaviour for a
        # missing AI backend, PodFailureWatcher.java:385-420)
        raise MissingCheckpoint(
            f"providerId tpu-native needs weights for {model_id!r} but "
            f"checkpoint_dir={checkpoint_dir!r} does not exist; mount a "
            f"checkpoint or set ALLOW_RANDOM_WEIGHTS=true (testing only)"
        )

    if aot is not None:
        # idempotent: the checkpoint branch already preloaded during the
        # weight stream; the random-init branches reach it only here
        aot.preload()
    # what both engines stand on (serving/runtime.py): one chip's device
    # state, built from the same settings whichever engine forms the steps
    runtime_args = dict(
        max_slots=max_slots,
        max_seq=max_seq,
        page_size=config.kv_page_size,
        kv_pages=config.kv_pages or None,
        sample_top_k=config.sample_top_k,
        aot_cache=aot,
        step_ring_capacity=config.step_ring_capacity,
    )
    scheduler = None
    if config.sched_mode == "continuous":
        from ..models.quant import hold_head_projections
        from .runtime import Runtime
        from .sched import Scheduler

        # the mixed step reads q, k and v from [L, out, in] (models/
        # quant.py): transposed once here, before the KV pool is placed,
        # and rebound, so the canonical matrices are freed, not held too
        params = hold_head_projections(params)
        # a Runtime and a Scheduler, and no wave engine: no decode block,
        # prefill grid, guided tables, LoRA stack or registered prefix
        generator = Runtime(params, model_config, tokenizer, **runtime_args)
        # automatic block-hash prefix caching (serving/kvstore.py): any
        # cached prompt prefix is reused, with an optional host-RAM
        # offload tier for evicted blocks (ops/kv_transfer.py)
        kvstore = None
        if config.kv_prefix_cache:
            from .kvstore import PrefixKVStore

            host_pool = None
            if config.kv_host_pool_mb > 0:
                from ..ops.kv_transfer import HostKVPool

                host_pool = HostKVPool(config.kv_host_pool_mb)
            kvstore = PrefixKVStore(
                config.kv_page_size,
                host_pool=host_pool,
                metrics=generator.metrics,
            )
        scheduler = Scheduler(
            generator,
            chunk=config.sched_chunk,
            token_budget=config.sched_token_budget,
            pipeline_depth=config.sched_pipeline_depth,
            spec_decode=config.spec_decode,
            spec_lookup_k=config.spec_lookup_k,
            kvstore=kvstore,
            # fleet KV fabric (operator_tpu/fabric/): mirror newly
            # registered prompt blocks into the host pool so peers
            # can fetch them over GET /kv/blocks/{hash}
            fabric_mirror=(
                config.kv_fabric
                and config.kv_fabric_mirror
                and kvstore is not None
                and kvstore.host_pool is not None
            ),
        )
        # loud, unambiguous mode line: fleet operators grep for it when a
        # rollout flips scheduling behaviour
        log.info(
            "serving mode: CONTINUOUS scheduler (pipeline_depth=%d "
            "spec_decode=%s spec_lookup_k=%d kv_prefix_cache=%s "
            "kv_host_pool_mb=%d)",
            scheduler.depth, scheduler.spec_k > 0, scheduler.spec_k,
            scheduler._kvstore is not None, config.kv_host_pool_mb,
        )
    else:
        generator = BatchedGenerator(
            params,
            model_config,
            tokenizer,
            paged=config.kv_cache_mode == "paged",
            mesh=mesh,
            decode_block=config.decode_block,
            pipeline_depth=config.pipeline_depth,
            lora_adapters=lora_adapters,
            lora_alpha=config.lora_alpha,
            prefill_chunk=prefill_chunk,
            **runtime_args,
        )
        log.info(
            "serving mode: WAVE engine (sched_mode=%s)", config.sched_mode
        )
        if config.prefix_cache and generator.paged:
            # the default template's static preamble is shared by every
            # explanation request: cache its KV once so each admission
            # prefills only its variable remainder.  CRs with a custom
            # promptTemplate simply fall back to full prefill (the engine
            # compares TOKENS per wave; a non-matching wave costs nothing).
            from .prompts import DEFAULT_TEMPLATE, template_preamble

            static_preamble = template_preamble(DEFAULT_TEMPLATE)
            try:
                generator.set_shared_prefix(static_preamble)
            except Exception:  # noqa: BLE001 - an optimisation must never block startup
                log.warning("shared-prefix priming failed; serving without it",
                            exc_info=True)
    # supervised by default in production wiring (docs/ROBUSTNESS.md): a
    # stalled or errored decode loop resets the engine and requeues
    # in-flight requests once with their residual deadlines.  Direct
    # ServingEngine(...) constructions (tests) keep the unsupervised
    # pre-supervisor semantics unless they opt in.
    supervisor = None
    if config.engine_supervisor:
        supervisor = SupervisorPolicy(
            stall_timeout_s=config.supervisor_stall_s,
            join_grace_s=config.supervisor_join_grace_s,
        )
    engine = ServingEngine(
        generator, supervisor=supervisor, scheduler=scheduler
    )
    engine.device = device
    engine.compile_watch = compile_watch
    # fleet KV fabric + disaggregation role (operator_tpu/fabric/,
    # docs/FABRIC.md).  The fetcher starts with a private empty index;
    # two feeders exist: in-process fleets (loadgen storm, tests)
    # point it at the router's health.kv_index, which the existing
    # /healthz poll keeps fresh, while a standalone replica (the k8s
    # Deployment) runs the KV_FABRIC_PEERS poller — without one of the
    # two the empty-index gate makes the fabric a true no-op (no probe,
    # no tokenize) rather than a silent per-request tax.
    from ..fabric.disagg import normalize_role

    engine.replica_role = normalize_role(config.replica_role)
    if config.kv_fabric and scheduler is not None:
        from ..fabric.fetch import FabricFetcher
        from ..fabric.index import FabricIndex

        self_id = (
            os.environ.get("SERVING_REPLICA_ID")
            or os.environ.get("POD_NAME")
            or ""
        )
        engine.fabric = FabricFetcher(
            FabricIndex(),
            api_token=os.environ.get("OPERATOR_TPU_API_TOKEN") or None,
            timeout_s=config.kv_fabric_fetch_timeout_s,
            concurrency=config.kv_fabric_concurrency,
            self_id=self_id,
            metrics=generator.metrics,
        )
        peers = [
            u.strip() for u in config.kv_fabric_peers.split(",") if u.strip()
        ]
        if peers:
            from ..fabric.peers import PeerPoller

            engine.fabric_poller = PeerPoller(
                engine.fabric.index,
                peers=peers,
                self_id=self_id,
                poll_s=config.kv_fabric_poll_s,
                timeout_s=config.kv_fabric_fetch_timeout_s,
                metrics=generator.metrics,
            )
        log.info(
            "fleet KV fabric: fetch timeout %.2fs concurrency %d role %s "
            "mirror %s peers %s",
            config.kv_fabric_fetch_timeout_s, config.kv_fabric_concurrency,
            engine.replica_role, config.kv_fabric_mirror,
            ",".join(peers) or "<in-process index>",
        )
    return engine, model_id


def build_tpu_native_provider(
    config: Optional[OperatorConfig] = None,
) -> TPUNativeProvider:
    """Factory for ProviderRegistry.register_factory('tpu-native', ...).

    Builds the shared engine once; every AIProvider CR with
    ``providerId: tpu-native`` then multiplexes onto the same batch.
    """
    engine, model_id = build_serving_engine(config)
    return TPUNativeProvider(
        engine, model_id=model_id,
        register_template_prefixes=(config or OperatorConfig()).prefix_cache,
    )
