"""Persisted AOT executables — the warm-start path for the serving engine.

A cold engine compiles its serving programs before the first token (how
long on the chip: PERF.md, chip_smoke.py's bring-up facts); the
supervisor's device-reset recovery and any scale-from-zero autoscaler pay
that again on every boot.  XLA's persistent *compilation* cache
(utils/platform.py) only skips the backend compile — tracing,
lowering and executable construction still run per program, and the cache
key is XLA's, not ours.  This module persists the **compiled executables
themselves** (``jax.experimental.serialize_executable``): on a warm boot
every serving program the grid drives is deserialized from disk instead of
compiled, so bring-up is dominated by the HBM weight transfer the loader
overlaps with it (models/loader.py ``load_params_async``).

Key discipline: executables are only valid for the exact (program shapes x
sharding x runtime) they were compiled for, so the cache directory is keyed
by a fingerprint over everything that shapes a program — model config,
engine shape grid inputs (slots/seq/paging/decode block/chunking), mesh
axes and device kind, weight/cache dtypes, jax+jaxlib versions and the
backend's platform version (libtpu on TPU).  Any mismatch is a MISS, never
a wrong load; any deserialize or call-time error falls back loudly to the
existing live compile (``CachedProgram``).

Wiring: ``BatchedGenerator`` owns an :class:`AotCache` when built with
``aot_cache_path`` (or a provider-prebuilt cache) and routes every program
construction site through ``_aot_wrap`` — wave prefill/chunk/finish/prefix
programs, both decode blocks, and the continuous scheduler's ONE mixed
program.  The supervisor's restart path needs no extra wiring: a reset
rebuilds programs through the same sites, which restore from the cache.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import pickle
import tempfile
import time
from typing import Any, Iterable, Optional

log = logging.getLogger(__name__)

#: bump when the on-disk record layout changes; old files then read as
#: corrupt (loud fallback + removal) instead of deserializing garbage
CACHE_FORMAT = 1

#: filename suffix for one serialized executable
_SUFFIX = ".aotx"


def _fresh_compile_scope():
    """Scope that bypasses XLA's persistent compilation cache for a compile
    whose executable will be serialized — BY DESIGN: with ``AOT_CACHE_PATH``
    set, serving programs never read or feed the persistent cache.  An
    executable reconstructed from a persistent-cache HIT serializes WITHOUT
    its jitted symbol definitions — ``deserialize_and_load`` then fails
    with "Symbols not found" in the next process, poisoning the stored
    ``.aotx``.  A fresh build serializes completely; nothing is lost
    because this cache supersedes XLA's for serving programs."""
    from jax._src import config as _jax_config

    return _jax_config.enable_compilation_cache(False)


#: jit-ed function names of the serving programs (programs.py inner defs,
#: engine decode methods, sched/mixed.py) — what a compile-log event must
#: contain to count as a SERVING-program compile.  Host glue (eager
#: ``convert_element_type`` / ``scatter`` / ... mini-programs) recompiles
#: per process and is excluded: it is milliseconds, not the warmup grid.
SERVING_PROGRAM_MARKERS = (
    "prefill_fn", "chunk_fn", "finish_fn", "mixed_fn", "_decode_block",
)


def serving_compile_events(events: Iterable) -> list:
    """Filter a ``CompileWatcher`` event list down to serving-program
    compiles (see SERVING_PROGRAM_MARKERS).  Events are the watcher's
    ``(t, name, duration)`` tuples."""
    return [
        ev for ev in events
        if any(marker in ev[1] for marker in SERVING_PROGRAM_MARKERS)
    ]


def runtime_versions() -> dict:
    """The runtime facts an executable is only valid for: jax/jaxlib
    versions and the backend platform + its runtime version (libtpu on
    TPU).  ``AOT_CACHE_SALT`` folds in so operators (and tests) can force
    a cold boot without deleting anything."""
    import jax
    import jax.extend
    import jaxlib

    backend = jax.extend.backend.get_backend()
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": backend.platform,
        "platform_version": str(backend.platform_version),
        "salt": os.environ.get("AOT_CACHE_SALT", ""),
    }


def _dtype_name(dtype: Any) -> str:
    if dtype is None:
        return "bfloat16"
    return getattr(dtype, "__name__", None) or str(dtype)


def generator_fingerprint(
    *,
    config: Any,
    weight_dtype: str,
    max_slots: int,
    max_seq: Optional[int] = None,
    cache_dtype: Any = None,
    paged: bool = False,
    page_size: int = 64,
    kv_pages: Optional[int] = None,
    mesh: Any = None,
    decode_block: int = 1,
    sample_top_k: Optional[int] = None,
    pipeline_depth: int = 1,
    prefill_chunk: Optional[int] = None,
    sched_pipeline_depth: int = 1,
    spec_width: int = 1,
    kv_prefix_cache: bool = False,
    lora_names: Iterable[str] = (),
) -> dict:
    """The fingerprint payload for a ``BatchedGenerator`` shape.

    Called with the generator's constructor arguments (provider and tests)
    or its resolved attributes (the generator itself); light normalisation
    here keeps the two call sites agreeing.  A divergence is SAFE — it
    reads as a cache miss and the programs compile live."""
    try:
        model = dataclasses.asdict(config)
    except TypeError:
        model = {k: v for k, v in vars(config).items() if not k.startswith("_")}
    family = getattr(config, "family", "llama")
    if family != "llama":
        # the family is a class attribute, not a field: two families that
        # shared every field value would otherwise share a fingerprint
        model["family"] = family
    mesh_desc = None
    if mesh is not None:
        first = next(iter(mesh.devices.flat))
        mesh_desc = {
            "axes": dict(zip(mesh.axis_names, [int(s) for s in mesh.devices.shape])),
            "devices": int(mesh.devices.size),
            "kind": str(getattr(first, "device_kind", "?")),
        }
    max_seq_limit = int(model.get("max_seq_len") or 0) or None
    resolved_seq = min(max_seq or max_seq_limit, max_seq_limit) if max_seq_limit else max_seq
    return {
        "format": CACHE_FORMAT,
        "model": model,
        "weight_dtype": weight_dtype,
        "max_slots": int(max_slots),
        "max_seq": resolved_seq,
        "cache_dtype": _dtype_name(cache_dtype),
        "paged": bool(paged),
        "page_size": int(page_size),
        "kv_pages": int(kv_pages or 0),
        "mesh": mesh_desc,
        "decode_block": int(decode_block),
        "sample_top_k": int(sample_top_k) if sample_top_k else None,
        "pipeline_depth": int(pipeline_depth),
        "prefill_chunk": int(prefill_chunk) if prefill_chunk else None,
        # continuous-scheduler shape knobs: the mixed program's sampled
        # width (1 + spec_lookup_k) changes the compiled executable, and
        # depth keys the persisted-executable join even though the trace
        # is depth-independent (conservative: a depth flip re-warms)
        "sched_pipeline_depth": int(sched_pipeline_depth),
        "spec_width": int(spec_width),
        # prefix caching shapes the mixed program's page-table bounds
        # (cache-owned pages share the row tables): keying on it keeps a
        # cache-on executable from being replayed into a cache-off boot
        "kv_prefix_cache": bool(kv_prefix_cache),
        "lora": sorted(str(n) for n in lora_names if n),
        "runtime": runtime_versions(),
    }


def fingerprint_digest(payload: dict) -> str:
    blob = json.dumps(payload, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()


class AotCache:
    """One fingerprint-keyed directory of serialized serving executables.

    ``get``/``put`` never raise: a miss or any I/O / deserialize error
    degrades to live compilation with a loud log line and the
    ``podmortem_aot_cache_{hit,miss,store,error}_total`` counters, so a
    wrong cache can cost seconds, never correctness.
    """

    def __init__(self, path: str, payload: dict, *, metrics: Any = None) -> None:
        self.payload = payload
        self.fingerprint = fingerprint_digest(payload)
        self.dir = os.path.join(path, self.fingerprint[:32])
        self.metrics = metrics
        self.hits = 0
        self.misses = 0
        self.errors = 0
        self.stored = 0
        #: programs compiled LIVE under this cache (cold or fallback) —
        #: the number a warm-boot assertion wants to see at zero
        self.live_compiles = 0
        #: entries discarded for the KNOWN environmental failure: XLA
        #: raising "Symbols not found" at deserialize_and_load.  It means
        #: the stored executable was serialized from an XLA
        #: persistent-compilation-cache HIT — the runtime handed back a
        #: cached binary whose jitted symbol definitions were never
        #: embedded in the serialized payload, so the .aotx is poisoned
        #: at STORE time and only detectable at the next boot's load.
        #: Distinct from ``errors`` so warm-boot tests can tell "cache
        #: fell back for the documented environmental reason" apart from
        #: genuine corruption.
        self.symbol_errors = 0
        self._preloaded: dict[str, Any] = {}
        self._warned_cold = False

    # -- bookkeeping ----------------------------------------------------
    def _incr(self, name: str) -> None:
        if self.metrics is not None:
            self.metrics.incr(name)

    def _file(self, name: str) -> str:
        return os.path.join(self.dir, name + _SUFFIX)

    def stats(self) -> dict:
        return {
            "fingerprint": self.fingerprint[:16],
            "dir": self.dir,
            "hits": self.hits,
            "misses": self.misses,
            "errors": self.errors,
            "symbol_errors": self.symbol_errors,
            "stored": self.stored,
            "live_compiles": self.live_compiles,
        }

    # -- load -----------------------------------------------------------
    def _deserialize(self, name: str, path: str) -> Any:
        with open(path, "rb") as f:
            record = pickle.load(f)
        if record.get("format") != CACHE_FORMAT:
            raise ValueError(f"cache format {record.get('format')!r} != {CACHE_FORMAT}")
        from jax.experimental import serialize_executable

        return serialize_executable.deserialize_and_load(
            record["payload"], record["in_tree"], record["out_tree"]
        )

    def preload(self) -> int:
        """Deserialize every stored executable now (the provider calls this
        while the weight stream owns the HBM bus — deserialization needs
        disk + host CPU only).  Returns the number preloaded."""
        try:
            names = [
                f[: -len(_SUFFIX)]
                for f in os.listdir(self.dir)
                if f.endswith(_SUFFIX)
            ]
        except OSError:
            return 0  # cold boot: directory appears on the first put
        for name in names:
            if name in self._preloaded:
                continue
            try:
                self._preloaded[name] = self._deserialize(name, self._file(name))
            except Exception as exc:  # noqa: BLE001 - one bad file must not kill boot
                self._note_deserialize_error(name, exc, stage="preload")
        return len(self._preloaded)

    def _note_deserialize_error(
        self, name: str, exc: BaseException, *, stage: str
    ) -> None:
        """Classify one deserialize failure, count it, discard the file.

        ``Symbols not found`` is the documented environmental mode (see
        ``symbol_errors``): a host whose shared XLA persistent
        compilation cache was already warm at STORE time serialized an
        executable without its jitted symbol definitions.  It gets a
        LOUD, named discard (``podmortem_aot_cache_symbols_lost_total``)
        and the live-compile lane re-stores a sound entry; anything else
        is generic corruption."""
        self.errors += 1
        self._incr("aot_cache_error")
        if "Symbols not found" in str(exc):
            self.symbol_errors += 1
            self._incr("aot_cache_symbols_lost")
            log.error(
                "AOT cache entry %r is missing its jitted symbol "
                "definitions (%s-time XLA 'Symbols not found'): it was "
                "serialized from a WARM shared XLA compilation cache, so "
                "the stored executable never contained its own code. "
                "Discarding it and compiling live; the re-stored entry "
                "will be self-contained.", name, stage,
            )
        else:
            log.warning(
                "AOT cache entry %r failed to deserialize during %s; "
                "falling back to live compile and discarding the file",
                name, stage, exc_info=True,
            )
        self._remove(name)

    def get(self, name: str) -> Optional[Any]:
        """The loaded executable for ``name``, or None (miss/corrupt —
        the caller compiles live)."""
        preloaded = self._preloaded.pop(name, None)
        if preloaded is not None:
            self.hits += 1
            self._incr("aot_cache_hit")
            return preloaded
        path = self._file(name)
        if not os.path.exists(path):
            self.misses += 1
            self._incr("aot_cache_miss")
            if not self._warned_cold:
                self._warned_cold = True
                log.warning(
                    "AOT executable cache MISS for %r (fingerprint %s): "
                    "compiling live and persisting for the next boot "
                    "(further misses this boot log at DEBUG)",
                    name, self.fingerprint[:16],
                )
            else:
                log.debug("AOT cache miss: %s", name)
            return None
        try:
            loaded = self._deserialize(name, path)
        except Exception as exc:  # noqa: BLE001 - corrupt entry: loud live-compile fallback
            self._note_deserialize_error(name, exc, stage="load")
            return None
        self.hits += 1
        self._incr("aot_cache_hit")
        return loaded

    def note_call_failure(self, name: str) -> None:
        """A restored executable was rejected at call time (aval/sharding
        drift the fingerprint missed): count it, drop the file so the next
        boot stores a fresh one, and let the caller compile live."""
        self.errors += 1
        self._incr("aot_cache_error")
        log.warning(
            "AOT cached executable %r rejected at call time; falling back "
            "to live compile (the stale file is discarded)", name,
        )
        self._remove(name)

    def _remove(self, name: str) -> None:
        try:
            os.remove(self._file(name))
        except OSError:
            pass

    # -- store ----------------------------------------------------------
    def put(self, name: str, compiled: Any) -> bool:
        """Serialize + persist one compiled executable (atomic rename so a
        crash mid-write can only leave a temp file, never a torn entry)."""
        try:
            from jax.experimental import serialize_executable

            payload, in_tree, out_tree = serialize_executable.serialize(compiled)
            blob = pickle.dumps({
                "format": CACHE_FORMAT,
                "name": name,
                "payload": payload,
                "in_tree": in_tree,
                "out_tree": out_tree,
            })
            os.makedirs(self.dir, exist_ok=True)
            self._write_manifest()
            fd, tmp = tempfile.mkstemp(dir=self.dir, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, self._file(name))
            except BaseException:
                try:
                    os.remove(tmp)
                except OSError:
                    pass
                raise
        except Exception:  # noqa: BLE001 - persistence is an optimisation only
            self.errors += 1
            self._incr("aot_cache_error")
            log.warning("AOT cache store failed for %r", name, exc_info=True)
            return False
        self.stored += 1
        self._incr("aot_cache_store")
        return True

    def _write_manifest(self) -> None:
        """Human-readable key anatomy next to the executables
        (docs/SERVING.md "Bring-up"): what exactly this directory is valid
        for, so a surprising miss is debuggable by diffing two manifests."""
        manifest = os.path.join(self.dir, "fingerprint.json")
        if os.path.exists(manifest):
            return
        try:
            with open(manifest, "w") as f:
                json.dump(
                    {"fingerprint": self.fingerprint, "payload": self.payload},
                    f, indent=2, sort_keys=True, default=str,
                )
        except OSError:
            pass


class CachedProgram:
    """One serving program behind the AOT cache.

    Warm: constructed with the deserialized executable and never compiles.
    Cold: the first call lowers + compiles the wrapped ``jax.jit`` function
    with its concrete arguments, persists the executable, then runs it.

    Two failure lanes, deliberately distinct:

    - a restored executable that rejects its VERY FIRST call (aval or
      sharding drift the fingerprint missed) is stale — discard the file
      loudly and compile live;
    - an executable that has already served matching calls and then sees
      different avals has a shape-POLYMORPHIC caller (the guided programs'
      automaton tables restack to new [A_pad, S_pad] shapes mid-serve) —
      that call delegates to the plain ``jax.jit``, whose trace cache
      handles the novel signature, and the executable stays for the
      canonical shape.  Executables are single-signature by construction;
      this keeps polymorphism correct without widening the cache format.
    """

    __slots__ = ("name", "_cache", "_fn", "_loaded", "_compiled", "_served")

    def __init__(self, cache: AotCache, name: str, fn: Any) -> None:
        self.name = name
        self._cache = cache
        self._fn = fn
        self._loaded = cache.get(name)
        self._compiled: Any = None
        self._served = 0

    @property
    def from_cache(self) -> bool:
        return self._loaded is not None

    def __call__(self, *args: Any) -> Any:
        exe = self._loaded if self._loaded is not None else self._compiled
        if exe is None:
            started = time.perf_counter()
            with _fresh_compile_scope():
                self._compiled = self._fn.lower(*args).compile()
            self._cache.live_compiles += 1
            log.info(
                "AOT cache: compiled %s live in %.2fs; persisting",
                self.name, time.perf_counter() - started,
            )
            self._cache.put(self.name, self._compiled)
            exe = self._compiled
        try:
            out = exe(*args)
        except Exception as err:
            # loaded executables validate input avals BEFORE donating, so
            # a rejection here leaves the arguments alive for the fallback
            if self._served == 0 and self._loaded is not None:
                self._cache.note_call_failure(self.name)
                self._loaded = None
                return self(*args)  # cold path: compile live + re-store
            if isinstance(err, (TypeError, ValueError)):
                log.debug(
                    "AOT program %s: novel arg signature; running via jit",
                    self.name,
                )
                return self._fn(*args)
            raise
        self._served += 1
        return out
