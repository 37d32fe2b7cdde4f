"""The device this process serves on, and where its compiled programs live.

Two rules, each decided in exactly one function here:

- **Device** (:func:`resolve_device`): the serving stack runs on a TPU.
  Any other backend is served only when it was asked for by name with
  ``OPERATOR_TPU_PLATFORM`` (e.g. ``cpu`` for tests and CPU dry runs) —
  an ambient ``JAX_PLATFORMS`` is not a request, because a sandbox sets it
  for every process.  Nothing substitutes a backend on its own: a process
  that finds no TPU and was not told otherwise fails at startup.
- **Compile cache** (:func:`enable_persistent_compilation_cache`): where
  ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and this code
  sets no directory at all; otherwise the cache is ``<checkout>/.jax_cache``
  — a fixed path (the path is part of the cache key), so two consecutive
  processes of one checkout share compiled programs.
"""

from __future__ import annotations

import dataclasses
import logging
import os

log = logging.getLogger(__name__)

#: the checkout root (this file is operator_tpu/utils/platform.py)
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


class NoAccelerator(RuntimeError):
    """The default JAX backend is not a TPU and no other was asked for."""


@dataclasses.dataclass(frozen=True)
class DeviceInfo:
    """What JAX reports for the backend this process holds."""

    platform: str  # jax.devices()[0].platform
    kind: str  # jax.devices()[0].device_kind
    count: int  # len(jax.devices())

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def resolve_device() -> DeviceInfo:
    """Initialise the JAX backend and say what it is; refuse a non-TPU
    backend that ``OPERATOR_TPU_PLATFORM`` did not name.

    Must run before any other backend query: the explicit request is
    applied with a live ``jax.config`` update, which only works while no
    backend is initialised."""
    import jax

    requested = os.environ.get("OPERATOR_TPU_PLATFORM", "").strip().lower()
    if requested:
        jax.config.update("jax_platforms", requested)
    devices = jax.devices()
    info = DeviceInfo(
        platform=devices[0].platform,
        kind=devices[0].device_kind,
        count=len(devices),
    )
    if info.platform != "tpu" and info.platform != requested:
        raise NoAccelerator(
            f"JAX found no TPU (default backend {info.platform!r}, "
            f"{info.kind!r} x{info.count}); set OPERATOR_TPU_PLATFORM="
            f"{info.platform} to serve on it deliberately"
        )
    return info


def enable_persistent_compilation_cache() -> str:
    """Make XLA programs survive process restarts; returns the directory
    in use (see the module doc for the rule)."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env_dir:
        return env_dir
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
