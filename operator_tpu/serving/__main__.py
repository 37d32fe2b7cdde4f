"""Serve the TPU engine over the OpenAI wire format.

    python -m operator_tpu.serving [--host 0.0.0.0] [--port 8000]

Model/weights/mesh come from the same operator config env the cluster
deployment uses (utils/config.py): OPERATOR_TPU_MODEL, CHECKPOINT_DIR,
WEIGHT_DTYPE, SERVING_MESH, MAX_BATCH_SIZE, ... plus
OPERATOR_TPU_API_TOKEN to require a bearer token.  This is the
standalone-inference face of the framework — the in-cluster operator
drives the identical engine in-process (serving/provider.py).

The server runs on a TPU and exits non-zero when JAX finds none; serving
on another backend takes ``OPERATOR_TPU_PLATFORM=<name>`` (e.g. ``cpu``
for a dry run) — see utils/platform.py.
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--host", default=os.environ.get("OPERATOR_TPU_HOST", "0.0.0.0"))
    parser.add_argument(
        "--port", type=int, default=int(os.environ.get("OPERATOR_TPU_PORT", "8000"))
    )
    args = parser.parse_args()
    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(name)s %(levelname)s %(message)s"
    )

    from .httpserver import serve_forever
    from .provider import TPUNativeProvider, build_serving_engine

    from ..utils.config import OperatorConfig

    cfg = OperatorConfig.from_env()
    engine, model_id = build_serving_engine()
    analysis_backend = TPUNativeProvider(
        engine, model_id=model_id,
        # same PREFIX_CACHE gate operator mode wires: a disabled cache
        # must not grow the registry through the analyze route
        register_template_prefixes=cfg.prefix_cache,
    )

    # /v1/embeddings: MiniLM when a checkpoint is mounted, lexical hashing
    # otherwise — the one shared ladder (patterns/semantic.py)
    from ..patterns.semantic import build_embedder

    embedder = build_embedder(os.environ.get("ENCODER_CHECKPOINT_DIR", "").strip())

    try:
        asyncio.run(
            serve_forever(
                engine,
                model_id=model_id,
                host=args.host,
                port=args.port,
                api_token=os.environ.get("OPERATOR_TPU_API_TOKEN") or None,
                embedder=embedder,
                analysis_backend=analysis_backend,
                # stable replica identity for the failover router's
                # /healthz polls: the serving Deployment injects POD_NAME
                # (downward API); hostname otherwise
                replica_id=(
                    os.environ.get("SERVING_REPLICA_ID")
                    or os.environ.get("POD_NAME")
                    or None
                ),
                # POST /profile?seconds=N (PROFILE_ENABLED / PROFILE_DIR)
                profile_enabled=cfg.profile_enabled,
                profile_dir=cfg.profile_dir,
            )
        )
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
