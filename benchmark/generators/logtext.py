"""Seeded synthetic log text in the vocabulary of ``tests/fixtures/*.log``.

The vocabulary (application names, packages, line shapes of JVM, Go,
Python and kubelet events) is frozen in ``log_vocabulary.json`` beside
this file; both prompt synthesisers draw from it.
"""

from __future__ import annotations

import json
import os
import random

_HERE = os.path.dirname(os.path.abspath(__file__))

with open(os.path.join(_HERE, "log_vocabulary.json"), encoding="utf-8") as _f:
    VOCABULARY = json.load(_f)


def pod_name(rng: random.Random) -> tuple[str, str, str]:
    """(app, pod, namespace) of one synthetic failing pod."""
    app = rng.choice(VOCABULARY["apps"])
    pod = f"{app}-{rng.getrandbits(36):09x}-{rng.getrandbits(20):05x}"
    return app, pod, rng.choice(VOCABULARY["namespaces"])


def log_line(rng: random.Random, app: str, pod: str, namespace: str) -> str:
    ts = (
        f"2026-{rng.randint(1, 12):02d}-{rng.randint(1, 28):02d}T"
        f"{rng.randint(0, 23):02d}:{rng.randint(0, 59):02d}:"
        f"{rng.randint(0, 59):02d}.{rng.randint(0, 999):03d}Z"
    )
    return rng.choice(VOCABULARY["lines"]).format(
        ts=ts, app=app, pod=pod, namespace=namespace,
        thread=rng.choice(VOCABULARY["threads"]),
        package=rng.choice(VOCABULARY["packages"]),
        n1=rng.randint(1, 9), n2=rng.randint(10, 99),
        n3=rng.randint(100, 999), n4=rng.randint(1000, 9999),
        hex=f"{rng.getrandbits(24):06x}",
    )


def log_text(
    rng: random.Random, chars: int, app: str, pod: str, namespace: str
) -> str:
    """Whole log lines up to ``chars`` characters, the last one cut."""
    lines: list[str] = []
    used = 0
    while used < chars:
        line = log_line(rng, app, pod, namespace)
        lines.append(line)
        used += len(line) + 1
    return "\n".join(lines)[:chars]
