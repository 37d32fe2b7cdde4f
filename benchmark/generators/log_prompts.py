"""Short unshared prompts: a terse instruction over a few seeded log lines.

What an external caller of ``/v1/completions``, or a CR with a terse
``promptTemplate``, sends.  Each prompt opens with its own random job line,
so no two share a prefix as long as a KV page and the prefix cache has
nothing to serve.  Lengths are drawn in characters
(``chars_low``..``chars_high``); the traffic file says what that gives in
tokens under the committed tokenizer.
"""

from __future__ import annotations

import random

from . import logtext


def make(seed: int, params: dict, at_s: list[float]) -> list[str]:
    """One prompt per entry of ``at_s`` (only its length is used)."""
    rng = random.Random(f"log_prompts:{seed}")
    low, high = int(params["chars_low"]), int(params["chars_high"])
    prompts = []
    for _ in at_s:
        app, pod, namespace = logtext.pod_name(rng)
        head = f"job {rng.getrandbits(48):012x}: write a runbook for pod {pod} in {namespace}.\n"
        body = logtext.log_text(
            rng, max(1, rng.randint(low, high) - len(head)), app, pod, namespace
        )
        prompts.append(head + body)
    return prompts
