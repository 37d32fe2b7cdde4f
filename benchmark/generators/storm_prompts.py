"""Explanation prompts as the operator sends them in a failure storm.

``TEMPLATE`` is a frozen copy of ``operator_tpu/serving/prompts.py
DEFAULT_TEMPLATE`` and the three character budgets beside it (evidence
1,600, log tail 1,200, prior incidents 1,200): the product's real prompt,
filled with seeded synthetic pods, pattern summaries, evidence windows and
log tails.  Every prompt shares the static preamble.  A share of the
arrivals (``reask_share``, exact where enough arrivals are eligible)
re-asks the exact prompt of an earlier arrival that was due
``reask_delay_s`` = [low, high] seconds before: the crash-looping pod,
which the prefix cache should serve.
"""

from __future__ import annotations

import random

from . import logtext

TEMPLATE = """You are a Kubernetes failure analyst. A pod failed; your job is to name the root cause and the most direct fix.

Ground rules:
- Trust the pattern analysis and the quoted log evidence over speculation; if they conflict, say which you believe and why.
- Distinguish the root cause from its symptoms (a CrashLoopBackOff is a symptom; the exception or exit code behind it is the cause).
- Common causes worth checking against the evidence: out-of-memory kills (exit 137, OOMKilled), failed liveness/readiness probes, image pull errors, missing config/secrets, permission errors, disk pressure or eviction, dependency outages (databases, DNS, upstream services), and application exceptions at startup.
- Name concrete Kubernetes objects and fields in the fix when the evidence identifies them (limits, probes, image tags, env vars).
- If the evidence is insufficient for a confident diagnosis, say so and name the single most useful signal to collect next.

Pod: {pod_name} (namespace {namespace})
Pattern analysis (severity {severity}): {patterns}

Strongest log evidence:
{evidence}

Recent log tail:
{log_tail}

Answer concisely with exactly two sections:
Root Cause: <one or two sentences naming the root cause>
Fix: <the most direct remediation>"""

MAX_EVIDENCE_CHARS = 1600
MAX_TAIL_CHARS = 1200
MAX_PRIOR_INCIDENT_CHARS = 1200

_PRIOR_HEAD = (
    "\n\nSimilar previously-analyzed incidents (for context; this "
    "failure is NOT identical to them — diagnose the evidence above "
    "on its own merits):\n"
)


def _spread(rng: random.Random, n: int, low: int, high: int) -> list[int]:
    """``n`` integers, one from each ``n``-th of ``[low, high]``, in seeded
    order: every seed sends the same lengths, differently arranged."""
    values = [int(low + (i + rng.random()) / n * (high - low)) for i in range(n)]
    rng.shuffle(values)
    return values


def _fresh_prompt(
    text: random.Random, evidence_chars: int, tail_chars: int, prior_chars: int,
    n_patterns: int, windows: int,
) -> str:
    """One prompt of the given shape; ``text`` draws only the words."""
    app, pod, namespace = logtext.pod_name(text)
    vocabulary = logtext.VOCABULARY
    patterns = "; ".join(
        f"{name} (score {text.uniform(0.4, 0.99):.2f})"
        for name in text.sample(vocabulary["patterns"], n_patterns)
    )
    # evidence: up to three match windows joined as pack_blocks joins them
    evidence = "\n---\n".join(
        logtext.log_text(text, evidence_chars // windows, app, pod, namespace)
        for _ in range(windows)
    )[:MAX_EVIDENCE_CHARS]
    tail = logtext.log_text(text, tail_chars, app, pod, namespace)
    prompt = TEMPLATE.format(
        pod_name=pod, namespace=namespace,
        severity=text.choice(vocabulary["severities"]),
        patterns=patterns, evidence=evidence, log_tail=tail,
    )
    if prior_chars:  # a near-miss recall adds prior incidents
        prior = logtext.log_text(text, prior_chars, app, pod, namespace)
        prompt += (
            _PRIOR_HEAD + f"[1] similarity {text.uniform(0.5, 0.9):.2f}, seen 3x\n" + prior
        )
    return prompt


def make(seed: int, params: dict, at_s: list[float]) -> list[str]:
    """One prompt per due time.  The lengths of the fresh prompts, the
    share of them with prior incidents (30%) and the share of re-asks are
    the same for every seed.  The lengths are spread over the fresh
    prompts alone: a re-ask is served from the prefix cache, so what it
    would have been must not change the tokens a window has to prefill.

    Two generators: the *shape* of the storm (who re-asks whom, which
    arrival gets which length, which have prior incidents) is drawn from
    ``structure_seed`` where the traffic file gives one, else from
    ``seed``; the *words* (pods, namespaces, log lines) always from
    ``seed``.  With a ``structure_seed`` every seed sends the same storm
    in other words, so two runs differ by the machine and not by which
    arrival drew the long prompt (PERF.md, PR 22)."""
    rng = random.Random(f"storm_prompts:{params.get('structure_seed', seed)}")
    text = random.Random(f"storm_text:{seed}")
    n = len(at_s)
    low, high = params.get("reask_delay_s", (0.0, 0.0))
    earlier = [
        [j for j in range(i) if low <= due - at_s[j] <= high]
        for i, due in enumerate(at_s)
    ]
    eligible = [i for i in range(n) if earlier[i]]
    wanted = int(float(params.get("reask_share", 0.0)) * n + 0.5)
    reasks = set(rng.sample(eligible, min(wanted, len(eligible))))
    fresh = n - len(reasks)
    evidence = _spread(rng, fresh, 500, MAX_EVIDENCE_CHARS)
    tails = _spread(rng, fresh, 400, MAX_TAIL_CHARS)
    prior = [k < int(0.3 * fresh + 0.5) for k in range(fresh)]
    rng.shuffle(prior)
    shapes = [
        (
            rng.randint(300, MAX_PRIOR_INCIDENT_CHARS // 2) if prior[k] else 0,
            rng.randint(1, 3), rng.randint(1, 3),
        )
        for k in range(fresh)
    ]
    prompts: list[str] = []
    k = 0
    for i in range(n):
        if i in reasks:
            prompts.append(prompts[rng.choice(earlier[i])])
        else:
            prompts.append(_fresh_prompt(text, evidence[k], tails[k], *shapes[k]))
            k += 1
    return prompts
