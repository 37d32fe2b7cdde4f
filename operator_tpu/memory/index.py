"""Embedding index over stored incidents — the near-miss half of recall.

Exact fingerprint equality catches literal replays; this index catches the
*same failure phrased differently* (another service, another JVM version,
another log format for one root cause).  It reuses the pattern engine's
embedder ladder (patterns/semantic.py: lexical :class:`HashingEmbedder`
always, MiniLM-class :class:`NeuralEmbedder` when a checkpoint is mounted)
and scores query × incidents on the MXU via the fused best-window kernel
(ops/similarity.py) — one query row against the whole incident matrix is
exactly the ``windows @ patterns.T`` shape that kernel streams.
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

import numpy as np

from ..patterns.semantic import Embedder, HashingEmbedder
from .store import Incident


class IncidentIndex:
    """(digests, embedding matrix) kept in lockstep; readers snapshot the
    pair atomically (same discipline as SemanticMatcher._state)."""

    def __init__(self, embedder: Optional[Embedder] = None) -> None:
        self.embedder = embedder or HashingEmbedder()
        self._lock = threading.Lock()
        self._state: tuple[list[str], np.ndarray] = (
            [],
            np.zeros((0, self.embedder.dim), np.float32),
        )

    def __len__(self) -> int:
        # graftlint: disable=GL004 reason=deliberate lock-free snapshot read; _state is an immutable tuple swapped atomically under the lock
        return len(self._state[0])

    # ------------------------------------------------------------------
    def rebuild(self, incidents: Sequence[Incident], texts: Optional[Sequence[str]] = None) -> int:
        """Re-embed every incident (after eviction or a restore).  ``texts``
        overrides the per-incident embedding text when the caller has richer
        basis than the stored template (recall passes fingerprint
        embedding_text)."""
        digests = [i.fingerprint for i in incidents if i.fingerprint]
        if texts is None:
            texts = [self._incident_text(i) for i in incidents if i.fingerprint]
        embeddings = self.embedder.embed(list(texts))
        with self._lock:
            self._state = (digests, embeddings)
        return len(digests)

    def add(self, incident: Incident, text: Optional[str] = None) -> None:
        """Append one incident's embedding row (no-op if already present —
        an upsert of an existing digest keeps its original embedding, the
        template is part of the identity and cannot have changed)."""
        if not incident.fingerprint:
            return
        row = self.embedder.embed([text or self._incident_text(incident)])
        with self._lock:
            digests, matrix = self._state
            if incident.fingerprint in digests:
                return
            self._state = (
                digests + [incident.fingerprint],
                np.concatenate([matrix, row.astype(np.float32)], axis=0),
            )

    def remove(self, evicted: Sequence[str]) -> None:
        if not evicted:
            return
        gone = set(evicted)
        with self._lock:
            digests, matrix = self._state
            keep = [i for i, d in enumerate(digests) if d not in gone]
            self._state = (
                [digests[i] for i in keep],
                matrix[keep] if keep else np.zeros((0, self.embedder.dim), np.float32),
            )

    @staticmethod
    def _incident_text(incident: Incident) -> str:
        from .fingerprint import incident_embedding_text  # one shared basis

        return incident_embedding_text(
            incident.template, incident.pattern_ids,
            incident.reason, incident.exit_code,
        )

    # ------------------------------------------------------------------
    def query(self, text: str, k: int = 3) -> list[tuple[str, float]]:
        """Top-k (digest, cosine score), descending.  Scores on the MXU via
        the fused Pallas kernel on TPU, the XLA reference elsewhere."""
        # graftlint: disable=GL004 reason=deliberate lock-free snapshot read; _state is an immutable tuple swapped atomically under the lock
        digests, matrix = self._state  # one consistent snapshot
        if not digests or not text.strip():
            return []
        query = self.embedder.embed([text]).astype(np.float32)  # [1, D]
        scores = self._score(query, matrix)
        k = min(max(1, k), len(digests))
        order = np.argsort(scores)[::-1][:k]
        return [(digests[int(i)], float(scores[int(i)])) for i in order]

    @staticmethod
    def _score(query: np.ndarray, matrix: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp

        from ..ops.similarity import best_window_scores

        # one query "window" against the incident matrix as the pattern
        # side: per-incident best == the cosine itself.  No fallback: on
        # a TPU a kernel failure raises (ops/_dispatch.py)
        scores, _ = best_window_scores(jnp.asarray(query), jnp.asarray(matrix))
        return np.asarray(scores)
