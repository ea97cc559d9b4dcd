"""Pure-jnp oracle for the batched retrieval top-k kernel.

One fused program: corpus similarity GEMM + top-k, over a device-resident
corpus.  This is both the test oracle for the Pallas kernel and the XLA
fast path `ops.retrieval_topk` compiles on non-TPU backends.

Tie semantics (pinned by tests): ``jax.lax.top_k`` is stable, so exactly
tied scores admit the LOWEST corpus id first — the same deterministic
tie-break the host ``VectorStore`` implements via composite keys.  Scores
are XLA float32 reductions: decision-level parity with the host path, not
the canonical GEMV bit pattern (see ``core/retrieval.py``'s contract).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import NEG_INF, SELECT_PRECISION

__all__ = ["NEG_INF", "retrieval_topk_ref"]


def retrieval_topk_ref(q, corpus, *, k: int):
    """Top-k ids + scores for a query block.

    Shapes: q (Bq, d), corpus (n, d).  Returns (scores (Bq, k) float32,
    ids (Bq, k) int32), scores descending, exact ties lowest-id first.
    """
    scores = jnp.matmul(q, corpus.T, precision=SELECT_PRECISION)  # (Bq, n)
    vals, idx = jax.lax.top_k(scores, k)  # stable: lowest index first on ties
    return vals.astype(jnp.float32), idx.astype(jnp.int32)
