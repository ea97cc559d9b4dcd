"""Pure-jnp oracle for the fused RPS scoring kernel.

Mirrors the shipped numpy Algorithm 3 (``RuntimePathSelector``): hard top-k
kNN voting over the training queries (Eq. 14), a single-argmax critical set
per query, the ``1e-3 * path_mean_acc`` tie-break prior, per-query SLO
vectors, and the evaluated-path validity mask.  This is both the test oracle
for the Pallas kernel and the XLA fast path `ops.dsqe_score` compiles on
non-TPU backends.

The ref is factored the same way the stage pipeline is
(``kernels/stages.py``): ``dsqe_score_ref`` = train-similarity top-k (the
exact computation ``retrieval_topk_ref`` performs) + ``dsqe_score_from_topk``
(vote scatter, prior, feasibility).  The score stage consumes the retrieve
stage's top-k through the SAME ``dsqe_score_from_topk``, so the composed
fused program and this monolithic ref are bit-identical on CPU by
construction, not by tolerance.

Tie semantics (pinned by tests): the critical set is the FIRST argmax
prototype (matching ``np.argmax``), and when training similarities tie
EXACTLY at the k-boundary the lowest-index training row wins
(``jax.lax.top_k`` is stable) — deterministic, and identical between this
ref and the Pallas kernel.  The numpy selector's ``np.argpartition`` leaves
the admitted member of such an exact tie unspecified, so exact k-boundary
ties are a documented (measure-zero on real float similarities) divergence
mode alongside the float32-vs-float64 score ulp caveat.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import NEG_INF, SELECT_PRECISION

__all__ = ["NEG_INF", "dsqe_score_from_topk", "dsqe_score_ref"]


def dsqe_score_from_topk(z, topk_vals, topk_ids, protos, path_weights,
                         contains, lat, cost, prior, valid, slo, *,
                         proto_valid=None):
    """Masked path scores + critical-set ids from precomputed kNN top-k.

    ``z`` (Bq, d) projected queries; ``topk_vals``/``topk_ids`` (Bq, k) the
    train-similarity top-k (descending, lowest-index ties first); remaining
    tables as in ``dsqe_score_ref``.  ``slo`` must already be (Bq, 2)
    float32.  Returns (scores (Bq, P), set_id (Bq,) int32).

    ``proto_valid`` (K,), optional: per-prototype validity mask for
    domain-sharded tables padded to a common K — pad rows are zero vectors
    whose similarity (0) would beat every REAL prototype when all real
    similarities are negative, so masked rows are forced to ``NEG_INF``
    before the argmax.  ``None`` (the single-domain path) is bit-for-bit the
    pre-mask computation.
    """
    Bq = z.shape[0]
    N = path_weights.shape[0]
    lat = lat.reshape(1, -1)
    cost = cost.reshape(1, -1)
    prior = prior.reshape(1, -1)
    valid = valid.reshape(1, -1)

    dot = functools.partial(jnp.matmul, precision=SELECT_PRECISION)
    psims = dot(z, protos.T)  # (Bq, K)
    if proto_valid is not None:
        psims = jnp.where(proto_valid.reshape(1, -1) > 0.5, psims, NEG_INF)
    set_id = jnp.argmax(psims, axis=1)  # first max wins on exact ties
    set_onehot = jax.nn.one_hot(set_id, protos.shape[0], dtype=jnp.float32)

    w = jnp.maximum(topk_vals, 0.0)
    # scatter the k vote weights back over N via a dense one-hot contraction
    # (XLA CPU lowers this ~30% faster than an .at[].add scatter)
    onehot = jax.nn.one_hot(topk_ids, N, dtype=jnp.float32)  # (Bq,k,N)
    votes = jnp.einsum("bkn,bk->bn", onehot, w, precision=SELECT_PRECISION)
    scores = dot(votes, path_weights) + prior

    feas_set = dot(set_onehot, contains)
    feasible = ((feas_set > 0.5) & (valid > 0.5)
                & (lat <= slo[:, 0:1]) & (cost <= slo[:, 1:2]))
    return jnp.where(feasible, scores, NEG_INF), set_id.astype(jnp.int32)


def dsqe_score_ref(q, protos, train, path_weights, contains, lat, cost,
                   prior, valid, slo, *, knn: int = 16):
    """Masked path scores + critical-set ids for a query batch.

    Shapes: q (Bq,d), protos (K,d), train (N,d), path_weights (N,P) —
    one-hot(P_q) * A(q,P_q) rows — contains (K,P), lat/cost/prior/valid
    (P,) or (1,P), slo (Bq,2) or (2,) broadcast per-query
    [max_latency, max_cost].  Returns (scores (Bq,P), set_id (Bq,)).
    """
    Bq = q.shape[0]
    slo = jnp.broadcast_to(jnp.asarray(slo, jnp.float32).reshape(-1, 2), (Bq, 2))
    # (Bq, N): the same GEMM as retrieval_topk_ref
    tsims = jnp.matmul(q, train.T, precision=SELECT_PRECISION)
    k = min(knn, train.shape[0])
    vals, idx = jax.lax.top_k(tsims, k)  # stable: lowest index first on ties
    return dsqe_score_from_topk(q, vals, idx, protos, path_weights, contains,
                                lat, cost, prior, valid, slo)
