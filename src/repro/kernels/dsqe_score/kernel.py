"""Fused Runtime Path Selection Pallas TPU kernel (paper Algorithm 3).

The paper's RPS runs per query in 30-50 ms of host Python.  On a TPU serving
fleet the decision is a few matvecs and a masked reduction; this kernel
fuses them so selection costs microseconds per query batch:

  1. train-query similarities (Bq, d) x (N, d)  -> hard top-k kNN vote
     weights (Eq. 14), accumulated ACROSS train blocks: the grid is
     ``(query blocks, train blocks)`` with the train dimension innermost,
     each (block_n, d) train tile is DMA'd HBM->VMEM by the grid pipeline
     (double-buffered: tile j+1 in flight while j is on the MXU) and a
     per-query running top-k lives in VMEM scratch (the same streaming
     merge as ``retrieval_topk``, so the training table no longer has to
     fit in VMEM whole);
  2. on the LAST train block: prototype similarities (Bq, d) x (K, d) ->
     nearest component set k* (single argmax — the numpy selector's tie
     semantics), vote weights scattered back over N by per-slot one-hot
     adds (slots hold disjoint ids after extract-max, so the adds are
     exact — no float-order divergence vs the ref's einsum), path scores
     votes (Bq, N) @ path one-hot A-weighted (N, P) + the
     1e-3 * path_mean_acc tie-break prior, and the feasibility mask:
     per-query SLO (latency/cost) ∧ critical-set containment row k* ∧
     evaluated-path validity.

Residency bound: ``path_weights`` (N, P) and the (Bq_block, N) vote scatter
stay fully VMEM-resident in the final step (P, K ≲ a few hundred; N up to a
few thousand rows ≈ 2-4 MB) — only the (N, d) train embeddings stream.

Tie semantics: ``jnp.argmax`` picks the first maximum, so exactly-tied
prototype similarities resolve to the lowest set id (matching the numpy
selector's ``np.argmax``) and exactly-tied train similarities at the
k-boundary admit the lowest-index training row — identical to the ref
oracle (the streaming merge preserves this: see ``retrieval_topk.kernel``).
The numpy selector's ``np.argpartition`` leaves exact k-boundary ties
unspecified instead; see ref.py for the documented divergence caveat.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF
from repro.kernels.retrieval_topk.kernel import topk_merge


def _dsqe_kernel(q_ref, protos_ref, train_ref, pathw_ref, contains_ref,
                 lat_ref, cost_ref, prior_ref, valid_ref, slo_ref,
                 score_ref, set_ref, run_v, run_i, *, knn: int, k_valid: int,
                 n_valid: int, block_n: int, n_blocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():  # fresh query block: reset the running kNN champions
        run_v[...] = jnp.full(run_v.shape, NEG_INF, jnp.float32)
        run_i[...] = jnp.zeros(run_i.shape, jnp.int32)

    q = q_ref[...]  # (Bq, d)
    train = train_ref[...]  # (block_n, d) — streamed tile
    tsims = jax.lax.dot_general(q, train, (((1,), (1,)), ((), ())))
    gid = jax.lax.broadcasted_iota(jnp.int32, tsims.shape, 1) + j * block_n
    tsims = jnp.where(gid < n_valid, tsims, NEG_INF)  # padded rows never vote
    v, i = topk_merge(run_v[...], run_i[...], tsims, gid, knn)
    run_v[...] = v
    run_i[...] = i

    @pl.when(j == n_blocks - 1)
    def _():
        protos = protos_ref[...]  # (K, d)
        pathw = pathw_ref[...]  # (N, P) one-hot(P_q) * A(q, P_q)
        contains = contains_ref[...]  # (K, P) 1.0 if path contains set k
        lat = lat_ref[...]  # (1, P)
        cost = cost_ref[...]  # (1, P)
        prior = prior_ref[...]  # (1, P) tie-break prior (pre-scaled)
        valid = valid_ref[...]  # (1, P) 1.0 for evaluated paths
        slo = slo_ref[...]  # (Bq, 128): [:, 0] max_latency, [:, 1] max_cost

        psims = jax.lax.dot_general(q, protos, (((1,), (1,)), ((), ())))
        k_iota = jax.lax.broadcasted_iota(jnp.int32, psims.shape, 1)
        psims = jnp.where(k_iota < k_valid, psims, NEG_INF)  # pads never win
        set_id = jnp.argmax(psims, axis=1)  # (Bq,) first max wins
        set_onehot = (k_iota == set_id[:, None]).astype(jnp.float32)

        # scatter the k champion votes over N: one one-hot add per slot.
        # Slots carry disjoint ids (extract-max removes each pick), so every
        # vote entry is a single term — exact vs the ref einsum.  Exhausted
        # slots (val == NEG_INF) contribute weight max(NEG_INF, 0) == 0.
        vals, ids = run_v[...], run_i[...]
        w = jnp.maximum(vals, 0.0)  # (Bq, knn)
        n_iota = jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], pathw.shape[0]), 1)
        votes = jnp.zeros((q.shape[0], pathw.shape[0]), jnp.float32)
        for s in range(knn):
            votes = votes + jnp.where(
                n_iota == ids[:, s:s + 1], w[:, s:s + 1], 0.0)
        scores = jax.lax.dot(votes, pathw) + prior  # (Bq, P)

        feas_set = jax.lax.dot(set_onehot, contains)  # (Bq, P) >0 if contained
        feasible = ((feas_set > 0.5) & (valid > 0.5)
                    & (lat <= slo[:, 0:1]) & (cost <= slo[:, 1:2]))
        score_ref[...] = jnp.where(feasible, scores, NEG_INF)
        set_ref[...] = set_id[:, None].astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("knn", "block_q", "block_n", "interpret", "k_valid",
                     "n_valid"))
def dsqe_score_kernel(
    q: jax.Array,  # (Bq, d) projected query embeddings
    protos: jax.Array,  # (K, d)
    train: jax.Array,  # (N, d) projected train embeddings, streamed
    path_weights: jax.Array,  # (N, P)
    contains: jax.Array,  # (K, P) float 0/1
    lat: jax.Array,  # (1, P)
    cost: jax.Array,  # (1, P)
    prior: jax.Array,  # (1, P)
    valid: jax.Array,  # (1, P)
    slo: jax.Array,  # (Bq, 128) per-query [max_latency, max_cost] in lanes 0-1
    *,
    knn: int = 16,
    block_q: int = 128,
    block_n: int = 512,
    interpret: bool = False,
    k_valid: int = 0,
    n_valid: int = 0,
):
    Bq, d = q.shape
    block_q = min(block_q, Bq)
    assert Bq % block_q == 0
    K, N, P = protos.shape[0], train.shape[0], path_weights.shape[1]
    block_n = min(block_n, N)
    assert N % block_n == 0, "train rows must be padded to the block size"
    n_blocks = N // block_n
    kernel = functools.partial(_dsqe_kernel, knn=knn,
                               k_valid=k_valid or K, n_valid=n_valid or N,
                               block_n=block_n, n_blocks=n_blocks)
    return pl.pallas_call(
        kernel,
        grid=(Bq // block_q, n_blocks),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((K, d), lambda i, j: (0, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((N, P), lambda i, j: (0, 0)),
            pl.BlockSpec((K, P), lambda i, j: (0, 0)),
            pl.BlockSpec((1, P), lambda i, j: (0, 0)),
            pl.BlockSpec((1, P), lambda i, j: (0, 0)),
            pl.BlockSpec((1, P), lambda i, j: (0, 0)),
            pl.BlockSpec((1, P), lambda i, j: (0, 0)),
            pl.BlockSpec((block_q, slo.shape[1]), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, P), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bq, P), jnp.float32),
            jax.ShapeDtypeStruct((Bq, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, knn), jnp.float32),  # running kNN vals
            pltpu.VMEM((block_q, knn), jnp.int32),  # running kNN train ids
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, protos, train, path_weights, contains, lat, cost, prior, valid, slo)
