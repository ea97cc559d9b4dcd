"""Batched retrieval top-k Pallas TPU kernel (compiled block specs).

The emulator's retrieval stage is one similarity GEMM plus a top-k per
query.  The kernel streams the corpus through VMEM instead of requiring it
to fit: the grid is ``(query blocks, corpus blocks)`` with the corpus
dimension innermost, so each ``(block_n, d)`` corpus tile is DMA'd
HBM->VMEM by the Pallas grid pipeline (which double-buffers consecutive
blocks automatically — tile ``j+1`` is in flight while ``j`` is on the MXU)
and a per-query running top-k accumulates in VMEM scratch across corpus
tiles.  The query dimension is parallel; the corpus dimension is a
sequential reduction (``dimension_semantics=("parallel", "arbitrary")``).

Merge step: each tile's ``(block_q, block_n)`` scores are concatenated
behind the running ``(block_q, k)`` champions and ``k`` extract-max steps
rebuild the champions.  ``jnp.argmax`` picks the FIRST maximum, and the
concatenation keeps every tie group in ascending-id order (champions carry
ids from earlier tiles; tile-local iota ascends), so exactly tied scores
admit the LOWEST corpus id — identical to the ref oracle's stable
``lax.top_k`` and to the host ``VectorStore`` composite-key tie-break.

Padded corpus rows are masked to ``NEG_INF`` *before* the merge (global
``iota < n_valid``), never zero-filled into the comparison: a zero-score pad
row would beat every real candidate on an all-negative similarity row (the
pad-fill hazard pinned by ``tests/test_kernels.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF, SELECT_PRECISION


def topk_merge(run_vals, run_ids, scores, ids, k: int):
    """Merge a block of (scores, ids) candidates into the running top-k.

    All inputs are (block_q, ·); returns the new (vals, ids) champions as
    ``k`` extract-max steps over the concatenation.  Champions are placed
    BEFORE the block so that within an exact-score tie group the earliest
    (lowest-id) candidate is found first by ``argmax``.
    """
    cat_v = jnp.concatenate([run_vals, scores], axis=1)
    cat_i = jnp.concatenate([run_ids, ids], axis=1)
    iota = jax.lax.broadcasted_iota(jnp.int32, cat_v.shape, 1)
    vals, picks = [], []
    for _ in range(k):
        a = jnp.argmax(cat_v, axis=1)  # first max -> lowest id on ties
        pick = iota == a[:, None]
        vals.append(jnp.max(cat_v, axis=1))
        picks.append(jnp.sum(jnp.where(pick, cat_i, 0), axis=1))
        cat_v = jnp.where(pick, NEG_INF, cat_v)
    return (jnp.stack(vals, axis=1),
            jnp.stack(picks, axis=1).astype(jnp.int32))


def _topk_kernel(q_ref, corpus_ref, vals_ref, ids_ref, run_v, run_i, *,
                 k: int, n_valid: int, block_n: int, n_blocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():  # fresh query block: reset the champions
        run_v[...] = jnp.full(run_v.shape, NEG_INF, jnp.float32)
        run_i[...] = jnp.zeros(run_i.shape, jnp.int32)

    q = q_ref[...]  # (block_q, d)
    c = corpus_ref[...]  # (block_n, d) — streamed tile
    s = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                            precision=SELECT_PRECISION)  # (block_q, block_n)
    gid = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block_n
    s = jnp.where(gid < n_valid, s, NEG_INF)  # padded corpus rows never win
    v, i = topk_merge(run_v[...], run_i[...], s, gid, k)
    run_v[...] = v
    run_i[...] = i

    @pl.when(j == n_blocks - 1)
    def _():
        vals_ref[...] = run_v[...]
        ids_ref[...] = run_i[...]


@functools.partial(
    jax.jit,
    static_argnames=("k", "block_q", "block_n", "interpret", "n_valid"))
def retrieval_topk_kernel(
    q: jax.Array,  # (Bq, d) query block
    corpus: jax.Array,  # (n, d) chunk embeddings, streamed HBM->VMEM
    *,
    k: int,
    block_q: int = 128,
    block_n: int = 512,
    interpret: bool = False,
    n_valid: int = 0,
):
    Bq, d = q.shape
    block_q = min(block_q, Bq)
    assert Bq % block_q == 0
    n = corpus.shape[0]
    block_n = min(block_n, n)
    assert n % block_n == 0, "corpus rows must be padded to the block size"
    n_blocks = n // block_n
    kernel = functools.partial(_topk_kernel, k=k, n_valid=n_valid or n,
                               block_n=block_n, n_blocks=n_blocks)
    return pl.pallas_call(
        kernel,
        grid=(Bq // block_q, n_blocks),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bq, k), jnp.float32),
            jax.ShapeDtypeStruct((Bq, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),  # running champion vals
            pltpu.VMEM((block_q, k), jnp.int32),  # running champion ids
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, corpus)
