"""Composable init/apply device stages for the selection pipeline.

The stax/NuX ``serial`` idiom applied to serving: a :class:`Stage` is a
named ``init`` thunk; calling ``init()`` returns ``(state, apply)`` where

* **state** is the stage's device-resident capture — corpus embeddings,
  DSQE parameters, path tables — materialized as jax arrays exactly once,
  at init time.  State is *threaded as an argument* into ``apply`` (never
  closed over), so a composed program can donate or shard it and the same
  ``apply`` can serve several table versions without retracing.
* **apply(state, carry) -> carry** is pure and jittable: no host callbacks,
  no Python side effects, no data-dependent shapes.  ``carry`` is a flat
  ``dict`` pytree of batch-major arrays; a stage reads the keys it needs
  and returns a NEW dict with its outputs added (inputs are never mutated
  — donation-safe).  Because every stage obeys this contract,
  ``jit(serial(...).apply)`` compiles the whole
  ``embed -> retrieve -> score -> argmax`` chain into ONE device program
  per shape bucket with no host hops between stages.

Carry keys used by the selection stages (one query batch, row-aligned):

  ``emb`` (B, d_in) raw embeddings -> [dsqe projection stage, core/dsqe.py]
  -> ``z`` (B, d) unit-norm -> [:func:`retrieve_stage`] -> ``topk_vals`` /
  ``topk_ids`` (B, k) -> [:func:`score_stage`, + ``slo`` (B, 2)] ->
  ``scores`` (B, P) masked / ``set_id`` (B,) -> [:func:`decode_stage`] ->
  ``best`` (B,) / ``feasible`` (B,).

The domain-sharded variants (:func:`shard_projection_stage`,
:func:`shard_retrieve_stage`, :func:`shard_score_stage`) serve a
multi-domain server from ONE jitted program: every table gains a leading
domain axis (padded to the per-shard maxima with validity masks) and the
carry gains a SCALAR ``domain_id`` (int32, one admission bucket = one
domain) that gathers the shard's row of each table inside the program.
Because ``domain_id`` is a traced argument — never a static one — switching
tenants/domains re-runs the SAME compiled program; the trace count stays
bounded by batch shape buckets exactly as in the single-domain path.

Padding/masking rules at stage boundaries (the ``kernels/common.py``
contract): every batch row of the carry is either real or a pad row that
the DRIVER (not the stages) appends and slices off; stages must be
row-independent so pad rows cannot influence real rows.  Within a stage,
zero-fill of padded table rows/lanes is legal only where a mask or slice
removes them before any score comparison; anywhere a padded candidate
could reach a top-k/argmax, the fill must be losing (``NEG_INF``).  The
retrieve and score stages inherit this from the ops they wrap
(``retrieval_topk`` masks padded corpus rows in-kernel; ``dsqe_score``
pads SLO rows with ``-inf`` so a pad row admits nothing).

On CPU/GPU each wrapped op dispatches its XLA ref, so the composed program
is pure XLA; on TPU the retrieve stage lowers to the compiled Pallas
streaming top-k and the score stage's dense vote scatter stays XLA (it is
a handful of one-hot contractions — MXU-friendly as-is).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.common import NEG_INF, SELECT_PRECISION
from repro.kernels.dsqe_score.ref import dsqe_score_from_topk
from repro.kernels.retrieval_topk.ops import retrieval_topk

Carry = dict
InitFn = Callable[[], tuple[Any, Callable[[Any, Carry], Carry]]]


class Stage(NamedTuple):
    """A named ``init() -> (state, apply)`` pair (see module docstring)."""
    name: str
    init: InitFn


def serial(*stages: Stage) -> Stage:
    """Compose stages left-to-right into one Stage.

    ``init()`` runs every child init and returns the tuple of child states;
    the composed ``apply`` threads the carry through the child applies in
    order.  Composition is associative — ``serial`` of ``serial``s flattens
    semantically — and the result is itself a Stage, so partial pipelines
    compose further.
    """
    def init():
        pairs = [s.init() for s in stages]
        states = tuple(st for st, _ in pairs)
        applies = tuple(ap for _, ap in pairs)

        def apply(state, carry: Carry) -> Carry:
            for ap, st in zip(applies, state):
                carry = ap(st, carry)
            return carry

        return states, apply

    return Stage("serial(" + ",".join(s.name for s in stages) + ")", init)


def retrieve_stage(corpus, *, k: int, query_key: str = "z",
                   out_vals: str = "topk_vals", out_ids: str = "topk_ids",
                   interpret: bool | None = None) -> Stage:
    """Top-k similarity search of ``carry[query_key]`` against ``corpus``.

    State: the (n, d) corpus, device-resident float32.  Adds descending
    ``out_vals``/``out_ids`` (B, k) to the carry; exact score ties admit the
    lowest corpus id (the ``retrieval_topk`` contract).
    """
    k = min(k, corpus.shape[0])

    def init():
        state = jnp.asarray(corpus, jnp.float32)

        def apply(corpus_dev, carry: Carry) -> Carry:
            vals, ids = retrieval_topk(carry[query_key], corpus_dev, k=k,
                                       interpret=interpret)
            return {**carry, out_vals: vals, out_ids: ids}

        return state, apply

    return Stage(f"retrieve[k={k}]", init)


def score_stage(protos, path_weights, contains, lat, cost, prior, valid, *,
                query_key: str = "z", slo_key: str = "slo") -> Stage:
    """Algorithm-3 path scoring from the retrieve stage's top-k.

    State: the seven selection tables, device-resident float32.  Consumes
    ``carry[query_key]`` (for the prototype argmax), ``topk_vals``/
    ``topk_ids`` and the per-row (B, 2) ``carry[slo_key]``; adds masked
    ``scores`` (B, P) and ``set_id`` (B,).  Infeasible entries are NEG_INF,
    never 0 — a later argmax must see them lose.
    """
    def init():
        state = tuple(jnp.asarray(t, jnp.float32) for t in (
            protos, path_weights, contains, lat, cost, prior, valid))

        def apply(tables, carry: Carry) -> Carry:
            scores, set_id = dsqe_score_from_topk(
                carry[query_key], carry["topk_vals"], carry["topk_ids"],
                *tables, carry[slo_key])
            return {**carry, "scores": scores, "set_id": set_id}

        return state, apply

    return Stage("score", init)


def shard_projection_stage(layers, *, in_key: str = "emb",
                           out_key: str = "z",
                           id_key: str = "domain_id") -> Stage:
    """DSQE projection over stacked per-domain parameter shards.

    ``layers`` is a list of ``{"w": (D, d_i, d_o), "b": (D, d_o)}`` dicts —
    each domain's trained projection stacked on a leading domain axis (all
    domains share the DSQE topology, so shapes agree without padding).  The
    scalar ``carry[id_key]`` gathers the shard's matrices inside the traced
    program; the math then mirrors ``core/dsqe.project`` exactly (ReLU
    between layers, unit-norm output with the 1e-6 floor), so a shard row
    produces the same floats its domain's single-domain stage would.
    """
    def init():
        state = tuple((jnp.asarray(l["w"], jnp.float32),
                       jnp.asarray(l["b"], jnp.float32)) for l in layers)

        def apply(params_dev, carry: Carry) -> Carry:
            did = carry[id_key]
            x = carry[in_key]
            n = len(params_dev)
            for i, (w, b) in enumerate(params_dev):
                x = jnp.matmul(x, w[did], precision=SELECT_PRECISION) + b[did]
                if i < n - 1:
                    x = jax.nn.relu(x)
            z = x / jnp.maximum(
                jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-6)
            return {**carry, out_key: z}

        return state, apply

    return Stage("dsqe_project_shards", init)


def shard_retrieve_stage(corpora, corpus_valid, *, k: int,
                         query_key: str = "z", id_key: str = "domain_id",
                         out_vals: str = "topk_vals",
                         out_ids: str = "topk_ids") -> Stage:
    """Top-k similarity search against the ``carry[id_key]`` corpus shard.

    State: ``corpora`` (D, N_max, d) per-domain training embeddings padded
    with zero rows to the fleet-wide ``N_max``, plus ``corpus_valid``
    (D, N_max) row masks.  Pad-row similarities are forced to ``NEG_INF``
    BEFORE the top-k (zero-fill would beat real negative similarities —
    the ``kernels/common.py`` hazard), so a pad row can only be admitted
    once every real row is, and its vote weight ``max(NEG_INF, 0) = 0``
    plus its all-zero ``path_weights`` row contribute nothing downstream —
    decision parity with the per-domain oracle at any k.

    The gathered-shard GEMM is plain XLA (same math as
    ``retrieval_topk_ref``); the Pallas streaming path is single-corpus
    only and stays on the single-domain :func:`retrieve_stage`.
    """
    def init():
        state = (jnp.asarray(corpora, jnp.float32),
                 jnp.asarray(corpus_valid, jnp.float32))

        def apply(state_dev, carry: Carry) -> Carry:
            corpus, valid = state_dev
            did = carry[id_key]
            sims = jnp.matmul(carry[query_key], corpus[did].T,
                              precision=SELECT_PRECISION)  # (B, N_max)
            sims = jnp.where(valid[did][None, :] > 0.5, sims, NEG_INF)
            vals, ids = jax.lax.top_k(sims, k)  # stable: lowest index first
            return {**carry, out_vals: vals, out_ids: ids.astype(jnp.int32)}

        return state, apply

    return Stage(f"retrieve_shards[k={k}]", init)


def shard_score_stage(protos, proto_valid, path_weights, contains, lat, cost,
                      prior, valid, *, query_key: str = "z",
                      slo_key: str = "slo",
                      id_key: str = "domain_id") -> Stage:
    """Algorithm-3 scoring over the ``carry[id_key]`` table shard.

    State: the selection tables with a leading domain axis — ``protos``
    (D, K_max, d) padded with zero prototypes masked by ``proto_valid``
    (D, K_max), ``path_weights`` (D, N_max, P), ``contains`` (D, K_max, P),
    and (D, P) ``lat``/``cost``/``prior``/``valid``.  The gathered shard
    row feeds the SAME ``dsqe_score_from_topk`` as the single-domain stage;
    ``proto_valid`` keeps padded prototypes out of the critical-set argmax.
    """
    def init():
        state = tuple(jnp.asarray(t, jnp.float32) for t in (
            protos, proto_valid, path_weights, contains, lat, cost, prior,
            valid))

        def apply(tables, carry: Carry) -> Carry:
            pr, pv, pw, ct, la, co, pi, va = tables
            did = carry[id_key]
            scores, set_id = dsqe_score_from_topk(
                carry[query_key], carry["topk_vals"], carry["topk_ids"],
                pr[did], pw[did], ct[did], la[did], co[did], pi[did],
                va[did], carry[slo_key], proto_valid=pv[did])
            return {**carry, "scores": scores, "set_id": set_id}

        return state, apply

    return Stage("score_shards", init)


def decode_stage(floor: float = NEG_INF / 2) -> Stage:
    """Argmax decode: adds ``best`` (B,) int32 and ``feasible`` (B,) bool.

    ``jnp.argmax`` picks the FIRST maximum, matching the host oracle's
    ``np.argmax`` lowest-index tie-break; a row is feasible iff its best
    masked score clears ``floor`` (above-the-mask sentinel threshold).
    Stateless — the fallback for infeasible rows stays on the host.
    """
    def init():
        def apply(_, carry: Carry) -> Carry:
            scores = carry["scores"]
            best = jnp.argmax(scores, axis=1).astype(jnp.int32)
            top = jnp.take_along_axis(scores, best[:, None].astype(jnp.int32),
                                      axis=1)[:, 0]
            return {**carry, "best": best, "feasible": top > floor}

        return None, apply

    return Stage("decode", init)
