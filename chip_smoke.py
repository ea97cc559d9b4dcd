"""Chip smoke test: the ECO-LLM serving path on one TPU, end to end.

    python chip_smoke.py

Runs in one process on the first TPU device and refuses any other platform.
Phases, in order; any failure raises and exits non-zero:

1. serve — ``build_server("automotive", n_queries=1000, use_kernel=True)``
   (emulator exploration, CCA labels, DSQE training on the device), then
   every held-out query is served twice: through the async ``Orchestrator``
   and through ``handle_batch``.  Every request must be served, none shed
   or failed, and the fused pass traced at most once per admission bucket.
2. fused — the fused selection pass, compiled for one admission bucket,
   must hold the Pallas retrieve kernel (``tpu_custom_call``), not its XLA
   reference.
3. parity — the fused engine's decisions on the held-out batch against the
   numpy oracle (the same selector inputs, ``use_kernel=False``).  Path and
   critical-set id must agree, except where the two candidates' scores are
   within ``TIE_RTOL`` of each other; each such tie is printed.
4. retrieval — ``retrieval_topk`` over a seeded 100,352 x 256 float32
   corpus (196 streamed tiles of 512 rows) for 128 queries, k=16, against a
   float64 numpy top-k.  Ids must agree except at near-ties (score gap
   below ``NEAR_TIE``), which are counted.

With ``--trace-dir DIR`` the held-out queries are served once more through
the ``Orchestrator`` under a profiler session written to ``DIR``: the
serving path's ``eco.*`` spans on the device operations' clock (the
recording behind ``tests/data/eco_spans.tpu.xplane.pb``).

The last line of stdout is ``{"ok": true, "device": {...}}``, printed only
when every phase passed.  Wall times printed are smoke wall times on the
host clock, compilation included, not device metrics.
"""
from __future__ import annotations

import argparse
import asyncio
import functools
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs nowhere
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.rps import RuntimePathSelector, bucket_batch  # noqa: E402
from repro.core.slo import SLO  # noqa: E402
from repro.kernels.retrieval_topk.ops import retrieval_topk  # noqa: E402
from repro.launch.serve import (build_server, drive_async,  # noqa: E402
                                enable_compile_cache)
from repro.runtime.server import Request  # noqa: E402

DOMAIN = "automotive"  # the paper's smart-car assistant
N_QUERIES = 1000
MAX_BATCH = 32
# per-request SLOs, cycled: unconstrained, two real budgets, and an
# impossible one that forces the host fallback
SLOS = (SLO(),
        SLO(max_latency_s=4.0, max_cost_usd=0.01),
        SLO(max_latency_s=2.0, max_cost_usd=0.004),
        SLO(max_latency_s=1e-6, max_cost_usd=0.0))
TIE_RTOL = 1e-6  # parity: candidates this close may resolve either way
NEAR_TIE = 1e-5  # retrieval: rank neighbours this close may swap
KERNEL_SHAPE = (128, 100_352, 256, 16)  # (Bq, N, d, k)
TRACE_RATE_QPS = 2000.0  # open-loop rate of the traced serve: many buckets


def _rel_close(a: float, b: float, rtol: float = TIE_RTOL) -> bool:
    return abs(a - b) <= rtol * max(abs(a), abs(b))


def _check_served(mode: str, reqs, served, shed: int, failed: int) -> None:
    acc = np.mean([r.accuracy for r in served])
    ttft = np.mean([r.latency_s for r in served])
    cost = np.mean([r.cost_usd for r in served])
    print(f"serve[{mode}]: offered {len(reqs)} served {len(served)} "
          f"shed {shed} failed {failed} | accuracy {acc:.4f} "
          f"emulated TTFT {ttft:.4f} s, cost ${cost * 1e3:.4f}/1k queries")
    assert len(served) == len(reqs) and shed == 0 and failed == 0, mode


def serve_phase(n_queries: int = N_QUERIES):
    """Build the automotive server on the fused engine and serve every
    held-out query through both entry points.  Returns (server, requests)."""
    server, test_idx = build_server(DOMAIN, n_queries=n_queries, budget=4.0,
                                    use_kernel=True)
    rps = server.rps
    print(f"built {DOMAIN}: {len(rps.table.query_ids)} training rows, "
          f"P={len(rps.table.paths)} paths, K={len(rps.cca.set_vocab)} sets, "
          f"{len(test_idx)} held-out queries")
    reqs = [Request(prompt="", qid=int(q), slo=SLOS[i % len(SLOS)])
            for i, q in enumerate(test_idx)]
    served, shed, stats = asyncio.run(
        drive_async(server, reqs, max_batch=MAX_BATCH))
    _check_served("async", reqs, served, shed, stats["failed"])
    print(f"  admission buckets {stats['batches']}, "
          f"mean size {stats['dispatched'] / stats['batches']:.2f}")
    _check_served("batch", reqs, server.handle_batch(reqs), 0, 0)
    buckets = ({bucket_batch(b) for b in range(1, MAX_BATCH + 1)}
               | {bucket_batch(len(reqs))})
    print(f"  fused traces {rps.kernel_trace_count}, bucket shapes possible "
          f"{sorted(buckets)}")
    assert 1 <= rps.kernel_trace_count <= len(buckets)
    return server, reqs


def trace_phase(server, reqs, log_dir: str) -> None:
    """Serve ``reqs`` through the orchestrator at ``TRACE_RATE_QPS`` under a
    profiler session written to ``log_dir`` (no Python call events)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(log_dir, profiler_options=opts):
        served, shed, stats = asyncio.run(drive_async(
            server, reqs, max_batch=MAX_BATCH, rate_qps=TRACE_RATE_QPS))
    _check_served("trace", reqs, served, shed, stats["failed"])
    print(f"  trace written under {log_dir} (orchestrator totals: "
          f"{stats['batches']} buckets, {stats['select_passes']} passes)")


def fused_kernel_phase(server) -> None:
    """The fused pass of one admission bucket, compiled for this device,
    must run the retrieve stage as the Pallas kernel."""
    state, fused = server.rps._ensure_kernel()
    d_in = server.domain.query_embeddings.shape[1]
    embs = jax.ShapeDtypeStruct((MAX_BATCH, d_in), jnp.float32)
    slo = jax.ShapeDtypeStruct((MAX_BATCH, 2), jnp.float32)
    hlo = fused.lower(state, embs, slo).compile().as_text()
    n = hlo.count("tpu_custom_call")
    print(f"fused pass [bucket {MAX_BATCH}]: {n} tpu_custom_call")
    assert n > 0, "retrieve stage did not lower to the Pallas kernel"


def parity_phase(server, reqs) -> dict:
    """Fused-engine decisions vs the numpy oracle over the same selector
    inputs.  Returns the counts it printed."""
    rps, dom = server.rps, server.domain
    oracle = RuntimePathSelector(
        rps.space, rps.dsqe, rps.cca, rps.table,
        dom.query_embeddings[rps.table.query_ids], lam=rps.lam, knn=rps.knn)
    embs = dom.query_embeddings[[r.qid for r in reqs]]
    slos = [r.slo for r in reqs]
    fused = rps.select_batch(embs, slos)
    ref = oracle.select_batch(embs, slos)
    # the oracle's float64 evidence for judging a disagreement
    scores, _ = oracle._score_batch_numpy(
        embs, np.array([s.max_latency_s for s in slos]),
        np.array([s.max_cost_usd for s in slos]), oracle._ver)
    z = np.asarray(rps.dsqe.project(jnp.asarray(embs)), np.float64)
    protos = np.asarray(rps.dsqe.params["protos"], np.float64)
    psims = z @ (protos / np.linalg.norm(protos, axis=1, keepdims=True)).T
    sims = z @ np.asarray(oracle.train_emb_proj, np.float64).T
    k = min(rps.knn, sims.shape[1])
    ties = wrong = 0
    for b, (f, r) in enumerate(zip(fused, ref)):
        if (f.path.key, f.set_id, f.used_fallback) == \
                (r.path.key, r.set_id, r.used_fallback):
            continue
        if f.set_id != r.set_id:
            kind, a, c = "set", psims[b, f.set_id], psims[b, r.set_id]
        elif f.used_fallback == r.used_fallback and not f.used_fallback:
            top = np.sort(sims[b])[::-1]
            jf = rps._path_index[f.path]
            jr = rps._path_index[r.path]
            kind, a, c = "path", scores[b, jf], scores[b, jr]
            if not _rel_close(a, c) and k < len(top) and \
                    _rel_close(top[k - 1], top[k]):
                kind, a, c = "knn boundary", top[k - 1], top[k]
        else:
            kind, a, c = "fallback", np.nan, np.nan
        tie = kind != "fallback" and _rel_close(a, c)
        ties += tie
        wrong += not tie
        print(f"  parity row {b}: {kind} {'tie' if tie else 'MISMATCH'} "
              f"fused=({f.path.key}, set {f.set_id}) "
              f"oracle=({r.path.key}, set {r.set_id}) scores {a!r} vs {c!r}")
    n_fb = sum(d.used_fallback for d in ref)
    print(f"parity: {len(reqs)} rows ({n_fb} fallback), "
          f"{ties + wrong} disagreements: {ties} within {TIE_RTOL:g} ties, "
          f"{wrong} mismatches")
    assert wrong == 0, f"{wrong} fused-vs-numpy mismatches"
    return {"rows": len(reqs), "ties": ties, "mismatches": wrong}


def retrieval_phase(shape=KERNEL_SHAPE, seed: int = 0) -> dict:
    """``retrieval_topk`` on a seeded unit-norm corpus against a float64
    numpy top-k; on a TPU the compiled program must hold the kernel."""
    bq, n, d, k = shape
    rng = np.random.default_rng(seed)
    corpus = rng.standard_normal((n, d), dtype=np.float32)
    corpus /= np.linalg.norm(corpus, axis=1, keepdims=True)
    q = rng.standard_normal((bq, d), dtype=np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    topk = jax.jit(functools.partial(retrieval_topk, k=k))
    q_dev, c_dev = jnp.asarray(q), jnp.asarray(corpus)
    if jax.devices()[0].platform == "tpu":
        n_cc = topk.lower(q_dev, c_dev).compile().as_text().count(
            "tpu_custom_call")
        assert n_cc > 0, "retrieval_topk did not lower to the Pallas kernel"
    vals, ids = (np.asarray(x) for x in topk(q_dev, c_dev))
    s64 = q.astype(np.float64) @ corpus.astype(np.float64).T
    ref = np.argsort(-s64, axis=1, kind="stable")[:, :k]
    got = np.take_along_axis(s64, ids.astype(np.int64), axis=1)
    want = np.take_along_axis(s64, ref, axis=1)
    off = ids != ref
    near = off & (np.abs(got - want) < NEAR_TIE)
    val_err = float(np.max(np.abs(vals - got)))
    print(f"retrieval [{bq}x{n}x{d}, k={k}, {n // 512} tiles of 512]: "
          f"{int(off.sum())} id disagreements, {int(near.sum())} near-ties "
          f"below {NEAR_TIE:g}, max |score - float64| {val_err:.3g}")
    assert not (off & ~near).any(), "retrieval ids differ beyond near-ties"
    assert all(len(set(row)) == k for row in ids.tolist())
    assert val_err < NEAR_TIE, "retrieval scores drift from float64"
    return {"disagreements": int(off.sum()), "near_ties": int(near.sum()),
            "max_score_err": val_err}


def main(trace_dir: str | None = None) -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (first device is "
              f"{dev.platform!r}); this smoke runs on the chip only",
              file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    server, reqs = serve_phase()
    try:
        if trace_dir:
            trace_phase(server, reqs, trace_dir)
        fused_kernel_phase(server)
        parity_phase(server, reqs)
    finally:
        server.fleet.close()
    retrieval_phase()
    print(f"smoke wall time {time.perf_counter() - t0:.1f} s "
          f"(host clock, compilation included)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace-dir", help="record the traced serve here")
    sys.exit(main(ap.parse_args().trace_dir))
