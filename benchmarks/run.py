"""Benchmark driver: one function per paper table/figure + kernel micro-
benchmarks + the roofline table.  Prints ``name,us_per_call,derived`` CSV
rows (plus the rendered tables) so results are both human- and machine-
readable.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run table4 slo # subset
"""
from __future__ import annotations

import sys
import time

import numpy as np

CSV: list[tuple[str, float, str]] = []


def _csv(name: str, us: float, derived: str) -> None:
    CSV.append((name, us, derived))


def bench_table3() -> None:
    from benchmarks import table3_hardware as t3

    t0 = time.time()
    res = t3.run()
    print("\n=== Table 3: hardware platforms (acc% / $/1k / s (sel ms)) ===")
    print(t3.render(res))
    m4 = res[("automotive", "m4")]
    _csv("table3_hardware", (time.time() - t0) * 1e6,
         f"m4_auto_ecoL_latency_s={m4['eco_l'].latency_s:.2f};"
         f"orin_auto_ecoL_latency_s={res[('automotive','orin')]['eco_l'].latency_s:.2f}")


def bench_table4() -> None:
    from benchmarks import table4_domains as t4

    t0 = time.time()
    res = t4.run()
    print("\n=== Table 4: five domains on M4 (acc% / $/1k / s (sel ms)) ===")
    print(t4.render(res))
    s = t4.summarize(res)
    print(f"summary: {s}")
    _csv("table4_domains", (time.time() - t0) * 1e6,
         f"cost_reduction_vs_r75={s['cost_reduction_vs_r75']:.2f};"
         f"latency_speedup_vs_r75={s['latency_speedup_vs_r75']:.1f}x;"
         f"eco_acc={s['eco_acc_range'][0]*100:.0f}-{s['eco_acc_range'][1]*100:.0f};"
         f"routellm_acc={s['routellm_acc_range'][0]*100:.0f}-{s['routellm_acc_range'][1]*100:.0f}")


def bench_table5() -> None:
    from benchmarks import table5_ablation as t5

    t0 = time.time()
    res = t5.run()
    print("\n=== Table 5: ablation — static / CCA-only / full ECO-LLM ===")
    print(t5.render(res))
    avg_static_lat = np.mean([res[d]["static_cost"].latency_s for d in res])
    avg_eco_lat = np.mean([res[d]["eco_cost"].latency_s for d in res])
    _csv("table5_ablation", (time.time() - t0) * 1e6,
         f"costfirst_latency_static={avg_static_lat:.2f}s_eco={avg_eco_lat:.2f}s")


def bench_table6() -> None:
    from benchmarks import table6_budget as t6

    t0 = time.time()
    res = t6.run()
    print("\n=== Table 6: SBA budget efficiency (delta pts vs full, % explored) ===")
    print(t6.render(res))
    worst = min(min(v["delta_pts"] for v in row.values()) for row in res.values())
    _csv("table6_budget", (time.time() - t0) * 1e6, f"worst_delta_pts={worst:.1f}")


def bench_fig4() -> None:
    from benchmarks import fig4_slo as f4

    t0 = time.time()
    res = f4.run()
    print("\n=== Figure 4: SLO attainment ===")
    print(f4.render(res))
    relaxed = np.mean([row["latency"][-1]["violation_rate"] for row in res.values()])
    _csv("fig4_slo", (time.time() - t0) * 1e6, f"relaxed_latency_violation={relaxed:.3f}")


def bench_batch() -> None:
    from benchmarks import batch_speedup as bs

    t0 = time.time()
    rows = bs.run()
    print("\n=== Batch engine: scalar vs vectorized emulator ===")
    print(bs.render(rows))
    best = max(rows, key=lambda r: r.speedup)
    _csv("batch_speedup", (time.time() - t0) * 1e6,
         f"best_speedup={best.speedup:.1f}x;prefix_hit_rate={best.hit_rate:.2f};"
         f"exact={all(r.exact_match for r in rows)}")


def bench_select() -> None:
    from benchmarks import select_batch_speedup as sb

    t0 = time.time()
    r = sb.run()
    print("\n=== Select: per-query / numpy batch / fused kernel ===")
    print(sb.render(r))
    _csv("select_batch_speedup", (time.time() - t0) * 1e6,
         f"vs_select={r.speedup_vs_select:.1f}x;vs_batch={r.speedup_vs_batch:.2f}x;"
         f"backend={r.backend};parity={r.decisions_match};"
         f"fallbacks={r.fallback_rows}")


def bench_retrieval() -> None:
    from benchmarks import retrieval_batch_speedup as rb

    t0 = time.time()
    r = rb.run()
    print("\n=== Retrieval: per-query search / batched GEMM / device kernel ===")
    print(rb.render(r))
    _csv("retrieval_batch_speedup", (time.time() - t0) * 1e6,
         f"batch={r.speedup_batch:.2f}x;kernel={r.speedup_kernel:.2f}x;"
         f"ivf={r.ivf_speedup:.2f}x;emu={r.emu_speedup:.2f}x;"
         f"parity={r.parity_exact and r.parity_ivf and r.emu_exact and r.kernel_ids_match};"
         f"backend={r.backend}")


def bench_fleet() -> None:
    from benchmarks import fleet_throughput as ft

    t0 = time.time()
    r = ft.run()
    print("\n=== Fleet: sequential vs concurrent submit_many ===")
    print(ft.render(r))
    _csv("fleet_throughput", (time.time() - t0) * 1e6,
         f"speedup={r.speedup:.1f}x;hedges={r.hedges};lost={r.lost};"
         f"dup={r.duplicated};counters_exact={r.counters_exact}")


def bench_multitenant() -> None:
    from benchmarks import multitenant_serving as mt

    t0 = time.time()
    r = mt.run()
    print("\n=== Multi-tenant: sharded admission over a shared fleet ===")
    print(mt.render(r))
    _csv("multitenant_serving", (time.time() - t0) * 1e6,
         f"victim_p99_ratio={r.victim_p99_ratio:.2f}x;"
         f"attacker_shed={r.attacker_shed};"
         f"traces={r.fused_traces}/{r.distinct_buckets};"
         f"parity={r.parity_ok};accounting={r.accounting_exact};"
         f"thpt_4sh={r.thpt_qps_by_shards.get(4, 0.0):.0f}qps")


def bench_drift() -> None:
    from benchmarks import drift_adaptation as da

    t0 = time.time()
    r = da.run()
    print("\n=== Drift: adaptive vs frozen tables under a mid-run shift ===")
    print(da.render(r))
    _csv("drift_adaptation", (time.time() - t0) * 1e6,
         f"swaps={r.swaps};tail_slo_adaptive={r.adaptive_slo[1]:.2f};"
         f"tail_slo_frozen={r.frozen_slo[1]:.2f};"
         f"recovered_waves={r.waves_to_recover};"
         f"overhead={r.overhead_ratio:.2f}x;"
         f"traces={max(r.fused_traces_frozen, r.fused_traces_adaptive)}"
         f"/{r.distinct_buckets}")


def bench_placement() -> None:
    from benchmarks import placement_pipeline as pp

    t0 = time.time()
    r = pp.run(smoke=True)  # decision/parity gates; full sweep is nightly
    print("\n=== Placement: pipelined edge-cloud stage splits vs monolithic ===")
    print(pp.render(r))
    _csv("placement_pipeline", (time.time() - t0) * 1e6,
         f"plans={r.n_plans};sim_parity={r.sim_parity_ok};"
         f"win={r.win_pipelined_s:.2f}s_vs_{r.win_monolithic_s}s;"
         f"monotonic={r.monotonic_ok}")


def bench_roofline() -> None:
    from benchmarks import roofline as rl
    from repro.perf.roofline import render

    t0 = time.time()
    rows = rl.run()
    print("\n=== Roofline: per-cell terms (single pod, 256 chips) ===")
    print(render(rows))
    _csv("roofline_cells", (time.time() - t0) * 1e6, f"cells={len(rows)}")


def bench_kernels() -> None:
    """Microbenchmarks of the hot-path implementations (CPU wall-clock for
    the XLA paths; Pallas kernels are TPU-target and validated in tests)."""
    import jax
    import jax.numpy as jnp

    from repro.models.layers import flash_attention_xla

    q = jax.random.normal(jax.random.key(0), (1, 1024, 8, 64), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (1, 1024, 4, 64), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (1, 1024, 4, 64), jnp.float32)
    f = jax.jit(lambda q, k, v: flash_attention_xla(q, k, v, q_chunk=256, kv_chunk=256))
    f(q, k, v)[0].block_until_ready()
    t0 = time.perf_counter()
    n = 5
    for _ in range(n):
        f(q, k, v).block_until_ready()
    us = (time.perf_counter() - t0) / n * 1e6
    flops = 4 * 1024 * 1024 * 8 * 64
    _csv("flash_attention_xla_1k", us, f"gflops_s={flops/us/1e3:.1f}")

    # RPS selection end-to-end (the paper's 30-50ms hot path)
    from benchmarks.common import build_rps, deploy
    from repro.core.slo import SLO

    dep = deploy("agriculture", "m4")
    rps = build_rps(dep, lam=0)
    slo = SLO(max_latency_s=5.0, max_cost_usd=0.01)
    emb = dep.domain.query_embeddings[dep.test_idx[0]]
    rps.select(emb, slo)
    t0 = time.perf_counter()
    for qid in dep.test_idx[:20]:
        rps.select(dep.domain.query_embeddings[qid], slo)
    us = (time.perf_counter() - t0) / 20 * 1e6
    _csv("rps_select", us, f"paths={len(dep.space)}")


BENCHES = {
    "batch": bench_batch,
    "retrieval": bench_retrieval,
    "select": bench_select,
    "multitenant": bench_multitenant,
    "drift": bench_drift,
    "placement": bench_placement,
    "fleet": bench_fleet,
    "kernels": bench_kernels,
    "table3": bench_table3,
    "table4": bench_table4,
    "table5": bench_table5,
    "table6": bench_table6,
    "slo": bench_fig4,
    "roofline": bench_roofline,
}


def main() -> None:
    sel = sys.argv[1:] or list(BENCHES)
    for name in sel:
        BENCHES[name]()
    print("\nname,us_per_call,derived")
    for name, us, derived in CSV:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
