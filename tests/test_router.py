"""Multi-tenant serving plane: router hashing, quotas, DRR fairness, and
per-domain sharded selection.

Pins the tenancy contract from ``repro/runtime/router.py``: deterministic
consistent-hash placement with bounded reshard movement, per-domain sharded
selection parity (fused == staged == each domain's numpy selector, traces
bounded by shape buckets), deficit-round-robin convergence to the weight
ratio at 10:1 skew (without small-bucket starvation), the two isolation
walls (token-bucket quota, per-tenant queue bound) shedding only the
offending tenant, and the merged per-tenant accounting identities.
"""
import asyncio

import numpy as np
import pytest

from repro.core.rps import bucket_batch
from repro.core.slo import SLO
from repro.launch.serve import build_multi_server
from repro.runtime.orchestrator import Overloaded
from repro.runtime.router import (AdmissionShard, HashRing, TenantRouter,
                                  TenantSpec)
from repro.runtime.server import DEFAULT_TENANT, Request

DOMAINS = ["smarthome", "techqa"]


@pytest.fixture(scope="module")
def multi():
    """One 2-domain server shared by every test; tiny build sizes."""
    return build_multi_server(DOMAINS, n_queries=24, budget=2.0, seed=0)


def _same_shard_pair(n_shards: int) -> tuple[str, str]:
    """Two tenant names the ring co-locates (deterministic probe)."""
    ring = HashRing(n_shards)
    a = "tenantA"
    for i in range(10_000):
        b = f"tenantB{i:04d}"
        if ring.lookup(b) == ring.lookup(a):
            return a, b
    raise AssertionError("ring never collided")


# -- consistent hashing ------------------------------------------------------

def test_hash_ring_deterministic_and_bounded_reshard():
    """Placement depends only on (tenant, n_shards); growing the ring moves
    a bounded minority of tenants (consistent-hash property), and every
    tenant that moves lands on the NEW shard."""
    keys = [f"tenant-{i}" for i in range(1000)]
    r4a, r4b, r5 = HashRing(4), HashRing(4), HashRing(5)
    assert [r4a.lookup(k) for k in keys] == [r4b.lookup(k) for k in keys]
    moved = [k for k in keys if r4a.lookup(k) != r5.lookup(k)]
    # ideal movement is 1/5 of keys; vnode variance gives it slack
    assert 0 < len(moved) < 450
    assert all(r5.lookup(k) == 4 for k in moved)


def test_router_places_all_of_a_tenants_traffic_on_one_shard(multi):
    server, tests = multi
    router = TenantRouter(server, [TenantSpec("acme")], n_shards=4)
    idx = router.shard_index("acme")
    assert router.shard_for("acme") is router.shards[idx]
    assert all(router.shard_index("acme") == idx for _ in range(10))


# -- per-domain sharded selection --------------------------------------------

def test_sharded_selection_parity_including_fallback(multi):
    """Fused sharded program == staged pipeline == each domain's own numpy
    selector, decision-for-decision, feasible and infeasible-SLO rows."""
    server, tests = multi
    sh = server.sharded_selector()

    def keyed(d):
        return (d.path.key, d.set_id, d.used_fallback)

    for name, idx in tests.items():
        dom, rps, _ = server.domain_entry(name)
        canon = server.canonical_domain(name)
        embs = dom.query_embeddings[idx]
        for slos in ([SLO()] * len(idx),
                     [SLO(max_latency_s=1e-9, max_cost_usd=1e-12)] * len(idx)):
            base = rps.select_batch(embs, slos)
            fused = sh.select_batch(embs, slos, canon)
            staged = sh.select_batch_staged(embs, slos, canon)
            assert [keyed(d) for d in base] \
                == [keyed(d) for d in fused] \
                == [keyed(d) for d in staged]


def test_sharded_traces_bounded_by_shape_buckets_not_domains(multi):
    """All domains share every jit trace: the domain id is a traced scalar,
    so the trace count tracks distinct batch-shape buckets only."""
    server, tests = multi
    sh = server.sharded_selector()
    t0 = sh.kernel_trace_count
    sizes_by_dom = {name: [3, 5, 7] for name in tests}  # one bucket (8)
    buckets = set()
    for name, sizes in sizes_by_dom.items():
        dom = server.domain_entry(name)[0]
        canon = server.canonical_domain(name)
        base = dom.query_embeddings[tests[name]]
        for B in sizes:
            embs = np.tile(base, (B // len(base) + 1, 1))[:B]
            sh.select_batch(embs, [SLO()] * B, canon)
            buckets.add(bucket_batch(B))
    new = sh.kernel_trace_count - t0
    assert new <= len(buckets), \
        f"{new} new traces for {len(buckets)} shape buckets"


# -- DRR fairness ------------------------------------------------------------

def _preloaded_shard(server, weights, backlog, max_queue=512):
    """An un-started shard with each tenant's queue pre-filled."""
    shard = AdmissionShard(server, shard_id=0, tenant_weights=weights,
                           max_queue=max_queue)

    async def fill():
        for tenant, n in backlog.items():
            for _ in range(n):
                await shard.submit(Request(prompt="", qid=0, tenant=tenant))

    asyncio.run(fill())
    return shard


def test_drr_converges_to_10_to_1_weight_ratio(multi):
    server, _ = multi
    shard = _preloaded_shard(server, {"heavy": 10.0, "light": 1.0},
                             {"heavy": 200, "light": 40})
    served = {"heavy": 0, "light": 0}
    # while BOTH tenants stay backlogged, the served ratio is the weights'
    while shard._tq["light"] and shard._tq["heavy"]:
        for t in shard._drr_take(22):  # >= weight sum: one full rotation
            served[t.request.tenant] += 1
    assert served["light"] > 0
    ratio = served["heavy"] / served["light"]
    assert ratio == pytest.approx(10.0, rel=0.15), served


def test_drr_small_buckets_do_not_starve_light_tenants(multi):
    """A heavy tenant whose quantum alone fills max_batch must not
    monopolise every bucket: the rotation pointer persists across buckets,
    so the light tenant is drained within the first two buckets."""
    server, _ = multi
    shard = _preloaded_shard(server, {"heavy": 10.0, "light": 1.0},
                             {"heavy": 100, "light": 5})
    first = [t.request.tenant for t in shard._drr_take(10)]
    second = [t.request.tenant for t in shard._drr_take(10)]
    assert "light" in first + second, (first, second)


def test_drr_bucket_ordered_by_priority(multi):
    """The formed bucket heads its highest-priority (deadline-class)
    tickets, FIFO within a priority — the fleet fan-out preserves this
    order into the per-replica queues."""
    server, _ = multi
    shard = AdmissionShard(server, shard_id=0, max_queue=64)

    async def fill():
        for prio in (0, 2, 0, 2, 1, 0):
            await shard.submit(Request(prompt="", qid=0, tenant="t"),
                               priority=prio)

    asyncio.run(fill())
    prios = [t.priority for t in shard._drr_take(6)]
    assert prios == sorted(prios, reverse=True)


def test_drr_idle_tenant_banks_no_credit(multi):
    server, _ = multi
    shard = _preloaded_shard(server, {"a": 5.0, "b": 1.0},
                             {"a": 10, "b": 10})
    while any(shard._tq.values()):
        shard._drr_take(8)
    assert all(d == 0.0 for d in shard._deficit.values())


# -- isolation walls ---------------------------------------------------------

def test_quota_sheds_before_the_shard_with_typed_reason(multi):
    server, tests = multi
    qid = int(tests[DOMAINS[0]][0])
    router = TenantRouter(
        server, [TenantSpec("metered", rate_qps=1e-9, burst=2.0,
                            domain=DOMAINS[0])], n_shards=2)

    async def flood():
        return [await router.submit(Request(prompt="", qid=qid,
                                            tenant="metered"))
                for _ in range(10)]

    tickets = asyncio.run(flood())
    shed = [t for t in tickets if t.shed]
    assert len(shed) == 8  # burst of 2 admitted, the rest refused at the door
    results = [t._future.result() for t in shed]
    assert all(isinstance(r, Overloaded) and r.reason == "quota"
               for r in results)
    st = router.stats()["tenants"]["metered"]
    assert st["offered"] == 10 and st["admitted"] == 2 and st["shed"] == 8
    assert st["shed_reasons"] == {"quota": 8}


def test_saturating_tenant_sheds_only_itself(multi):
    """ISSUE satellite: one tenant floods past its own queue bound on the
    SAME shard as a deadline-class tenant; only the flooder sheds
    (queue_full), the deadline tenant's under-quota traffic all serves."""
    server, tests = multi
    victim, flooder = _same_shard_pair(n_shards=2)
    specs = [TenantSpec(victim, slo_class="deadline", domain=DOMAINS[0]),
             TenantSpec(flooder, slo_class="standard", domain=DOMAINS[1])]
    router = TenantRouter(server, specs, n_shards=2, max_batch=8,
                          max_wait_ms=1.0, max_queue=8, hedge=False)
    vic_q = [int(q) for q in tests[DOMAINS[0]][:6]]
    flood_q = [int(tests[DOMAINS[1]][i % len(tests[DOMAINS[1]])])
               for i in range(40)]

    async def main():
        # pre-start floods land in the shard queues un-drained, so the
        # flooder overflows its own bound while the victim's queue is free
        flood = [await router.submit(Request(prompt="", qid=q,
                                             tenant=flooder))
                 for q in flood_q]
        vic = [await router.submit(Request(prompt="", qid=q, tenant=victim))
               for q in vic_q]
        async with router:
            await asyncio.gather(*(t.wait() for t in flood + vic))
        return flood, vic

    flood, vic = asyncio.run(main())
    assert not any(t.shed for t in vic), "victim traffic was shed"
    stats = router.stats()["tenants"]
    vs, fs = stats[victim], stats[flooder]
    assert vs["shed"] == 0 and vs["served"] == len(vic_q)
    assert fs["shed"] == len(flood_q) - 8  # its own max_queue bound
    assert fs["shed_reasons"] == {"queue_full": len(flood_q) - 8}
    for st in (vs, fs):
        assert st["offered"] == st["admitted"] + st["shed"]
        assert st["admitted"] == st["served"] + st["failed"]


# -- router front door -------------------------------------------------------

def test_slo_class_defaults_stamped_on_requests(multi):
    server, tests = multi
    router = TenantRouter(
        server, [TenantSpec("pager", slo_class="deadline",
                            domain=DOMAINS[0])], n_shards=1)
    req = Request(prompt="", qid=int(tests[DOMAINS[0]][0]), tenant="pager")

    async def submit():
        return await router.submit(req)

    t = asyncio.run(submit())
    assert req.slo_class == "deadline"
    assert req.domain == DOMAINS[0]
    assert req.slo == router.classes["deadline"].slo
    assert t.priority == router.classes["deadline"].priority
    assert t.deadline_s == router.classes["deadline"].deadline_s


def test_unknown_slo_class_rejected(multi):
    server, _ = multi
    with pytest.raises(ValueError, match="unknown SLO class"):
        TenantRouter(server, [TenantSpec("x", slo_class="platinum")])


def test_default_tenant_flows_through_router(multi):
    """Requests that never name a tenant ride DEFAULT_TENANT with standard
    class defaults — no spec required."""
    server, tests = multi
    router = TenantRouter(server, [], n_shards=2, max_batch=4,
                          max_wait_ms=1.0, hedge=False)
    qids = [int(q) for q in tests[DOMAINS[0]][:4]]

    async def main():
        async with router:
            ts = [await router.submit(Request(prompt="", qid=q))
                  for q in qids]
            return await asyncio.gather(*(t.wait() for t in ts))

    resps = asyncio.run(main())
    assert all(not isinstance(r, Overloaded) for r in resps)
    st = router.stats()["tenants"][DEFAULT_TENANT]
    assert st["offered"] == st["served"] == len(qids)


def test_system_state_reports_router_and_shard_attribution(multi):
    server, tests = multi
    router = TenantRouter(server, [TenantSpec("acme", domain=DOMAINS[1])],
                          n_shards=2, max_batch=4, max_wait_ms=1.0,
                          hedge=False)
    qids = [int(q) for q in tests[DOMAINS[1]][:5]]
    shard_tag = f"shard{router.shard_index('acme')}"
    # the fleet is shared module-wide: earlier tests' tagged dispatches
    # persist, so attribute by delta
    before = server.system_state()["dispatched_by_shard"].get(shard_tag, 0)

    async def main():
        async with router:
            ts = [await router.submit(Request(prompt="", qid=q,
                                              tenant="acme"))
                  for q in qids]
            await asyncio.gather(*(t.wait() for t in ts))

    asyncio.run(main())
    state = server.system_state()
    rt = state["router"]
    assert rt["n_shards"] == 2
    assert rt["tenants"]["acme"]["served"] == len(qids)
    assert rt["tenants"]["acme"]["shard"] == router.shard_index("acme")
    assert state["dispatched_by_shard"][shard_tag] - before == len(qids)
    # the shards' selection passes fold into the router's and the server's
    shard = rt["shards"][router.shard_index("acme")]
    assert shard["select_passes"] == shard["batches"] >= 2
    assert rt["select_passes"] == sum(r["select_passes"]
                                      for r in rt["shards"])
    assert state["select_passes"] >= rt["select_passes"]


def test_chunk_counters_fold_per_shard_into_router_and_system_state(multi):
    """``chunks_streamed`` and ``chunk_wakes`` land on the tenant's shard
    and fold, summed, into ``TenantRouter.stats()`` and ``system_state()``
    (checked by delta: the server folds whichever router fronts it)."""
    server, tests = multi
    router = TenantRouter(server, [TenantSpec("acme", domain=DOMAINS[1])],
                          n_shards=2, max_batch=4, max_wait_ms=1.0,
                          hedge=False)
    qids = [int(q) for q in tests[DOMAINS[1]][:4]]
    before = server.system_state()

    async def main():
        async with router:
            ts = [await router.submit(Request(prompt="", qid=q,
                                              tenant="acme"))
                  for q in qids]
            read = [c async for c in ts[-1]]  # one consumer, the rest unread
            await asyncio.gather(*(t.wait() for t in ts))
            return ts, read

    ts, read = asyncio.run(main())
    state = server.system_state()
    rt = state["router"]
    home = router.shard_index("acme")
    streamed = sum(t._n_chunks for t in ts)
    assert streamed == 5 * len(qids) and len(read) == 5
    wakes = ts[-1]._n_wakes
    assert wakes >= 1 and all(t._n_wakes == 0 for t in ts[:-1])
    for row in rt["shards"]:
        mine = row["shard_id"] == home
        assert row["chunks_streamed"] == (streamed if mine else 0)
        assert row["chunk_wakes"] == (wakes if mine else 0)
    assert rt["chunks_streamed"] == streamed and rt["chunk_wakes"] == wakes
    assert state["chunks_streamed"] - before["chunks_streamed"] == streamed
    assert state["chunk_wakes"] - before["chunk_wakes"] == wakes


def test_shard_reconfigure_carries_best_per_tenant(multi):
    """Shrinking max_queue keeps each tenant's best (highest-priority,
    earliest) tickets and sheds ONLY that tenant's overflow."""
    server, _ = multi
    shard = AdmissionShard(server, shard_id=0, max_queue=8)

    async def fill():
        out = {"a": [], "b": []}
        for tenant in ("a", "b"):
            for i in range(8):
                out[tenant].append(await shard.submit(
                    Request(prompt="", qid=0, tenant=tenant),
                    priority=i % 2))
        return out

    tickets = asyncio.run(fill())
    shard.reconfigure(max_queue=4)
    for tenant in ("a", "b"):
        kept = [e[2] for e in shard._tq[tenant]]
        assert len(kept) == 4
        assert all(t.priority == 1 for t in kept)  # best survive
        shed = [t for t in tickets[tenant] if t.shed]
        assert len(shed) == 4
        assert all(t.priority == 0 for t in shed)
    st = shard.stats()["tenants"]
    assert st["a"]["shed"] == st["b"]["shed"] == 4
