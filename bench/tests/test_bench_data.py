"""The benchmark's own data and analysis: embeddings and explored tables
made from the seed, the reference's critical-component analysis against
the program's, and the event-loop stall watcher."""
import asyncio
import time

import numpy as np
import pytest

import _tiny  # noqa: F401  (puts the checkout on the path)
from bench.harness import data, drive, reference, spec
from repro.core.cca import critical_component_analysis
from repro.core.emulator import EvalTable
from repro.core.paths import MODULES, PathSpace

GEN = spec.config("smartcar-100k")["data"]
SPACE = PathSpace()
COMPS = [{m: p.component(m).key for m in MODULES} for p in SPACE.paths]


def table(n, seed):
    emb, topic = data.embeddings(n, 64, GEN, [seed, 0, 0])
    return emb, data.explored_table(topic, COMPS, GEN, [seed, 0, 1])


def test_same_seed_same_data():
    e1, (a1, l1, c1) = table(500, 7)
    e2, (a2, l2, c2) = table(500, 7)
    e3, (a3, _, _) = table(500, 2**34 + 7)
    assert np.array_equal(e1, e2) and e1.dtype == np.float32
    np.testing.assert_allclose(np.linalg.norm(e1, axis=1), 1.0, rtol=1e-5)
    for x, y in ((a1, a2), (l1, l2), (c1, c2)):
        assert np.array_equal(x, y, equal_nan=True)
    assert not np.array_equal(a1, a3, equal_nan=True)


def test_explored_table_shape():
    _, (acc, lat, cost) = table(2000, 3)
    explored = ~np.isnan(acc)
    assert np.array_equal(explored, ~np.isnan(lat))
    assert np.array_equal(explored, ~np.isnan(cost))
    assert explored.any(axis=1).all(), "every row explored some path"
    assert explored.any(axis=0).all(), "every path explored for some row"
    share = explored.mean()
    assert share == pytest.approx(GEN["explored_per_row"] / len(COMPS),
                                  rel=0.1)
    ok = acc[explored]
    assert ok.min() >= 0.0 and ok.max() <= 1.0
    assert (lat[explored] > 0).all() and (cost[explored] >= 0).all()


@pytest.mark.parametrize("lam", [0, 1])
def test_plain_cca_matches_the_programs(lam):
    _, (acc, lat, cost) = table(1500, 11)
    vocab, labels = reference.critical_sets(acc, lat, cost, COMPS, lam,
                                            GEN["cca_tau"])
    prog = critical_component_analysis(
        EvalTable(list(range(len(acc))), list(SPACE.paths), acc, lat, cost,
                  ~np.isnan(acc)), tau=GEN["cca_tau"], lam=lam)
    assert list(prog.set_vocab) == vocab
    assert np.array_equal(prog.set_ids, labels)
    assert np.array_equal(np.asarray(prog.best_path),
                          reference.best_paths(acc, lat, cost, lam))


def test_prototypes_are_unit_set_means():
    emb, _ = table(300, 5)
    labels = np.arange(300) % 7
    p = data.dsqe_params(emb, labels, 7, 32, 2, 2**35 + 1)
    assert [l["w"].shape for l in p["layers"]] == [(64, 32), (32, 32)]
    np.testing.assert_allclose(np.linalg.norm(p["protos"], axis=1), 1.0,
                               rtol=1e-5)
    ref = reference.Reference({
        "layers": [(l["w"], l["b"]) for l in p["layers"]],
        "protos": p["protos"], "accuracy": np.ones((300, 1)),
        "latency": np.ones((300, 1)), "cost": np.ones((300, 1)),
        "lam": 0, "knn": 4, "acc_floor": 0.5, "set_vocab": [()],
        "path_components": [{m: "x" for m in MODULES}],
        "path_keys": ["x"], "log_emb": emb})
    z = ref.project(emb)
    for k in range(7):
        mean = z[labels == k].sum(axis=0)
        np.testing.assert_allclose(p["protos"][k], mean /
                                   np.linalg.norm(mean), atol=1e-5)


def test_loop_watch_finds_a_stall_and_its_stack():
    def hold_the_loop():
        time.sleep(1.2)

    async def main():
        w = drive.LoopWatch()
        w.start()
        await asyncio.sleep(0.1)
        hold_the_loop()
        await asyncio.sleep(0.1)
        await w.stop()
        return w.summary(0.0)

    out = asyncio.run(main())
    assert out["loop_stalls"] == 1
    assert 1.0 < out["loop_stall_max_s"] < 2.0
    stall = out["loop_stall_detail"][0]
    assert any("hold_the_loop" in f for f in stall["threads"]["loop"])
    assert stall["samples"] and stall["wakeups"] == 0
