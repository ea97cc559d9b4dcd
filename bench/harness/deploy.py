"""Build one configuration's deployment: the query logs and held-out pools,
the explored tables and the DSQE parameters, all made by the benchmark
(``data.py``), and the program's own Runtime over them (CCA,
``RuntimePathSelector(use_kernel=True)``, ``EcoLLMServer``).

The Emulator is ECO-LLM's offline half and exploring a 10^5-row log would
cost minutes in every run, so each domain's explored table is made from
``data_seed`` the way a model benchmark makes weights; so are the query
embeddings, which stand in for an upstream encoder's.  The program's text
generator still makes the queries' text, which the fleet's host emulation
of each path reads.  The Runtime runs unchanged.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from bench.harness import data as data_mod
from bench.harness import reference

# the program's own seeds are 31-bit; the run seed may be larger
PROGRAM_SEED_MOD = 2**31 - 1


@dataclass
class Domain:
    """One domain as served: the program's objects and the plain arrays
    the reference reads (``ref``)."""

    name: str
    data: object          # repro DomainData
    rps: object           # repro RuntimePathSelector
    executor: object      # repro PipelineExecutor
    log_qids: np.ndarray  # (N,) query ids of the log rows
    pool_qids: np.ndarray  # (pool,) held-out query ids the traffic draws
    ref: dict             # plain inputs of the reference (reference.py)
    parts: tuple = ()     # (space, dsqe, cca, table, log embeddings)


@dataclass
class Deployment:
    config: dict
    server: object
    domains: list[Domain]

    @property
    def multi(self) -> bool:
        return len(self.domains) > 1


def program_protos(prog_vocab: list, vocab: list, protos: np.ndarray):
    """The prototypes in the program's critical-set order (matched by
    content; a set the benchmark's analysis does not have gets a zero
    prototype) and how many sets did not match."""
    pos = {s: i for i, s in enumerate(vocab)}
    out = np.zeros((len(prog_vocab), protos.shape[1]), protos.dtype)
    missing = 0
    for k, s in enumerate(prog_vocab):
        if s in pos:
            out[k] = protos[pos[s]]
        else:
            missing += 1
    return out, missing + len(set(vocab) - set(prog_vocab))


def build_domain(spec: dict, index: int, cfg: dict, seed: int) -> Domain:
    """One domain: its queries, embeddings and explored table (from
    ``data_seed``), the program's CCA over the table, the DSQE parameters
    (from the run seed) and the selector."""
    from repro.core.cca import critical_component_analysis
    from repro.core.devices import EDGE_DEVICES
    from repro.core.domains import build_domain as make_domain
    from repro.core.dsqe import DSQE
    from repro.core.emulator import EvalTable
    from repro.core.paths import MODULES, PathSpace
    from repro.core.pipeline import PipelineExecutor

    data_seed = int(cfg["data_seed"])
    gen = cfg["data"]
    n_log, n_total = spec["log_rows"], spec["log_rows"] + spec["pool"]
    data = make_domain(spec["name"], n_queries=n_total, seed=data_seed)
    emb, topic = data_mod.embeddings(n_total, cfg["d_in"], gen,
                                     [data_seed, index, 0])
    data.query_embeddings = emb
    space = PathSpace()
    if len(space.paths) != cfg["paths"]:
        raise ValueError(f"path space has {len(space.paths)} paths, config "
                         f"says {cfg['paths']}")
    comps = [{m: p.component(m).key for m in MODULES} for p in space.paths]
    log_qids, pool_qids = np.arange(n_log), np.arange(n_log, n_total)
    acc, lat, cost = data_mod.explored_table(topic[:n_log], comps, gen,
                                             [data_seed, index, 1])
    vocab, labels = reference.critical_sets(acc, lat, cost, comps,
                                            cfg["lam"], gen["cca_tau"])
    table = EvalTable([int(q) for q in log_qids], list(space.paths), acc,
                      lat, cost, ~np.isnan(acc))
    cca = critical_component_analysis(table, tau=gen["cca_tau"],
                                      lam=cfg["lam"])
    emb_log = emb[:n_log]
    d = cfg["dsqe"]
    params = data_mod.dsqe_params(emb_log, labels, len(vocab), d["d_hidden"],
                                  d["n_layers"], seed)
    protos, unmatched = program_protos(list(cca.set_vocab), vocab,
                                       params["protos"])
    dsqe = DSQE(params={"layers": params["layers"], "protos": protos},
                n_sets=len(cca.set_vocab))
    ref = {"log_emb": emb_log, "accuracy": acc, "latency": lat, "cost": cost,
           "layers": [(l["w"], l["b"]) for l in params["layers"]],
           "protos": params["protos"], "set_vocab": vocab,
           "path_components": comps, "path_keys": [p.key for p in
                                                   space.paths],
           "knn": cfg["knn"], "lam": cfg["lam"],
           "acc_floor": cfg["acc_floor"], "sets_unmatched": unmatched}
    executor = PipelineExecutor(data, EDGE_DEVICES["m4"],
                                seed=data_seed % PROGRAM_SEED_MOD)
    dom = Domain(spec["name"], data, None, executor, log_qids, pool_qids,
                 ref, (space, dsqe, cca, table, emb_log))
    dom.rps = selector(dom, cfg)
    return dom


def selector(dom: Domain, cfg: dict):
    """A new ``RuntimePathSelector(use_kernel=True)`` over the domain's
    trained parts (its jitted pass is traced on first use)."""
    from repro.core.rps import RuntimePathSelector

    space, dsqe, cca, table, emb_log = dom.parts
    return RuntimePathSelector(space, dsqe, cca, table, emb_log,
                               lam=cfg["lam"], knn=cfg["knn"],
                               acc_floor=cfg["acc_floor"], use_kernel=True)


def build(cfg: dict, seed: int) -> Deployment:
    """Every domain of ``cfg`` and one server over them."""
    return serve([build_domain(spec, i, cfg, seed)
                  for i, spec in enumerate(cfg["domains"])], cfg, seed)


def serve(domains: list[Domain], cfg: dict, seed: int) -> Deployment:
    """One ``EcoLLMServer`` over the domains' selectors: the first domain
    is the server's default, further domains join by name."""
    from repro.runtime.server import EcoLLMServer

    first = domains[0]
    server = EcoLLMServer(first.data, first.rps, first.executor,
                          n_replicas=cfg["serving"]["n_replicas"],
                          seed=seed % PROGRAM_SEED_MOD)
    if len(domains) > 1:
        server.alias_default_domain(first.name)
        for dom in domains[1:]:
            server.add_domain(dom.name, dom.data, dom.rps, dom.executor)
    return Deployment(cfg, server, domains)


def warm_selection(dep: Deployment) -> None:
    """Compile (or load from the cache) the selection pass for each bucket
    shape the admission loop forms, and nothing else."""
    from repro.core.slo import SLO

    for dom in dep.domains:
        emb = dom.data.query_embeddings[dom.pool_qids]
        for b in dep.config["warm_buckets"]:
            rows = emb[np.arange(b) % len(emb)]
            if dep.multi:
                dep.server.sharded_selector().select_batch(
                    rows, [SLO()] * b, dep.server.canonical_domain(dom.name))
            else:
                dom.rps.select_batch(rows, [SLO()] * b)
        if not dep.multi:
            break
