"""Deployment kind ``eco_select``: ECO-LLM's runtime path selection.

Build one configuration's deployment: the query logs and held-out pools,
the explored tables and the DSQE parameters, all made by the benchmark
(``bench/harness/data.py``), and the program's own Runtime over them (CCA,
``RuntimePathSelector(use_kernel=True)``, ``EcoLLMServer``).

The Emulator is ECO-LLM's offline half and exploring a 10^5-row log would
cost minutes in every run, so each domain's explored table is made from
``data_seed`` the way a model benchmark makes weights; so are the query
embeddings, which stand in for an upstream encoder's.  The program's text
generator still makes the queries' text, which the fleet's host emulation
of each path reads.  The Runtime runs unchanged.

A kind module gives the shared harness (``bench/run.py``,
``bench/harness/drive.py``) these functions, and the harness reads nothing
else of a deployment but its ``config`` and its ``server`` (the program's
``EcoLLMServer``, behind the front door ``drive.make_front`` builds):

* ``check_traffic(mix)``: refuse a traffic file whose ``request`` form the
  kind does not serve (before set-up);
* ``check_names()``: the names ``compare`` returns, each a key of the
  configuration's ``check_limits``;
* ``build(cfg, seed)``: the deployment; ``close(dep)``: free it;
* ``warm(dep)``: compile (or load) every program shape the window uses;
* ``pools(dep)``: ``{domain: ids}`` the traffic draws from, in the order
  tenant ``i`` is routed (domain ``i mod D``);
* ``extend(cfg, mix, arrivals, seed)``: the arrivals with the kind's own
  per-request fields, drawn from a generator of its own
  (``np.random.default_rng([seed, <tag>])``), never from the traffic's;
* ``make_request(dep, arrival)``: the program's ``Request``;
* ``install_recorders(dep, seed, spans)``: recorders of what the timed
  path produces (an object whose ``on`` the harness sets for the window),
  and with ``spans`` the benchmark's span wraps of the kind's layers;
* ``counters(dep)``: cumulative counts, read before and after the window,
  whose differences go to ``info``;
* ``compare(dep, res, seed)``: ``{name: (value, limit)}`` after the
  window; ``correct`` is every value at or under its limit; it may set
  ``res["check_counts"]``;
* ``layer_context(dep, res)``: the kind's fields of the per-layer readers'
  context;
* ``info(dep, res)``: the kind's entries of the result's ``info``;
* ``cpu_cut(cfg)``: the configuration cut to a CPU test run (a copy).

A kind may import and extend another (``from bench.kinds import
eco_select``).
"""
from __future__ import annotations

import copy
import threading
from dataclasses import dataclass, field

import numpy as np

from bench.harness import check
from bench.harness import data as data_mod
from bench.harness import reference

# the program's own seeds are 31-bit; the run seed may be larger
PROGRAM_SEED_MOD = 2**31 - 1
REQUESTS = ("qid",)    # request forms served: the query arrives embedded
CHECKS = ("decision_gap", "score_err", "unsettled")
RESERVOIR = 32         # selection passes kept for the stage comparison


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


def check_traffic(mix: dict) -> None:
    form = mix.get("request")
    if form not in REQUESTS:
        raise ValueError(f"traffic request form {form!r} is not served by "
                         f"kind eco_select (it serves {list(REQUESTS)})")


def check_names() -> tuple[str, ...]:
    return CHECKS


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


def close(dep: Deployment) -> None:
    dep.server.fleet.close()


def warm(dep: Deployment) -> None:
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


def pools(dep: Deployment) -> dict[str, np.ndarray]:
    return {d.name: d.pool_qids for d in dep.domains}


def extend(cfg: dict, mix: dict, arrivals: list, seed: int) -> list:
    """A ``qid`` request carries nothing beyond the shared arrival."""
    return arrivals


def make_request(dep: Deployment, a):
    from repro.core.slo import SLO
    from repro.runtime.server import DEFAULT_TENANT, Request

    return Request(prompt="", qid=a.qid,
                   slo=SLO(max_latency_s=a.max_latency_s,
                           max_cost_usd=a.max_cost_usd),
                   tenant=a.tenant or DEFAULT_TENANT,
                   domain=a.domain if dep.multi else None)


@dataclass
class PassRecorder:
    """Wraps the jitted selection pass the window drives and keeps a
    uniform sample (reservoir, from the run seed) of its calls inside the
    window: inputs and outputs exactly as the program passed and got
    them, as device arrays (no copy inside the window)."""

    inner: object
    rng: np.random.Generator
    on: bool = False
    calls: int = 0
    kept: list = field(default_factory=list)
    lock: threading.Lock = field(default_factory=threading.Lock)

    def __call__(self, state, embs, slo, *did):
        out = self.inner(state, embs, slo, *did)
        if self.on:
            with self.lock:
                self.calls += 1
                item = (embs, slo, did[0] if did else None, out)
                if len(self.kept) < RESERVOIR:
                    self.kept.append(item)
                else:
                    j = int(self.rng.integers(self.calls))
                    if j < RESERVOIR:
                        self.kept[j] = item
        return out


def install_recorders(dep: Deployment, seed: int,
                      spans=None) -> PassRecorder:
    """Put a recorder around the selection pass (built and warm); with
    ``spans`` (a ``drive.Spans``), wrap each domain's fleet executor and
    the domain-sharded selector for its spans too."""
    rng = np.random.default_rng([seed, 2])

    def bare(fn):  # a deployment measured again keeps one recorder
        return fn.inner if isinstance(fn, PassRecorder) else fn

    if dep.multi:
        sharded = dep.server.sharded_selector()
        state, jitted, vers = sharded._ensure_kernel()
        rec = PassRecorder(bare(jitted), rng)
        sharded._kernel_state = (state, rec, vers)
    else:
        rps = dep.domains[0].rps
        rps._ensure_kernel()
        rec = PassRecorder(bare(rps._fused_pass), rng)
        rps._fused_pass = rec
    if spans is not None:
        for d in dep.domains:
            spans.wrap_fleet(d.executor)
        if dep.multi:
            spans.wrap_sharded(dep.server.sharded_selector())
    return rec


def counters(dep: Deployment) -> dict[str, int]:
    """Traces of the selection kernel and the fleet's hedged requests."""
    if dep.multi:
        traces = dep.server.sharded_selector().kernel_trace_count
    else:
        traces = dep.domains[0].rps.kernel_trace_count
    return {"kernel_traces_in_window": traces,
            "fleet_hedges": dep.server.fleet.hedge_count}


def served_rows(records) -> list:
    """(domain, qid, max_lat, max_cost, path_key, set_id, fallback) of every
    request the window served."""
    return [(r.arrival.domain, r.arrival.qid, r.arrival.max_latency_s,
             r.arrival.max_cost_usd, *r.decision)
            for r in records if r.outcome == "ok"]


def unsettled(records) -> int:
    """Requests with no response: failed, or still open."""
    return sum(1 for r in records if r.outcome not in ("ok", "shed"))


def compare(dep: Deployment, res: dict, seed: int) -> dict:
    """The numbers that decide ``correct``, each with its limit."""
    limits = dep.config["check_limits"]
    refs = {d.name: reference.Reference(d.ref) for d in dep.domains}
    names = [d.name for d in dep.domains]
    emb = {d.name: d.data.query_embeddings for d in dep.domains}
    passes = [(np.asarray(e), np.asarray(s), None if did is None else
               int(did), tuple(np.asarray(x) for x in out))
              for e, s, did, out in res["recorder"].kept]
    gap, n_dec, skip_dec = check.decision_gap(
        refs, served_rows(res["records"]),
        lambda dom, q: emb[dom][q], seed)
    err, n_rows, skip_rows = check.score_err(refs, passes, names)
    res["check_counts"] = {"decisions": n_dec, "decision_ties": skip_dec,
                           "pass_rows": n_rows, "pass_row_ties": skip_rows,
                           "passes": len(passes)}
    return {"decision_gap": (gap, limits["decision_gap"]),
            "score_err": (err, limits["score_err"]),
            "unsettled": (unsettled(res["records"]), limits["unsettled"])}


def layer_context(dep: Deployment, res: dict) -> dict:
    """``pass_rows``: (real rows, domain) of each selection pass in the
    window; ``shapes``: each domain's log as the retrieve stage is
    called."""
    spans = res["spans"]
    if dep.multi:
        first = dep.domains[0].name
        pass_rows = [(n, first if d == "default" else d)
                     for n, d in spans.pass_rows]
    else:
        pass_rows = [(n, dep.domains[0].name) for _, _, n in spans.select]
    shapes = {}
    n_max = max(d.ref["log_emb"].shape[0] for d in dep.domains)
    for d in dep.domains:
        n = d.ref["log_emb"].shape[0]
        # the corpus as the retrieve stage is called: padded to the sharded
        # maximum, or to the kernel's 512-row tile
        padded = n_max if dep.multi else -(-n // 512) * 512
        shapes[d.name] = {"n_log": n, "n_log_padded": padded,
                          "n_sets": d.ref["protos"].shape[0]}
    return {"shapes": shapes, "pass_rows": pass_rows}


def info(dep: Deployment, res: dict) -> dict:
    return {"selection_passes_in_window": res["recorder"].calls,
            **{f"sets.{d.name}": int(d.ref["protos"].shape[0])
               for d in dep.domains},
            **{f"sets_unmatched.{d.name}": d.ref["sets_unmatched"]
               for d in dep.domains}}


def cpu_cut(cfg: dict) -> dict:
    """A few hundred log rows per domain."""
    cfg = copy.deepcopy(cfg)
    for d in cfg["domains"]:
        d.update(log_rows=600, pool=48)
    return cfg
