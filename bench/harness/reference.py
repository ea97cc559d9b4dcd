"""Plain reference of ECO-LLM's runtime decision (paper sec. 3.3.4,
Algorithm 3), in float64 numpy.  It imports nothing of the program and
takes nothing the program made: it reads the benchmark's own data (log
embeddings, explored table and DSQE parameters, ``data.py``) and the path
space as component keys, and derives every table itself, the critical-set
vocabulary by its own Critical Component Analysis (``critical_sets``).

For one query embedding e and SLO (L, C):

1. z = unit(f(e)) with f the DSQE MLP (ReLU between layers); the critical
   set is the prototype of highest cosine.
2. A path is feasible iff its mean latency <= L, its mean cost <= C, it
   holds every component of the critical set, and it was explored for
   some log row (means over explored rows only).
3. Each log row votes for its best path (highest accuracy, within 0.01;
   then lowest cost, or latency when lam = 1; lowest index on ties) with
   weight max(cos(z, z_row), 0) x that path's accuracy on the row; only
   the k most similar rows vote (lowest row index on ties).  A path's
   score is its votes plus 1e-3 x its mean accuracy.
4. The decision is the feasible path of highest score (lowest index on
   ties); with none feasible, the fallback: among paths that hold the
   critical set with mean accuracy >= acc_floor (else any with mean
   accuracy >= acc_floor, else any), the cheapest (fastest when lam = 1).
"""
from __future__ import annotations

import warnings

import numpy as np

BLOCK = 128         # query rows per block of the similarity pass
BEST_TOL = 0.01     # accuracy tolerance of a row's best path
PRIOR = 1e-3        # weight of the mean-accuracy prior in a path's score
MODULES = ("qproc", "retrieval", "cproc", "model")


def best_paths(acc: np.ndarray, lat: np.ndarray, cost: np.ndarray,
               lam: int) -> np.ndarray:
    """Each row's best explored path: highest accuracy within ``BEST_TOL``,
    then lowest cost (latency when lam = 1), lowest index on ties."""
    explored = ~np.isnan(acc)
    best_acc = np.max(np.where(explored, acc, -np.inf), axis=1)
    cand = explored & (acc >= best_acc[:, None] - BEST_TOL)
    second = cost if lam == 0 else lat
    return np.argmin(np.where(cand, second, np.inf), axis=1)


def critical_sets(acc: np.ndarray, lat: np.ndarray, cost: np.ndarray,
                  path_components: list[dict], lam: int, tau: float):
    """Critical Component Analysis (paper sec. 3.3.2, Algorithm 2): a row's
    critical set holds each (module, component) of its best path whose
    impact, the mean accuracy over explored paths with that component less
    the mean over explored paths without it, exceeds ``tau``.  Returns the
    sorted vocabulary of distinct sets and each row's index into it."""
    explored = ~np.isnan(acc)
    a0 = np.where(explored, acc, 0.0)
    e0 = explored.astype(np.float64)
    best = best_paths(acc, lat, cost, lam)
    rows = np.arange(len(acc))
    tot_s, tot_n = a0.sum(axis=1), e0.sum(axis=1)
    keys, codes = [], np.zeros(len(acc), np.int64)
    for m in MODULES:
        names = sorted({c[m] for c in path_components})
        comp = np.array([names.index(c[m]) for c in path_components])
        onehot = (comp[:, None] == np.arange(len(names))[None]).astype(
            np.float64)
        v = comp[best]
        w_s, w_n = (a0 @ onehot)[rows, v], (e0 @ onehot)[rows, v]
        o_s, o_n = tot_s - w_s, tot_n - w_n
        ok = (w_n > 0) & (o_n > 0)
        with np.errstate(invalid="ignore", divide="ignore"):
            impact = w_s / w_n - o_s / o_n
        crit = ok & (impact > tau)
        codes = codes * (len(names) + 1) + np.where(crit, v + 1, 0)
        keys.append(names)
    uniq, inv = np.unique(codes, return_inverse=True)
    sets = []
    for code in uniq.tolist():
        parts = []
        for m, names in zip(reversed(MODULES), reversed(keys)):
            code, c = divmod(code, len(names) + 1)
            if c:
                parts.append((m, names[c - 1]))
        sets.append(tuple(reversed(parts)))
    order = sorted(range(len(sets)), key=lambda i: sets[i])
    rank = np.empty(len(sets), np.int64)
    rank[order] = np.arange(len(sets))
    return [sets[i] for i in order], rank[inv]


class Reference:
    def __init__(self, ref: dict):
        f64 = np.float64
        self.layers = [(np.asarray(w, f64), np.asarray(b, f64))
                       for w, b in ref["layers"]]
        protos = np.asarray(ref["protos"], f64)
        self.protos_unit = protos / np.maximum(
            np.linalg.norm(protos, axis=1, keepdims=True), 1e-6)
        acc = np.asarray(ref["accuracy"], f64)
        lat = np.asarray(ref["latency"], f64)
        cost = np.asarray(ref["cost"], f64)
        explored = ~np.isnan(acc)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            self.lat = np.nan_to_num(np.nanmean(lat, axis=0), nan=np.inf)
            self.cost = np.nan_to_num(np.nanmean(cost, axis=0), nan=np.inf)
            self.mean_acc = np.nan_to_num(np.nanmean(acc, axis=0), nan=0.0)
        self.evaluated = explored.any(axis=0)
        self.lam = int(ref["lam"])
        self.knn = int(ref["knn"])
        self.acc_floor = float(ref["acc_floor"])
        self.best_path = best_paths(acc, lat, cost, self.lam)
        rows = np.arange(len(acc))
        self.best_acc = np.nan_to_num(acc[rows, self.best_path])
        comps = ref["path_components"]
        self.contains = np.array(
            [[all(c[m] == key for m, key in req) for c in comps]
             for req in ref["set_vocab"]], bool)          # (K, P)
        self.path_keys = list(ref["path_keys"])
        self.train = self.project(ref["log_emb"])          # (N, d)

    def project(self, e: np.ndarray) -> np.ndarray:
        x = np.asarray(e, np.float64)
        for i, (w, b) in enumerate(self.layers):
            x = x @ w + b
            if i < len(self.layers) - 1:
                x = np.maximum(x, 0.0)
        return x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-6)

    def topk(self, sims: np.ndarray):
        """Per row: the k most similar log rows (descending, lowest index
        on ties) and the gap between the k-th and (k+1)-th similarity."""
        n = sims.shape[1]
        k = min(self.knn, n)
        kth = [n - k - 1, n - k] if n > k else [n - k]
        part = np.partition(sims, kth, axis=1)
        v_k = part[:, n - k]
        gap = v_k - part[:, n - k - 1] if n > k else np.full(len(sims), np.inf)
        ids = np.empty((len(sims), k), np.int64)
        for r, s in enumerate(sims):
            above = np.flatnonzero(s > v_k[r])
            tied = np.flatnonzero(s == v_k[r])[:k - len(above)]
            sel = np.concatenate([above, tied])
            ids[r] = sel[np.lexsort((sel, -s[sel]))]
        return ids, gap

    def score(self, embs: np.ndarray, max_lat: np.ndarray,
              max_cost: np.ndarray, set_ids: np.ndarray | None = None):
        """Masked scores (B, P) (-inf where infeasible), critical sets (B,),
        prototype cosines (B, K) and the kNN boundary gaps (B,).
        ``set_ids`` overrides the critical sets (to score a tie's other
        side)."""
        z = self.project(embs)
        psims = z @ self.protos_unit.T
        sets = np.argmax(psims, axis=1) if set_ids is None else set_ids
        feasible = ((self.lat[None] <= np.asarray(max_lat)[:, None])
                    & (self.cost[None] <= np.asarray(max_cost)[:, None])
                    & self.contains[sets] & self.evaluated[None])
        B, P = len(z), len(self.lat)
        scores = np.zeros((B, P))
        gaps = np.empty(B)
        for lo in range(0, B, BLOCK):
            sims = z[lo:lo + BLOCK] @ self.train.T
            ids, gaps[lo:lo + BLOCK] = self.topk(sims)
            w = np.maximum(np.take_along_axis(sims, ids, axis=1), 0.0)
            contrib = w * self.best_acc[ids]
            rows = np.repeat(np.arange(lo, lo + len(ids)), ids.shape[1])
            np.add.at(scores, (rows, self.best_path[ids].ravel()),
                      contrib.ravel())
        scores += PRIOR * self.mean_acc
        scores[~feasible] = -np.inf
        return scores, sets, psims, gaps

    def fallback(self, set_id: int) -> int:
        mask = self.contains[set_id] & (self.mean_acc >= self.acc_floor)
        if not mask.any():
            mask = self.mean_acc >= self.acc_floor
        if not mask.any():
            mask = np.ones(len(self.mean_acc), bool)
        second = self.lat if self.lam == 1 else self.cost
        cand = np.flatnonzero(mask)
        return int(cand[np.argmin(second[cand])])

    def decide(self, scores: np.ndarray, sets: np.ndarray):
        """(path index, used fallback) per row."""
        best = np.argmax(scores, axis=1)
        out = []
        for r, j in enumerate(best):
            if np.isfinite(scores[r, j]):
                out.append((int(j), False))
            else:
                out.append((self.fallback(int(sets[r])), True))
        return out
