"""The comparison that decides ``correct``.

After the window has closed, the timed path's own outputs are held against
the float64 reference (``reference.py``) of each domain:

* ``decision_gap``: for a sample of the window's served requests (drawn
  from the run seed), the decision each response carries (path, critical
  set, fallback) against the reference's.  Per request it is the largest
  of: the prototype cosine by which the served critical set lies below the
  reference's; the reference score by which the served path lies below the
  reference's best, relative to that best (floored at 1e-3); and 1 where
  one side fell back and the other did not, or the served path is
  infeasible for the reference.
* ``score_err``: for a sample of the window's selection passes (the
  recorder's reservoir), the largest absolute difference between the
  pass's masked scores (B, P) and the reference's, over real rows and
  paths feasible on both sides; 1 where the feasibility masks or the
  critical sets differ.  This covers the retrieve and score stages: a
  wrong neighbour moves a score by its whole vote.
* ``unsettled``: requests due in the window that never settled with a
  response (failed, or still open a minute after the close).  Shed
  requests are refusals, counted against the latency metrics instead.

Rows whose k-th and (k+1)-th reference similarities lie within ``TIE`` of
each other may take either neighbour and are left out of both numbers
(counted); a critical set within ``TIE`` of the reference's is scored as
served.
"""
from __future__ import annotations

import numpy as np

TIE = 1e-6          # similarity gap under which either side may be chosen
NEG_INF = -1e30     # the program's masked-score sentinel
SAMPLE = 512        # served requests compared per run
GAP_FLOOR = 1e-3    # floor of the score a decision gap is relative to


def _decision_gaps(ref, embs, lat, cost, served) -> tuple[np.ndarray, int]:
    """``served`` is a list of (path index, set id, fallback) per row."""
    scores, sets, psims, gaps = ref.score(embs, lat, cost)
    s_prog = np.array([s for _, s, _ in served])
    rows = np.arange(len(served))
    set_gap = psims[rows, sets] - psims[rows, s_prog]
    tie = (s_prog != sets) & (set_gap < TIE)
    if tie.any():  # score a critical-set tie on the served side
        idx = np.flatnonzero(tie)
        sc, _, _, _ = ref.score(embs[idx], lat[idx], cost[idx], s_prog[idx])
        scores[idx], sets[idx], set_gap[idx] = sc, s_prog[idx], 0.0
    decided = ref.decide(scores, sets)
    out, skipped = [], 0
    for r, ((j, _, fb), (j_ref, fb_ref)) in enumerate(zip(served, decided)):
        if gaps[r] < TIE:
            skipped += 1
            continue
        if fb != fb_ref:
            path_gap = 1.0
        elif fb:
            path_gap = 0.0 if j == j_ref else 1.0
        elif not np.isfinite(scores[r, j]):
            path_gap = 1.0
        else:
            best = scores[r, j_ref]
            path_gap = (best - scores[r, j]) / max(abs(best), GAP_FLOOR)
        out.append(max(path_gap, set_gap[r]))
    return np.asarray(out), skipped


def decision_gap(refs: dict, served: list, emb_of, seed: int):
    """``served``: (domain, qid, max_lat, max_cost, path_key, set_id,
    fallback) of each served request.  Returns (gap, compared, skipped)."""
    rng = np.random.default_rng([seed, 3])
    pick = sorted(rng.choice(len(served), min(SAMPLE, len(served)),
                             replace=False)) if served else []
    by_dom: dict[str, list] = {}
    for i in pick:
        by_dom.setdefault(served[i][0], []).append(served[i])
    worst, n, skipped = 0.0, 0, 0
    for dom, rows in by_dom.items():
        ref = refs[dom]
        index = {k: j for j, k in enumerate(ref.path_keys)}
        embs = np.stack([emb_of(dom, r[1]) for r in rows])
        lat = np.array([r[2] for r in rows])
        cost = np.array([r[3] for r in rows])
        prog = [(index[r[4]], int(r[5]), bool(r[6])) for r in rows]
        g, s = _decision_gaps(ref, embs, lat, cost, prog)
        skipped += s
        n += len(g)
        if len(g):
            worst = max(worst, float(g.max()))
    return worst, n, skipped


def score_err(refs: dict, passes: list, domain_names: list[str]):
    """``passes``: (embs, slo, domain id | None, (scores, set_id, best,
    feasible)) as the selection pass took and returned them.  Returns
    (err, rows compared, rows skipped)."""
    worst, n, skipped = 0.0, 0, 0
    for embs, slo, did, out in passes:
        dom = domain_names[0 if did is None else int(did)]
        ref = refs[dom]
        embs, slo = np.asarray(embs), np.asarray(slo, np.float64)
        scores, set_id = np.asarray(out[0], np.float64), np.asarray(out[1])
        real = slo[:, 0] > -np.inf  # pad rows carry -inf SLOs
        r_scores, r_sets, psims, gaps = ref.score(
            embs[real], slo[real, 0], slo[real, 1])
        scores, set_id = scores[real], set_id[real]
        for r in range(len(r_scores)):
            if gaps[r] < TIE:
                skipped += 1
                continue
            n += 1
            if set_id[r] != r_sets[r] and \
                    psims[r, r_sets[r]] - psims[r, set_id[r]] >= TIE:
                worst = max(worst, 1.0)
                continue
            if set_id[r] != r_sets[r]:  # a tie: score the served side
                r_scores[r] = ref.score(embs[real][r:r + 1],
                                        slo[real, 0][r:r + 1],
                                        slo[real, 1][r:r + 1],
                                        set_id[r:r + 1])[0][0]
            feas_p = scores[r] > NEG_INF / 2
            feas_r = np.isfinite(r_scores[r])
            if (feas_p != feas_r).any():
                worst = max(worst, 1.0)
                continue
            if feas_p.any():
                worst = max(worst, float(np.max(np.abs(
                    scores[r, feas_p] - r_scores[r, feas_p]))))
    return worst, n, skipped
