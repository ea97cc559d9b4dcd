"""The data one domain is served from, made here from the configuration's
``data_seed`` the way a model benchmark makes its weights: the query
embeddings, the log's explored table, and the DSQE parameters (from the
run seed).  Nothing here calls the program; the program is handed what is
made here, and the reference reads the same arrays.

* Embeddings: ``topics`` unit centroids; each query is its topic's centroid
  plus isotropic noise of norm about ``topic_spread``, unit-normed, in
  float32.  Log rows and the held-out pool are drawn alike.
* Explored table, shaped like Algorithm 1's output: each row explores each
  path with probability ``explored_per_row / P`` (row ``i`` always explores
  path ``i mod P``, so every path has evidence), NaN elsewhere.  A path's
  latency and cost are the sums of its components' (configuration's
  ``components``), times per-row noise; its accuracy is
  sigmoid(row bias + the components' qualities + a per-topic effect of each
  component) plus noise, clipped to [0, 1].  The per-topic effects make
  the critical components differ from region to region of the log.
* DSQE parameters (``dsqe_params``): a random projection of the
  configuration's widths, drawn from the run seed, and one prototype per
  critical set, the unit mean of its log rows' projections.  One jitted
  call on the device.
"""
from __future__ import annotations

import numpy as np

MODULES = ("qproc", "retrieval", "cproc", "model")


def embeddings(n: int, d: int, data: dict, seed) -> tuple[np.ndarray,
                                                          np.ndarray]:
    """``n`` unit query embeddings (float32) and each one's topic."""
    rng = np.random.default_rng(seed)
    cent = rng.standard_normal((data["topics"], d), np.float32)
    cent /= np.linalg.norm(cent, axis=1, keepdims=True)
    topic = rng.integers(data["topics"], size=n)
    e = rng.standard_normal((n, d), np.float32)
    e *= np.float32(data["topic_spread"] / np.sqrt(d))
    e += cent[topic]
    e /= np.linalg.norm(e, axis=1, keepdims=True)
    return e, topic


def component_index(path_components: list[dict]) -> dict[str, np.ndarray]:
    """Per module, each path's component as an index into that module's
    sorted component keys."""
    out = {}
    for m in MODULES:
        keys = sorted({c[m] for c in path_components})
        pos = {k: i for i, k in enumerate(keys)}
        out[m] = np.array([pos[c[m]] for c in path_components], np.int64)
    return out


def explored_table(topic: np.ndarray, path_components: list[dict],
                   data: dict, seed) -> tuple[np.ndarray, np.ndarray,
                                              np.ndarray]:
    """(accuracy, latency, cost), each (N, P) float64, NaN where a row did
    not explore a path."""
    rng = np.random.default_rng(seed)
    comps = data["components"]
    N, P = len(topic), len(path_components)
    lat_p = np.zeros(P)
    cost_p = np.zeros(P)
    qual_p = np.zeros(P)
    effect = np.zeros((data["topics"], P))
    for m in MODULES:
        keys = sorted({c[m] for c in path_components})
        missing = [k for k in keys if k not in comps[m]]
        if missing:
            raise KeyError(f"no data for {m} components {missing}")
        spec = np.array([comps[m][k] for k in keys], np.float64)  # (n_m, 3)
        idx = component_index(path_components)[m]
        lat_p += spec[idx, 0]
        cost_p += spec[idx, 1]
        qual_p += spec[idx, 2]
        per_topic = rng.normal(0.0, data["topic_effect"],
                               (data["topics"], len(keys)))
        effect += per_topic[:, idx]
    logit = (rng.normal(0.0, data["row_effect"], (N, 1)) + qual_p[None]
             + effect[topic])
    acc = 1.0 / (1.0 + np.exp(-logit))
    acc += rng.normal(0.0, data["acc_noise"], (N, P))
    np.clip(acc, 0.0, 1.0, out=acc)
    lat = lat_p[None] * np.exp(rng.normal(0.0, data["lat_noise"], (N, P)))
    cost = cost_p[None] * np.exp(rng.normal(0.0, data["cost_noise"], (N, P)))
    explored = rng.random((N, P)) < data["explored_per_row"] / P
    explored[np.arange(N), np.arange(N) % P] = True
    for a in (acc, lat, cost):
        a[~explored] = np.nan
    return acc, lat, cost


def dsqe_params(log_emb: np.ndarray, labels: np.ndarray, n_sets: int,
                d_hidden: int, n_layers: int, seed: int) -> dict:
    """Projection layers drawn from ``seed`` and the critical sets' unit
    mean projections as prototypes, in float32 (as served)."""
    import jax
    import jax.numpy as jnp

    d_in = log_emb.shape[1]
    dims = [d_in] + [d_hidden] * n_layers

    @jax.jit
    def make(key, e, y):
        keys = jax.random.split(key, n_layers)
        layers, x = [], e
        for i in range(n_layers):
            w = jax.random.normal(keys[i], (dims[i], dims[i + 1]),
                                  jnp.float32) / np.sqrt(dims[i])
            b = jnp.zeros((dims[i + 1],), jnp.float32)
            layers.append({"w": w, "b": b})
            x = jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST) + b
            if i < n_layers - 1:
                x = jax.nn.relu(x)
        x = x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True), 1e-6)
        sums = jax.ops.segment_sum(x, y, num_segments=n_sets)
        protos = sums / jnp.maximum(
            jnp.linalg.norm(sums, axis=1, keepdims=True), 1e-6)
        return {"layers": layers, "protos": protos}

    key = jax.random.fold_in(jax.random.key(np.uint32(seed % 2**32)),
                             np.uint32((seed >> 32) % 2**32))
    out = make(key, jnp.asarray(log_emb, jnp.float32),
               jnp.asarray(labels, jnp.int32))
    return jax.tree.map(np.asarray, out)
