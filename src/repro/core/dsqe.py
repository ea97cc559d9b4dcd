"""Domain-Specific Query Encoding (paper §3.3.3) — in JAX.

A frozen base embedding e_q is passed through a trained MLP projection
f_θ (Eq. 10-11: Linear -> Dropout -> ReLU stack) into a space where queries
that need the same critical component set cluster; K learnable prototype
vectors {v_k} represent CCA's distinct component sets.  Training optimizes
(Eq. 12):

    L = L_contrast + α·L_diversity + β·L_reg

  * contrastive: InfoNCE of the query against its set's prototype,
  * diversity: mean pairwise prototype cosine (pushed down, anti-collapse),
  * reg: L2 on projection weights.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import SELECT_PRECISION
from repro.kernels.stages import Stage
from repro.optim import adamw, constant_schedule


def projection_stage(params: dict, *, in_key: str = "emb",
                     out_key: str = "z") -> Stage:
    """Device stage wrapping the trained DSQE projection.

    State: the parameter pytree pushed to the device at init.  Adds the
    unit-norm projection ``carry[out_key]`` (B, d) of ``carry[in_key]``.
    """
    def init():
        state = jax.tree.map(jnp.asarray, params)

        def apply(params_dev, carry):
            return {**carry, out_key: project(params_dev, carry[in_key])}

        return state, apply

    return Stage("dsqe_project", init)


@dataclass
class DSQE:
    params: dict
    n_sets: int
    temperature: float = 0.1

    def project(self, e: jax.Array) -> jax.Array:
        return project(self.params, e, dropout_rng=None)

    def as_stage(self, *, in_key: str = "emb", out_key: str = "z") -> Stage:
        """This encoder's frozen projection as a composable device stage."""
        return projection_stage(self.params, in_key=in_key, out_key=out_key)

    def predict_set(self, e: jax.Array) -> jax.Array:
        """Most-similar prototype index per query. e: (..., d)."""
        z = self.project(e)
        sims = prototype_sims(self.params, z)
        return jnp.argmax(sims, axis=-1)


def init_dsqe(key, d_in: int, n_sets: int, d_hidden: int = 256, n_layers: int = 2) -> dict:
    keys = jax.random.split(key, n_layers + 1)
    layers = []
    dims = [d_in] + [d_hidden] * n_layers
    for i in range(n_layers):
        w = jax.random.normal(keys[i], (dims[i], dims[i + 1]), jnp.float32)
        layers.append({"w": w / math.sqrt(dims[i]), "b": jnp.zeros((dims[i + 1],), jnp.float32)})
    protos = jax.random.normal(keys[-1], (n_sets, dims[-1]), jnp.float32)
    protos = protos / jnp.linalg.norm(protos, axis=-1, keepdims=True)
    return {"layers": layers, "protos": protos}


def project(params: dict, e: jax.Array, dropout_rng=None, dropout: float = 0.1) -> jax.Array:
    x = e
    n = len(params["layers"])
    for i, layer in enumerate(params["layers"]):
        x = jnp.matmul(x, layer["w"], precision=SELECT_PRECISION) + layer["b"]
        if dropout_rng is not None:
            keep = jax.random.bernoulli(jax.random.fold_in(dropout_rng, i), 1 - dropout, x.shape)
            x = jnp.where(keep, x / (1 - dropout), 0.0)
        if i < n - 1:
            x = jax.nn.relu(x)
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-6)


def prototype_sims(params: dict, z: jax.Array) -> jax.Array:
    protos = params["protos"]
    protos = protos / jnp.maximum(jnp.linalg.norm(protos, axis=-1, keepdims=True), 1e-6)
    return z @ protos.T


def dsqe_loss(params: dict, e: jax.Array, labels: jax.Array, rng,
              temperature: float = 0.1, alpha: float = 0.5, beta: float = 1e-4):
    z = project(params, e, dropout_rng=rng)
    sims = prototype_sims(params, z) / temperature  # (B, K)
    contrast = -jnp.mean(jax.nn.log_softmax(sims, axis=-1)[jnp.arange(e.shape[0]), labels])
    protos = params["protos"]
    protos = protos / jnp.maximum(jnp.linalg.norm(protos, axis=-1, keepdims=True), 1e-6)
    K = protos.shape[0]
    gram = protos @ protos.T
    off = gram - jnp.eye(K) * gram
    diversity = jnp.sum(jax.nn.relu(off)) / max(K * (K - 1), 1)
    reg = sum(jnp.sum(jnp.square(l["w"])) for l in params["layers"])
    total = contrast + alpha * diversity + beta * reg
    return total, {"contrast": contrast, "diversity": diversity, "reg": reg}


def train_dsqe(embeddings: np.ndarray, set_ids: np.ndarray, n_sets: int,
               *, steps: int = 400, batch: int = 64, lr: float = 3e-3,
               seed: int = 0, temperature: float = 0.1) -> DSQE:
    """Train projection + prototypes on CCA labels.  Returns a frozen DSQE."""
    d = embeddings.shape[1]
    key = jax.random.key(seed)
    params = init_dsqe(key, d, n_sets)
    opt = adamw(constant_schedule(lr), weight_decay=0.0)
    opt_state = opt.init(params)
    e_all = jnp.asarray(embeddings, jnp.float32)
    y_all = jnp.asarray(set_ids, jnp.int32)
    n = e_all.shape[0]

    @jax.jit
    def step_fn(params, opt_state, step, rng):
        idx = jax.random.randint(jax.random.fold_in(rng, 0), (min(batch, n),), 0, n)
        e, y = e_all[idx], y_all[idx]
        (loss, parts), grads = jax.value_and_grad(dsqe_loss, has_aux=True)(
            params, e, y, jax.random.fold_in(rng, 1), temperature
        )
        params, opt_state = opt.update(grads, opt_state, params, step)
        return params, opt_state, loss

    step = jnp.zeros((), jnp.int32)
    for i in range(steps):
        params, opt_state, loss = step_fn(params, opt_state, step + i, jax.random.fold_in(key, i))
    return DSQE(params=jax.tree.map(np.asarray, params), n_sets=n_sets, temperature=temperature)
