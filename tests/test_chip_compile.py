"""Ahead-of-time compiles of the serving path's device programs for a TPU
v5e that is described, not attached.

Nothing runs: each test lowers a program against one chip of a described
``v5e:2x2`` topology and compiles it with the TPU compiler, which refuses
what the chip would refuse (tile alignment, VMEM budget, unsupported
lowering).  The topology is described inside a module fixture, so only the
worker that runs these tests loads the TPU library, and every test here
skips where the topology cannot be described.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels.common as kcommon
from repro.core.dsqe import init_dsqe, projection_stage
from repro.kernels.retrieval_topk.kernel import retrieval_topk_kernel
from repro.kernels.stages import (decode_stage, retrieve_stage, score_stage,
                                  serial)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_retrieval(one_chip, bq: int, n: int, d: int, k: int):
    q = jax.ShapeDtypeStruct((bq, d), jnp.float32, sharding=one_chip)
    corpus = jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip)
    fn = jax.jit(functools.partial(retrieval_topk_kernel, k=k, block_n=512,
                                   n_valid=n))
    return fn.lower(q, corpus).compile()


def test_retrieval_topk_compiles_at_serve_width(one_chip):
    """A 64-query admission bucket against a two-tile projected query log."""
    compiled = _compile_retrieval(one_chip, 64, 1024, 256, 16)
    assert "tpu_custom_call" in compiled.as_text()


def test_retrieval_topk_compiles_at_deployment_scale(one_chip):
    """128 queries streamed over 196 corpus tiles (100,352 x 256 float32,
    ~103 MB resident): the corpus must stay in HBM, not VMEM."""
    compiled = _compile_retrieval(one_chip, 128, 100_352, 256, 16)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 100_352 * 256 * 4


def test_fused_selection_program_compiles(one_chip, monkeypatch):
    """serial(projection, retrieve, score, decode) at the automotive
    serving shape (d_in=512, N=700, P=210, K=96), with the retrieve stage
    dispatched to the Pallas kernel as it is on a TPU."""
    monkeypatch.setattr(kcommon, "is_tpu", lambda: True)
    d_in, d, n, p, k_sets, bucket = 512, 256, 700, 210, 96, 32
    rng = np.random.default_rng(0)
    params = jax.tree.map(np.asarray,
                          init_dsqe(jax.random.key(0), d_in, k_sets))
    corpus = rng.standard_normal((n, d)).astype(np.float32)
    pathw = np.zeros((n, p), np.float32)
    pathw[np.arange(n), rng.integers(0, p, n)] = rng.random(n)
    stage = serial(
        projection_stage(params),
        retrieve_stage(corpus, k=16),
        score_stage(params["protos"], pathw, rng.random((k_sets, p)) < 0.5,
                    rng.random(p), rng.random(p), 1e-3 * rng.random(p),
                    np.ones(p, bool)),
        decode_stage())
    state, apply = stage.init()
    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    state_spec = jax.tree.map(lambda a: spec(a.shape, a.dtype), state)
    carry = {"emb": spec((bucket, d_in), jnp.float32),
             "slo": spec((bucket, 2), jnp.float32)}
    compiled = jax.jit(apply).lower(state_spec, carry).compile()
    assert "tpu_custom_call" in compiled.as_text()
