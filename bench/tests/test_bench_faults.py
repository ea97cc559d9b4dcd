"""The comparison that decides ``correct`` catches a broken timed path:
a run is driven whole at a tiny size on the CPU with one fault planted
underneath, and ``correct`` has to come out false."""
import numpy as np

import _tiny
from bench.harness import drive
from repro.core.rps import RuntimePathSelector
from repro.runtime.orchestrator import Orchestrator


def test_decision_altered_where_produced(monkeypatch):
    """Every selection pass returns the path after the best one."""
    inner = RuntimePathSelector._score_batch_kernel

    def altered(self, *a, **k):
        scores, sets, best, feas = inner(self, *a, **k)
        return scores, sets, (best + 1) % scores.shape[1], feas

    monkeypatch.setattr(RuntimePathSelector, "_score_batch_kernel", altered)
    out = _tiny.run_tiny("smartcar-100k.poisson", seed=21)
    assert out["correct"] is False
    assert out["checks"]["decision_gap"]["value"] > \
        out["checks"]["decision_gap"]["limit"]


def test_stage_output_altered(monkeypatch):
    """The selection pass's scores carry an error of a bfloat16 pass."""
    orig = RuntimePathSelector._build_kernel_state

    def build(self, ver):
        orig(self, ver)
        inner = self._fused_pass

        def pass_(state, embs, slo):
            scores, sets, best, feas = inner(state, embs, slo)
            return scores * (1 + 2.0 ** -8), sets, best, feas

        self._fused_pass = pass_

    monkeypatch.setattr(RuntimePathSelector, "_build_kernel_state", build)
    out = _tiny.run_tiny("smartcar-100k.poisson", seed=22)
    assert out["correct"] is False
    assert out["checks"]["score_err"]["value"] > \
        out["checks"]["score_err"]["limit"]


def test_half_the_bucket_left_out(monkeypatch):
    """Each bucket dispatches only its first half; the rest never
    settle."""
    inner = Orchestrator._dispatch

    async def half(self, tickets):
        await inner(self, tickets[:max(1, len(tickets) // 2)])

    monkeypatch.setattr(Orchestrator, "_dispatch", half)
    monkeypatch.setattr(drive, "SETTLE_GRACE_S", 1.0)
    out = _tiny.run_tiny("smartcar-100k.poisson", seed=23, rate_scale=1.0)
    assert out["correct"] is False
    assert out["checks"]["unsettled"]["value"] > 0


def test_precision_switch_reaches_every_selection_dot():
    import jax
    import jax.numpy as jnp

    from bench.harness.precision import set_select_precision
    from repro.core import dsqe

    params = dsqe.init_dsqe(jax.random.key(0), 16, 4, d_hidden=8)
    e = jnp.ones((2, 16))
    try:
        changed = set_select_precision("high")
        assert {"repro.kernels.common", "repro.kernels.stages",
                "repro.core.dsqe"} <= set(changed)
        text = str(jax.make_jaxpr(lambda x: dsqe.project(params, x))(e))
        assert "Precision.HIGH" in text and "HIGHEST" not in text
    finally:
        set_select_precision("highest")
    text = str(jax.make_jaxpr(lambda x: dsqe.project(params, x))(e))
    assert "HIGHEST" in text
    assert np.isfinite(np.asarray(dsqe.project(params, e))).all()
