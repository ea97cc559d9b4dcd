"""``chip_smoke.py`` on the CPU: its serve, parity and retrieval phases at a
small size through the XLA references, its refusal to run without a TPU,
and the compile-cache helper it shares with ``repro.launch.serve``."""
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.launch import serve

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_and_parity_phases(smoke, capsys):
    """Both entry points serve every held-out query, the fused engine's
    trace count stays within its buckets, and its decisions equal the
    numpy oracle's."""
    server, reqs = smoke.serve_phase(n_queries=120)
    try:
        counts = smoke.parity_phase(server, reqs)
    finally:
        server.fleet.close()
    assert counts == {"rows": len(reqs), "ties": 0, "mismatches": 0}
    out = capsys.readouterr().out
    for mode in ("async", "batch"):
        assert f"serve[{mode}]: offered {len(reqs)} served {len(reqs)} " \
               "shed 0 failed 0" in out


def test_retrieval_phase_small(smoke):
    """Two streamed corpus tiles' worth of rows against the float64 top-k."""
    counts = smoke.retrieval_phase((8, 1024, 256, 16), seed=1)
    assert counts["disagreements"] == counts["near_ties"]
    assert counts["max_score_err"] < smoke.NEAR_TIE


def test_main_refuses_cpu(smoke, capsys):
    """Without a TPU: a non-zero exit, a reason on stderr, no result line."""
    assert jax.devices()[0].platform == "cpu"
    assert smoke.main() != 0
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU" in err


@pytest.mark.parametrize("env_dir", [None, "/cache/from/env"])
def test_compile_cache_location(monkeypatch, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` wins and is left to JAX; otherwise the
    cache is set to the checkout's fixed ``.jax_cache``."""
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.__setitem__(name, value))
    want = env_dir or str(ROOT / ".jax_cache")
    assert serve.enable_compile_cache() == want
    assert updates.get("jax_compilation_cache_dir") == (
        None if env_dir else want)
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0
