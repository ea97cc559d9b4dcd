"""A cell cut to a size the CPU test run holds: its kind's CPU cut of the
configuration and a low rate.  Used by the CPU tests only."""
from __future__ import annotations

import copy
import gc
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.harness import spec  # noqa: E402

CPU_PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def tiny_cell(name: str, rate_scale: float = 1 / 8) -> spec.Cell:
    """``name`` cut to the CPU."""
    c = spec.cell(name)
    cfg = spec.kind_of(c).cpu_cut(c.config)
    cfg["warm_requests"] = 32
    mix = copy.deepcopy(c.traffic)
    for ph in mix.get("arrivals", {}).get("phases", []):
        ph["rate_qps"] *= rate_scale
    if mix["loop"] == "closed":
        mix["outstanding"] = 16
    return spec.Cell(c.name, cfg, mix, 1, c.end_to_end, c.per_layer)


def cpu_device(chips: int) -> dict:
    """The harness's device record, taken on the CPU."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def run_tiny(name: str, seed: int, seconds: float = 1.0,
             trace: bool = False, **kw) -> dict:
    """One whole run of a tiny cell with the look for a chip skipped; the
    persistent compilation cache is left off."""
    return run_cell(tiny_cell(name, **kw), seed, seconds, trace)


def run_cell(cell: spec.Cell, seed: int, seconds: float = 1.0,
             trace: bool = False) -> dict:
    """``run.run_cell`` on the CPU, as ``run_tiny`` makes it."""
    from bench import run as R

    saved = (R.accelerator, R.enable_compile_cache, R.spec.peaks)
    R.accelerator = cpu_device
    R.enable_compile_cache = lambda: "off"
    R.spec.peaks = lambda kind: CPU_PEAKS
    try:
        return R.run_cell(cell, seed, seconds, trace)
    finally:
        R.accelerator, R.enable_compile_cache, R.spec.peaks = saved
        gc.unfreeze()  # the run froze what it had built; give it back
