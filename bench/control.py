"""Readings that the correctness limits are set from, on the chip.

    python3 bench/control.py --workload <cell> --seed <n> --seeds 12 \
        --control-seeds 3 --seconds 3 --precisions high,default

One process builds the cell's deployment (kind ``eco_select``) once, then
runs short windows of the cell's own traffic at the cell's own size:
``--seeds`` of them through the program as the configuration states it
(HIGHEST selection dots; the lower readings), then ``--control-seeds``
through new selectors traced with each lower precision (the control,
which has to fail).  Each window
prints one JSON line with its compared numbers; the last line is the
summary: per number, the largest program reading and the smallest reading
of each control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run as R  # noqa: E402
from bench.harness import spec  # noqa: E402
from bench.harness.precision import set_select_precision  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--precisions", default="high,default")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    try:
        device = R.accelerator(cell.chips)
    except R.Refused as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    R.enable_compile_cache()
    counter = R.CompileCounter()
    kind = spec.kind_of(cell)  # eco_select: its selectors and server
    dep = kind.build(cell.config, args.seed)
    readings: dict[str, dict[str, list]] = {}
    plan = [("highest", args.seeds)] + [
        (p, args.control_seeds) for p in args.precisions.split(",") if p]
    try:
        for prec, n in plan:
            if prec != "highest":
                set_select_precision(prec)
                for d in dep.domains:
                    d.rps = kind.selector(d, cell.config)
                kind.close(dep)
                dep = kind.serve(dep.domains, cell.config, args.seed)
            for i in range(n):
                seed = args.seed + 1 + i
                out = R.measure(dep, cell, seed, args.seconds, False,
                                device, counter)
                line = {"precision": prec, "seed": seed,
                        "checks": out["checks"], "metrics": out["metrics"],
                        "info": out["info"]}
                print(json.dumps(R._finite(line)), flush=True)
                for k, c in out["checks"].items():
                    readings.setdefault(prec, {}).setdefault(k, []).append(
                        c["value"])
    finally:
        kind.close(dep)
    summary = {"program_max": {k: max(v) for k, v in
                               readings.get("highest", {}).items()}}
    for prec, vals in readings.items():
        if prec != "highest":
            summary[f"{prec}_min"] = {k: min(v) for k, v in vals.items()}
    print(json.dumps(R._finite({"summary": summary, "readings": readings})))
    return 0


if __name__ == "__main__":
    sys.exit(main())
