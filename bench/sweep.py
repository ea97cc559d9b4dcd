"""Find the highest rate a cell's deployment sustains, on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 5 \
        --rates 500,1000,1500,2000

One process builds the deployment once, then runs one window per rate,
the cell's traffic with its arrivals replaced by a Poisson process at that
rate, and prints per window the offered rate, the decision and response tails, the served
rate, how late the generator ran and how long the backlog took to drain
after the close (a growing backlog: the rate is past the knee).  The
correctness comparison is not run.  The rates a cell offers are fixed
numbers in its traffic file, set once from such a sweep.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run as R  # noqa: E402
from bench.harness import spec  # noqa: E402


def poisson(cell: spec.Cell, rate: float) -> spec.Cell:
    mix = copy.deepcopy(cell.traffic)
    mix["loop"] = "open"
    mix["arrivals"] = {"cycle_s": 1.0,
                       "phases": [{"s": 1.0, "rate_qps": rate}]}
    return spec.Cell(cell.name, cell.config, mix, cell.chips,
                     cell.end_to_end, cell.per_layer)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    try:
        device = R.accelerator(cell.chips)
    except R.Refused as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    R.enable_compile_cache()
    counter = R.CompileCounter()
    kind = spec.kind_of(cell)
    dep = kind.build(cell.config, args.seed)
    try:
        for i, rate in enumerate(float(x) for x in args.rates.split(",")):
            out = R.measure(dep, poisson(cell, rate), args.seed + i,
                            args.seconds, False, device, counter,
                            with_checks=False)
            print(json.dumps(R._finite({
                "offered_qps": rate,
                "served_qps": out["attempted"] / args.seconds,
                "metrics": out["metrics"], "info": out["info"]})),
                flush=True)
    finally:
        kind.close(dep)
    return 0


if __name__ == "__main__":
    sys.exit(main())
