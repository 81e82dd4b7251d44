#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13

For each seed, in one process: the cell's set-up and a window of one
whole unit of its traffic at the cell's own load, then the comparison
that decides ``correct``, once for the program and once with the
control (the reference computed one precision lower) in its place:
each verdict with the numbers it compared.  One JSON line per seed.
Needs the chip, like ``run.py``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]


def readings(cell, seed: int, devices) -> dict:
    from bench.harness import generator_class
    generator = generator_class(cell)(cell, seed, devices)
    generator.setup()
    generator.window(0)
    out = {"seed": seed}
    for side, control in (("program", False), ("control", True)):
        checks = generator.check(control=control)
        out[side] = {"correct": all(c["ok"] for c in checks),
                     "checks": {c["name"]: c["value"] for c in checks}}
    del generator
    gc.collect()                 # the next seed's data needs the memory
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    for p in (str(CHECKOUT), str(CHECKOUT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.harness import Benchmark, NoChip, devices_for, enable_cache
    cell = Benchmark().cell(args.workload)
    try:
        devices = devices_for(cell)
    except NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    enable_cache()

    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = readings(cell, seed, devices)
        out["wall_s"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
