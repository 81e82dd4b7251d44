#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Without a TPU, or with fewer chips than the cell asks for, or without
the program's ``src/`` beside ``bench/``, it exits non-zero and prints
no result.  The numbers that decide ``correct`` are printed as the last
lines on standard error, each beside its limit; the last line on
standard output is the result as one JSON object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (CHECKOUT / "src" / "repro").is_dir():
        print(f"bench: no program sources under {CHECKOUT / 'src'}",
              file=sys.stderr)
        return 2
    for p in (str(CHECKOUT), str(CHECKOUT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.harness import BenchError, Benchmark, NoChip, run_cell
    try:
        cell = Benchmark().cell(args.workload)
        result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_start=T_START)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
