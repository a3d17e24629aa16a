"""Readings from which the limits in ``limits.json`` are set.

Usage, from the repository root, on a machine with the chip::

    python3 benchmarks/chip/calibrate.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] [--control 1]

One process sets the cell up once, then for each seed runs a window of
``--seconds`` at the cell's own load (the closed loop of ``harness.py``)
and prints, as one JSON line, the numbers the check compares for the
program's answers.  With ``--control 1`` it also prints them for the
control: the float64 reference computed in bfloat16, put in the program's
place on the same sampled trials.  The last line gives the lower reading
(the largest the program gave) and the upper reading (the smallest the
control gave) of each number.  The benchmark's own runs never run the
control.
"""

from __future__ import annotations

import argparse
import json
import sys

import harness

import checks


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    harness.use_compile_cache()
    try:
        cell = harness.load_cell(harness.ROOT, args.workload)
        bench = harness.Bench(cell)
    except harness.BenchError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 1
    bench.warm_up(args.seeds[0])
    count = harness.check_count(cell)
    lower, upper = {}, {}
    for seed in args.seeds:
        win = bench.window(seed, args.seconds)
        row = {"seed": seed, "trials": len(win.answers), "failed": win.failed,
               "program": checks.program_numbers(cell.config, seed, win.answers,
                                                 bench.algorithms, count)}
        for k, v in row["program"].items():
            lower[k] = max(lower.get(k, 0.0), v)
        if args.control:
            row["control"] = checks.control_numbers(cell.config, seed, len(win.answers),
                                                    bench.algorithms, count)
            for k, v in row["control"].items():
                upper[k] = min(upper.get(k, float("inf")), v)
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(args.seeds),
                      "lower": lower, "upper": upper or None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
