"""Readings for the limit on answers scored on W servers.

Usage::

    # on a machine with the chip: the program's answers, a JSON line a job group
    python3 benchmarks/chip/servers_readings.py program --config <file> --n-jobs <N> \
        --servers <W> --algorithms sr rank --seeds <n> [<n> ...] > answers.jsonl
    # anywhere: the numbers the check compares, for those answers and the control
    python3 benchmarks/chip/servers_readings.py compare answers.jsonl [--control 1]

``program`` takes the configuration file with ``n_jobs`` and ``n_servers``
set as given, draws for each seed the trials of one run's stream that
cover each workload set once (``workgen.trial_group``, trials 0 to S - 1)
and scores each group with the program's W-server path,
``repro.core.evaluator.expected_sojourn_dynamic(jobs, policy,
n_servers=W)``, on the default device: the Pallas kernels on a TPU.
``compare`` scores the same groups with the float64 reference, as a
run's check does (``checks.program_numbers``), and with ``--control 1``
with its bfloat16 control (``checks.control_numbers``).  It prints a line
a seed, with the seconds the reference took, then the lower reading (the
largest the program gave) and the upper one (the least the control
gave).

``calibrate.py`` takes such readings through a run's own window; this
script scores the W-server path directly, for as long as
``evaluate_many`` takes no server count.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from itertools import groupby
from pathlib import Path

import harness

import checks
import workgen


def config_of(path: str, n_jobs: int, servers: int) -> dict:
    config = json.loads((harness.ROOT / path).read_text())
    config.update(n_jobs=n_jobs, n_servers=servers)
    return config


def program(args) -> None:
    harness.use_compile_cache()
    import jax

    from repro.core import evaluator
    from repro.core.jobs import JobSpec

    config = config_of(args.config, args.n_jobs, args.servers)
    kind = jax.devices()[0].device_kind
    for seed in args.seeds:
        for trial in range(len(config["workload_sets"])):
            sizes, probs = workgen.trial_group(config, seed, trial)
            jobs = [JobSpec(sizes=sizes[i], probs=probs[i], job_id=i) for i in range(len(sizes))]
            answers = {a: evaluator.expected_sojourn_dynamic(jobs, a, n_servers=args.servers)
                       for a in args.algorithms}
            print(json.dumps({"config": args.config, "n_jobs": args.n_jobs,
                              "n_servers": args.servers, "seed": seed, "trial": trial,
                              "device": kind, "answers": answers}), flush=True)


def compare(args) -> None:
    rows = [json.loads(line) for line in Path(args.answers).read_text().splitlines() if line]
    lower, upper = {}, {}
    key = lambda r: (r["config"], r["n_jobs"], r["n_servers"], r["seed"])  # noqa: E731
    for (path, n_jobs, servers, seed), group in groupby(rows, key):
        group = sorted(group, key=lambda r: r["trial"])
        if [r["trial"] for r in group] != list(range(len(group))):
            raise ValueError(f"seed {seed}: the trials are not 0 to {len(group) - 1}")
        config = config_of(path, n_jobs, servers)
        answers = [r["answers"] for r in group]
        algorithms = list(answers[0])
        t0 = time.perf_counter()
        row = {"seed": seed, "n_jobs": n_jobs, "n_servers": servers, "trials": len(answers),
               "program": checks.program_numbers(config, seed, answers, algorithms, len(answers)),
               "reference_s": time.perf_counter() - t0}
        for k, v in row["program"].items():
            lower[k] = max(lower.get(k, 0.0), v)
        if args.control:
            row["control"] = checks.control_numbers(config, seed, len(answers), algorithms,
                                                    len(answers))
            for k, v in row["control"].items():
                upper[k] = min(upper.get(k, float("inf")), v)
        print(json.dumps(row), flush=True)
    print(json.dumps({"lower": lower, "upper": upper or None}))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = parser.add_subparsers(dest="step", required=True)
    p = sub.add_parser("program")
    p.add_argument("--config", required=True, help="configuration file, from the repository root")
    p.add_argument("--n-jobs", type=int, required=True)
    p.add_argument("--servers", type=int, required=True)
    p.add_argument("--algorithms", nargs="+", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    c = sub.add_parser("compare")
    c.add_argument("answers")
    c.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if args.step == "program":
        program(args)
    else:
        compare(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
