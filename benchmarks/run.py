"""Benchmark harness: one function per paper table/figure + roofline.

Paper mapping:
  fig1_objective_gap   -> Figure 1   (all-jobs vs successful-jobs objective)
  table_sojourn        -> Tables IV-VIII / Figures 3-7 (mean sojourn, sets 1-5)
  table_competitive    -> Tables IX-XIII (max/p95/p75 competitive ratios)
  table_stages         -> Table XIV  (stage-count sweep)
  table_trace          -> Tables XVI-XVIII (trace-driven online study)
  table_roofline       -> EXPERIMENTS.md §Roofline (reads dry-run artifacts)

Default is a CI-friendly scale (~minutes on 1 CPU core): fewer trials and
a load-matched subsampled trace; ``--full`` switches to paper scale
(50k trials, 109,967 jobs).  Orderings and relative gaps are the
reproduction target at either scale; absolute numbers carry sampling
error shown as ±stderr.  Results are printed as markdown and written to
artifacts/bench/*.json.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import time

import numpy as np

from repro.configs.paper_workloads import NUMERICAL, TRACE
from repro.core.evaluator import evaluate_many
from repro.core.jobs import generate_workload
from repro.core.simulator import simulate
from repro.core.trace import synthesize_trace
from repro.runtime import init_compile_cache

ART = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "artifacts", "bench")


def _save(name: str, obj) -> None:
    """Write artifacts/bench/<name>.json.

    Rows are wrapped as ``{"rows": ..., "workload_cache": cache_stats()}``
    so every artifact records the workload-keyed cache behavior of the
    run that produced it.
    """
    from repro.core import policies

    if not isinstance(obj, dict):
        obj = {"rows": obj}
    obj = {**obj, "workload_cache": policies.cache_stats()}
    os.makedirs(ART, exist_ok=True)
    with open(os.path.join(ART, f"{name}.json"), "w") as f:
        json.dump(obj, f, indent=1)


def _trials_for(n_jobs: int, full: bool) -> int:
    if full:
        return NUMERICAL.trials
    return {3: 400, 4: 400, 5: 300, 6: 200, 7: 120, 8: 60}.get(n_jobs, 200)


# ---------------------------------------------------------------------------
# Figure 1
# ---------------------------------------------------------------------------


def fig1_objective_gap(full: bool = False):
    """Mean sojourn of successful jobs: optimize-for-all (SR) vs
    optimize-for-successful (RANK), vs number of jobs."""
    rows = []
    rng = np.random.default_rng(42)
    for n in (3, 4, 5, 6, 7, 8, 9, 10):
        trials = _trials_for(min(n, 8), full)
        vals = {"rank": [], "sr": []}
        for _ in range(trials):
            jobs = generate_workload(rng, n, workload_set=1)
            res = evaluate_many(jobs, ("rank", "sr"), rng)
            for k in vals:
                vals[k].append(res[k])
        rows.append({
            "n_jobs": n,
            "optimize_successful(RANK)": float(np.mean(vals["rank"])),
            "optimize_all(SR)": float(np.mean(vals["sr"])),
            "gap_pct": 100 * (np.mean(vals["sr"]) / np.mean(vals["rank"]) - 1),
        })
    _save("fig1", rows)
    return rows


# ---------------------------------------------------------------------------
# Tables IV-VIII (+ Figures 3-7) and IX-XIII
# ---------------------------------------------------------------------------


def _numerical_study(full: bool, sets=None, n_jobs=None):
    """Shared sweep: per (workload set, N): mean sojourn per alg +
    competitive ratios vs OPTIMAL."""
    sets = sets or NUMERICAL.workload_sets
    n_jobs = n_jobs or NUMERICAL.n_jobs_sweep
    algs = ("optimal", "rank", "serpt", "sr", "random")
    out = {}
    rng = np.random.default_rng(7)
    for ws in sets:
        for n in n_jobs:
            trials = _trials_for(n, full)
            vals = {a: np.empty(trials) for a in algs}
            for t in range(trials):
                jobs = generate_workload(rng, n, num_stages=NUMERICAL.num_stages,
                                         workload_set=ws)
                res = evaluate_many(jobs, algs, rng)
                for a in algs:
                    vals[a][t] = res[a]
            cr = {a: vals[a] / vals["optimal"] for a in algs if a != "optimal"}
            out[(ws, n)] = {
                "mean": {a: float(vals[a].mean()) for a in algs},
                "stderr": {a: float(vals[a].std() / np.sqrt(trials)) for a in algs},
                "cr_max": {a: float(v.max()) for a, v in cr.items()},
                "cr_p95": {a: float(np.percentile(v, 95)) for a, v in cr.items()},
                "cr_p75": {a: float(np.percentile(v, 75)) for a, v in cr.items()},
                "trials": trials,
            }
    return out


def table_sojourn(full: bool = False, study=None):
    """Tables IV-VIII: average expected sojourn of successful jobs."""
    study = study or _numerical_study(full)
    rows = []
    for (ws, n), r in sorted(study.items()):
        rows.append({
            "workload_set": ws, "n_jobs": n, "trials": r["trials"],
            **{f"{a}": r["mean"][a] for a in ("optimal", "rank", "serpt", "sr", "random")},
            "rank_vs_optimal_pct": 100 * (r["mean"]["rank"] / r["mean"]["optimal"] - 1),
        })
    _save("table_sojourn", rows)
    return rows


def table_competitive(full: bool = False, study=None):
    """Tables IX-XIII: competitive-ratio max / p95 / p75."""
    study = study or _numerical_study(full)
    rows = []
    for (ws, n), r in sorted(study.items()):
        for metric in ("cr_max", "cr_p95", "cr_p75"):
            rows.append({
                "workload_set": ws, "n_jobs": n, "metric": metric,
                **{a: r[metric][a] for a in ("rank", "serpt", "sr", "random")},
            })
    _save("table_competitive", rows)
    return rows


def table_stages(full: bool = False):
    """Table XIV: stage-count sweep at N=5, uniform set."""
    rows = []
    rng = np.random.default_rng(11)
    n = 5
    for m in NUMERICAL.stages_sweep:
        trials = _trials_for(n, full)
        vals = {"optimal": np.empty(trials), "rank": np.empty(trials)}
        crs = np.empty(trials)
        for t in range(trials):
            jobs = generate_workload(rng, n, num_stages=m, workload_set=1)
            res = evaluate_many(jobs, ("optimal", "rank"), rng)
            vals["optimal"][t] = res["optimal"]
            vals["rank"][t] = res["rank"]
            crs[t] = res["rank"] / res["optimal"]
        rows.append({
            "num_stages": m, "trials": trials,
            "optimal": float(vals["optimal"].mean()),
            "rank": float(vals["rank"].mean()),
            "max_cr": float(crs.max()),
        })
    _save("table_stages", rows)
    return rows


# ---------------------------------------------------------------------------
# Tables XVI-XVIII: trace-driven online study
# ---------------------------------------------------------------------------


def table_trace(full: bool = False):
    rows = []
    n_jobs = TRACE.n_jobs if full else TRACE.n_jobs_fast
    duration = TRACE.duration_days * (n_jobs / TRACE.n_jobs)  # load-matched
    for sp in TRACE.synthetic_success_probs:
        dataset = {None: "philly-synthetic", 0.5: "synthetic-I", 0.25: "synthetic-II"}[sp]
        rng = np.random.default_rng(13)
        jobs = synthesize_trace(rng, n_jobs=n_jobs, duration_days=duration,
                                success_prob=sp)
        for w in TRACE.server_counts:
            row = {"dataset": dataset, "servers": w}
            for pol in TRACE.policies:
                res = simulate(jobs, w, policy=pol, rng=np.random.default_rng(17))
                row[pol] = res.mean_sojourn_successful
                row[f"{pol}_nsucc"] = res.n_success
            row["rank_vs_serpt_pct"] = 100 * (1 - row["rank"] / row["serpt"])
            rows.append(row)
    _save("table_trace", rows)
    return rows


# ---------------------------------------------------------------------------
# Beyond-paper: policy robustness under failures / stragglers / elasticity
# ---------------------------------------------------------------------------


def table_faults(full: bool = False):
    """RANK's advantage must survive the failure modes of a real cluster
    (the paper's model is failure-free).  Same trace-style workload, now
    with node failures (gang restart from checkpoint), straggler
    re-dispatch, and an elastic resize mid-run."""
    from repro.cluster.faults import FaultConfig
    from repro.cluster.manager import ClusterManager, TrainingJob

    n = 2000 if not full else 10000
    servers = 8
    rng = np.random.default_rng(21)
    # offered load ~2x capacity: queueing decisions matter
    arrivals = np.sort(rng.uniform(0, n * 0.75 / (2 * servers), n))
    base_jobs = generate_workload(rng, n, num_stages=3, workload_set=1,
                                  arrivals=arrivals)
    scenarios = {
        "clean": dict(fault_cfg=None),
        "faulty": dict(fault_cfg=FaultConfig(mtbf_hours=0.002, restart_overhead=0.5,
                                             straggler_prob=0.05,
                                             straggler_slowdown=5.0),
                       nodes_per_server=8),
        "elastic": dict(fault_cfg=None,
                        resize_events=[(20.0, 12), (60.0, 4)]),
    }
    rows = []
    for scen, kw in scenarios.items():
        row = {"scenario": scen}
        for pol in ("rank", "serpt", "sr", "fifo"):
            jobs = [TrainingJob(spec=s) for s in base_jobs]
            res = ClusterManager(jobs, servers, policy=pol,
                                 rng=np.random.default_rng(5), **kw).run()
            row[pol] = res.mean_sojourn_successful
            if pol == "rank":
                row["restarts"] = res.restarts
                row["straggler_redisp"] = res.straggler_redispatches
        row["rank_vs_serpt_pct"] = 100 * (1 - row["rank"] / row["serpt"])
        rows.append(row)
    _save("table_faults", rows)
    return rows


# ---------------------------------------------------------------------------
# Roofline aggregation (reads dry-run artifacts)
# ---------------------------------------------------------------------------


def table_roofline():
    from repro.launch.roofline import RooflineReport

    paths = sorted(glob.glob("artifacts/dryrun/*.json"))
    if not paths:
        print("  (no dry-run artifacts; run `python -m repro.launch.dryrun` first)")
        return []
    report = RooflineReport.load(paths)
    print(report.to_markdown())
    _save("table_roofline", report.rows)
    return report.rows


# ---------------------------------------------------------------------------


def _fmt(rows: list[dict]) -> str:
    if not rows:
        return "  (empty)"
    keys = list(rows[0].keys())
    head = "| " + " | ".join(keys) + " |"
    sep = "|" + "---|" * len(keys)
    body = []
    for r in rows:
        body.append(
            "| " + " | ".join(
                f"{r[k]:.4g}" if isinstance(r[k], float) else str(r[k]) for k in keys
            ) + " |"
        )
    return "\n".join([head, sep] + body)


TABLES = {
    "fig1": fig1_objective_gap,
    "sojourn": table_sojourn,
    "competitive": table_competitive,
    "stages": table_stages,
    "trace": table_trace,
    "faults": table_faults,
    "roofline": lambda full=False: table_roofline(),
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--table", default="all", choices=["all", *TABLES])
    ap.add_argument("--full", action="store_true", help="paper-scale trials")
    ap.add_argument("--cache-dir", default=None, metavar="DIR",
                    help="persist the workload-keyed memo tier in DIR "
                         "(overrides REPRO_CACHE_DIR)")
    args = ap.parse_args()

    if args.cache_dir:
        from repro.core import policies as _policies

        os.environ["REPRO_CACHE_DIR"] = args.cache_dir
        print(f"workload cache dir: {_policies.ensure_cache_dir()}")
    elif args.full:
        # Paper-scale sweeps revisit the same workloads across tables and
        # reruns: persist the workload-keyed memo tier unless the user
        # already pointed REPRO_CACHE_DIR somewhere.
        from repro.core import policies as _policies

        print(f"workload cache dir: {_policies.ensure_cache_dir()}")

    names = list(TABLES) if args.table == "all" else [args.table]
    shared_study = None
    for name in names:
        t0 = time.perf_counter()
        if name in ("sojourn", "competitive") and args.table == "all":
            if shared_study is None:
                shared_study = _numerical_study(args.full)
            rows = TABLES[name](args.full, study=shared_study)
        else:
            rows = TABLES[name](full=args.full)
        dt = time.perf_counter() - t0
        print(f"\n## {name}  ({dt:.1f}s)")
        if name != "roofline":  # roofline prints its own markdown
            print(_fmt(rows))

    from repro.core import policies

    stats = policies.cache_stats()
    print(
        f"\nworkload cache: {stats['hits']} hits / {stats['misses']} misses "
        f"(hit rate {stats['hit_rate']:.1%}, {stats['entries']} entries)"
    )


if __name__ == "__main__":
    init_compile_cache()
    main()
