"""Run the scheduler's device path once on one TPU chip and check its answers.

Usage, from the repository root::

    python3 chip_smoke.py

The fused sojourn evaluators are driven through the entry points a user
calls (``evaluate_many``, ``optimal_order``, ``expected_sojourn_static``,
``expected_sojourn_dynamic``) at the paper's sizes, on workloads drawn
by ``generate_workload`` from fixed seeds:

1. ``numerical_study``: N = 9 jobs, M = 2 stages, workload sets 1-5 and
   all five algorithms; OPTIMAL scores all 9! = 362,880 orders.
2. ``exact_cap``: N = 21 (K = 2^21): RANK as a static order, and the
   SR and RANK index policies on 1 and 3 servers; then N = 26
   (K = 2^26 = ``MAX_EXACT_COMBOS``): RANK.
3. ``streamed_mc``: N = 27 (K = 2^27), so ``evaluate_many`` takes its
   shared-seed Monte Carlo branch with 2^20 samples; RANK is also scored
   over the same samples passed as an explicit outcome table.

The chip computes in float32.  Each phase checks every answer against a
float64 reference: the dense oracles of ``ref.py`` where their tables
fit, and otherwise the x64 XLA path run on the host CPU device.  An
answer passes within ``ops.CHIP_RTOL`` relative error for its entry mode,
and OPTIMAL's order must be optimal in float64 up to that tolerance.

Each phase runs its device work twice and prints one JSON line: the
implementation the ops resolved to (from the ``repro.obs.profiling``
span names, which must end in ``.pallas``), backend compile seconds and
persistent-cache hits and misses of the first run, the second run's
wall seconds, and the largest relative error per entry mode.  The last
line is ``{"ok": true, "device": {...}}``.  The script exits nonzero
without that line when the first device is not a TPU or a phase fails.
One process; it starts no other.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

SEED = 20220526
N_NUMERICAL = 9  # largest N that OPTIMAL's exhaustive search takes
N_EXACT = 21  # K = 2^21
N_CAP = 26  # K = 2^26 = MAX_EXACT_COMBOS
N_MC = 27  # K = 2^27: past the exact cap
MC_SAMPLES = 1 << 20
NUMERICAL_ALGS = ("optimal", "rank", "serpt", "sr", "random")
MC_ALGS = ("rank", "serpt", "sr", "random")
DYNAMIC_CASES = (("sr", 1), ("sr", 3), ("rank", 3))  # (index policy, servers)


class CompileLog:
    """Backend compile seconds and persistent-cache hits/misses, from JAX's
    monitoring events."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return self.seconds, self.hits, self.misses


def rel_err(got, want):
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.abs(want)))


# ---------------------------------------------------------------------------
# Phases.  Each draws its inputs on the host and returns ``device``, which
# runs the entry points on the chip, and ``check``, which compares their
# answers with the float64 references and returns {label: (mode, rel_err)}
# and any further verdicts.
# ---------------------------------------------------------------------------


def numerical_study():
    import numpy as np

    from repro.configs.paper_workloads import NUMERICAL
    from repro.core import evaluator, policies
    from repro.core.jobs import generate_workload

    orders = np.array(
        list(itertools.permutations(range(N_NUMERICAL))), dtype=np.int32
    )
    sets = {
        ws: generate_workload(
            np.random.default_rng([SEED, 1, ws]), N_NUMERICAL,
            NUMERICAL.num_stages, ws,
        )
        for ws in NUMERICAL.workload_sets
    }

    def device():
        return {
            ws: {
                "many": evaluator.evaluate_many(
                    jobs, NUMERICAL_ALGS, np.random.default_rng([SEED, 2, ws])
                ),
                "optimal": evaluator.optimal_order(jobs),
                "all": evaluator.expected_sojourn_static(jobs, orders),
            }
            for ws, jobs in sets.items()
        }

    def check(results):
        from repro.kernels.sojourn_eval.ops import CHIP_RTOL
        from repro.kernels.sojourn_eval.ref import ref_sojourn, ref_sojourn_dynamic

        errs, verdicts = {}, {}
        for ws, jobs in sets.items():
            res = results[ws]
            sizes, probs, num_stages = policies.padded_arrays(jobs)
            ref_all, _ = ref_sojourn(sizes, probs, num_stages, orders)
            # evaluate_many draws only RANDOM's order from its rng.
            random = policies.random_order(jobs, np.random.default_rng([SEED, 2, ws]))
            ref_static, _ = ref_sojourn(
                sizes, probs, num_stages,
                np.stack([policies.rank_order(jobs), random]),
            )
            best, best_val = res["optimal"]
            idx = int(np.flatnonzero((orders == best).all(axis=1))[0])
            verdicts[f"set{ws}.optimal_is_argmin"] = bool(
                ref_all[idx] <= ref_all.min() * (1 + CHIP_RTOL["enum"])
            )
            errs[f"set{ws}.all_orders"] = ("enum", rel_err(res["all"], ref_all))
            errs[f"set{ws}.optimal"] = ("enum", rel_err(res["many"]["optimal"], ref_all.min()))
            errs[f"set{ws}.optimal_order"] = ("enum", rel_err(best_val, ref_all[idx]))
            errs[f"set{ws}.rank"] = ("enum", rel_err(res["many"]["rank"], ref_static[0]))
            errs[f"set{ws}.random"] = ("enum", rel_err(res["many"]["random"], ref_static[1]))
            for pol in ("serpt", "sr"):
                want, _ = ref_sojourn_dynamic(
                    probs, policies.stage_durations(jobs), num_stages,
                    policies.index_table(jobs, pol),
                )
                errs[f"set{ws}.{pol}"] = ("enum", rel_err(res["many"][pol], want))
        return errs, verdicts

    return device, check


def exact_cap():
    import jax
    import numpy as np

    from repro.core import evaluator, policies
    from repro.core.jobs import generate_workload

    jobs21 = generate_workload(np.random.default_rng([SEED, 3]), N_EXACT, 2, 1)
    jobs26 = generate_workload(np.random.default_rng([SEED, 4]), N_CAP, 2, 1)
    rank21 = policies.rank_order(jobs21)
    rank26 = policies.rank_order(jobs26)

    def device():
        return {
            "rank21": evaluator.expected_sojourn_static(jobs21, rank21),
            **{
                f"{pol}21.W{w}": evaluator.expected_sojourn_dynamic(
                    jobs21, pol, n_servers=w
                )
                for pol, w in DYNAMIC_CASES
            },
            "rank26": evaluator.expected_sojourn_static(jobs26, rank26),
        }

    def check(res):
        from repro.kernels.sojourn_eval.ref import ref_sojourn

        sizes, probs, num_stages = policies.padded_arrays(jobs21)
        (want21,), _ = ref_sojourn(sizes, probs, num_stages, rank21[None])
        errs = {"rank21": ("enum", rel_err(res["rank21"], want21))}
        # No dense table fits at these K: the float64 XLA path on the host.
        with jax.default_device(jax.devices("cpu")[0]):
            for pol, w in DYNAMIC_CASES:
                want = evaluator.expected_sojourn_dynamic(
                    jobs21, pol, n_servers=w, impl="xla"
                )
                errs[f"{pol}21.W{w}"] = ("enum", rel_err(res[f"{pol}21.W{w}"], want))
            want26 = evaluator.expected_sojourn_static(jobs26, rank26, impl="xla")
        errs["rank26"] = ("enum", rel_err(res["rank26"], want26))
        return errs, {}

    return device, check


def streamed_mc():
    import jax
    import numpy as np

    from repro.core import evaluator, policies
    from repro.core.jobs import generate_workload
    from repro.kernels.sojourn_eval import rng as kernel_rng
    from repro.kernels.sojourn_eval.ref import ref_mc_outcomes, ref_sojourn

    jobs = generate_workload(np.random.default_rng([SEED, 5]), N_MC, 2, 1)
    sizes, probs, num_stages = policies.padded_arrays(jobs)
    assert evaluator.exact_combination_count(jobs) > evaluator.MAX_EXACT_COMBOS
    # evaluate_many draws, in order, the shared MC seed and RANDOM's order.
    replay = np.random.default_rng([SEED, 6])
    seed = int(replay.integers(0, kernel_rng.MAX_SEED))
    random = policies.random_order(jobs, replay)
    rank = policies.rank_order(jobs)
    outcomes, weights = ref_mc_outcomes(probs, num_stages, seed, MC_SAMPLES)

    def device():
        many = evaluator.evaluate_many(
            jobs, MC_ALGS, np.random.default_rng([SEED, 6]), mc_samples=MC_SAMPLES
        )
        table = evaluator.expected_sojourn_static(jobs, rank, outcomes, weights)
        return {"many": many, "rank_table": table}

    def check(res):
        (want_rank, want_random), _ = ref_sojourn(
            sizes, probs, num_stages, np.stack([rank, random]), outcomes, weights
        )
        errs = {
            "rank": ("mc", rel_err(res["many"]["rank"], want_rank)),
            "random": ("mc", rel_err(res["many"]["random"], want_random)),
            "rank_table": ("outcomes", rel_err(res["rank_table"], want_rank)),
        }
        with jax.default_device(jax.devices("cpu")[0]):
            for pol in ("serpt", "sr"):
                want = evaluator.expected_sojourn_dynamic(
                    jobs, pol, samples=(seed, MC_SAMPLES), impl="xla"
                )
                errs[pol] = ("mc", rel_err(res["many"][pol], want))
        return errs, {}

    return device, check


PHASES = (
    ("numerical_study", numerical_study),
    ("exact_cap", exact_cap),
    ("streamed_mc", streamed_mc),
)


def run_phase(name, build, log) -> dict:
    from repro.kernels.sojourn_eval.ops import CHIP_RTOL
    from repro.obs import get_registry, profiling

    device, check = build()
    registry = get_registry()
    registry.clear()
    profiling.enable()
    c0 = log.mark()
    t0 = time.perf_counter()
    device()
    cold_s = time.perf_counter() - t0
    c1 = log.mark()
    t0 = time.perf_counter()
    results = device()
    steady_s = time.perf_counter() - t0
    profiling.enable(False)
    spans = sorted(
        k[len("prof."): -len(".seconds")]
        for k in registry.snapshot()["histograms"]
        if k.startswith("prof.sojourn_eval.")
    )
    impls = sorted({s.rsplit(".", 1)[1] for s in spans})
    errs, verdicts = check(results)
    max_err = {}
    for mode, err in errs.values():
        max_err[mode] = max(max_err.get(mode, 0.0), err)
    ok = (
        impls == ["pallas"]
        and all(err <= CHIP_RTOL[mode] for mode, err in errs.values())
        and all(verdicts.values())
    )
    return {
        "phase": name,
        "ok": ok,
        "impl": ",".join(impls),
        "spans": spans,
        "compile_s": c1[0] - c0[0],
        "cache_hits": c1[1] - c0[1],
        "cache_misses": c1[2] - c0[2],
        "cold_s": cold_s,
        "steady_s": steady_s,
        "max_rel_err": max_err,
        "rtol": {mode: CHIP_RTOL[mode] for mode in max_err},
        "rel_err": {label: err for label, (_, err) in errs.items()},
        "verdicts": verdicts,
    }


def main() -> int:
    from repro.runtime import init_compile_cache

    cache_dir = init_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: the first JAX device is {devices[0].platform!r}, not a TPU",
            file=sys.stderr,
        )
        return 2
    print(json.dumps({"compile_cache": cache_dir}), flush=True)
    log = CompileLog(jax)
    ok = True
    for name, build in PHASES:
        try:
            report = run_phase(name, build, log)
        except Exception:  # noqa: BLE001 - report the phase, run the others
            traceback.print_exc()
            report = {"phase": name, "ok": False}
        ok &= report["ok"]
        print(json.dumps(report), flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
