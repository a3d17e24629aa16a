"""Chip benchmark of the scheduler: one run of one cell.

Usage, from the root of a checkout, on a machine with the chip::

    python3 benchmarks/chip/harness.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) is one configuration of the
paper's numerical study (``configs/<name>.json``: N jobs, M checkpoints,
the Table III workload sets) under one traffic mix
(``traffic/<name>.json``: the algorithms scored on each job group and how
many answers the check compares).  The run is a closed loop with one
client, as a parameter sweep is: trial t draws a fresh job group from
``(seed, t)``, scores it with ``repro.core.evaluator.evaluate_many`` and
starts the next trial when that returns.

A configuration may name ``n_servers`` = W, the size of the cluster that
serves the group (paper Section V; 1 when absent).  The harness then
scores each group with ``evaluate_many(jobs, algorithms, rng,
n_servers=W)`` and checks RANK, SR and SERPT as index policies on W
servers; a configuration without the key is scored with
``evaluate_many(jobs, algorithms, rng)``.

The run loads, warms every shape its window uses (set-up), measures for
``--seconds``, checks a sample of the window's answers against the
float64 reference (``checks.py``) and prints one JSON line last.  With
``--trace 0`` the line holds the end-to-end metrics; with ``--trace 1``
the window runs under the JAX profiler and the program's spans, and the
line holds the per-layer metrics, each computed by its reader
``metrics/<name>.py``.  The run fails, printing no result, when the first
device is not a TPU, when there are fewer chips than the cell asks for,
or when the device is not in ``peaks.json``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: JAX's persistent compile cache: a fixed directory inside the checkout.
CACHE_DIR = HERE / ".jax_cache"
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import checks  # noqa: E402
import workgen  # noqa: E402
import xplane  # noqa: E402


class BenchError(RuntimeError):
    """The run cannot give a result on this machine or with these files."""


def load_cell(root: Path, name: str) -> SimpleNamespace:
    """Everything ``BENCHMARK.json`` under ``root`` says about cell ``name``,
    with its configuration, traffic, limits and peaks read from their files."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    bench_dir = root / spec["paths"][0]
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads((bench_dir / "traffic" / f"{cell['traffic']}.json").read_text())
    try:
        checks.numbers_of(config, traffic["algorithms"])
    except ValueError as e:
        raise BenchError(f"cell {name!r}: {e}") from None

    def applies(metric):
        return name in metric.get("workloads", [name])

    return SimpleNamespace(
        name=name,
        chips=cell["chips"],
        config=config,
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)],
        bench_dir=bench_dir,
        limits=json.loads((bench_dir / "limits.json").read_text()),
        peaks=json.loads((bench_dir / "peaks.json").read_text()),
    )


def load_reader(bench_dir: Path, metric: str):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    path = bench_dir / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location("metric_" + re.sub(r"\W", "_", metric), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


class CompileLog:
    """Backend compiles and persistent-cache hits and misses, from JAX's
    monitoring events."""

    def __init__(self, jax):
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> tuple[int, int, int]:
        return self.compiles, self.hits, self.misses


class Bench:
    """One cell's system under test, set up once for one or more windows."""

    def __init__(self, cell: SimpleNamespace, require_tpu: bool = True):
        import jax

        self.jax = jax
        self.cell = cell
        devices = jax.devices()
        if require_tpu:
            if devices[0].platform != "tpu":
                raise BenchError(f"the first device is {devices[0].platform!r}, not a TPU")
            if len(devices) < cell.chips:
                raise BenchError(f"{len(devices)} chips found, the cell asks for {cell.chips}")
            if devices[0].device_kind not in cell.peaks:
                raise BenchError(f"device kind {devices[0].device_kind!r} is not in peaks.json")
        self.devices = devices[: cell.chips]
        self.peaks = cell.peaks.get(devices[0].device_kind, {})
        from repro.core import evaluator
        from repro.core.jobs import JobSpec
        from repro.obs import get_registry, profiling

        self.evaluator, self.JobSpec = evaluator, JobSpec
        self.registry, self.profiling = get_registry(), profiling
        self.algorithms = tuple(cell.traffic["algorithms"])
        # The server count reaches the program only where the configuration names it.
        self.servers = ({"n_servers": cell.config["n_servers"]} if "n_servers" in cell.config
                        else {})
        self.log = CompileLog(jax)

    def jobs(self, sizes, probs):
        return [self.JobSpec(sizes=sizes[i], probs=probs[i], job_id=i) for i in range(len(sizes))]

    def warm_up(self, seed: int) -> None:
        """Score a few job groups of the warm-up stream: every program the
        window calls is then compiled or read from the cache."""
        config = self.cell.config
        for i in range(self.cell.traffic["warmup_trials"]):
            sizes, probs = workgen.job_group(config, workgen.stream(workgen.WARMUP, seed, 2 * i), i)
            rng = workgen.stream(workgen.WARMUP, seed, 2 * i + 1)
            self.evaluator.evaluate_many(self.jobs(sizes, probs), self.algorithms, rng,
                                         **self.servers)

    def window(self, seed: int, seconds: float, trace_dir: str | None = None):
        """Score trials back to back until ``seconds`` have passed.

        Returns the answers (``None`` for a trial that raised), the window's
        seconds, the seconds spent inside ``evaluate_many``, the failures
        and the compiles, cache hits and misses inside the window.
        """
        jax, evaluator = self.jax, self.evaluator
        tracing = trace_dir is not None
        annotate = jax.profiler.TraceAnnotation if tracing else (lambda _: contextlib.nullcontext())
        evaluate = evaluator.evaluate
        if tracing:
            def annotated(jobs, policy, *args, **kwargs):
                with annotate(f"alg.{policy}"):
                    return evaluate(jobs, policy, *args, **kwargs)

            evaluator.evaluate = annotated  # evaluate_many looks it up per call
            self.registry.clear()
            self.profiling.enable()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        answers, eval_s, failed = [], 0.0, 0
        c0 = self.log.mark()
        try:
            with annotate(xplane.WINDOW_SPAN):
                t_start = time.perf_counter()
                while True:
                    t = len(answers)
                    with annotate("trial"):
                        with annotate("generate"):
                            sizes, probs = workgen.trial_group(self.cell.config, seed, t)
                            jobs = self.jobs(sizes, probs)
                            rng = workgen.stream(workgen.RANDOM, seed, t)
                        t1 = time.perf_counter()
                        try:
                            answers.append(evaluator.evaluate_many(jobs, self.algorithms, rng,
                                                                   **self.servers))
                        except Exception:  # noqa: BLE001 - a failed trial is counted, not fatal
                            if not failed:
                                traceback.print_exc()
                            failed += 1
                            answers.append(None)
                        t2 = time.perf_counter()
                    eval_s += t2 - t1
                    if t2 - t_start >= seconds:
                        break
        finally:
            if tracing:
                jax.profiler.stop_trace()
                self.profiling.enable(False)
                evaluator.evaluate = evaluate
        c1 = self.log.mark()
        return SimpleNamespace(
            answers=answers, seconds=t2 - t_start, eval_s=eval_s, failed=failed,
            compiles=c1[0] - c0[0], cache_hits=c1[1] - c0[1], cache_misses=c1[2] - c0[2],
        )

    def memory_peak_bytes(self) -> int:
        stats = [d.memory_stats() or {} for d in self.devices]
        return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    def span_seconds(self) -> dict[str, float]:
        """Total seconds of each program span (``prof.<name>.seconds``)."""
        hist = self.registry.snapshot()["histograms"]
        return {
            k[len("prof."): -len(".seconds")]: v.get("sum", 0.0)
            for k, v in hist.items()
            if k.startswith("prof.") and k.endswith(".seconds")
        }


def per_layer(bench: Bench, win, trace: xplane.Trace) -> tuple[dict, dict]:
    """Each per-layer metric its reader finds, and the readers' notes."""
    ctx = SimpleNamespace(
        trials=len(win.answers), eval_s=win.eval_s, spans=bench.span_seconds(),
        trace=trace, config=bench.cell.config, algorithms=bench.algorithms,
        peaks=bench.peaks, notes={},
    )
    metrics = {}
    for m in bench.cell.per_layer:
        value = load_reader(bench.cell.bench_dir, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, ctx.notes


def check_count(cell: SimpleNamespace) -> int:
    """Trials the check compares: as many as ``check_combinations`` of
    reference work allow, and at least one of each workload set."""
    combos = cell.config["num_stages"] ** cell.config["n_jobs"]
    return max(len(cell.config["workload_sets"]), cell.traffic["check_combinations"] // combos)


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def run(cell: SimpleNamespace, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True) -> tuple[dict, list[str]]:
    """One run: the result line's object and the check lines."""
    t0 = time.perf_counter()
    bench = Bench(cell, require_tpu)
    t1 = time.perf_counter()
    bench.warm_up(seed)
    setup_s = time.perf_counter() - T_START
    compiles, hits, misses = bench.log.mark()
    print(json.dumps({"setup_parts_s": {"start": t0 - T_START, "devices_and_imports": t1 - t0,
                                        "warm_up": T_START + setup_s - t1},
                      "setup_compiles": compiles, "setup_cache_hits": hits,
                      "setup_cache_misses": misses}), file=sys.stderr)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        win = bench.window(seed, seconds, trace_dir)
        memory_peak = bench.memory_peak_bytes()
        reduced = xplane.Trace(xplane.find(trace_dir)) if trace else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps({"trials": len(win.answers), "window_s": win.seconds,
                      "compiles_in_window": win.compiles, "cache_hits_in_window": win.cache_hits,
                      "cache_misses_in_window": win.cache_misses}), file=sys.stderr)
    device = {"platform": bench.devices[0].platform, "kind": bench.devices[0].device_kind,
              "count": len(bench.jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": False, "attempted": len(win.answers), "failed": win.failed}
    if trace:
        metrics, notes = per_layer(bench, win, reduced)
        device.update(busy_s=reduced.busy_s(), window_s=reduced.window_s)
        result["breakdown"] = {"device_ops": top(reduced.op_seconds()),
                               "idle_gaps": top(reduced.idle_gaps())}
        if notes:
            print(json.dumps({"notes": notes}), file=sys.stderr)
    else:
        values = {"trials_per_s": len(win.answers) / win.seconds, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    t_check = time.perf_counter()
    numbers = checks.program_numbers(cell.config, seed, win.answers, bench.algorithms,
                                     check_count(cell))
    print(json.dumps({"check_s": time.perf_counter() - t_check}), file=sys.stderr)
    compared = {k: {"value": v, "limit": cell.limits[k]} for k, v in sorted(numbers.items())}
    result["correct"] = bool(
        win.answers and not win.failed and all(c["value"] <= c["limit"] for c in compared.values())
    )
    result.update(metrics=metrics, device=device, checks=compared)
    lines = [f"check {k} {c['value']!r} limit {c['limit']!r}" for k, c in compared.items()]
    return result, lines


def use_compile_cache() -> None:
    """Keep every compiled program in ``CACHE_DIR``, whatever the
    environment names: the kernels compile in about a second, under JAX's
    default threshold, and a cache shared with another checkout would mix
    two programs' entries."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    use_compile_cache()
    try:
        cell = load_cell(ROOT, args.workload)
        result, lines = run(cell, args.seed, args.seconds, bool(args.trace))
    except BenchError as e:
        print(f"harness: {e}", file=sys.stderr)
        return 1
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
