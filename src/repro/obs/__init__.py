"""Observability: trace recording, metrics, profiling (docs/observability.md).

Three pieces, wired through every scheduling layer:

* :class:`TraceRecorder` (:mod:`repro.obs.recorder`) — a batching DES
  observer exporting Chrome-trace/Perfetto JSON, per-server Gantt
  tables and queue-depth / utilization series from both frontends
  (``simulate(..., recorder=...)``, ``ClusterManager.run(recorder=...)``).
* :class:`MetricsRegistry` (:mod:`repro.obs.metrics`) — counters /
  gauges / histograms with a JSON snapshot, replacing ad-hoc result
  dicts; both frontends populate it via ``metrics=``.
* :mod:`repro.obs.profiling` — opt-in wall-clock spans around the fused
  ``sojourn_eval`` ops and the workload-cache tiers, surfaced in the
  same registry snapshot.

``python -m repro.obs.report`` replays a synthetic Philly-trace
workload and writes the trace + metrics artifacts.

The recorder is imported on first use: it depends on ``repro.core``,
whose evaluator imports the kernels, which import
:mod:`repro.obs.profiling`.  Loading it eagerly here would make
``import repro.kernels.sojourn_eval`` circular.
"""

from repro.obs.metrics import (  # noqa: F401
    MetricsRegistry,
    format_snapshot,
    get_registry,
    record_run_metrics,
)

_RECORDER_NAMES = ("TraceRecorder", "validate_chrome_trace")


def __getattr__(name):
    if name in _RECORDER_NAMES:
        from repro.obs import recorder

        return getattr(recorder, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
