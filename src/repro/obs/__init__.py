"""Request-to-kernel observability plane (docs/observability.md).

Three cooperating pieces:

* :mod:`repro.obs.registry` — streaming metrics registry (counters,
  gauges, fixed-bucket histograms with p50/p90/p99); the single
  aggregation substrate behind ``serving/metrics.EngineMetrics``.
* :mod:`repro.obs.trace` — the span vocabulary: ``span`` writes the
  engine loop's host spans into a running ``jax.profiler`` trace and, when
  enabled, into the Chrome-trace/Perfetto recorder beside the request
  lifecycle; ``annotate`` puts the same names on the jitted step.
* speculation-quality telemetry — per-step speculative page-hit rate,
  corrected-head count, and selection churn, accumulated **on device**
  inside ``decode_window``'s ``(k, B)`` stat blocks and pulled only at
  sync boundaries (``nonsync_host_bytes`` stays 0 by construction).

``Observability`` bundles the run-level switches; ``ServeEngine`` takes
one and hands it to the scheduler. Metric *values* live in the
per-run registry owned by ``EngineMetrics`` (``eng.last_metrics``), so
exporters always see exactly one run's worth of data.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from repro.obs.registry import (  # noqa: F401  (re-exports)
    COUNT_BUCKETS,
    LATENCY_BUCKETS,
    RATE_BUCKETS,
    SNAPSHOT_SCHEMA_VERSION,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    exponential_buckets,
    linear_buckets,
)
from repro.obs.timeseries import (  # noqa: F401
    DEFAULT_WINDOW_S,
    TIMESERIES_SCHEMA_VERSION,
    TimeSeriesBoard,
    WindowRate,
    WindowStat,
    validate_timeseries_snapshot,
)
from repro.obs.trace import (  # noqa: F401
    SPAN_ATTN_COMPUTE,
    SPAN_DECODE_WINDOW,
    SPAN_RECALL_CORRECTION,
    SPAN_RECALL_REUSE,
    SPAN_RECALL_SELECT,
    SPAN_RECALL_STAGED,
    SPAN_RECALL_TOPUP,
    SPAN_REQUEST_DECODE,
    SPAN_REQUEST_DONE,
    SPAN_REQUEST_PREFILL,
    SPAN_REQUEST_QUEUED,
    TraceRecorder,
    annotate,
    span,
    validate_chrome_trace,
)


@dataclass
class Observability:
    """Run-level observability switches handed to ``ServeEngine``.

    ``enabled`` gates per-step histogram/trace work in the scheduler
    (the registry-backed counters in ``EngineMetrics`` always run — they
    replace the old dataclass fields and cost the same). ``trace`` is
    the span recorder; construct with ``TraceRecorder(enabled=False)``
    to keep lifecycle spans off. ``timeseries`` is the optional
    sliding-window board (``repro.obs.timeseries``) the scheduler feeds
    rolling TTFT/ITL/tokens-per-s/occupancy series into — the payload the
    HTTP front-end serves live at ``/stats``; ``None`` (the default)
    skips all windowed work.
    """

    enabled: bool = True
    trace: TraceRecorder = field(
        default_factory=lambda: TraceRecorder(enabled=False))
    timeseries: "TimeSeriesBoard | None" = None

    @classmethod
    def off(cls) -> "Observability":
        return cls(enabled=False, trace=TraceRecorder(enabled=False))

    @classmethod
    def full(cls) -> "Observability":
        return cls(enabled=True, trace=TraceRecorder(enabled=True),
                   timeseries=TimeSeriesBoard())


def validate_snapshot(snap: dict) -> list:
    """Schema check for ``MetricsRegistry.snapshot()`` dicts / JSONL
    lines (shared by tests and tools/check_obs.py). Returns problems."""
    errors = []
    if not isinstance(snap, dict):
        return ["snapshot is not an object"]
    if snap.get("schema_version") != SNAPSHOT_SCHEMA_VERSION:
        errors.append(f"schema_version != {SNAPSHOT_SCHEMA_VERSION}")
    for sect in ("counters", "gauges", "histograms"):
        if not isinstance(snap.get(sect), dict):
            errors.append(f"missing section {sect!r}")
    for sect in ("counters", "gauges"):
        for name, v in (snap.get(sect) or {}).items():
            if not isinstance(v, (int, float)):
                errors.append(f"{sect}.{name}: non-numeric value")
    for name, h in (snap.get("histograms") or {}).items():
        if not isinstance(h, dict):
            errors.append(f"histograms.{name}: not an object")
            continue
        for key in ("count", "sum", "mean", "p50", "p90", "p99",
                    "buckets", "bucket_counts"):
            if key not in h:
                errors.append(f"histograms.{name}: missing {key!r}")
        bc, b = h.get("bucket_counts"), h.get("buckets")
        if isinstance(bc, list) and isinstance(b, list) \
                and len(bc) != len(b) + 1:
            errors.append(f"histograms.{name}: bucket_counts must have "
                          "len(buckets)+1 entries")
        if isinstance(bc, list) and isinstance(h.get("count"), (int, float)) \
                and sum(bc) != h["count"]:
            errors.append(f"histograms.{name}: bucket_counts don't sum "
                          "to count")
    return errors
