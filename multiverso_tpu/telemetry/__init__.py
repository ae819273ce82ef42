"""Telemetry spine (PAPER/SURVEY §6.1: per-step wall-clock dashboard +
profiler hooks): typed metrics, span tracing, multihost aggregation,
and a report CLI.

- :mod:`multiverso_tpu.telemetry.metrics` — Counter/Gauge/Histogram in
  a process-wide registry; JSONL event sink (``MVTPU_METRICS_JSONL``),
  JSON snapshots, Prometheus text export.
- :mod:`multiverso_tpu.telemetry.trace` — :func:`span`, the one timing
  primitive with three outputs (a ``jax.profiler.TraceAnnotation`` on
  any profile being taken, the ``span.seconds{name=…}`` histogram, a
  JSONL record when ``MVTPU_TRACE_JSONL`` / ``MVTPU_TRACE_DIR`` set a
  sink); :func:`scope` (``jax.named_scope``) names the ops of a traced
  body; per-superstep :func:`step_timeline`.
- :mod:`multiverso_tpu.telemetry.aggregate` — :func:`gather_metrics` /
  :func:`fleet_snapshot` all-gather per-host snapshots through the mesh
  (single-host fallback: local only).
- :mod:`multiverso_tpu.telemetry.watchdog` — the flight recorder's
  stall side: heartbeat :class:`Watchdog` (+ module-level :func:`beat`)
  that dumps all-thread stacks, a metrics snapshot, queue gauges, SLO
  violations, and the trace tail into ``MVTPU_DUMP_DIR`` on a missed
  deadline, then optionally self-terminates
  (``MVTPU_WATCHDOG_ACTION``).
- :mod:`multiverso_tpu.telemetry.statusz` — live introspection over
  stdlib HTTP (``MVTPU_STATUSZ_PORT``): ``/metrics`` (Prometheus),
  ``/healthz`` (watchdog heartbeats), ``/statusz`` (topology, tables,
  kernel engines, checkpoints, queues), ``/trace`` (span tail).
- :mod:`multiverso_tpu.telemetry.slo` — declarative tail-latency SLO
  rules (``MVTPU_SLO=table.add.p99<5ms,...``) evaluated on snapshot
  cadence; violations counted and escalated through the watchdog
  warn → dump path.
- :mod:`multiverso_tpu.telemetry.health` — training-health monitor:
  fused device-side numerics stats (``ops/stat_kernels.py``) folded
  into per-table EWMA drift windows, a ``MVTPU_HEALTH`` rule grammar
  mirroring the SLO one, and ``MVTPU_HEALTH_ACTION=dump|rollback``
  escalation closing the loop into the ``ft/`` checkpoint machinery.
- :mod:`multiverso_tpu.telemetry.profiling` — the compile side:
  :func:`profiled_jit` (lowering/compile wall time + XLA cost/memory
  analysis per jitted function), :func:`record_device_memory`
  (live-buffer and allocator gauges), :func:`profile_window`
  (``MVTPU_PROFILE_DIR``-gated ``jax.profiler`` capture),
  :func:`op_scopes` (compiled instruction -> program scope).
- ``python -m multiverso_tpu.telemetry.report <file>`` — render any
  telemetry artifact as a table, Perfetto-loadable Chrome trace
  (``--chrome-trace``), or hot list (``--top N``).

The legacy ``utils.dashboard`` API (``profile`` / ``emit_metric`` /
``report``) keeps working as a shim over this registry.
"""

from multiverso_tpu.telemetry import (aggregate, metrics, profiling,
                                      trace, watchdog)
from multiverso_tpu.telemetry.aggregate import (fleet_snapshot,
                                                gather_metrics,
                                                merge_snapshots)
from multiverso_tpu.telemetry.metrics import (LATENCY_BUCKETS, Counter,
                                              Gauge, Histogram,
                                              MetricRegistry,
                                              QueueGauges, counter,
                                              emit, gauge, histogram,
                                              host_index,
                                              log_spaced_bounds,
                                              registry, snapshot,
                                              snapshot_quantile,
                                              write_snapshot)
from multiverso_tpu.telemetry.profiling import (op_scopes,
                                                profile_window,
                                                profiled_jit,
                                                record_device_memory)
from multiverso_tpu.telemetry.trace import (adopt, current_request,
                                            link, new_request_id,
                                            read_trace, request, scope,
                                            set_trace_file, span,
                                            step_timeline)
from multiverso_tpu.telemetry.watchdog import (Watchdog,
                                               active_watchdogs, beat,
                                               maybe_watchdog)
# statusz/slo/health import AFTER the siblings above: they resolve
# metrics/trace/watchdog through the already-bound package attributes
from multiverso_tpu.telemetry import health, slo, statusz
from multiverso_tpu.telemetry.health import (HealthMonitor,
                                             maybe_health_monitor)
from multiverso_tpu.telemetry.slo import SloMonitor, maybe_slo_monitor
from multiverso_tpu.telemetry.statusz import (StatuszServer,
                                              maybe_statusz,
                                              publish_fleet)

__all__ = [
    "aggregate", "health", "metrics", "profiling", "slo", "statusz",
    "trace", "watchdog",
    "Counter", "Gauge", "Histogram", "MetricRegistry", "QueueGauges",
    "LATENCY_BUCKETS", "log_spaced_bounds", "snapshot_quantile",
    "counter", "gauge", "histogram", "emit", "host_index", "registry",
    "snapshot", "write_snapshot",
    "span", "scope", "step_timeline", "set_trace_file", "read_trace",
    "request", "new_request_id", "current_request", "link", "adopt",
    "gather_metrics", "merge_snapshots", "fleet_snapshot",
    "Watchdog", "beat", "maybe_watchdog", "active_watchdogs",
    "SloMonitor", "maybe_slo_monitor",
    "HealthMonitor", "maybe_health_monitor",
    "StatuszServer", "maybe_statusz", "publish_fleet",
    "profiled_jit", "profile_window", "record_device_memory",
    "op_scopes",
]
