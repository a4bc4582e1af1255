"""Structured telemetry for the crawl stack — dependency-free.

Four small pieces, one coherent layer (replacing the ad-hoc ``print``
breakdown that left BENCH_r05's rc=124 postmortem with nothing but an XLA
platform warning):

- :mod:`.metrics` — named counters, gauges, and phase timers with
  level-indexed breakdowns, grouped into per-component ``Registry``
  objects (each collector server owns one; the in-process driver, the
  RPC leader, and the mesh leader own theirs) plus span-style timing
  contexts that mark "what is running right now" for the heartbeat.
- :mod:`.logs` — structured log emission: human-readable lines by
  default, JSON-lines via ``FHH_LOG_FORMAT=json``; stream and severity
  threshold are env/config knobs.
- :mod:`.heartbeat` — a periodic daemon thread that logs every live
  registry's active span (phase name, level, elapsed), so a wedged run
  shows exactly which phase and level it died in.
- :mod:`.report` — the end-of-run machine-readable report: per-level
  phase seconds, data-plane bytes sent/received, device-fetch counts,
  GC test counts, OT batch sizes, frontier/survivor sizes, checkpoint
  events — everything the registries accumulated, as one JSON document.
- :mod:`.hist` — fixed-bucket latency histograms (log-spaced, mergeable
  across registries/processes) feeding the ``slo`` sections of
  ``status`` and the run report: per-level crawl latency, per-verb RPC
  latency, ingest admit latency, window seal-to-hitters.
- :mod:`.trace` — cross-process distributed tracing: the leader mints a
  trace id per crawl/window, every verb carries a span id, and each
  process appends Chrome-trace events to a JSONL ring under
  ``FHH_TRACE_DIR``; ``python -m fuzzyheavyhitters_tpu.obs.trace merge``
  emits one clock-corrected Perfetto timeline.  ``FHH_PROFILE`` adds
  JAX profiler captures keyed to the same trace ids.
- :mod:`.exporter` — the LIVE plane: a zero-dependency Prometheus
  ``/metrics`` HTTP endpoint (``FHH_METRICS_PORT``; strictly zero-cost
  unset) serving every live registry's counters/gauges/timers plus the
  fixed-bucket histograms as ``_bucket`` series.
- :mod:`.devmem` — device-memory + XLA-compile telemetry: HBM
  in-use/watermark/delta gauges (live-arrays fallback on CPU),
  per-session key-plane residency bytes, and fresh-compile counters
  attributed to the active phase — a recompile past the warmup ladder
  is a named, counted event.
- :mod:`.alerts` — declarative threshold rules (tenant stall, SLO burn,
  ingest backlog, recompile-after-warmup, HBM high water) fired once
  per subject into the logs + trace ring, ``status.alerts``, and the
  run report's ``alerts`` section.
- :mod:`.ops` — ``python -m fuzzyheavyhitters_tpu.obs.ops top``: the
  one-screen live view scraping all three processes' /metrics and
  merging per-collection rows.

Env knobs (all optional):

- ``FHH_LOG_FORMAT``: ``human`` (default) | ``json`` (JSON-lines)
- ``FHH_LOG_STREAM``: ``stderr`` (default) | ``stdout`` | a file path
- ``FHH_LOG_LEVEL``: ``debug`` | ``info`` (default) | ``warn`` | ``error``
- ``FHH_HEARTBEAT_S``: heartbeat period in seconds (``0`` disables; the
  binaries default to 30 s when unset)
- ``FHH_RUN_REPORT``: path; when set, the binaries write the end-of-run
  report there
- ``FHH_TRACE_DIR``: directory; when set, every process appends trace
  events there (off = zero-cost, like ``FHH_DEBUG_GUARDS``);
  ``FHH_TRACE_RING`` bounds events per ring segment
- ``FHH_PROFILE``: directory; wrap each crawl (or only the levels in
  ``FHH_PROFILE_LEVELS=2,5``) in a ``jax.profiler`` capture
- ``FHH_METRICS_PORT``: base port; when set, each process serves
  ``/metrics`` on base + its tag offset (leader +0, s0 +1, s1 +2);
  ``0`` binds an ephemeral port (tests).  ``FHH_METRICS_HOST`` binds a
  non-loopback interface.
- ``FHH_ALERT_STALL_S`` / ``FHH_ALERT_LEVEL_P95_S`` /
  ``FHH_ALERT_BACKLOG_KEYS`` / ``FHH_ALERT_HBM_FRAC``: alert-rule
  thresholds (obs.alerts; defaults 120 / 2.0 / 100000 / 0.9)
"""

from . import alerts, devmem, exporter, trace
from .heartbeat import start_heartbeat, stop_heartbeat
from .hist import Histogram
from .logs import configure as configure_logs, emit
from .metrics import Registry, all_registries, default_registry
from .report import (
    claim_report_path,
    exit_report,
    maybe_write_run_report,
    per_process_report_path,
    run_report,
    write_run_report,
)

__all__ = [
    "Histogram",
    "Registry",
    "alerts",
    "all_registries",
    "claim_report_path",
    "devmem",
    "exporter",
    "configure_logs",
    "default_registry",
    "emit",
    "exit_report",
    "maybe_write_run_report",
    "per_process_report_path",
    "run_report",
    "start_heartbeat",
    "stop_heartbeat",
    "trace",
    "write_run_report",
]
