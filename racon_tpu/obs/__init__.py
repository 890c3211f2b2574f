"""Unified trace/metrics subsystem for the streaming pipeline.

The reference instruments its GPU path with nvprof ranges
(src/cuda/cudapolisher.cpp:66-70) plus a stage ``Logger``; after the
r8 streaming pipeline this codebase is a concurrent system (align
ladder + speculative POA consumer + watcher threads + double-buffered
dispatch) whose timing story needs first-class tooling:

* :mod:`racon_tpu.obs.trace` — a thread-safe span tracer emitting
  **Chrome trace-event JSON** (loadable in Perfetto /
  ``chrome://tracing``).  Spans are nested per thread (stage → align
  rung → pack/wait/decode, POA pack/wait/extract) and device
  dispatches get their own virtual "device" lanes fed by the watcher
  threads.  Every :func:`span` also enters
  ``jax.profiler.TraceAnnotation``, so it lands in a concurrent JAX
  profile on the profiler's clock and names the device's idle gaps;
  pool-worker spans (CPU lanes, breaking-point decode) are kept to the
  Chrome JSON.
* :mod:`racon_tpu.obs.metrics` — a process-wide metrics registry
  (counters / gauges / histograms) that is the single source of truth
  for every number ``bench.py`` used to tally privately:
  ``poa_device_s``, ``align_wfa_device_s`` / ``align_band_device_s``,
  ``pipeline_overlap_s``, ``poa_spec_used`` / ``poa_spec_wasted`` /
  ``poa_spec_skipped``, AOT-shelf hit/miss/fallback, ladder rung
  admissions/retries, the WindowLedger ready-queue high-water mark.  Each polisher owns a
  per-run child registry that propagates into the global one.
* :mod:`racon_tpu.obs.provenance` — per-run environment provenance
  (resolved ``RACON_TPU_*`` knobs, jax backend, host-capability
  probe) and the ``--metrics-json`` run-report writer.
* :mod:`racon_tpu.obs.context` — request-scoped job identity
  (``job_id``/``tenant``/``trace_id`` contextvar) entered by the
  serve scheduler around each job; the tracer, flight recorder and
  logger auto-tag whatever is recorded under it.
* :mod:`racon_tpu.obs.aggregate` — exact cross-process merging of
  registry snapshots (counters sum, gauges keep per-source values,
  fixed-ladder histograms merge bucket-wise so fleet percentiles are
  bit-for-bit the union stream's) — the substrate of the r15 fleet
  telemetry plane (racon_tpu/serve/fleet.py).
* :mod:`racon_tpu.obs.flight` — an always-on bounded ring of
  structured events (admits, rejects, fused dispatches, errors with
  tracebacks), dumped on crash/drain and readable live over the
  serve socket — crash forensics for the daemon.
* :mod:`racon_tpu.obs.decision` — the decision-record plane (r16):
  a bounded exemplar ring of placement decisions (align ladder path,
  POA split/speculation, shelf variant contacts) tagged with job
  context, behind the ``explain`` op and ``racon-tpu explain``.
* :mod:`racon_tpu.obs.calhealth` — per-stage predicted-vs-actual
  drift ratios (EWMA + p50/p99 in the registry) with advisory
  recalibration flags — the calibration-health model the explain
  waterfall, ``top`` drift column and bench-gate DRIFT warning read.

Determinism contract: clocks here feed ONLY the trace and the
metrics, never control flow — a tracing-enabled run emits
byte-identical output to a tracing-off run (pinned by
tests/test_obs.py and tests/test_pipeline.py).

All raw timing in ``racon_tpu/`` goes through :func:`now` (the lint in
ci/cpu/obs_tier1.sh and tests/test_obs.py fails on raw
``time.monotonic`` calls outside this package and utils/logger.py).
"""

from __future__ import annotations

from racon_tpu.obs.aggregate import merge_histograms, merge_snapshots
from racon_tpu.obs.calhealth import DRIFT_BAND
from racon_tpu.obs.context import (JobContext, current, job_context,
                                   jobs_for_tenant, valid_trace_id)
from racon_tpu.obs.decision import DECISIONS, DecisionRecorder
from racon_tpu.obs.devutil import DEVICE_UTIL, DeviceUtil
from racon_tpu.obs.flight import FLIGHT, FlightRecorder
from racon_tpu.obs.metrics import (HIST_BUCKETS, REGISTRY, MetricAttr,
                                   Registry, hist_quantile)
from racon_tpu.obs.trace import (TRACER, enable_trace, now, span,
                                 wall_now, write_trace)

__all__ = [
    "REGISTRY", "Registry", "MetricAttr", "TRACER",
    "HIST_BUCKETS", "hist_quantile", "DEVICE_UTIL", "DeviceUtil",
    "now", "wall_now", "span", "enable_trace",
    "write_trace",
    "JobContext", "job_context", "current", "jobs_for_tenant",
    "valid_trace_id", "FLIGHT", "FlightRecorder",
    "DECISIONS", "DecisionRecorder", "DRIFT_BAND",
    "merge_histograms", "merge_snapshots",
]
