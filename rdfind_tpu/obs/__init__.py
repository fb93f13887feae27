"""Unified observability: span tracing, metrics registry, HBM watermarks.

The reference publishes accumulators on every run (AbstractFlinkProgram.java:
65-77,175-182 — "always report"); this reproduction outgrew that discipline
piecemeal (DispatchStats, the exchange ledger, ingest lanes, the fault
ladder each with its own dict keys and print format).  ``obs`` is the one
surface they all publish through:

  tracer    hierarchical host spans (run -> stage -> pass -> dispatch/pull/
            exchange/checkpoint) recorded as JSONL events per host and
            exportable as Chrome-trace JSON (Perfetto); host spans emit
            matching jax.profiler.TraceAnnotations so an XLA --profile-dir
            trace lines up with the host timeline.  Off by default and
            near-free when disabled.
  metrics   typed counters/gauges/histograms mirroring every legacy
            ``stats`` key bit-for-bit (the publish shims update both), with
            optional Prometheus text exposition to a file.
  memory    per-pass HBM high-water marks + allocation deltas from jax
            memory stats, with a near-cap warning that fires BEFORE the
            degradation ladder does.
  report    the per-host trace merge tool, Chrome-trace export, and the ONE
            formatter behind --debug / -c counter rendering.
  heartbeat the run's liveness/status file (current stage, pass, last-event
            timestamp) so a wedged run is distinguishable from a slow one.
  flightrec crash-surviving bounded ring of the last N events, dumped
            atomically on signals, fault-ladder rungs and preemptions — the
            post-mortem when the jsonl tracer was off.
  compiles  a per-program compile counter (program, seconds, persistent
            cache hit or miss, open stage) kept from jax.monitoring events
            and reported in RunResult.counters.
  sentinel  the BENCH_HISTORY.jsonl perf series + the noise-aware
            regression gate (``python -m rdfind_tpu.obs.sentinel --check``).

Import-light by design: every submodule is stdlib-only at import time (jax
is imported lazily at call sites), so runtime/dispatch.py and
runtime/faults.py can depend on obs without widening their import footprint.
"""

from __future__ import annotations

from . import (compiles, console, datastats, flightrec,  # noqa: F401
               forecast, heartbeat, memory, metrics, report, sentinel, tracer)


def active() -> bool:
    """Whether any obs output is live (tracing, metrics exposition, or the
    run console) — the gate for sampling work that is pure overhead without
    a consumer (e.g. per-pass HBM watermark reads)."""
    return (tracer.enabled() or metrics.export_requested()
            or console.serving())


def snapshot() -> dict:
    """One JSON-able snapshot of everything obs knows right now: the
    metrics registry (dispatch + exchange + ingest + fault telemetry) and
    the current device-memory watermarks.  Embedded by bench.py in its
    detail rows so every BENCH_* artifact carries one schema."""
    return {
        "metrics": metrics.registry().snapshot(jsonable=True),
        "memory": memory.sample(None, publish=False),
    }
