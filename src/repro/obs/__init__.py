"""Observability layer for the scan-compiled FL stack (DESIGN.md §13).

Three pieces, importable separately (none of them imports repro.core at
module scope, so core modules are free to use `repro.obs.trace` phases):

* :mod:`repro.obs.metrics` — `MetricStream`, the streaming tap that gets
  per-round scalar metrics OUT of a running ``lax.scan`` dispatch via a
  chunked, ordered `io_callback`, without unrolling the scan or changing
  the trajectory (bitwise — pinned in tests/test_obs.py).
* :mod:`repro.obs.trace` — `phase` (in-jit `jax.named_scope` annotations
  for the protocol phases listed in `trace.PHASES`: round → cohort-select →
  batch-select → client-compute → dp-privatize → codec-encode →
  ef-gather/ef-scatter → aggregate → collective → head-compute →
  surrogate-solve → round-metrics), `host_span` (a
  `jax.profiler.TraceAnnotation`; the round driver's ``rounds/inputs``,
  ``rounds/launch``, ``rounds/eval`` and ``rounds/history``), `HostSpans`
  (host wall-clock span rows at dispatch boundaries), and `profile(dir)`
  (an xprof/perfetto trace of the whole run).
* :mod:`repro.obs.sinks` — pluggable row consumers (JSONL/CSV/stdout/
  memory), the run manifest (config, mesh, codec, topology, git sha,
  per-dispatch HLO cost), and `bench_json` (the BENCH_*.json emitter the
  benchmarks share).
"""
from repro.obs.metrics import MetricStream
from repro.obs.sinks import (CsvSink, JsonlSink, MemorySink, StdoutSink,
                             bench_json, run_manifest, write_manifest)
from repro.obs.trace import HostSpans, phase, profile

__all__ = [
    "MetricStream", "JsonlSink", "CsvSink", "StdoutSink", "MemorySink",
    "bench_json", "run_manifest", "write_manifest", "HostSpans", "phase",
    "profile",
]
