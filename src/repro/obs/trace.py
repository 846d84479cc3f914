"""Span-based phase tracing for the federated round (DESIGN.md §13).

Two clocks, one vocabulary:

* **In-jit phases** (`phase`): `jax.named_scope` annotations compiled into
  the HLO metadata, so an xprof/perfetto dump attributes device time to
  protocol phases — ``round → cohort-select → batch-select →
  client-compute (→ mla-attention, moe-dispatch, expert-compute inside a
  language-model client) → dp-privatize → codec-encode → ef-gather/ef-scatter →
  aggregate → collective → head-compute → surrogate-solve →
  round-metrics`` (`PHASES`). Scopes are free at runtime (they only label
  ops at trace time) and therefore safe on the hot path; they are applied
  inside `core/topology.py`, `core/optimizer.py`, `core/fed.py`,
  `core/algorithms.py`, `core/baselines.py` and the round drivers
  unconditionally.
* **Host spans** (`host_span`): `jax.profiler.TraceAnnotation`s, events of
  the profiler's host plane on the same clock as the device planes, so an
  idle gap of the device can be put down to what the host was doing.
  `core/rounds.run_rounds` runs each chunk under ``rounds/inputs``,
  ``rounds/launch`` and ``rounds/eval`` and the history assembly under
  ``rounds/history`` (`DRIVER_SPANS`); with the profiler off each costs one
  annotation object. `HostSpans` adds wall-clock timing at dispatch
  boundaries — the scan dispatch itself, eval hooks, checkpoint writes — as
  plain rows (``kind="span"``) emitted through the sink API, so a JSONL log
  interleaves rounds, evals, and spans in order.

`profile(logdir)` wraps a whole run in `jax.profiler.start_trace` /
`stop_trace`; the resulting directory opens in xprof/perfetto and contains
the named scopes and host spans above (exercised by the CI obs-smoke job).
"""
from __future__ import annotations

import contextlib
import functools
import os
import time

import jax

# the phase names the program uses, in protocol order (DESIGN.md §13);
# tests/test_obs.py holds every literal passed to `phase`/`scoped` to it
PHASES = ("round", "cohort-select", "batch-select", "client-compute",
          "mla-attention", "moe-dispatch", "expert-compute",
          "dp-privatize", "codec-encode", "ef-gather", "ef-scatter",
          "aggregate", "collective", "head-compute", "surrogate-solve",
          "round-metrics")

# the round driver's host spans (core/rounds.run_rounds), in the order a
# chunk runs them; ``rounds/history`` runs once per call
DRIVER_SPANS = ("rounds/inputs", "rounds/launch", "rounds/eval",
                "rounds/history")


def phase(name: str):
    """In-jit phase annotation: a `jax.named_scope` context manager. Use
    around trace-time code regions; compiles to op metadata, costs nothing
    at runtime."""
    return jax.named_scope(name)


def scoped(name: str, fn=None):
    """Wrap fn so every call runs under `phase(name)`. Usable directly —
    ``scoped("round", step_fn)`` (the round drivers label the scanned step
    this way) — or as a decorator: ``@scoped("surrogate-solve")``."""
    if fn is None:
        return lambda f: scoped(name, f)

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)
    return wrapped


def host_span(name: str, **attrs):
    """A host span on the profiler timeline: `jax.profiler.TraceAnnotation`
    ``name`` with ``attrs`` as its metadata. Emits no row; with the profiler
    off it costs one annotation object."""
    return jax.profiler.TraceAnnotation(name, **attrs)


class HostSpans:
    """Host-side wall-clock spans at dispatch boundaries.

    Each completed span emits ``{"kind": "span", "span": name,
    "dur_s": ..., **attrs}`` through the attached stream (any object with
    ``emit_event(row)``, e.g. `obs.metrics.MetricStream`), so the JSONL log
    carries dispatch timings next to the round rows they bracket. The span
    body also runs under `host_span(name, **attrs)`, putting the same name
    on the profiler timeline.
    """

    def __init__(self, stream=None):
        self.stream = stream

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        t0 = time.perf_counter()
        with host_span(name, **attrs):
            yield
        row = {"kind": "span", "span": name,
               "dur_s": time.perf_counter() - t0}
        row.update(attrs)
        if self.stream is not None:
            self.stream.emit_event(row)


@contextlib.contextmanager
def profile(logdir: str):
    """Profile the enclosed block with `jax.profiler` into ``logdir``
    (created if missing). The dump contains the `phase` named scopes and
    every `host_span`; open it with xprof or ui.perfetto.dev."""
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()
