"""`MetricStream`: streaming per-round metrics out of a running scan
(DESIGN.md §13).

The scan driver's contract (core/rounds.py) is that K federated rounds are
ONE XLA dispatch — which makes the operator blind for the whole dispatch.
The naive fix, a host callback in the scan body, is NOT free (measured on
the CPU backend): the mere presence of a callback custom-call inside
`lax.scan` costs ~0.15-0.3 ms per iteration — comparable to a whole MLP
round — even when `lax.cond`-gated to fire rarely. Worse, ANY effect in a
program — even one no-op callback appended after the scan — drops the
whole executable off the runtime's fast dispatch path, slowing the
unrelated scan itself by a further ~3-4% (measured).

So the tap keeps the compute program 100% pure: K rounds are split into
ceil(K/F) flush-chunks (F = ``flush_every``), each chunk runs through THE
SAME cached jitted scan as the bare engine (`rounds._scan_jit` — literally
the same compiled executable, so trajectories and stacked metrics are
bitwise-identical; pinned in tests/test_obs.py), and right after each
asynchronous chunk dispatch the chunk's stacked metric arrays — still
in-flight device futures — are handed to a daemon *drainer* thread. The
drainer blocks on the futures (off the dispatch path), builds rows, and
feeds the sinks, so rows hit the JSONL file as each chunk completes while
the host keeps enqueueing subsequent chunks. The dispatch thread never
waits on metrics or file I/O; measured overhead of an active stream is
~1-2% at ~0.25 ms/round (benchmarks/obs_bench.py, <5% acceptance bar).

``transport="callback"`` instead flushes each chunk through a separate
tiny jitted program holding one `jax.experimental.io_callback` on the
(F, M) float32 metric matrix + (F,) round numbers. It exists for backends
where host reads of in-flight futures are undesirable, and as the measured
baseline: even this microscopic effectful companion costs ~2.3 ms per
flush on CPU, because ANY effect drops a program off the runtime's fast
dispatch path — which is why it is not the default.

Ordering: one flush per chunk, chunks complete in dispatch order, and the
single drainer consumes a single queue — round flushes AND `emit_event`
rows alike — so everything arrives in dispatch order without any caller
blocking; `sync()` (effects barrier + queue join) makes pending rows
visible when you need to read them. Rate limiting (`log_every`) is
applied host-side in the drainer — the device→host payload is a few KB
per chunk either way, and host-side thinning keeps the compiled programs
independent of the log rate.

Rows are flat dicts (``{"kind": "round", "t": <global round>, <metric>:
float, ...}``) appended to :attr:`MetricStream.rows` and fanned out to the
sinks (obs/sinks.py). `emit_event` lets drivers interleave eval results and
host spans into the same ordered log. Whatever the step's metrics dict
carries streams untouched — a DP run (core/privacy.py, DESIGN.md §15) adds
``dp_epsilon`` (the RDP accountant's composed ε through round t, computed
in-graph from the row's own ``t``), ``dp_clip_frac``, and
``dp_noise_norm`` rows this way, and the run manifest (obs/sinks.py
``extra=``) records the matching calibration + end-of-run ε.
"""
from __future__ import annotations

import queue
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback


def _row_floats(names, vec) -> dict:
    """The ONE place metric values become row values: a float32 cast (on
    device for the callback transport, here otherwise), then python float
    — so streamed rows are bit-identical across drivers and transports.
    `tolist` runs the cast→float loop in C; one np.asarray covers both the
    loop driver's list of scalars and the scan transports' matrix rows."""
    return dict(zip(names, np.asarray(vec, dtype=np.float32).tolist()))


def _drain_loop(stream_ref, q):
    """Daemon drainer: builds rows from raw flushes and feeds the sinks,
    off the dispatch thread. Holds only a weakref to the stream so the
    thread cannot keep it alive; exits once the stream is collected. An
    exception (a failed device computation, a sink that cannot write) is
    kept on the stream and re-raised by :meth:`MetricStream.sync`; the
    thread keeps consuming so ``sync`` never waits on a dead drainer."""
    while True:
        try:
            kind, *item = q.get(timeout=1.0)
        except queue.Empty:
            if stream_ref() is None:
                return
            continue
        stream = stream_ref()
        try:
            if stream is not None and stream._error is None:
                if kind == "rounds":
                    stream._flush_rows(*item)
                else:
                    with stream._lock:
                        stream._emit(item[0])
                        stream._flush_sinks()
        except Exception as e:       # re-raised by sync()
            stream._error = e
        finally:
            del stream
            q.task_done()


class MetricStream:
    """Streams per-round scalar metrics to host sinks while the scan runs.

    Use through the drivers — ``run_rounds(..., obs=stream)`` /
    ``run_feature_rounds(..., obs=stream)`` or any `core.algorithms` /
    `core.baselines` driver's ``obs=`` — or call :meth:`run` directly in
    place of `rounds.scan_rounds`. The returned ``(state, stacked
    metrics)`` are bitwise-identical to the un-observed engine's.

    Parameters
    ----------
    sinks : iterable of sink objects (obs/sinks.py); every row is fanned
        out to each in order. Rows are always also kept in :attr:`rows`.
    log_every : emit every Nth round row (host-side thinning; the global
        round number t is used, so chunking does not shift the cadence).
        Eval/span rows from `emit_event` are never thinned.
    flush_every : rounds per flush-chunk dispatch (F above). Smaller =
        lower latency but more dispatches (chunking a sub-ms-round scan
        into 50-round pieces costs ~5% by itself); the default matches
        the drivers' typical dispatch size so short runs stay one chunk.
        Capped at the dispatch size automatically.
    transport : "future" (default) hands the chunk's in-flight device
        arrays to the drainer thread, which blocks on them off the
        dispatch path — the compute program stays effect-free. "callback"
        flushes through a companion jitted `io_callback` program instead
        (~2.3 ms/flush on CPU; see module docstring).
    """

    def __init__(self, sinks=(), log_every: int = 1, flush_every: int = 200,
                 name: str = "run", transport: str = "future"):
        if transport not in ("future", "callback"):
            raise ValueError(
                f"unknown transport {transport!r} (choose future|callback)")
        self.sinks = tuple(sinks)
        self.log_every = max(1, int(log_every))
        self.flush_every = max(1, int(flush_every))
        self.transport = transport
        self.name = name
        self.rows: list = []
        self._lock = threading.Lock()
        self._queue: queue.Queue | None = None
        self._error: BaseException | None = None    # first drainer failure
        # compiled flush programs, keyed by the step's metric-name tuple
        # (the chunk scans themselves come from rounds.py's weak caches)
        self._flushers: dict = {}

    # -- host side ----------------------------------------------------------

    def _emit(self, row: dict):
        self.rows.append(row)
        for s in self.sinks:
            s.emit(row)

    def _flush_sinks(self):
        for s in self.sinks:
            f = getattr(s, "flush", None)
            if f is not None:
                f()

    def _ensure_drainer(self) -> queue.Queue:
        if self._queue is None:
            self._queue = queue.Queue()
            threading.Thread(target=_drain_loop,
                             args=(weakref.ref(self), self._queue),
                             daemon=True,
                             name=f"obs-drain-{self.name}").start()
        return self._queue

    def _flush_rows(self, names, t_vec, mat):
        """Drainer target: one (F,) t-vector + the chunk's metric columns
        per flush-chunk — an (F, M) np matrix from the callback transport,
        or a list of per-metric device arrays (possibly still in flight)
        from the future transport; blocking on those here is the point.
        The single drainer + the lock keep rows in round order even with
        concurrent emit_event calls."""
        t_list = np.asarray(t_vec).tolist()
        if not isinstance(mat, np.ndarray):
            mat = np.stack([np.asarray(c).astype(np.float32) for c in mat],
                           axis=1)
        # same cast→float convention as _row_floats (f32 astype + tolist),
        # hoisted to ONE C call for the whole chunk: per-row np indexing
        # is most of the drainer's CPU on small hosts
        vals = mat.astype(np.float32, copy=False).tolist()
        with self._lock:
            for i, t in enumerate(t_list):
                t = int(t)
                if t % self.log_every:
                    continue
                # reserved keys last so a step metric literally named "t"
                # or "kind" cannot shadow the row schema (it still reaches
                # the stacked history as round_t / round_kind)
                row = dict(zip(names, vals[i]))
                row["kind"] = "round"
                row["t"] = t
                self._emit(row)
            self._flush_sinks()

    def emit_event(self, row: dict):
        """Append a non-round row (eval result, host span, ledger snapshot)
        to the log, in order with the streamed round rows: once the drainer
        exists, events ride the same queue as the round flushes, so an
        event emitted after a chunk dispatch lands after that chunk's rows
        without anyone blocking."""
        if self._queue is not None:
            self._queue.put(("event", dict(row)))
        else:
            with self._lock:
                self._emit(dict(row))
                self._flush_sinks()

    def sync(self):
        """Block until every dispatched flush has reached the sinks (so
        :attr:`rows` reflects all dispatched rounds). Raises the drainer's
        first exception, if it had one: rows after it were dropped."""
        jax.effects_barrier()
        if self._queue is not None:
            self._queue.join()
        if self._error is not None:
            raise RuntimeError(
                f"metric stream {self.name!r}: the drainer failed, rows "
                "after the failure were dropped") from self._error

    def close(self):
        """Drain pending flushes and close every sink (closed even when the
        drain raises)."""
        try:
            self.sync()
        finally:
            for s in self.sinks:
                s.close()

    # -- device side --------------------------------------------------------

    def _flusher(self, names):
        """The tiny effectful companion program for one metric-name set:
        stacks the chunk's metrics to an (F, M) float32 matrix and hands it
        (with the (F,) round numbers) to the drainer queue via ONE
        io_callback. Cached per names-tuple so the callback closure always
        carries the right column labels."""
        fn = self._flushers.get(names)
        if fn is None:
            stream_ref = weakref.ref(self)
            q = self._ensure_drainer()

            def on_flush(t_vec, mat):
                if stream_ref() is not None:
                    q.put(("rounds", names, np.asarray(t_vec),
                           np.asarray(mat)))

            def flush(t_vec, ms):
                mat = jnp.stack([ms[k].astype(jnp.float32) for k in names],
                                axis=1)
                # one callback per chunk; cross-chunk order comes from
                # dispatch-queue order, so no ordering token is needed
                io_callback(on_flush, None, t_vec, mat, ordered=False)

            fn = jax.jit(flush)
            self._flushers[names] = fn
        return fn

    def run(self, step_fn, state, inputs, driver: str = "scan"):
        """Drop-in replacement for ``rounds.ENGINES[driver](step_fn, state,
        inputs)`` that additionally streams each round's metrics to the
        sinks. Returns the same (state, stacked (K,) metrics) — bitwise.

        Returns as soon as the compute is dispatched and the flushes are
        queued; rows become visible as chunks complete. Call :meth:`sync`
        (or :meth:`close`) before reading :attr:`rows` directly."""
        from repro.core import rounds as rounds_lib

        if driver == "loop":
            return self._run_loop(step_fn, state, inputs)
        if driver != "scan":
            raise ValueError(f"unknown driver {driver!r} (choose scan|loop)")
        k = inputs.num_rounds
        f = min(self.flush_every, k)
        scan = rounds_lib.scan_jit_for(step_fn, state)
        parts = []
        for c0 in range(0, k, f):
            chunk = (inputs if f == k else
                     jax.tree.map(lambda x: x[c0: c0 + f], inputs))
            state, ms = scan(state, chunk)
            if ms:
                names = tuple(sorted(ms))
                if self.transport == "future":
                    # hand the in-flight device arrays straight to the
                    # drainer; it blocks on them off the dispatch path
                    self._ensure_drainer().put(
                        ("rounds", names, chunk.t, [ms[k] for k in names]))
                else:
                    self._flusher(names)(chunk.t, ms)
            parts.append(ms)
        if not parts:
            stacked = {}
        elif len(parts) == 1:
            stacked = parts[0]
        else:
            stacked = {key: jnp.concatenate([p[key] for p in parts])
                       for key in parts[0]}
        return state, stacked

    def _run_loop(self, step_fn, state, inputs):
        """Loop-driver tap: one dispatch per round already returns metrics
        to the host, so rows are built directly (through the same
        `_row_floats` cast as the scan path — bit-identical rows)."""
        from repro.core import rounds as rounds_lib

        step = rounds_lib._step_jit(step_fn)
        ms = []
        for r in range(inputs.num_rounds):
            inp = jax.tree.map(lambda x: x[r], inputs)
            state, m = step(state, inp)
            ms.append(m)
            if not m:
                continue
            t = int(inp.t)
            if t % self.log_every:
                continue
            names = tuple(sorted(m))
            row = _row_floats(names, [np.asarray(m[nm]) for nm in names])
            row["kind"] = "round"
            row["t"] = t
            if self._queue is not None:   # keep order with prior scan runs
                self._queue.put(("event", row))
            else:
                with self._lock:
                    self._emit(row)
                    self._flush_sinks()
        stacked = ({key: jnp.stack([m[key] for m in ms]) for key in ms[0]}
                   if ms else {})
        return state, stacked
