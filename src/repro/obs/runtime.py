"""The ``Obs`` facade — one capture object for a live train/serve run.

Ties the registry (``repro.obs.metrics``), the span sink
(``repro.obs.spans``) and the exporters (``repro.obs.export``) together and
owns the built-in instrumentation seams:

  * :meth:`Obs.attach_engine` — hooks the execution engine's dispatch
    tracer (``repro.kernels.engine.set_tracer``) and turns every kernel
    dispatch into ``engine.dispatch`` counters (labeled ``part``/``op``/
    ``backend``/``impl``), ``engine.grid_steps_compiled``,
    ``engine.gathers`` (B rows the BCSR kernel's steps fetch),
    ``engine.gather_dmas`` (the DMA descriptors that fetch them) and
    ``engine.const_bytes`` (host arrays turned into program constants)
    counters.  Dispatches fire at *trace* time — once per jit compilation
    — so these are compilation-workload counters, deliberately not
    per-execution (the per-execution signal is the step/request latency
    recorded on the host).  The hook **chains**: a previously installed
    tracer (e.g. a ``repro.perf.trace.TraceRecorder``) keeps receiving
    every dispatch.
  * :meth:`Obs.watch_cache` — registers a ``repro.tune.PlanCache`` whose
    ``CacheStats`` are exported as ``tune.cache.*`` gauges at snapshot
    time (pull model: the cache is read when records are exported, so the
    hit rate reflects the whole run).
  * :meth:`Obs.wrap_step` — wraps a jitted step function (the
    ``dist/step.py`` builder products take ``obs=``): each call runs under
    a span, blocks on its outputs, and lands one observation in the
    ``step.wall_us`` histogram for its ``op``.

and the rendezvous of the *active* capture (:func:`set_active`), which code
far from any ``Obs`` reports to without holding one:

  * :func:`span` — a program span (``loops.coo_to_csr``, ``loops.format``,
    ``loops.panelize``, ``loops.transpose``) on the active capture; a
    shared no-op without one; :func:`count` — a counter on it
    (``loops.transpose_entries``), nothing without one;
  * collective accounting — ``repro.dist.compress.compressed_psum``
    reports its per-call wire bytes (trace-time, so the gauge is
    bytes-per-call and the counter counts compiled call sites);
  * compile accounting — one process-wide ``jax.monitoring`` listener,
    registered when a capture is first made active, turns jax's compile
    and persistent-cache events into ``compile.*`` counters;
  * degraded paths — ``repro.resilience.inject.note_degraded`` counts
    fallbacks (``engine.fallback``, ``dist.fallback``, ...).

``save()`` writes both serialisations (JSONL + Chrome trace) next to each
other under ``benchmarks/results/obs/`` by default.
"""
from __future__ import annotations

import collections
import functools
import pathlib
import threading
import time
from typing import Dict, Optional, Tuple

from .export import (chrome_trace, default_obs_dir, obs_records, write_chrome_trace,
                     write_jsonl)
from .metrics import MetricsRegistry
from .spans import SpanSink

__all__ = ["Obs", "set_active", "get_active", "span", "count",
           "note_collective"]

# Process-wide active capture (the collective hook's rendezvous; launch
# drivers install their Obs here for the duration of a run).
_ACTIVE: Optional["Obs"] = None


def set_active(obs: Optional["Obs"]) -> Optional["Obs"]:
    """Install ``obs`` as the process-wide capture (None detaches);
    returns the previous one so callers can restore it.  The first capture
    made active registers the compile listener (:class:`_CompileListener`)."""
    global _ACTIVE
    if obs is not None:
        _CompileListener.install()
    prev, _ACTIVE = _ACTIVE, obs
    return prev


def get_active() -> Optional["Obs"]:
    return _ACTIVE


class _NoSpan:
    """What :func:`span` gives without an active capture: records nothing."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass

    def fence(self, *values) -> None:
        pass


_NO_SPAN = _NoSpan()


def span(name: str, **args):
    """A program span named ``name`` on the active capture (category
    ``program``), or the shared no-op :class:`_NoSpan` without one."""
    obs = _ACTIVE
    if obs is None:
        return _NO_SPAN
    return obs.span(name, cat="program", **args)


def count(name: str, n: float = 1.0, **labels) -> None:
    """Add ``n`` to the counter ``name`` of the active capture; nothing
    without one."""
    if _ACTIVE is not None:
        _ACTIVE.counter(name, **labels).inc(n)


class _CompileListener:
    """jax's compile events as ``compile.*`` counters of the active capture.

    jax times each stage of a compile in a region that opens with a scalar
    event (its start time, with ``fun_name``) and closes with a duration
    event of the same name:

    * ``jaxpr_trace_duration`` and ``jaxpr_to_mlir_module_duration`` add to
      ``compile.trace_lower_s{fun_name}``.  Tracing an outer function
      traces the jitted functions it calls inside its own region; only
      the outermost region of a thread counts, so nothing counts twice;
    * ``backend_compile_duration`` encloses the persistent-cache lookup.
      A hit inside it records ``cache_retrieval_time_sec``, which goes to
      ``compile.cache_load_s{fun_name}`` and is taken off the region:
      ``compile.xla_s{fun_name}`` is XLA compile alone, so a hit reads as
      a load and not as a compile;
    * ``cache_hits`` and ``cache_misses`` (jax counts a miss when it writes
      the compiled program to the cache) count into
      ``compile.cache_hits{fun_name}`` and ``compile.cache_misses{fun_name}``,
      labelled by the compile they happen in.
    """

    TRACE_LOWER = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration")
    XLA = "/jax/core/compile/backend_compile_duration"
    CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
    CACHE_COUNTS = {"/jax/compilation_cache/cache_hits": "compile.cache_hits",
                    "/jax/compilation_cache/cache_misses":
                        "compile.cache_misses"}
    _installed = False
    _lock = threading.Lock()

    @classmethod
    def install(cls) -> None:
        """Register one listener for the process (idempotent)."""
        with cls._lock:
            if cls._installed:
                return
            from jax import monitoring
            listener = cls()
            monitoring.register_scalar_listener(listener.on_start)
            monitoring.register_event_duration_secs_listener(
                listener.on_duration)
            monitoring.register_event_listener(listener.on_event)
            cls._installed = True

    def __init__(self):
        self._local = threading.local()

    def _state(self):
        st = self._local
        if not hasattr(st, "depth"):
            st.depth = collections.Counter()
            st.compiling = "?"     # fun_name of the open backend compile
            st.loaded = 0.0        # cache load seconds inside it
        return st

    def on_start(self, event: str, value, fun_name: str = "?", **_) -> None:
        if event in self.TRACE_LOWER or event == self.XLA:
            st = self._state()
            st.depth[event] += 1
            if event == self.XLA:
                st.compiling, st.loaded = fun_name, 0.0

    def on_duration(self, event: str, secs: float, fun_name: str = "?",
                    **_) -> None:
        st = self._state()
        if event == self.CACHE_LOAD:
            st.loaded += secs
            self._count("compile.cache_load_s", st.compiling, secs)
            return
        if event not in self.TRACE_LOWER and event != self.XLA:
            return
        depth = st.depth[event] - 1
        st.depth[event] = max(depth, 0)
        if depth > 0:
            return          # nested in an outer region of the same stage
        if event == self.XLA:
            self._count("compile.xla_s", fun_name, max(secs - st.loaded, 0.0))
            st.loaded = 0.0
        else:
            self._count("compile.trace_lower_s", fun_name, secs)

    def on_event(self, event: str, **_) -> None:
        name = self.CACHE_COUNTS.get(event)
        if name is not None:
            self._count(name, self._state().compiling, 1.0)

    @staticmethod
    def _count(name: str, fun_name: str, value: float) -> None:
        obs = _ACTIVE
        if obs is not None:
            obs.metrics.counter(name, fun_name=fun_name).inc(value)


def note_collective(nbytes: int, *, kind: str, precision: str) -> None:
    """Report one collective call site's per-call wire bytes to the active
    capture (no-op without one).  Called from inside traced code, so it
    fires once per compilation: ``dist.collective_bytes`` is a
    bytes-per-call gauge and ``dist.collective_sites`` counts compiled
    call sites — never per-execution totals."""
    obs = _ACTIVE
    if obs is None:
        return
    obs.metrics.gauge("dist.collective_bytes", kind=kind,
                      precision=precision).set(float(nbytes))
    obs.metrics.counter("dist.collective_sites", kind=kind,
                        precision=precision).inc()


class _EngineTracer:
    """Adapter from the engine's ``on_dispatch`` hook to obs instruments,
    forwarding every event to a previously installed tracer."""

    def __init__(self, obs: "Obs", prev=None):
        self.obs = obs
        self.prev = prev

    def on_dispatch(self, *, part: str, op: str, **fields) -> None:
        m = self.obs.metrics
        m.counter("engine.dispatch", part=part, op=op,
                  backend=fields.get("backend", "?"),
                  impl=fields.get("impl", "?")).inc()
        steps = fields.get("steps")
        if steps is not None:
            m.counter("engine.grid_steps_compiled", part=part,
                      op=op).inc(float(steps))
        cb = fields.get("const_bytes")
        if cb:
            m.counter("engine.const_bytes", part=part).inc(float(cb))
        gathers = fields.get("gathers")
        if gathers is not None:
            m.counter("engine.gathers", part=part).inc(float(gathers))
        dmas = fields.get("dmas")
        if dmas is not None:
            m.counter("engine.gather_dmas", part=part).inc(float(dmas))
        sb = fields.get("scratch_bytes")
        if sb is not None:
            m.gauge("kernel.scratch_bytes", part=part, op=op).set(float(sb))
        if self.prev is not None:
            self.prev.on_dispatch(part=part, op=op, **fields)


class _Attach:
    def __init__(self, obs: "Obs"):
        self.obs = obs

    def __enter__(self):
        from ..kernels import engine
        self._prev = engine.set_tracer(_EngineTracer(self.obs,
                                                     prev=engine.get_tracer()))
        return self.obs

    def __exit__(self, *exc):
        from ..kernels import engine
        engine.set_tracer(self._prev)
        return False


class Obs:
    """One observability capture: metrics + spans + exporters."""

    def __init__(self, source: str = "run"):
        self.source = source
        self.metrics = MetricsRegistry()
        self.sink = SpanSink(on_drop=self._on_span_drop)
        self.started_at = time.time()   # wall epoch, metadata only — all
        # interval timing inside the capture is perf_counter-based

    # -- spans -------------------------------------------------------------

    def _on_span_drop(self, name: str) -> None:
        self.metrics.counter("obs.spans_dropped_traced", span=name).inc()

    def span(self, name: str, cat: str = "obs", **args):
        """Open a wall-clock span (see :mod:`repro.obs.spans`); inside an
        abstract trace this records nothing and counts a drop instead."""
        return self.sink.span(name, cat=cat, **args)

    # -- instruments (delegates) ------------------------------------------

    def counter(self, name: str, **labels):
        return self.metrics.counter(name, **labels)

    def gauge(self, name: str, **labels):
        return self.metrics.gauge(name, **labels)

    def histogram(self, name: str, buckets=None, **labels):
        return self.metrics.histogram(name, buckets=buckets, **labels)

    # -- engine seam -------------------------------------------------------

    def attach_engine(self) -> _Attach:
        """Context manager installing the dispatch adapter on the engine
        tracer hook (chains to any tracer already installed)."""
        return _Attach(self)

    # -- tuner seam --------------------------------------------------------

    def watch_cache(self, cache, name: str = "plan") -> None:
        """Export ``cache.stats`` (a ``repro.tune.CacheStats``) as
        ``tune.cache.*`` gauges whenever records are exported."""
        self._caches = getattr(self, "_caches", [])
        self._caches.append((name, cache))

    def _collect_caches(self) -> None:
        for name, cache in getattr(self, "_caches", []):
            st = cache.stats
            self.metrics.gauge("tune.cache.hits", cache=name).set(st.hits)
            self.metrics.gauge("tune.cache.near_hits",
                               cache=name).set(st.near_hits)
            self.metrics.gauge("tune.cache.misses", cache=name).set(st.misses)
            self.metrics.gauge("tune.cache.hit_rate",
                               cache=name).set(st.hit_rate)
            # distinct name from the ``tune.cache.quarantined`` *counter*
            # (note_degraded): the registry forbids one name in two kinds
            self.metrics.gauge("tune.cache.quarantined_files",
                               cache=name).set(st.quarantined)

    # -- step seam ---------------------------------------------------------

    def wrap_step(self, fn, *, op: str):
        """Wrap a (jitted) step function: every call runs under a span,
        blocks on its outputs (the async dispatch tail lands inside the
        measured interval) and records ``step.wall_us{op=...}``."""
        hist = self.metrics.histogram("step.wall_us", op=op)
        counter = [0]

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            t0 = time.perf_counter()
            with self.span(f"step.{op}", cat="step", step=counter[0]) as sp:
                out = fn(*args, **kwargs)
                sp.fence(out)
            hist.observe((time.perf_counter() - t0) * 1e6)
            counter[0] += 1
            return out

        return wrapped

    # -- readout / persistence --------------------------------------------

    def records(self):
        self._collect_caches()
        return obs_records(self)

    def chrome(self) -> Dict:
        self._collect_caches()
        return chrome_trace(self)

    def summary(self) -> Dict:
        """Small human-oriented digest (the launch drivers print this)."""
        self._collect_caches()
        out: Dict = {"source": self.source, "spans": len(self.sink.events)}
        dispatches = sum(
            inst.value for kind, inst in self.metrics.instruments()
            if kind == "counter" and inst.name == "engine.dispatch")
        out["engine_dispatches"] = int(dispatches)
        for kind, inst in self.metrics.instruments():
            if kind == "hist" and inst.count:
                label = ",".join(f"{k}={v}"
                                 for k, v in sorted(inst.labels.items()))
                key = f"{inst.name}{{{label}}}" if label else inst.name
                s = inst.summary()
                out[key] = {"count": s["count"],
                            "p50_us": round(s["p50"], 1),
                            "p99_us": round(s["p99"], 1)}
        return out

    def save(self, directory=None, stem: Optional[str] = None
             ) -> Tuple[pathlib.Path, pathlib.Path]:
        """Write ``<stem>.jsonl`` and ``<stem>.trace.json`` (Chrome trace)
        under ``directory`` (default ``benchmarks/results/obs/``); returns
        both paths.  Deterministic names — a re-run replaces the previous
        capture instead of accumulating."""
        directory = pathlib.Path(directory) if directory is not None \
            else default_obs_dir()
        stem = stem or self.source
        jsonl = write_jsonl(self.records(), directory / f"{stem}.jsonl")
        chrome = write_chrome_trace(self.chrome(),
                                    directory / f"{stem}.trace.json")
        return jsonl, chrome
