"""Wall-clock spans with thread-local nesting — the attribution half of obs.

A span is one timed region of host execution::

    with obs.span("serve.decode_step", token=i) as sp:
        cache, logits = serve_fn(params, cache, toks, pos)
        sp.fence(logits)        # block_until_ready before the clock stops

Completed spans become Chrome-trace ``"X"`` (complete) events: name,
category, start timestamp (µs since the recorder's epoch), duration, thread
id and a free-form ``args`` dict.  Nesting is structural — each thread keeps
its own span stack, a child opened under a parent always closes before it —
so the exported events are properly nested per thread and Perfetto renders
them as a flame graph without any reparenting pass.  Each event also
carries a process-wide ``id`` and the ``parent`` id of the span open around
it on its thread (None at the top), so a reader can follow cause to effect.

While it is open a span also holds a ``jax.profiler.TraceAnnotation`` of its
name: inside a ``jax.profiler.trace`` it appears as a host event on the
profiler's clock, in the same trace as the device ops it caused.

jit-safety: a ``with span(...)`` placed *inside* a jitted function's Python
body executes while jax is abstractly tracing — the timed interval would be
compile time, recorded once per compilation and never again.  Entering a
span under an active trace therefore records **nothing** (the span is
dropped and counted in the sink's ``obs.spans_dropped_traced`` counter);
spans belong at blocking host call sites, with :meth:`Span.fence` pinning
the async dispatch tail into the measured interval.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, Optional

__all__ = ["Span", "SpanSink", "current_span"]

_LOCAL = threading.local()
_IDS = itertools.count(1)


def _stack() -> List["Span"]:
    st = getattr(_LOCAL, "stack", None)
    if st is None:
        st = _LOCAL.stack = []
    return st


def current_span() -> Optional["Span"]:
    """The innermost open span on this thread (None outside any span)."""
    st = _stack()
    return st[-1] if st else None


def _tracing() -> bool:
    """True while jax is tracing on this thread (jit, grad, vmap, ...)."""
    import jax
    return not jax.core.trace_ctx.is_top_level()


class SpanSink:
    """Collects completed span events against one perf_counter epoch.

    ``events`` is append-only; each event is a plain dict with ``name``,
    ``cat``, ``ts`` (µs since epoch), ``dur`` (µs), ``tid``, ``depth``,
    ``id``, ``parent`` and ``args`` — the exporter's native unit (Chrome
    traces are µs-based).
    """

    def __init__(self, on_drop=None):
        self.epoch = time.perf_counter()
        self.events: List[Dict] = []
        self._lock = threading.Lock()
        self._on_drop = on_drop

    def now_us(self) -> float:
        return (time.perf_counter() - self.epoch) * 1e6

    def span(self, name: str, cat: str = "obs", **args) -> "Span":
        return Span(self, name, cat=cat, args=args)

    def emit(self, event: Dict) -> None:
        with self._lock:
            self.events.append(event)

    def dropped(self, name: str) -> None:
        if self._on_drop is not None:
            self._on_drop(name)


class Span:
    """Context manager for one timed region (see module docstring)."""

    __slots__ = ("sink", "name", "cat", "args", "_t0", "_fences", "_live",
                 "_annotation", "id", "parent")

    def __init__(self, sink: SpanSink, name: str, cat: str = "obs",
                 args: Optional[Dict] = None):
        self.sink = sink
        self.name = name
        self.cat = cat
        self.args = dict(args or {})
        self._fences: list = []
        self._live = False

    def fence(self, *values) -> None:
        """Register jax values to ``block_until_ready`` before the span
        closes, so asynchronously dispatched device work lands inside the
        measured interval instead of leaking into the next span."""
        self._fences.extend(values)

    def set(self, **args) -> None:
        """Attach/overwrite args after entry (e.g. a result size)."""
        self.args.update(args)

    def __enter__(self) -> "Span":
        if _tracing():
            # Abstract tracing: recording here would mean once-per-compile
            # semantics.  Drop (counted), keep the context-manager shape.
            self.sink.dropped(self.name)
            self._live = False
            return self
        import jax
        self._live = True
        st = _stack()
        self.id = next(_IDS)
        self.parent = st[-1].id if st else None
        st.append(self)
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if not self._live:
            return False
        if self._fences:
            import jax
            jax.block_until_ready(self._fences)
        t1 = time.perf_counter()
        self._annotation.__exit__(exc_type, exc, tb)
        st = _stack()
        # Tolerate exceptions unwinding several spans at once: pop until us.
        while st and st[-1] is not self:
            st.pop()
        if st:
            st.pop()
        depth = len(st)
        if exc_type is not None:
            self.args.setdefault("error", exc_type.__name__)
        self.sink.emit({
            "name": self.name, "cat": self.cat,
            "ts": (self._t0 - self.sink.epoch) * 1e6,
            "dur": (t1 - self._t0) * 1e6,
            "tid": threading.get_ident(), "depth": depth,
            "id": self.id, "parent": self.parent,
            "args": self.args,
        })
        return False
