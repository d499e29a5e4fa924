"""Span tracing: lightweight context-manager spans exported as Chrome
trace-event JSON (loadable in Perfetto / chrome://tracing).

The observability layer's second pillar. A `Tracer` collects complete
("ph": "X") trace events; `span("engine.step", step=7)` times a block and
records one event with its keyword arguments as event args, so the
prefill / decode / scrub / preemption interleaving of the serving engine
becomes a visible timeline per step and per tenant.

Ambient installation mirrors `use_metrics` / `use_policy`:

    tracer = Tracer()
    with use_tracer(tracer):
        engine.run()
    tracer.to_chrome_trace("trace.json")       # open in ui.perfetto.dev

Disabled (the default), `span(...)` returns a shared no-op context
manager — the hot loop pays one ambient lookup and nothing else.

Two jax-aware extras:

- `span(..., sync=x)` calls `jax.block_until_ready(x)` before closing the
  span, so the recorded duration covers device completion, not just
  dispatch (async dispatch otherwise attributes device time to whichever
  later span happens to block);
- `Tracer(jax_profiler=True)` additionally wraps every span in
  `jax.profiler.TraceAnnotation`, so the same span names line up inside a
  `jax.profiler.trace(...)` capture when one is active.

Nesting is tracked per thread: sibling and child spans nest correctly in
the rendered flame because their timestamps nest. Each span's event args
carry its integer `id`, the `parent` id of the span that enclosed it on
the same thread (None at the top) and its `depth`, so one call's spans
form a tree and a layer's self time is its duration less its children's.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import threading
import time

__all__ = ["Tracer", "NULL_TRACER", "current", "use_tracer", "span"]


class _Span:
    """One in-flight span (context manager recorded on exit)."""

    __slots__ = ("tracer", "name", "args", "sync", "t0", "depth", "id",
                 "parent")

    def __init__(self, tracer: "Tracer", name: str, sync, args: dict):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.sync = sync
        self.t0 = 0
        self.depth = 0
        self.id = 0
        self.parent = None

    def __enter__(self):
        self.tracer._enter_profiler(self.name)
        tl = self.tracer._tls
        self.depth = getattr(tl, "depth", 0)
        self.parent = getattr(tl, "span_id", None)
        self.id = next(self.tracer._ids)
        tl.depth = self.depth + 1
        tl.span_id = self.id
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self.sync is not None:
            import jax
            jax.block_until_ready(self.sync)
        t1 = time.perf_counter_ns()
        self.tracer._exit_profiler()
        tl = self.tracer._tls
        tl.depth = self.depth
        tl.span_id = self.parent
        self.tracer._record(self, t1)
        return False

    def set(self, **args) -> None:
        """Attach/overwrite event args from inside the span."""
        self.args.update(args)


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def set(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _NullTracer:
    enabled = False

    def span(self, name: str, *, sync=None, **args) -> _NullSpan:
        return _NULL_SPAN

    def instant(self, name: str, **args) -> None:
        pass

    def events(self) -> list:
        return []


NULL_TRACER = _NullTracer()


class Tracer:
    """Collects Chrome trace events. `max_events` bounds memory (oldest
    events are dropped with a `truncated` marker rather than growing
    without bound under a long-running engine)."""

    enabled = True

    def __init__(self, *, pid: int = 0, max_events: int = 200_000,
                 jax_profiler: bool = False):
        self.pid = pid
        self.max_events = max_events
        self.jax_profiler = jax_profiler
        self._events: list[dict] = []
        self._dropped = 0
        self._epoch_ns = time.perf_counter_ns()
        self._tls = threading.local()
        self._lock = threading.Lock()
        self._ids = itertools.count()

    # -- recording -----------------------------------------------------------

    def span(self, name: str, *, sync=None, **args) -> _Span:
        """Context manager timing a block; `sync` (any jax pytree) is
        blocked on before the span closes so device work is billed to the
        span that launched it."""
        return _Span(self, name, sync, args)

    def instant(self, name: str, **args) -> None:
        """Zero-duration marker event (preemptions, injections)."""
        ts = (time.perf_counter_ns() - self._epoch_ns) / 1e3
        self._append({"name": name, "ph": "i", "s": "t", "ts": ts,
                      "pid": self.pid, "tid": threading.get_ident() % 2**31,
                      "args": args})

    def _record(self, sp: _Span, t1_ns: int) -> None:
        ev_args = dict(sp.args)
        ev_args.update(depth=sp.depth, id=sp.id, parent=sp.parent)
        self._append({
            "name": sp.name, "ph": "X",
            "ts": (sp.t0 - self._epoch_ns) / 1e3,        # microseconds
            "dur": (t1_ns - sp.t0) / 1e3,
            "pid": self.pid, "tid": threading.get_ident() % 2**31,
            "args": ev_args})

    def _append(self, ev: dict) -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                self._events.pop(0)
                self._dropped += 1
            self._events.append(ev)

    def _enter_profiler(self, name: str) -> None:
        """Open the span's `TraceAnnotation`. A failure propagates: a
        traced run that cannot annotate must fail, not lose its spans."""
        if not self.jax_profiler:
            return
        import jax.profiler
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        stack = getattr(self._tls, "annotations", None)
        if stack is None:
            stack = self._tls.annotations = []
        stack.append(ann)

    def _exit_profiler(self) -> None:
        if not self.jax_profiler:
            return
        stack = getattr(self._tls, "annotations", None)
        if stack:
            stack.pop().__exit__(None, None, None)

    # -- export --------------------------------------------------------------

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def spans(self, name: str | None = None) -> list[dict]:
        """Complete ("X") events, optionally filtered by name."""
        return [e for e in self.events()
                if e["ph"] == "X" and (name is None or e["name"] == name)]

    def to_chrome_trace(self, path: str | None = None) -> dict:
        """The Chrome trace-event JSON object; written to `path` when
        given. Load with chrome://tracing or ui.perfetto.dev."""
        doc = {"traceEvents": self.events(), "displayTimeUnit": "ms",
               "otherData": {"dropped_events": self._dropped}}
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc


# ---------------------------------------------------------------------------
# ambient tracer
# ---------------------------------------------------------------------------

_current = NULL_TRACER


def current():
    return _current


@contextlib.contextmanager
def use_tracer(tracer: Tracer | None = None):
    """Install `tracer` as the ambient span sink for the block (a fresh
    `Tracer` when called with None). Yields the tracer."""
    global _current
    tr = Tracer() if tracer is None else tracer
    prev = _current
    _current = tr
    try:
        yield tr
    finally:
        _current = prev


def span(name: str, *, sync=None, **args):
    """`with span("engine.step", step=i):` — records on the ambient tracer,
    free (a shared no-op) when tracing is disabled."""
    t = _current
    if not t.enabled:
        return _NULL_SPAN
    return t.span(name, sync=sync, **args)
