"""Span tracer: nested context-manager spans + Chrome-trace export
(DESIGN.md 1j).

``span("plan")`` / ``span("execute", executor="fused")`` wrap the phases of
a request with parent nesting tracked per thread, so a
``PairwiseService.similarity`` call produces a small tree (DESIGN.md 1j)::

    request                      compiles
    ├── upload                   bytes       (the table)
    ├── plan
    │   ├── schema               family, cached (plan_a2a / plan_x2y)
    │   └── lower                cached      (the reducer plan / sub-plan)
    └── execute                  block_bytes (reducer blocks written)
        ├── maps                 what, cached (source map, partition, ...)
        ├── upload               bytes       (plan arrays, source map)
        └── launch               compiles    (the jitted program's call)

Spans time host work only: none of them waits for the device.  Completed
spans land in a bounded ring buffer (serving loops never grow memory);
spans the full ring pushes out are counted in ``dropped`` and the
``obs.spans_dropped`` counter.  ``chrome_trace()`` renders the ring in the
Chrome trace-event format, so ``export_chrome_trace("trace.json")`` loads
directly in ``chrome://tracing`` or https://ui.perfetto.dev.

``Tracer(annotate=True)`` (or ``REPRO_OBS_XPROF=1``) additionally enters a
``jax.profiler.TraceAnnotation`` for every span, its attributes attached as
metadata when it closes, so the host-side phases line up with XLA device
traces when a jax profile is being captured.  The jax import is lazy and
optional — the obs layer stays importable without jax (zero-dependency
contract).

Overhead: a span is two ``perf_counter`` calls, a dataclass, and a deque
append; disabled (``repro.obs.configure(enabled=False)``) it is a single
flag test yielding a shared no-op.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Optional

from . import _config
from .metrics import REGISTRY

__all__ = ["Span", "Tracer", "TRACER", "span"]


@dataclasses.dataclass
class Span:
    """One completed (or in-flight) span; times from ``perf_counter``."""

    name: str
    span_id: int
    parent_id: Optional[int]
    tid: int
    start: float                 # perf_counter seconds
    duration: float = 0.0        # seconds; 0 while in flight
    attrs: dict = dataclasses.field(default_factory=dict)


class Tracer:
    """Ring-buffered span collector with per-thread parent nesting."""

    def __init__(self, capacity: int = 65536,
                 annotate: Optional[bool] = None):
        if annotate is None:
            annotate = os.environ.get("REPRO_OBS_XPROF", "") not in ("", "0")
        self.annotate = bool(annotate)
        self._spans: deque = deque(maxlen=capacity)
        self.dropped = 0             # spans the full ring pushed out
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    @contextmanager
    def span(self, name: str, **attrs):
        """Context manager: time a phase, nest under the thread's current
        span, record into the ring.  Yields the live :class:`Span` (attach
        late attributes via ``s.attrs[...] = ...``); yields None when
        observability is disabled."""
        if not _config.ENABLED:
            yield None
            return
        stack = self._stack()
        s = Span(name=str(name), span_id=next(self._ids),
                 parent_id=stack[-1].span_id if stack else None,
                 tid=threading.get_ident(), start=time.perf_counter(),
                 attrs=dict(attrs))
        stack.append(s)
        ann = None
        if self.annotate:
            try:
                from jax.profiler import TraceAnnotation
                ann = TraceAnnotation(s.name)
                ann.__enter__()
            except Exception:        # jax absent / profiler unavailable
                ann = None
        try:
            yield s
        finally:
            s.duration = time.perf_counter() - s.start
            if ann is not None:
                if s.attrs:
                    ann.set_metadata(**{k: _jsonable(v)
                                        for k, v in s.attrs.items()})
                ann.__exit__(None, None, None)
            stack.pop()
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
                REGISTRY.counter("obs.spans_dropped").inc()
            self._spans.append(s)

    # ------------------------------------------------------------- queries
    def current(self) -> Optional[Span]:
        """The calling thread's innermost open span, or None."""
        stack = self._stack()
        return stack[-1] if stack else None

    def spans(self) -> list:
        """Snapshot of the completed-span ring (oldest first)."""
        return list(self._spans)

    def clear(self) -> None:
        """Empty the ring and zero ``dropped``."""
        self._spans.clear()
        self.dropped = 0

    def chrome_trace(self) -> dict:
        """The ring as a Chrome trace-event JSON object (``ph: "X"``
        complete events, microsecond timestamps) — loadable in
        ``chrome://tracing`` / Perfetto."""
        pid = os.getpid()
        events = []
        for s in self._spans:
            args = {k: _jsonable(v) for k, v in s.attrs.items()}
            if s.parent_id is not None:
                args["parent"] = s.parent_id
            args["span_id"] = s.span_id
            events.append({
                "name": s.name, "cat": "repro", "ph": "X",
                "ts": s.start * 1e6, "dur": s.duration * 1e6,
                "pid": pid, "tid": s.tid, "args": args,
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str) -> str:
        """Write :meth:`chrome_trace` to ``path``; returns the path."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


#: process-global tracer; ``span(...)`` below is its bound method.
TRACER = Tracer()
span = TRACER.span
