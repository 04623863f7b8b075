"""Host-side tracer: nested spans, compile events, JSONL, profiler glue.

Design constraints (docs/DESIGN.md §8):

* **Bitwise neutrality** — nothing here runs inside a traced function.
  Spans time HOST phases (upload, dispatch, readback, compile); device-side
  phase markers are ``jax.named_scope``
  annotations placed at the instrumentation sites themselves (metadata
  only — they never change the lowered math).
* **Zero cost when off** — the tracer is disabled by default and
  :func:`span` short-circuits to a shared no-op context manager, so the
  serving hot loop and the warm bench walls pay one attribute read per
  call site (the ≤5 % telemetry-overhead budget is measured with the
  tracer ON in benchmarks/bench_engine.py).
* **Structured emission** — spans/events append to an in-memory buffer
  and, when enabled with a path (or ``REPRO_TRACE=<path>`` in the
  environment), stream to JSONL one object per line: ``{"type": "span" |
  "event", "name", "t0", "t0_ns", "t1_ns", "wall_s", "depth", "parent",
  ...attrs}``.
* **One clock with the device trace** — ``t0_ns``/``t1_ns`` are
  ``time.time_ns()``, the realtime clock whose value at the profile's
  start the trace stores (``profile_start_time`` in its ``Task
  Environment`` plane); ``t0`` is the same instant in epoch seconds and
  ``wall_s`` the ``perf_counter`` duration.
* **Profiler integration** — :func:`profile_trace` wraps
  ``jax.profiler.start_trace``/``stop_trace`` (the ``--profile`` flag on
  ``benchmarks/run.py`` and ``python -m repro.serve``); while profiling,
  every span ALSO enters a ``jax.profiler.TraceAnnotation`` carrying the
  span's index (``span=<index>``) and attributes as stats, so host phases
  line up with the device timeline and each annotation names its span.
"""
from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional


@dataclass
class Span:
    """One closed host span (or an open one still on the stack)."""

    name: str
    t0_ns: int = 0                 # time.time_ns() at entry (epoch ns)
    t1_ns: int = 0                 # time.time_ns() at exit
    wall_s: float = 0.0            # perf_counter delta
    depth: int = 0
    index: int = 0                 # position in the tracer's span list
    parent: int = -1               # index of the enclosing span (-1 = root)
    attrs: Dict[str, Any] = field(default_factory=dict)

    @property
    def t0(self) -> float:
        """Entry time in epoch seconds."""
        return self.t0_ns * 1e-9

    def to_json(self) -> Dict[str, Any]:
        return {"type": "span", "name": self.name, "t0": self.t0,
                "t0_ns": self.t0_ns, "t1_ns": self.t1_ns,
                "wall_s": self.wall_s, "depth": self.depth,
                "index": self.index, "parent": self.parent, **self.attrs}


class _NullCm:
    """Reusable no-op context manager — the disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _NullCm()


class Tracer:
    """The host tracer.  One global instance (:data:`TRACER`); tests may
    construct private ones.  Thread-safe enough for the repo's use (the
    serving feed thread never opens spans; a lock guards the buffers)."""

    def __init__(self):
        self.enabled = False
        self.profiling = False
        self.spans: List[Span] = []
        self.events: List[Dict[str, Any]] = []
        self._stack: List[Span] = []
        self._lock = threading.Lock()
        self._jsonl = None          # open file handle when streaming

    # -- lifecycle --------------------------------------------------------

    def enable(self, jsonl_path: Optional[str] = None) -> None:
        """Turn span/event recording on; ``jsonl_path`` streams every
        closed span and event to disk as it happens."""
        with self._lock:
            if jsonl_path:
                d = os.path.dirname(jsonl_path)
                if d:
                    os.makedirs(d, exist_ok=True)
                self._jsonl = open(jsonl_path, "a")
            self.enabled = True

    def disable(self) -> None:
        with self._lock:
            self.enabled = False
            if self._jsonl is not None:
                self._jsonl.close()
                self._jsonl = None

    def clear(self) -> None:
        with self._lock:
            self.spans = []
            self.events = []
            self._stack = []

    # -- recording --------------------------------------------------------

    def span(self, name: str, **attrs):
        """Context manager timing a nested host phase.  No-op (shared null
        object, no allocation) while the tracer is disabled and no
        profiler trace is active."""
        if not (self.enabled or self.profiling):
            return _NULL
        return self._span_cm(name, attrs)

    @contextmanager
    def _span_cm(self, name: str, attrs: Dict[str, Any]):
        if not self.enabled:        # profiling-only: annotate, don't record
            import jax
            with jax.profiler.TraceAnnotation(name, **attrs):
                yield None
            return
        with self._lock:
            sp = Span(name=name, depth=len(self._stack),
                      index=len(self.spans),
                      parent=self._stack[-1].index if self._stack else -1,
                      attrs=dict(attrs))
            self.spans.append(sp)
            self._stack.append(sp)
        ann = None
        if self.profiling:          # host phase marker on the trace timeline
            import jax
            ann = jax.profiler.TraceAnnotation(name, span=sp.index, **attrs)
            ann.__enter__()
        sp.t0_ns, w0 = time.time_ns(), time.perf_counter()
        try:
            yield sp
        finally:
            sp.wall_s = time.perf_counter() - w0
            sp.t1_ns = time.time_ns()
            if ann is not None:
                ann.__exit__(None, None, None)
            with self._lock:
                if self._stack and self._stack[-1] is sp:
                    self._stack.pop()
                if self._jsonl is not None:
                    self._jsonl.write(json.dumps(sp.to_json()) + "\n")
                    self._jsonl.flush()

    def event(self, name: str, **attrs) -> None:
        """Record a point event (e.g. a compile-cache miss).  No-op while
        disabled."""
        if not self.enabled:
            return
        t_ns = time.time_ns()
        with self._lock:
            ev = {"type": "event", "name": name, "t0": t_ns * 1e-9,
                  "t0_ns": t_ns, "t1_ns": t_ns,
                  "depth": len(self._stack),
                  "parent": self._stack[-1].index if self._stack else -1,
                  **attrs}
            self.events.append(ev)
            if self._jsonl is not None:
                self._jsonl.write(json.dumps(ev) + "\n")
                self._jsonl.flush()

    # -- introspection ----------------------------------------------------

    def find(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def dump_jsonl(self, path: str) -> str:
        """Write the whole in-memory buffer to ``path`` (one JSON object
        per line, spans then events in record order)."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            for sp in self.spans:
                f.write(json.dumps(sp.to_json()) + "\n")
            for ev in self.events:
                f.write(json.dumps(ev) + "\n")
        return path


TRACER = Tracer()
if os.environ.get("REPRO_TRACE"):
    TRACER.enable(os.environ["REPRO_TRACE"])


def span(name: str, **attrs):
    """Module-level alias of :meth:`Tracer.span` on the global tracer —
    the instrumentation sites' one-liner."""
    return TRACER.span(name, **attrs)


def event(name: str, **attrs) -> None:
    TRACER.event(name, **attrs)


def spans(name: Optional[str] = None) -> List[Span]:
    return TRACER.find(name) if name else list(TRACER.spans)


@contextmanager
def profile_trace(logdir: str):
    """Dump a TensorBoard-loadable ``jax.profiler`` trace of the block to
    ``logdir`` (the ``--profile`` flag's implementation).  While active,
    host spans double as ``TraceAnnotation`` phase markers, and the
    device-side ``jax.named_scope`` markers (the round step's phases,
    ``eval_block``, the DP kernels' ``dp_layout``/``dp_noise``/
    ``dp_kernel``) reach the trace in the HLO's ``op_name`` metadata.  The
    options are the benchmark's (``bench/harness.py::Profiler``): no
    Python tracer, host annotations, and the HLO protos, so the trace
    reduces with ``bench/trace/xplane.py``."""
    import jax

    os.makedirs(logdir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = True
    jax.profiler.start_trace(logdir, profiler_options=opts)
    TRACER.profiling = True
    try:
        yield logdir
    finally:
        TRACER.profiling = False
        jax.profiler.stop_trace()
