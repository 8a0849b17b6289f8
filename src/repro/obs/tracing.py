"""Program spans on the profiler's clock.

``with tracer.span("plane.drop", layer=3):`` opens a
``jax.profiler.TraceAnnotation`` named ``repro:plane.drop``.  Inside a
``jax.profiler`` capture it lands on the host thread's line of the
``.xplane.pb``, on the same clock as the runtime's own host events and
the device's operations; with no capture running it costs about a
microsecond, so spans are always emitted and have no off switch.

Each span also reads ``time.perf_counter`` at its edges (``Span.t0``,
``Span.dur``), so a caller that keeps its own per-phase timing reads the
span instead of a second clock, and a per-thread stack links each span
to its parent (``child_s``: the seconds its direct children took).

A span is counted only if ``COUNTED`` names it — the spans a benchmark
metric or a documented operator view reads.  Counting adds the span's
duration to the registry counter ``<name>_s`` and one call to
``<name>_calls``, or, for ``"self_s"``, its duration minus its direct
children's to ``<name>_self_s``: the span and the counter are one
measurement exported twice.

Spans must stay outside jitted stage bodies (one there would fire once,
at trace time, and never again): the ``no-obs-in-jit`` analyzer rule
enforces it.  See docs/architecture.md §11 for the span table.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from repro.obs.metrics import MetricsRegistry

PREFIX = "repro:"

# span name -> the counters it feeds, each a suffix of the span's name
COUNTED: Dict[str, Tuple[str, ...]] = {
    "engine.step": ("self_s",),
    "plane.drop": ("s", "calls"),
    "plane.restore": ("s",),
    "plane.kv_readback": ("s",),
}


_LOCAL = threading.local()


def _stack() -> List["Span"]:
    """This thread's open spans, innermost last (one stack per thread,
    shared by every recorder, so a span's parent is the span open around
    it whichever component opened that)."""
    stack = getattr(_LOCAL, "stack", None)
    if stack is None:
        stack = _LOCAL.stack = []
    return stack


class Span:
    """One open span: a TraceAnnotation plus its perf_counter edges.

    ``set(**args)`` adds arguments known only after the span opened
    (the profiler records them with the span)."""

    __slots__ = ("name", "t0", "dur", "child_s", "_ann", "_count",
                 "_parent")

    def __init__(self, name: str, args: Dict,
                 count: Optional[Callable[["Span"], None]] = None):
        self.name = name
        self.t0 = 0.0
        self.dur = 0.0
        self.child_s = 0.0
        self._ann = TraceAnnotation(PREFIX + name, **args)
        self._count = count
        self._parent: Optional[Span] = None

    def set(self, **args) -> None:
        self._ann.set_metadata(**args)

    def __enter__(self) -> "Span":
        stack = _stack()
        self._parent = stack[-1] if stack else None
        stack.append(self)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.dur = time.perf_counter() - self.t0
        self._ann.__exit__(*exc)
        _stack().pop()
        if self._parent is not None:
            self._parent.child_s += self.dur
        if self._count is not None:
            self._count(self)
        return False


class Tracer:
    """The program's span recorder.

    ``metrics`` is the registry counted spans feed (the engine hands its
    own).  Counters are created once, here; spans may open on any
    thread."""

    def __init__(self, metrics: Optional[MetricsRegistry] = None):
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._counted: Dict[str, Callable[[Span], None]] = {
            name: self._counter(name, kinds)
            for name, kinds in COUNTED.items()}

    def _counter(self, name: str, kinds: Tuple[str, ...]
                 ) -> Callable[[Span], None]:
        m = self.metrics
        secs = m.counter(name + "_s", f"seconds inside {name} spans"
                         ) if "s" in kinds else None
        calls = m.counter(name + "_calls", f"{name} spans"
                          ) if "calls" in kinds else None
        own = m.counter(name + "_self_s",
                        f"seconds inside {name} spans that no child span "
                        f"covers") if "self_s" in kinds else None

        def count(span: Span) -> None:
            if secs is not None:
                secs.inc(span.dur)
            if calls is not None:
                calls.inc()
            if own is not None:
                own.inc(span.dur - span.child_s)
        return count

    def span(self, name: str, **args) -> Span:
        """``with tracer.span(name, **args) as s:`` — a profiler span
        ``repro:<name>`` whose arguments are ``args``."""
        return Span(name, args, self._counted.get(name))


# the recorder of a component built outside an engine, and of the stage
# registries, which engines share; nothing reads its registry
DEFAULT = Tracer()
