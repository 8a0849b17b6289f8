"""The profiler trace of a ``--trace 1`` run, reduced to numbers.

Capture: ``jax.profiler`` writes an ``.xplane.pb``; host spans come from
``jax.profiler.TraceAnnotation`` names the harness sets (``SPAN_PREFIX``),
so device time and host spans share the profiler's clock.

Reduction (pure functions on (start, end, name) tuples, so a small
synthetic trace can test them):

* busy: the union of device-operation intervals inside the window,
  averaged over the chips used;
* idle gaps: the holes in that union, each labelled with the innermost
  harness span open on the dispatching thread at the gap's midpoint;
* the device operations that took most time, by name.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float, str]          # (start s, end s, name)

SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
DEVICE_LINES = ("XLA Ops",)          # busy: operations
PROGRAM_LINES = ("XLA Modules",)     # breakdown: the programs they ran in


def merge(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """The union of intervals clipped to [lo, hi], as sorted disjoint
    pieces."""
    xs = sorted((max(a, lo), min(b, hi)) for a, b, _ in intervals
                if b > lo and a < hi)
    out: List[Tuple[float, float]] = []
    for a, b in xs:
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_seconds(intervals: Sequence[Interval], lo: float, hi: float
                 ) -> float:
    return sum(b - a for a, b in merge(intervals, lo, hi))


def idle_gaps(intervals: Sequence[Interval], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """Holes in the busy union inside [lo, hi]."""
    gaps, t = [], lo
    for a, b in merge(intervals, lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _name(span: Interval) -> str:
    n = span[2]
    return n[len(SPAN_PREFIX):] if n.startswith(SPAN_PREFIX) else n


def labels_at(spans: Sequence[Interval], times: Sequence[float]
              ) -> List[str]:
    """For each time (ascending), the innermost span open then, by name
    without the prefix; "(no span)" when none is.  Spans of one thread
    nest, so one sweep with a stack of open spans answers all times."""
    order = sorted((s for s in spans if s[2] != WINDOW_SPAN),
                   key=lambda s: (s[0], -s[1]))
    out, stack, i = [], [], 0
    for t in times:
        while i < len(order) and order[i][0] <= t:
            while stack and stack[-1][1] < order[i][0]:
                stack.pop()
            stack.append(order[i])
            i += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        out.append(_name(stack[-1]) if stack else "(no span)")
    return out


def gaps_by_label(gaps: Sequence[Tuple[float, float]],
                  spans: Sequence[Interval]) -> List[Tuple[str, float]]:
    """Idle seconds summed by what the host was doing, largest first."""
    gaps = sorted(gaps)
    tot: Dict[str, float] = {}
    for (a, b), lab in zip(gaps, labels_at(spans,
                                           [0.5 * (a + b) for a, b in gaps])):
        tot[lab] = tot.get(lab, 0.0) + (b - a)
    return sorted(tot.items(), key=lambda kv: -kv[1])


_ID = re.compile(r"([._-]?\d+)+$")


def op_family(name: str) -> str:
    """A program's or operation's name without what varies per compile:
    ``jit_fn(1234)`` -> ``jit_fn``, ``%fusion.12 = f32[...] ...`` ->
    ``fusion``."""
    base = name.split("(")[0].split(" = ")[0].strip().lstrip("%")
    return _ID.sub("", base) or base


def top_ops(ops: Sequence[Interval], lo: float, hi: float, n: int = 10
            ) -> List[Tuple[str, float]]:
    tot: Dict[str, float] = {}
    for a, b, name in ops:
        if b > lo and a < hi:
            key = op_family(name)
            tot[key] = tot.get(key, 0.0) + (min(b, hi) - max(a, lo))
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def reduce(devices: Dict[str, List[Interval]], spans: List[Interval],
           window: Tuple[float, float],
           programs: Optional[Dict[str, List[Interval]]] = None) -> Dict:
    """Numbers of one traced window: busy seconds averaged over the
    devices, the window's length, the device programs (or, without them,
    operations) that took most time, and the idle gaps by host span."""
    lo, hi = window
    busy = [busy_seconds(ev, lo, hi) for ev in devices.values()]
    first = next(iter(devices.values()), [])
    gaps = idle_gaps(first, lo, hi)
    named = programs if programs else devices
    return {"busy_s": sum(busy) / max(len(busy), 1),
            "window_s": hi - lo,
            "device_ops": [[k, v] for k, v in top_ops(
                [e for ev in named.values() for e in ev], lo, hi)],
            "idle_gaps": [[k, v] for k, v in
                          gaps_by_label(gaps, spans)[:10]],
            "idle_gap_count": len(gaps)}


# ---------------------------------------------------------------------------
# capture and reading (needs a chip for device planes)
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def capture(log_dir: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def read(log_dir: str) -> Dict:
    """(device intervals by device plane, harness spans of the thread that
    opened the window, the window) from the newest trace under
    ``log_dir``; times in seconds on the profiler's clock."""
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"no profiler trace under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    devices: Dict[str, List[Interval]] = {}
    programs: Dict[str, List[Interval]] = {}
    lines_by_thread: List[List[Interval]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            for line in plane.lines:
                dest = (devices if line.name in DEVICE_LINES else
                        programs if line.name in PROGRAM_LINES else None)
                if dest is not None:
                    dest.setdefault(plane.name, []).extend(
                        (e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                       for e in line.events
                       if e.name.startswith(SPAN_PREFIX)]
                if evs:
                    lines_by_thread.append(evs)
    window = None
    spans: List[Interval] = []
    for evs in lines_by_thread:
        w = [e for e in evs if e[2] == WINDOW_SPAN]
        if w:
            window = (w[0][0], w[0][1])
            spans = evs
    if window is None:
        raise RuntimeError("the trace holds no window span")
    return {"devices": devices, "programs": programs, "spans": spans,
            "window": window, "path": paths[-1]}


class Spans:
    """Harness spans around the program's layers, as TraceAnnotations.

    ``wrap(obj, attr, name)`` replaces a bound method or attribute with one
    that opens a span around each call.  An attribute the program no
    longer has is an error: its time would pass unseen into the
    breakdown's "(no span)", where the bottleneck is read."""

    def __init__(self):
        import jax
        self._ann = jax.profiler.TraceAnnotation
        self.wrapped: List[str] = []

    def span(self, name: str):
        return self._ann(SPAN_PREFIX + name)

    def wrap(self, obj, attr: str, name: str) -> None:
        f = getattr(obj, attr, None)
        if f is None or not callable(f):
            raise AttributeError(
                f"span {name!r}: {getattr(obj, '__name__', type(obj).__name__)}"
                f" has no callable {attr!r}; update the harness's spans")
        ann, full = self._ann, SPAN_PREFIX + name

        def wrapped(*a, **k):
            with ann(full):
                return f(*a, **k)
        setattr(obj, attr, wrapped)
        self.wrapped.append(name)
