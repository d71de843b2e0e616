"""Host spans of the serving loop, on the host clock and the profiler's.

    rec = SpanRecorder()
    with rec.span("serve.wave", wave=3) as w:
        with rec.span("serve.dispatch"):
            ...
        w.set(batch=8)
    self_s, total_s = rec.take()

Each span does two things.  It adds its *self time* (its duration less
that of its child spans) to the recorder's totals under its name, timed
with `time.perf_counter`; `take` hands the totals over and starts new
ones, counting the part of any span still open up to that moment.  And it
opens a `jax.profiler.TraceAnnotation` of the same name and attributes,
so that a profiler trace shows the span on its host plane, on the same
clock as the device's operations.  With no profiler running the
annotation costs about a microsecond.

`outside(name)` times what is not a span, such as the time a generator
sits suspended at a yield while its caller works: it adds to the totals
only, and the profiler does not see it.

`spanned(name)` makes a method of an object with a `spans` recorder run
inside a span.  One recorder belongs to one serving loop on one thread;
spans nest strictly.
"""

from __future__ import annotations

import functools
import time

from jax.profiler import TraceAnnotation

_clock = time.perf_counter


class Span:
    """One open span of a `SpanRecorder`; `seconds` holds its duration
    once it has closed."""

    __slots__ = ("rec", "name", "attrs", "ann", "start", "mark", "child",
                 "seconds")

    def __init__(self, rec: "SpanRecorder", name: str, attrs: dict):
        self.rec, self.name, self.attrs = rec, name, attrs
        self.seconds = 0.0

    def __enter__(self) -> "Span":
        self.ann = TraceAnnotation(self.name, **self.attrs)
        self.ann.__enter__()
        # `mark` is where the part not yet in the totals begins: the start,
        # or the last `take` while the span was open
        self.start = self.mark = _clock()
        self.child = 0.0  # closed children's time since `mark`
        self.rec._stack.append(self)
        return self

    def __exit__(self, *exc) -> bool:
        now = _clock()
        rec = self.rec
        rec._stack.pop()
        part = now - self.mark
        rec._add(self.name, part - self.child, part)
        if rec._stack:
            rec._stack[-1].child += part
        self.seconds = now - self.start
        self.ann.__exit__(None, None, None)
        return False

    def set(self, **attrs) -> None:
        """Attributes known only once the span is open (profiler only)."""
        self.ann.set_metadata(**attrs)


class _Outside:
    __slots__ = ("rec", "name", "start")

    def __init__(self, rec: "SpanRecorder", name: str):
        self.rec, self.name = rec, name

    def __enter__(self):
        self.start = _clock()

    def __exit__(self, *exc) -> bool:
        dt = _clock() - self.start
        self.rec._add(self.name, dt, dt)
        return False


class SpanRecorder:
    def __init__(self):
        self._stack: list[Span] = []
        self._self: dict[str, float] = {}
        self._total: dict[str, float] = {}

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def outside(self, name: str) -> _Outside:
        return _Outside(self, name)

    def _add(self, name: str, self_s: float, total_s: float) -> None:
        self._self[name] = self._self.get(name, 0.0) + self_s
        self._total[name] = self._total.get(name, 0.0) + total_s

    def take(self) -> tuple[dict[str, float], dict[str, float]]:
        """Seconds since the last take, per name: self time, and time
        inside spans of that name.  Spans still open count up to now."""
        now = _clock()
        inner = 0.0  # the open child's part since its mark
        for s in reversed(self._stack):
            part = now - s.mark
            self._add(s.name, part - s.child - inner, part)
            s.mark, s.child, inner = now, 0.0, part
        taken = self._self, self._total
        self._self, self._total = {}, {}
        return taken


def spanned(name: str):
    """Method decorator: run the method inside `self.spans.span(name)`."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(self, *args, **kwargs):
            with self.spans.span(name):
                return fn(self, *args, **kwargs)

        return wrapped

    return deco
