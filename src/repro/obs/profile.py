"""Program spans and counters, on the profiler's clock.

`span(name)` is the program's one span API. Every span is named
`repro.<layer>.<phase>` and lands in two places:

  * any `jax.profiler` trace that is running (`jax.profiler.start_trace`,
    the profiler server): the span opens a `jax.profiler.TraceAnnotation`,
    so it sits in the same xplane as the device ops, on the same clock, and
    a reducer can line a host phase up with the device work inside it;
  * the in-process `Profiler`'s ring buffer, when it is enabled
    (`enable_profiling()`), timed with `time.perf_counter`.

With neither active a span costs one flag test and one call to the
tracer's `is_enabled` (about 1 us with the `with` statement), `count` and
`ready` do nothing, and no device work is waited on.

Counters are arguments of the span: `sp.count(rows=3)` writes them as
stats of the span's trace event (nothing is recorded outside a trace).
Counters that cost a device-to-host transfer are computed only under
`tracing_active()`.

`sp.ready(x)` blocks on device work (and returns x) only when the
`Profiler` is enabled, so its host-clock spans time execution rather than
the enqueue; under a trace alone it is the identity, and the device trace
says when the work ran.

    >>> from repro.obs import enable_profiling, get_profiler
    >>> enable_profiling()
    >>> ...  # run solves
    >>> get_profiler().summary()            # name -> count/total/mean/max
    >>> get_profiler().top_spans(5)         # slowest individual spans
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque

from jax.profiler import TraceAnnotation

_MAX_SPANS = 16384


def tracing_active() -> bool:
    """True while a profiler trace records host spans: the gate for
    counters that cost a transfer."""
    return TraceAnnotation.is_enabled()


@dataclasses.dataclass(frozen=True)
class ProfileSpan:
    """One completed span: label, start (perf_counter seconds), duration."""

    name: str
    t0: float
    dur: float


class _ActiveSpan:
    """One live span: a trace annotation while tracing, a ring-buffer entry
    while `profiler` is set (enabled)."""

    __slots__ = ("_profiler", "name", "_t0", "_ta")

    def __init__(self, profiler: "Profiler | None", name: str):
        self._profiler = profiler
        self.name = name
        self._ta = TraceAnnotation(name) if tracing_active() else None

    def count(self, **counters) -> None:
        """Attach counters to the span's trace event (a no-op untraced)."""
        if self._ta is not None:
            self._ta.set_metadata(**counters)

    def ready(self, x):
        if self._profiler is None:
            return x
        import jax
        return jax.block_until_ready(x)

    def __enter__(self):
        if self._ta is not None:
            self._ta.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._profiler is not None:
            self._profiler._push(ProfileSpan(
                name=self.name, t0=self._t0,
                dur=time.perf_counter() - self._t0))
        if self._ta is not None:
            self._ta.__exit__(*exc)
        return False


class _NullSpan:
    """Span with nothing recording: no timing; `count` and `ready` do
    nothing."""

    __slots__ = ()

    def count(self, **counters) -> None:
        pass

    def ready(self, x):
        return x

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class Profiler:
    """Span collector: bounded deque of completed `ProfileSpan`s."""

    def __init__(self, enabled: bool = False, max_spans: int = _MAX_SPANS):
        self.enabled = bool(enabled)
        self._spans: deque[ProfileSpan] = deque(maxlen=int(max_spans))

    def _push(self, span: ProfileSpan) -> None:
        self._spans.append(span)

    def span(self, name: str):
        """`with profiler.span("repro.x.y"): ...`; also lands in a running
        trace. A no-op when neither records."""
        if self.enabled:
            return _ActiveSpan(self, name)
        if tracing_active():
            return _ActiveSpan(None, name)
        return _NULL_SPAN

    @property
    def spans(self) -> list[ProfileSpan]:
        return list(self._spans)

    def clear(self) -> None:
        self._spans.clear()

    def summary(self) -> dict[str, dict]:
        """{name: {count, total_s, mean_s, max_s}} over retained spans."""
        agg: dict[str, dict] = {}
        for s in self._spans:
            row = agg.setdefault(s.name, {"count": 0, "total_s": 0.0,
                                          "max_s": 0.0})
            row["count"] += 1
            row["total_s"] += s.dur
            row["max_s"] = max(row["max_s"], s.dur)
        for row in agg.values():
            row["mean_s"] = row["total_s"] / row["count"]
        return agg

    def top_spans(self, k: int = 10) -> list[ProfileSpan]:
        """The k slowest individual spans, slowest first."""
        return sorted(self._spans, key=lambda s: -s.dur)[:k]


_PROFILER = Profiler(enabled=False)


def get_profiler() -> Profiler:
    return _PROFILER


def enable_profiling(on: bool = True) -> Profiler:
    """Turn the module-level profiler on (or off); returns it."""
    _PROFILER.enabled = bool(on)
    return _PROFILER


def span(name: str):
    """Module-level span against the default profiler (the instrumented
    library call sites use this)."""
    return _PROFILER.span(name)


@contextlib.contextmanager
def profile_block(name: str):
    """Enable profiling for a `with` block, restoring the prior state."""
    prev = _PROFILER.enabled
    _PROFILER.enabled = True
    try:
        yield _PROFILER
    finally:
        _PROFILER.enabled = prev


__all__ = ["Profiler", "ProfileSpan", "get_profiler", "enable_profiling",
           "span", "profile_block", "tracing_active"]
