"""Spans and counters of the serving and campaign hot paths.

Every layer boundary of a fleet dispatch, a served frame, an objective
call and a campaign epoch opens a `span`: a `jax.profiler.TraceAnnotation`
of the same name, so a profiler trace shows it on the host plane on the
same clock as the device's operations, plus one count and the span's
wall seconds (`time.perf_counter`, GIL waits included) added to a
process-wide table.  `add` records what is not a span, such as a
request's wait in the queue.  `snapshot()` returns the table; an
operator reads it through `ClassifierFleet.stats_summary()["spans"]` and
the server's STATS reply.

The profiler alone decides whether the annotations are recorded: there is
no switch here.  Whatever is recorded while a profiler trace is being
collected is also added to a second table, `snapshot(traced=True)`, so
the totals of exactly a traced interval can be read beside the trace.

Spans are opened once a batch, a frame, a call or an epoch: never per
reading, and never inside a jitted function.
"""
from __future__ import annotations

import sys
import threading
import time

_lock = threading.Lock()
_table: dict[str, list] = {}        # name -> [count, seconds]
_traced: dict[str, list] = {}       # the part recorded under a trace
_trace_me = None                    # jax.profiler.TraceAnnotation, lazily


def _annotation():
    """The profiler's annotation class, or None in a process that has not
    imported JAX (it cannot be tracing, and a span must not pull JAX into
    a worker that never uses it)."""
    global _trace_me
    if _trace_me is None:
        if "jax" not in sys.modules:
            return None
        import jax.profiler

        _trace_me = jax.profiler.TraceAnnotation
    return _trace_me


def _tracing(ann) -> bool:
    return ann is not None and ann.is_enabled()


def _add(name: str, seconds: float, n: int, traced: bool) -> None:
    with _lock:
        for table in (_table, _traced) if traced else (_table,):
            row = table.get(name)
            if row is None:
                row = table[name] = [0, 0.0]
            row[0] += n
            row[1] += seconds


def add(name: str, seconds: float, n: int = 1) -> None:
    """Record `n` events of `name` that took `seconds` in all."""
    _add(name, seconds, n, _tracing(_annotation()))


class span:
    """`with span(name, **ids):` times the block into the table and, while
    the profiler traces, annotates it with `ids` (tenant, batch, replica,
    ...) as metadata; `set(**ids)` adds ids learned inside the block."""

    __slots__ = ("name", "_ids", "_ann", "_t0")

    def __init__(self, name: str, **ids):
        self.name = name
        self._ids = ids

    def __enter__(self) -> "span":
        ann = _annotation()
        if _tracing(ann):
            self._ann = ann(self.name, **self._ids)
            self._ann.__enter__()
        else:
            self._ann = None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _add(self.name, dt, 1, self._ann is not None)

    def set(self, **ids) -> None:
        if self._ann is not None:
            self._ann.set_metadata(**ids)


def snapshot(traced: bool = False) -> dict[str, dict]:
    """`{name: {"n": count, "s": seconds}}` since the process started, or
    with `traced` only what was recorded while a profiler trace was
    being collected."""
    with _lock:
        table = _traced if traced else _table
        return {k: {"n": n, "s": s} for k, (n, s) in sorted(table.items())}
