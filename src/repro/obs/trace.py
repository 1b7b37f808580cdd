"""Per-query stamps, program spans, and the span table.

Two kinds of timing live here.

**Per-query stamps.**  The serving engines stamp ``obs.clock.now`` values
straight onto the request future (``AsyncResult``: ``submitted_at``,
``dispatched_at``, ``device_done_at``, ``completed_at``), so tracing a
query allocates nothing beyond the future that exists anyway.  The
derived spans (:func:`span_fields`):

    admission ............ submitted_at            (queue entry)
    queue wait + linger .. dispatched_at - submitted_at
    device compute ....... device_done_at - dispatched_at
                           (async dispatch -> device->host readback done;
                           includes the rerank stage, which runs inside
                           the same compiled program)
    extract .............. completed_at - device_done_at
    total ................ completed_at - submitted_at

Ordering invariant (pinned by tests/test_obs_querylog.py):
``submitted_at <= dispatched_at <= device_done_at <= completed_at``.

:class:`Sampler` decides which queries produce a query-log record.  It is
deterministic (a fractional accumulator, not an RNG): rate 1.0 takes
every query, rate 0.25 every 4th, rate 0.0 nothing — and the 0.0 path is
a single attribute compare, so an untraced engine pays no per-query work
and allocates nothing.

**Program spans.**  :func:`span` is the program's one span primitive; the
build (``core/build.py``: ``deg.add*``, ``deg.refine``, ``deg.search_from``,
``deg.tick``), refinement (``core/optimize.py``: ``deg.refine.*``), the
graph's device twin (``core/graph.py``: ``deg.graph.sync``) and the async
engine's threads (``serving/async_engine.py``: ``deg.serve.*``) open one
at every layer boundary.  Off (the default) a span is the check of one
module flag and hands back a shared null context.  On
(:func:`enable`), each span opens a ``jax.profiler.TraceAnnotation`` —
so host work lands in the profiler's host plane on the device ops'
timeline — and on exit adds itself to the *span table*: count, total,
self (total minus its children on the same thread) and max seconds, keyed
by ``(root, name)`` where the root is the outermost span open on the
thread (``deg.refine.edge`` under ``deg.add`` and under ``deg.refine``
are two rows).  A ``device=True`` span waits on a device result or hands
the device a transfer: the count of device spans is the round-trip
count.  :func:`snapshot` copies the table; readers difference two.

A span that names a ``metric`` measures its block on or off, and with a
metrics registry observes ``<metric>_ms`` there on exit — that is how
``build_wave_search_ms``, ``build_wave_extend_ms`` and
``refine_chunk_ms`` (and ``DEGIndex.build_stats``) are fed.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Optional

from . import clock
from .metrics import MetricsRegistry

_ON = False
_TABLE: dict = {}
_LOCK = threading.Lock()
_LOCAL = threading.local()


def enable(on: bool = True) -> None:
    """Turn program spans on or off for the whole process."""
    global _ON
    _ON = bool(on)


def enabled() -> bool:
    return _ON


@dataclasses.dataclass
class SpanStat:
    """One row of the span table (seconds)."""

    count: int = 0
    total_s: float = 0.0
    self_s: float = 0.0
    max_s: float = 0.0
    device: bool = False


def snapshot() -> dict:
    """A copy of the span table: ``{(root, name): SpanStat}``."""
    with _LOCK:
        return {k: dataclasses.replace(v) for k, v in _TABLE.items()}


def diff(after: dict, before: dict) -> dict:
    """``after - before`` of two snapshots, rows that did not move left
    out.  ``max_s`` is the later snapshot's (a maximum does not
    difference)."""
    out = {}
    for key, a in after.items():
        b = before.get(key)
        if b is None:
            out[key] = dataclasses.replace(a)
        elif a.count != b.count:
            out[key] = SpanStat(a.count - b.count, a.total_s - b.total_s,
                                a.self_s - b.self_s, a.max_s, a.device)
    return out


def reset() -> None:
    """Empty the span table."""
    with _LOCK:
        _TABLE.clear()


class _Null:
    """The span handed out while spans are off and nothing times."""

    __slots__ = ()
    seconds = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


class _Timed:
    """Spans off, and the span feeds a metric: only the duration."""

    __slots__ = ("registry", "metric", "t0", "seconds")

    def __init__(self, registry, metric):
        self.registry, self.metric = registry, metric
        self.seconds = 0.0

    def __enter__(self):
        self.t0 = clock.now()
        return self

    def __exit__(self, *exc):
        self.seconds = clock.now() - self.t0
        if self.registry is not None:
            self.registry.histogram(self.metric + "_ms").observe(
                self.seconds * 1e3)
        return False


class _Span(_Timed):
    """An open program span: a profiler annotation plus a table row."""

    __slots__ = ("name", "device", "meta", "ann", "root", "child_s",
                 "parent")

    def __init__(self, name, registry, metric, device, meta):
        super().__init__(registry, metric)
        self.name, self.device, self.meta = name, device, meta

    def __enter__(self):
        import jax.profiler

        stack = getattr(_LOCAL, "stack", None)
        if stack is None:
            stack = _LOCAL.stack = []
        self.parent = stack[-1] if stack else None
        self.root = stack[0].name if stack else self.name
        self.child_s = 0.0
        stack.append(self)
        self.ann = jax.profiler.TraceAnnotation(self.name, **self.meta)
        self.ann.__enter__()
        self.t0 = clock.now()
        return self

    def __exit__(self, *exc):
        t = clock.now() - self.t0
        self.ann.__exit__(*exc)
        _LOCAL.stack.pop()
        if self.parent is not None:
            self.parent.child_s += t
        self.seconds = t
        with _LOCK:
            row = _TABLE.get((self.root, self.name))
            if row is None:
                row = _TABLE[(self.root, self.name)] = SpanStat(
                    device=self.device)
            row.count += 1
            row.total_s += t
            row.self_s += t - self.child_s
            if t > row.max_s:
                row.max_s = t
        if self.registry is not None and self.metric is not None:
            self.registry.histogram(self.metric + "_ms").observe(t * 1e3)
        return False


def span(name: str, registry: Optional[MetricsRegistry] = None, *,
         device: bool = False, metric: Optional[str] = None, **meta):
    """A program span named ``name`` (``deg.<layer>.<step>``), with
    ``meta`` as the profiler annotation's metadata.

    Off, this is one check of the module flag and, for a span with no
    ``metric``, the shared null context.  A span with a ``metric`` always
    measures its block: the context's ``seconds`` holds the duration, and
    with a ``registry`` the exit observes ``<metric>_ms`` there."""
    if not _ON:
        return _NULL if metric is None else _Timed(registry, metric)
    return _Span(name, registry, metric, device, meta)


class Sampler:
    """Deterministic fractional sampler.  ``take()`` returns True for
    ``rate`` of calls, evenly spaced.  Not thread-safe by design: each
    engine owns one and calls it from a single thread (the scheduler)."""

    __slots__ = ("rate", "_acc")

    def __init__(self, rate: float):
        self.rate = min(max(float(rate), 0.0), 1.0)
        self._acc = 0.0

    @property
    def active(self) -> bool:
        return self.rate > 0.0

    def take(self) -> bool:
        if self.rate <= 0.0:
            return False
        if self.rate >= 1.0:
            return True
        self._acc += self.rate
        if self._acc >= 1.0:
            self._acc -= 1.0
            return True
        return False


def span_fields(result) -> dict:
    """The per-query span timings (ms) derivable from an ``AsyncResult``'s
    monotonic stamps — the ``spans`` object of a query-log record.  Absent
    stamps (sync engine, which has no dispatch pipeline) yield a partial
    dict."""
    out: dict = {}
    sub = getattr(result, "submitted_at", None)
    dis = getattr(result, "dispatched_at", None)
    dev = getattr(result, "device_done_at", None)
    com = getattr(result, "completed_at", None)
    if sub is not None and dis is not None:
        out["queue_wait_ms"] = (dis - sub) * 1e3
    if dis is not None and dev is not None:
        out["device_ms"] = (dev - dis) * 1e3
    if dev is not None and com is not None:
        out["extract_ms"] = (com - dev) * 1e3
    if sub is not None and com is not None:
        out["total_ms"] = (com - sub) * 1e3
    return out
