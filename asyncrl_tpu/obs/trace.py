"""Per-thread span ring buffers: the tracing core of ``asyncrl_tpu.obs``.

Design constraints (ISSUE 5 tentpole):

- **Lock-free hot path.** Each thread owns one :class:`SpanRing`; recording
  a span is three list stores and an integer increment by the owning
  thread, no lock. Cross-thread readers (export, flight recorder) take a
  :meth:`SpanRing.snapshot`, which copies the slot lists under the GIL and
  discards the bounded window of slots a concurrent writer may have been
  overwriting mid-copy — a snapshot can lose a few newest/oldest spans,
  never produce a torn one that claims to be valid.
- **Preallocated, drop-oldest.** Rings are fixed capacity, allocated once
  per thread; overflow overwrites the oldest span and counts into
  ``dropped`` (exported as the ``trace_dropped_spans`` window counter).
- **Near-zero cost when disabled.** ``trace.span(name)`` with no armed
  tracer returns one shared no-op context manager — no allocation, no
  ring registration, one module-global read and a ``None`` check (the
  same compile-away discipline as ``utils.faults.site``).
- **One name, two clocks.** An armed span is also a
  ``jax.profiler.TraceAnnotation``: the rings keep ``perf_counter``
  stamps for the export and the flight recorder, and while a profiler
  session is active the same name is an event of its trace, beside the
  device's ops.

Arming mirrors ``utils.faults``: explicit :func:`configure` (the trainer's
``config.trace``), or lazily from ``ASYNCRL_TRACE=1`` on first use
(``ASYNCRL_TRACE_RING`` overrides the per-thread capacity).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any

from asyncrl_tpu.obs import spans as span_names

ENV_VAR = "ASYNCRL_TRACE"
ENV_RING = "ASYNCRL_TRACE_RING"
DEFAULT_CAPACITY = 4096
_FALSEY = ("", "0", "false", "no")


class _NoopSpan:
    """The disabled-mode context manager: one shared instance, no state."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _Span:
    """One in-flight span: records [enter, exit) into the owning ring, and
    is a ``jax.profiler.TraceAnnotation`` around it — which records only
    while a profiler session is active, so under ``--profile DIR`` (or any
    ``jax.profiler.start_trace``) the span is an event of the profiler's
    trace, on the device trace's own clock, nested under whatever the
    caller annotates."""

    __slots__ = ("_ring", "_name", "_t0", "_annotation")

    def __init__(self, ring: "SpanRing", name: str, annotation=None):
        self._ring = ring
        self._name = name
        self._t0 = 0.0
        self._annotation = None if annotation is None else annotation(name)

    def __enter__(self):
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._ring.record(self._name, self._t0, time.perf_counter())
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        return False


class SpanRing:
    """One thread's preallocated span storage (single-writer).

    ``idx`` counts spans ever recorded; slot ``idx % capacity`` is the
    write target, so overflow is drop-oldest by construction and
    ``dropped == max(0, idx - capacity)``. Only the owning thread writes;
    snapshot readers tolerate the bounded copy-window tear (see module
    docstring) — this is the declared non-lock discipline.
    """

    __slots__ = ("capacity", "thread_name", "group", "names", "starts",
                 "ends", "metas", "idx", "thread")

    def __init__(self, capacity: int, thread_name: str, group: str,
                 thread=None):
        self.capacity = capacity
        self.thread_name = thread_name
        # The owning Thread object (None for legacy/test construction):
        # the tracer's bounded dead-ring retention needs liveness, and
        # names alone cannot provide it.
        self.thread = thread
        # lint: thread-shared-ok(written only via tag_thread on the owning thread; readers see old or new group, both coherent)
        self.group = group
        # lint: thread-shared-ok(single-writer ring slots; snapshot discards the copy-window slots a concurrent record may touch)
        self.names: list[str | None] = [None] * capacity
        # lint: thread-shared-ok(single-writer ring slots, same snapshot discipline as names)
        self.starts: list[float] = [0.0] * capacity
        # lint: thread-shared-ok(single-writer ring slots, same snapshot discipline as names)
        self.ends: list[float] = [0.0] * capacity
        # Optional per-span metadata (request trace ids). None for the
        # overwhelming majority of spans — snapshots emit the legacy
        # (name, start, end) 3-tuple unless a meta dict is present.
        # lint: thread-shared-ok(single-writer ring slots, same snapshot discipline as names)
        self.metas: list[dict | None] = [None] * capacity
        # lint: thread-shared-ok(GIL-atomic int; single-writer monotone counter, snapshot reads it before/after the copy)
        self.idx = 0

    def record(self, name: str, start: float, end: float,
               meta: dict | None = None) -> None:
        i = self.idx % self.capacity
        self.names[i] = name
        self.starts[i] = start
        self.ends[i] = end
        self.metas[i] = meta
        self.idx += 1

    @property
    def dropped(self) -> int:
        return max(0, self.idx - self.capacity)

    def snapshot(self) -> dict[str, Any]:
        """A consistent copy of this ring, taken from ANY thread.

        Logical indices valid after the copy: ``[i1 - capacity + 1, i0)``
        where ``i0``/``i1`` are ``idx`` before/after the list copies —
        slots the writer may have overwritten (or been mid-store on)
        during the copy are excluded, so no returned span is torn.
        """
        i0 = self.idx
        names = list(self.names)
        starts = list(self.starts)
        ends = list(self.ends)
        metas = list(self.metas)
        i1 = self.idx
        lo = max(0, i1 - self.capacity + 1)
        out = []
        for j in range(lo, i0):
            slot = j % self.capacity
            name = names[slot]
            if name is not None:
                if metas[slot] is None:
                    out.append((name, starts[slot], ends[slot]))
                else:
                    out.append(
                        (name, starts[slot], ends[slot], metas[slot])
                    )
        return {
            "thread": self.thread_name,
            "group": self.group,
            "recorded": i0,
            "dropped": max(0, i0 - self.capacity),
            "spans": out,
        }


class Tracer:
    """The armed span collector: a registry of per-thread rings plus the
    perf_counter->unix clock anchor every exporter needs."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        if capacity < 2:
            raise ValueError(f"trace ring capacity must be >= 2, got {capacity}")
        self.capacity = capacity
        self._lock = threading.Lock()
        # A LIST, deliberately not a dict keyed on thread.ident: CPython
        # recycles idents, and a restarted actor's fresh ring must never
        # evict its crashed predecessor's spans from the export/dumps.
        self._rings: list[SpanRing] = []  # guarded-by: _lock
        self.pruned = 0  # guarded-by: _lock
        self._local = threading.local()
        # Clock anchor: exported timestamps are
        # (span.start - anchor_perf) in µs, wall-anchored by anchor_unix.
        self.anchor_perf = time.perf_counter()
        self.anchor_unix = time.time()
        # jax is imported here, once, and only by a process that arms
        # tracing; without it the spans stay in the rings alone.
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = None
        self._annotation = TraceAnnotation

    # Bound on RETAINED rings: dead threads' rings stay for forensics (a
    # crashed actor's spans must survive into the export/dumps), but
    # thread-per-request servers (the gateway's HTTP handlers) would
    # otherwise grow the registry one ring per connection, forever —
    # unbounded RSS and O(total requests) window closes. Past the cap,
    # the OLDEST dead rings are pruned (live rings are never touched);
    # the cap is far above any bounded fleet's thread count, so actor
    # forensics keep the old retention semantics in practice.
    RING_RETENTION = 128

    def _ring(self) -> SpanRing:
        ring = getattr(self._local, "span_ring", None)
        if ring is None:
            thread = threading.current_thread()
            ring = SpanRing(
                self.capacity, thread.name,
                span_names.thread_group(thread.name),
                thread=thread,
            )
            self._local.span_ring = ring
            with self._lock:
                self._rings.append(ring)
                if len(self._rings) > self.RING_RETENTION:
                    excess = len(self._rings) - self.RING_RETENTION
                    dead = [
                        r for r in self._rings
                        if r.thread is not None and not r.thread.is_alive()
                    ][:excess]
                    for old in dead:
                        self._rings.remove(old)
                    self.pruned += len(dead)
        return ring

    def span(self, name: str) -> _Span:
        return _Span(self._ring(), name, self._annotation)

    def tag_thread(self, group: str) -> None:
        """Override the calling thread's group (the trainer tags its drain
        thread ``learner`` — it usually runs on MainThread)."""
        self._ring().group = group

    def snapshots(self) -> list[dict[str, Any]]:
        """One snapshot per registered thread ring (any thread may call);
        dead threads' rings are retained (up to ``RING_RETENTION``, then
        oldest-dead-first pruning) — a crashed actor's spans stay in the
        export and the flight dumps, while thread-per-request handlers
        cannot grow the registry without bound."""
        with self._lock:
            rings = list(self._rings)
        return [r.snapshot() for r in rings]

    def stats(self) -> dict[str, int]:
        """Window-metric view: spans recorded and dropped, all threads."""
        with self._lock:
            rings = list(self._rings)
            pruned = self.pruned
        return {
            "trace_spans": sum(r.idx for r in rings),
            "trace_dropped_spans": sum(r.dropped for r in rings),
            "trace_threads": len(rings),
            "trace_rings_pruned": pruned,
        }


_ARM_LOCK = threading.Lock()
# Double-checked lazy arming (the faults.py pattern): writes happen under
# _ARM_LOCK; the hot-path read in active() is deliberately lock-free.
# lint: thread-shared-ok(single reference swap under _ARM_LOCK; lock-free readers see None or a fully-constructed Tracer)
_TRACER: Tracer | None = None
# lint: thread-shared-ok(GIL-atomic bool latch, written under _ARM_LOCK; a racing reader at worst re-enters the locked init once)
_ENV_CHECKED = False


def configure(enabled: bool = True, capacity: int | None = None) -> Tracer | None:
    """Arm (or disarm) process-wide tracing explicitly. Returns the armed
    tracer (None when disabling). Re-arming replaces the tracer — old
    rings stop receiving spans at each thread's next ``span()`` call."""
    global _TRACER, _ENV_CHECKED
    with _ARM_LOCK:
        if enabled:
            # `is not None`, not truthiness: capacity=0 must reach the
            # Tracer's >= 2 validation and fail fast, never silently
            # substitute the default.
            _TRACER = Tracer(
                capacity if capacity is not None else _env_capacity()
            )
        else:
            _TRACER = None
        _ENV_CHECKED = True
        return _TRACER


def _env_capacity() -> int:
    raw = os.environ.get(ENV_RING, "")
    return int(raw) if raw else DEFAULT_CAPACITY


def active() -> Tracer | None:
    """The armed tracer, lazily initialized from ``ASYNCRL_TRACE`` on
    first call (so plain scripts get tracing without code changes)."""
    global _TRACER, _ENV_CHECKED
    if not _ENV_CHECKED:
        with _ARM_LOCK:
            if not _ENV_CHECKED:
                if os.environ.get(ENV_VAR, "").lower() not in _FALSEY:
                    _TRACER = Tracer(_env_capacity())
                _ENV_CHECKED = True
    return _TRACER


def enabled() -> bool:
    return active() is not None


def env_requests() -> bool | None:
    """What ASYNCRL_TRACE asks for: None when unset (the config decides),
    else its truthiness — the precedence obs.setup implements."""
    raw = os.environ.get(ENV_VAR)
    if raw is None:
        return None
    return raw.lower() not in _FALSEY


def span(name: str):
    """THE instrumentation entry point: a context manager recording one
    span into the calling thread's ring — or the shared no-op when
    tracing is disabled (no allocation, no ring registration)."""
    tracer = active()
    if tracer is None:
        return _NOOP
    return tracer.span(name)


def record_span(name: str, start: float, end: float,
                meta: dict | None = None) -> None:
    """Record one already-timed span (perf_counter stamps) into the
    calling thread's ring — the request-journal replay path, which emits
    trace-id-stamped ``request.*`` spans at journal close, and the
    ``compile.*`` events of ``obs/introspect.py``'s listener. Rings only:
    a ``TraceAnnotation`` stamps the profiler's clock as it is entered and
    left, so a span that is already over cannot become one. No-op when
    tracing is disabled."""
    tracer = active()
    if tracer is not None:
        tracer._ring().record(name, start, end, meta)


def tag_thread(group: str) -> None:
    """Tag the calling thread's group in the armed tracer (no-op when
    disabled)."""
    tracer = active()
    if tracer is not None:
        tracer.tag_thread(group)


def stats() -> dict[str, int]:
    """Window-metric counters ({} when disabled)."""
    tracer = active()
    return tracer.stats() if tracer is not None else {}


def snapshots() -> list[dict[str, Any]]:
    """All thread-ring snapshots ([] when disabled)."""
    tracer = active()
    return tracer.snapshots() if tracer is not None else []
