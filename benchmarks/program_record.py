"""Readers of the program's own record of set-up and compiles.

``asyncrl_tpu.obs.introspect.process_record()`` is the process's: phases
``(name, t0, t1)`` and compile events ``(event, fun_name, t_end,
duration_s)`` on ``time.perf_counter()``, the clock of the harness's
``evidence["window"]``. The harness and the program share a process, so a
reader asks for the record itself, after the loop. A process may have built
many agents (a test worker has), so a reader takes the LAST phase of its
name that ended before the measured window opened. A program without the
record (a parent commit), a phase that never ran, or a phase whose events
the record's cap has already dropped, gives ``None``, and the harness
leaves the metric out. The COUNT of a phase's programs does not depend on
the record's events: the harness logs the backend's events itself, on the
same clock, and ``programs_in_phase`` counts those inside the phase.

A metric is ``layer_metrics/<name>.json`` for its ``params`` and a one-line
``layer_metrics/<name>.py`` that imports its reader from here as ``read``.
"""

from __future__ import annotations

from benchmarks import xplane

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


def _find(ev, phase: str) -> tuple[dict, float, float] | None:
    """The record, and ``t0, t1`` of the last ``phase`` in it that ended
    before the window opened."""
    from asyncrl_tpu.obs import introspect

    read = getattr(introspect, "process_record", None)
    if read is None:
        return None
    record = read()
    ended = [(t0, t1) for name, t0, t1 in record["phases"]
             if name == phase and t1 <= ev["window"][0]]
    return (record, *ended[-1]) if ended else None


def _events_in(ev, phase: str, events) -> tuple[list, float, float] | None:
    """The compile events of the named kinds stamped inside the phase, with
    its ``t0, t1``; ``None`` also where the record's cap has dropped events
    and it no longer reaches back to the phase's start."""
    found = _find(ev, phase)
    if found is None:
        return None
    record, t0, t1 = found
    compiles = record["compiles"]
    if record["dropped"] and (not compiles or compiles[0][2] > t0):
        return None
    return [c for c in compiles if c[0] in events and t0 <= c[2] <= t1], t0, t1


def phase_seconds(ev, phase: str):
    """Wall seconds of a set-up phase."""
    found = _find(ev, phase)
    return None if found is None else found[2] - found[1]


def programs_in_phase(ev, phase: str):
    """Programs asked of the backend inside a phase: each one a compile, or
    the load from the persistent cache that stood in for it. The phase is
    the record's; the events are counted from the harness's own log of them
    (``loops/common.py Counters``, armed before ``make_agent`` and never
    cut), since a long set-up pushes the phase's events out of the newest
    16,384 the record keeps."""
    found = _find(ev, phase)
    stamps = ev.get("counters", {}).get("backend_compile_stamps")
    if found is None or stamps is None:
        return None
    _, t0, t1 = found
    return sum(1 for t in stamps if t0 <= t <= t1)


def compile_seconds_in_phase(ev, phase: str, events: list[str]):
    """Seconds of a phase spent in compile events of the given kinds: the
    union of their intervals, clipped to the phase. JAX reports a trace of
    every function traced inside another's, so a sum of durations would
    count nested seconds once per level."""
    found = _events_in(ev, phase, events)
    if found is None:
        return None
    inside, t0, t1 = found
    intervals = [(t_end - duration, t_end) for _, _, t_end, duration in inside]
    return float(xplane.union_ps(xplane.clip(intervals, t0, t1)))
