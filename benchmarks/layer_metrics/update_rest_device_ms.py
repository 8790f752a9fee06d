"""Device time an update that none of the step's scopes names."""

from benchmarks import xplane


def read(ev, scopes: list[str], needs: list[str]):
    """Busy time (mean over chips, inside ``bench.window``) less the self
    time of the ops under any of ``scopes``, per update; an op under two of
    them is taken off once. A scope that labels no op takes nothing off, but
    for those of ``needs``: a program that does not label them has their
    time in the remainder, which is then another quantity, and gets no
    number."""
    trace, updates = ev.get("trace"), ev.get("traced_updates")
    if trace is None or not updates:
        return None
    ops = [(op, t) for d in trace.devices for op, t in d.op_self_times]
    if not all(any(xplane.in_scope(op, s) for op, _ in ops) for s in needs):
        return None
    named = sum(t for op, t in ops if any(xplane.in_scope(op, s) for s in scopes))
    busy = sum(d.busy_ps() for d in trace.devices)
    return (busy - named) / len(trace.devices) / 1e9 / updates
