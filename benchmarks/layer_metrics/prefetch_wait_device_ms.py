"""Device time an update the rollout waits on XLA's prefetches."""

from benchmarks import xplane


def read(ev, ops: list[str], scope: str):
    """Self time of the ops whose HLO name is one of ``ops`` with its number
    (``slice-done.493``) and whose path has ``scope`` as a component, per
    update, mean over chips; ``None`` where the trace has none."""
    trace, updates = ev.get("trace"), ev.get("traced_updates")
    if trace is None or not updates:
        return None
    ps = sum(
        t for d in trace.devices for op, t in d.op_self_times
        if op.name.split(" = ", 1)[0].lstrip("%").split(".")[0] in ops
        and xplane.in_scope(op, scope)
    )
    return ps / len(trace.devices) / 1e9 / updates if ps else None
