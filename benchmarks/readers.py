"""General readers of per-layer metrics.

A metric is ``layer_metrics/<name>.json``: ``{"reader": <a function here>,
"params": {...}}``, or a module ``layer_metrics/<name>.py`` with its own
``read(evidence, **params)``. A reader takes the run's evidence (the
profiler trace, the program's counters, shapes and peaks) and returns a
number, or ``None`` where it finds nothing to read; the harness then
leaves the metric out.
"""

from __future__ import annotations

from benchmarks import flops


def counter(ev, key: str, scale: float = 1.0):
    value = ev["counters"].get(key)
    return None if value is None else float(value) * scale


def _per_update(ev, picoseconds: float, unit: float):
    updates = ev.get("traced_updates")
    if not updates:
        return None
    return picoseconds / unit / updates


def scope_device_ms(ev, scope: str):
    """Device time per update under a ``jax.named_scope``, mean over chips."""
    trace = ev.get("trace")
    if trace is None:
        return None
    ps = sum(d.scope_ps(scope) for d in trace.devices) / len(trace.devices)
    return _per_update(ev, ps, 1e9) if ps else None


def mosaic_device_us(ev, kernel: str):
    """Device time per call of one Mosaic (compiled Pallas) kernel on chip
    0: the custom calls whose op carries the kernel's ``name=``. A step has
    other kernels' calls by the thousand; they are not this one's."""
    trace = ev.get("trace")
    calls = trace.devices[0].mosaic_calls(kernel) if trace else []
    if not calls:
        return None
    return sum(e.duration_ps for e in calls) / len(calls) / 1e6


def fused_vtrace_roofline(ev, kernel: str):
    """Least time the chip could take for the kernel's bytes (it is bound
    by bytes: ~10 flop per 32 bytes) over the time it took, in percent."""
    per_call_us = mosaic_device_us(ev, kernel)
    if per_call_us is None:
        return None
    g = ev["geometry"]
    bytes_moved = flops.fused_vtrace_bytes(
        g["unroll_len"], g["num_envs"] // ev["chips"]
    )
    least_us = bytes_moved / ev["peaks"]["hbm_bytes_per_s"] * 1e6
    return 100.0 * least_us / per_call_us


def model_flops_util(ev):
    """FLOPs the traced updates required, from the layer shapes, over the
    seconds in which an op ran on a chip inside the traced window (the
    trace's busy time, mean over chips) times the chip's peak: how well
    the step uses the chip while it runs. Time the chip waits for the host
    is ``device_idle_share``'s: a slow dispatch lowers the end-to-end rate
    and raises that share, and leaves this where it is."""
    trace = ev.get("trace")
    updates = ev.get("traced_updates")
    if trace is None or not updates or "model" not in ev or not trace.busy_s:
        return None
    g = ev["geometry"]
    per_chip = flops.train_flops_per_call(
        ev["model"], g["num_envs"] // ev["chips"], g["unroll_len"], updates,
        g["rollout_on_device"],
    )
    return 100.0 * per_chip / trace.busy_s / ev["peaks"]["flops_per_s_bf16"]


def collective_ms(ev, exposed: bool = False):
    """All-reduce (and any other collective) time per update on chip 0;
    ``exposed``: only the part during which no other op runs there."""
    trace = ev.get("trace")
    if trace is None or len(trace.devices) < 2:
        return None
    total, bare = trace.devices[0].collectives()
    return _per_update(ev, bare if exposed else total, 1e9)


def device_idle_share(ev):
    trace = ev.get("trace")
    if trace is None or not trace.window_s:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
