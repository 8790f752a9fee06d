"""Readers of the sequence-policy cell's per-layer metrics: shares of a
peak, from the device trace and the counts in ``seq_counts.py``. Each gives
``None`` where it finds nothing to read (no trace, no such scope, a program
without the sequence policy), and the harness then leaves the metric out.

A metric is ``layer_metrics/<name>.json`` for its ``params`` and a one-line
``layer_metrics/<name>.py`` that imports its reader from here as ``read``.
"""

from __future__ import annotations

from benchmarks import readers, seq_counts


def seq_step_mfu(ev):
    """FLOPs the traced updates required (rollout forward + learner forward
    and backward, from shapes) over the seconds an op ran on the chip inside
    the traced window, times the chip's peak: the share of the whole step."""
    trace, updates, seq = ev.get("trace"), ev.get("traced_updates"), ev.get("seq")
    if trace is None or not updates or seq is None or not trace.busy_s:
        return None
    g = ev["geometry"]
    flops = updates * seq_counts.train_flops_per_update(
        seq["dims"], g["num_envs"] // ev["chips"] * g["unroll_len"],
        seq["attended"], seq["held_per_token"],
    )
    return 100.0 * flops / trace.busy_s / ev["peaks"]["flops_per_s_bf16"]


def _bytes_roofline(ev, scopes: tuple[str, ...], bytes_per_step: float):
    """Least time the chip could take to move ``bytes_per_step`` on each of
    the fragment's T decode steps, over the device time under ``scopes``
    (None unless the first of them is in the trace)."""
    times = [readers.scope_device_ms(ev, scope) for scope in scopes]
    if times[0] is None:
        return None
    ms = sum(t or 0.0 for t in times)
    least_ms = (
        bytes_per_step * ev["geometry"]["unroll_len"]
        / ev["peaks"]["hbm_bytes_per_s"] * 1e3
    )
    return 100.0 * least_ms / ms


def rollout_hbm_roofline(ev):
    """Bytes one decode step must move (weights touched at the products'
    width + carry read and written) x T / 819 GB/s / ``rollout`` time."""
    seq = ev.get("seq")
    if seq is None:
        return None
    g = ev["geometry"]
    return _bytes_roofline(ev, ("rollout",), seq_counts.decode_bytes_per_step(
        seq["dims"], g["num_envs"] // ev["chips"], seq["attended"]
    ))


def kda_step_roofline(ev):
    """The KDA layers' carry (float32 states and conv tails) read and
    written once a step x T / 819 GB/s / the time under ``kda_step`` and
    ``core_reset``: the two scopes that pass over those leaves, and the
    bytes of both. The reset's time belongs to the recurrence: XLA fuses
    the write of the new state into the reset's select and names the
    fusion after the select, so ``kda_step`` alone leaves out a pass over
    the state (over that time alone the first chip run read 178%:
    PERF.md, PR 26). Bytes bound it (7 flop per 8 bytes)."""
    seq = ev.get("seq")
    if seq is None:
        return None
    g = ev["geometry"]
    return _bytes_roofline(ev, ("kda_step", "core_reset"), seq_counts.kda_carry_bytes(
        seq["dims"], g["num_envs"] // ev["chips"]
    ))
