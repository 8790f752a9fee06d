"""Readers of the ``granite_h_rl`` cell's shares of a peak: from the device
trace, the program's counters in the traced updates and the counts in
``granite_counts.py``. Each gives ``None`` where it finds nothing to read
(no trace, no such scope, a program without this policy), and the harness
then leaves the metric out.

A metric is ``layer_metrics/<name>.json`` for its ``params`` and a one-line
``layer_metrics/<name>.py`` that imports its reader from here as ``read``.
"""

from __future__ import annotations

from benchmarks import granite_counts, readers


def granite_step_mfu(ev):
    """FLOPs the traced updates required (rollout forward + learner forward
    and backward from the shapes, the chunked scan's algebra over its chunk
    shapes, attention over the rows attended) over the seconds an op ran on
    the chip inside the traced window, times the chip's peak: the share of
    the whole step."""
    trace, updates, m = ev.get("trace"), ev.get("traced_updates"), ev.get("granite")
    if trace is None or not updates or m is None or not trace.busy_s:
        return None
    g = ev["geometry"]
    flops = updates * granite_counts.train_flops_per_update(
        m["dims"], g["num_envs"] // ev["chips"], g["unroll_len"], m["attended"])
    return 100.0 * flops / trace.busy_s / ev["peaks"]["flops_per_s_bf16"]


def granite_rollout_hbm_roofline(ev):
    """Bytes one decode step must move (the weights in bfloat16, the Mamba
    layers' states and conv tails read and written, the key and value rows
    up to ``len``) x T / 819 GB/s / ``rollout`` time. Bytes bound it."""
    m = ev.get("granite")
    ms = readers.scope_device_ms(ev, "rollout")
    if m is None or ms is None:
        return None
    g = ev["geometry"]
    per_step = granite_counts.decode_bytes_per_step(
        m["dims"], g["num_envs"] // ev["chips"], m["attended"])
    least_ms = per_step * g["unroll_len"] / ev["peaks"]["hbm_bytes_per_s"] * 1e3
    return 100.0 * least_ms / ms

