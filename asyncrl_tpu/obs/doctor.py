"""``obs doctor``: offline run-health diagnosis for a recorded run_dir.

Replays everything a run left behind — ``timeseries.jsonl`` (the metric
history), ``flightrec-*.json`` (crash forensics), ``trace-*.json`` (the
span export) — into one report:

1. **Detector timeline**: the health events recorded live, merged with an
   offline :func:`~asyncrl_tpu.obs.health.replay` of the same detector
   set over the samples (same thresholds, read back from the run's meta
   line) — so runs recorded before a detector existed still get judged
   by it, and a live monitor that died mid-run loses nothing.
2. **Learning timeline** (ISSUE 8): the learning-health trajectory —
   entropy, behaviour-vs-learner KL, V-trace clip saturation, value
   explained-variance, off-policy staleness percentiles, compile counts,
   memory watermarks — first/last/min/max per metric, plus every
   recorded compile event with its static-shape blame. The offline
   replay of what the introspection layer measured live.
3. **Serving timeline**: the serving-side story — cumulative gateway/
   fleet counters (shed, deadline shed, failover, canary promote/
   rollback) from the windows, and, when the run journaled requests
   (``requests.jsonl``), the deciding-stage census for every non-200,
   per-stage duration percentiles across all hops, and the worst
   journals' budget waterfalls inlined (the ``obs explain`` shape).
4. **Bottleneck attribution**: the stall-attribution table from the run's
   newest trace export (falling back to the newest flight dump's embedded
   trace) — the ``obs report`` analysis inlined.

Exit code: 0 clean, 1 when a detector fired (recorded live or replayed),
2 when the run_dir has no readable timeseries. Speed is not judged here:
that is the benchmark's (``benchmarks/``, ``PERF.md``).
"""

from __future__ import annotations

import glob
import json
import os
from typing import Any

from asyncrl_tpu.obs import health, report, timeseries


def load_run(run_dir: str) -> dict[str, Any]:
    """{"meta", "samples", "events"} from ``<run_dir>/timeseries.jsonl``.
    Raises FileNotFoundError when the run recorded no timeseries."""
    path = os.path.join(run_dir, timeseries.FILENAME)
    return timeseries.read_jsonl(path)


def _latest_trace_doc(run_dir: str) -> tuple[dict[str, Any] | None, str | None]:
    """The newest analyzable trace document in the run_dir: a full
    ``trace-*.json`` export preferred, else the newest flight dump's
    embedded trace section."""
    traces = sorted(glob.glob(os.path.join(run_dir, "trace-*.json")))
    for path in reversed(traces):
        try:
            with open(path) as f:
                return json.load(f), path
        except (OSError, json.JSONDecodeError):
            continue
    dumps = sorted(glob.glob(os.path.join(run_dir, "flightrec-*.json")))
    for path in reversed(dumps):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if doc.get("trace"):
            return doc["trace"], path
    return None, None


# Learning-health keys the learning-timeline section summarizes, in
# display order (only keys the run actually recorded are shown).
LEARNING_KEYS = (
    "loss", "entropy", "kl", "target_kl", "rho_clip_frac", "c_clip_frac",
    "explained_variance", "staleness_p50", "staleness_p95",
    "staleness_max", "reuse_p50", "reuse_p95", "replay_fill_frac",
    "learner_stall_frac", "compiles", "infer_recompile",
    "learner_recompile",
    "mem_device_bytes_in_use", "mem_device_peak_bytes",
    "mem_host_rss_bytes", "mem_host_rss_peak_bytes",
)


def learning_timeline(
    samples: list[dict[str, Any]], events: list[dict[str, Any]]
) -> list[str]:
    """The learning-timeline section lines: metric trajectories
    (first/last/min/max over the run) + recorded compile events with
    their static-shape blame."""
    lines: list[str] = []
    for key in LEARNING_KEYS:
        values = timeseries.series_of(samples, key)
        if not values:
            continue
        lines.append(
            f"{key:<26} first {values[0]:>12.5g}  last {values[-1]:>12.5g}"
            f"  min {min(values):>12.5g}  max {max(values):>12.5g}"
        )
    if not lines:
        lines.append(
            "no learning-health metrics recorded (introspection was off, "
            "or the run predates it)"
        )
    compiles = [e for e in events if e.get("type") == "compile"]
    if compiles:
        lines.append(f"-- {len(compiles)} recorded compile event(s) --")
    for event in compiles:
        dt = event.get("compile_s")
        lines.append(
            f"compile #{event.get('seq', '?')} at {event.get('site', '?')}"
            + (f" ({1e3 * dt:.0f}ms)" if isinstance(dt, (int, float)) else "")
            + f": {event.get('blame', '?')}"
        )
    return lines


# Serving-side counters the serving-timeline section surfaces (window
# samples carry them cumulatively; only non-zero keys are shown).
SERVING_KEYS = (
    "gateway_requests", "gateway_errors", "gateway_shed",
    "gateway_deadline_shed", "gateway_stale_served",
    "gateway_fallback_served", "gateway_netfaults", "fleet_failovers",
    "fleet_ejections", "fleet_readmissions", "fleet_promotions",
    "fleet_rollbacks", "fleet_replica_restarts", "request_journals",
    "request_journals_persisted", "request_journals_capped",
)


def serving_timeline(
    run_dir: str, samples: list[dict[str, Any]]
) -> list[str]:
    """The serving-timeline section lines: shed/failover/canary counters
    from the windows, plus — when the run journaled requests — the
    deciding-stage census, per-stage duration percentiles, and the worst
    journals' budget waterfalls from ``requests.jsonl``."""
    from asyncrl_tpu.obs import requests as requests_mod

    lines: list[str] = []
    any_counter = False
    for key in SERVING_KEYS:
        values = timeseries.series_of(samples, key)
        if not values or max(values) <= 0:
            continue
        any_counter = True
        lines.append(f"{key:<28} last {values[-1]:>10.0f}")
    if not any_counter:
        lines.append("no serving traffic recorded in the timeseries")
    path = os.path.join(run_dir, requests_mod.FILENAME)
    if not os.path.exists(path):
        lines.append(
            "no requests.jsonl: request journaling was off "
            "(config.request_trace / ASYNCRL_REQUEST_TRACE)"
        )
        return lines
    docs = requests_mod.read_jsonl(path)["requests"]
    if not docs:
        lines.append("requests.jsonl holds no finished journals")
        return lines
    non200 = sum(1 for d in docs if int(d.get("status", 0)) != 200)
    lines.append(f"-- {len(docs)} journaled request(s), {non200} non-200 --")
    deciders: dict[str, int] = {}
    for d in docs:
        if int(d.get("status", 0)) != 200:
            key = str(d.get("decided_by") or "?")
            deciders[key] = deciders.get(key, 0) + 1
    for key in sorted(deciders, key=lambda k: -deciders[k]):
        lines.append(f"decided_by {key:<24} {deciders[key]:>6}")
    stage_durs: dict[str, list[float]] = {}
    for d in docs:
        for hop in d.get("hops", ()):
            stage_durs.setdefault(str(hop.get("stage", "?")), []).append(
                float(hop.get("dur_ms", 0.0))
            )
    if stage_durs:
        lines.append("per-stage dur_ms:            count       p50       "
                     "p95       max")
        for stage in sorted(stage_durs):
            vals = sorted(stage_durs[stage])
            p50 = vals[max(0, min(len(vals) - 1, int(0.50 * len(vals))))]
            p95 = vals[max(0, min(len(vals) - 1, int(0.95 * len(vals))))]
            lines.append(
                f"{stage:<26} {len(vals):>7}  {p50:>8.1f}  {p95:>8.1f}"
                f"  {vals[-1]:>8.1f}"
            )
    text, code = requests_mod.explain(run_dir, worst=3)
    if code == 0:
        lines.append("-- worst journals (obs explain --worst 3) --")
        lines.extend(text.splitlines())
    return lines


def _timeline(
    recorded: list[dict[str, Any]], replayed: list[health.HealthEvent]
) -> list[dict[str, Any]]:
    """Recorded + replayed events, deduplicated on (detector, window) —
    a live event and its offline re-derivation are the same fact."""
    out: list[dict[str, Any]] = []
    seen: set[tuple[str, int]] = set()
    for event in recorded:
        key = (event.get("detector", "?"), int(event.get("window_idx", -1)))
        if key not in seen:
            seen.add(key)
            out.append(dict(event, source="recorded"))
    for event in replayed:
        key = (event.detector, event.window_idx)
        if key not in seen:
            seen.add(key)
            out.append(dict(event.to_dict(), source="replayed"))
    out.sort(key=lambda e: (e.get("window_idx", 0), e.get("detector", "")))
    return out


def diagnose(run_dir: str) -> tuple[str, int]:
    """(report text, exit code) for a recorded run_dir."""
    try:
        run = load_run(run_dir)
    except OSError as e:
        return f"obs doctor: {run_dir}: no readable timeseries — {e}", 2
    meta, samples, recorded = run["meta"], run["samples"], run["events"]
    if not samples:
        return (
            f"obs doctor: {run_dir}: timeseries holds no window samples "
            "(the run died before its first window closed)",
            2,
        )
    thresholds = health.Thresholds.from_meta(meta)
    replayed = health.replay(samples, thresholds=thresholds)
    # The event stream mixes detector firings and compile annotations
    # (both are kind=event lines): the detector timeline reads the
    # former, the learning timeline the latter.
    health_events = [e for e in recorded if "detector" in e]
    timeline = _timeline(health_events, replayed)

    lines: list[str] = []
    steps = timeseries.series_of(samples, "env_steps")
    lines.append(
        f"obs doctor: {run_dir}"
    )
    lines.append(
        f"run: env_id={meta.get('env_id')} algo={meta.get('algo')} "
        f"backend={meta.get('backend')} platform={meta.get('platform')} "
        f"windows={len(samples)} env_steps={int(steps[-1]) if steps else 0}"
    )
    lines.append("")
    lines.append(f"== detector timeline ({len(timeline)} event(s)) ==")
    if not timeline:
        lines.append("no health events: every detector stayed quiet")
    for event in timeline:
        lines.append(
            f"[window {event.get('window_idx', '?'):>4} | "
            f"steps {int(event.get('env_steps', 0) or 0):>10}] "
            f"{event.get('severity', '?'):<8} {event.get('detector', '?'):<20} "
            f"({event.get('component', '?')}, {event.get('source')}): "
            f"{event.get('message', '')}"
        )

    lines.append("")
    lines.append("== learning timeline ==")
    lines.extend(learning_timeline(samples, recorded))

    lines.append("")
    lines.append("== serving timeline ==")
    lines.extend(serving_timeline(run_dir, samples))

    lines.append("")
    lines.append("== bottleneck attribution ==")
    doc, trace_path = _latest_trace_doc(run_dir)
    if doc is None:
        lines.append(
            "no trace export or flight dump with a trace section in the "
            "run_dir (tracing was off, or the run never exported)"
        )
    else:
        analysis = report.analyze(doc)
        if analysis["waits"]:
            share, group, name, _ = analysis["waits"][0]
            from asyncrl_tpu.obs import spans as span_names

            cause = span_names.WAIT_CAUSES.get(name, "")
            lines.append(f"from {trace_path}:")
            lines.append(
                f"dominant stall: {name} ({100.0 * share:.1f}% of {group} "
                f"wall time)" + (f" — {cause}" if cause else "")
            )
        else:
            lines.append(
                f"from {trace_path}: no wait spans recorded — nothing "
                "in the pipeline blocked long enough to attribute"
            )

    code = 1 if timeline else 0
    lines.append("")
    lines.append(
        f"verdict: {'DEGRADED' if code else 'CLEAN'} "
        f"({len(timeline)} health event(s))"
    )
    return "\n".join(lines), code
