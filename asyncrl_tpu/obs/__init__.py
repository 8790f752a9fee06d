"""``asyncrl_tpu.obs``: tracing, metrics registry, run-health telemetry.

The observability subsystem for the async host path (ISSUE 5 + ISSUE 7):

- :mod:`asyncrl_tpu.obs.trace` — per-thread lock-free span rings behind
  ``trace.span("actor.env_step")`` context managers (near-zero cost when
  disabled).
- :mod:`asyncrl_tpu.obs.spans` — the span taxonomy + wait/compute
  classification + stall causes.
- :mod:`asyncrl_tpu.obs.registry` — the counters/gauges/histograms
  registry the metric window sinks drain from.
- :mod:`asyncrl_tpu.obs.export` — Chrome/Perfetto ``trace_event`` JSON
  export and its schema validator.
- :mod:`asyncrl_tpu.obs.report` — per-stage time shares, wait-vs-compute
  breakdown, stall attribution (the ``python -m asyncrl_tpu.obs report``
  CLI).
- :mod:`asyncrl_tpu.obs.flightrec` — crash-time span/counter dumps to
  ``runs/<run>/flightrec-*.json``.
- :mod:`asyncrl_tpu.obs.timeseries` — the bounded per-window sample ring
  persisted to ``runs/<run>/timeseries.jsonl``.
- :mod:`asyncrl_tpu.obs.health` — the detector framework evaluated at
  each window close (NaN loss, stall attribution, fps collapse, SLO
  breach persistence, restart storms, eval regression), each firing a
  flight-recorder dump with ``reason=health.<detector>``.
- :mod:`asyncrl_tpu.obs.http` — the ``/metrics`` / ``/healthz`` /
  ``/timeseries`` exposition endpoint (``config.obs_http_port`` /
  ``ASYNCRL_OBS_PORT``; off by default — zero threads when off).
- :mod:`asyncrl_tpu.obs.introspect` — training introspection (ISSUE 8):
  off-policy staleness aggregation, compile/recompile accounting with
  static-shape blame on the learner/inference entry points, and memory
  watermarks (``config.introspect`` / ``ASYNCRL_INTROSPECT``; on by
  default).
- :mod:`asyncrl_tpu.obs.doctor` — offline run diagnosis
  (``python -m asyncrl_tpu.obs doctor <run_dir>``).

:func:`setup` is the trainer-facing entry point: it arms tracing and the
flight recorder per ``config.trace`` (``ASYNCRL_TRACE`` wins when set,
mirroring ``utils.faults``), mounts the time-series store + health
monitor (+ the HTTP endpoint when a port is configured), and returns the
handle the trainer's window aggregation and teardown drive.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import sys
import time

from asyncrl_tpu.obs import export, flightrec, introspect, registry, trace
from asyncrl_tpu.obs import health as health_mod
from asyncrl_tpu.obs import http as http_mod
from asyncrl_tpu.obs import requests as requests_mod
from asyncrl_tpu.obs import timeseries as timeseries_mod

# Process-wide export sequence: two agents sharing a run_dir (A/B
# harnesses) must never overwrite each other's same-second export.
# lint: thread-shared-ok(itertools.count.__next__ is GIL-atomic)
_EXPORT_SEQ = itertools.count(1)

__all__ = [
    "PipelineObs", "setup", "export", "flightrec", "introspect",
    "registry", "trace",
]


def _default_run_dir(config) -> str:
    slug = "".join(
        ch if ch.isalnum() else "-" for ch in str(config.env_id)
    ).strip("-").lower()
    stamp = time.strftime("%Y%m%d-%H%M%S")
    return os.path.join(
        "runs", f"{slug}-{config.algo}-s{config.seed}-{stamp}-{os.getpid()}"
    )


def _arm_requests(config, run_dir: str | None) -> None:
    """Arm/disarm request hop journaling (obs/requests.py) per
    ``config.request_trace``, ``ASYNCRL_REQUEST_TRACE`` winning when set
    — the trace-arming precedence. Off DISARMS any predecessor's store
    (fresh-agent semantics); on with no run_dir keeps the recent ring and
    span emission but persists no ``requests.jsonl``."""
    env = requests_mod.env_requests()
    on = bool(config.request_trace) if env is None else env
    if on:
        requests_mod.arm(
            run_dir=run_dir,
            cap=config.request_journal_cap,
            slow_ms=config.request_sample_slow_ms,
            meta={"env_id": config.env_id, "algo": config.algo,
                  "seed": config.seed},
        )
    else:
        requests_mod.disarm()


def _platform() -> str | None:
    """The JAX backend platform for the timeseries meta (the doctor's
    report names it). Lazy + failure-tolerant: obs must stay
    importable (and setup must succeed) without a working jax install."""
    # lint: broad-except-ok(metadata enrichment only; a broken jax backend must not break observability setup)
    try:
        import jax

        return jax.default_backend()
    except Exception:
        return None


class PipelineObs:
    """One trainer's observability handle (always constructed; inert when
    everything is disabled — ``window()`` still drains the registry, which
    is the one metrics path that runs unconditionally). The handle holds
    THE tracer/recorder/store its setup mounted: a later trainer re-arming
    the globals must never redirect this trainer's export, stats, or
    health telemetry to its own rings."""

    def __init__(self, enabled: bool, run_dir: str | None, recorder,
                 tracer=None, store=None, monitor=None, http=None,
                 introspect_on: bool = False):
        self.enabled = enabled
        self.run_dir = run_dir
        self._recorder = recorder
        self._tracer = tracer
        self.store = store
        self.monitor = monitor
        self.http = http
        # Training introspection (obs/introspect.py): when on, the window
        # drain samples the memory watermarks (registry gauges) and
        # persists pending compile events into the time-series store.
        self.introspect_on = introspect_on

    def window(self) -> dict[str, float]:
        """Counters/gauges/histograms + this trainer's trace stats for one
        metrics window."""
        out = registry.window()
        if self._tracer is not None:
            out.update(self._tracer.stats())
        return out

    def observe_window(self, agg: dict) -> dict:
        """THE per-window drain: merges :meth:`window` (ONE registry
        snapshot) into ``agg``, then runs the health detectors and records
        the sample into the time-series store. Every downstream consumer —
        stdout, JSONL, TensorBoard, the timeseries, ``/metrics`` — sees
        this identical dict: no sink can drift on which keys a window
        carries. Returns ``agg`` (mutated in place)."""
        if self.introspect_on:
            # Memory watermarks FIRST (they publish as registry gauges),
            # so the one registry snapshot below already carries them.
            introspect.sample_memory()
        agg.update(self.window())
        if self.monitor is not None:
            # The monitor owns the store.append (sample + annotations in
            # order); setup() never mounts a store without a monitor.
            self.monitor.on_window(agg)
        if self.store is not None:
            # Compile events recorded since the last window (any thread)
            # persist as kind=event annotations AFTER the sample, on this
            # (the writer) thread — the store's single-writer contract.
            for event in introspect.drain_compile_events():
                self.store.annotate(event)
        return agg

    def export_trace(self) -> str | None:
        """Write THIS trainer's rings as a Perfetto export into the run
        dir (None when tracing is off); called from close()."""
        if not self.enabled or self.run_dir is None or self._tracer is None:
            return None
        seq = next(_EXPORT_SEQ)
        # stamp + pid + per-process seq: unique across agents in one
        # process AND across processes sharing a run_dir.
        path = os.path.join(
            self.run_dir,
            f"trace-{time.strftime('%Y%m%d-%H%M%S')}"
            f"-{os.getpid()}-{seq:03d}.json",
        )
        doc = export.to_trace_events(
            self._tracer.snapshots(),
            self._tracer.anchor_perf,
            self._tracer.anchor_unix,
        )
        return export.write_document(doc, path)

    def close(self) -> None:
        """Flush this trainer's flight recorder (only if it is still the
        armed one — a newer trainer's recorder is not ours to close).
        Non-destructive and re-callable: ``train()`` calls it at the end
        of EVERY call, and the agent may train again."""
        if self._recorder is not None and flightrec.active() is self._recorder:
            self._recorder.drain()

    def shutdown(self) -> None:
        """Final teardown (the agent's ``close()``): stop the exposition
        endpoint, close the time-series JSONL, flush forensics.
        Idempotent."""
        if self.http is not None:
            self.http.stop()
            self.http = None
        if self.store is not None:
            self.store.close()
        self.close()


def setup(config) -> PipelineObs:
    """Arm tracing + flight recorder + run-health telemetry per config/env.

    ``ASYNCRL_TRACE`` (when present) wins over ``config.trace``, and
    ``ASYNCRL_OBS_PORT`` over ``config.obs_http_port`` — the
    no-code-change knobs, exactly the ``ASYNCRL_FAULTS`` precedence. The
    registry resets so a fresh agent never reports a predecessor's
    counters (same semantics as re-arming faults).

    The health layer (store + detectors) mounts when tracing is on OR an
    exposition port is configured; with both off the handle is inert and
    the per-window cost is exactly one registry snapshot. The HTTP server
    thread exists only when a port is configured (endpoint off ⇒ zero
    threads).
    """
    registry.registry().reset()
    # A fresh agent must never persist a predecessor's compile events
    # into its own run_dir (the registry-reset semantics).
    introspect.reset()
    intro = introspect.enabled(config)
    env = trace.env_requests()
    enabled = bool(config.trace) if env is None else env
    # Always RE-ARM (even under env arming): a fresh agent gets fresh
    # rings — its export/dumps/stats must never include a predecessor's
    # spans. Env arming keeps the env's ring capacity; config arming
    # uses config.trace_ring.
    tracer = trace.configure(
        enabled, capacity=config.trace_ring if env is None else None
    )
    port = http_mod.env_port(config.obs_http_port)
    if not enabled and port == 0:
        # Disarm any predecessor's flight recorder too: a trace=False
        # agent must never dump forensics into an OLD agent's run_dir
        # with the old agent's config embedded (faults.arm("") precedent).
        flightrec.disarm()
        # Request journaling is orthogonal to span tracing: a serving
        # deployment may want hop journals without paying for ring
        # tracing, so it arms even on this early-return path.
        _arm_requests(
            config,
            os.environ.get("ASYNCRL_RUN_DIR") or config.run_dir or None,
        )
        return PipelineObs(False, None, None, introspect_on=intro)
    if enabled:
        run_dir = (
            os.environ.get("ASYNCRL_RUN_DIR")
            or config.run_dir
            or _default_run_dir(config)
        )
        recorder = flightrec.arm(
            run_dir, window_s=config.trace_window_s, config=config
        )
    else:
        # Endpoint without tracing: live exposition only. No flight
        # recorder (nothing armed to dump spans), and the timeseries
        # persists only if the operator named a run_dir explicitly.
        flightrec.disarm()
        recorder = None
        run_dir = os.environ.get("ASYNCRL_RUN_DIR") or config.run_dir or None
    _arm_requests(config, run_dir)
    thresholds = health_mod.Thresholds.from_config(config)
    store = timeseries_mod.TimeSeriesStore(
        capacity=config.obs_timeseries_cap,
        persist_path=(
            os.path.join(run_dir, timeseries_mod.FILENAME) if run_dir else None
        ),
        meta={
            "env_id": config.env_id,
            "algo": config.algo,
            "backend": config.backend,
            "seed": config.seed,
            "num_envs": config.num_envs,
            "unroll_len": config.unroll_len,
            "platform": _platform(),
            "thresholds": dataclasses.asdict(thresholds),
        },
    )
    # The monitor binds THE recorder this setup armed (None when tracing
    # is off): a later trainer re-arming the global flight recorder must
    # never receive — or redirect — this trainer's health forensics.
    monitor = health_mod.HealthMonitor(
        thresholds=thresholds, store=store, tracer=tracer,
        recorder=recorder,
    )
    server = None
    if port != 0:
        try:
            server = http_mod.ObsHTTPServer(
                port=port, store=store, monitor=monitor,
                bind_host=http_mod.env_host(config.obs_http_host),
            ).start()
        except OSError as e:
            # A taken/forbidden port must not kill training — the run is
            # the product, the endpoint is the window onto it.
            print(
                f"asyncrl_tpu.obs: could not bind exposition endpoint on "
                f"port {port}: {e} (continuing without /metrics)",
                file=sys.stderr,
            )
    return PipelineObs(
        enabled, run_dir, recorder, tracer=tracer,
        store=store, monitor=monitor, http=server, introspect_on=intro,
    )
