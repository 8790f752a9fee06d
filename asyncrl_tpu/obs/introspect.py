"""Training introspection: learning health and device behavior as telemetry.

Everything in ``obs/`` so far watches the *system* — fps, stalls, restarts,
latency. Nothing measured whether the *learning* is healthy or what the
*device* is doing. This module closes that gap with three host-side pieces
(the device-side half lives in the loss aux — ``ops/losses.py`` exports
behaviour-vs-learner KL, V-trace rho/c clip fractions, and value
explained-variance as loss metrics when ``config.introspect`` is on):

- :class:`StalenessWindow` — per-window off-policy staleness aggregation.
  Each consumed fragment carries its behaviour-params version (the
  ``ParamStore`` version stamped into ``Fragment.version``); the trainer
  feeds each fragment's lag-in-learner-updates here and drains
  ``staleness_p50/p95/max/mean`` at window close. IMPACT-style
  staleness-tolerant replay (PAPERS.md, arxiv 1912.00167) is only safe when
  off-policy-ness is *observed*, not assumed — this is the observation.

- :func:`instrument` — a thin wrapper around a jitted callable that counts
  (re)compilations with static-shape blame. Detection is a signature set
  over the argument shapes/dtypes (deterministic and testable: the counter
  trips exactly when an argument SHAPE changes — the same condition that
  keys jit's own cache), so the inference server's partial-batch recompile
  behavior (``rollout/inference_server.py``) is measurable for the first
  time. Each detected compile increments its registry counters (the shared
  ``compiles`` total plus site counters like ``infer_recompile``), observes
  the call's wall time into the ``compile_ms`` histogram (the compile-time
  vs run-time split: steady-state calls are covered by the existing
  ``learner.update``/``serve.dispatch`` spans, compile calls additionally
  get a ``<site>.compile`` span and the histogram), and pushes a structured
  event that the trainer's window close persists into ``timeseries.jsonl``
  as a ``kind=event`` annotation. The count is per-wrapper-lifetime: wrap
  ONCE next to where the jit cache lives (the trainer holds the jitted
  inference fn across supervised server rebuilds, so the counter never
  resets with the server).

- :func:`sample_memory` — per-window memory watermarks: device memory
  stats where the backend supports them (``Device.memory_stats()``;
  ``mem_device_bytes_in_use`` / ``mem_device_peak_bytes``), with a
  host-RSS fallback (``mem_host_rss_bytes`` from /proc/self/statm, plus a
  monotone ``mem_host_rss_peak_bytes`` watermark) — published as registry
  gauges so every window sink and ``/metrics`` carry them.

- :func:`phase` / :func:`process_record` — the always-on record of the
  PROCESS, not of an agent: the set-up phases it went through
  (``setup.*``, a dozen a process) and every program JAX traced, lowered,
  compiled or loaded from the persistent cache, as JAX itself reports them
  (one ``jax.monitoring`` listener, which runs only when JAX builds a
  program). ``obs.setup`` does not clear it. On ``time.perf_counter()``,
  the clock of the span rings.

Arming: ``config.introspect`` (default on), with ``ASYNCRL_INTROSPECT``
winning when set — the no-code-change A/B knob, the ``ASYNCRL_TRACE``
precedence. ``scripts/introspect_smoke.sh`` is the on/off A/B gate
(identical losses, overhead within tolerance).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Iterable

import numpy as np

from asyncrl_tpu.obs import registry, trace
from asyncrl_tpu.obs import spans as span_names

ENV_VAR = "ASYNCRL_INTROSPECT"
_FALSEY = ("", "0", "false", "no")

# Bounded in-memory compile-event log (the timeseries JSONL keeps them all
# once drained; an undrained process — store off — caps here).
COMPILE_EVENTS_CAP = 256


def env_requests() -> bool | None:
    """What ASYNCRL_INTROSPECT asks for: None when unset (the config
    decides), else its truthiness — the obs.setup/trace precedence."""
    raw = os.environ.get(ENV_VAR)
    if raw is None:
        return None
    return raw.lower() not in _FALSEY


def enabled(config) -> bool:
    """Is introspection on for ``config``? Env wins when set."""
    env = env_requests()
    if env is not None:
        return env
    return bool(config.introspect)


# ------------------------------------------------------------- staleness


class StalenessWindow:
    """Per-window staleness-lag aggregation (lag in learner updates).

    Single-thread by contract: the trainer's learner-drain thread both
    observes (per consumed fragment) and drains (at window close) — the
    same thread, so no lock. Keys follow the window-metric convention:
    ``staleness_p50`` / ``staleness_p95`` / ``staleness_max`` /
    ``staleness_mean``; a window that consumed no fragments contributes
    no keys (absent, never a misleading 0).
    """

    def __init__(self) -> None:
        self._lags: list[float] = []

    def observe(self, lag_updates: float) -> None:
        self._lags.append(float(lag_updates))

    def drain(self) -> dict[str, float]:
        if not self._lags:
            return {}
        lags = np.asarray(self._lags, np.float64)
        self._lags = []
        return {
            "staleness_p50": float(np.percentile(lags, 50)),
            "staleness_p95": float(np.percentile(lags, 95)),
            "staleness_max": float(lags.max()),
            "staleness_mean": float(lags.mean()),
        }


# ------------------------------------------------------- compile tracking


class _CompileLog:
    """Process-wide bounded compile-event sink, drained on the trainer's
    window-close thread into the time-series store."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._events: deque[dict] = deque(
            maxlen=COMPILE_EVENTS_CAP
        )  # guarded-by: _lock
        self._dropped = 0  # guarded-by: _lock

    def push(self, event: dict) -> None:
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self._dropped += 1
            self._events.append(event)

    def drain(self) -> list[dict]:
        with self._lock:
            out = list(self._events)
            self._events.clear()
            return out

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0


_LOG = _CompileLog()


def drain_compile_events() -> list[dict]:
    """Pop every pending compile event (the window-close drain)."""
    return _LOG.drain()


def reset() -> None:
    """Drop pending compile events AND the host-RSS peak watermark (a
    fresh trainer's obs setup — a new agent must never persist a
    predecessor's compiles, nor report a peak its own run never
    reached, into its run_dir). The process record stays: it is the
    process's, not an agent's."""
    global _RSS_PEAK
    _LOG.reset()
    _RSS_PEAK = 0.0


# ---------------------------------------------------------- process record

# Entries kept of each kind (drop-oldest, counted). JAX reports a trace of
# every function traced inside another's: one benchmark process of the
# ``atari_impala`` cell (an agent, its K=8 step, the reference's loss)
# reports ~3,500 events, nine tenths of them nested traces, and what is
# read last is the oldest (the programs of ``make_agent``). So four times
# that; the cap is for a process that never stops building programs.
PROCESS_RECORD_CAP = 16384

# jax.monitoring duration event -> the span it becomes under an armed tracer.
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": span_names.COMPILE_TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": span_names.COMPILE_LOWER,
    "/jax/core/compile/backend_compile_duration": span_names.COMPILE_BACKEND,
    "/jax/compilation_cache/cache_retrieval_time_sec":
        span_names.COMPILE_CACHE_LOAD,
}


class _ProcessRecord:
    """What this process did to get ready, for as long as it lives: set-up
    phases ``(name, t0, t1)`` and compile events ``(event, fun_name, t_end,
    duration_s)``, both on ``time.perf_counter()``. A fact of the process:
    agents come and go (``obs.setup`` resets THEIR counters), the programs
    they made JAX build stay built, and stay recorded."""

    def __init__(self, cap: int = PROCESS_RECORD_CAP) -> None:
        self._lock = threading.Lock()
        self._phases: deque[tuple] = deque(maxlen=cap)  # guarded-by: _lock
        self._compiles: deque[tuple] = deque(maxlen=cap)  # guarded-by: _lock
        self._dropped = 0  # guarded-by: _lock
        self._pool_sites = {"kernel": 0, "fallback": 0}  # guarded-by: _lock
        self._kda_sites = dict.fromkeys(  # guarded-by: _lock
            ("step", "step_kernel", "chunk", "pair", "pair_kernel"), 0)
        self._moe_sites = dict.fromkeys(  # guarded-by: _lock
            ("grouped", "gathered", "dense", "dense_skip"), 0)
        self._gqa_sites = {"step": 0, "step_kernel": 0}  # guarded-by: _lock
        self._dsa_sites = {"step": 0, "step_kernel": 0}  # guarded-by: _lock
        self._mla_sites = {"step": 0, "step_kernel": 0}  # guarded-by: _lock
        self._ssd_sites = {"step": 0, "chunk": 0}  # guarded-by: _lock
        self._listening = False  # guarded-by: _lock

    def listen(self) -> None:
        """Register the one jax.monitoring listener (first use; a process
        that never gets here never imports jax on obs's account)."""
        with self._lock:
            if self._listening:
                return
            self._listening = True
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self.on_event)

    def on_event(self, event: str, duration: float, **kwargs) -> None:
        """Runs on whichever thread made JAX build a program, and only
        then: nothing on a steady call."""
        span = COMPILE_EVENTS.get(event)
        if span is None:
            return
        t_end = time.perf_counter()
        fun = str(kwargs.get("fun_name", ""))
        with self._lock:
            self._dropped += len(self._compiles) == self._compiles.maxlen
            self._compiles.append((event, fun, t_end, float(duration)))
        trace.record_span(span, t_end - duration, t_end, meta={"fun": fun})

    def add_phase(self, name: str, t0: float, t1: float) -> None:
        with self._lock:
            self._dropped += len(self._phases) == self._phases.maxlen
            self._phases.append((name, t0, t1))

    def count_pool_site(self, path: str) -> None:
        with self._lock:
            self._pool_sites[path] += 1

    def count_kda_site(self, form: str) -> None:
        with self._lock:
            self._kda_sites[form] += 1

    def count_moe_site(self, path: str) -> None:
        with self._lock:
            self._moe_sites[path] += 1

    def count_gqa_site(self, form: str) -> None:
        with self._lock:
            self._gqa_sites[form] += 1

    def count_dsa_site(self, form: str) -> None:
        with self._lock:
            self._dsa_sites[form] += 1

    def count_mla_site(self, form: str) -> None:
        with self._lock:
            self._mla_sites[form] += 1

    def count_ssd_site(self, form: str) -> None:
        with self._lock:
            self._ssd_sites[form] += 1

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            return {
                "phases": list(self._phases),
                "compiles": list(self._compiles),
                "dropped": self._dropped,
                "pool_sites": dict(self._pool_sites),
                "kda_sites": dict(self._kda_sites),
                "moe_sites": dict(self._moe_sites),
                "gqa_sites": dict(self._gqa_sites),
                "dsa_sites": dict(self._dsa_sites),
                "mla_sites": dict(self._mla_sites),
                "ssd_sites": dict(self._ssd_sites),
            }


_RECORD = _ProcessRecord()


@contextlib.contextmanager
def phase(name: str):
    """One set-up phase of the process (``spans.SETUP_*``): appended to the
    process record as ``(name, t0, t1)``, and a ``trace.span(name)`` when
    the tracer is armed. For set-up sites only — a dozen calls a process;
    the hot path uses ``trace.span``, which costs nothing disarmed."""
    _RECORD.listen()
    t0 = time.perf_counter()
    try:
        with trace.span(name):
            yield
    finally:
        _RECORD.add_phase(name, t0, time.perf_counter())


def process_record() -> dict[str, Any]:
    """``{"phases": [(name, t0, t1)], "compiles": [(event, fun_name, t_end,
    duration_s)], "dropped": n, "pool_sites": {"kernel": n, "fallback":
    n}, "kda_sites": {"step": n, "step_kernel": n, "chunk": n, "pair": n,
    "pair_kernel": n}, "moe_sites": {"grouped": n, "gathered": n, "dense":
    n, "dense_skip": n}, "gqa_sites": {"step": n, "step_kernel": n},
    "dsa_sites": {"step": n, "step_kernel": n}, "mla_sites": {"step": n,
    "step_kernel": n}, "ssd_sites": {"step": n, "chunk": n}}``: copies,
    oldest first, ``perf_counter`` stamps (a compile event started at ``t_end -
    duration_s``). A compile event belongs to the phases whose ``[t0, t1]``
    hold its ``t_end``."""
    _RECORD.listen()
    return _RECORD.snapshot()


def count_pool_site(path: str) -> None:
    """One differentiated max-pool site of a program being lowered took the
    Pallas kernels (``"kernel"``) or ``nn.max_pool``'s own VJP
    (``"fallback"``): called by ``ops/max_pool.py``, once per site and
    program, nothing on a steady call."""
    _RECORD.count_pool_site(path)


def count_kda_site(form: str) -> None:
    """One KDA site of a program took the one-token recurrence as its Pallas
    kernel (``"step_kernel"``) or in plain ``jax.numpy`` (``"step"``), the
    chunked fragment form (``"chunk"``), or inside that form the pairs of a
    sub-chunk as their Pallas kernels, forward or backward
    (``"pair_kernel"``), or as the plain lines (``"pair"``): called by
    ``ops/kda.py``, once per site and trace (where the platform chose, once
    per site and program lowered), nothing on a steady call."""
    _RECORD.count_kda_site(form)


def count_moe_site(path: str) -> None:
    """One held-expert site of a program being lowered was built with the
    one buffer over all held experts (``"grouped"``: dense routing, a
    fragment's tokens), a buffer an expert (``"gathered"``: sparse routing),
    or every held expert on every token (``"dense"``: a decode step's few
    tokens; the first two keep that side behind a ``lax.cond``, which the
    update's ``moe_dense_blocks`` counts), or a decode step's held experts
    that some token chose and no other (``"dense_skip"``: ``ops/moe.py``'s
    kernel ``moe_chosen``): called by ``ops/moe.py``, once per site and
    program lowered, nothing on a steady call."""
    _RECORD.count_moe_site(path)


def count_gqa_site(form: str) -> None:
    """One one-token grouped-query attention site of a program took the
    Pallas kernel that reads the cache up to ``len`` (``"step_kernel"``) or
    the plain lines over its whole capacity (``"step"``): called by
    ``ops/gqa.py``, once per site and trace (where the platform chose, once
    per site and program lowered), nothing on a steady call."""
    _RECORD.count_gqa_site(form)


def count_dsa_site(form: str) -> None:
    """One one-token sparse-attention site of a program (a cache deeper than
    ``top_k``) attended its chosen rows by ``ops/gqa.py``'s kernel under the
    selection's mask, reading the cache up to ``len`` (``"step_kernel"``),
    or by the plain products over its whole capacity (``"step"``): called by
    ``ops/dsa.py``, once per site and trace (where the platform chose, once
    per site and program lowered), nothing on a steady call."""
    _RECORD.count_dsa_site(form)


def count_mla_site(form: str) -> None:
    """One one-token latent-attention site of a program (``models/mla.py
    step``) attended the latent cache by ``ops/gqa.py``'s kernel under its
    latent option, reading an env's rows up to ``len`` once
    (``"step_kernel"``), or by the plain lines over the cache's whole
    capacity (``"step"``): called by ``ops/gqa.py latent_step``, once per
    site and trace (where the platform chose, once per site and program
    lowered), nothing on a steady call."""
    _RECORD.count_mla_site(form)


def count_ssd_site(form: str) -> None:
    """One state-space site of a program being lowered took the one-token
    recurrence (``"step"``) or the chunked fragment form (``"chunk"``):
    called by ``ops/ssd.py``, once per site and program lowered, nothing on
    a steady call."""
    _RECORD.count_ssd_site(form)


def _sig(obj: Any) -> Any:
    """A hashable (shape, dtype) signature of one argument pytree, without
    importing jax: containers recurse, array-likes reduce to their shape/
    dtype, everything else to its type. Flax ``struct.dataclass`` nodes
    (Rollout, LearnerState) walk their fields."""
    if isinstance(obj, (tuple, list)):
        return tuple(_sig(x) for x in obj)
    if isinstance(obj, dict):
        return tuple(sorted((k, _sig(v)) for k, v in obj.items()))
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return tuple(
            (f.name, _sig(getattr(obj, f.name)))
            for f in dataclasses.fields(obj)
        )
    shape = getattr(obj, "shape", None)
    if shape is not None:
        return ("arr", tuple(shape), str(getattr(obj, "dtype", "?")))
    return ("py", type(obj).__name__)


def _fmt_sig(sig: Any) -> str:
    """Compact human-readable rendering of a :func:`_sig` signature."""
    if isinstance(sig, tuple) and len(sig) == 3 and sig[0] == "arr":
        return f"{sig[2]}{list(sig[1])}"
    if isinstance(sig, tuple) and len(sig) == 2 and sig[0] == "py":
        return sig[1]
    if isinstance(sig, tuple):
        return "(" + ", ".join(_fmt_sig(s) for s in sig) + ")"
    return str(sig)


def _blame(prev: Any, new: Any) -> str:
    """Which argument's shape changed between the previous call and this
    compiling one — the static-shape blame line of a compile event."""
    if prev is None:
        return "first call"
    for (argnum, old), (_, cur) in zip(prev, new):
        if old != cur:
            return (
                f"arg{argnum} shape changed: "
                f"{_fmt_sig(old)} -> {_fmt_sig(cur)}"
            )
    if len(prev) != len(new):
        return f"arity changed: {len(prev)} -> {len(new)} args"
    return "signature changed (non-shape static argument)"


class InstrumentedFn:
    """Compile-counting wrapper for a jitted callable (see module doc).

    Thread-safe: any thread may call (actor threads share the per-thread
    inference fn). The signature check/registration runs under a tiny
    lock; the wrapped call itself never does — a compile must not
    serialize unrelated callers.
    """

    def __init__(
        self,
        fn: Callable,
        site: str,
        counters: Iterable[str] = ("compiles",),
        ignore_argnums: Iterable[int] = (),
    ):
        self._fn = fn
        self.site = site
        self._ignore = frozenset(ignore_argnums)
        # Counter NAMES, resolved at increment time: the wrapper is
        # typically constructed before ``obs.setup`` resets the registry
        # (the trainer builds learner/inference fns first), so holding
        # instrument objects here would strand the increments on orphans
        # the window drain never sees. Compiles are rare — the per-compile
        # registry lookup is free.
        self._counter_names = tuple(counters)
        self._lock = threading.Lock()
        self._seen: set[Any] = set()  # guarded-by: _lock
        self._prev: Any = None  # guarded-by: _lock
        # Written under _lock; GIL-atomic metrics-only reads (tests).
        self.compiles = 0  # guarded-by: _lock

    def _signature(self, args: tuple) -> tuple:
        return tuple(
            (i, _sig(arg))
            for i, arg in enumerate(args)
            if i not in self._ignore
        )

    def __call__(self, *args):
        sig = self._signature(args)
        with self._lock:
            known = sig in self._seen
            prev = self._prev
            self._prev = sig
            if not known:
                self._seen.add(sig)
                self.compiles += 1
                seq = self.compiles
        if known:
            return self._fn(*args)
        # New signature: count it, blame the changed shape, and time the
        # call — on a new shape the jit trace+compile happens inside this
        # dispatch, so its wall time IS (approximately) the compile cost.
        for name in self._counter_names:
            registry.counter(name).inc()
        t0 = time.perf_counter()
        with trace.span(f"{self.site}.compile"):
            out = self._fn(*args)
        dt = time.perf_counter() - t0
        registry.histogram("compile_ms").observe(1e3 * dt)
        _LOG.push({
            "type": "compile",
            "site": self.site,
            "seq": seq,
            "t": time.time(),
            "compile_s": round(dt, 6),
            "blame": _blame(prev, sig),
            "signature": _fmt_sig(sig),
        })
        return out


def instrument(
    fn: Callable,
    site: str,
    counters: Iterable[str] = ("compiles",),
    ignore_argnums: Iterable[int] = (),
) -> InstrumentedFn:
    """Wrap ``fn`` (typically a ``jax.jit`` product) in compile counting.

    ``site`` names the entry point in events/spans (``"infer"``,
    ``"learner.update"``); ``counters`` are the registry counters each
    detected compile increments (always include the shared ``"compiles"``
    total so the recompile-storm detector sees every site); and
    ``ignore_argnums`` skips arguments whose pytrees are large and whose
    shapes cannot change (the params/state argument) — keeping the
    per-call signature walk to the small, shape-varying arguments.
    """
    return InstrumentedFn(
        fn, site, counters=counters, ignore_argnums=ignore_argnums
    )


# ------------------------------------------------------ memory watermarks

# Monotone host-RSS high-water mark across the run. Window-close-thread
# only (sample_memory's single caller is PipelineObs.observe_window).
_RSS_PEAK = 0.0


def _host_rss_bytes() -> float | None:
    """Current resident set size. /proc/self/statm (Linux); falls back to
    ru_maxrss (which is a PEAK — still a usable watermark) elsewhere."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return float(pages * os.sysconf("SC_PAGE_SIZE"))
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource
        import sys

        # This fallback only runs where /proc is absent — i.e. almost
        # always macOS, where ru_maxrss is BYTES; Linux reports KiB.
        # ru_maxrss is a peak, not current RSS — still a usable watermark.
        raw = float(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
        return raw if sys.platform == "darwin" else raw * 1024.0
    except (ImportError, OSError, ValueError):
        return None


def device_memory_stats() -> dict[str, float]:
    """Backend device-memory stats, when the platform exposes them (TPU/GPU
    runtimes do; CPU returns nothing). Lazy + failure-tolerant like
    ``obs._platform``: introspection must never break on a backend that
    can't answer."""
    out: dict[str, float] = {}
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats()
    # lint: broad-except-ok(metadata enrichment only; a backend without memory_stats or a broken jax install must not break the window close)
    except Exception:
        return out
    if not stats:
        return out
    for src, dst in (
        ("bytes_in_use", "mem_device_bytes_in_use"),
        ("peak_bytes_in_use", "mem_device_peak_bytes"),
        ("bytes_limit", "mem_device_bytes_limit"),
    ):
        value = stats.get(src)
        if isinstance(value, (int, float)):
            out[dst] = float(value)
    return out


def sample_memory() -> dict[str, float]:
    """Sample the memory watermarks into registry gauges (and return them).

    Called once per metrics window from ``PipelineObs.observe_window``
    (the window-close thread) when introspection is on — the gauges then
    ride the shared registry drain into every sink, ``/metrics``, and
    ``timeseries.jsonl``.
    """
    global _RSS_PEAK
    out = device_memory_stats()
    rss = _host_rss_bytes()
    if rss is not None:
        out["mem_host_rss_bytes"] = rss
        if rss > _RSS_PEAK:
            _RSS_PEAK = rss
        out["mem_host_rss_peak_bytes"] = _RSS_PEAK
    for key, value in out.items():
        registry.gauge(key).set(value)
    return out
