"""SebulbaTrainer: host actor threads + device learner, pipelined.

``backend="sebulba"`` is the framework's answer to the reference's default
architecture — per-thread actors feeding a learner through a queue
(BASELINE.json:5; SURVEY.md §3.1) — for envs that cannot live in HBM (C++
engines, gymnasium suites). Actors produce ``Rollout`` fragments on the host;
the learner thread transfers them batch-sharded to the mesh and steps the
``RolloutLearner``; weights publish back through a ``ParamStore`` every
``actor_staleness`` updates. The bounded queue is the pipelining element:
actors run ahead of the learner by up to ``queue_capacity`` fragments, and
V-trace (algo="impala") corrects the resulting off-policyness exactly as in
the reference (SURVEY.md §7.3).

With ``config.overlap_h2d`` (default on) the fragment data itself moves
zero-copy: actors write into leased staging-slab rows (rollout/staging.py),
the drain transfers whole slabs double-buffered against the learner's
compute, and per-window pipeline metrics (h2d_wait_s, h2d_bytes,
learner_stall_frac, slab_reuse_waits) make the overlap measurable — see
docs/ARCHITECTURE.md "Data path & transfer overlap".
"""

from __future__ import annotations

import os
import queue
import sys
import threading
import time
from collections import deque
from typing import Any, Callable

import jax
import numpy as np

from asyncrl_tpu import obs
from asyncrl_tpu.obs import flightrec, introspect
from asyncrl_tpu.obs import registry as obs_registry
from asyncrl_tpu.obs import spans as span_names
from asyncrl_tpu.obs import trace
from asyncrl_tpu.learn.learner import (
    validate_ppo_geometry,
    validate_train_target,
)
from asyncrl_tpu.learn import replay as replay_lib
from asyncrl_tpu.learn.rollout_learner import (
    LearnerState,
    RolloutLearner,
    rollout_sharding,
)
from asyncrl_tpu.models.networks import build_model, is_recurrent, reset_core
from asyncrl_tpu.ops import distributions
from asyncrl_tpu.ops.normalize import normalizing_apply
from asyncrl_tpu.parallel.mesh import TIME_AXIS, dp_size, make_mesh
from asyncrl_tpu.rollout.sebulba import (
    ActorThread,
    Fragment,
    FragmentSequenceChecker,
    ParamStore,
    make_host_pool,
    make_inference_fn,
)
from asyncrl_tpu.runtime import durability
from asyncrl_tpu.utils import faults
from asyncrl_tpu.utils.config import Config, default_eval_max_steps


def _stack_fragments(rollouts):
    """K host fragments -> one [K, T, B, ...] stack for the fused-dispatch
    learner (updates_per_call > 1). K=1 fast path: the single fragment
    passes through AS-IS — no stack, no copy (the K=1 learner expects the
    plain [T, B, ...] layout anyway, and a redundant ``np.stack`` here
    would tax every update of the default configuration). Legacy path
    only; the staging ring (config.overlap_h2d) never stacks at all."""
    if len(rollouts) == 1:
        return rollouts[0]
    return jax.tree.map(lambda *xs: np.stack(xs), *rollouts)


class SebulbaTrainer:
    """Owns host actor threads, the param store, and the device learner."""

    def __init__(
        self, config: Config, spec=None, model=None, mesh=None, restore=None
    ):
        self.config = config
        # Chaos layer (utils/faults.py): config-armed unless the operator's
        # ASYNCRL_FAULTS is set (env wins — it is the no-code-change knob).
        # An empty fault_spec DISARMS, so constructing a fresh agent never
        # inherits a previous agent's armed sites in the same process.
        # Armed BEFORE the body so the constructor's own checkpoint restore
        # and probe pool run under the spec'd sites; disarmed again if
        # construction fails — a half-built trainer must not leave its
        # faults armed for whatever runs next in the process.
        armed = not os.environ.get(faults.ENV_VAR)
        if armed:
            faults.arm(config.fault_spec)
        try:
            self._init(config, spec, model, mesh, restore)
        except BaseException:
            if armed:
                faults.disarm()
            raise

    def _init(self, config, spec, model, mesh, restore):
        # Resolve the ASYNCRL_INTROSPECT override ONCE (env wins over
        # config.introspect, the ASYNCRL_TRACE precedence) so every
        # downstream consumer — the jitted loss aux, the learner's compile
        # instrumentation, the staleness tracker — reads the same resolved
        # flag instead of re-consulting the environment.
        if introspect.enabled(config) != config.introspect:
            config = config.replace(introspect=introspect.enabled(config))
            self.config = config
        # Device replay ring (learn/replay.py): ASYNCRL_REPLAY wins over
        # config.replay_slabs when set — resolved ONCE here, like
        # ASYNCRL_INTROSPECT, so the jitted IMPACT update and the ring
        # construction below read the same resolved depth and never
        # re-consult the environment.
        env_replay = os.environ.get("ASYNCRL_REPLAY", "")
        if env_replay and int(env_replay) != config.replay_slabs:
            config = config.replace(replay_slabs=int(env_replay))
            self.config = config
        if config.num_envs % config.actor_threads:
            raise ValueError(
                f"num_envs={config.num_envs} not divisible by "
                f"actor_threads={config.actor_threads}"
            )
        self._envs_per_actor = config.num_envs // config.actor_threads

        # Spec comes from a probe pool (host envs are authoritative here).
        with introspect.phase(span_names.SETUP_ENV):
            probe = make_host_pool(config, 1, seed=config.seed)
            self.spec = (
                spec if spec is not None else _pool_spec(probe, config)
            )
            _close(probe)

        with introspect.phase(span_names.SETUP_MODEL):
            self.model = (
                model if model is not None
                else build_model(config, self.spec)
            )
        with introspect.phase(span_names.SETUP_MESH):
            self.mesh = (
                mesh
                if mesh is not None
                else make_mesh(config.mesh_shape, config.mesh_axes)
            )

        # Eager geometry validation, mirroring the Anakin Learner: fail at
        # construction, not with a cryptic sharding error mid-train after
        # actor threads have already started.
        dp = dp_size(self.mesh)
        if self._envs_per_actor % dp:
            raise ValueError(
                f"num_envs/actor_threads={self._envs_per_actor} not "
                f"divisible by dp={dp}"
            )
        # On a time-sharded mesh each (dp, sp) shard shuffles its
        # (unroll/sp)-step slice of the per-actor fragment, so the
        # divisibility check runs on that local geometry.
        sp = (
            self.mesh.shape[TIME_AXIS]
            if TIME_AXIS in self.mesh.axis_names
            else 1
        )
        if config.unroll_len % sp:
            # RolloutLearner re-raises this, but it must come BEFORE the
            # minibatch check: a floored unroll_len//sp there would report
            # a wrong-geometry error for what is really sp-indivisibility.
            raise ValueError(
                f"unroll_len={config.unroll_len} not divisible by the "
                f"time-shard axis sp={sp}"
            )
        validate_ppo_geometry(
            config, self._envs_per_actor // dp, "per-device",
            unroll=config.unroll_len // sp,
            recurrent=is_recurrent(self.model),
        )
        with introspect.phase(span_names.SETUP_LEARNER):
            self.learner = RolloutLearner(
                config, self.spec, self.model, self.mesh
            )
        with introspect.phase(span_names.SETUP_INIT_STATE):
            self.state: LearnerState = self.learner.init_state(config.seed)
        self.env_steps = 0

        # Checkpoint/resume (SURVEY.md §5.4): learner-side state only — host
        # env states are transient by design (actors restart from fresh envs
        # on resume, exactly as after a §5.3 actor restart).
        with introspect.phase(span_names.SETUP_CHECKPOINT):
            # orbax is imported here, and is most of this phase in a
            # process that neither restores nor saves.
            from asyncrl_tpu.utils import checkpoint

            self._ckpt, self.state, self.env_steps = checkpoint.setup(
                config, restore, self.state
            )
        self.checkpointer = self._ckpt.checkpointer

        self._inference_fn = make_inference_fn(self.model, self.spec, config)
        if config.introspect:
            # Compile accounting on the inference entry point
            # (obs/introspect.py): wrapped ONCE here — not per server —
            # because the jit cache lives in this function object and
            # survives supervised server rebuilds; the counter must match
            # its lifetime. ``infer_recompile`` makes the shared server's
            # partial-batch recompiles (deadline flushes change the batch
            # shape) measurable next to ``infer_coalesce_batch``. The
            # params argument's shapes never change and is skipped.
            self._inference_fn = introspect.instrument(
                self._inference_fn, "infer",
                counters=("compiles", "infer_recompile"),
                ignore_argnums=(0,),
            )
        # Per-window off-policy staleness aggregation (obs/introspect.py):
        # fed one lag per consumed fragment, drained at window close.
        self._staleness = (
            introspect.StalenessWindow() if config.introspect else None
        )
        self._initial_core = (
            self.model.initial_core if is_recurrent(self.model) else None
        )
        self._store = ParamStore(self._published(self.state), self.env_steps)
        cap = config.queue_capacity or 2 * config.actor_threads
        self._queue: "queue.Queue[Fragment]" = queue.Queue(maxsize=cap)
        # Elastic runtime (asyncrl_tpu/runtime/elastic.py): resolved ONCE
        # (ASYNCRL_ELASTIC wins over config.elastic, the ASYNCRL_SERVE
        # precedence) and validated eagerly — the in-flight ring swap does
        # not compose with fused multi-fragment slabs, and the legacy
        # InferenceServer's client set is fixed-shape.
        self._elastic_on = self._use_elastic()
        if self._elastic_on:
            if config.updates_per_call > 1:
                raise ValueError(
                    "elastic=True requires updates_per_call=1: a fused "
                    "[K>1] slab interrupted by a ring swap would strand "
                    "its partial batch"
                )
            if config.inference_server and not self._use_serve_core():
                raise ValueError(
                    "elastic=True requires the serve core for the shared "
                    "server (serve=True / ASYNCRL_SERVE=1): the legacy "
                    "InferenceServer's client set is fixed-shape"
                )
            emin, emax = self._elastic_bounds()
            if not emin <= config.actor_threads <= emax:
                raise ValueError(
                    f"actor_threads={config.actor_threads} outside the "
                    f"elastic bounds [{emin}, {emax}]"
                )
        else:
            registry = faults.active()
            if registry is not None and registry.has_kind("scale"):
                raise ValueError(
                    "fault spec arms a 'scale' site but the elastic "
                    "runtime is off (elastic=True / ASYNCRL_ELASTIC=1): "
                    "scripted scale requests would accumulate with no "
                    "controller to drain them"
                )
        # Zero-copy staging ring (rollout/staging.py): actors write
        # fragments straight into preallocated [K, T, B, ...] slabs and
        # the drain transfers whole slabs, double-buffered against the
        # learner's compute. config.overlap_h2d=False keeps the legacy
        # copy-and-stack path (A/B-compared by tests/test_perf_smoke.py).
        # Under elasticity the ring sits behind a RingSwapHolder so a
        # fleet-scale event can install a right-sized ring while in-flight
        # leases finish on the old one.
        self._staging = None
        self._staging_template = None
        self._staging_rows = max(config.updates_per_call, 1)
        if config.overlap_h2d:
            from asyncrl_tpu.rollout import staging

            template = staging.fragment_template(
                config, self.spec, self.model, self._envs_per_actor
            )
            self._staging_template = template
            K = self._staging_rows
            ring = staging.StagingRing(
                template,
                rows_per_slab=K,
                num_slabs=(
                    config.staging_slabs
                    or staging.auto_num_slabs(cap, config.actor_threads, K)
                ),
            )
            self._staging = (
                staging.RingSwapHolder(ring) if self._elastic_on else ring
            )
        # IMPACT-style device replay (learn/replay.py; ROADMAP item 3):
        # the last replay_slabs consumed fragments stay resident in
        # device memory, re-fed to the learner between fresh slabs so
        # the duty cycle stops tracking actor throughput. replay off
        # constructs NOTHING (the elastic/introspect off-is-bit-identical
        # discipline). Fragment geometry is invariant under elastic
        # scaling (fleet size changes, per-actor env count does not), so
        # the ring composes with the elastic runtime as-is.
        self._replay = None
        self._reuse_window = None
        self._replay_rng = None
        self._stall_history = None
        if config.replay_slabs > 0:
            from asyncrl_tpu.rollout import staging

            # ONE source of slab geometry: reuse the staging ring's
            # template when the overlap path already derived it.
            replay_template = (
                self._staging_template
                if self._staging_template is not None
                else staging.fragment_template(
                    config, self.spec, self.model, self._envs_per_actor
                )
            )
            self._replay = replay_lib.DeviceReplayRing(
                replay_template,
                rollout_sharding(self.mesh, replay_template, stacked=True),
                rows=config.replay_slabs,
            )
            self._reuse_window = replay_lib.ReuseWindow()
            # Replay-row selection is seed-deterministic (ties among
            # equally-reused rows break by this stream), decorrelated
            # from the actor seed ladder.
            self._replay_rng = np.random.default_rng(config.seed * 9973 + 13)
            # Trailing stall fractions for the learner_stall_trend key
            # (this window minus the trailing mean: the operator-facing
            # "is replay actually closing the duty-cycle gap" signal).
            self._stall_history = deque(maxlen=8)
        # HBM rollout hand-off (rollout/device_queue.py): the staging
        # ledger one tier down — bounds device-resident fragments
        # between H2D and the consuming update, and (with the replay
        # ring) enables the zero-copy ref publish. "auto" resolves on
        # the backend: fragments live in HBM only on a real accelerator;
        # on CPU the device array aliases host memory and host staging
        # already owns the hand-off, so the off path constructs NOTHING.
        dq = config.device_queue
        if dq == "auto":
            dq = "on" if jax.default_backend() == "tpu" else "off"
            config = config.replace(device_queue=dq)
            self.config = config
        if dq not in ("on", "off"):
            raise ValueError(
                f"unknown device_queue {config.device_queue!r}; "
                "expected auto|on|off"
            )
        self._device_queue = None
        if dq == "on":
            from asyncrl_tpu.rollout import device_queue as devq_lib

            self._device_queue = devq_lib.DeviceRolloutQueue(
                self.learner.put_rollout,
                slots=config.device_queue_slots,
            )
        # Replay adoption (publish ref=True) hands the learner the SAME
        # device pytree on replayed passes, so it is only sound when the
        # update does not donate its fragment argument.
        self._replay_ref = (
            self._device_queue is not None and not config.donate_buffers
        )
        # Observability (asyncrl_tpu/obs/): arms span tracing + the
        # flight recorder per config.trace (ASYNCRL_TRACE wins), resets
        # the counters/histograms registry, and mounts the run-health
        # layer (time-series store + detectors + optional /metrics
        # endpoint per config.obs_http_port); the window aggregation
        # (observe_window) and close()/shutdown() drive the handle.
        self._obs = obs.setup(config)
        # The elastic controller itself (policy) + the save → reconfigure
        # → restore barrier (safety). Both None when elasticity is off —
        # the off path constructs NOTHING elastic, the bit-identity
        # contract of scripts/elastic_smoke.sh.
        self._elastic = None
        self._elastic_barrier = None
        if self._elastic_on:
            from asyncrl_tpu.obs import health as health_mod
            from asyncrl_tpu.runtime import elastic as elastic_mod

            monitor = self._obs.monitor
            blame_fn = None
            if monitor is not None:

                def blame_fn():
                    # Runs AFTER observe_window advanced the monitor's
                    # close timestamp — pass the closed window's duration
                    # or the span horizon collapses to the 1s clamp.
                    stage, _ = monitor.bottleneck(
                        elapsed=monitor.last_window_s
                    )
                    return health_mod.blame_component(stage)

            self._elastic = elastic_mod.ElasticController(
                min_actors=self._elastic_bounds()[0],
                max_actors=self._elastic_bounds()[1],
                cooldown_windows=config.elastic_cooldown_windows,
                up_stall_frac=config.elastic_up_stall_frac,
                up_shed_rate=config.elastic_up_shed_rate,
                down_backpressure=config.elastic_down_backpressure,
                down_admission=config.elastic_down_admission,
                # The replay inversion: high ring fill + low stall means
                # sample reuse is covering the learner's duty cycle, so
                # the fleet is oversized — armed only when the ring
                # exists (0 keeps the signal out of every replay-off
                # identity A/B, the elastic_smoke discipline).
                down_replay_fill=(
                    elastic_mod.DOWN_REPLAY_FILL
                    if config.replay_slabs > 0
                    else 0.0
                ),
                blame_fn=blame_fn,
            )
            self._elastic_barrier = elastic_mod.ReconfigureBarrier(self._ckpt)
        # Durable runs (asyncrl_tpu/runtime/durability.py): the drain
        # grace and resume flag resolve ONCE (env wins — the ASYNCRL_SERVE
        # precedence), and a preempt-kind fault spec is refused when the
        # drain is disabled: its scripted SIGTERM would hit a process with
        # no handler and kill it undrained — the one outcome the spec
        # exists to test against.
        self._drain_grace = durability.drain_grace(config)
        self._resume_on = durability.resume_enabled(config)
        registry = faults.active()
        if (
            registry is not None
            and registry.has_kind("preempt")
            and self._drain_grace <= 0
        ):
            raise ValueError(
                "fault spec arms a 'preempt' site but the preemption "
                "drain is disabled (drain_grace_s=0 / "
                "ASYNCRL_DRAIN_GRACE_S=0): the scripted SIGTERM would "
                "kill the run undrained instead of testing the drain"
            )
        # External gateway (asyncrl_tpu/serve/gateway.py): the wire
        # frontier over the serve core. gateway_port=0 constructs NOTHING
        # (zero threads, zero registry keys — the introspect=False
        # bit-identity discipline); when on, the gateway requires the
        # serve core (it routes through ServeCore.submit_external) and a
        # feed-forward inference signature (recurrent/eps serving over
        # the wire is a follow-up: core state has no wire story yet).
        # A netfault-kind fault site is refused when the gateway is off —
        # the preempt/scale precedent: a chaos script that can never fire
        # is a chaos script that silently tests nothing.
        self._gateway = None
        self._gateway_backend = None
        self._gateway_tenants = None
        self._gateway_port: int | None = None
        self._gateway_restarts = 0
        self._recent_gateway_restarts: list[float] = []
        # Supervisor re-bind backoff (a failed rebuild retries, it never
        # kills training — see _supervise_gateway).
        self._gateway_retry_at = 0.0
        if config.gateway_port != 0:
            if not config.inference_server or not self._use_serve_core():
                raise ValueError(
                    "gateway_port != 0 requires inference_server=True and "
                    "the serve core (serve=True / ASYNCRL_SERVE=1): the "
                    "gateway serves through ServeCore's continuous batch"
                )
            from asyncrl_tpu.rollout.sebulba import inference_mode

            if inference_mode(config, self.model) != "ff":
                raise ValueError(
                    "gateway_port != 0 requires a feed-forward policy "
                    "(core='ff', algo != 'qlearn'): recurrent/epsilon "
                    "inference has no wire protocol yet"
                )
            if config.gateway_deadline_ms <= 0:
                raise ValueError(
                    "gateway_deadline_ms must be > 0: it is the default "
                    "end-to-end budget for requests without an "
                    f"X-Deadline-Ms header (got {config.gateway_deadline_ms})"
                )
            from asyncrl_tpu.serve import gateway as gateway_mod

            # Eager spec validation: a malformed SLO matrix (or deadline,
            # above) fails at construction, where the operator reads it —
            # not mid-train when the gateway first spawns.
            self._gateway_tenants = gateway_mod.parse_tenant_spec(
                config.gateway_tenant_spec
            )
        else:
            registry = faults.active()
            if registry is not None and registry.has_kind("netfault"):
                raise ValueError(
                    "fault spec arms a 'netfault' site but the gateway is "
                    "off (gateway_port=0): the scripted wire failure "
                    "could never fire and would silently test nothing"
                )
        # Automatic divergence rollback (RollbackPolicy): armed by
        # rollback_bad_windows > 0, which also arms the learner's
        # device-side NaN-guard. Needs a checkpoint_dir — without retained
        # steps there is nothing to roll back to.
        self._rollback = None
        if config.rollback_bad_windows > 0:
            if not config.checkpoint_dir:
                raise ValueError(
                    "rollback_bad_windows > 0 requires checkpoint_dir: "
                    "divergence rollback restores the last-good retained "
                    "checkpoint"
                )
            self._rollback = durability.RollbackPolicy(
                config.rollback_bad_windows, config.rollback_max_attempts
            )
        # Cumulative NaN-guard skip count (window key nonfinite_skips).
        self._nonfinite_skips = 0.0
        # §5.2b debug mode: transport invariants on drained fragments.
        from asyncrl_tpu.utils.debug import sync_debug_enabled

        self._seq_checker = (
            FragmentSequenceChecker() if sync_debug_enabled() else None
        )
        self._errors: "queue.Queue[tuple[int, int, BaseException]]" = (
            queue.Queue()
        )
        self._stop = threading.Event()
        self._actors: list[ActorThread] = []
        # Per-slot restart counters (monotone across stop/start cycles;
        # stamped into fragments for the §5.2b transport checker).
        self._actor_gens = [0] * config.actor_threads
        self._updates = 0
        # version -> update count at publish, for the param_lag metric
        # (with fused dispatch, publishes are no longer every
        # actor_staleness updates, so the mapping must be recorded, not
        # derived). Version 0 is the constructor-published initial params.
        self._published_updates: dict[int, int] = {0: 0}
        self._actor_restarts = 0
        # Crash-storm window: CRASH-caused restarts only. Watchdog
        # retirements keep their own window below, and deliberate elastic
        # scale-downs enter NEITHER — a run must never abort for being
        # scaled (or stall-churned) the way it aborts for crash-looping.
        self._recent_restarts: list[float] = []
        self._recent_watchdog: list[float] = []
        self._RESTART_WINDOW_S = 300.0
        # Supervised inference-server restarts (same storm window; the
        # threshold is the actor rule at one instance: > 3 in the window).
        self._server_restarts = 0
        self._recent_server_restarts: list[float] = []
        # Cumulative queue.Full retries of RETIRED actors; the live window
        # metric adds the running actors' own counters on top.
        self._backpressure_base = 0
        self._next_actor_seed = config.seed * 7919 + 1
        self._actor_device = None  # CpuAsyncTrainer pins actors to host CPU
        self._server = None  # shared inference server (config.inference_server)
        # The server's OWN stop event (never the cohort's): a supervised
        # server restart must be able to retire one server without taking
        # every healthy actor down with it.
        self._server_stop = threading.Event()
        # Inference-server coalescing snapshot for the per-window
        # infer_coalesce_batch metric: (server incarnation, rounds, rows)
        # at the last window close. Keyed on the monotonic restart counter
        # — not id(server), whose freed address can be reused — so a
        # rebuilt server's fresh counters never read as a negative delta.
        self._infer_snap: tuple[int | None, int, int] = (None, 0, 0)
        # Caches built on first use but DECLARED here (no hasattr dances):
        # evaluation host pools per (num_episodes, seed), and the jitted
        # greedy fn (set lazily in evaluate — model apply shape is known
        # only there for recurrent cores).
        self._eval_pools = {}
        self._greedy_fn = None
        # Crash-consistent resume (runtime/durability.py): checkpoint.setup
        # above already restored the LEARNER state (the pre-existing
        # auto-resume); with resume armed the checkpoint's run_state
        # metadata restores the rest of the run — host counters, the
        # actor-PRNG cursor, the health monitor's window cursor, the
        # elastic fleet size (applied when the fleet starts), and the
        # rollback attempt budget — so every counter is monotone across
        # the process boundary and timeseries.jsonl continues as a new
        # segment marked with a resume event.
        self._resume_fleet: int | None = None
        run_state = (self._ckpt.restore_meta or {}).get("run_state")
        if self._resume_on and run_state:
            self._updates = int(run_state.get("updates", 0))
            # Staleness-ledger rebase: the restored params ARE version 0
            # of this process, published at the restored update count —
            # without this, every resumed fragment would report a lag of
            # the full pre-preemption update count.
            self._published_updates = {0: self._updates}
            self._next_actor_seed = int(
                run_state.get("next_actor_seed", self._next_actor_seed)
            )
            self._actor_restarts = int(run_state.get("actor_restarts", 0))
            self._server_restarts = int(run_state.get("server_restarts", 0))
            self._gateway_restarts = int(
                run_state.get("gateway_restarts", 0)
            )
            if self._rollback is not None:
                self._rollback.attempts = int(
                    run_state.get("rollback_attempts", 0)
                )
            fleet = int(run_state.get("actors_live", config.actor_threads))
            if self._elastic_on and fleet != config.actor_threads:
                emin, emax = self._elastic_bounds()
                self._resume_fleet = max(emin, min(emax, fleet))
            monitor = self._obs.monitor
            if monitor is not None:
                monitor.window_idx = int(run_state.get("window_idx", 0))
            if self._obs.store is not None:
                self._obs.store.annotate({
                    "event_type": "resume",
                    "restored_update": self._updates,
                    "env_steps": float(self.env_steps),
                    "actors": fleet,
                })
        # The fleet size the last STOPPED fleet ran at: stop() clears
        # self._actors before the drain's final save_now (and before the
        # crash-path finalize), so without this snapshot an elastically
        # scaled fleet would checkpoint as the CONFIGURED size and resume
        # at the wrong shape.
        self._last_live_fleet = self._resume_fleet or config.actor_threads
        # Every save from here on carries the full run state in its
        # metadata (TrainerCheckpointing.meta_fn), so ANY retained step —
        # periodic, elastic-barrier, or the drain's final save — can
        # resume the whole run.
        self._ckpt.meta_fn = self._run_state

    def _elastic_bounds(self) -> tuple[int, int]:
        """The elastic fleet bounds ``[min_actors, max_actors]``
        (``elastic_max_actors=0`` defaults the max to 2x the configured
        fleet) — ONE definition shared by the construct-time validation,
        the live controller, and the resume clamp."""
        cfg = self.config
        return (
            cfg.elastic_min_actors,
            cfg.elastic_max_actors or 2 * cfg.actor_threads,
        )

    def _run_state(self) -> dict[str, Any]:
        """The resume inventory carried by every checkpoint's metadata
        (see docs/ARCHITECTURE.md "Durable runs & divergence rollback")."""
        monitor = self._obs.monitor
        return {
            "actors_live": len(self._actors) or self._last_live_fleet,
            "next_actor_seed": self._next_actor_seed,
            "updates": self._updates,
            "window_idx": monitor.window_idx if monitor is not None else 0,
            "rollback_attempts": (
                self._rollback.attempts if self._rollback is not None else 0
            ),
            "actor_restarts": self._actor_restarts,
            "server_restarts": self._server_restarts,
            "gateway_restarts": self._gateway_restarts,
        }

    def _published(self, state):
        """What actors act under: the params, bundled with the obs-
        normalization stats when enabled (make_inference_fn unpacks)."""
        if self.config.normalize_obs:
            return (state.params, state.obs_stats)
        return state.params

    # --------------------------------------------------------------- actors

    def _epsilon_fn(self, index: int):
        """Per-thread behaviour-ε schedule for the Q-learning family: thread
        ``index``'s env slots take their rungs of the shared schedule
        (``learn.learner.qlearn_epsilon_schedule`` — one formula for every
        backend), annealed by the trainer's AUTHORITATIVE global frame
        counter, published to the ParamStore alongside params. (An earlier
        design extrapolated global frames from the thread's own count
        times actor_threads, which drifted under uneven thread progress
        and after actor restarts — ADVICE.md round 1.) The thread's own
        frames since its last store read are added so the anneal still
        advances between publishes (cadence: actor_staleness updates)."""
        cfg = self.config
        if cfg.algo != "qlearn":
            return None
        from asyncrl_tpu.learn.learner import qlearn_epsilon_schedule

        B = self._envs_per_actor
        gidx = index * B + np.arange(B, dtype=np.float32)
        store = self._store
        last = {"steps": store.env_steps(), "frames": 0, "anneal": 0.0}

        def epsilon_fn(thread_frames: int) -> np.ndarray:
            published = store.env_steps()
            if published != last["steps"]:
                last["steps"] = published
                last["frames"] = thread_frames
            frames = published + (thread_frames - last["frames"]) * max(
                cfg.actor_threads, 1
            )
            # Monotone anneal: the between-publish extrapolation can
            # OVERshoot true global progress (this thread faster than the
            # others), and the next publish would snap frames back down —
            # epsilon must never rise again once lowered.
            frames = max(float(frames), last["anneal"])
            last["anneal"] = frames
            return np.asarray(qlearn_epsilon_schedule(cfg, gidx, frames))

        return epsilon_fn

    def _spawn_actor(self, index: int) -> ActorThread:
        seed = self._next_actor_seed
        self._next_actor_seed += 104729
        pool = make_host_pool(self.config, self._envs_per_actor, seed=seed)
        inference_fn = (
            self._server.client(index)
            if self._server is not None
            else self._inference_fn
        )
        actor = ActorThread(
            index=index,
            pool=pool,
            inference_fn=inference_fn,
            store=self._store,
            out_queue=self._queue,
            unroll_len=self.config.unroll_len,
            seed=seed,
            stop_event=self._stop,
            errors=self._errors,
            device=self._actor_device,
            initial_core=self._initial_core,
            epsilon_fn=self._epsilon_fn(index),
            track_returns=self.config.normalize_returns,
            return_discount=self.config.gamma,
            generation=self._actor_gens[index],
            staging=self._staging,
        )
        actor.start()
        return actor

    def _start_actors(self) -> None:
        if self._actors:
            return
        # A FRESH stop event per cohort (never .clear() the old one): if a
        # previous stop()'s join timed out, the zombie thread still holds
        # the old event — which stays set, so the zombie exits at its next
        # check instead of being revived alongside its replacement. Every
        # new cohort also bumps all generation stamps, so a zombie's late
        # fragments can never collide with the new cohort's seq streams.
        self._stop = threading.Event()
        self._actor_gens = [g + 1 for g in self._actor_gens]
        if self.config.inference_server:
            self._spawn_server()
        if self.config.gateway_port != 0:
            self._spawn_gateway()
        self._actors = [
            self._spawn_actor(i) for i in range(self.config.actor_threads)
        ]

    def _use_serve_core(self) -> bool:
        """Serve core (asyncrl_tpu/serve/) vs legacy InferenceServer for
        the shared server. ``ASYNCRL_SERVE`` wins over ``config.serve``
        when set — the no-code-change A/B knob, like ASYNCRL_FAULTS."""
        env = os.environ.get("ASYNCRL_SERVE", "")
        if env:
            return env.lower() not in ("0", "false", "no")
        return self.config.serve

    def _use_elastic(self) -> bool:
        """Elastic runtime on? ``ASYNCRL_ELASTIC`` wins over
        ``config.elastic`` when set — same precedence as ASYNCRL_SERVE."""
        env = os.environ.get("ASYNCRL_ELASTIC", "")
        if env:
            return env.lower() not in ("0", "false", "no")
        return self.config.elastic

    def _spawn_server(self) -> None:
        """(Re)build the shared inference server on a fresh personal stop
        event. Callers re-wire actors separately: existing clients of a
        dead/retired server raise into their actor threads, whose restarts
        pick up ``self._server``'s new clients. Both cores expose the same
        supervisor surface (heartbeat, _fatal, client(i), coalesce
        counters), so everything downstream is core-agnostic."""
        from asyncrl_tpu.rollout.sebulba import inference_mode

        cfg = self.config
        self._server_stop = threading.Event()
        # Decorrelate the restarted server's action-sampling key stream
        # from its predecessor's.
        seed = cfg.seed + 1_000_003 * self._server_restarts
        mode = inference_mode(cfg, self.model)
        if self._use_serve_core():
            from asyncrl_tpu.serve.scheduler import ServeCore
            from asyncrl_tpu.serve.slo import SLOGate

            self._server = ServeCore(
                self._inference_fn,
                store=self._store,
                # The LIVE fleet size, not the configured one: a
                # supervised rebuild after an elastic scale-up must cover
                # every live client slot (fresh construction sees an
                # empty fleet and falls back to the config).
                num_clients=max(cfg.actor_threads, len(self._actors)),
                stop_event=self._server_stop,
                mode=mode,
                seed=seed,
                device=self._actor_device,
                deadline_ms=cfg.serve_deadline_ms,
                slo=SLOGate(
                    p95_target_ms=cfg.serve_slo_p95_ms,
                    max_inflight=cfg.serve_max_inflight,
                    shed=cfg.serve_shed,
                ),
            )
        else:
            from asyncrl_tpu.rollout.inference_server import InferenceServer

            self._server = InferenceServer(
                self._inference_fn,
                self._store,
                num_clients=cfg.actor_threads,
                stop_event=self._server_stop,
                seed=seed,
                mode=mode,
                device=self._actor_device,
            )
        self._server.start()

    def _spawn_gateway(self) -> None:
        """(Re)build the external gateway (serve/gateway.py). The BACKEND
        persists across rebuilds — its serve-stale anchor (a held
        ParamSlots lease on the last-good generation) must survive a
        gateway crash, that being exactly the outage stale mode exists
        for. A rebuild after a crash re-binds the SAME port the first
        spawn resolved (ephemeral -1 included), so external clients'
        retry layers reconnect without re-discovery."""
        from asyncrl_tpu.serve import gateway as gateway_mod

        cfg = self.config
        if self._gateway_backend is None:
            self._gateway_backend = gateway_mod.CoreBackend(
                core_fn=lambda: self._server,
                inference_fn=self._inference_fn,
                obs_shape=self.spec.obs_shape,
                seed=cfg.seed,
            )
        port = (
            self._gateway_port
            if self._gateway_port is not None
            else cfg.gateway_port
        )
        self._gateway = gateway_mod.ServeGateway(
            self._gateway_backend,
            port=port,
            bind_host=gateway_mod.env_host(cfg.gateway_host),
            tenants=self._gateway_tenants,
            default_deadline_ms=cfg.gateway_deadline_ms,
        ).start()
        self._gateway_port = self._gateway.port

    def _supervise_gateway(self) -> None:
        """Supervised gateway rebuild: a gateway whose serving thread died
        (netfault crash, serving-loop failure) is retired and rebuilt on
        its own storm window — the ACTOR FLEET IS NEVER TOUCHED (the
        chaos matrix's headline assertion for this boundary: a frontier
        death must cost external availability only, never training). The
        same invariant covers the REBUILD itself: a re-bind that fails
        (the port momentarily taken during the outage) costs external
        availability only — training continues and the supervisor keeps
        retrying on a short backoff. (The INITIAL bind in _start_actors
        stays loud: a taken port at startup is an operator config error,
        not an outage.)"""
        if self._stop.is_set() or self.config.gateway_port == 0:
            return
        gateway = self._gateway
        if gateway is None:
            # A previous rebuild could not re-bind: retry, backed off.
            if time.monotonic() < self._gateway_retry_at:
                return
            try:
                self._spawn_gateway()
            except OSError as e:
                self._gateway_retry_at = time.monotonic() + 2.0
                print(
                    f"asyncrl_tpu: gateway re-bind failed ({e}); external "
                    "serving stays down, retrying (training continues)",
                    file=sys.stderr,
                )
            return
        if gateway.is_alive() and gateway.fatal is None:
            return
        fatal = gateway.fatal
        flightrec.record(
            "supervisor.gateway_restart", detail=f"{fatal!r}"
        )
        self._gateway_restarts += 1
        obs_registry.counter("gateway_restarts").inc()
        # The server storm rule at one instance: > 3 in the window aborts.
        self._storm_guard(
            self._recent_gateway_restarts, 3, "gateway", fatal
        )
        gateway.stop()
        self._gateway = None  # a failed re-spawn must not re-reap the dead one
        try:
            self._spawn_gateway()
        except OSError as e:
            self._gateway_retry_at = time.monotonic() + 2.0
            flightrec.record(
                "supervisor.gateway_rebind_failed", detail=f"{e}"
            )
            print(
                f"asyncrl_tpu: gateway re-bind failed ({e}); external "
                "serving stays down, retrying (training continues)",
                file=sys.stderr,
            )

    def _supervise(self) -> None:  # thread-entry: watchdog@learner
        """The reap loop: rebuild a dead/hung inference server, restart
        dead actors (SURVEY.md §5.3 — fresh env pool each time), retire and
        replace HUNG actors via the heartbeat watchdog, and re-raise only
        if failures repeat rapidly. "Rapidly" means within
        ``_RESTART_WINDOW_S``: sporadic transient failures over a long run
        recover indefinitely; a crash loop aborts."""
        from asyncrl_tpu.rollout.inference_server import InvariantViolation

        self._supervise_server()
        self._supervise_gateway()
        self._supervise_stalled_actors()
        try:
            while True:
                index, gen, err = self._errors.get_nowait()
                if isinstance(err, InvariantViolation):
                    # §5.2b failures are integrity bugs, not transient actor
                    # faults: abort NOW instead of churning restarts (even
                    # when reported by an already-replaced generation). The
                    # one abort class that means a REAL pipeline bug gets
                    # forensics like every other failure path.
                    flightrec.record(
                        "supervisor.invariant_abort",
                        detail=f"actor {index} gen {gen}: {err!r}",
                    )
                    self.stop()
                    raise err
                if index >= len(self._actors) or gen != self._actor_gens[index]:
                    # A thread the supervisor already retired (watchdog
                    # abandonment racing the thread's own death report) or
                    # a slot a deliberate scale-down removed (its gen was
                    # bumped at retirement): ONE failure must not restart
                    # the slot twice — the second restart would orphan the
                    # live replacement (or resurrect a retired slot).
                    continue
                self._restart_actor(index, err)
        except queue.Empty:
            pass

    def _storm_guard(
        self,
        stamps: list[float],
        threshold: int,
        what: str,
        cause: BaseException | None,
    ) -> None:
        """ONE sliding-window storm policy for every supervised component:
        record a restart, prune the window, abort past the threshold."""
        now = time.monotonic()
        stamps.append(now)
        stamps[:] = [t for t in stamps if now - t < self._RESTART_WINDOW_S]
        if len(stamps) > threshold:
            # Last forensics before the abort: the flight recorder gets
            # the final seconds of every thread's spans (no-op unarmed).
            flightrec.record(
                "supervisor.storm_abort",
                detail=f"{what}: {len(stamps)} restarts in "
                f"{self._RESTART_WINDOW_S}s (cause: {cause!r})",
            )
            self.stop()
            raise RuntimeError(
                f"{what} failed repeatedly ({len(stamps)} restarts in "
                f"{self._RESTART_WINDOW_S}s)"
            ) from cause

    def _restart_actor(
        self, index: int, err: BaseException | None, reason: str = "crash"
    ) -> None:
        """Retire actor ``index`` (already dead or abandoned) and spawn its
        replacement, aborting on a restart storm. ``reason`` classifies
        the retirement cause for the storm accounting: ``"crash"`` feeds
        the crash-storm window, ``"watchdog"`` its own window — a
        stall-churning fleet and a crash-looping one are different
        failures and must not pool toward one abort threshold (and a
        deliberate elastic scale-down goes through
        :meth:`_scale_down_actor` instead, entering neither)."""
        # Forensics FIRST, replacement second: the dump captures every
        # thread's spans as they were when the failure was detected
        # (crash or watchdog retirement alike). No-op when unarmed.
        flightrec.record(
            "supervisor.actor_restart",
            detail=(
                f"actor {index} gen {self._actor_gens[index]} "
                f"reason={reason}: {err!r}"
            ),
        )
        self._actor_restarts += 1
        stamps = (
            self._recent_watchdog
            if reason == "watchdog"
            else self._recent_restarts
        )
        # The bar follows the LIVE fleet (3 per actor), not the configured
        # actor_threads: an elastically grown fleet earns proportionally
        # more tolerated restarts, a shrunken one keeps the tight bar a
        # small fleet had before elasticity existed.
        self._storm_guard(
            stamps, 3 * max(1, len(self._actors)),
            f"actor {index} ({reason})", err,
        )
        self._actor_gens[index] += 1
        self._backpressure_base += self._actors[index].backpressure
        if self._staging is not None:
            # Void the dead/abandoned thread's open slab lease: the row
            # re-opens for the replacement under a fresh generation, and
            # any late write/commit from a zombie raises StaleLeaseError
            # instead of scribbling on the re-leased row.
            lease = self._actors[index]._open_lease
            if lease is not None:
                self._staging.void(lease)
        self._actors[index] = self._spawn_actor(index)

    def _supervise_stalled_actors(self) -> None:
        """Heartbeat watchdog (config.stall_timeout_s > 0): an actor whose
        progress stamp went stale is HUNG — a raised exception would have
        landed in the error queue — so retire it through its personal
        abandon event and restart, under the same storm accounting as a
        crash. A thread wedged past the join window is abandoned exactly
        like stop()'s timeout path (it can only exit, never produce: its
        puts check the abandon event, and generations already advanced)."""
        timeout_s = self.config.stall_timeout_s
        if timeout_s <= 0 or not self._actors:
            return
        now = time.monotonic()
        for index, actor in enumerate(self._actors):
            if not actor.is_alive():
                continue  # crashed, not hung: the error path owns it
            if now - actor.heartbeat <= timeout_s:
                continue
            actor.abandon.set()
            actor.join(timeout=1.0)
            if actor.is_alive():
                print(
                    f"asyncrl_tpu: hung actor {actor.index} did not join "
                    "within 1s; abandoning thread (it exits at its next "
                    "abandon-event check)",
                    file=sys.stderr,
                )
            self._restart_actor(
                index,
                RuntimeError(
                    f"actor {index} made no progress for more than "
                    f"{timeout_s}s (heartbeat watchdog)"
                ),
                reason="watchdog",
            )

    def _supervise_server(self) -> None:
        """Supervised inference-server restart: a server thread that died
        (any exception — recorded in ``_fatal``) or hung (stale heartbeat
        under the watchdog) is retired via its personal stop event and
        rebuilt. Its orphaned clients raise the real cause into their
        actor threads, whose restarts wire up to the new server. An
        ``InvariantViolation`` death aborts instead — transport-integrity
        bugs must never feed a restart loop."""
        server = self._server
        if server is None or self._stop.is_set():
            return
        from asyncrl_tpu.rollout.inference_server import InvariantViolation

        fatal = server._fatal
        if isinstance(fatal, InvariantViolation):
            flightrec.record(
                "supervisor.invariant_abort", detail=f"server: {fatal!r}"
            )
            self.stop()
            raise fatal
        hung = (
            self.config.stall_timeout_s > 0
            and server.is_alive()
            and time.monotonic() - server.heartbeat
            > self.config.stall_timeout_s
        )
        if server.is_alive() and not hung:
            return
        # Authoritative _fatal re-read: the cause is written just before
        # the thread exits, so the first read above can race it — but once
        # is_alive() is False the assignment is guaranteed visible. Without
        # this, an InvariantViolation landing in that window would feed a
        # rebuild instead of the abort the policy promises.
        fatal = server._fatal or fatal
        if isinstance(fatal, InvariantViolation):
            flightrec.record(
                "supervisor.invariant_abort", detail=f"server: {fatal!r}"
            )
            self.stop()
            raise fatal
        flightrec.record(
            "supervisor.server_restart",
            detail=f"hung={hung}: {fatal!r}",
        )
        self._server_restarts += 1
        # The actor storm rule at one instance: > 3 in the window aborts.
        self._storm_guard(
            self._recent_server_restarts, 3, "inference server", fatal
        )
        self._server_stop.set()  # wake blocked clients of the old server
        server.join(timeout=5.0)
        if server.is_alive():
            print(
                "asyncrl_tpu: hung inference server did not join within "
                "5s; abandoning thread (its stop event stays set)",
                file=sys.stderr,
            )
        self._spawn_server()
        # Actors were likely blocked on the dead server; their stamps are
        # stale through no fault of their own — refresh so the stall
        # watchdog doesn't double-count the outage against them. Stamped
        # AFTER the join above (which can eat seconds on a wedged server);
        # an earlier timestamp could already be past stall_timeout_s.
        refreshed = time.monotonic()
        for actor in self._actors:
            actor.heartbeat = refreshed

    # -------------------------------------------------------------- elastic

    def _scale_up_actor(self) -> None:
        """Grow the fleet by one slot (window-close thread). The serve
        core's client slot registers FIRST (``client(index)`` must not
        bounds-fail), the thread spawns LAST — mutate-last, so a failing
        env-pool build observed by the reconfigure barrier leaves the
        fleet exactly as it was."""
        index = len(self._actors)
        while len(self._actor_gens) <= index:
            self._actor_gens.append(0)
        if self._server is not None:
            self._server.ensure_client(index)
        try:
            self._actors.append(self._spawn_actor(index))
        # lint: broad-except-ok(not a swallow: cleanup-and-reraise — the serve-client registration unwinds and the original failure propagates to the reconfigure barrier)
        except BaseException:
            # _spawn_actor registers the serve-client slot (client(index))
            # BEFORE the thread exists; if the build fails after that
            # point, a ghost registration would hold every future
            # dispatch's slab-full target one client high — each batch
            # waiting out its full deadline on a client that can never
            # submit. remove_client is idempotent, so this is safe even
            # when the failure preceded the registration.
            if self._server is not None:
                self._server.remove_client(index)
            raise

    def _scale_down_actor(self) -> None:
        """Retire the highest slot (window-close thread) through the
        existing per-thread retirement path — the abandon event, the join
        window, the lease void — so shrink is drain-clean by the same
        argument as a watchdog retirement: the thread can only exit, and
        its voided OPEN lease raises ``StaleLeaseError`` on any late
        write. Fragments it already committed and queued keep valid
        leases and drain into the learner normally — real on-policy data
        is consumed, not discarded (the "zero dropped leases" chaos
        assertion counts on exactly this). The slot's
        generation bumps so a zombie's late error report (and a future
        regrow of the same index) can never be confused with the retired
        stream. Deliberate: enters NO storm window."""
        index = len(self._actors) - 1
        actor = self._actors[index]
        actor.abandon.set()
        actor.join(timeout=5.0)
        if actor.is_alive():
            print(
                f"asyncrl_tpu: scaled-down actor {index} did not join "
                "within 5s; abandoning thread (it exits at its next "
                "abandon-event check)",
                file=sys.stderr,
            )
        self._actors.pop()
        self._actor_gens[index] += 1
        self._backpressure_base += actor.backpressure
        if self._staging is not None:
            lease = actor._open_lease
            if lease is not None:
                self._staging.void(lease)
        if self._server is not None:
            # AFTER the join: the actor can no longer submit, so removing
            # its registration cannot strand a pending request — and the
            # removal wakes the batch-fill wait so the slab-full condition
            # re-targets the shrunken client set.
            self._server.remove_client(index)

    def _build_staging_ring(self, actor_count: int):
        """Allocate — NOT install — a staging ring sized for
        ``actor_count`` (auto sizing only; an explicit ``staging_slabs``
        is an operator's fixed choice). None = no resize needed. The
        fallible slab allocation lives here so the reconfigure closure
        can run it BEFORE any fleet mutation; installing is the separate
        ``self._staging.swap`` (the RingSwapHolder generation protocol,
        rollout/staging.py: in-flight leases finish on the old ring)."""
        if self._staging_template is None or self.config.staging_slabs:
            return None
        from asyncrl_tpu.rollout import staging

        depth = staging.auto_num_slabs(
            self._queue.maxsize, actor_count, self._staging_rows
        )
        if depth == self._staging.num_slabs:
            return None
        return staging.StagingRing(
            self._staging_template,
            rows_per_slab=self._staging_rows,
            num_slabs=depth,
        )

    def _elastic_step(self, window: dict[str, Any]) -> None:
        """One controller evaluation at window close (window-close thread,
        next to the health monitor). A decision executes inside the
        save → reconfigure → restore barrier and is recorded as a
        structured event everywhere a crash would be: flight recorder,
        registry counters, time-series annotation."""
        decision = self._elastic.decide(window, len(self._actors))
        if decision is None:
            return
        before = len(self._actors)
        flightrec.record(
            f"elastic.scale_{decision.direction}",
            detail=f"{decision.reason}: {decision.detail} "
            f"(fleet {before} {decision.delta:+d})",
        )

        def reconfigure():
            # Exactly ONE slot per decision (the controller's delta
            # contract: delta is always ±1) — and mutate-last across the
            # COMPOSED action: the ring resize's fallible slab allocation
            # runs before the fleet changes, and the swap installs it
            # only after the slot operation succeeded. A failure anywhere
            # leaves both the fleet and the data path on the pre-scale
            # shape the barrier's restore message describes; an unused
            # pre-built ring is just garbage-collected.
            new_ring = self._build_staging_ring(before + decision.delta)
            if decision.delta > 0:
                self._scale_up_actor()
            else:
                self._scale_down_actor()
            if new_ring is not None:
                self._staging.swap(new_ring)

        with trace.span(span_names.ELASTIC_RECONFIGURE):
            self.state, self.env_steps, ok = self._elastic_barrier.run(
                self.state, self.env_steps, reconfigure
            )
        if not ok:
            # A rolled-back scale is NOT a scale: only
            # elastic_reconfigure_failed records the attempt, so the
            # scale counters/annotations never report a fleet change
            # that did not happen.
            obs_registry.counter("elastic_reconfigure_failed").inc()
            flightrec.record(
                "elastic.reconfigure_failed",
                detail=f"restored checkpoint barrier; fleet stays at "
                f"{len(self._actors)}",
            )
            return
        obs_registry.counter(f"elastic_scale_{decision.direction}").inc()
        if self._obs.store is not None:
            self._obs.store.annotate(
                decision.event(before, len(self._actors))
            )

    def _advance_updates(self, n: int) -> None:
        """Advance the learner-update counter by ``n`` and publish at
        every crossed actor_staleness boundary — ONE home for the
        publish cadence, so the fresh drain and the replay passes can
        never drift on when actors see new weights. (With n >= the
        staleness period, every call publishes — the fused-dispatch
        coarsening trade, unchanged.)"""
        before = self._updates
        self._updates += n
        staleness = max(self.config.actor_staleness, 1)
        if before // staleness != self._updates // staleness:
            version = self._store.publish(
                self._published(self.state), self.env_steps
            )
            self._published_updates[version] = self._updates
            # Bound the map: anything older than the deepest possible
            # in-flight fragment is unreachable.
            for old in [
                v for v in self._published_updates
                if v < version - 4 * (self._queue.maxsize + 2)
            ]:
                del self._published_updates[old]

    def _replay_passes(self, pending: list) -> None:
        """The IMPACT reuse phase, run after each fresh update: lease up
        to ``replay_passes - 1`` least-reused ring rows and feed each to
        the learner as one more SGD pass. Replayed consumptions feed the
        PR-8 staleness ledger (lag measured against the slab's ORIGINAL
        behaviour publish — off-policy-ness stays observed, not guessed)
        and the reuse/target-lag window; env_steps does NOT advance (no
        new environment data was consumed)."""
        cfg = self.config
        # target_lag is phased on the HOST update cursor. Approximation,
        # documented: under the NaN-guard (a skipped update holds the
        # device-side update_step while this cursor advances) or after a
        # rollback restore (device step rewinds, this cursor does not —
        # the PR-10 rule that only resume rewrites it), the reported
        # phase can drift from the device refresh schedule. Diagnostic-
        # grade by design; deriving it from the device step would cost a
        # host sync per consumed sample.
        period = max(cfg.target_update_period, 1)
        for _ in range(cfg.replay_passes - 1):
            rlease = self._replay.lease_sample(self._replay_rng)
            if rlease is None:
                break
            try:
                replayed, reuse, behaviour = rlease.consume()
            except replay_lib.ReplayStaleError:
                continue
            self.state, metrics = self.learner.update(self.state, replayed)
            pending.append(metrics)
            # Observed BEFORE the counter advances, matching the fresh
            # path's convention (lag = consuming update's pre-advance
            # index minus the behaviour publish): the replay pass that
            # immediately follows a fresh consumption at lag L reports
            # L+1, not L+2.
            if self._staleness is not None:
                self._staleness.observe(self._updates - behaviour)
            self._reuse_window.observe(reuse, self._updates % period)
            self._advance_updates(1)

    def _infer_coalesce_window(self) -> dict[str, float]:
        """Mean coalesced inference-batch rows per served round since the
        last window close ({} without a shared server). Snapshots per
        server INCARNATION (the restart counter), so a supervised
        rebuild's fresh counters never read as a negative delta."""
        server = self._server
        if server is None:
            return {}
        incarnation = self._server_restarts
        rounds, rows = server.coalesce_rounds, server.coalesce_rows
        snap_inc, snap_rounds, snap_rows = self._infer_snap
        if snap_inc != incarnation:
            snap_rounds = snap_rows = 0
        d_rounds = rounds - snap_rounds
        d_rows = rows - snap_rows
        self._infer_snap = (incarnation, rounds, rows)
        return {
            "infer_coalesce_batch": d_rows / d_rounds if d_rounds else 0.0
        }

    def _drain_queue(self) -> None:
        """Discard queued fragments — THROUGH the §5.2b checker when armed,
        so a discarded fragment still advances its stream (a later gap from
        skipping it unchecked would be a false positive, and a real
        transport bug hiding among discards would go unseen)."""
        try:
            while True:
                fragment = self._queue.get_nowait()
                if self._seq_checker is not None:
                    self._seq_checker.check(fragment)
        except queue.Empty:
            pass

    def stop(self) -> None:
        """Stop actor threads (and the inference server), drain the queue."""
        self._stop.set()
        if self._gateway is not None:
            # The wire boundary closes FIRST: external clients observe
            # 503-draining (and then connection refused) rather than
            # requests dying mid-pipeline behind them.
            self._gateway.close_admissions()
            self._gateway.stop()
            self._gateway = None
        # The server's personal event must be set BEFORE the actor joins:
        # actors blocked in _submit wake on the SERVER's stop event, not
        # the cohort's — setting it late would make every join below eat
        # its full timeout against a wedged server.
        self._server_stop.set()
        # Unblock producers stuck on a full queue.
        self._drain_queue()
        for actor in self._actors:
            actor.join(timeout=5.0)
            if actor.is_alive():
                # Loud, not silent: the thread outlived the join window
                # (e.g. wedged in pool.step). Its cohort's stop event stays
                # set forever — it can only exit, never resume — and the
                # next cohort gets a fresh event + bumped generations.
                print(
                    f"asyncrl_tpu: actor {actor.index} did not join within "
                    "5s; abandoning thread (it will exit at its next "
                    "stop-event check)",
                    file=sys.stderr,
                )
        # Drain AGAIN after the joins: an actor mid-put when the first drain
        # ran can still land one fragment; left queued, it would feed the
        # next train() a stale-cohort fragment.
        self._drain_queue()
        for actor in self._actors:
            self._backpressure_base += actor.backpressure
        if self._actors:
            self._last_live_fleet = len(self._actors)
        self._actors = []
        if self._server is not None:
            self._server_stop.set()
            self._server.join(timeout=5.0)
            self._server = None
        if self._staging is not None:
            # Every lease (queued, open, or held by an abandoned zombie)
            # goes stale and every slab frees: the next train() starts on
            # a clean ring, and a zombie's late commit raises instead of
            # landing in a recycled row.
            self._staging.reset()
        if self._replay is not None:
            # Same hygiene at the device tier: a new cohort starts on an
            # empty replay ring — cross-cohort replay would resurrect a
            # stopped run's off-policy tail — and on fresh telemetry
            # (the trend baseline and any undrained reuse observations
            # belong to the stopped cohort's windows).
            self._replay.quarantine()
            self._reuse_window.drain()
            self._stall_history.clear()
        if self._device_queue is not None:
            # Straggler device leases go stale and every pending update
            # handle drains: no async consumer of a slot outlives the
            # cohort whose drain minted it.
            self._device_queue.reset()

    # ----------------------------------------------------- durable runs

    def _restore_fleet(self) -> None:
        """Resume path: grow/shrink the just-started fleet to the
        checkpointed size (one slot at a time through the SAME executors
        a live scale uses, ring resize included), so a run preempted at
        an elastically-scaled shape resumes at that shape instead of the
        configured one."""
        target = self._resume_fleet
        if target is None:
            return
        self._resume_fleet = None
        before = len(self._actors)
        while len(self._actors) != target:
            step = 1 if len(self._actors) < target else -1
            new_ring = self._build_staging_ring(len(self._actors) + step)
            if step > 0:
                self._scale_up_actor()
            else:
                self._scale_down_actor()
            if new_ring is not None:
                self._staging.swap(new_ring)
        flightrec.record(
            "durability.fleet_restored",
            detail=f"resume rebuilt the fleet at {target} actors "
            f"(configured {before})",
        )

    def _preempt_drain(self, drain) -> None:
        """The preemption-safe drain (SIGTERM/SIGINT under a grace
        budget): stop serve admissions, retire the fleet through the
        existing void/commit path, flush the partial obs window + flight
        recorder (reason=preempt), make ONE final full-run-state
        checkpoint durable, then leave with the distinct EXIT_DRAINED
        code. Runs on the train (window-close) thread; the coordinator's
        deadline watchdog hard-kills past the grace."""
        flightrec.record(
            "supervisor.preempt",
            detail=f"signal {drain.signum}: draining within "
            f"{drain.grace_s:.0f}s, then exiting {durability.EXIT_DRAINED}",
        )
        if self._gateway is not None:
            # The drain protocol's outermost edge: gateway admissions
            # close BEFORE the serve gate, so no external request can be
            # admitted into a pipeline that is about to drain under it —
            # and before the final checkpoint below, so the checkpoint
            # never races live wire traffic.
            self._gateway.close_admissions()
        server = self._server
        if server is not None:
            gate = getattr(server, "slo", None)
            if gate is not None:
                # New admissions refuse FIRST, so the actor joins below
                # never race fresh requests into the dispatch queue.
                gate.close()
        # stop() is the existing drain-clean retirement: queued fragments
        # discard through the §5.2b checker, actors join (or abandon),
        # every staging lease goes stale, every slab frees.
        self.stop()
        # Flush the partial metrics window so the timeseries' final
        # sample records where the run actually stopped (counters are
        # cumulative, so a short window is honest, never misleading).
        agg: dict[str, Any] = {
            "env_steps": self.env_steps,
            "drain_preempt": 1.0,
            "actor_restarts": self._actor_restarts,
            "server_restarts": self._server_restarts,
        }
        if self.config.gateway_port != 0:
            # Same guarded key the main-loop window exports: the terminal
            # sample must not drop the gateway's restart history.
            agg["gateway_restarts"] = self._gateway_restarts
        agg.update(faults.counters())
        self._obs.observe_window(agg)
        if self._ckpt.checkpointer is not None:
            # The final checkpoint carries the full run state via meta_fn
            # and must be DURABLE before the exit code promises it.
            self._ckpt.save_now(self.state, self.env_steps)
            self._ckpt.checkpointer.wait()
        self._obs.close()  # flight-recorder queue flushed to disk
        drain.finish()
        raise durability.PreemptedExit(drain.signum)

    def _quarantine_poisoned(self, slab_groups, fragments) -> int:
        """Divergence quarantine: fragments produced under (or poisoned
        by) a diverging policy must never reach the learner. Queued
        fragments discard through the §5.2b checker with their slab
        leases voided (rows re-open under fresh generations — the
        supervisor-retirement mechanics applied to data instead of
        threads); partial slab groups and legacy-path stacks clear the
        same way. Returns the quarantined fragment count."""
        count = 0
        try:
            while True:
                fragment = self._queue.get_nowait()
                if self._seq_checker is not None:
                    self._seq_checker.check(fragment)
                if fragment.lease is not None and self._staging is not None:
                    self._staging.void(fragment.lease)
                count += 1
        except queue.Empty:
            pass
        for group in slab_groups.values():
            for fragment in group:
                if fragment.lease is not None and self._staging is not None:
                    self._staging.void(fragment.lease)
                count += 1
        slab_groups.clear()
        count += len(fragments)
        fragments.clear()
        if self._replay is not None:
            # The PR-10 path extended to the replay tier: every
            # outstanding replay lease voids (a zombie consume raises)
            # and the ring empties — slabs produced under, or reused
            # across, the diverging stretch must never feed another
            # update. The telemetry purges with the data (the stop()
            # hygiene): the poisoned stretch's reuse/target-lag
            # observations and its stall baseline must not contaminate
            # the first post-rollback window's keys.
            dropped = self._replay.quarantine()
            self._reuse_window.drain()
            self._stall_history.clear()
            if dropped:
                obs_registry.counter("replay_quarantined").inc(dropped)
        if count:
            obs_registry.counter("rollback_quarantined").inc(count)
        return count

    def _execute_rollback(self, action) -> None:
        """Restore the last-good checkpoint (window-close thread). The
        tainted steps saved AFTER the last clean window are evicted
        first, so the fallback restore cannot land on a checkpoint
        written while the run was already diverging; the actor-PRNG
        cursor folds so the replayed stretch decorrelates from the
        trajectory that diverged; the restored params republish
        immediately so actors stop acting under the poisoned weights."""
        ckpt = self._ckpt.checkpointer
        ckpt.wait()
        steps = sorted(ckpt.all_steps())
        if not steps:
            # Rollback fired before the first save landed: there is
            # nothing to restore, but the NaN-guard already held the
            # params through every poisoned update, so the run continues
            # on the held state — record the degraded action instead of
            # dying on a restore that cannot exist.
            flightrec.record(
                "rollback.no_checkpoint",
                detail="rollback fired with no retained steps; "
                "continuing on NaN-guard-held params",
            )
            return
        last_good = self._rollback.last_good_step
        target = None
        if last_good is not None:
            good = [s for s in steps if s <= last_good]
            if good:
                target = good[-1]
        if target is None:
            # The banked last-good step was rotated out by max_to_keep
            # retention (or no clean window has banked one yet): the
            # OLDEST retained step is the closest surviving
            # approximation. Never evict the whole directory hunting for
            # a step that no longer exists.
            target = steps[0]
        for step in steps:
            if step > target:
                ckpt.delete_step(step)
        self.state, self.env_steps = ckpt.restore(self.state)
        # The run RE-TRAINS from here with fresh data: when it reaches
        # the restored step number again the save must REPLACE, not
        # no-op on the idempotent-save rule.
        ckpt.invalidate_restored()
        self._next_actor_seed += 104729 * 997  # fresh PRNG fold
        version = self._store.publish(
            self._published(self.state), self.env_steps
        )
        self._published_updates[version] = self._updates

    def _rollback_step(self, agg, slab_groups, fragments) -> bool:
        """One RollbackPolicy evaluation at window close (next to the
        health monitor and the elastic controller, same thread). Returns
        True when an action fired — the elastic controller skips a
        window whose signals a divergence just poisoned."""
        monitor = self._obs.monitor
        if monitor is not None:
            events = [
                e for e in monitor.recent_events()
                if e.window_idx == monitor.window_idx
            ]
        else:
            # No health layer mounted (trace off, no exposition port):
            # the policy still sees the one divergence signal the window
            # dict itself carries — a non-finite loss/grad_norm.
            events = []
            for key in ("loss", "grad_norm"):
                value = agg.get(key)
                if isinstance(value, float) and not np.isfinite(value):
                    events.append(
                        type("E", (), {"detector": "nonfinite_loss"})()
                    )
                    break
        ckpt = self._ckpt.checkpointer
        latest = ckpt.latest_step() if ckpt is not None else None
        action = self._rollback.on_window(events, latest)
        if action is None:
            return False
        counter = {
            "quarantine": "rollback_quarantine",
            "rollback": "rollback_restores",
            "abort": "rollback_abort",
        }[action.kind]
        obs_registry.counter(counter).inc()
        flightrec.record(f"rollback.{action.kind}", detail=action.detail)
        if self._obs.store is not None:
            self._obs.store.annotate(action.event())
        if action.kind == "abort":
            self.stop()
            raise RuntimeError(
                f"divergence rollback attempts exhausted: {action.detail}"
            )
        quarantined = self._quarantine_poisoned(slab_groups, fragments)
        print(
            f"asyncrl_tpu: rollback policy: {action.kind} — "
            f"{action.detail} ({quarantined} in-flight fragment(s) "
            "quarantined)",
            file=sys.stderr,
        )
        if action.kind == "rollback":
            self._execute_rollback(action)
        return True

    # ---------------------------------------------------------------- train

    def train(  # thread-entry: learner-drain@learner
        self,
        total_env_steps: int | None = None,
        callback: Callable[[dict[str, Any]], None] | None = None,
    ) -> list[dict[str, Any]]:
        """Drain fragments and update until ``total_env_steps`` consumed.

        Metric dicts match ``Trainer.train``'s contract (env_steps, fps,
        episode_return/length/count + loss terms).
        """
        cfg = self.config
        target = total_env_steps or cfg.total_env_steps
        validate_train_target(cfg, target)
        steps_per_fragment = self._envs_per_actor * cfg.unroll_len
        history: list[dict[str, Any]] = []

        # The drain usually runs on MainThread — tag its span ring with
        # the pipeline-stage group so reports/flight dumps say "learner".
        trace.tag_thread("learner")
        # Preemption-safe drain (runtime/durability.py): with a grace
        # budget, SIGTERM/SIGINT route through the coordinator (handlers
        # install on the main thread only; the scripted `preempt` fault
        # kind reaches the same coordinator either way) and the loop
        # polls one Event per iteration — the unarmed cost discipline.
        drain = None
        if self._drain_grace > 0:
            drain = durability.DrainCoordinator(self._drain_grace)
            drain.install()
            durability.set_active(drain)
        try:
            self._start_actors()
            self._restore_fleet()
        # lint: broad-except-ok(cleanup-and-reraise: the drain handlers uninstall, then the startup failure propagates unchanged)
        except BaseException:
            # Startup died before the main try/finally below could own
            # the teardown: the process signal handlers (and the
            # scripted-preempt registration) must not outlive the train
            # call that installed them — a later Ctrl-C would request a
            # drain nothing polls, and the orphaned watchdog would
            # os._exit the host process 30s later.
            if drain is not None:
                drain.finish()
                drain.uninstall()
                durability.clear_active(drain)
            raise
        pending: list[dict[str, jax.Array]] = []
        ret_sum = len_sum = count = lag_sum = 0.0
        # Fresh fragments consumed this window: the param_lag mean's
        # denominator (``pending`` also carries replay-pass metrics when
        # the ring is armed, so len(drained) would over-count).
        frag_count = 0
        window_start = time.perf_counter()
        window_steps = 0
        # Pipeline instrumentation (utils/metrics.py window keys):
        # learner_stall_frac = fraction of window wall time the drain spent
        # waiting on the fragment queue (the learner starved for data);
        # h2d_wait_s = time in host->device transfer the compute could not
        # hide (overlap path: an explicit transfer barrier before the next
        # dispatch; legacy path: the device_put call itself); h2d_bytes =
        # host bytes shipped.
        stall_s = 0.0
        h2d_wait_s = 0.0
        h2d_bytes = 0
        # Cumulative-counter baseline: a SECOND train() call on this agent
        # must not fire an eval at its first log boundary.
        updates_at_eval = self._updates
        K = cfg.updates_per_call
        fragments: list[Fragment] = []
        # Staging mode: fragments grouped by slab until a slab has all K
        # rows in hand (completion order, like the legacy arrival order).
        # Keyed by (minting ring, slab): under an elastic ring swap the
        # old ring's in-flight fragments and the new ring's never share a
        # group — a batch is one ring's slab, always.
        slab_groups: dict[tuple[Any, int], list[Fragment]] = {}
        ring = self._staging
        try:
            while self.env_steps < target:
                if drain is not None and drain.requested:
                    self._preempt_drain(drain)  # raises PreemptedExit
                self._supervise()
                t_wait = time.perf_counter()
                try:
                    with trace.span(span_names.LEARNER_QUEUE_WAIT):
                        fragment = self._queue.get(timeout=1.0)
                except queue.Empty:
                    stall_s += time.perf_counter() - t_wait
                    continue
                stall_s += time.perf_counter() - t_wait
                if self._seq_checker is not None:
                    self._seq_checker.check(fragment)
                if ring is not None:
                    lease = fragment.lease
                    if lease is None or not lease.valid():
                        # A zombie's fragment: its lease was voided when
                        # the supervisor retired the thread, and the row
                        # now belongs to the replacement. (The checker
                        # above already advanced the old stream.)
                        continue
                    batch_ring = lease.ring
                    group_key = (batch_ring, lease.slab)
                    group = slab_groups.setdefault(group_key, [])
                    group.append(fragment)
                    if len(group) >= K:
                        # Re-validate at the boundary: a lease can go
                        # stale AFTER queueing (supervisor voiding racing
                        # the actor's post-put bookkeeping) — the voided
                        # row's replacement fragment completes the slab.
                        group[:] = [f for f in group if f.lease.valid()]
                    if len(group) < K:
                        continue
                    batch = sorted(
                        slab_groups.pop(group_key),
                        key=lambda f: f.lease.row,
                    )
                    slab_id = lease.slab
                    rollout = batch_ring.batch(slab_id)
                else:
                    fragments.append(fragment)
                    if len(fragments) < K:
                        # Fused-dispatch mode: keep draining until K
                        # fragments are in hand (actors keep producing;
                        # supervision keeps running between gets).
                        continue
                    batch, fragments = fragments, []
                    slab_id = None
                    batch_ring = None
                    rollout = _stack_fragments([f.rollout for f in batch])
                if cfg.reward_scale != 1.0 or cfg.step_cost != 0.0:
                    # Learner's reward view (living cost, then scale). Host
                    # fragments carry RAW rewards, so the cost applies here.
                    # The disc_returns stream (normalize_returns' std
                    # tracker) is scaled but NOT cost-shifted — the same
                    # cost-free stream the anakin path tracks (see
                    # rollout/anakin.py), so both backends normalize by the
                    # same statistic for the same config.
                    rollout = rollout.replace(
                        rewards=(rollout.rewards - cfg.step_cost)
                        * cfg.reward_scale,
                        disc_returns=(
                            None
                            if rollout.disc_returns is None
                            else rollout.disc_returns * cfg.reward_scale
                        ),
                    )
                t_put = time.perf_counter()
                dlease = None
                try:
                    with trace.span(span_names.LEARNER_H2D_WAIT):
                        if self._device_queue is None:
                            rollout_d = self.learner.put_rollout(rollout)
                        else:
                            # HBM hand-off (rollout/device_queue.py): the
                            # same sharded transfer, behind the queue's
                            # slot ledger — enqueue blocks here (counted in
                            # devq_reuse_waits) when the drain has outrun
                            # the learner by the full queue depth.
                            dlease = self._device_queue.enqueue(rollout)
                            rollout_d = dlease.rollout()
                        if ring is not None:
                            # Transfer barrier: wait for slab i+1's H2D to
                            # finish BEFORE dispatching its update — this
                            # wait runs while the PREVIOUS update still
                            # computes on device, so transfer time hides
                            # behind compute and h2d_wait_s records only
                            # the part that didn't fit under it.
                            jax.block_until_ready(rollout_d)
                    h2d_wait = time.perf_counter() - t_put
                    h2d_wait_s += h2d_wait
                    # Registry histogram (obs/registry.py): the per-update
                    # unhidden-transfer distribution — p50/p95/max surface
                    # in the window next to the legacy h2d_wait_s sum.
                    obs_registry.histogram("h2d_wait_ms").observe(
                        1e3 * h2d_wait
                    )
                    # Slab batches are constant-sized (precomputed); only
                    # the legacy stack path needs the per-update leaf walk.
                    h2d_bytes += (
                        batch_ring.slab_nbytes
                        if batch_ring is not None
                        else int(
                            sum(
                                leaf.nbytes
                                for leaf in jax.tree.leaves(rollout)
                            )
                        )
                    )
                    if self._replay is not None:
                        # The fresh slab enters the device ring BEFORE the
                        # update can donate it (publish is a device-to-
                        # device install into the leased row, oldest-
                        # generation eviction); the fresh pass itself
                        # counts as the row's first consumption.
                        self._replay.publish(
                            rollout_d,
                            behaviour_update=self._published_updates.get(
                                batch[0].version, self._updates
                            ),
                            # Zero-copy adoption when the fragment is HBM-
                            # resident behind the device queue's ledger and
                            # the update cannot donate it out from under
                            # the ring (see DeviceReplayRing.publish).
                            ref=self._replay_ref,
                        )
                        self._reuse_window.observe(
                            1,
                            self._updates
                            % max(cfg.target_update_period, 1),
                        )
                    self.state, metrics = self.learner.update(
                        self.state, rollout_d
                    )
                # lint: broad-except-ok(cleanup-and-reraise: the held HBM lease voids so the slot cannot leak past train(), then the failure propagates unchanged)
                except BaseException:
                    if dlease is not None:
                        # The update never consumed this fragment: void
                        # the lease (barriers the in-flight H2D) so the
                        # slot frees instead of leaking held.
                        dlease.void()
                    raise
                if dlease is not None:
                    # The slot re-leases only once THIS update's output
                    # is ready — the staging retire gate, device tier.
                    dlease.consume(self.state.update_step)
                if batch_ring is not None:
                    # The slab frees only once this update's OUTPUT is
                    # ready — the gate that makes reuse safe even where
                    # the device buffer aliases host memory (CPU client).
                    # Retired on the MINTING ring: after an elastic ring
                    # swap an old-ring slab must free on the old ring.
                    batch_ring.retire(slab_id, self.state.update_step)
                self.env_steps += steps_per_fragment * K
                window_steps += steps_per_fragment * K
                pending.append(metrics)
                for i, f in enumerate(batch):
                    ret_sum += f.return_sum
                    len_sum += f.length_sum
                    count += f.count
                    # Policy lag of each fragment, in learner updates: it
                    # was consumed by fused inner update self._updates + i,
                    # and its behaviour params were published at the
                    # RECORDED update count of its version (publishes are
                    # per-boundary, not per-update, under fused dispatch).
                    # With inference_server=True this is an UPPER BOUND —
                    # the server evaluates under the latest published
                    # params, so later steps of a fragment can be fresher
                    # than its fragment-start version implies.
                    lag = (self._updates + i) - self._published_updates.get(
                        f.version, self._updates
                    )
                    lag_sum += lag
                    frag_count += 1
                    if self._staleness is not None:
                        self._staleness.observe(lag)

                self._advance_updates(K)
                if self._replay is not None:
                    # IMPACT reuse phase: replay_passes - 1 more SGD
                    # passes from the device ring, between fresh
                    # fragments — the learner trains while the actors
                    # are still producing the next slab.
                    self._replay_passes(pending)
                self._ckpt.after_update(self.state, self.env_steps)

                if len(pending) >= cfg.log_every or self.env_steps >= target:
                    with trace.span(span_names.LEARNER_METRICS):
                        drained = jax.device_get(pending)
                    pending = []
                    elapsed = time.perf_counter() - window_start
                    window_start = time.perf_counter()
                    # Metric leaves are scalars (K=1) or [K] stacks (fused
                    # dispatch): np handles both.
                    agg = {
                        k: float(np.mean([np.mean(m[k]) for m in drained]))
                        for k in drained[0]
                    }
                    agg["episode_count"] = count
                    agg["episode_return"] = ret_sum / max(count, 1.0)
                    agg["episode_length"] = len_sum / max(count, 1.0)
                    agg["param_lag"] = lag_sum / max(frag_count, 1)
                    agg["env_steps"] = self.env_steps
                    agg["fps"] = window_steps / max(elapsed, 1e-9)
                    # Recovery/robustness counters (cumulative), so the
                    # JSONL/TensorBoard record shows WHEN the pipeline
                    # churned: supervisor restarts, actor->learner queue
                    # backpressure, and per-site injected-fault counts.
                    agg["actor_restarts"] = self._actor_restarts
                    agg["server_restarts"] = self._server_restarts
                    if self.config.gateway_port != 0:
                        # Guarded: gateway off leaks zero gateway keys.
                        agg["gateway_restarts"] = self._gateway_restarts
                    agg["queue_backpressure"] = self._backpressure_base + sum(
                        a.backpressure for a in self._actors
                    )
                    # Pipeline metrics: the transfer-overlap story in
                    # numbers, per window (see the accumulator comments
                    # above and docs/ARCHITECTURE.md "Data path & transfer
                    # overlap").
                    agg["h2d_wait_s"] = h2d_wait_s
                    agg["h2d_bytes"] = h2d_bytes
                    agg["learner_stall_frac"] = min(
                        stall_s / max(elapsed, 1e-9), 1.0
                    )
                    if ring is not None:
                        agg["slab_reuse_waits"] = ring.reuse_waits
                    if self._device_queue is not None:
                        # Device-tier twin of slab_reuse_waits: enqueues
                        # that blocked on a pending update's handle (the
                        # drain outran the learner by the queue depth).
                        agg["devq_reuse_waits"] = (
                            self._device_queue.reuse_waits
                        )
                    # Off-policy staleness distribution for the window
                    # (staleness_p50/p95/max/mean, in learner updates) —
                    # the per-fragment lags behind the param_lag mean.
                    # The compile counters (compiles / infer_recompile /
                    # learner_recompile) ride the shared registry drain
                    # in observe_window below, landing next to
                    # infer_coalesce_batch in this same dict.
                    if self._staleness is not None:
                        agg.update(self._staleness.drain())
                    if "nonfinite_skip" in agg:
                        # NaN-guard accounting (rollback armed): the
                        # per-update skip flags fold into ONE cumulative
                        # counter key; the per-update mean the generic
                        # aggregation produced would under-read as a
                        # fraction.
                        self._nonfinite_skips += float(
                            sum(
                                np.sum(m["nonfinite_skip"]) for m in drained
                            )
                        )
                        del agg["nonfinite_skip"]
                        agg["nonfinite_skips"] = self._nonfinite_skips
                    if self._replay is not None:
                        # Replay telemetry (the ISSUE-14 aux): ring fill,
                        # per-sample reuse percentiles + target lag, and
                        # the stall-fraction trend vs the trailing mean
                        # (negative = replay is closing the duty-cycle
                        # gap). target_kl rides the learner metrics into
                        # this same dict. Replay off leaks NONE of these
                        # keys (the introspect=False discipline).
                        agg["replay_fill_frac"] = self._replay.fill_frac()
                        agg.update(self._reuse_window.drain())
                        hist = self._stall_history
                        agg["learner_stall_trend"] = (
                            agg["learner_stall_frac"]
                            - sum(hist) / len(hist)
                            if hist
                            else 0.0
                        )
                        hist.append(agg["learner_stall_frac"])
                    agg.update(self._infer_coalesce_window())
                    agg.update(faults.counters())
                    ret_sum = len_sum = count = lag_sum = 0.0
                    frag_count = 0
                    window_steps = 0
                    stall_s = h2d_wait_s = 0.0
                    h2d_bytes = 0
                    # In-training greedy eval on the log boundary. Actors
                    # keep filling the (bounded) queue during the pause, so
                    # window_start is deliberately NOT reset: the eval's
                    # wall time counts against the next window (an honest
                    # under-report) rather than letting the queue backlog
                    # drain into a shortened window and report fps above
                    # hardware throughput.
                    if (
                        cfg.eval_every > 0
                        # eval_every counts update CALLS (config.py), and a
                        # fused call is K updates — match Anakin's cadence.
                        and self._updates - updates_at_eval
                        >= cfg.eval_every * K
                    ):
                        updates_at_eval = self._updates
                        with trace.span(span_names.LEARNER_EVAL):
                            agg["eval_return"] = self.evaluate(
                                num_episodes=cfg.eval_episodes
                            )
                        self._ckpt.maybe_save_best(
                            self.state, self.env_steps, agg["eval_return"]
                        )
                    # Fleet-shape gauges (registry → window snapshot →
                    # /metrics + timeseries), exported EVEN when
                    # elasticity is off: without them a retired-and-not-
                    # replaced actor is indistinguishable from a quiet
                    # one in the recorded history (the obs-doctor gap).
                    obs_registry.gauge("actors_live").set(
                        float(sum(a.is_alive() for a in self._actors))
                    )
                    obs_registry.gauge("servers_live").set(
                        1.0
                        if self._server is not None and self._server.is_alive()
                        else 0.0
                    )
                    obs_registry.gauge("staging_slabs_live").set(
                        float(ring.num_slabs) if ring is not None else 0.0
                    )
                    if cfg.gateway_port != 0:
                        # Gateway liveness for /healthz and the recorded
                        # history — guarded on the CONFIG (not the
                        # object), so a crash-plus-failed-rebind outage
                        # (self._gateway is None while the supervisor
                        # retries) reads 0.0 instead of freezing at the
                        # last healthy value; gateway-off still leaks
                        # zero gateway keys (the bit-identity contract).
                        obs_registry.gauge("gateway_live").set(
                            1.0
                            if self._gateway is not None
                            and self._gateway.is_alive()
                            else 0.0
                        )
                    # ONE shared window snapshot (obs/__init__.py): the
                    # registry/trace drain merges in here, the health
                    # detectors run, and the time-series store records —
                    # all on THIS dict, so stdout, JSONL, TensorBoard,
                    # /metrics, and timeseries.jsonl can never disagree
                    # on what the window contained. Placed after the
                    # eval so eval_return feeds the regression detector.
                    self._obs.observe_window(agg)
                    # Divergence rollback: evaluated FIRST at window
                    # close — a window the divergence poisoned must not
                    # also drive a fleet-scale decision.
                    remediated = False
                    if self._rollback is not None:
                        remediated = self._rollback_step(
                            agg, slab_groups, fragments
                        )
                    # Elastic runtime: the controller reads the SAME
                    # merged window the sinks saw; a decision reconfigures
                    # the fleet here, between updates, on this thread.
                    if self._elastic is not None and not remediated:
                        self._elastic_step(agg)
                    history.append(agg)
                    if callback:
                        callback(agg)
        finally:
            self.stop()
            # A crash (including the §5.3 actor crash-loop abort) must not
            # lose progress: save final state and flush async writes.
            # (After a completed preemption drain this re-save no-ops on
            # the idempotent same-step rule — the drain already made the
            # final checkpoint durable.)
            self._ckpt.finalize(self.state, self.env_steps)
            # Flush any flight dumps still queued on the writer thread.
            # (The Perfetto export happens ONCE, in close(): exporting
            # per train() call would tax the measured hot path, and
            # crash-time forensics are the flight recorder's job.)
            self._obs.close()
            if drain is not None:
                # Disarm the deadline watchdog on EVERY exit path (a
                # crash racing a signal must not be hard-killed mid-
                # forensics), restore the previous handlers, and drop the
                # scripted-preempt registration.
                drain.finish()
                drain.uninstall()
                durability.clear_active(drain)
        return history

    def save_checkpoint(self) -> None:
        """Save the current LearnerState now (async; see ``Checkpointer``)."""
        self._ckpt.save_now(self.state, self.env_steps)

    def close(self) -> None:
        """Stop actors, flush pending checkpoint saves, release resources."""
        self.stop()
        if self._gateway_backend is not None:
            # Release the serve-stale anchor leases (stop() keeps the
            # backend alive across gateway rebuilds; final teardown is
            # here, after the last possible rebuild).
            self._gateway_backend.close()
            self._gateway_backend = None
        for pool in self._eval_pools.values():
            _close(pool)
        self._eval_pools = {}
        self._ckpt.close()
        # Perfetto export of everything the rings still hold (the whole
        # run's tail, all threads), then the final obs teardown: stop the
        # exposition endpoint, close timeseries.jsonl, flush forensics.
        self._obs.export_trace()
        self._obs.shutdown()

    # ----------------------------------------------------------------- eval

    def evaluate(
        self,
        num_episodes: int = 32,
        max_steps: int | None = None,
        seed: int = 1234,
        return_episodes: bool = False,
    ):
        """Mean greedy-policy return over ``num_episodes`` fresh host envs.

        Each env counts only its FIRST completed episode (pools auto-reset;
        ``pool.reset()`` below starts the fresh episodes).
        ``return_episodes=True`` returns the per-episode return vector
        instead of the mean — the same contract as ``Trainer.evaluate``, so
        per-episode audits (scripts/eval_caps.py) work on host-backend
        checkpoints too (VERDICT r4 Weak #7).
        """
        if max_steps is None:
            # Contain the longest builtin episode (same contract as
            # Trainer.evaluate — shared helper).
            max_steps = default_eval_max_steps(self.config)
        # Eval pools are cached per (num_episodes, seed) for the trainer's
        # lifetime: in-training evals would otherwise rebuild the pool —
        # and, for JaxHostPool, re-jit its env step — every eval period.
        pool_key = (num_episodes, seed)
        pool = self._eval_pools.get(pool_key)
        if pool is None:
            pool = make_host_pool(self.config, num_episodes, seed=seed)
            # Evaluation runs OUTSIDE the supervised pipeline: an injected
            # pool.step fault here would escape evaluate() un-recovered
            # (and consume the site's deterministic RNG/max budget meant
            # for the actor path under test), so eval pools always step
            # unarmed.
            pool.disarm_faults()
            self._eval_pools[pool_key] = pool
        recurrent = is_recurrent(self.model)
        # One jitted greedy fn for the trainer's lifetime (in-training
        # evals would otherwise redefine-and-retrace it every period; jit
        # still specializes per num_episodes batch shape, cached).
        if self._greedy_fn is None:
            dist = distributions.for_config(self.config, self.spec)
            apply_fn = self.model.apply

            if recurrent:

                @jax.jit
                def greedy_rec(params, obs_stats, obs, core, done_prev):
                    napply = normalizing_apply(apply_fn, obs_stats)
                    core = reset_core(core, done_prev)
                    dist_params, _, core = napply(params, obs, core)
                    return dist.mode(dist_params), core

                self._greedy_fn = greedy_rec
            else:

                @jax.jit
                def greedy(params, obs_stats, obs):
                    napply = normalizing_apply(apply_fn, obs_stats)
                    dist_params, _ = napply(params, obs)
                    return dist.mode(dist_params)

                self._greedy_fn = greedy
        greedy_fn = self._greedy_fn

        params = self.state.params
        obs_stats = self.state.obs_stats
        core = self.model.initial_core(num_episodes) if recurrent else None
        done_prev = np.zeros((num_episodes,), bool)
        try:
            obs = pool.reset()
            ep_return = np.zeros((num_episodes,), np.float64)
            finished = np.zeros((num_episodes,), bool)
            final_return = np.zeros((num_episodes,), np.float64)
            for _ in range(max_steps):
                # ONE batched jax.device_get per eval step (np.asarray
                # was a separate blocking sync per leaf — measurably worse
                # on a high-latency device link); the recurrent core stays
                # on device.
                if recurrent:
                    actions_d, core = greedy_fn(
                        params, obs_stats, obs, core, done_prev
                    )
                    actions = jax.device_get(actions_d)
                else:
                    actions = jax.device_get(greedy_fn(params, obs_stats, obs))
                obs, rew, term, trunc = pool.step(actions)
                done_prev = np.logical_or(term, trunc)
                ep_return += np.where(finished, 0.0, rew)
                done = np.logical_or(term, trunc) & ~finished
                final_return = np.where(done, ep_return, final_return)
                finished |= done
                if finished.all():
                    break
            final_return = np.where(finished, final_return, ep_return)
            if return_episodes:
                return final_return.astype(np.float32)
            return float(final_return.mean())
        # lint: broad-except-ok(not a swallow: evicts the broken eval pool from the cache, then re-raises the original failure)
        except BaseException:
            # A broken pool must not be reused; drop it from the cache.
            self._eval_pools.pop(pool_key, None)
            _close(pool)
            raise


def _pool_spec(pool, config: Config):
    """EnvSpec from a host pool: adapters carry one; the native pool exposes
    obs_dim/num_actions; fall back to the registry env's spec."""
    spec = getattr(pool, "spec", None)
    if spec is not None:
        return spec
    from asyncrl_tpu.envs.core import EnvSpec

    return EnvSpec(
        obs_shape=(pool.obs_dim,), num_actions=pool.num_actions
    )


def _close(pool) -> None:
    close = getattr(pool, "close", None)
    if close is not None:
        try:
            close()
        # lint: broad-except-ok(best-effort pool teardown at a supervisor boundary; a failing close must not mask the path that led here)
        except Exception:
            pass
