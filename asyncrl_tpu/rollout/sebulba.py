"""Sebulba host-actor runtime: Python actor threads pipelined against the
device learner (SURVEY.md §7.2 M3, §5.8b).

This is the TPU-native analogue of the reference's thread-per-actor +
actor→learner queue design (BASELINE.json:5; SURVEY.md §3.1): each
``ActorThread`` owns a slice of the env batch as a *host* env pool (the C++
``NativeEnvPool``, a gymnasium adapter, or a CPU-jitted functional env),
steps it with batched device inference, assembles time-major ``Rollout``
fragments in reusable numpy buffers, and puts them on a bounded queue. The
learner thread drains the queue, ``device_put``s fragments batch-sharded onto
the mesh, and steps the ``RolloutLearner``. Weight "publishing" back to
actors is a ``ParamStore`` swap of device arrays — no tensor ever leaves HBM
for the publish path; actors read the store at fragment boundaries
(staleness = learner updates between publishes, the queue bound gives the
pipelining the reference got from true asynchrony — SURVEY.md §7.3).

Failure handling (SURVEY.md §5.3): actor threads never raise into nowhere —
exceptions land in an error sink the trainer polls; dead actors are restarted
with a fresh env pool.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from asyncrl_tpu.envs.core import Environment, EnvSpec
from asyncrl_tpu.models.networks import (
    is_recurrent,
    reset_core,
    settle_core,
)
from asyncrl_tpu.ops import distributions
from asyncrl_tpu.ops.normalize import normalize
from asyncrl_tpu.obs import spans as span_names
from asyncrl_tpu.obs import trace
from asyncrl_tpu.rollout.buffer import Rollout, RolloutBuffer
from asyncrl_tpu.utils import faults


class ParamStore:
    """Latest published learner params (device arrays) + version counter.

    The reference's back-channel from learner to actors was shared memory /
    the actors re-reading updated weights (SURVEY.md §3.1); here it is a
    lock-guarded reference swap — actors fetch at fragment start, so a
    fragment is always generated under one consistent ``behaviour`` policy.
    """

    def __init__(
        self, params: Any, env_steps: int = 0, debug: bool | None = None
    ):
        self._lock = threading.Lock()
        self._params = params  # guarded-by: _lock
        self._version = 0  # guarded-by: _lock
        # Authoritative global frame counter, published by the trainer loop
        # alongside params. Epsilon/anneal schedules read THIS rather than
        # extrapolating from a single thread's frame count (which drifts
        # when threads progress unevenly or after an actor restart).
        self._env_steps = int(env_steps)  # guarded-by: _lock
        # §5.2b debug mode: seqlock-style write stamp around every mutation
        # (odd = publish in flight). With the lock held this is invisible;
        # if the lock discipline is ever broken, a concurrent get() observes
        # an odd or changed stamp and raises instead of serving a torn
        # params/version pair. Kept unconditionally cheap (two int adds);
        # the read-side verification only arms under ASYNCRL_DEBUG_SYNC=1.
        self._seq = 0  # guarded-by: _lock
        if debug is None:
            from asyncrl_tpu.utils.debug import sync_debug_enabled

            debug = sync_debug_enabled()
        self._debug = debug

    def publish(self, params: Any, env_steps: int | None = None) -> int:
        """Swap in new params; returns the new version number (the trainer
        records what update count each version was published at, for the
        param_lag metric)."""
        with self._lock:
            self._seq += 1
            self._params = params
            self._version += 1
            if env_steps is not None:
                self._env_steps = int(env_steps)
            self._seq += 1
            return self._version

    def _torn(self, s1: int, s2: int) -> bool:  # holds: _lock
        return s1 != s2 or s1 % 2 == 1

    def get(self) -> tuple[Any, int]:
        with self._lock:
            if self._debug:
                s1 = self._seq
                pair = (self._params, self._version)
                if self._torn(s1, self._seq):
                    raise RuntimeError(
                        "ParamStore torn read: a publish was observed mid-get"
                        " — the store's lock discipline is broken"
                    )
                return pair
            return self._params, self._version

    def env_steps(self) -> int:
        with self._lock:
            if self._debug:
                s1 = self._seq
                steps = self._env_steps
                if self._torn(s1, self._seq):
                    raise RuntimeError(
                        "ParamStore torn read: a publish was observed "
                        "mid-env_steps — the store's lock discipline is "
                        "broken"
                    )
                return steps
            return self._env_steps


class Fragment:
    """One host-side rollout fragment + the episode stats gathered while
    producing it. Arrays are owned copies, safe to retain. ``actor``/``seq``
    stamp the producing thread and its fragment counter for the §5.2b
    transport invariants (``FragmentSequenceChecker``)."""

    __slots__ = (
        "rollout", "return_sum", "length_sum", "count", "version",
        "actor", "gen", "seq", "lease",
    )

    def __init__(self, rollout: Rollout, return_sum: float, length_sum: float,
                 count: float, version: int, actor: int = 0, gen: int = 0,
                 seq: int = 0, lease=None):
        # lint: thread-shared-ok(queue hand-off: Queue.put/get is the happens-before edge; the producer only rebinds rollout before the put)
        self.rollout = rollout
        self.return_sum = return_sum
        self.length_sum = length_sum
        self.count = count
        self.version = version
        self.actor = actor
        self.gen = gen
        self.seq = seq
        # Staging-slab lease (rollout/staging.py) when the zero-copy path
        # is on: the rollout's arrays are views of the leased row; None on
        # the legacy copy path (the rollout owns its arrays).
        self.lease = lease


class FragmentSequenceChecker:
    """§5.2b debug invariant on the actor→learner transport: within one
    actor thread lifetime — keyed (actor, gen), where the trainer bumps
    ``gen`` on every restart — fragments must reach the learner gapless
    (seq 0,1,2,…), duplicate-free, and in production order; and per actor
    (across restarts) the behaviour-param version must never decrease.
    ``queue.Queue`` guarantees all of this today; the checker exists so a
    future transport swap or refactor that silently drops, duplicates, or
    reorders fragments fails loudly under ASYNCRL_DEBUG_SYNC=1 instead of
    corrupting training. Generations (not a reset) distinguish a restarted
    actor's fresh stream from its predecessor's fragments still queued.
    Single-consumer use (the trainer's learner loop)."""

    def __init__(self) -> None:
        self._next_seq: dict[tuple[int, int], int] = {}
        self._last_version: dict[int, int] = {}

    def check(self, fragment: "Fragment") -> None:
        key = (fragment.actor, fragment.gen)
        expect = self._next_seq.get(key, 0)
        if fragment.seq != expect:
            raise RuntimeError(
                f"fragment transport invariant broken: actor "
                f"{fragment.actor} (gen {fragment.gen}) delivered seq "
                f"{fragment.seq}, expected {expect} (fragments lost, "
                f"duplicated, or reordered)"
            )
        self._next_seq[key] = expect + 1
        last = self._last_version.get(fragment.actor, -1)
        if fragment.version < last:
            raise RuntimeError(
                f"fragment transport invariant broken: actor "
                f"{fragment.actor} param version went backwards "
                f"({last} -> {fragment.version})"
            )
        self._last_version[fragment.actor] = fragment.version


class JaxHostPool:
    """Host env pool wrapping a functional JAX env, stepped on the CPU
    backend. Lets every registry env drive the Sebulba path even without a
    native/gymnasium implementation (useful for tests and for pixel envs)."""

    def __init__(self, env: Environment, num_envs: int, seed: int = 0):
        self.num_envs = num_envs
        self.spec = env.spec
        self._seed = seed
        self._cpu = jax.devices("cpu")[0]
        with jax.default_device(self._cpu):
            self._init = jax.jit(lambda keys: _pool_init(env, keys))
            self._step = jax.jit(
                lambda state, actions, key: _pool_step(env, state, actions, key)
            )
            self._key = jax.random.PRNGKey(seed)
        self._state = None
        # Chaos layer (utils/faults.py): one handle fetch; None when
        # unarmed, so the hot step pays a single identity check. The owner
        # (ActorThread) wires ``fault_stop`` so an injected stall wakes
        # when the thread is stopped/abandoned.
        self._fault_step = faults.site("pool.step")
        self.fault_stop = None

    def reset(self) -> np.ndarray:  # thread-entry: env-pool@actor
        """Deterministic: restart the key stream from the construction
        seed, so a pool reused across evaluations replays the same initial
        states (matching the gymnasium adapter's reset(seed=...))."""
        with jax.default_device(self._cpu):
            self._key = jax.random.PRNGKey(self._seed)
            self._key, sub = jax.random.split(self._key)
            keys = jax.random.split(sub, self.num_envs)
            self._state, obs = self._init(keys)
        return np.asarray(obs)

    def step(self, actions: np.ndarray):  # thread-entry: env-pool@actor
        with jax.default_device(self._cpu):
            self._key, sub = jax.random.split(self._key)
            self._state, ts = self._step(self._state, jnp.asarray(actions), sub)
        out = (
            np.asarray(ts.obs),
            np.asarray(ts.reward),
            np.asarray(ts.terminated),
            np.asarray(ts.truncated),
        )
        if self._fault_step is not None:
            out = self._fault_step.fire(stop=self.fault_stop, payload=out)
        return out

    def disarm_faults(self) -> None:
        """Detach this pool from the chaos layer (evaluation pools step
        outside the supervised pipeline; see SebulbaTrainer.evaluate)."""
        self._fault_step = None

    def close(self) -> None:
        self._state = None


def _pool_init(env: Environment, keys):
    state = jax.vmap(env.init)(keys)
    return state, jax.vmap(env.observe)(state)


def _pool_step(env: Environment, state, actions, key):
    keys = jax.random.split(key, actions.shape[0])
    return jax.vmap(env.step)(state, actions, keys)


def _env_knobs_set(config) -> bool:
    """True when the config requests env-modifying knobs only the JAX
    registry implements (ALE semantics; opponent modes for the envs that
    HAVE an opponent — the pong_* knobs are inert on every other env and
    must not disqualify its native/gym pool)."""
    if config.frame_skip > 1 or config.sticky_actions > 0.0:
        return True
    return config.env_id in ("JaxPong-v0", "JaxPongPixels-v0") and (
        config.pong_opponent != "tracker"
        or config.pong_opponent_speed != 0.0
    )


def make_host_pool(config, num_envs: int, seed: int):
    """Pick the fastest available host pool for ``config.env_id``.

    Preference order for ``host_pool="auto"``: native C++ pool (GIL-releasing
    batched stepping) → gymnasium vector adapter → CPU-jitted JAX env.

    The ALE-semantics / opponent knobs (frame_skip, sticky_actions,
    pong_opponent*) exist only in the JAX registry: "auto" routes to the
    JAX pool when any is set, and an explicit native/gym pool choice
    REFUSES rather than silently training against the unmodified env.
    """
    kind = config.host_pool
    env_id = config.env_id

    if _env_knobs_set(config):
        if kind in ("native", "gym"):
            raise ValueError(
                f"host_pool={kind!r} cannot honor the configured env knobs "
                "(frame_skip/sticky_actions/pong_opponent*): they are "
                "implemented by the JAX env registry only. Use "
                "host_pool='jax' (or 'auto')."
            )
        kind = "jax"

    if kind in ("auto", "native"):
        from asyncrl_tpu.envs import native_pool

        if env_id in native_pool.NATIVE_ENV_IDS:
            try:
                return native_pool.NativeEnvPool(env_id, num_envs, seed=seed)
            # lint: broad-except-ok(auto mode falls through to the next pool backend; an explicit native choice re-raises)
            except Exception:
                if kind == "native":
                    raise
        elif kind == "native":
            raise KeyError(
                f"no native pool for {env_id!r}; have "
                f"{sorted(native_pool.NATIVE_ENV_IDS)}"
            )

    if kind in ("auto", "gym"):
        from asyncrl_tpu.envs import gym_adapter

        if gym_adapter.available(env_id):
            return gym_adapter.GymnasiumHostPool(env_id, num_envs, seed=seed)
        if kind == "gym":
            raise KeyError(f"no gymnasium env for {env_id!r}")

    if kind in ("auto", "jax"):
        from asyncrl_tpu.envs import registry

        return JaxHostPool(
            registry.make(env_id, config), num_envs, seed=seed
        )

    raise ValueError(
        f"unknown host_pool {kind!r}; expected auto|native|gym|jax"
    )


def inference_mode(config, model) -> str:
    """THE (config, model) -> inference-signature mapping — the single
    dispatch site shared by ``make_inference_fn`` (which builds the
    callable) and the ``InferenceServer`` (which must unpack the same
    arity): "ff" | "eps" | "rec" | "rec_eps"."""
    recurrent = is_recurrent(model)
    if config.algo == "qlearn":
        return "rec_eps" if recurrent else "eps"
    return "rec" if recurrent else "ff"


def make_inference_fn(model, spec: EnvSpec, config: Any) -> Callable:
    """Jitted batched action selection for ``model`` (a flax module; the
    signature follows ``inference_mode(config, model)``, so the wrong
    variant cannot be built). Feed-forward: (params, obs[B], key) ->
    (actions, behaviour_logp, new_key). Recurrent (LSTM) models:
    (params, obs, key, core, done_prev) -> (..., new_core) — the core stays
    ON DEVICE across calls (only actions/logp sync to host), and is reset
    where the PREVIOUS step ended an episode, mirroring the Anakin scan.

    With ``config.algo == "qlearn"`` the signature instead is
    (params, obs, key, eps[B]) — ε-greedy over the model's Q-values, the
    per-env ε appended onto dist_params exactly as the Anakin ``dist_extra``
    channel does (ops.distributions.EpsilonGreedy). Recurrent (DRQN) Q
    models combine both contracts: (params, obs, key, core, done_prev, eps)
    -> (actions, logp, key, core).

    With ``config.normalize_obs`` the ``params`` argument is the PUBLISHED
    BUNDLE ``(params, obs_stats)`` (what SebulbaTrainer puts in the
    ParamStore): observations normalize under the bundled stats before the
    model apply, so host actors act under exactly the learner's view."""
    dist = distributions.for_config(config, spec)
    if config.normalize_obs:
        raw_apply = model.apply

        def apply_fn(bundle, obs, *rest):
            params, stats = bundle
            return raw_apply(params, normalize(obs, stats), *rest)

    else:
        apply_fn = model.apply
    mode = inference_mode(config, model)

    if mode in ("eps", "rec_eps"):
        if mode == "rec_eps":

            @jax.jit
            def infer_eps_recurrent(params, obs, key, core, done_prev, eps):
                core = reset_core(core, done_prev)
                key, sub = jax.random.split(key)
                q, _, core = apply_fn(params, obs, core)
                dist_params = jnp.concatenate(
                    [q, eps[:, None].astype(q.dtype)], axis=-1
                )
                act_keys = jax.random.split(sub, obs.shape[0])
                actions = jax.vmap(dist.sample)(act_keys, dist_params)
                logp = dist.logp(dist_params, actions)
                return actions, logp, key, core

            return infer_eps_recurrent

        @jax.jit
        def infer_eps(params, obs, key, eps):
            key, sub = jax.random.split(key)
            q, _ = apply_fn(params, obs)
            dist_params = jnp.concatenate(
                [q, eps[:, None].astype(q.dtype)], axis=-1
            )
            act_keys = jax.random.split(sub, obs.shape[0])
            actions = jax.vmap(dist.sample)(act_keys, dist_params)
            logp = dist.logp(dist_params, actions)
            return actions, logp, key

        return infer_eps

    if mode == "rec":

        @jax.jit
        def infer_recurrent(params, obs, key, core, done_prev):
            core = reset_core(core, done_prev)
            key, sub = jax.random.split(key)
            dist_params, _, core = apply_fn(params, obs, core)
            act_keys = jax.random.split(sub, obs.shape[0])
            actions = jax.vmap(dist.sample)(act_keys, dist_params)
            logp = dist.logp(dist_params, actions)
            return actions, logp, key, core

        return infer_recurrent

    @jax.jit
    def infer(params, obs, key):
        key, sub = jax.random.split(key)
        dist_params, _ = apply_fn(params, obs)
        act_keys = jax.random.split(sub, obs.shape[0])
        actions = jax.vmap(dist.sample)(act_keys, dist_params)
        logp = dist.logp(dist_params, actions)
        return actions, logp, key

    return infer


class ActorThread(threading.Thread):
    """One host actor: a pool slice + the fragment production loop.

    The reference's ``ActorWorker.run`` (BASELINE.json:5) stepped ONE env per
    thread; here each thread steps a *batch* through a pool (the C++ engine
    releases the GIL during stepping, so threads overlap env physics with
    device inference — SURVEY.md §7.3 "host↔device throughput").
    """

    def __init__(
        self,
        index: int,
        pool,
        inference_fn: Callable,
        store: ParamStore,
        out_queue: "queue.Queue[Fragment]",
        unroll_len: int,
        seed: int,
        stop_event: threading.Event,
        errors: "queue.Queue[tuple[int, int, BaseException]]",
        device=None,
        initial_core: Callable[[int], Any] | None = None,
        epsilon_fn: Callable[[int], np.ndarray] | None = None,
        track_returns: bool = False,
        return_discount: float = 0.0,
        generation: int = 0,
        staging=None,
    ):
        super().__init__(name=f"actor-{index}", daemon=True)
        self.index = index
        # Restart counter for this actor slot (stamped into fragments so
        # the §5.2b checker can tell a restarted thread's fresh seq stream
        # from its predecessor's fragments still sitting in the queue).
        self.generation = generation
        self.pool = pool
        self.inference_fn = inference_fn
        self.store = store
        self.out_queue = out_queue
        self.unroll_len = unroll_len
        self.seed = seed
        self.stop_event = stop_event
        self.errors = errors
        # Recurrent policies: builds the initial (c, h) carry for B envs;
        # None for feed-forward.
        self.initial_core = initial_core
        # Q-learning family: maps this thread's cumulative env frames -> the
        # per-env behaviour ε vector [B] (the A3C paper's per-thread ε,
        # annealed). None for the policy-gradient algos.
        self.epsilon_fn = epsilon_fn
        # normalize_returns: when ``track_returns`` (the SAME predicate the
        # learner keys its stats on — a discount of 0 must degrade to
        # reward-std tracking, not disagree), record the per-env
        # discounted-return stream G = discount*G + r (RAW rewards; the
        # trainer scales the stream together with the rewards).
        self.track_returns = track_returns
        self.return_discount = return_discount
        # ``jax.default_device`` is thread-local, so a device pin must be
        # re-established INSIDE the thread: the cpu_async backend pins actors
        # to host CPU (never touching an attached accelerator); sebulba
        # leaves None (batched inference on the accelerator is the point).
        self.device = device
        # Per-thread retirement signal: the watchdog abandons a HUNG thread
        # through this (the cohort stop event would take every healthy
        # sibling down with it), and a deliberate elastic scale-down
        # (runtime/elastic.py) retires the highest slot through the SAME
        # event — one drain-clean exit path, two callers. An abandoned
        # thread exits at its next check and its late error/fragment
        # output is discarded.
        self.abandon = threading.Event()
        # Progress stamp for the trainer's heartbeat watchdog: refreshed
        # every iteration of the production loop (including the bounded-
        # queue retry loop — a backpressured actor is alive, not hung).
        # lint: thread-shared-ok(GIL-atomic float stamp; the watchdog reads staleness only and refreshes after server outages)
        self.heartbeat = time.monotonic()
        # queue.Full retries observed on the fragment handoff (exported via
        # the metrics window as ``queue_backpressure``): how often actors
        # out-ran the learner+queue. Plain int under the GIL; the trainer
        # only ever reads it.
        self.backpressure = 0  # lint: thread-shared-ok(GIL-atomic int; single-writer, metrics-only reader)
        # Zero-copy staging ring (rollout/staging.py); None = legacy
        # copy-on-emit path. The actor leases one slab row per fragment
        # and writes transitions straight into it; ``_open_lease`` is the
        # not-yet-queued lease the supervisor voids if this thread dies.
        # Under the elastic runtime this is a RingSwapHolder, not a bare
        # StagingRing — same acquire contract, but a mid-wait ring swap
        # wakes the acquire and retries on the new ring.
        self.staging = staging
        # lint: thread-shared-ok(supervisor reads it only after this thread is dead or abandoned; StagingRing.void re-checks generations under its lock)
        self._open_lease = None
        # Chaos layer handles (None when unarmed — hot loop pays one
        # identity check per iteration; utils/faults.py).
        self._fault_step = faults.site("actor.step")
        self._fault_put = faults.site("actor.queue_put")
        # An injected pool.step stall must wake when THIS thread is
        # stopped/abandoned (a chaos stall has to stay abandonable, like
        # the wedged engine it models); harmless no-op on pools without an
        # armed site.
        # lint: thread-shared-ok(written before Thread.start: publication happens-before the run loop)
        self.pool.fault_stop = self._stopped

    def _stopped(self) -> bool:
        """Cohort shutdown OR individual watchdog retirement."""
        return self.stop_event.is_set() or self.abandon.is_set()

    def run(self) -> None:  # thread-entry: actor
        try:
            if self.device is not None:
                with jax.default_device(self.device):
                    self._run()
            else:
                self._run()
        # lint: broad-except-ok(thread boundary: the failure is delivered to the supervisor's error sink, never swallowed — §5.3)
        except BaseException as e:
            # ...unless the run is shutting down (or the watchdog already
            # retired this thread): an inference call (or server client)
            # interrupted by stop()/abandonment is not a failure. The
            # generation stamp lets the supervisor drop an error from a
            # thread it ALREADY replaced (a wedged actor can both trip the
            # watchdog and deliver its exception — one failure, not two).
            if not self._stopped():
                self.errors.put((self.index, self.generation, e))
        finally:
            close = getattr(self.pool, "close", None)
            if close is not None:
                try:
                    close()
                # lint: broad-except-ok(best-effort teardown on a dying thread; the primary failure is already reported above)
                except Exception:
                    pass

    def _heartbeat(self) -> None:
        self.heartbeat = time.monotonic()

    def _run(self) -> None:
        pool = self.pool
        T, B = self.unroll_len, pool.num_envs
        obs = pool.reset()
        key = jax.random.PRNGKey(self.seed)

        track_returns = self.track_returns
        ring = self.staging
        buffer = None
        if ring is None:
            buffer = RolloutBuffer(
                T, B, obs.shape[1:], obs.dtype, track_returns=track_returns
            )
        disc_g = np.zeros((B,), np.float32)
        running_return = np.zeros((B,), np.float64)
        running_length = np.zeros((B,), np.float64)
        core = self.initial_core(B) if self.initial_core else None
        done_prev = np.zeros((B,), bool)
        frames = 0  # this thread's cumulative env frames (for epsilon_fn)
        seq = 0  # fragment counter (§5.2b transport invariant stamp)

        while not self._stopped():
            lease = None
            if ring is not None:
                # Lease one slab row for this fragment. A blocked acquire
                # (ring under pressure) refreshes the heartbeat: a back-
                # pressured actor is alive, not hung.
                with trace.span(span_names.ACTOR_LEASE_WAIT):
                    lease = ring.acquire(
                        stop=self._stopped, on_wait=self._heartbeat
                    )
                if lease is None:
                    break  # stopped/abandoned while waiting
                # lint: protocol-ok(sanctioned hand-off: the supervisor voids _open_lease when it retires this thread — the one escape the lease protocol is built around)
                self._open_lease = lease
                buffer = lease.buffer
            params, version = self.store.get()
            # ε is fragment-constant (same anneal granularity as Anakin).
            # Kept as numpy: it rides the same device dispatch as obs (no
            # extra round trip), and the inference server's slab coalescer
            # packs host arrays without a per-client transfer.
            eps = (
                np.asarray(self.epsilon_fn(frames))
                if self.epsilon_fn is not None
                else None
            )
            ret_sum = 0.0
            len_sum = 0.0
            count = 0.0
            # Fragment-initial core AFTER the pending episode-boundary reset
            # (the jitted inference applies the reset; mirror it here so the
            # recorded carry is the one the fragment actually starts from).
            if core is not None:
                core = settle_core(reset_core(core, jnp.asarray(done_prev)))
                done_prev = np.zeros((B,), bool)
                init_core = jax.tree.map(np.asarray, core)
            while not buffer.full:
                self.heartbeat = time.monotonic()
                if self._fault_step is not None:
                    self._fault_step.fire(stop=self._stopped)
                with trace.span(span_names.ACTOR_INFERENCE):
                    if core is not None and eps is not None:
                        actions_d, logp_d, key, core = self.inference_fn(
                            params, obs, key, core, done_prev, eps
                        )
                    elif core is not None:
                        actions_d, logp_d, key, core = self.inference_fn(
                            params, obs, key, core, done_prev
                        )
                    elif eps is not None:
                        actions_d, logp_d, key = self.inference_fn(
                            params, obs, key, eps
                        )
                    else:
                        actions_d, logp_d, key = self.inference_fn(
                            params, obs, key
                        )
                    # ONE batched device→host sync for both leaves (two
                    # np.asarray calls were two round trips on a high-
                    # latency link); numpy passes through untouched (server
                    # clients already hand back host arrays).
                    actions, logp = jax.device_get((actions_d, logp_d))
                prev_obs = obs
                with trace.span(span_names.ACTOR_ENV_STEP):
                    obs, rew, term, trunc = pool.step(actions)
                if track_returns:
                    disc_g = self.return_discount * disc_g + rew
                    buffer.append(
                        prev_obs, actions, logp, rew, term,
                        trunc, disc_return=disc_g,
                    )
                    disc_g = np.where(
                        np.logical_or(term, trunc), 0.0, disc_g
                    ).astype(np.float32)
                else:
                    buffer.append(
                        prev_obs, actions, logp, rew, term, trunc
                    )
                done_prev = np.logical_or(term, trunc)
                frames += B

                running_return += rew
                running_length += 1.0
                done = done_prev
                if done.any():
                    ret_sum += float(running_return[done].sum())
                    len_sum += float(running_length[done].sum())
                    count += float(done.sum())
                    running_return[done] = 0.0
                    running_length[done] = 0.0

            rollout = buffer.emit(bootstrap_obs=obs)
            if core is not None:
                if lease is not None:
                    rollout = lease.write_init_core(rollout, init_core)
                else:
                    rollout = rollout.replace(init_core=init_core)
            fragment = Fragment(
                rollout,
                ret_sum, len_sum, count, version,
                actor=self.index, gen=self.generation, seq=seq,
                lease=lease,
            )
            seq += 1
            if self._fault_put is not None:
                corrupted = self._fault_put.fire(
                    stop=self._stopped, payload=fragment.rollout.rewards
                )
                if corrupted is not fragment.rollout.rewards:
                    if lease is not None:
                        # Slab path: the drain reads the SLAB, so the
                        # injected damage must land there (write-through
                        # the view) — a detached copy would silently
                        # un-corrupt the payload.
                        np.copyto(fragment.rollout.rewards, corrupted)
                    else:
                        fragment.rollout = fragment.rollout.replace(
                            rewards=corrupted
                        )
            if lease is not None:
                # Content-complete: raises StaleLeaseError if the
                # supervisor voided this lease (thread already retired) —
                # caught by run()'s stopped-thread swallow.
                lease.commit()
            # Bounded put that stays responsive to shutdown (and to the
            # watchdog retiring this thread mid-backpressure). The span
            # covers the retry loop: its duration IS the backpressure
            # wait (a free queue slot makes it ~one put's epsilon).
            with trace.span(span_names.ACTOR_QUEUE_PUT):
                while not self._stopped():
                    try:
                        self.out_queue.put(fragment, timeout=0.1)
                        self._open_lease = None
                        break
                    except queue.Full:
                        self.backpressure += 1
                        self.heartbeat = time.monotonic()
                        continue
