"""Config system: frozen dataclasses + a tiny ``key=value`` override parser.

The reference family uses per-script argparse (SURVEY.md §5.6); here every
workload is a frozen-dataclass preset (``asyncrl_tpu.configs``) and the CLI
applies ``key=value`` overrides — no heavyweight config dependency.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping


@dataclasses.dataclass(frozen=True)
class Config:
    """Training configuration for one workload.

    Mirrors the knobs implied by the reference's five benchmark configs
    (BASELINE.json:6-12): env selection, actor parallelism, algorithm family,
    and optimization hyperparameters.
    """

    # --- workload ---
    env_id: str = "CartPole-v1"
    algo: str = "a3c"  # "a3c" | "impala" | "ppo" | "qlearn"
    backend: str = "tpu"  # "tpu" (anakin) | "sebulba" | "cpu_async"

    # --- rollout geometry ---
    # Global env batch across the whole mesh (the reference's "actors");
    # must divide evenly by the dp axis size — each device runs
    # num_envs / dp of them.
    num_envs: int = 64
    unroll_len: int = 32  # t_max: steps per rollout fragment
    total_env_steps: int = 500_000

    # --- model ---
    torso: str = "mlp"  # "mlp" | "nature_cnn" | "impala_cnn"
    hidden_sizes: tuple[int, ...] = (64, 64)
    channels: tuple[int, ...] = (16, 32, 32)
    # Recurrent core after the torso: "ff" (none) or "lstm" (the A3C/IMPALA
    # LSTM-agent variant; all backends). Core state rides the rollout scan
    # carry (Anakin) or stays device-resident across host actor steps
    # (sebulba/cpu_async), resetting at episode boundaries.
    core: str = "ff"
    core_size: int = 256
    # A token-level sequence policy in place of torso + core: the name of a
    # model-shape record in ``models/kimi_linear.py SHAPES`` (published
    # widths and the cut to one chip's share). "" = torso + core above.
    seq_model: str = ""
    # JaxTokenTask-v0 (envs/token_task.py): (vocab, min_len, max_len,
    # min_prompt, max_prompt) -- the vocabulary, the range an episode's
    # length is drawn from (log-uniform) and the range of its prompt's.
    token_task: tuple[int, ...] = (64, 4, 32, 1, 2)

    # --- optimization ---
    # "adam" (the reference's Learner optimizer, BASELINE.json:5) or
    # "rmsprop" — the A3C-paper family default (SURVEY.md:143): RMSProp
    # whose statistics the paper's async threads SHARED. Here sharing is
    # by construction: gradients psum over the mesh into one optimizer
    # state, which is exactly the shared-statistics recipe without races.
    optimizer: str = "adam"
    learning_rate: float = 3e-4
    # "constant", or "linear": anneal from learning_rate to 0 over the run's
    # total_env_steps (the IMPALA recipe for its Atari/DMLab suites).
    lr_schedule: str = "constant"
    adam_eps: float = 1e-8
    # RMSProp knobs (A3C paper, Mnih et al. 2016 §8: decay 0.99, eps 0.1).
    rmsprop_decay: float = 0.99
    rmsprop_eps: float = 0.1
    max_grad_norm: float = 0.5
    gamma: float = 0.99
    gae_lambda: float = 0.95

    # --- loss coefficients ---
    value_coef: float = 0.5
    entropy_coef: float = 0.01
    # Entropy annealing (the A3C-family exploration schedule): with
    # entropy_anneal_steps > 0 the effective coefficient ramps linearly
    # from entropy_coef to entropy_coef_final over that many learner
    # updates, then holds. Early exploration pressure, late policy
    # sharpening — computed INSIDE the jitted step from update_step, so
    # fused multi-update calls see per-update values. 0 = constant coef.
    entropy_coef_final: float = 0.0
    entropy_anneal_steps: int = 0
    # Reward scaling applied to the learner's view of rewards (episode-return
    # metrics stay raw). Essential for continuous-control workloads whose raw
    # returns are in the hundreds/thousands (e.g. Pendulum ≈ −1200): without
    # it the value loss dwarfs the policy gradient under grad-norm clipping.
    # Brax's PPO does the same for Ant/Humanoid (BASELINE.json:11).
    reward_scale: float = 1.0
    # Per-step living cost subtracted from the LEARNER's reward view before
    # reward_scale (episode-return metrics and eval stay raw, same contract
    # as reward_scale). The survival-vs-decisiveness shaping knob: a policy
    # that can defend forever but rarely converts (the measured JaxPong
    # plateau — perfect defense, 3000-step truncated rallies,
    # scripts/pong_diagnose.py) gets an explicit gradient toward ENDING
    # rallies. Potential-free shaping: it changes the training objective,
    # so the headline metric must always be the raw eval return.
    step_cost: float = 0.0
    # Running observation normalization (the VecNormalize / Brax-PPO recipe,
    # ops/normalize.py): stats ride the train state, update inside the
    # jitted step (psum'd over the mesh), and normalize the actor's,
    # learner's, and eval's model inputs alike. On host backends the stats
    # publish to actors bundled with the params.
    normalize_obs: bool = False
    # Return-based reward scaling (VecNormalize's other half / the Brax
    # recipe): rewards divide by the running std of the per-env discounted
    # return before the loss — an adaptive, workload-independent
    # reward_scale. Episode-return metrics stay raw. All backends (host
    # actors record the discounted-return stream into each fragment).
    normalize_returns: bool = False

    # --- IMPALA / V-trace ---
    vtrace_rho_clip: float = 1.0
    vtrace_c_clip: float = 1.0
    actor_staleness: int = 1  # learner updates between actor weight refreshes

    # --- PPO ---
    ppo_clip_eps: float = 0.2
    ppo_epochs: int = 4
    ppo_minibatches: int = 4

    # --- qlearn (async n-step Q-learning) ---
    # Double-Q bootstrap: argmax under the online net, value under the
    # target net (the stale actor_params copy; actor_staleness is the
    # target-update period for this algo).
    double_q: bool = True
    # Per-env final ε ladder (Ape-X form): eps_base ** (1 + eps_alpha * i/(N-1)),
    # annealed from 1.0 over the first exploration_steps env frames.
    eps_base: float = 0.4
    eps_alpha: float = 7.0
    exploration_steps: int = 100_000
    # Dueling Q decomposition (Wang et al. 2016): separate value/advantage
    # streams, Q = V + A - mean(A).
    dueling: bool = False
    # Huber TD loss delta (the DQN default is 1.0); 0 = plain squared TD.
    # Pair with normalize_returns or reward_scale: Huber caps the TD
    # gradient at delta, so unscaled returns-sized TDs learn very slowly
    # (DQN uses it WITH reward clipping).
    huber_delta: float = 0.0

    # --- ALE-semantics knobs (JAX-native env registry; SURVEY.md §3.3) ---
    # Action repeat: each env step plays the action frame_skip times
    # (rewards summed, frozen at episode end). 1 = off.
    frame_skip: int = 1
    # Pixel envs + frame_skip: max-pool the last two RAW frames of each
    # window (the ALE flicker recipe; envs/pixels.py). Off by default —
    # the built-in renderers never flicker, so pooling is a bit-identical
    # second render; enable for strict ALE-preprocessing parity runs.
    frame_pool: bool = False
    # Machado et al. 2018 sticky actions: probability the env repeats the
    # previous action instead of the agent's. ALE-standard value 0.25;
    # 0 = off.
    sticky_actions: float = 0.0
    # JaxPong opponent (envs/pong.py): "tracker" follows the ball's current
    # y (rate-limited; beatable by persistent spin), "predictive"
    # extrapolates the ball's intercept with wall bounces while it
    # approaches — a strictly harder opponent that punishes the lazy
    # constant-spin exploit. Speed 0.0 = the mode's tuned default.
    pong_opponent: str = "tracker"
    pong_opponent_speed: float = 0.0
    # JaxPong episode truncation cap, in AGENT DECISIONS (the registry
    # scales by frame_skip so the underlying core-step cap tracks ALE's
    # raw-frame accounting). Default 3000 is ~9x TIGHTER than ALE's
    # PongNoFrameskip-v4 semantics (108,000 frames = 27,000 skip-4
    # decisions, envs/pong.py ALE_MAX_STEPS) — a deliberate,
    # strictly-harder choice: the 18.0 target must be met at a scoring
    # RATE, not by letting games run long. Set 27000 for ALE-faithful
    # evaluation; scripts/eval_caps.py records numbers under both caps.
    pong_max_steps: int = 3000
    # Self-play (Anakin backend, duel envs like JaxPongDuel-v0): the rival
    # paddle is driven by a FROZEN SNAPSHOT of the agent's own policy,
    # refreshed from the live params every selfplay_refresh updates — the
    # ladder alternative to scripted opponents. Greedy evaluation still
    # runs against the calibrated scripted opponent (the duel env's
    # single-action step), so the 18.0-bar metric is unchanged.
    selfplay: bool = False
    selfplay_refresh: int = 200

    # --- parallelism ---
    mesh_shape: tuple[int, ...] = (-1,)  # -1: all local devices on axis "dp"
    mesh_axes: tuple[str, ...] = ("dp",)

    # --- sebulba / cpu_async host backends ---
    actor_threads: int = 2  # host actor threads; each owns num_envs/threads
    queue_capacity: int = 0  # actor→learner queue bound; 0 = 2*actor_threads
    host_pool: str = "auto"  # "auto" | "native" | "gym" | "jax"
    # Shared inference server (rollout/inference_server.py): coalesce every
    # actor thread's action-selection query into ONE batched device call per
    # env step (the podracer inference-thread design). Pays off with many
    # threads and/or a high-latency device link; off = per-thread dispatch.
    inference_server: bool = False
    # --- policy serving (asyncrl_tpu/serve/; applies when the shared
    # server is on) ---
    # Serve core vs legacy coalescing server: with serve=True the shared
    # server is the continuous-batching ServeCore (deadline-based
    # admission, SLO gate, multi-policy router, generation-stamped
    # zero-drain weight swaps); False keeps the legacy fixed-round
    # InferenceServer for A/B measurement (scripts/serve_smoke.sh).
    # ASYNCRL_SERVE (when set) wins over this flag, like ASYNCRL_TRACE.
    serve: bool = True
    # Admission deadline budget per request, ms: a batch dispatches when
    # every registered client of its policy has a request in (slab full)
    # or when the OLDEST admitted request has waited this long (deadline
    # flush, partial batch) — whichever comes first.
    serve_deadline_ms: float = 2.0
    # SLO target on the rolling p95 serve latency, ms: when breached, the
    # admission gate sheds (serve_shed=True) or backpressures new
    # requests until p95 recovers (the server_overload counter records
    # breaches; serve_latency_ms_p50/p95/p99 export per window). 0 = off.
    serve_slo_p95_ms: float = 0.0
    # Hard cap on admitted-but-unfinished requests (the gate blocks — or
    # sheds, under serve_shed — at the cap). 0 = uncapped.
    serve_max_inflight: int = 0
    # Overload response: True = refuse (RequestShed) at the admission
    # gate; False = backpressure (block the client until capacity frees).
    # Training keeps the default False — actor threads must slow down,
    # not crash; shed mode is for external-traffic front-ends that own a
    # retry policy.
    serve_shed: bool = False
    # --- external gateway (asyncrl_tpu/serve/gateway.py) ---
    # Wire frontier over the serve core: /v1/act + /v1/evaluate on a
    # versioned JSON protocol with deadline propagation, per-tenant SLO
    # classes, and graceful degradation. 0 = off — NOTHING constructs
    # (zero threads, zero registry keys, loss-bit-identical; the
    # introspect=False discipline, pinned by scripts/gateway_smoke.sh
    # act 1); -1 = bind an OS-assigned ephemeral port (tests/smokes read
    # it back from the handle), positive = bind exactly there. Requires
    # inference_server=True and the serve core (the gateway routes
    # through ServeCore's continuous batch).
    gateway_port: int = 0
    # Bind host for the gateway's socket; loopback by default — exposing
    # beyond the host is a deliberate operator decision.
    # ASYNCRL_GATEWAY_HOST wins when set (obs_http_host has the matching
    # ASYNCRL_OBS_HOST knob).
    gateway_host: str = "127.0.0.1"
    # Default end-to-end budget for requests that carry no X-Deadline-Ms
    # header; the remaining budget propagates into the serve core's
    # batch-fill deadline, and a request that cannot make it is shed
    # before it occupies a batch slot.
    gateway_deadline_ms: float = 1000.0
    # Per-tenant SLO classes: "name:mode[:k=v,...]" ';'-separated
    # (serve/gateway.py grammar; modes shed|stale|fallback, options
    # p95_ms, inflight, rps, burst, fallback). Empty = one permissive
    # shed-mode class every tenant folds into. The "*" class catches
    # unmatched tenant ids.
    gateway_tenant_spec: str = ""
    # Zero-copy overlapped actor→learner data path (rollout/staging.py):
    # actors write fragments straight into preallocated pinned staging
    # slabs (no per-fragment emit copy, no per-drain np.stack) and the
    # drain thread transfers slab i+1 while the learner computes update i
    # (double-buffered H2D). Off = the legacy copy-and-stack path, kept for
    # A/B comparison (tests/test_perf_smoke.py) and as the paranoia fallback;
    # both paths are bit-identical on fragment content (tests/test_staging).
    overlap_h2d: bool = True
    # Staging-ring depth in SLABS (each slab holds updates_per_call
    # fragments). 0 = auto: enough rows to cover the fragment queue bound +
    # one open lease per actor + a filling and an in-flight slab, so
    # steady-state acquisition never blocks (blocking is counted in the
    # slab_reuse_waits metric either way).
    staging_slabs: int = 0
    # HBM rollout hand-off (rollout/device_queue.py): bound the device-
    # resident fragments between H2D and the consuming update behind a
    # generation/lease ledger (the staging-ring discipline one tier
    # down), and give the replay ring a zero-copy (by-reference) publish
    # path. "auto" resolves at Sebulba trainer construction: on where
    # the default backend is a TPU (fragments live in HBM), off
    # elsewhere (CPU device arrays alias host memory — there is no HBM
    # tier to manage, and host staging already owns the hand-off).
    # "on"/"off" force it either way; the off path constructs NOTHING
    # (the elastic/introspect off-is-bit-identical discipline).
    device_queue: str = "auto"
    # Queue depth in fragments; 2 = the double-buffer (slot B's transfer
    # overlaps slot A's update). Must be >= 2 when the queue is on.
    device_queue_slots: int = 2

    # --- device-resident replay (learn/replay.py; host backends) ---
    # IMPACT-style sample reuse (arXiv:1912.00167): a circular ring of
    # the last N consumed slabs kept in DEVICE memory, re-fed to the
    # learner between fresh fragments so learner FLOPs stop being
    # rate-limited by actor throughput (learner_stall_frac -> ~0). The
    # ring reuses the staging-ring generation/lease discipline: rows are
    # generation-stamped, eviction is oldest-generation, and a zombie
    # read after eviction/quarantine raises instead of returning a newer
    # slab's rows. 0 = off — bit-identical to the pre-replay program
    # (the introspect=False discipline; pinned by tests/test_replay.py
    # and scripts/replay_smoke.sh). Requires algo="impala" (the
    # importance-ratio anchoring below is V-trace-specific),
    # updates_per_call=1, core="ff", and normalize_obs/normalize_returns
    # off (the jitted step folds every consumed fragment into the
    # running stats and cannot tell fresh from replayed — reuse would
    # bias them). ASYNCRL_REPLAY (when set) wins, like ASYNCRL_SERVE.
    replay_slabs: int = 0
    # Total SGD passes per drained fragment when replay is on: 1 fresh
    # pass + (replay_passes - 1) replayed slabs sampled least-reused-
    # first from the ring. 2x-3x is the IMPACT-recommended regime.
    replay_passes: int = 2
    # Learner updates between clipped-target-network refreshes: the
    # target's log-probs anchor the importance ratio on every replay-
    # mode update, so a slab reused across many updates keeps a bounded
    # correction even as its behaviour policy goes stale.
    target_update_period: int = 100
    # Cap on the target-anchored importance ratio: the effective
    # behaviour log-prob is floored at log pi_target - log(clip), so
    # rho = pi/mu never exceeds clip * pi/pi_target. Must be >= 1
    # (a cap below 1 would down-weight perfectly on-policy data).
    replay_rho_clip: float = 2.0

    # --- elastic runtime (asyncrl_tpu/runtime/elastic.py; host backends) ---
    # Signal-driven fleet scaling: an ElasticController evaluated at each
    # window close grows/shrinks the actor fleet (and resizes the staging
    # ring through a checkpoint-consistent swap) from the signals the obs
    # stack already exports — learner_stall_frac + span blame for
    # scale-up, queue-backpressure/admission/staleness pressure for
    # scale-down — behind hysteresis and a post-action cooldown. Off by
    # default; ASYNCRL_ELASTIC (when set) wins over this flag, like
    # ASYNCRL_SERVE. Requires updates_per_call=1 (the in-flight ring swap
    # does not compose with fused multi-fragment slabs yet) and, when a
    # shared server is on, the serve core (the legacy InferenceServer's
    # client set is fixed-shape). elastic=False is bit-identical on
    # losses and leaks zero elastic keys into the window snapshot
    # (pinned by scripts/elastic_smoke.sh and tests/test_elastic.py).
    elastic: bool = False
    # Fleet bounds: the controller (and any scripted chaos scale event)
    # never moves the live actor count outside [min, max].
    elastic_min_actors: int = 1
    # 0 = auto: 2x the configured actor_threads.
    elastic_max_actors: int = 0
    # Windows the controller stays quiet after each of its own scale
    # actions (scripted chaos events bypass the cooldown; bounds always
    # apply). Lets the pipeline re-equilibrate before the next verdict.
    elastic_cooldown_windows: int = 2
    # Scale-up trigger: learner_stall_frac must exceed this for the
    # hysteresis run (and the span blame, when tracing is armed, must
    # point at the actors). 1.0 disables the organic up signal — the
    # stall fraction is capped at exactly 1.0 — leaving only scripted
    # chaos events (how the smoke/tests pin deterministic fleets).
    elastic_up_stall_frac: float = 0.5
    # Scale-up trigger #2: the external gateway's shed counters
    # (admission 429s + wire-deadline sheds) must grow by at least this
    # much in a window — client pain, complementary to the learner-pain
    # stall signal and deliberately NOT subject to the span-blame veto.
    # 0 disables (the default: runs without a gateway never see it).
    elastic_up_shed_rate: float = 0.0
    # Scale-down trigger: the queue_backpressure counter must grow by at
    # least this much in a window (actors out-ran the learner). 0
    # disables the organic backpressure signal.
    elastic_down_backpressure: float = 1.0
    # Scale-down trigger #2: the serve admission gate's overload+shed
    # counters must grow by at least this much in a window (actors
    # out-ran the server). 0 disables — every organic signal has a
    # disable knob so identity A/B runs can pin the controller
    # armed-but-quiet (the elastic_smoke.sh discipline).
    elastic_down_admission: float = 1.0

    # --- durable runs (asyncrl_tpu/runtime/durability.py; host backends) ---
    # Preemption-safe drain grace budget, seconds: with > 0, train()
    # installs SIGTERM/SIGINT handlers (main thread only; restored on
    # exit) that convert a platform kill into a graceful drain — serve
    # admissions close, staging leases drain through the void/commit
    # path, the partial metrics window and flight recorder flush
    # (reason=preempt), and ONE final checkpoint carrying the full run
    # state lands — then the process exits with the distinct
    # EXIT_DRAINED code. A deadline watchdog hard-kills past the grace
    # (EXIT_DEADLINE); a second signal hard-kills immediately. 0
    # disables the handler (the legacy KeyboardInterrupt path).
    # ASYNCRL_DRAIN_GRACE_S wins when set.
    drain_grace_s: float = 30.0
    # Crash-consistent resume: restore the FULL run state recorded in the
    # checkpoint metadata (elastic fleet size, staleness ledger rebased
    # onto the restored update count, actor-PRNG cursor, health-monitor
    # window cursor) on top of the learner-state auto-resume that
    # checkpoint_dir already provides — counters stay monotone across
    # the boundary and timeseries.jsonl appends a new marked segment.
    # ASYNCRL_RESUME wins when set.
    resume: bool = False
    # Automatic divergence rollback: with > 0, a RollbackPolicy evaluated
    # at each window close (next to the health detectors) reacts to the
    # critical learning-health events (nonfinite_loss, grad_explosion,
    # entropy_collapse): the learner's device-side NaN-guard skips every
    # poisoned update (params/opt state/stats hold; the nonfinite_skips
    # metric counts), in-flight fragments quarantine back to the staging
    # ring, and after this many CONSECUTIVE bad windows the run rolls
    # back to the last-good checkpoint (fallback restore, fresh PRNG
    # fold, cooldown). 0 disables (the default — bit-identical to the
    # pre-rollback program). Requires checkpoint_dir (something to roll
    # back to).
    rollback_bad_windows: int = 0
    # Bound on rollbacks per run: one more bad streak past this many
    # restores aborts with forensics instead of looping forever on a
    # run that re-diverges deterministically.
    rollback_max_attempts: int = 2

    # --- fault tolerance (host backends; utils/faults.py) ---
    # Heartbeat watchdog: an actor thread or the inference server whose
    # progress stamp is older than this many seconds is declared hung and
    # restarted exactly like a crashed one (counted in the same restart-
    # storm window). 0 disables the watchdog — the safe default, because a
    # first-fragment jit compile can legitimately take minutes on a slow
    # host; enable with a margin over your measured step time.
    stall_timeout_s: float = 0.0
    # Deterministic fault injection, the ASYNCRL_FAULTS grammar
    # ("site:kind:prob:seed[:k=v,...]", ';'-separated; see utils/faults.py).
    # Empty = unarmed (every injection site is a no-op identity check).
    # The env var takes precedence when both are set.
    fault_spec: str = ""

    # --- observability (asyncrl_tpu/obs/; host backends) ---
    # Pipeline tracing: per-thread span ring buffers across the actor/
    # server/staging/learner stages, Perfetto-exportable, with the flight
    # recorder armed alongside (crash-time span dumps into run_dir).
    # ASYNCRL_TRACE (when set) wins over this flag, like ASYNCRL_FAULTS.
    # Off = the no-op fast path (one None check per span site).
    trace: bool = False
    # Per-thread span ring capacity (drop-oldest on overflow; overflow is
    # counted in the trace_dropped_spans window metric).
    trace_ring: int = 4096
    # Flight recorder lookback: seconds of spans dumped on a fault,
    # watchdog retirement, or supervisor restart.
    trace_window_s: float = 10.0
    # Observability output directory (trace exports, flightrec-*.json,
    # timeseries.jsonl). Empty = runs/<env>-<algo>-s<seed>-<stamp>-<pid>
    # when tracing is on; ASYNCRL_RUN_DIR overrides.
    run_dir: str = ""
    # Request hop journals (obs/requests.py): per-request wire tracing
    # with deadline-budget accounting across gateway -> fleet -> replica
    # -> batch. ASYNCRL_REQUEST_TRACE (when set) wins, like ASYNCRL_TRACE.
    # Off = begin() returns None; every hook is one thread-local read.
    request_trace: bool = False
    # Persistence budget: at most this many journals append to
    # runs/<run>/requests.jsonl (past it, the request_journals_capped
    # counter moves and the file stays fixed size).
    request_journal_cap: int = 512
    # Sampling bar: served (200) journals persist only when latency_ms
    # reaches this; <= 0 persists every finished journal. Non-200s always
    # persist (a shed IS the story).
    request_sample_slow_ms: float = 0.0
    # --- run-health telemetry (obs/timeseries.py, obs/health.py,
    # obs/http.py) ---
    # Exposition endpoint port (/metrics, /healthz, /timeseries): 0 = off
    # (the default — zero threads, zero per-window cost beyond the one
    # shared registry snapshot), -1 = bind an OS-assigned ephemeral port
    # (tests/smoke harnesses read it back from the handle), positive =
    # bind exactly there (127.0.0.1). ASYNCRL_OBS_PORT wins when set.
    obs_http_port: int = 0
    # Bind host for the exposition endpoint (obs/http.py always took a
    # bind_host; this makes it configurable). Loopback default;
    # ASYNCRL_OBS_HOST wins when set.
    obs_http_host: str = "127.0.0.1"
    # Per-window samples retained in the in-memory time-series ring
    # (drop-oldest; the timeseries.jsonl persistence is unbounded).
    obs_timeseries_cap: int = 4096
    # Detector thresholds (obs/health.py; the doctor replays the same
    # values from the run's recorded meta):
    # learner_stall fires when learner_stall_frac exceeds this.
    health_stall_frac: float = 0.9
    # fps_collapse fires when a window's fps drops below this fraction of
    # the run's own trailing median (>= 4 windows of history required).
    health_fps_collapse: float = 0.5
    # grad_explosion fires when grad_norm exceeds this; 0 disables (the
    # default: a healthy clipped run's grad_norm scale is workload-
    # specific, so an absolute bar is an operator choice).
    health_grad_norm_max: float = 0.0
    # eval_regression fires when eval_return falls this far below the
    # run's best; 0 disables (return scales are workload-specific).
    health_eval_drop: float = 0.0
    # Windows a fired event keeps the /healthz verdict degraded (the
    # recovery horizon: no new events for this many windows => ok again).
    health_window_ttl: int = 3
    # --- training introspection (obs/introspect.py) ---
    # Learning-health + device-behavior telemetry: off-policy staleness
    # percentiles per window, loss-aux diagnostics (behaviour-vs-learner
    # KL, V-trace rho/c clip saturation, value explained-variance),
    # compile/recompile accounting with static-shape blame on the
    # learner/inference entry points, and per-window memory watermarks.
    # On by default (the device side is a handful of scalar reductions
    # folded into the existing metrics aux — no extra host sync;
    # scripts/introspect_smoke.sh is the on/off A/B gate).
    # ASYNCRL_INTROSPECT (when set) wins, like ASYNCRL_TRACE.
    introspect: bool = True
    # Detector thresholds for the learning-health detectors (obs/health.py;
    # all default 0 = off — the scales are workload-specific, so arming an
    # absolute bar is an operator choice, the health_grad_norm_max rule):
    # entropy_collapse fires when the window's policy entropy falls below
    # this floor (nats; exploration is dead / the policy went deterministic
    # early).
    health_entropy_floor: float = 0.0
    # staleness_runaway fires when the window's max behaviour-params lag
    # (in learner updates, staleness_max) exceeds this.
    health_staleness_max: float = 0.0
    # rho_clip_saturation fires when the V-trace rho-clip fraction exceeds
    # this (near 1.0 = importance weights pinned at the cap: the learner
    # has drifted too far from the behaviour policy for the correction to
    # mean much).
    health_rho_clip_frac: float = 0.0
    # recompile_storm fires when `compiles` grows by at least this many in
    # ONE window (a recompile storm — e.g. unstable batch shapes — silently
    # taxes every number a bench reports). The first window is exempt:
    # cold-start compilation is expected, not a storm.
    health_recompile_storm: int = 0
    # memory_growth fires when the memory watermark (device bytes-in-use
    # where available, else host RSS) exceeds the run's first recorded
    # watermark by more than this fraction (0.5 = +50%): the leak detector.
    health_mem_growth: float = 0.0

    # --- runtime ---
    seed: int = 0
    # Anakin backend: learner updates fused into ONE jitted call via
    # lax.scan — removes per-update Python dispatch from the hot loop
    # (metrics come back stacked [K] and are aggregated at drain time).
    # Checkpoint/log cadences count CALLS, i.e. multiples of this.
    updates_per_call: int = 1
    log_every: int = 20  # learner update CALLS between metric drains
    # In-training greedy evaluation: every `eval_every` update calls
    # (rounded up to the next log boundary), run `eval_episodes` greedy
    # episodes and report `eval_return` in that metrics window. 0 = off.
    eval_every: int = 0
    eval_episodes: int = 32
    # Updates between periodic checkpoint saves; 0 disables the periodic
    # cadence (with checkpoint_dir set, a final save on train() exit — clean
    # or crashed — still happens).
    checkpoint_every: int = 0
    checkpoint_dir: str = ""
    # Keep the best-evaluation checkpoint (requires checkpoint_dir AND
    # eval_every): whenever an in-training eval improves on the best
    # eval_return so far, the full state also saves under
    # "<checkpoint_dir>-best" (one retained copy; the best score survives
    # resume via the checkpoint metadata).
    checkpoint_best: bool = False
    precision: str = "bf16_matmul"  # "f32" | "bf16_matmul"
    # Gradient accumulation (microbatching): split each fragment's env axis
    # into this many sequential chunks inside the jitted step (lax.scan),
    # summing chunk gradients before the ONE optimizer update. Numerically
    # the full-batch gradient (equal chunks; pinned by tests/test_learner),
    # but peak activation memory drops ~grad_accum-fold — THE lever that
    # fits the reference's 1024-envs/chip pixel workload (BASELINE.json:9)
    # into a 16G v5e HBM, where the fused backward otherwise allocates 21G+
    # (measured OOM, BENCH notes r3). Applies to the single-pass learner
    # (impala/a3c/qlearn/1-epoch PPO); multipass PPO already bounds memory
    # via ppo_minibatches — combining the two is refused loudly.
    grad_accum: int = 1
    # Rematerialize the torso in the backward pass (jax.checkpoint /
    # nn.remat at torso-stage granularity): store only stage boundaries
    # forward, recompute conv intermediates when the gradient needs them.
    # Composes with grad_accum; worth it on CNN torsos where stage
    # intermediates dominate HBM, a no-op-ish trade on MLPs.
    remat: bool = False
    # V-trace/GAE reverse-scan implementation (ops/scan.py). "auto"
    # resolves to "associative" everywhere. The Pallas VMEM kernel IS
    # real-chip validated (scripts/validate_pallas_tpu.py on TPU v5 lite,
    # 2026-07-31, its kernel_validation lines: accuracy on par
    # with the associative tree against a float64 truth on all five preset
    # geometries) — it stays OPT-IN because its measured win is only
    # ~1.0-1.2x on a scan that is itself a small slice of the update, not
    # worth a non-default codepath's risk by default. Force "pallas" to
    # use it on TPU (long-T fragments benefit most), or
    # "pallas_interpret" | "sequential" for debugging.
    scan_impl: str = "auto"
    # Fused V-trace/GAE device hot path (ops/pallas_scan.py
    # fused_vtrace_pallas): TD errors + reverse recurrence + vs/pg
    # reconstruction in one Pallas kernel instead of ~10 HBM round trips
    # of lax elementwise + scan. "auto" resolves at Learner construction
    # (learn/learner.py resolve_scan_impl): "pallas" on TPU, "lax" on
    # CPU/GPU. "interpret" runs the same kernel in the Pallas
    # interpreter (CPU CI; tier-1 differential coverage). The fused path
    # is bit-identical to the lax reference with scan_impl="sequential"
    # (tests/test_differential.py) and supersedes scan_impl when active
    # — scan_impl then only governs the lax fallback (zero-length
    # traces, time-sharded losses).
    fused_scan: str = "auto"
    # Donate the TrainState (Anakin) / the consumed fragment (host path)
    # into the compiled step, for in-place updates. The INVALID_ARGUMENT
    # this flag was turned off for was the program's own: init_state
    # aliased params and actor_params to one buffer ("Attempt to donate
    # the same buffer twice in Execute()", v5e and CPU alike, jax 0.9.0),
    # repaired in learn/learner.py. With that repaired an atari_impala
    # step runs donated on the v5e (chip run, PR 21). The default stays
    # off until cells judge what it does to memory and speed.
    donate_buffers: bool = False

    def replace(self, **kwargs: Any) -> "Config":
        return dataclasses.replace(self, **kwargs)

    @property
    def batch_steps_per_update(self) -> int:
        return self.num_envs * self.unroll_len


def _coerce(old: Any, raw: str) -> Any:
    """Parse ``raw`` to the type of ``old`` (bool/int/float/str/tuple)."""
    if isinstance(old, bool):
        if raw.lower() in ("1", "true", "yes"):
            return True
        if raw.lower() in ("0", "false", "no"):
            return False
        raise ValueError(f"not a bool: {raw!r}")
    if isinstance(old, int):
        return int(raw)
    if isinstance(old, float):
        return float(raw)
    if isinstance(old, tuple):
        items = [s for s in raw.strip("()[] ").split(",") if s.strip()]
        elem = old[0] if old else raw
        return tuple(type(elem)(s.strip()) if old else s.strip() for s in items)
    return raw


def default_eval_max_steps(config: Config) -> int:
    """Eval-rollout horizon that contains the longest builtin episode for
    ``config``'s env (shared by Trainer.evaluate and
    SebulbaTrainer.evaluate — ONE copy, so a cap change cannot drift
    between backends). JaxPong episodes run to Config.pong_max_steps
    (27,000 under the ALE-faithful cap — a 3,200 horizon would silently
    count partial returns); everything else builtin truncates well under
    3,200 (CartPole 500)."""
    if "JaxPong" in config.env_id:
        return max(3200, config.pong_max_steps + 200)
    return 3200


def override(config: Config, kvs: Mapping[str, str] | list[str]) -> Config:
    """Apply CLI-style ``key=value`` overrides onto a frozen config."""
    if isinstance(kvs, list):
        pairs = dict(kv.split("=", 1) for kv in kvs)
    else:
        pairs = dict(kvs)
    field_names = {f.name for f in dataclasses.fields(config)}
    updates = {}
    for key, raw in pairs.items():
        if key not in field_names:
            raise KeyError(
                f"unknown config key: {key!r}; valid keys: {sorted(field_names)}"
            )
        updates[key] = _coerce(getattr(config, key), raw)
    return config.replace(**updates)
