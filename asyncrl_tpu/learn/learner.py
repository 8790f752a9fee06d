"""Learner: the reference's ``Learner.update`` (BASELINE.json:5) as a single
donated-buffer ``jit`` of a ``shard_map`` over the device mesh.

One call = one fused XLA program that (per device shard): rolls out
``unroll_len`` steps across the local env batch with the (possibly stale)
actor params, recomputes logits/values under learner params, applies the
algorithm loss (A3C / IMPALA-V-trace / PPO), all-reduces gradients with
``lax.pmean`` over the ``dp`` axis, and applies Adam. Weight "publishing" to
actors (the reference's queue-back channel) is the ``actor_params`` refresh —
a pytree select every ``actor_staleness`` updates, staying entirely in HBM
(SURVEY.md §5.8b, §7.3).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import Mesh, PartitionSpec as P

from asyncrl_tpu.envs.core import Environment
from asyncrl_tpu.ops.distributions import Evaluated
from asyncrl_tpu.ops.gae import gae
from asyncrl_tpu.ops.normalize import (
    init_stats,
    normalizing_apply,
    update_stats,
)
from asyncrl_tpu.models.networks import is_recurrent, reset_core
from asyncrl_tpu.models.seq_common import MODEL_LOSS
from asyncrl_tpu.obs import introspect, trace
from asyncrl_tpu.obs import spans as span_names
from asyncrl_tpu.ops.losses import (
    a3c_loss,
    impala_loss,
    ppo_loss,
    qlearn_loss,
)
from asyncrl_tpu.parallel.mesh import (
    axis_size,
    dp_axes,
    dp_size,
    shard_map,
)
from asyncrl_tpu.rollout.anakin import ActorState, actor_init, unroll
from asyncrl_tpu.rollout.buffer import Rollout
from asyncrl_tpu.utils.config import Config


# Axes-tolerant collectives: the train-step body is also used with
# ``axes=()`` (population mode, api/population.py — members are independent,
# nothing may reduce across them), where each collective degenerates to the
# single-shard identity.
def _pmean(x, axes):
    return x if not axes else jax.lax.pmean(x, axes)


def _psum(x, axes):
    return x if not axes else jax.lax.psum(x, axes)


def _axis_size(axes) -> int:
    return 1 if not axes else axis_size(axes)


def _axis_index(axes):
    return jnp.zeros((), jnp.int32) if not axes else jax.lax.axis_index(axes)


@struct.dataclass
class TrainState:
    """Full training state; the unit of checkpointing (SURVEY.md §5.4).

    ``params`` are the learner weights; ``actor_params`` the stale copy the
    rollout uses (equal for on-policy algos, lagged for IMPALA). ``actor``
    holds env states/obs/keys, sharded over the dp axis. ``obs_stats`` is
    the running observation-normalization state (ops/normalize.py) — None
    (empty subtree) unless ``config.normalize_obs``.
    """

    params: Any
    actor_params: Any
    opt_state: Any
    actor: ActorState
    update_step: jax.Array  # int32 scalar
    obs_stats: Any = None
    # Running scalar stats of the per-env discounted return (reward
    # normalization, config.normalize_returns); None when disabled.
    ret_stats: Any = None
    # Self-play (config.selfplay): the frozen rival snapshot the duel env
    # plays against, refreshed from params every selfplay_refresh updates.
    # None (empty subtree) otherwise — keeps old checkpoints restorable.
    opponent_params: Any = None


def state_partition_spec(axes: tuple[str, ...]) -> TrainState:
    """Pytree-prefix PartitionSpecs for shard_map in/out_specs: params and
    optimizer replicated, actor state sharded on its leading env dim over
    all data-parallel axes (one axis on a single slice, (dcn, dp) on a
    hybrid multi-slice mesh)."""
    return TrainState(
        params=P(),
        actor_params=P(),
        opt_state=P(),
        actor=P(axes),
        update_step=P(),
        obs_stats=P(),
        ret_stats=P(),
        opponent_params=P(),
    )


def _total_optimizer_steps(config: Config) -> int:
    """Projected count of ``optimizer.update`` calls over a full run — the
    LR schedule's horizon. optax schedules tick once per optimizer call, so
    this must model the configured backend and algorithm:

    - Anakin consumes ``num_envs * unroll_len`` frames per learner update;
      the host backends (sebulba/cpu_async) consume one ACTOR's fragment,
      ``(num_envs / actor_threads) * unroll_len``, per update;
    - multipass PPO takes ``ppo_epochs * ppo_minibatches`` optimizer steps
      inside each learner update.
    """
    frames_per_update = config.batch_steps_per_update
    if config.backend in ("sebulba", "cpu_async"):
        frames_per_update //= max(config.actor_threads, 1)
    updates = max(1, config.total_env_steps // max(frames_per_update, 1))
    if config.algo == "ppo":
        updates *= max(1, config.ppo_epochs) * max(1, config.ppo_minibatches)
    return updates


def base_optimizer(config: Config):
    """The per-step transform factory (rate injected later): Adam (the
    reference Learner's optimizer, BASELINE.json:5) or shared-statistics
    RMSProp (the A3C-paper family default, SURVEY.md:143 — "shared" holds
    by construction here: one mesh-wide optimizer state fed by psum'd
    gradients). Returned as a factory so population training can wrap it
    in ``optax.inject_hyperparams`` for per-member rates."""
    if config.optimizer == "adam":
        return optax.adam, {"eps": config.adam_eps}
    if config.optimizer == "rmsprop":
        return optax.rmsprop, {
            "decay": config.rmsprop_decay,
            "eps": config.rmsprop_eps,
        }
    raise ValueError(
        f"unknown optimizer {config.optimizer!r}; expected adam|rmsprop"
    )


def make_optimizer(config: Config) -> optax.GradientTransformation:
    """Global-norm clip + the configured base optimizer, with the configured
    LR schedule. The schedule is indexed by the optimizer's own update
    count; its horizon is the projected optimizer-step total for this
    backend/algorithm (``_total_optimizer_steps``), so "linear" reaches
    zero at the run's step budget — not a fraction of the way through it."""
    if config.lr_schedule == "constant":
        lr = config.learning_rate
    elif config.lr_schedule == "linear":
        lr = optax.linear_schedule(
            config.learning_rate, 0.0, _total_optimizer_steps(config)
        )
    else:
        raise ValueError(
            f"unknown lr_schedule {config.lr_schedule!r}; "
            "expected constant|linear"
        )
    base, kwargs = base_optimizer(config)
    return optax.chain(
        optax.clip_by_global_norm(config.max_grad_norm),
        base(lr, **kwargs),
    )


def resolve_scan_impl(config: Config, mesh: Mesh) -> Config:
    """Resolve ``scan_impl="auto"`` and ``fused_scan="auto"`` to concrete
    implementations. Called by each learner constructor so the per-shard
    loss code sees a fixed choice.

    ``scan_impl`` "auto" -> "associative" everywhere. The plain Pallas
    scan kernel (ops/pallas_scan.py) WAS validated on a real TPU v5lite
    chip (2026-07-30): its Mosaic lowering compiles and runs, and it is
    numerically identical to the associative scan (rtol 2e-5 over
    [128, 1024] fragments). End-to-end it is indistinguishable — the
    reverse scan ALONE is a negligible slice of the train step at RL
    fragment lengths — so it stays opt-in (``scan_impl=pallas``).

    ``fused_scan`` "auto" -> "pallas" on TPU meshes, "lax" elsewhere.
    Unlike the bare scan swap, the fused kernel replaces the WHOLE
    V-trace/GAE tail — five [T, B] elementwise HBM passes plus the
    O(log T) scan rounds collapse into one tile-resident pass — and it
    is bit-identical to the lax reference (sequential schedule), so the
    TPU default changes no training numerics beyond the documented
    sequential-vs-associative rounding split that scan_impl already
    owns. "interpret" (the Pallas interpreter) is the CPU CI surface;
    it is never auto-selected."""
    if config.fused_scan == "auto":
        platform = mesh.devices.flat[0].platform if mesh.devices.size else "cpu"
        config = config.replace(
            fused_scan="pallas" if platform == "tpu" else "lax"
        )
    elif config.fused_scan not in ("pallas", "interpret", "lax"):
        raise ValueError(
            f"unknown fused_scan {config.fused_scan!r}; "
            "expected auto|pallas|interpret|lax"
        )
    if config.scan_impl != "auto":
        return config
    return config.replace(scan_impl="associative")


def fused_smap_opts(config: Config) -> dict:
    """shard_map kwargs for a learner step. Every compiled config —
    the Mosaic fused kernel included — runs under the CHECKED shard_map:
    on jax 0.9.0 a compiled ``pallas_call`` passes ``check_vma`` because
    its ``out_shape`` declares vma (ops/pallas_scan.py ``_out_struct``).
    The one opt-out is ``fused_scan="interpret"`` (CPU CI): the Pallas HLO
    interpreter evaluates the kernel against scratch and output buffers
    that carry no vma, which the checker rejects ("Primitive concatenate
    requires varying manual axes to match"). The opt-out is not free —
    see :func:`reduce_grads`."""
    return {"check_vma": False} if _smap_unchecked(config) else {}


def _smap_unchecked(config: Config) -> bool:
    return config.fused_scan == "interpret"


def reduce_grads(grads, axes, config: Config):
    """Cross-shard sum for gradients of the REPLICATED params taken inside
    a learner body. Under the checked shard_map the transpose of the
    implicit replicated->varying cast psums those cotangents (the bodies
    scale their loss by 1/axis_size to match), so this is the identity.
    An unchecked shard_map (:func:`fused_smap_opts`) inserts no such cast:
    every shard would keep its LOCAL gradient and the replicas would
    silently drift apart (measured on the 8-device CPU mesh, jax 0.9.0:
    grad_norm 0.56 vs 4.34, param shards unequal after three updates), so
    there the sum is explicit. Bit-identical to the implicit one on that
    mesh."""
    if not axes or not _smap_unchecked(config):
        return grads
    return jax.tree.map(lambda g: jax.lax.psum(g, axes), grads)


def validate_qlearn_config(config: Config) -> None:
    """Shared constructor-time check for the Q-learning family: every
    builder of the train-step body (Learner, PopulationTrainer) must call
    this, since the degenerate configuration fails silently, not loudly."""
    if config.algo == "qlearn" and config.actor_staleness < 2:
        raise ValueError(
            "algo='qlearn' needs actor_staleness >= 2: that field is the "
            "target-network update period for this algo, and at 1 the "
            "bootstrap comes from the net being optimized (double_q "
            "degenerates to max-Q too). The cartpole_qlearn preset "
            "uses 4."
        )


def validate_train_target(config: Config, target: int) -> None:
    """Shared guard for Trainer.train / SebulbaTrainer.train: with an
    annealing LR schedule, training past the configured horizon would
    silently run at lr=0 — refuse instead."""
    if config.lr_schedule != "constant" and target > config.total_env_steps:
        raise ValueError(
            f"train(total_env_steps={target}) exceeds the lr_schedule "
            f"horizon (config.total_env_steps={config.total_env_steps}): "
            "the annealed rate would sit at 0 for the excess steps. Set "
            "config.total_env_steps to the real budget instead."
        )


def validate_selfplay_config(config: Config, env, model) -> None:
    """Eager self-play checks (Anakin Learner only): the env must be a duel
    env, the policy feed-forward (the frozen rival has no core-state
    plumbing in v1), and the backend the fused one."""
    if not config.selfplay:
        return
    if config.backend != "tpu":
        raise NotImplementedError(
            "selfplay is Anakin-only (backend='tpu'): host actor threads "
            "have no opponent-snapshot channel"
        )
    # frame_skip / sticky_actions compose with self-play: the ALE wrappers
    # forward the duel protocol (both paddles' actions repeat across a skip
    # window; each paddle draws its own stick — envs/wrappers.py), so the
    # hasattr check below sees through them.
    if not (
        hasattr(env, "step_duel") and hasattr(env, "observe_opponent")
    ):
        raise ValueError(
            f"selfplay needs a duel env (step_duel + observe_opponent); "
            f"{config.env_id!r} is not one — use JaxPongDuel-v0"
        )


def validate_recurrent_config(config: Config, model) -> None:
    """Shared constructor-time checks for recurrent policies (Anakin and
    host-fragment learners alike). Recurrent multipass PPO is supported
    via sequence-preserving minibatching (see ``_ppo_multipass``); its
    geometry constraint (envs, not samples, divide into minibatches) is
    enforced by ``validate_ppo_geometry(recurrent=True)``."""
    if config.core == "lstm" and not is_recurrent(model):
        raise ValueError(
            "config.core='lstm' but the given model is not recurrent — "
            "pass a RecurrentActorCritic (policy-gradient algos) / "
            "RecurrentQNetwork (qlearn), or use core='ff'"
        )


def fragment_form(apply_fn):
    """The model's whole-fragment form (``models/kimi_linear.py``), where
    the model behind ``apply_fn`` has one; None for a model that is only
    ever called one step at a time."""
    model = getattr(apply_fn, "__self__", None)
    if not hasattr(model, "fragment"):
        return None
    return functools.partial(apply_fn, method="fragment")


def _forward_evaluated(fragment, apply_fn, params, rollout: Rollout):
    """Learner forward through the model's fragment form, from the
    fragment-initial behaviour carry as ``_forward_fragment`` below:
    ``((logp, entropy) [T, B] of the fragment's own actions, values
    [T+1, B], aux)``. The policy head is already evaluated (the
    [T, B, actions] logits are never whole): ``distributions.Evaluated``
    reads the pair where a loss asks a distribution."""
    logp, entropy, values, core_end, aux = fragment(
        params, rollout.obs, rollout.done, rollout.init_core, rollout.actions
    )
    # Every loss stops the gradient at the bootstrap value: stopping it on
    # the way in spares this step's backward pass, what it would keep, and
    # the tracing of either (its kernels then lower as in the rollout).
    _, boot_value, _ = apply_fn(
        *jax.lax.stop_gradient((params, rollout.bootstrap_obs, core_end))
    )
    values = jnp.concatenate([values, boot_value[None]], axis=0)
    return (logp, entropy), values, aux


def _forward_fragment(apply_fn, params, rollout: Rollout):
    """Learner forward over one fragment -> (dist_params, values), both
    [T+1, ...] (final entry is the bootstrap step).

    Feed-forward: one batched apply over the stacked [T+1, B] obs.
    Recurrent (``rollout.init_core`` present): a ``lax.scan`` over time
    carrying the core from the fragment-initial behaviour carry (IMPALA's
    stale-core recipe) and resetting it at episode boundaries, exactly as
    the actor did."""
    if rollout.init_core is None:
        obs_all = jnp.concatenate(
            [rollout.obs, rollout.bootstrap_obs[None]], axis=0
        )
        return apply_fn(params, obs_all)

    def fwd(core, inputs):
        obs_t, done_t = inputs
        dist_params, value, new_core = apply_fn(params, obs_t, core)
        return reset_core(new_core, done_t), (dist_params, value)

    core_end, (logits_t, values_t) = jax.lax.scan(
        fwd, rollout.init_core, (rollout.obs, rollout.done)
    )
    boot_logits, boot_value, _ = apply_fn(
        params, rollout.bootstrap_obs, core_end
    )
    logits = jnp.concatenate([logits_t, boot_logits[None]], axis=0)
    values = jnp.concatenate([values_t, boot_value[None]], axis=0)
    return logits, values


def qlearn_bootstrap(config: Config, online_boot_q, target_boot_q):
    """THE target-network bootstrap selection for the Q-learning family
    (shared by the unsharded and time-sharded loss paths): ``max_a
    Q_target``, or the double-Q selection — argmax under the ONLINE net,
    evaluated under the target — to damp the max bias."""
    target_boot_q = jax.lax.stop_gradient(target_boot_q)
    if config.double_q:
        sel = jnp.argmax(jax.lax.stop_gradient(online_boot_q), axis=-1)
        return jnp.take_along_axis(target_boot_q, sel[..., None], axis=-1)[
            ..., 0
        ]
    return jnp.max(target_boot_q, axis=-1)


def entropy_coef_at(config: Config, update_step) -> jax.Array | float:
    """Effective entropy coefficient at ``update_step`` (traced scalar):
    linear ramp entropy_coef -> entropy_coef_final over
    entropy_anneal_steps updates, constant thereafter — and the plain
    Python float when annealing is off, keeping the non-annealed program
    bit-identical to before the feature existed."""
    if config.entropy_anneal_steps <= 0:
        return config.entropy_coef
    frac = jnp.clip(
        update_step.astype(jnp.float32) / float(config.entropy_anneal_steps),
        0.0,
        1.0,
    )
    return config.entropy_coef + frac * (
        config.entropy_coef_final - config.entropy_coef
    )


def _algo_loss(
    config: Config, apply_fn, params, rollout: Rollout,
    axis_name: str | None = None, dist=None, target_params=None,
    entropy_coef=None,
):
    """Forward the learner net over [T+1, B] obs and apply the configured
    algorithm's loss. Returns (loss, metrics). ``axis_name`` is the dp mesh
    axis when called inside shard_map (for losses needing global batch
    moments, i.e. PPO advantage normalization). ``dist`` interprets the
    policy head (ops.distributions). ``target_params`` is the Q-learning
    family's target network (required for algo='qlearn', unused otherwise).
    ``entropy_coef`` overrides config.entropy_coef (the annealed traced
    value, entropy_coef_at); None = the constant."""
    if entropy_coef is None:
        entropy_coef = config.entropy_coef
    fragment = fragment_form(apply_fn)
    if fragment is not None and config.algo != "qlearn":
        logits_t, values, aux = _forward_evaluated(
            fragment, apply_fn, params, rollout
        )
        dist = Evaluated()
    else:
        logits, values = _forward_fragment(apply_fn, params, rollout)
        logits_t, aux = logits[:-1], {}
    values_t = values[:-1]
    bootstrap_value = values[-1]
    discounts = rollout.discounts(config.gamma)

    def with_aux(loss_and_metrics):
        loss, metrics = loss_and_metrics
        # a loss term of the model's own (``models/seq_common.py
        # MODEL_LOSS``): added to the algorithm's, coefficient 1, and kept
        # out of the metrics
        extra = dict(aux)
        own = extra.pop(MODEL_LOSS, None)
        if own is not None:
            loss = loss + own
        return loss, {**metrics, **extra}

    if config.algo == "qlearn":
        # ``logits`` ARE the online Q-values here (QNetwork head). The
        # bootstrap comes from the target network (the stale actor_params
        # copy, refreshed every actor_staleness updates — the async-Q target
        # network θ⁻) via the shared ``qlearn_bootstrap`` selection.
        if rollout.init_core is None:
            q_target = apply_fn(target_params, rollout.bootstrap_obs)[0]
        else:
            # DRQN: the target net needs ITS OWN core at the bootstrap
            # step, so re-forward the whole fragment under target params
            # from the stored behaviour-initial carry (the stored-state
            # DRQN recipe; same shape of work as the online re-forward).
            q_target = _forward_fragment(
                apply_fn, target_params, rollout
            )[0][-1]
        boot = qlearn_bootstrap(config, logits[-1], q_target)
        return qlearn_loss(
            logits_t, rollout.actions, rollout.rewards, discounts, boot,
            scan_impl=config.scan_impl, fused_scan=config.fused_scan,
            huber_delta=config.huber_delta,
        )
    if config.algo == "a3c":
        return with_aux(a3c_loss(
            logits_t, values_t, rollout.actions, rollout.rewards, discounts,
            jax.lax.stop_gradient(bootstrap_value),
            value_coef=config.value_coef, entropy_coef=entropy_coef,
            dist=dist, scan_impl=config.scan_impl,
            fused_scan=config.fused_scan,
            diagnostics=config.introspect,
        ))
    if config.algo == "impala":
        return with_aux(impala_loss(
            logits_t, values_t, rollout.actions, rollout.behaviour_logp,
            rollout.rewards, discounts, jax.lax.stop_gradient(bootstrap_value),
            value_coef=config.value_coef, entropy_coef=entropy_coef,
            rho_clip=config.vtrace_rho_clip, c_clip=config.vtrace_c_clip,
            dist=dist, scan_impl=config.scan_impl,
            fused_scan=config.fused_scan,
            diagnostics=config.introspect,
        ))
    if config.algo == "ppo":
        # Single-pass PPO over the fresh fragment (used when
        # ppo_epochs == ppo_minibatches == 1; the multi-epoch minibatched
        # path is _ppo_multipass below).
        adv = gae(
            rollout.rewards, discounts, jax.lax.stop_gradient(values_t),
            jax.lax.stop_gradient(bootstrap_value), config.gae_lambda,
            scan_impl=config.scan_impl, fused=config.fused_scan,
        )
        return with_aux(ppo_loss(
            logits_t, values_t, rollout.actions, rollout.behaviour_logp,
            adv.advantages, adv.returns,
            clip_eps=config.ppo_clip_eps, value_coef=config.value_coef,
            entropy_coef=entropy_coef, axis_name=axis_name,
            dist=dist, diagnostics=config.introspect,
        ))
    raise ValueError(f"unknown algo {config.algo!r}")


def _ppo_multipass(
    config: Config, apply_fn, optimizer, dist, params, opt_state,
    rollout: Rollout, update_step: jax.Array,
    *,
    axes: tuple[str, ...],  # required: () is now a MEANINGFUL value
    # (population mode, no cross-shard reduction) — a silent default here
    # would turn a forgotten-axes call site into unsynchronized params.
    member_seed: jax.Array | None = None,
    time_axis: str | None = None,
):
    """PPO's real update: ``ppo_epochs`` passes over the fragment, each a
    scan of ``ppo_minibatches`` shuffled minibatch Adam steps (the reference's
    Procgen PPO config, BASELINE.json:10).

    Advantages/returns are computed ONCE under the pre-update params (the
    standard PPO recipe); each minibatch recomputes the ratio against the
    progressively-updated params. Runs inside shard_map: each device shuffles
    its local fragment independently (decorrelated minibatches), while
    gradients and advantage-normalization moments ride the implicit/explicit
    psum over the dp axis, so every device applies identical parameter
    updates.

    Recurrent policies (``rollout.init_core`` present) use SEQUENCE-
    PRESERVING minibatching: the shuffle permutes ENVS, never time — each
    minibatch is a [T, B/mb] block of whole fragments re-forwarded by a
    time scan from its slice of the stored fragment-initial carries (with
    episode-boundary resets), so the core always sees the exact temporal
    structure the behaviour policy generated. Feed-forward keeps the flat
    [T*B] sample shuffle (strictly more decorrelated, and cheaper).

    ``time_axis`` (host-fragment learner on an sp mesh): the fragment's T
    dim is sequence-parallel, so GAE runs as the two-level distributed
    reverse scan and every per-sample quantity is a LOCAL [T_local, B]
    slice. Minibatching needs nothing else: PPO's per-sample loss has no
    cross-time coupling (the only time recursion is the one-shot GAE), so
    each (dp, sp) shard shuffles its local samples independently — the
    global minibatch is time-stratified, the same decorrelation argument
    as the dp-local shuffle above. ``axes`` must then be the FULL reduce
    set (dp axes + time axis), making the loss scaling / advantage moments
    / shuffle-key folding span the time shards like any other data axis.
    Recurrent cores stay excluded from sp meshes (rollout_learner's
    eager check; docs/ARCHITECTURE.md).
    """
    fragment = fragment_form(apply_fn)
    if time_axis is None:
        if fragment is not None:
            _, values_all, _ = _forward_evaluated(
                fragment, apply_fn, params, rollout
            )
        else:
            _, values_all = _forward_fragment(apply_fn, params, rollout)
        values_t, bootstrap_value = values_all[:-1], values_all[-1]
        adv = gae(
            rollout.rewards,
            rollout.discounts(config.gamma),
            jax.lax.stop_gradient(values_t),
            jax.lax.stop_gradient(bootstrap_value),
            config.gae_lambda,
            scan_impl=config.scan_impl,
            fused=config.fused_scan,
        )
    else:
        from asyncrl_tpu.parallel.timeshard import gae_timesharded

        # ``bootstrap_obs`` is replicated over the time axis (same calling
        # contract as rollout_learner._algo_loss_timesharded): every shard
        # computes the tiny bootstrap forward, the distributed scan
        # consumes it on the last shard only.
        _, values_t = apply_fn(params, rollout.obs)
        _, bootstrap_value = apply_fn(params, rollout.bootstrap_obs)
        adv = gae_timesharded(
            rollout.rewards,
            rollout.discounts(config.gamma),
            jax.lax.stop_gradient(values_t),
            jax.lax.stop_gradient(bootstrap_value),
            config.gae_lambda,
            axis_name=time_axis,
        )

    T, B = rollout.actions.shape[:2]
    recurrent = rollout.init_core is not None
    validate_ppo_geometry(
        config, B, "trace-time local", unroll=T, recurrent=recurrent
    )
    mb = config.ppo_minibatches

    # Deterministic per-(step, device, epoch) shuffle key; no PRNG state
    # threads through TrainState.
    # ``member_seed`` (population mode) replaces config.seed so member i's
    # shuffle stream equals a STANDALONE run with seed=member_seed — the
    # exact-equivalence invariant tests/test_population.py asserts.
    seed = config.seed if member_seed is None else member_seed
    base_key = jax.random.fold_in(
        jax.random.PRNGKey(seed + 0x5EB), update_step
    )
    base_key = jax.random.fold_in(base_key, _axis_index(axes))

    def minibatch_step_with(forward, dist=dist):
        def minibatch_step(carry, batch):
            params, opt_state = carry

            def scaled_loss(p):
                logits, values = forward(p, batch)
                loss, metrics = ppo_loss(
                    logits, values, batch["actions"],
                    batch["behaviour_logp"],
                    batch["advantages"], batch["returns"],
                    clip_eps=config.ppo_clip_eps,
                    value_coef=config.value_coef,
                    entropy_coef=entropy_coef_at(config, update_step),
                    axis_name=axes or None,
                    dist=dist,
                    diagnostics=config.introspect,
                )
                metrics = dict(metrics, loss=loss)
                return loss / _axis_size(axes), metrics

            grads, metrics = jax.grad(scaled_loss, has_aux=True)(params)
            grads = reduce_grads(grads, axes, config)
            metrics["grad_norm"] = optax.global_norm(grads)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state), metrics

        return minibatch_step

    if recurrent:
        per_env = {
            "obs": rollout.obs,
            "actions": rollout.actions,
            "behaviour_logp": rollout.behaviour_logp,
            "advantages": jax.lax.stop_gradient(adv.advantages),
            "returns": jax.lax.stop_gradient(adv.returns),
            "done": rollout.done,
        }  # every leaf [T, B, ...]

        if fragment is not None:
            # The model's own whole-fragment form in place of the scan of
            # steps; the head comes back evaluated at the batch's actions.
            dist = Evaluated()

            def forward(p, batch):
                logp, entropy, values, _, _ = fragment(
                    p, batch["obs"], batch["done"], batch["init_core"],
                    batch["actions"],
                )
                return (logp, entropy), values

        else:

            def forward(p, batch):
                def fwd(core, inputs):
                    obs_t, done_t = inputs
                    dist_params, value, new_core = apply_fn(p, obs_t, core)
                    return reset_core(new_core, done_t), (dist_params, value)

                _, (logits, values) = jax.lax.scan(
                    fwd, batch["init_core"], (batch["obs"], batch["done"])
                )
                return logits, values

        def epoch_step(carry, ekey):
            perm = jax.random.permutation(ekey, B)

            def split_envs(x):  # [T, B, ...] -> [mb, T, B/mb, ...]
                x = x[:, perm].reshape(T, mb, B // mb, *x.shape[2:])
                return jnp.moveaxis(x, 1, 0)

            batches = jax.tree.map(split_envs, per_env)
            batches["init_core"] = jax.tree.map(
                lambda c: c[perm].reshape(mb, B // mb, *c.shape[1:]),
                rollout.init_core,
            )
            return jax.lax.scan(
                minibatch_step_with(forward, dist), carry, batches
            )

    else:
        n = T * B
        flat = {
            "obs": rollout.obs.reshape(n, *rollout.obs.shape[2:]),
            "actions": rollout.actions.reshape(n, *rollout.actions.shape[2:]),
            "behaviour_logp": rollout.behaviour_logp.reshape(n),
            "advantages": jax.lax.stop_gradient(adv.advantages).reshape(n),
            "returns": jax.lax.stop_gradient(adv.returns).reshape(n),
        }

        def forward(p, batch):
            return apply_fn(p, batch["obs"])

        def epoch_step(carry, ekey):
            perm = jax.random.permutation(ekey, n)
            batches = jax.tree.map(
                lambda x: x[perm].reshape(mb, n // mb, *x.shape[1:]), flat
            )
            return jax.lax.scan(minibatch_step_with(forward), carry, batches)

    epoch_keys = jax.random.split(base_key, config.ppo_epochs)
    (params, opt_state), metrics = jax.lax.scan(
        epoch_step, (params, opt_state), epoch_keys
    )
    # [E, M, ...] scalars -> means; psum-averaged later by the caller.
    metrics = jax.tree.map(jnp.mean, metrics)
    loss = metrics.pop("loss")
    grad_norm = metrics.pop("grad_norm")
    return params, opt_state, loss, grad_norm, metrics


def qlearn_epsilon_schedule(config: Config, global_env_index, env_frames):
    """THE ε schedule for the async Q-learning family — single source of
    truth for every backend (Anakin's in-jit ``qlearn_epsilon`` and the host
    backends' per-thread ``SebulbaTrainer._epsilon_fn`` both call this, so
    the ladder/anneal can never drift between them).

    Each global env slot gets its own final ε on the Ape-X ladder
    ``eps_base ** (1 + alpha * i / (N-1))`` (the vectorized analogue of the
    A3C paper's per-thread sampled ε), annealed from 1.0 over the first
    ``exploration_steps`` global env frames. Accepts np or jnp inputs;
    returns f32 of ``global_env_index``'s shape."""
    frac = global_env_index / max(config.num_envs - 1, 1)
    final_eps = config.eps_base ** (1.0 + config.eps_alpha * frac)
    anneal = jnp.minimum(
        1.0, env_frames / max(config.exploration_steps, 1)
    )
    return (1.0 + anneal * (final_eps - 1.0)).astype(jnp.float32)


def qlearn_epsilon(
    config: Config, update_step: jax.Array, local_envs: int, axes
) -> jax.Array:
    """Anakin per-shard view of ``qlearn_epsilon_schedule``: global env
    indices from the shard's mesh position, global frames from the update
    counter. Returns [local_envs] f32; constant across one fragment (anneal
    granularity = one update)."""
    gidx = _axis_index(axes) * local_envs + jnp.arange(local_envs)
    env_frames = update_step.astype(jnp.float32) * (
        config.num_envs * config.unroll_len
    )
    return qlearn_epsilon_schedule(
        config, gidx.astype(jnp.float32), env_frames
    )


def validate_ppo_geometry(
    config: Config,
    local_envs: int,
    label: str,
    unroll: int | None = None,
    recurrent: bool = False,
) -> None:
    """One rule, three callers (Learner.__init__, PopulationTrainer,
    _ppo_multipass's trace-time check): a multipass-PPO fragment must split
    evenly into minibatches — flat samples for feed-forward, whole-fragment
    ENV groups for recurrent (sequence-preserving minibatching never splits
    the time axis). The trace-time caller passes the ACTUAL fragment length
    as ``unroll`` (host-fed rollouts can differ from config.unroll_len);
    eager callers omit it."""
    if config.algo == "ppo" and (
        config.ppo_epochs > 1 or config.ppo_minibatches > 1
    ):
        if recurrent:
            if local_envs % config.ppo_minibatches:
                raise ValueError(
                    f"{label}: recurrent multipass PPO minibatches over "
                    f"envs (time is never split), but {local_envs} envs "
                    f"are not divisible by "
                    f"ppo_minibatches={config.ppo_minibatches}"
                )
            return
        frag = local_envs * (
            config.unroll_len if unroll is None else unroll
        )
        if frag % config.ppo_minibatches:
            raise ValueError(
                f"{label} fragment of {frag} samples not divisible by "
                f"ppo_minibatches={config.ppo_minibatches}"
            )


def derive_init_keys(key: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The one canonical (params key, actor key) split for a training run.

    Shared by ``Learner.init_state`` AND ``PopulationTrainer._member_init``:
    a population member with seed s must reproduce a standalone run with
    seed s bit-for-bit (tests/test_population.py), so the derivation lives
    in exactly one place.
    """
    return tuple(jax.random.split(key))


def init_params(model, env: Environment, pkey: jax.Array):
    """Canonical model init for a training run (see derive_init_keys)."""
    dummy_obs = jnp.zeros((1, *env.spec.obs_shape), env.spec.obs_dtype)
    if is_recurrent(model):
        return model.init(pkey, dummy_obs, model.initial_core(1))
    return model.init(pkey, dummy_obs)


def fuse_updates(body: Callable, updates_per_call: int) -> Callable:
    """Fuse K sequential train-step updates into ONE XLA program via
    ``lax.scan`` — zero host dispatch between them (the amortization that
    matters when a call's dispatch outweighs its compute; how much that is
    on this runtime is unmeasured, ROADMAP A5). Metrics leaves stack to
    [K].

    Shared by Learner (single-run) and PopulationTrainer (vmapped members —
    VERDICT r2 Next #4): extra positional args (e.g. the member seed) pass
    through to every fused step unchanged.
    """
    if updates_per_call <= 1:
        return body

    def multi_step(state: TrainState, *args):
        return jax.lax.scan(
            lambda s, _: body(s, *args), state, None,
            length=updates_per_call,
        )

    return multi_step


def _chunk_envs(rollout, n: int):
    """Reshape a fragment into ``n`` env-axis chunks with a leading scan
    axis: time-major leaves [T, B, ...] -> [n, T, B/n, ...], batch-major
    leaves (bootstrap_obs, init_core) [B, ...] -> [n, B/n, ...]. Chunks
    are whole envs — time stays intact, so V-trace/GAE per-env scans are
    untouched; only the batch mean is split (see grad_accum)."""

    def tm(x):
        return jnp.moveaxis(
            x.reshape(x.shape[0], n, -1, *x.shape[2:]), 1, 0
        )

    def bm(x):
        return x.reshape(n, -1, *x.shape[1:])

    return rollout.replace(
        obs=tm(rollout.obs),
        actions=tm(rollout.actions),
        behaviour_logp=tm(rollout.behaviour_logp),
        rewards=tm(rollout.rewards),
        terminated=tm(rollout.terminated),
        truncated=tm(rollout.truncated),
        bootstrap_obs=bm(rollout.bootstrap_obs),
        init_core=jax.tree.map(bm, rollout.init_core),
        disc_returns=jax.tree.map(tm, rollout.disc_returns),
    )


def validate_grad_accum_config(config: Config, envs_per_shard: int) -> None:
    """grad_accum must split the per-shard env axis into equal whole
    chunks (equality of chunk means is what makes the summed gradient
    exact), and is refused for PPO entirely: multipass PPO has
    ppo_minibatches as the same memory lever, and single-pass PPO
    normalizes advantages over the batch — chunk-local moments would
    silently change the gradient, breaking grad_accum's exactness
    contract."""
    if config.grad_accum <= 1:
        return
    if config.algo == "ppo":
        raise ValueError(
            "grad_accum > 1 is not supported for PPO: advantage"
            " normalization computes batch moments, which chunking would"
            " silently localize. Use ppo_minibatches — PPO's native"
            " microbatching knob — instead."
        )
    if envs_per_shard % config.grad_accum != 0:
        raise ValueError(
            f"grad_accum={config.grad_accum} must divide the per-shard env"
            f" count ({envs_per_shard}): unequal chunks would bias the"
            " accumulated gradient."
        )


def accumulate_grads(scaled_loss, params, rollout, n_accum: int):
    """Microbatched gradient: scan over env-axis chunks (``_chunk_envs``),
    summing per-chunk grads of ``scaled_loss(params, chunk)``. Each chunk's
    backward materializes only its own activations, so peak HBM drops
    ~n_accum-fold; the summed gradient equals the full-batch one exactly
    (equal chunks + the caller's 1/n_accum loss scaling). Losses/metrics
    are per-env means, so the chunk mean recovers the batch mean. Chunk
    count is identical on every shard, so per-chunk collectives (e.g.
    time-sharded V-trace psums) stay in lockstep across the mesh.

    Shared by the Anakin train step and the host-fragment RolloutLearner —
    the two must never diverge. Returns ``(grads, loss, metrics)``."""

    def accum_body(g_acc, frag):
        (_, aux), g = jax.value_and_grad(scaled_loss, has_aux=True)(
            params, frag
        )
        return jax.tree.map(jnp.add, g_acc, g), aux

    grads, (loss_k, metrics_k) = jax.lax.scan(
        accum_body,
        jax.tree.map(jnp.zeros_like, params),
        _chunk_envs(rollout, n_accum),
    )
    return (
        grads,
        jnp.mean(loss_k),
        jax.tree.map(lambda m: jnp.mean(m, 0), metrics_k),
    )


def make_train_step(
    config: Config,
    env: Environment,
    apply_fn: Callable,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    axes: tuple[str, ...] | None = None,
) -> Callable[[TrainState], tuple[TrainState, dict[str, jax.Array]]]:
    """Build the per-shard train-step body (to be wrapped in shard_map).

    ``axes`` defaults to the mesh's data-parallel axes; pass ``()`` for a
    fully self-contained body with no cross-shard reduction (population
    mode: each vmapped member is its own training run).
    """
    from asyncrl_tpu.ops import distributions

    dist = distributions.for_config(config, env.spec)

    # Static choice: PPO with epochs/minibatches > 1 takes the multipass
    # update path; everything else is one fused gradient step.
    ppo_multipass = config.algo == "ppo" and (
        config.ppo_epochs > 1 or config.ppo_minibatches > 1
    )
    qlearn = config.algo == "qlearn"

    if axes is None:
        axes = dp_axes(mesh)

    def train_step(state: TrainState, member_seed: jax.Array | None = None):
        # ``member_seed``: population mode only (api/population.py) — the
        # per-member integer seed whose standalone run this member must
        # reproduce exactly. None everywhere else.
        # named_scope: sections show up as labeled blocks in jax.profiler
        # traces (SURVEY.md §5.1; CLI --profile).
        # Observation normalization: behaviour, learner, and (this step's)
        # target forwards all see the SAME pre-update stats; the stats fold
        # in this rollout's observations afterwards, for the next step.
        napply = normalizing_apply(apply_fn, state.obs_stats)
        dist_extra = None
        if qlearn:
            # ε rides the dist_params channel (ops.distributions
            # .EpsilonGreedy): per-env final values, annealed by env frames.
            eps = qlearn_epsilon(
                config, state.update_step, state.actor.keys.shape[0], axes
            )
            dist_extra = eps[:, None]
        with jax.named_scope("rollout"):
            actor, rollout, stats = unroll(
                napply, state.actor_params, env, state.actor,
                config.unroll_len, dist=dist, reward_scale=config.reward_scale,
                step_cost=config.step_cost,
                dist_extra=dist_extra,
                return_discount=(
                    config.gamma if config.normalize_returns else 0.0
                ),
                opponent_params=(
                    state.opponent_params if config.selfplay else None
                ),
            )
        if config.normalize_returns:
            # Scale this fragment's rewards by the PRE-update return std
            # (mean is NOT subtracted — shifting rewards changes the MDP);
            # fold the fragment's discounted-return stream in afterwards.
            ret_var = state.ret_stats.m2 / state.ret_stats.count
            rollout = rollout.replace(
                rewards=rollout.rewards
                * jax.lax.rsqrt(jnp.maximum(ret_var, 1e-8))
            )

        if ppo_multipass:
            with jax.named_scope("ppo_multipass"):
                params, opt_state, loss, grad_norm, metrics = _ppo_multipass(
                    config, napply, optimizer, dist,
                    state.params, state.opt_state, rollout, state.update_step,
                    axes=axes, member_seed=member_seed,
                )
        else:
            # shard_map autodiff semantics (jax>=0.8 vma tracking): the
            # gradient of a REPLICATED input (params) w.r.t. a device-varying
            # loss is automatically psum'd across the mesh axis during
            # transposition. So we scale the per-shard loss by 1/axis_size —
            # the implicit psum of local-mean gradients then yields exactly
            # the global-batch-mean gradient, with no explicit pmean(grads)
            # (which would double-count: verified 8x inflation on the
            # 8-device CPU mesh, tests/test_learner).
            n_accum = max(config.grad_accum, 1)

            def scaled_loss(p, frag):
                loss, metrics = _algo_loss(
                    config, napply, p, frag,
                    axis_name=axes or None, dist=dist,
                    target_params=state.actor_params,
                    entropy_coef=entropy_coef_at(config, state.update_step),
                )
                return loss / (_axis_size(axes) * n_accum), (loss, metrics)

            if n_accum == 1:
                with jax.named_scope("loss_and_grad"):
                    (_, (loss, metrics)), grads = jax.value_and_grad(
                        scaled_loss, has_aux=True
                    )(state.params, rollout)
            else:
                with jax.named_scope("loss_and_grad_accum"):
                    grads, loss, metrics = accumulate_grads(
                        scaled_loss, state.params, rollout, n_accum
                    )
            grads = reduce_grads(grads, axes, config)
            with jax.named_scope("optimizer"):
                grad_norm = optax.global_norm(grads)
                updates, opt_state = optimizer.update(
                    grads, state.opt_state, state.params
                )
                params = optax.apply_updates(state.params, updates)

        metrics = _pmean(metrics, axes)
        loss = _pmean(loss, axes)

        step = state.update_step + 1
        # "publish": the copy of the parameters the actors see. A label and
        # no barrier: where XLA fuses the optimizer's passes into this
        # select, the fusion carries one of the two names (PERF.md §5).
        with jax.named_scope("publish"):
            if (
                config.algo in ("impala", "qlearn")
                and config.actor_staleness > 1
            ):
                # IMPALA: the stale behaviour-policy copy. Q-learning: the
                # SAME stale copy doubles as the target network θ⁻ (and the
                # ε-greedy behaviour net), so actor_staleness is the
                # target-update period.
                refresh = (step % config.actor_staleness) == 0
                actor_params = jax.tree.map(
                    lambda new, old: jnp.where(refresh, new, old),
                    params, state.actor_params,
                )
            else:
                # On-policy (and staleness<=1 IMPALA): actors always see the
                # newest weights next fragment — one full update of lag, the
                # minimum true-IMPALA staleness.
                actor_params = params

        obs_stats = state.obs_stats
        if obs_stats is not None:
            with jax.named_scope("obs_stats"):
                obs_stats = update_stats(obs_stats, rollout.obs, axes)
        ret_stats = state.ret_stats
        if ret_stats is not None:
            ret_stats = update_stats(ret_stats, rollout.disc_returns, axes)

        if config.selfplay:
            # Ladder refresh: the frozen rival becomes the CURRENT policy
            # every selfplay_refresh updates (same select pattern as the
            # actor_params staleness refresh).
            promote = (step % max(config.selfplay_refresh, 1)) == 0
            opponent_params = jax.tree.map(
                lambda new, old: jnp.where(promote, new, old),
                params, state.opponent_params,
            )
            if actor.opp_core is not None:
                # The rival's recurrent carry belongs to the OLD snapshot;
                # on promotion zero it (mid-episode amnesia beats feeding
                # the new params a foreign hidden state).
                keep = 1.0 - promote.astype(jnp.float32)
                actor = actor.replace(
                    opp_core=jax.tree.map(
                        lambda c: c * keep, actor.opp_core
                    )
                )
        else:
            opponent_params = state.opponent_params  # None subtree

        metrics = dict(metrics)
        metrics["loss"] = loss
        metrics["grad_norm"] = grad_norm
        metrics["episode_return_sum"] = _psum(stats.completed_return_sum, axes)
        metrics["episode_length_sum"] = _psum(stats.completed_length_sum, axes)
        metrics["episode_count"] = _psum(stats.completed_count, axes)

        new_state = TrainState(
            params=params,
            actor_params=actor_params,
            opt_state=opt_state,
            actor=actor,
            update_step=step,
            obs_stats=obs_stats,
            ret_stats=ret_stats,
            opponent_params=opponent_params,
        )
        return new_state, metrics

    return train_step


class Learner:
    """Owns the compiled train step and the train state lifecycle.

    Name parity with the reference's ``Learner`` (BASELINE.json:5); its
    ``update`` method is one mesh-wide fused step.
    """

    def __init__(
        self,
        config: Config,
        env: Environment,
        model,
        mesh: Mesh,
    ):
        config = resolve_scan_impl(config, mesh)
        self.config = config
        self.env = env
        self.model = model
        self.mesh = mesh
        self.optimizer = make_optimizer(config)

        # Eager geometry validation (clearer than a trace-time failure).
        validate_recurrent_config(config, model)
        validate_qlearn_config(config)
        validate_selfplay_config(config, env, model)
        if config.updates_per_call < 1:
            raise ValueError(
                f"updates_per_call={config.updates_per_call} must be >= 1"
            )
        dp = dp_size(mesh)
        if config.num_envs % dp:
            raise ValueError(
                f"num_envs={config.num_envs} not divisible by dp={dp}"
            )
        validate_ppo_geometry(
            config, config.num_envs // dp, "per-device",
            recurrent=is_recurrent(model),
        )
        validate_grad_accum_config(config, config.num_envs // dp)

        spec = state_partition_spec(dp_axes(mesh))
        body = make_train_step(config, env, model.apply, self.optimizer, mesh)

        wrapped = fuse_updates(body, config.updates_per_call)

        self._step = jax.jit(
            shard_map(
                wrapped, mesh=mesh, in_specs=(spec,), out_specs=(spec, P()),
                **fused_smap_opts(config),
            ),
            donate_argnums=(0,) if config.donate_buffers else (),
        )
        self._updated = False

    def init_state(self, seed: int) -> TrainState:
        """Build the initial TrainState with proper shardings."""
        cfg = self.config
        dp = dp_size(self.mesh)
        if cfg.num_envs % dp:
            raise ValueError(
                f"num_envs={cfg.num_envs} not divisible by dp={dp}"
            )
        key = jax.random.PRNGKey(seed)
        pkey, akey = derive_init_keys(key)
        params = init_params(self.model, self.env, pkey)
        opt_state = self.optimizer.init(params)

        # Per-device actor init inside shard_map so env states are born
        # sharded (no host-side giant arrays for big env batches).
        local_envs = cfg.num_envs // dp
        axes = dp_axes(self.mesh)

        def shard_actor_init(keys):
            return actor_init(
                self.env, local_envs, keys[0], model=self.model,
                track_returns=cfg.normalize_returns,
                selfplay=cfg.selfplay,
            )

        per_device_keys = jax.random.split(akey, dp)
        actor = jax.jit(
            shard_map(
                shard_actor_init,
                mesh=self.mesh,
                in_specs=(P(axes),),
                out_specs=P(axes),
            )
        )(per_device_keys)

        obs_stats = (
            init_stats(self.env.spec.obs_shape) if cfg.normalize_obs else None
        )
        ret_stats = init_stats(()) if cfg.normalize_returns else None
        # Place replicated leaves explicitly on the mesh.
        from jax.sharding import NamedSharding

        rep = NamedSharding(self.mesh, P())
        params = jax.device_put(params, rep)

        def copy(tree):
            # device_put of an already-placed array returns the SAME
            # buffer, and a donated TrainState (config.donate_buffers) may
            # not name one buffer twice ("INVALID_ARGUMENT: Attempt to
            # donate the same buffer twice in Execute()").
            return jax.tree.map(jnp.copy, tree)

        return TrainState(
            params=params,
            actor_params=copy(params),
            opt_state=jax.device_put(opt_state, rep),
            actor=actor,
            update_step=jax.device_put(jnp.zeros((), jnp.int32), rep),
            obs_stats=(
                None if obs_stats is None else jax.device_put(obs_stats, rep)
            ),
            ret_stats=(
                None if ret_stats is None else jax.device_put(ret_stats, rep)
            ),
            opponent_params=copy(params) if cfg.selfplay else None,
        )

    def update(self, state: TrainState):
        """One train step: rollout + loss + pmean(grads) + Adam. Donates
        ``state``. The span covers the dispatch; the first call, which
        traces, lowers and compiles (or loads) the step, is also the
        process record's ``setup.first_update``."""
        if self._updated:
            with trace.span(span_names.LEARNER_UPDATE):
                return self._step(state)
        else:
            self._updated = True
            with introspect.phase(span_names.SETUP_FIRST_UPDATE), trace.span(
                span_names.LEARNER_UPDATE
            ):
                return self._step(state)
