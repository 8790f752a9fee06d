"""Learner that consumes host-produced rollout fragments (Sebulba path).

The Anakin ``Learner`` (learn/learner.py) fuses rollout + update into one XLA
program because its envs live in HBM. The Sebulba and ``cpu_async`` backends
instead produce ``Rollout`` fragments on the host (C++ env pools / gymnasium /
Python actor threads — SURVEY.md §7.2 M3-M4), so this learner exposes the
other half only: ``update(state, rollout)`` — one jitted ``shard_map`` over
the mesh that runs the Anakin step's own update half
(``learner.fragment_update``: the learner forward, the configured loss, the
gradient all-reduce, the optimizer) around what is this learner's alone: its
target network, the IMPACT anchor and the NaN guard. The rollout arrives
batch-sharded (``[T, B]`` with B split over dp), mirroring how the
reference's learner consumed queue-batched fragments (BASELINE.json:5;
SURVEY.md §3.2).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from asyncrl_tpu.envs.core import EnvSpec
from asyncrl_tpu.learn.learner import (
    fold_stats,
    fragment_update,
    fuse_updates,
    fused_smap_opts,
    make_optimizer,
    resolve_scan_impl,
    scale_rewards,
    stale_copy,
    validate_grad_accum_config,
    validate_qlearn_config,
    validate_recurrent_config,
)
from asyncrl_tpu.learn.replay import validate_replay_config
from asyncrl_tpu.models.networks import is_recurrent
from asyncrl_tpu.obs import introspect
from asyncrl_tpu.obs import spans as span_names
from asyncrl_tpu.obs import trace
from asyncrl_tpu.ops import distributions
from asyncrl_tpu.ops.normalize import init_stats, normalizing_apply
from asyncrl_tpu.parallel.mesh import (
    TIME_AXIS,
    dp_axes,
    dp_size,
    shard_map,
)
from asyncrl_tpu.rollout.buffer import Rollout
from asyncrl_tpu.utils.config import Config


@struct.dataclass
class LearnerState:
    """Learner-side train state for host-rollout backends.

    Unlike the Anakin ``TrainState`` there is no ``actor`` (env states live
    on the host) and no ``actor_params`` (weight publishing to host actors
    goes through ``rollout.sebulba.ParamStore``). ``target_params`` is the
    Q-learning family's target network θ⁻ (None — an empty subtree — for
    the policy-gradient algos): unlike Anakin, where the in-program
    actor_params copy doubles as the target, the host path's behaviour
    params live outside the jit, so the target needs its own slot.
    """

    params: Any
    opt_state: Any
    update_step: jax.Array  # int32 scalar
    target_params: Any = None
    # Running observation-normalization stats (ops/normalize.py); None
    # unless config.normalize_obs. Published to host actors alongside the
    # params (SebulbaTrainer bundles them through the ParamStore).
    obs_stats: Any = None
    # Running scalar stats of the per-env discounted return (reward
    # normalization, config.normalize_returns); None when disabled.
    ret_stats: Any = None


def learner_state_spec() -> LearnerState:
    return LearnerState(
        params=P(), opt_state=P(), update_step=P(), target_params=P(),
        obs_stats=P(), ret_stats=P(),
    )


def rollout_partition_spec(
    axes: tuple[str, ...], time_axis: str | None = None, stacked: bool = False
) -> Rollout:
    """Time-major [T, B, ...] fragments, batch dim sharded over all
    data-parallel axes; with ``time_axis`` set (sequence parallelism,
    SURVEY.md §5.7) the T dim shards over it too. ``stacked`` prepends an
    unsharded leading axis for [K, T, B, ...] fused-update stacks
    (``updates_per_call``). ``init_core``'s P is a pytree PREFIX: it
    applies to every leaf of the recurrent (c, h) carry when present, and
    to nothing for feed-forward fragments (None = empty subtree)."""
    lead = (None,) if stacked else ()
    tm = P(*lead, time_axis, axes)
    bf = P(*lead, axes)
    return Rollout(
        obs=tm,
        actions=tm,
        behaviour_logp=tm,
        rewards=tm,
        terminated=tm,
        truncated=tm,
        bootstrap_obs=bf,
        init_core=bf,
        disc_returns=tm,
    )


def rollout_sharding(
    mesh: Mesh, rollout: Rollout, stacked: bool = False
) -> Rollout:
    """NamedShardings for ``jax.device_put`` of one host fragment (or a
    [K, ...] fused stack) — built against the fragment's own pytree
    structure (device_put needs an exact structural match, unlike
    shard_map's prefix specs)."""
    axes = dp_axes(mesh)
    time_axis = TIME_AXIS if TIME_AXIS in mesh.axis_names else None
    lead = (None,) if stacked else ()
    time_major = NamedSharding(mesh, P(*lead, time_axis, axes))
    batch_first = NamedSharding(mesh, P(*lead, axes))
    return Rollout(
        obs=time_major,
        actions=time_major,
        behaviour_logp=time_major,
        rewards=time_major,
        terminated=time_major,
        truncated=time_major,
        bootstrap_obs=batch_first,
        init_core=(
            None
            if rollout.init_core is None
            else jax.tree.map(lambda _: batch_first, rollout.init_core)
        ),
        disc_returns=(
            None if rollout.disc_returns is None else time_major
        ),
    )


class RolloutLearner:
    """Compiled ``update(state, rollout)`` step + state lifecycle.

    Same loss/optimizer machinery as the Anakin learner (single source of
    truth in learn/learner.py), minus the on-device unroll.
    """

    def __init__(self, config: Config, spec: EnvSpec, model, mesh: Mesh):
        validate_recurrent_config(config, model)
        validate_qlearn_config(config)
        validate_replay_config(config)
        # IMPACT mode (learn/replay.py; arXiv:1912.00167): with the
        # device replay ring armed, every update — fresh or replayed —
        # runs under the clipped-target-network importance anchor, and
        # the target net refreshes every target_update_period updates.
        # Off (the default) traces NONE of it: bit-identical program.
        replay_mode = config.replay_slabs > 0
        # Host fragments arrive with the FULL env batch on the sharded-in
        # time/batch layout; the per-shard env count the chunker sees is
        # num_envs / (product of dp axes).
        validate_grad_accum_config(
            config, config.num_envs // max(dp_size(mesh), 1)
        )
        if config.selfplay:
            raise NotImplementedError(
                "selfplay is Anakin-only (backend='tpu'): host actor "
                "threads have no opponent-snapshot channel"
            )
        time_sharded = TIME_AXIS in mesh.axis_names and mesh.shape[TIME_AXIS] > 1
        if time_sharded:
            sp = mesh.shape[TIME_AXIS]
            if config.unroll_len % sp:
                raise ValueError(
                    f"unroll_len={config.unroll_len} not divisible by the "
                    f"time-shard axis sp={sp}"
                )
            if is_recurrent(model):
                raise NotImplementedError(
                    "recurrent cores cannot be time-sharded: an LSTM carry "
                    "composes nonlinearly, so unlike the affine V-trace/GAE "
                    "recurrences it has no exact parallel decomposition — "
                    "a time-sharded LSTM degenerates to a pipeline that "
                    "re-serializes the sp axis (full rationale: "
                    "docs/ARCHITECTURE.md, 'Recurrent cores are "
                    "deliberately NOT time-shardable'). Use a dp-only mesh "
                    "for core='lstm'"
                )
            # (qlearn's DRQN is refused with every recurrent core.) PPO's
            # minibatch geometry is not checked here: this learner never
            # knows the fragment's env batch — the trainer checks it with
            # the real B, and _ppo_multipass the local slice at trace time.
        config = resolve_scan_impl(config, mesh)
        self.config = config
        self.spec = spec
        self.model = model
        self.mesh = mesh
        self.optimizer = make_optimizer(config)
        dist = distributions.for_config(config, spec)
        apply_fn = model.apply
        optimizer = self.optimizer

        axes = dp_axes(mesh)
        time_axis = TIME_AXIS if time_sharded else None
        # Gradient/metric reduction spans every axis the fragment is
        # sharded over: batch axes always, plus the time axis when the
        # fragment's T dim is sequence-parallel.
        reduce_axes = axes + ((TIME_AXIS,) if time_sharded else ())
        # Divergence NaN-guard (runtime/durability.py rollback policy):
        # armed with the policy, a non-finite loss/grad_norm HOLDS the
        # entire state — params, opt state, target net, normalization
        # stats, and the update counter — via a device-side select, so a
        # poisoned update never lands and the guard costs no host sync.
        # The metrics still report the bad loss (the nonfinite_loss
        # detector must fire) plus a ``nonfinite_skip`` flag the trainer
        # accumulates into the cumulative ``nonfinite_skips`` counter.
        # Off (the default) the select never traces: bit-identical
        # program to the pre-rollback learner.
        nan_guard = config.rollback_bad_windows > 0

        def update_body(state: LearnerState, rollout: Rollout):
            # Observation normalization (ops/normalize.py): this step's
            # forwards all use the pre-update stats; the fragment's obs
            # fold in afterwards. Reward normalization likewise scales this
            # fragment by the PRE-update return std.
            napply = normalizing_apply(apply_fn, state.obs_stats)
            rollout = scale_rewards(config, rollout, state.ret_stats)
            target_kl = None
            if replay_mode:
                # IMPACT-style ratio anchoring: the slowly-updated
                # target network's log-probs FLOOR the behaviour
                # log-prob, so the V-trace importance ratio rho = pi/mu
                # never exceeds replay_rho_clip * pi/pi_target — a slab
                # reused across many updates (its mu frozen ever further
                # in the past) keeps a bounded correction anchored to a
                # policy at most target_update_period updates old,
                # instead of an unbounded one anchored to a dead mu.
                # Constant w.r.t. the differentiated params (target
                # forward under stop_gradient, applied before the loss).
                t_logits, _ = napply(state.target_params, rollout.obs)
                target_logp = jax.lax.stop_gradient(
                    dist.logp(t_logits, rollout.actions)
                )
                # Behaviour-vs-target divergence proxy E_mu[log mu -
                # log pi_target] (the existing ``kl`` aux's recipe, with
                # the target net in the learner's seat): it bounds how
                # much anchoring the clip below is actually doing.
                target_kl = jnp.mean(
                    rollout.behaviour_logp - target_logp
                )
                rollout = rollout.replace(
                    behaviour_logp=jnp.maximum(
                        rollout.behaviour_logp,
                        target_logp - math.log(config.replay_rho_clip),
                    )
                )
            # (``fused_scan``'s whole-T kernel has no sharded form: with a
            # time axis the loss takes the distributed recurrences.)
            params, opt_state, loss, grad_norm, metrics = fragment_update(
                config, napply, optimizer, dist, state.params,
                state.opt_state, rollout, state.update_step,
                state.target_params, reduce_axes, time_axis,
            )
            metrics = dict(metrics)
            metrics["loss"] = loss
            metrics["grad_norm"] = grad_norm
            if target_kl is not None:
                metrics["target_kl"] = jax.lax.pmean(
                    target_kl, reduce_axes
                )
            step = state.update_step + 1
            target_params = state.target_params  # None subtree unless held
            if target_params is not None:
                # The Q target net refreshes every actor_staleness updates
                # (the Anakin learner's actor_params recipe); the IMPACT
                # anchor on its own period, so it is never more than
                # target_update_period updates stale.
                period = (
                    config.actor_staleness
                    if config.algo == "qlearn"
                    else config.target_update_period
                )
                target_params = stale_copy(
                    step, period, params, target_params
                )
            obs_stats, ret_stats = fold_stats(
                state.obs_stats, state.ret_stats, rollout, reduce_axes
            )
            new_state = LearnerState(
                params=params,
                opt_state=opt_state,
                update_step=step,
                target_params=target_params,
                obs_stats=obs_stats,
                ret_stats=ret_stats,
            )
            if nan_guard:
                finite = jnp.isfinite(metrics["loss"]) & jnp.isfinite(
                    metrics["grad_norm"]
                )
                new_state = jax.tree.map(
                    lambda new, old: jnp.where(finite, new, old),
                    new_state, state,
                )
                metrics["nonfinite_skip"] = 1.0 - finite.astype(jnp.float32)
            return new_state, metrics

        # updates_per_call > 1: the trainer stacks K queued fragments
        # [K, T, B, ...] and one dispatch applies them in arrival order —
        # identical training semantics, one host->device round trip
        # instead of K. Metrics come back stacked [K].
        K = config.updates_per_call
        update_body = fuse_updates(update_body, K, stacked=True)

        sspec = learner_state_spec()
        # NEVER donate the STATE, regardless of config.donate_buffers: the
        # params in it are published to concurrently-running actor threads
        # via ParamStore; donation would delete buffers mid-inference
        # ("Array has been deleted" in every actor). The Anakin learner can
        # donate because its params never escape the update loop.
        # The ROLLOUT argument is donatable under config.donate_buffers:
        # it is consumed exactly once, and the trainer's drain never
        # touches the device fragment after dispatching the update (the
        # staging ring gates host-slab reuse on the update's OUTPUT, so
        # deletion of the consumed input is invisible to it).
        self._step = jax.jit(
            shard_map(
                update_body,
                mesh=mesh,
                in_specs=(
                    sspec,
                    rollout_partition_spec(axes, time_axis, stacked=K > 1),
                ),
                out_specs=(sspec, P()),
                **fused_smap_opts(config),
            ),
            donate_argnums=(1,) if config.donate_buffers else (),
        )
        if config.introspect:
            # Compile accounting (obs/introspect.py): the learner's entry
            # point compiles once per fragment geometry — any further
            # compile is a silent recompile the bench numbers would
            # otherwise hide. The state argument's shapes are fixed, so
            # only the rollout argument is signature-walked. Reads the
            # RESOLVED flag (the trainers fold ASYNCRL_INTROSPECT in at
            # construction) — never re-consults the environment.
            self._step = introspect.instrument(
                self._step, "learner.update",
                counters=("compiles", "learner_recompile"),
                ignore_argnums=(0,),
            )
        # Fragment structure is fixed for this trainer (ff vs recurrent), so
        # the device_put sharding pytree is built once, not per update.
        template = Rollout(
            obs=None, actions=None, behaviour_logp=None, rewards=None,
            terminated=None, truncated=None, bootstrap_obs=None,
            init_core=model.initial_core(1) if is_recurrent(model) else None,
            # Placeholder non-None leaf: the stream must get its time-major
            # sharding like every other fragment field (a None here would
            # device_put it uncommitted).
            disc_returns=0.0 if config.normalize_returns else None,
        )
        self._rollout_sharding = rollout_sharding(mesh, template, stacked=K > 1)
        self._updated = False

    # ---------------------------------------------------------------- state

    def init_state(self, seed: int) -> LearnerState:
        key = jax.random.PRNGKey(seed)
        dummy_obs = jnp.zeros((1, *self.spec.obs_shape), self.spec.obs_dtype)
        if is_recurrent(self.model):
            params = self.model.init(
                key, dummy_obs, self.model.initial_core(1)
            )
        else:
            params = self.model.init(key, dummy_obs)
        opt_state = self.optimizer.init(params)
        rep = NamedSharding(self.mesh, P())
        params = jax.device_put(params, rep)
        return LearnerState(
            params=params,
            opt_state=jax.device_put(opt_state, rep),
            update_step=jax.device_put(jnp.zeros((), jnp.int32), rep),
            # qlearn — and the IMPACT replay anchor — start the target
            # net equal to the online net (device arrays are immutable,
            # so sharing the reference is safe).
            target_params=(
                params
                if self.config.algo == "qlearn"
                or self.config.replay_slabs > 0
                else None
            ),
            obs_stats=(
                jax.device_put(init_stats(self.spec.obs_shape), rep)
                if self.config.normalize_obs
                else None
            ),
            ret_stats=(
                jax.device_put(init_stats(()), rep)
                if self.config.normalize_returns
                else None
            ),
        )

    # --------------------------------------------------------------- update

    def put_rollout(self, rollout: Rollout) -> Rollout:
        """Transfer a host (numpy) fragment to the mesh, batch-sharded.

        The span is the DISPATCH cost only (device_put is async); the
        unhidden transfer time shows up in the trainer's
        ``learner.h2d_wait`` span around its explicit barrier."""
        with trace.span(span_names.LEARNER_H2D):
            return jax.device_put(rollout, self._rollout_sharding)

    def update(self, state: LearnerState, rollout: Rollout):
        """One gradient step on a device-resident fragment. The span
        covers the jitted dispatch (plus, on the CPU backend where
        dispatch is effectively synchronous, the compute itself); the
        first call, which traces, lowers and compiles (or loads) the step,
        is also the process record's ``setup.first_update``."""
        if self._updated:
            with trace.span(span_names.LEARNER_UPDATE):
                return self._step(state, rollout)
        else:
            self._updated = True
            with introspect.phase(span_names.SETUP_FIRST_UPDATE), trace.span(
                span_names.LEARNER_UPDATE
            ):
                return self._step(state, rollout)
