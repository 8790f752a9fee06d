"""Anakin rollout: envs resident in HBM, unrolled with ``vmap`` + ``lax.scan``.

This is the TPU-native replacement for the reference's per-thread
``ActorWorker.run`` loop (BASELINE.json:5): instead of N Python threads each
stepping one env, a single XLA program steps B envs in lockstep for T steps.
The policy forward, action sample, env physics, auto-reset, and trajectory
write all fuse into one compiled scan — zero host round-trips per fragment.

PRNG design: every env slot carries its own raw uint32 key ([B, 2]), so the
whole ``ActorState`` pytree shards over the mesh's ``dp`` axis with a single
``P('dp')`` prefix spec — no replicated-key divergence problems inside
``shard_map`` (SURVEY.md §7.3 "mesh-size-agnostic").
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from flax import struct

from asyncrl_tpu.envs.core import Environment
from asyncrl_tpu.models.networks import (
    is_recurrent,
    reset_core,
    settle_core,
)
from asyncrl_tpu.rollout.buffer import EpisodeStats, Rollout


@struct.dataclass
class ActorState:
    """Carry for the rollout scan: env states + current obs + per-env PRNG
    keys + running per-env episode accumulators (device-resident metrics).
    ``core`` is the policy's recurrent (c, h) carry for LSTM agents — None
    for feed-forward policies (an empty pytree subtree, so all partition
    specs apply unchanged)."""

    env_state: Any  # vmapped env-state pytree, leading dim B
    obs: jax.Array  # [B, *obs_shape]
    keys: jax.Array  # [B, 2] uint32 raw PRNG keys
    running_return: jax.Array  # [B] f32
    running_length: jax.Array  # [B] f32
    # Per-env DISCOUNTED return accumulator (G = discount*G + r, reset at
    # done) — the statistic behind normalize_returns reward scaling
    # (VecNormalize/Brax recipe). None (empty subtree) unless tracking:
    # an always-present leaf would break restore of checkpoints saved
    # before the field existed, even with the feature off.
    disc_return: Any = None  # [B] f32 when tracking
    core: Any = None  # recurrent policy carry, leading dim B
    # Frozen-rival recurrent carry (selfplay x lstm): the opponent snapshot
    # plays through its OWN (c, h), reset at episode ends like the agent's
    # and zeroed on ladder promotion (the old carry means nothing to the
    # newly frozen params). None unless both selfplay and recurrent — the
    # empty-subtree trick keeps old checkpoints restorable, like
    # disc_return above.
    opp_core: Any = None


def actor_init(
    env: Environment,
    num_envs: int,
    seed_key: jax.Array,
    model=None,
    track_returns: bool = False,
    selfplay: bool = False,
) -> ActorState:
    init_keys, carry_keys = jax.random.split(seed_key)
    env_keys = jax.random.split(init_keys, num_envs)
    env_state = jax.vmap(env.init)(env_keys)
    obs = jax.vmap(env.observe)(env_state)
    zeros = jnp.zeros((num_envs,), jnp.float32)
    core = (
        model.initial_core(num_envs)
        if model is not None and is_recurrent(model)
        else None
    )
    return ActorState(
        env_state=env_state,
        obs=obs,
        keys=jax.random.split(carry_keys, num_envs),
        running_return=zeros,
        running_length=zeros,
        disc_return=zeros if track_returns else None,
        core=core,
        opp_core=core if selfplay and core is not None else None,
    )


def unroll(
    apply_fn: Callable[[Any, jax.Array], tuple[jax.Array, jax.Array]],
    params: Any,
    env: Environment,
    actor_state: ActorState,
    unroll_len: int,
    dist=None,
    reward_scale: float = 1.0,
    step_cost: float = 0.0,
    dist_extra: jax.Array | None = None,
    return_discount: float = 0.0,
    opponent_params: Any = None,
) -> tuple[ActorState, Rollout, EpisodeStats]:
    """Roll the policy forward ``unroll_len`` steps over the env batch.

    ``apply_fn(params, obs[B]) -> (dist_params[B, P], value[B])``. The value
    head output is discarded here (the learner recomputes values under its
    own params); only the behaviour log-prob is recorded — exactly what
    V-trace needs (SURVEY.md §3.3). ``dist`` (ops.distributions) interprets
    the policy head; defaults to the spec's distribution.

    ``dist_extra`` ([B, E], optional) is concatenated onto the model's
    dist_params at every step — the channel for per-env, training-schedule-
    dependent behaviour knobs the frozen ``dist`` object can't carry (the
    Q-learning family's annealed per-env ε rides here, constant across the
    fragment).

    The discounted-return stream ``G_t = return_discount * G_{t-1} + r_t``
    (reset at episode ends; built from the learner's SCALED reward view)
    records into ``rollout.disc_returns`` whenever the actor state tracks
    it (``actor_init(track_returns=True)``) — ONE predicate, shared with
    the learner's stats fold, so the carry, the stream, and the consumer
    cannot disagree (a ``return_discount`` of 0 degrades to reward-std
    tracking rather than crashing).

    ``opponent_params`` (self-play, Config.selfplay): the env must be a
    duel env (``observe_opponent`` + ``step_duel``); each step the SAME
    ``apply_fn`` evaluates the frozen opponent snapshot on the mirrored
    observation and its sampled action drives the rival paddle. The
    fragment records only the AGENT's side (actions/logp/rewards), so
    every learner consumes it unchanged. When None (the default), the
    PRNG stream and the compiled program are bit-identical to before the
    feature existed.
    """
    if dist is None:
        from asyncrl_tpu.ops import distributions

        dist = distributions.for_spec(env.spec)

    recurrent = actor_state.core is not None
    track_returns = actor_state.disc_return is not None

    selfplay = opponent_params is not None

    def step_fn(carry: ActorState, _):
        n_keys = 4 if selfplay else 3
        split = jax.vmap(lambda k: jax.random.split(k, n_keys))(carry.keys)
        next_keys, act_keys, step_keys = split[:, 0], split[:, 1], split[:, 2]

        # The two scopes split the step's device time in a profile
        # (metadata only: the compiled ops are the same without them).
        with jax.named_scope("actor_forward"):
            if recurrent:
                dist_params, _, core = apply_fn(
                    params, carry.obs, carry.core
                )
            else:
                dist_params, _ = apply_fn(params, carry.obs)
                core = None
            if dist_extra is not None:
                dist_params = jnp.concatenate(
                    [dist_params, dist_extra.astype(dist_params.dtype)],
                    axis=-1,
                )
            actions = jax.vmap(dist.sample)(act_keys, dist_params)
            behaviour_logp = dist.logp(dist_params, actions)

        if selfplay:
            with jax.named_scope("actor_forward"):
                opp_obs = jax.vmap(env.observe_opponent)(carry.env_state)
                if carry.opp_core is not None:
                    opp_dist_params, _, opp_core = apply_fn(
                        opponent_params, opp_obs, carry.opp_core
                    )
                else:
                    opp_dist_params, _ = apply_fn(opponent_params, opp_obs)
                    opp_core = None
                if dist_extra is not None:
                    # The rival samples under the SAME behaviour knobs as
                    # the agent (e.g. the Q-family's annealed ε) — without
                    # this, an EpsilonGreedy dist would default the
                    # opponent to ε=0 and the frozen snapshot would play
                    # deterministic argmax.
                    opp_dist_params = jnp.concatenate(
                        [
                            opp_dist_params,
                            dist_extra.astype(opp_dist_params.dtype),
                        ],
                        axis=-1,
                    )
                opp_actions = jax.vmap(dist.sample)(
                    split[:, 3], opp_dist_params
                )
            with jax.named_scope("env_step"):
                env_state, ts = jax.vmap(env.step_duel)(
                    carry.env_state, actions, opp_actions, step_keys
                )
        else:
            with jax.named_scope("env_step"):
                env_state, ts = jax.vmap(env.step)(
                    carry.env_state, actions, step_keys
                )
            opp_core = None

        if recurrent:
            core = reset_core(core, ts.done)
            if opp_core is not None:
                opp_core = reset_core(opp_core, ts.done)

        done_f = ts.done.astype(jnp.float32)
        ep_return = carry.running_return + ts.reward
        ep_length = carry.running_length + 1.0
        # Discounted-return stream for reward normalization (scaled view).
        learner_reward = (ts.reward - step_cost) * reward_scale
        # The return-std stream deliberately EXCLUDES step_cost (scaled raw
        # rewards only): the host backends' actor-built streams cannot
        # reconstruct the cost's time-since-reset-dependent offset, so both
        # paths track the same cost-free stream and stay comparable; the
        # constant living cost is not what return normalization exists to
        # equalize anyway.
        g = (
            carry.disc_return * return_discount + ts.reward * reward_scale
            if track_returns
            else None
        )
        new_carry = ActorState(
            env_state=env_state,
            obs=ts.obs,
            keys=next_keys,
            running_return=ep_return * (1.0 - done_f),
            running_length=ep_length * (1.0 - done_f),
            disc_return=g * (1.0 - done_f) if track_returns else None,
            core=core,
            opp_core=opp_core,
        )
        out = (
            carry.obs,
            actions,
            behaviour_logp,
            learner_reward,  # learner's view (cost + scale); metrics stay raw
            ts.terminated,
            ts.truncated,
            ep_return * done_f,
            ep_length * done_f,
            done_f,
            g,
        )
        return new_carry, out

    final_state, outs = jax.lax.scan(step_fn, actor_state, None, length=unroll_len)
    if recurrent:
        # Inside the scan a reset may wait for the policy's next read of the
        # carry; what leaves is settled (the next fragment's ``init_core``,
        # ``agent.state``, a checkpoint). The identity for an LSTM's carry.
        final_state = final_state.replace(
            core=settle_core(final_state.core),
            opp_core=settle_core(final_state.opp_core),
        )
    (obs, actions, behaviour_logp, rewards, terminated, truncated,
     done_returns, done_lengths, dones, disc_returns) = outs

    rollout = Rollout(
        obs=obs,
        actions=actions,
        behaviour_logp=behaviour_logp,
        rewards=rewards,
        terminated=terminated,
        truncated=truncated,
        bootstrap_obs=final_state.obs,
        # Fragment-initial recurrent carry (behaviour policy's), for the
        # learner's re-forward — the IMPALA "stale core state" recipe.
        init_core=actor_state.core,
        disc_returns=disc_returns,
    )
    stats = EpisodeStats(
        completed_return_sum=jnp.sum(done_returns),
        completed_length_sum=jnp.sum(done_lengths),
        completed_count=jnp.sum(dones),
    )
    return final_state, rollout, stats
