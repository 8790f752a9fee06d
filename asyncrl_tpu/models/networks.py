"""Policy/value networks for the reference's workload suites (SURVEY.md §1.2
L2): MLP torso for classic control / continuous-control stand-ins, Nature-CNN
and IMPALA-ResNet torsos for pixel suites (Atari/Procgen), with a shared
categorical policy head + value head.

TPU notes: matmuls run in bfloat16 when ``compute_dtype`` says so (params and
loss math stay f32 — MXU-friendly mixed precision); conv torsos use NHWC which
XLA:TPU prefers.
"""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from asyncrl_tpu.ops.max_pool import max_pool_3x3_s2

ORTHO = nn.initializers.orthogonal


class MLPTorso(nn.Module):
    hidden_sizes: Sequence[int] = (64, 64)
    compute_dtype: jnp.dtype = jnp.float32
    obs_rank: int = 1  # trailing dims that form one observation; flattened

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = x.reshape(*x.shape[: x.ndim - self.obs_rank], -1)
        x = x.astype(self.compute_dtype)
        for size in self.hidden_sizes:
            x = nn.Dense(size, dtype=self.compute_dtype, kernel_init=ORTHO(jnp.sqrt(2)))(x)
            x = nn.tanh(x)
        return x


class NatureCNN(nn.Module):
    """DQN/Nature conv torso (84x84 stacked frames)."""

    compute_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = x.astype(self.compute_dtype)
        x = nn.relu(nn.Conv(32, (8, 8), strides=(4, 4), dtype=self.compute_dtype)(x))
        x = nn.relu(nn.Conv(64, (4, 4), strides=(2, 2), dtype=self.compute_dtype)(x))
        x = nn.relu(nn.Conv(64, (3, 3), strides=(1, 1), dtype=self.compute_dtype)(x))
        x = x.reshape(*x.shape[:-3], -1)
        x = nn.relu(nn.Dense(512, dtype=self.compute_dtype, kernel_init=ORTHO(jnp.sqrt(2)))(x))
        return x


class ResidualBlock(nn.Module):
    channels: int
    compute_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        y = nn.relu(x)
        y = nn.Conv(self.channels, (3, 3), dtype=self.compute_dtype)(y)
        y = nn.relu(y)
        y = nn.Conv(self.channels, (3, 3), dtype=self.compute_dtype)(y)
        return x + y


class ImpalaCNN(nn.Module):
    """IMPALA deep ResNet torso (Espeholt et al. 2018 'large' network).

    ``remat=True`` rematerializes at RESIDUAL-BLOCK granularity
    (``nn.remat``): the backward pass keeps only stage-boundary
    activations live and recomputes each block's conv intermediates when
    its gradient is needed — block granularity bounds simultaneous
    liveness by one block's internals, where whole-torso remat would
    still need every conv activation alive at once during the replayed
    backward. Param tree is identical either way (lifted transform), so
    checkpoints swap freely between the two."""

    channels: Sequence[int] = (16, 32, 32)
    compute_dtype: jnp.dtype = jnp.float32
    remat: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = x.astype(self.compute_dtype)
        # Explicit names pin the param paths to the non-remat auto-naming
        # (nn.remat would otherwise prefix the class name with "Checkpoint",
        # silently forking the checkpoint format).
        block = nn.remat(ResidualBlock) if self.remat else ResidualBlock
        for i, ch in enumerate(self.channels):
            # jax name scopes, for a profile's per-section device time; not
            # flax scopes, so the param paths (and checkpoints) do not move.
            with jax.named_scope(f"section{i}"):
                x = nn.Conv(ch, (3, 3), dtype=self.compute_dtype)(x)
                with jax.named_scope("max_pool"):
                    x = max_pool_3x3_s2(x)
                x = block(
                    ch, self.compute_dtype, name=f"ResidualBlock_{2 * i}"
                )(x)
                x = block(
                    ch, self.compute_dtype, name=f"ResidualBlock_{2 * i + 1}"
                )(x)
        x = nn.relu(x)
        x = x.reshape(*x.shape[:-3], -1)
        x = nn.relu(nn.Dense(256, dtype=self.compute_dtype, kernel_init=ORTHO(jnp.sqrt(2)))(x))
        return x


def _apply_torso(module: nn.Module, obs: jax.Array) -> jax.Array:
    """Shared torso dispatch for the (Recurrent)ActorCritic modules; reads
    the torso hyperparameters off ``module``."""
    if module.torso == "mlp":
        # name= pins the remat param path to the auto name (see ImpalaCNN).
        cls = nn.remat(MLPTorso) if module.remat else MLPTorso
        return cls(
            module.hidden_sizes, module.compute_dtype, module.obs_rank,
            name="MLPTorso_0" if module.remat else None,
        )(obs)
    if module.torso == "nature_cnn":
        cls = nn.remat(NatureCNN) if module.remat else NatureCNN
        return cls(
            module.compute_dtype,
            name="NatureCNN_0" if module.remat else None,
        )(obs)
    if module.torso == "impala_cnn":
        return ImpalaCNN(
            module.channels, module.compute_dtype, remat=module.remat
        )(obs)
    raise ValueError(f"unknown torso {module.torso!r}")


def _apply_heads(
    module: nn.Module, h: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Shared policy + value heads: returns ``(dist_params, value)`` in
    float32 regardless of compute dtype, so losses and V-trace stay
    full-precision. For discrete envs ``dist_params`` are logits [..., A];
    for continuous envs concat(mean, log_std) [..., 2*D] with log_std a
    learned state-independent bias (the standard continuous-PPO head) —
    interpreted by ``ops.distributions``."""
    if module.continuous:
        mean = nn.Dense(
            module.action_dim, dtype=jnp.float32, kernel_init=ORTHO(0.01)
        )(h)
        log_std = module.param(
            "log_std", nn.initializers.zeros, (module.action_dim,), jnp.float32
        )
        dist_params = jnp.concatenate(
            [mean, jnp.broadcast_to(log_std, mean.shape)], axis=-1
        )
    else:
        dist_params = nn.Dense(
            module.num_actions, dtype=jnp.float32, kernel_init=ORTHO(0.01)
        )(h)
    value = nn.Dense(1, dtype=jnp.float32, kernel_init=ORTHO(1.0))(h)[..., 0]
    return dist_params.astype(jnp.float32), value.astype(jnp.float32)


class ActorCritic(nn.Module):
    """Shared-torso policy + value network (see ``_apply_heads`` for the
    head/output contract)."""

    num_actions: int
    torso: str = "mlp"  # "mlp" | "nature_cnn" | "impala_cnn"
    hidden_sizes: Sequence[int] = (64, 64)
    channels: Sequence[int] = (16, 32, 32)
    compute_dtype: jnp.dtype = jnp.float32
    obs_rank: int = 1  # rank of one observation (e.g. 3 for H,W,C images)
    continuous: bool = False
    action_dim: int = 0
    remat: bool = False

    @nn.compact
    def __call__(self, obs: jax.Array) -> tuple[jax.Array, jax.Array]:
        return _apply_heads(self, _apply_torso(self, obs))


def _q_head(module: nn.Module, h: jax.Array) -> jax.Array:
    """Shared Q head for the (Recurrent)QNetwork pair: one Q-value per
    action, f32 regardless of compute dtype (same drift-prevention role as
    ``_apply_heads`` for the actor-critic pair).

    ``module.dueling`` switches to the dueling decomposition (Wang et al.
    2016): Q(s,a) = V(s) + A(s,a) - mean_a A(s,a) — separate value and
    advantage streams, identifiable via the mean-advantage constraint."""
    if getattr(module, "dueling", False):
        value = nn.Dense(
            1, dtype=jnp.float32, kernel_init=ORTHO(1.0)
        )(h).astype(jnp.float32)
        adv = nn.Dense(
            module.num_actions, dtype=jnp.float32, kernel_init=ORTHO(0.01)
        )(h).astype(jnp.float32)
        return value + adv - jnp.mean(adv, axis=-1, keepdims=True)
    return nn.Dense(
        module.num_actions, dtype=jnp.float32, kernel_init=ORTHO(0.01)
    )(h).astype(jnp.float32)


def _zero_core(batch_size: int, core_size: int):
    """Zero LSTM (c, h) carry — shared by every recurrent module."""
    zeros = jnp.zeros((batch_size, core_size), jnp.float32)
    return (zeros, zeros)


class QNetwork(nn.Module):
    """Q-value network for the async Q-learning family (the A3C paper's
    value-based siblings — async one-step/n-step Q; PAPERS.md:8).

    Same torso zoo as ``ActorCritic``; the head emits one Q-value per action.
    Returns ``(q_values, max_q)`` so it satisfies the generic
    ``(dist_params, value)`` apply contract — the rollout interprets
    ``q_values`` through ``ops.distributions.EpsilonGreedy`` and the learner
    reads them directly in ``qlearn_loss``.
    """

    num_actions: int
    torso: str = "mlp"
    hidden_sizes: Sequence[int] = (64, 64)
    channels: Sequence[int] = (16, 32, 32)
    compute_dtype: jnp.dtype = jnp.float32
    obs_rank: int = 1
    dueling: bool = False
    remat: bool = False

    @nn.compact
    def __call__(self, obs: jax.Array) -> tuple[jax.Array, jax.Array]:
        q = _q_head(self, _apply_torso(self, obs))
        return q, jnp.max(q, axis=-1)


class RecurrentActorCritic(nn.Module):
    """Recurrent policy + value network: torso -> LSTM core -> heads.

    The async-rl/A3C family's LSTM variant (the A3C paper's recurrent agent;
    IMPALA's LSTM agent). TPU-idiomatic: the core state is an explicit
    ``(c, h)`` pytree carried through the rollout ``lax.scan`` — the same
    carry that holds env states — so the whole recurrent rollout stays one
    fused XLA program. Call as ``apply(params, obs[B], core) ->
    (dist_params, value, new_core)``; the CALLER resets the core where
    episodes end (``reset_core``), keeping the cell itself stateless.
    """

    num_actions: int
    torso: str = "mlp"
    hidden_sizes: Sequence[int] = (64, 64)
    channels: Sequence[int] = (16, 32, 32)
    core_size: int = 256
    compute_dtype: jnp.dtype = jnp.float32
    obs_rank: int = 1
    continuous: bool = False
    action_dim: int = 0
    remat: bool = False

    @nn.compact
    def __call__(self, obs, core):
        h = _apply_torso(self, obs)
        # LSTM math in f32: tiny vs the torso, and carries must not
        # accumulate bf16 rounding across hundreds of steps.
        cell = nn.OptimizedLSTMCell(self.core_size, dtype=jnp.float32)
        core, h = cell(core, h.astype(jnp.float32))
        dist_params, value = _apply_heads(self, h)
        return dist_params, value, core

    def initial_core(self, batch_size: int):
        """Zero (c, h) carry for ``batch_size`` envs."""
        return _zero_core(batch_size, self.core_size)


class RecurrentQNetwork(nn.Module):
    """DRQN-style recurrent Q network: torso -> LSTM core -> Q head.

    The Q-learning family's answer to partial observability (Hausknecht &
    Stone's DRQN recipe applied the A3C-LSTM way): same call/carry contract
    as ``RecurrentActorCritic`` — ``apply(params, obs[B], core) ->
    (q_values, max_q, new_core)`` with the CALLER resetting the core at
    episode boundaries — so every recurrent code path (rollout scan,
    learner re-forward, eval) works unchanged.
    """

    num_actions: int
    torso: str = "mlp"
    hidden_sizes: Sequence[int] = (64, 64)
    channels: Sequence[int] = (16, 32, 32)
    core_size: int = 256
    compute_dtype: jnp.dtype = jnp.float32
    obs_rank: int = 1
    dueling: bool = False
    remat: bool = False

    @nn.compact
    def __call__(self, obs, core):
        h = _apply_torso(self, obs)
        # LSTM math in f32 for the same carry-rounding reason as
        # RecurrentActorCritic.
        cell = nn.OptimizedLSTMCell(self.core_size, dtype=jnp.float32)
        core, h = cell(core, h.astype(jnp.float32))
        q = _q_head(self, h)
        return q, jnp.max(q, axis=-1), core

    def initial_core(self, batch_size: int):
        return _zero_core(batch_size, self.core_size)


def reset_core(core, done):
    """Zero the recurrent carry where ``done`` (episode boundary); ``done``
    is [B] bool/float. An LSTM's ``(c, h)`` leaves are [B, H]; a carry
    that holds more than one kind of state (``seq_common.SeqCore``) says
    itself what a reset is."""
    if hasattr(core, "reset"):
        return core.reset(done.astype(bool))
    keep = 1.0 - done.astype(jnp.float32)
    return jax.tree.map(lambda c: c * keep[:, None], core)


def settle_core(core):
    """The carry with no reset pending. ``reset_core`` may leave a reset to
    the policy's next read of the carry (``seq_common.SeqCore`` does, for
    its KDA states); whoever hands a carry to anything but the policy (a
    rollout's exit, a recorded ``init_core``) settles it first. The
    identity for a carry that resets eagerly (an LSTM's ``(c, h)``)."""
    return core.settle() if hasattr(core, "settle") else core


def is_recurrent(model) -> bool:
    """A model that is called through a carry: ``apply(params, obs, core)``
    and ``initial_core(batch)``."""
    return hasattr(model, "initial_core")


def build_model(config, env_spec):
    """Construct the (Recurrent)ActorCritic matching a Config + EnvSpec."""
    compute_dtype = (
        jnp.bfloat16 if config.precision == "bf16_matmul" else jnp.float32
    )
    if config.seq_model:
        from asyncrl_tpu.models import (
            granite_h, keye_moe, kimi_linear, lfm2_moe, moonlight)

        # each sequence policy keeps the shape records it builds
        policies = ((kimi_linear.SHAPES, kimi_linear.SeqPolicy),
                    (lfm2_moe.SHAPES, lfm2_moe.Lfm2Policy),
                    (keye_moe.SHAPES, keye_moe.KeyePolicy),
                    (moonlight.SHAPES, moonlight.MoonlightPolicy),
                    (granite_h.SHAPES, granite_h.GraniteHPolicy))
        for shapes, policy in policies:
            if config.seq_model in shapes:
                shape = shapes[config.seq_model]
                break
        else:
            raise ValueError(
                f"unknown seq_model {config.seq_model!r}; have "
                f"{sorted(name for shapes, _ in policies for name in shapes)}"
            )
        if config.algo == "qlearn" or env_spec.num_actions != shape.vocab:
            raise ValueError(
                f"seq_model={config.seq_model!r} is a policy over its "
                f"{shape.vocab}-token vocabulary for the policy-gradient "
                f"algorithms; got algo={config.algo!r} on an env with "
                f"{env_spec.num_actions} actions"
            )
        if not 0 < env_spec.max_episode_steps <= shape.max_positions:
            raise ValueError(
                f"seq_model={config.seq_model!r} holds {shape.max_positions} "
                f"positions of an episode; the env's episodes have up to "
                f"{env_spec.max_episode_steps or 'an unstated number of'} steps"
            )
        return policy(shape, compute_dtype)
    if config.algo == "qlearn":
        if env_spec.continuous:
            raise ValueError(
                "algo='qlearn' requires a discrete action space; "
                f"{config.env_id!r} is continuous"
            )
        q_common = dict(
            num_actions=env_spec.num_actions,
            torso=config.torso,
            hidden_sizes=tuple(config.hidden_sizes),
            channels=tuple(config.channels),
            compute_dtype=compute_dtype,
            obs_rank=len(env_spec.obs_shape),
            dueling=config.dueling,
            remat=config.remat,
        )
        if config.core == "lstm":
            return RecurrentQNetwork(core_size=config.core_size, **q_common)
        if config.core != "ff":
            raise ValueError(f"unknown core {config.core!r}; expected ff|lstm")
        return QNetwork(**q_common)
    common = dict(
        num_actions=env_spec.num_actions,
        torso=config.torso,
        hidden_sizes=tuple(config.hidden_sizes),
        channels=tuple(config.channels),
        compute_dtype=compute_dtype,
        obs_rank=len(env_spec.obs_shape),
        continuous=env_spec.continuous,
        action_dim=env_spec.action_dim,
        remat=config.remat,
    )
    if config.core == "lstm":
        return RecurrentActorCritic(core_size=config.core_size, **common)
    if config.core != "ff":
        raise ValueError(f"unknown core {config.core!r}; expected ff|lstm")
    return ActorCritic(**common)
