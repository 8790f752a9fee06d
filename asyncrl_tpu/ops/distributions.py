"""Policy action distributions.

The reference's suites span discrete control (CartPole/Atari/Procgen) and
continuous control (Brax Ant/Humanoid) — BASELINE.json:6-12. Rather than
special-casing losses and rollouts per action space, the policy head emits a
flat ``dist_params`` array and one of these (stateless, jit-friendly)
distribution objects interprets it:

- ``Categorical``: ``dist_params`` = logits [..., A]; int32 actions [...].
- ``DiagGaussian``: ``dist_params`` = concat(mean, log_std) [..., 2*D];
  float32 actions [..., D]. log_std is state-dependent only if the model
  makes it so (the builtin head uses a learned state-independent bias, the
  standard PPO continuous-control parameterization).

Everything is a pure function over arrays — usable inside ``vmap``/``scan``/
``shard_map`` with no dispatch overhead (shape-static branching happens at
trace time).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from asyncrl_tpu.utils.prng import gumbel_sample


@dataclasses.dataclass(frozen=True)
class Categorical:
    """Discrete action distribution over ``num_actions`` choices."""

    num_actions: int

    @property
    def param_size(self) -> int:
        return self.num_actions

    @property
    def action_dtype(self):
        return jnp.int32

    def sample(self, key: jax.Array, params: jax.Array) -> jax.Array:
        """Unbatched sample: params [A] -> scalar action (vmap for batches)."""
        return gumbel_sample(key, params)

    def logp(self, params: jax.Array, actions: jax.Array) -> jax.Array:
        logp = jax.nn.log_softmax(params, axis=-1)
        return jnp.take_along_axis(
            logp, actions[..., None].astype(jnp.int32), axis=-1
        )[..., 0]

    def entropy(self, params: jax.Array) -> jax.Array:
        logp = jax.nn.log_softmax(params, axis=-1)
        return -jnp.sum(jnp.exp(logp) * logp, axis=-1)

    def mode(self, params: jax.Array) -> jax.Array:
        return jnp.argmax(params, axis=-1)


@dataclasses.dataclass(frozen=True)
class DiagGaussian:
    """Diagonal Gaussian over ``action_dim`` continuous dims.

    Actions are emitted unsquashed (the env applies its own physical bounds,
    e.g. torque clipping); log-probs are of the unsquashed sample, the
    standard choice for clipped continuous PPO.
    """

    action_dim: int

    @property
    def param_size(self) -> int:
        return 2 * self.action_dim

    @property
    def action_dtype(self):
        return jnp.float32

    def _split(self, params: jax.Array) -> tuple[jax.Array, jax.Array]:
        mean = params[..., : self.action_dim]
        log_std = jnp.clip(params[..., self.action_dim :], -20.0, 2.0)
        return mean, log_std

    def sample(self, key: jax.Array, params: jax.Array) -> jax.Array:
        """Unbatched sample: params [2D] -> action [D] (vmap for batches)."""
        mean, log_std = self._split(params)
        noise = jax.random.normal(key, mean.shape, mean.dtype)
        return mean + jnp.exp(log_std) * noise

    def logp(self, params: jax.Array, actions: jax.Array) -> jax.Array:
        mean, log_std = self._split(params)
        z = (actions - mean) * jnp.exp(-log_std)
        per_dim = -0.5 * jnp.square(z) - log_std - 0.5 * math.log(2 * math.pi)
        return jnp.sum(per_dim, axis=-1)

    def entropy(self, params: jax.Array) -> jax.Array:
        _, log_std = self._split(params)
        return jnp.sum(log_std + 0.5 * math.log(2 * math.pi * math.e), axis=-1)

    def mode(self, params: jax.Array) -> jax.Array:
        mean, _ = self._split(params)
        return mean


@dataclasses.dataclass(frozen=True)
class EpsilonGreedy:
    """ε-greedy behaviour "distribution" over Q-values (the async Q-learning
    family's exploration policy — the A3C paper's value-based siblings,
    PAPERS.md:8).

    ``dist_params`` layout: either raw Q-values ``[..., A]`` (greedy-only
    contexts: eval ``mode``), or ``[..., A + 1]`` with a per-sample ε
    appended as the last column (the rollout appends it via ``unroll``'s
    ``dist_extra`` hook — ε varies per env slot and anneals over training,
    so it cannot live on this frozen object).
    """

    num_actions: int

    @property
    def param_size(self) -> int:
        return self.num_actions + 1  # Q-values + appended ε column

    @property
    def action_dtype(self):
        return jnp.int32

    def _split(self, params: jax.Array) -> tuple[jax.Array, jax.Array]:
        if params.shape[-1] == self.num_actions + 1:
            return params[..., : self.num_actions], params[..., -1]
        return params, jnp.zeros(params.shape[:-1], params.dtype)

    def _probs(self, params: jax.Array) -> jax.Array:
        q, eps = self._split(params)
        greedy = jax.nn.one_hot(jnp.argmax(q, axis=-1), self.num_actions)
        return (
            greedy * (1.0 - eps[..., None])
            + eps[..., None] / self.num_actions
        )

    def sample(self, key: jax.Array, params: jax.Array) -> jax.Array:
        """Unbatched sample: params [A(+1)] -> scalar action (vmap for
        batches). Greedy w.p. 1-ε, uniform-random w.p. ε."""
        q, eps = self._split(params)
        explore_key, action_key = jax.random.split(key)
        random_action = jax.random.randint(
            action_key, (), 0, self.num_actions
        )
        explore = jax.random.uniform(explore_key, ()) < eps
        return jnp.where(
            explore, random_action, jnp.argmax(q, axis=-1)
        ).astype(jnp.int32)

    def logp(self, params: jax.Array, actions: jax.Array) -> jax.Array:
        p = jnp.take_along_axis(
            self._probs(params), actions[..., None].astype(jnp.int32), axis=-1
        )[..., 0]
        return jnp.log(jnp.maximum(p, 1e-12))

    def entropy(self, params: jax.Array) -> jax.Array:
        p = self._probs(params)
        return -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-12)), axis=-1)

    def mode(self, params: jax.Array) -> jax.Array:
        q, _ = self._split(params)
        return jnp.argmax(q, axis=-1)


@dataclasses.dataclass(frozen=True)
class Evaluated:
    """A policy head already evaluated at the fragment's own actions: the
    "parameters" are the pair ``(logp, entropy)`` [T, B] that a model's
    fragment form returns in place of [T, B, actions] logits it never holds
    whole (``models/kimi_linear.py``). What a loss asks of a distribution,
    it reads off the pair."""

    def logp(self, params, actions) -> jax.Array:
        return params[0]

    def entropy(self, params) -> jax.Array:
        return params[1]


def for_spec(spec) -> Categorical | DiagGaussian:
    """Distribution matching an ``EnvSpec``."""
    if getattr(spec, "continuous", False):
        return DiagGaussian(spec.action_dim)
    return Categorical(spec.num_actions)


def for_config(config, spec):
    """Distribution matching a Config + EnvSpec: the algorithm family decides
    how the model's head output is interpreted (``algo="qlearn"`` heads emit
    Q-values acted on ε-greedily; the policy-gradient family emits
    logits / Gaussian parameters)."""
    if config.algo == "qlearn":
        if getattr(spec, "continuous", False):
            raise ValueError(
                "algo='qlearn' requires a discrete action space "
                f"(env {getattr(spec, 'env_id', spec)!r} is continuous)"
            )
        return EpsilonGreedy(spec.num_actions)
    return for_spec(spec)
