"""Functional environment API.

The reference steps Gym-style stateful envs from Python actor threads
(SURVEY.md §1.2 L1, §3.3). The TPU-native counterpart is a *functional* env:
state in, (state, timestep) out, so a batch of envs is ``vmap`` over the state
pytree and an episode is ``lax.scan`` over time — the whole rollout lives in
one XLA program in HBM (Anakin). Host-driven Gym envs are adapted to this
same interface for the Sebulba path (``envs/gym_adapter.py``).

Auto-reset semantics: ``step`` returns the *post-reset* observation whenever
the episode ends, plus separate ``terminated``/``truncated`` flags so the
algorithms can bootstrap correctly (bootstrap on truncation, not on
termination). ``last_obs`` carries the true final observation of the ended
episode for anyone who needs it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, TypeVar

import jax
import jax.numpy as jnp
from flax import struct

EnvState = TypeVar("EnvState")


@struct.dataclass
class TimeStep:
    """One transition's outputs, batched arbitrarily.

    Attributes:
      obs: observation *after* this step (post-reset if the episode ended).
      reward: reward for the transition just taken.
      terminated: episode ended inside the MDP (no bootstrap).
      truncated: episode ended by time limit (bootstrap from last_obs value).
      last_obs: the pre-reset observation this step produced (== obs unless
        the episode just ended).
    """

    obs: jax.Array
    reward: jax.Array
    terminated: jax.Array
    truncated: jax.Array
    last_obs: jax.Array

    @property
    def done(self) -> jax.Array:
        return jnp.logical_or(self.terminated, self.truncated)


@dataclasses.dataclass(frozen=True)
class EnvSpec:
    """Static env metadata used to build models and buffers.

    Discrete envs set ``num_actions``; continuous envs (the Brax-style
    workloads, BASELINE.json:11) set ``continuous=True`` + ``action_dim``
    and clip incoming actions to their own physical bounds.
    """

    obs_shape: tuple[int, ...]
    num_actions: int = 0  # discrete spaces; 0 for continuous envs
    obs_dtype: Any = jnp.float32
    continuous: bool = False
    action_dim: int = 0  # continuous spaces; 0 for discrete envs
    # Most steps an episode can have, where the env bounds it and a model
    # sizes something by it (a sequence policy's position cap); 0 = not said.
    max_episode_steps: int = 0


class Environment:
    """Pure-function environment. Subclasses implement the three methods.

    All methods must be jittable and vmappable: static shapes, no Python
    control flow on traced values.
    """

    spec: EnvSpec

    def init(self, key: jax.Array):
        """Fresh episode state."""
        raise NotImplementedError

    def observe(self, state) -> jax.Array:
        """Observation for the current state."""
        raise NotImplementedError

    def step(self, state, action: jax.Array, key: jax.Array):
        """Advance one step, auto-resetting on episode end.

        Returns ``(new_state, TimeStep)``.
        """
        raise NotImplementedError
