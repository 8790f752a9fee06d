"""A JAX-native token task: the env of a token-level sequence policy.

Observation = one token id, action = one token id. An episode is a prompt
of seeded tokens, then the policy's own tokens fed back; the target is the
prompt repeated, and each generated token that equals it earns +1 (a
programmatic, verifiable reward). The episode's length is drawn by the env
when the episode starts, log-uniform between ``min_len`` and ``max_len``,
and the policy cannot end it: the traffic (episode boundaries per
fragment) does not drift while the parameters move. Envs reset in place.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from flax import struct

from asyncrl_tpu.envs.core import Environment, EnvSpec, TimeStep


@struct.dataclass
class TokenTaskState:
    prompt: jax.Array  # [max_prompt] int32, the first ``prompt_len`` count
    prompt_len: jax.Array  # int32
    length: jax.Array  # int32: tokens in this episode
    t: jax.Array  # int32: position of the token now observed
    token: jax.Array  # int32: the token now observed


@dataclasses.dataclass(frozen=True)
class TokenTask(Environment):
    vocab: int = 64
    min_len: int = 4
    max_len: int = 32
    min_prompt: int = 1
    max_prompt: int = 2

    @classmethod
    def for_config(cls, config=None):
        return cls() if config is None else cls(*config.token_task)

    @property
    def spec(self) -> EnvSpec:
        return EnvSpec(
            obs_shape=(), num_actions=self.vocab, obs_dtype=jnp.int32,
            max_episode_steps=self.max_len,
        )

    def init(self, key: jax.Array) -> TokenTaskState:
        k_len, k_plen, k_prompt = jax.random.split(key, 3)
        length = jnp.exp(jax.random.uniform(
            k_len, (), jnp.float32, math.log(self.min_len),
            math.log(self.max_len + 1),
        )).astype(jnp.int32)
        length = jnp.clip(length, self.min_len, self.max_len)
        prompt = jax.random.randint(
            k_prompt, (self.max_prompt,), 0, self.vocab, jnp.int32
        )
        prompt_len = jnp.minimum(
            jax.random.randint(
                k_plen, (), self.min_prompt, self.max_prompt + 1, jnp.int32
            ),
            length - 1,
        )
        return TokenTaskState(
            prompt=prompt, prompt_len=prompt_len, length=length,
            t=jnp.zeros((), jnp.int32), token=prompt[0],
        )

    def observe(self, state: TokenTaskState) -> jax.Array:
        return state.token

    def step(self, state: TokenTaskState, action: jax.Array, key: jax.Array):
        t = state.t + 1  # the position the next observed token has
        target = state.prompt[t % state.prompt_len]
        generated = t >= state.prompt_len
        action = action.astype(jnp.int32)
        token = jnp.where(generated, action, target)
        reward = jnp.where(generated & (action == target), 1.0, 0.0)
        truncated = t >= state.length
        fresh = self.init(key)
        moved = state.replace(t=t, token=token)
        new_state = jax.tree.map(
            lambda a, b: jnp.where(truncated, a, b), fresh, moved
        )
        ts = TimeStep(
            obs=new_state.token,
            reward=reward.astype(jnp.float32),
            terminated=jnp.zeros((), bool),
            truncated=truncated,
            last_obs=token,
        )
        return new_state, ts
