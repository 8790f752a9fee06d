"""Which episodes a mix's ``episode_seed`` draws, without a policy.

``envs/token_task.py`` draws an episode's length from the env's own key
chain, and ``rollout/anakin.py unroll`` splits that chain once a step, so the
lengths a cell meets follow from the actor state's keys alone. ``simulate``
runs the program's own ``unroll`` from the actor state
``loops/common.py drawn_actor`` builds, under a stand-in policy and a
distribution that draws nothing, and gives back the ``done`` flag of every
step; ``rows_behind`` reduces them to what a mix's ``episode_seed`` is chosen
by: the mean position of a token in its episode over the window's steps.

The rule (``smallest_seed``): the smallest seed whose mean lies within
``tolerance`` of the length law's stationary mean, so that a cell with a
fixed draw stays at the traffic its ``why`` describes.
"""

from __future__ import annotations


class _NoDraw:
    """A distribution that samples action 0 and uses no key."""

    @staticmethod
    def sample(key, params):
        import jax.numpy as jnp

        return jnp.zeros((), jnp.int32)

    @staticmethod
    def logp(params, actions):
        import jax.numpy as jnp

        return jnp.zeros(actions.shape, jnp.float32)


def simulate(env, num_envs: int, n_dev: int, episode_seed, steps: int):
    """``done`` flags ``[steps, num_envs]`` of the env batch an
    ``episode_seed`` draws, from cold, through the program's ``unroll``."""
    import jax.numpy as jnp

    from asyncrl_tpu.rollout.anakin import unroll
    from benchmarks.loops import common

    actor = common.drawn_actor(env, num_envs, n_dev, episode_seed)
    stand_in = lambda params, obs: (
        jnp.zeros((*obs.shape[:1], 1)), jnp.zeros(obs.shape[:1]))
    _, rollout, _ = unroll(stand_in, None, env, actor, steps, dist=_NoDraw)
    return rollout.done


def rows_behind(done, last: int):
    """Mean over the last ``last`` steps and the envs of a token's position
    in its episode (the rows behind it), every env at position 0 at step 0."""
    import jax
    import jax.numpy as jnp

    def step(pos, flag):
        return jnp.where(flag, 0, pos + 1), pos

    _, pos = jax.lax.scan(step, jnp.zeros(done.shape[1:], jnp.int32), done)
    return jnp.mean(pos[-last:].astype(jnp.float32))


def stationary_rows(min_len: int, max_len: int) -> float:
    """The rows behind a token in the steady state, ``E[L^2] / 2 E[L]``,
    of lengths log-uniform in ``[min_len, max_len]``: a quarter of their sum
    (``E[L] = (b - a) / ln(b / a)``, ``E[L^2] = (b^2 - a^2) / 2 ln(b / a)``)."""
    return (min_len + max_len) / 4


def smallest_seed(env, num_envs: int, n_dev: int, steps: int, last: int,
                  tolerance: float, candidates: int):
    """``(seed, rows by candidate)``: the smallest ``episode_seed`` under
    ``candidates`` whose ``rows_behind`` lies within ``tolerance`` (a share)
    of the law's stationary mean; ``None`` where none does."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rows = np.asarray(jax.jit(jax.vmap(lambda seed: rows_behind(
        simulate(env, num_envs, n_dev, seed, steps), last
    )))(jnp.arange(candidates, dtype=jnp.int32)))
    target = stationary_rows(env.min_len, env.max_len)
    fits = np.flatnonzero(np.abs(rows - target) <= tolerance * target)
    return (int(fits[0]) if fits.size else None), rows
