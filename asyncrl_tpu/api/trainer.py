"""Trainer: the user-facing training-loop owner, name-parity with the
reference's ``Trainer`` (BASELINE.json:5; SURVEY.md §3.1).

``Trainer.train()`` drives ``Learner.update`` and drains device-resident
metrics to the host every ``log_every`` update CALLS (each call fuses
``updates_per_call`` learner updates) — the hot loop never blocks on host
sync between drains. ``Trainer.evaluate()`` runs greedy episodes fully on
device (SURVEY.md §3.5).
"""

from __future__ import annotations

import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from asyncrl_tpu import obs
from asyncrl_tpu.envs import registry
from asyncrl_tpu.learn.learner import (
    Learner,
    TrainState,
    validate_train_target,
)
from asyncrl_tpu.models.networks import build_model, is_recurrent, reset_core
from asyncrl_tpu.obs import introspect, trace
from asyncrl_tpu.obs import spans as span_names
from asyncrl_tpu.ops.normalize import normalizing_apply
from asyncrl_tpu.parallel.mesh import make_mesh
from asyncrl_tpu.utils.config import Config, default_eval_max_steps


def make_eval_rollout(config, env, model, num_episodes: int, max_steps: int):
    """Build ``eval_rollout(params, obs_stats, key) -> [num_episodes]``:
    one fully-on-device greedy rollout returning per-episode returns
    (SURVEY.md §3.5). Shared by ``Trainer.evaluate`` and the population
    trainer's per-member ranking (``jax.vmap`` over the params axis —
    api/population.py)."""
    from asyncrl_tpu.ops import distributions

    apply_fn = model.apply
    dist = distributions.for_config(config, env.spec)
    recurrent = is_recurrent(model)

    def eval_rollout(params, obs_stats, key):
        # Greedy eval must see the same normalized observations the
        # policy trained on (ops/normalize.py; identity when None).
        napply = normalizing_apply(apply_fn, obs_stats)
        init_keys = jax.random.split(key, num_episodes + 1)
        env_state = jax.vmap(env.init)(init_keys[:-1])
        obs = jax.vmap(env.observe)(env_state)
        step_key = init_keys[-1]
        core = model.initial_core(num_episodes) if recurrent else None

        def body(carry, _):
            env_state, obs, ret, alive, k, core = carry
            if recurrent:
                dist_params, _, core = napply(params, obs, core)
            else:
                dist_params, _ = napply(params, obs)
            actions = dist.mode(dist_params)
            k, sub = jax.random.split(k)
            step_keys = jax.random.split(sub, num_episodes)
            env_state, ts = jax.vmap(env.step)(env_state, actions, step_keys)
            if recurrent:
                core = reset_core(core, ts.done)
            ret = ret + ts.reward * alive
            alive = alive * (1.0 - ts.done.astype(jnp.float32))
            return (env_state, ts.obs, ret, alive, k, core), None

        zeros = jnp.zeros((num_episodes,), jnp.float32)
        (_, _, ret, _, _, _), _ = jax.lax.scan(
            body,
            (env_state, obs, zeros, zeros + 1.0, step_key, core),
            None,
            length=max_steps,
        )
        return ret

    return eval_rollout


class Trainer:
    """Owns env, model, mesh, learner, and the training loop.

    Checkpointing (SURVEY.md §5.4): with ``config.checkpoint_dir`` set, the
    full TrainState + env-steps counter is saved there every
    ``config.checkpoint_every`` updates (orbax, async), plus once when
    ``train()`` exits — by any path. On construction, an explicit
    ``restore=path`` loads initial state from that path read-only; otherwise
    an existing checkpoint under ``config.checkpoint_dir`` auto-resumes
    bit-exact.
    """

    def __init__(
        self, config: Config, env=None, model=None, mesh=None, restore=None
    ):
        # Observability first (asyncrl_tpu/obs/): config.trace /
        # ASYNCRL_TRACE arm span tracing here as they do on Sebulba, so
        # the set-up phases below are already spans of THIS agent's rings.
        self._obs = obs.setup(config)
        # Resolve the ASYNCRL_INTROSPECT override once (env wins over
        # config.introspect, the ASYNCRL_TRACE precedence): the jitted
        # loss aux reads the RESOLVED flag at trace time, never the env.
        if introspect.enabled(config) != config.introspect:
            config = config.replace(introspect=introspect.enabled(config))
        self.config = config
        with introspect.phase(span_names.SETUP_ENV):
            self.env = (
                env if env is not None
                else registry.make(config.env_id, config)
            )
        with introspect.phase(span_names.SETUP_MODEL):
            self.model = (
                model if model is not None
                else build_model(config, self.env.spec)
            )
        with introspect.phase(span_names.SETUP_MESH):
            self.mesh = (
                mesh
                if mesh is not None
                else make_mesh(config.mesh_shape, config.mesh_axes)
            )
        with introspect.phase(span_names.SETUP_LEARNER):
            self.learner = Learner(config, self.env, self.model, self.mesh)
        with introspect.phase(span_names.SETUP_INIT_STATE):
            self.state: TrainState = self.learner.init_state(config.seed)
        self.env_steps = 0
        self._eval_fns: dict[tuple[int, int], Callable] = {}

        with introspect.phase(span_names.SETUP_CHECKPOINT):
            # orbax is imported here, and is most of this phase in a
            # process that neither restores nor saves.
            from asyncrl_tpu.utils import checkpoint

            self._ckpt, self.state, self.env_steps = checkpoint.setup(
                config, restore, self.state
            )
        self.checkpointer = self._ckpt.checkpointer

    def save_checkpoint(self) -> None:
        """Save the current TrainState now (async; see ``Checkpointer``)."""
        self._ckpt.save_now(self.state, self.env_steps)

    def close(self) -> None:
        """Flush pending async checkpoint saves, export the trace (when
        tracing is on) and release resources."""
        self._ckpt.close()
        self._obs.export_trace()
        self._obs.shutdown()

    # ------------------------------------------------------------------ train

    def train(
        self,
        total_env_steps: int | None = None,
        callback: Callable[[dict[str, Any]], None] | None = None,
    ) -> list[dict[str, Any]]:
        """Run updates until ``total_env_steps`` env frames consumed.

        Returns the list of drained metric dicts (one per ``log_every``
        update calls; a call fuses ``updates_per_call`` updates), each
        including ``env_steps``, ``fps``, and ``episode_return`` (mean over
        episodes completed in the window).
        """
        cfg = self.config
        target = total_env_steps or cfg.total_env_steps
        validate_train_target(cfg, target)
        steps_per_update = cfg.batch_steps_per_update * cfg.updates_per_call
        history: list[dict[str, Any]] = []

        pending: list[dict[str, jax.Array]] = []
        window_start = time.perf_counter()
        window_steps = 0
        calls = calls_at_eval = 0

        try:
            while self.env_steps < target:
                self.state, metrics = self.learner.update(self.state)
                self.env_steps += steps_per_update
                window_steps += steps_per_update
                calls += 1
                pending.append(metrics)
                self._ckpt.after_update(self.state, self.env_steps)

                if len(pending) >= cfg.log_every or self.env_steps >= target:
                    with trace.span(span_names.LEARNER_METRICS):
                        drained = jax.device_get(pending)
                    pending = []
                    elapsed = time.perf_counter() - window_start
                    window_start = time.perf_counter()

                    # Metric leaves are scalars (updates_per_call=1) or [K]
                    # stacks (fused multi-update calls): np handles both.
                    agg = {
                        k: float(np.mean([np.mean(m[k]) for m in drained]))
                        for k in drained[0]
                        if not k.startswith("episode_")
                    }
                    ep_count = float(
                        np.sum([np.sum(m["episode_count"]) for m in drained])
                    )
                    agg["episode_count"] = ep_count
                    agg["episode_return"] = float(
                        np.sum(
                            [np.sum(m["episode_return_sum"]) for m in drained]
                        )
                        / max(ep_count, 1.0)
                    )
                    agg["episode_length"] = float(
                        np.sum(
                            [np.sum(m["episode_length_sum"]) for m in drained]
                        )
                        / max(ep_count, 1.0)
                    )
                    agg["env_steps"] = self.env_steps
                    agg["fps"] = window_steps / max(elapsed, 1e-9)
                    window_steps = 0
                    # In-training greedy eval on the log boundary (so the
                    # eval never lands mid-window and its wall time never
                    # pollutes a window's fps).
                    if (
                        cfg.eval_every > 0
                        and calls - calls_at_eval >= cfg.eval_every
                    ):
                        calls_at_eval = calls
                        with trace.span(span_names.LEARNER_EVAL):
                            agg["eval_return"] = self.evaluate(
                                num_episodes=cfg.eval_episodes
                            )
                        self._ckpt.maybe_save_best(
                            self.state, self.env_steps, agg["eval_return"]
                        )
                        window_start = time.perf_counter()
                    history.append(agg)
                    if callback:
                        callback(agg)
        finally:
            # A crash must not lose progress: save whatever state we have
            # (even with periodic saves disabled) and flush async writes.
            self._ckpt.finalize(self.state, self.env_steps)
        return history

    # ----------------------------------------------------------------- eval

    def evaluate(
        self,
        num_episodes: int = 32,
        max_steps: int | None = None,
        seed: int = 1234,
        return_episodes: bool = False,
    ):
        """Mean greedy-policy episode return over ``num_episodes`` fresh envs,
        fully on device (one jitted scan). ``return_episodes=True`` returns
        the per-episode return vector instead of the mean (same single
        batched rollout either way)."""
        # Default horizon: contain the longest builtin episode (shared
        # helper; pass a smaller value explicitly for quick checks).
        if max_steps is None:
            max_steps = default_eval_max_steps(self.config)
        cache_key = (num_episodes, max_steps)
        if cache_key not in self._eval_fns:
            self._eval_fns[cache_key] = jax.jit(
                make_eval_rollout(
                    self.config, self.env, self.model, num_episodes, max_steps
                )
            )
        returns = self._eval_fns[cache_key](
            self.state.params, self.state.obs_stats, jax.random.PRNGKey(seed)
        )
        if return_episodes:
            import numpy as np

            return np.asarray(returns)
        return float(jnp.mean(returns))
