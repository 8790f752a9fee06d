"""Demo/eval entry: ``python -m asyncrl_tpu.cli.play <preset> [opts]``.

The reference family ships a demo/play script alongside training (greedy
rollouts of a trained model, reward printout — SURVEY.md §3.5 "Evaluation").
This is that script: restore a checkpoint (or play from init for a dry
run), run greedy episodes on device, print per-episode returns, and
optionally dump episode frames/observations to an ``.npz`` for offline
inspection (pixel envs: [T, H, W, C] uint8 frames ready for any viewer;
vector envs: raw observation trajectories).
"""

from __future__ import annotations

import argparse
import json


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="asyncrl-tpu-play",
        description="Greedy-play a trained agent: per-episode returns, "
        "optional trajectory dump.",
    )
    parser.add_argument("preset", help="preset name (see asyncrl_tpu.configs)")
    parser.add_argument(
        "overrides", nargs="*", help="config overrides as key=value"
    )
    parser.add_argument(
        "--restore", metavar="DIR", default=None,
        help="checkpoint directory to restore (default: play from init)",
    )
    parser.add_argument(
        "--episodes", type=int, default=8, help="episodes to play"
    )
    parser.add_argument(
        "--max-steps", type=int, default=3200, help="step cap per episode"
    )
    parser.add_argument(
        "--save", metavar="FILE.npz", default=None,
        help="dump one episode's observation trajectory to FILE.npz",
    )
    parser.add_argument(
        "--json", action="store_true", help="emit results as one JSON line"
    )
    args = parser.parse_args(argv)

    from asyncrl_tpu.api.factory import make_agent
    from asyncrl_tpu.cli.common import prepare_runtime, resolve_config

    cfg = resolve_config(args.preset, args.overrides)
    prepare_runtime(cfg)

    agent = make_agent(cfg, restore=args.restore)
    try:
        returns: list[float] = []
        if args.episodes:
            try:
                # One batched device rollout for all episodes (tpu backend).
                returns = [
                    float(r)
                    for r in agent.evaluate(
                        num_episodes=args.episodes,
                        max_steps=args.max_steps,
                        return_episodes=True,
                    )
                ]
            except TypeError:
                # Host backends expose only the mean; report it as one row.
                returns = [
                    agent.evaluate(
                        num_episodes=args.episodes, max_steps=args.max_steps
                    )
                ]
        if returns:
            mean = sum(returns) / len(returns)
            if args.json:
                print(
                    json.dumps(
                        {
                            "preset": args.preset,
                            "restored": args.restore,
                            "episode_returns": returns,
                            "mean_return": mean,
                        }
                    )
                )
            else:
                for i, r in enumerate(returns):
                    print(f"episode {i}: return {r:.1f}")
                print(f"mean over {len(returns)} episodes: {mean:.2f}")

        if args.save:
            _dump_trajectory(agent, cfg, args.save, args.max_steps)
            print(f"trajectory saved to {args.save}")
    finally:
        close = getattr(agent, "close", None)
        if close is not None:
            close()
    return 0


def _dump_trajectory(agent, cfg, path: str, max_steps: int) -> None:
    """Greedy-roll one episode on device; save obs/action/reward arrays."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from asyncrl_tpu.models.networks import is_recurrent
    from asyncrl_tpu.ops import distributions

    # Reuse the agent's own env: only device-env backends own one (Sebulba
    # presets name gymnasium ids that are not in the device registry).
    env = getattr(agent, "env", None)
    if env is None:
        raise SystemExit(
            "--save needs a device-env (backend='tpu') preset; host-pool "
            f"backends ({cfg.backend!r}) have no on-device env to roll out"
        )
    model = agent.model
    params = agent.state.params
    dist = distributions.for_config(cfg, env.spec)
    recurrent = is_recurrent(model)

    from asyncrl_tpu.ops.normalize import normalizing_apply

    napply = normalizing_apply(
        model.apply, getattr(agent.state, "obs_stats", None)
    )

    def body(carry, _):
        env_state, obs, done, key, core = carry
        key, step_key = jax.random.split(key)
        if recurrent:
            # Single-episode rollout: no mid-trajectory reset needed (the
            # scan freezes at the first done), batch dim of 1 for the core.
            dist_params, _, core = napply(params, obs[None], core)
        else:
            dist_params, _ = napply(params, obs[None])
        action = dist.mode(dist_params)[0]
        new_state, ts = env.step(env_state, action, step_key)
        # Freeze the trajectory after the first episode end.
        keep = jnp.logical_not(done)
        out = (obs, action, jnp.where(keep, ts.reward, 0.0), done)
        new_done = jnp.logical_or(done, ts.done)
        carry = jax.tree.map(
            lambda n, o: jnp.where(keep, n, o), (new_state, ts.obs), (env_state, obs)
        ) + (new_done, key, core)
        return carry, out

    @jax.jit
    def rollout(key):
        init_key, run_key = jax.random.split(key)
        env_state = env.init(init_key)
        obs = env.observe(env_state)
        core = model.initial_core(1) if recurrent else None
        _, (obs_traj, act_traj, rew_traj, done_traj) = jax.lax.scan(
            body,
            (env_state, obs, jnp.zeros((), bool), run_key, core),
            None,
            length=max_steps,
        )
        return obs_traj, act_traj, rew_traj, done_traj

    obs_traj, act_traj, rew_traj, done_traj = rollout(jax.random.PRNGKey(7))
    # Trim to the episode length (first True in done_traj, else max_steps).
    # done_traj[t] is the PRE-step flag: the first True marks the first
    # frozen step after the episode, so the valid trajectory is [:argmax).
    done_np = np.asarray(done_traj)
    end = int(done_np.argmax()) if done_np.any() else max_steps
    np.savez_compressed(
        path,
        obs=np.asarray(obs_traj)[:end],
        actions=np.asarray(act_traj)[:end],
        rewards=np.asarray(rew_traj)[:end],
        episode_return=float(np.asarray(rew_traj)[:end].sum()),
    )


if __name__ == "__main__":
    raise SystemExit(main())
