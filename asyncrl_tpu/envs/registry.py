"""Env registry keyed by the reference's workload env ids (BASELINE.json:6-12).

Workloads whose native dependencies are absent in this image (ale-py, procgen,
brax — SURVEY.md §7.4 R1) map to JAX-native stand-ins so every config remains
runnable; the registry abstraction lets the real suites drop in later.
"""

from __future__ import annotations

from typing import Callable

from asyncrl_tpu.envs.core import Environment

_REGISTRY: dict[str, tuple[Callable[..., Environment], bool]] = {}


def register(
    env_id: str,
    factory: Callable[..., Environment],
    configurable: bool = False,
) -> None:
    """``configurable=True`` factories take one argument — the Config (or
    None) — and read their env-specific knobs from it (e.g. JaxPong's
    opponent mode, the pixel envs' frame_skip); plain factories take no
    arguments. Either way ``make`` applies the generic ALE-semantics
    wrappers (frame skip / sticky actions) afterwards."""
    _REGISTRY[env_id] = (factory, configurable)


def make(env_id: str, config=None) -> Environment:
    if env_id not in _REGISTRY:
        raise KeyError(
            f"unknown env {env_id!r}; registered: {sorted(_REGISTRY)}"
        )
    factory, configurable = _REGISTRY[env_id]
    env = factory(config) if configurable else factory()
    if config is not None:
        from asyncrl_tpu.envs.wrappers import apply_ale_knobs

        env = apply_ale_knobs(env, config)
    return env


def registered() -> list[str]:
    return sorted(_REGISTRY)


def _register_builtins() -> None:
    from asyncrl_tpu.envs.breakout import Breakout, BreakoutPixels
    from asyncrl_tpu.envs.cartpole import CartPole
    from asyncrl_tpu.envs.locomotion import (
        make_ant,
        make_halfcheetah,
        make_hopper,
        make_humanoid,
        make_walker2d,
    )
    from asyncrl_tpu.envs.pendulum import Pendulum
    from asyncrl_tpu.envs.pong import Pong, PongPixels

    def pong_kwargs(cfg):
        if cfg is None:
            return {}
        return {
            "opponent": cfg.pong_opponent,
            "opponent_speed": cfg.pong_opponent_speed,
            # Config.pong_max_steps counts AGENT DECISIONS; the env-level
            # cap counts core steps, and under frame_skip every decision
            # plays skip core steps (FrameSkip wrapper on the vector/duel
            # envs, frame_skip_scan inside the pixel env) — so the scale
            # happens HERE, once, for all three pong registrations.
            # 27,000 decisions x skip-4 = 108,000 core steps, exactly
            # ALE's max_num_frames_per_episode.
            "max_steps": cfg.pong_max_steps * max(cfg.frame_skip, 1),
            # Game balance under frame_skip (envs/pong.py __init__): the
            # scripted rival re-decides once per AGENT decision, so skip
            # changes observation/action cadence — never difficulty.
            "opponent_every": max(cfg.frame_skip, 1),
        }

    def pixel_kwargs(cfg):
        # Pixel envs take BOTH knobs internally at the raw-frame level
        # (per-core-step stick draws, skip-window pooling hooks); the
        # generic make() wrappers skip FrameStackPixels instances.
        if cfg is None:
            return {}
        return {
            "frame_skip": cfg.frame_skip,
            "frame_pool": cfg.frame_pool,
            "sticky_actions": cfg.sticky_actions,
        }

    register("CartPole-v1", CartPole)
    register("JaxPong-v0", lambda cfg: Pong(**pong_kwargs(cfg)), True)
    # Duel variant for self-play (Config.selfplay); its single-action step
    # keeps the scripted opponent, so eval measures vs the calibrated
    # ladder.
    from asyncrl_tpu.envs.pong import DuelPong

    register("JaxPongDuel-v0", lambda cfg: DuelPong(**pong_kwargs(cfg)), True)
    register(
        "JaxPongPixels-v0",
        lambda cfg: PongPixels(**pong_kwargs(cfg), **pixel_kwargs(cfg)),
        True,
    )
    register("JaxBreakout-v0", Breakout)
    register(
        "JaxBreakoutPixels-v0",
        lambda cfg: BreakoutPixels(**pixel_kwargs(cfg)),
        True,
    )
    register("JaxPendulum-v0", Pendulum)

    from asyncrl_tpu.envs.token_task import TokenTask

    register("JaxTokenTask-v0", TokenTask.for_config, True)
    from asyncrl_tpu.envs.gridworlds import Chaser, Maze
    from asyncrl_tpu.envs.minatari import (
        Asterix,
        Freeway,
        Seaquest,
        SpaceInvaders,
    )

    # MinAtar-style games widening the Atari family (BASELINE.json:9).
    register("JaxSpaceInvaders-v0", SpaceInvaders)
    register("JaxFreeway-v0", Freeway)
    register("JaxAsterix-v0", Asterix)
    register("JaxSeaquest-v0", Seaquest)

    # Procedurally-generated family (Procgen stand-ins, BASELINE.json:10).
    register("JaxMaze-v0", Maze)
    register("JaxChaser-v0", Chaser)
    # On-TPU rigid-body physics (Brax-workload stand-ins, BASELINE.json:11).
    register("JaxHopper-v0", make_hopper)
    register("JaxWalker2d-v0", make_walker2d)
    register("JaxHalfCheetah-v0", make_halfcheetah)
    register("JaxAnt-v0", make_ant)
    register("JaxHumanoid-v0", make_humanoid)


_register_builtins()
