"""Suite sweep: ``python -m asyncrl_tpu.cli.suite [--games ...] [opts]``.

The reference's Atari-57 workload is a *suite* run — one agent per game,
same hyperparameters, results aggregated across the family (BASELINE.json:9;
SURVEY.md §1.1). This entry point reproduces that shape over any set of
registered envs: it trains each game sequentially on the chip (suites are
throughput-bound, so one-at-a-time keeps every run at full batch size),
greedy-evaluates, and emits a per-game JSONL plus an aggregate summary
(mean/median of final returns — the "human-normalized median" slot of the
Atari-57 protocol, with raw returns since these games have no human
baseline).

Default game set: the six-game Atari stand-in family (JaxPong, JaxBreakout,
and the MinAtar-style four) — swap with ``--games`` for e.g. the procedural
or locomotion families.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

# Image-observation variants throughout: the default preset's CNN torso
# must be able to consume every game in the default sweep.
ATARI_FAMILY = [
    "JaxPongPixels-v0",
    "JaxBreakoutPixels-v0",
    "JaxSpaceInvaders-v0",
    "JaxFreeway-v0",
    "JaxAsterix-v0",
    "JaxSeaquest-v0",
]


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="asyncrl-tpu-suite",
        description="Train one agent per game over an env suite "
        "(the Atari-57 workload shape) and aggregate results.",
    )
    parser.add_argument(
        "overrides", nargs="*", help="config overrides as key=value"
    )
    parser.add_argument(
        "--games", nargs="+", default=None,
        help="env ids to sweep (default: the five-game Atari stand-in "
        "family); 'all' = every registered env",
    )
    parser.add_argument(
        "--preset", default="atari_impala",
        help="base preset supplying hyperparameters (default atari_impala)",
    )
    parser.add_argument(
        "--steps", type=int, default=None, help="override total_env_steps"
    )
    parser.add_argument(
        "--eval-episodes", type=int, default=32,
        help="greedy-eval episodes per game",
    )
    parser.add_argument(
        "--jsonl", metavar="FILE", default=None,
        help="append one JSON line per game to FILE",
    )
    args = parser.parse_args(argv)

    from asyncrl_tpu.api.factory import make_agent
    from asyncrl_tpu.cli.common import prepare_runtime, resolve_config
    from asyncrl_tpu.envs import registered

    games = args.games or ATARI_FAMILY
    if games == ["all"]:
        games = registered()
    unknown = [g for g in games if g not in registered()]
    if unknown:
        print(
            f"unknown envs {unknown}; registered: {registered()}",
            file=sys.stderr,
        )
        return 2

    base = resolve_config(args.preset, args.overrides, args.steps)
    prepare_runtime(base)

    from asyncrl_tpu.envs.registry import make as make_env
    from asyncrl_tpu.utils.metrics import JsonlSink

    def incompatible(game: str) -> str | None:
        """Config/game mismatches detectable before spending train time."""
        spec = make_env(game).spec
        if base.torso in ("nature_cnn", "impala_cnn") and (
            len(spec.obs_shape) != 3
        ):
            return (
                f"torso {base.torso!r} needs image-shaped obs, "
                f"{game} has {spec.obs_shape}"
            )
        return None

    results = []
    sink = JsonlSink(args.jsonl) if args.jsonl else None

    def emit(row: dict) -> None:
        print(json.dumps(row), flush=True)
        if sink:
            sink.write(row)

    try:
        for game in games:
            skip = incompatible(game)
            if skip:
                emit({"game": game, "skipped": skip})
                continue
            cfg = base.replace(env_id=game)
            t0 = time.perf_counter()
            try:
                agent = make_agent(cfg)
                try:
                    hist = agent.train()
                    ret = agent.evaluate(num_episodes=args.eval_episodes)
                finally:
                    close = getattr(agent, "close", None)
                    if close is not None:
                        close()
            except Exception as e:  # keep the sweep alive per game
                emit({"game": game, "error": f"{type(e).__name__}: {e}"})
                continue
            row = {
                "game": game,
                "final_return": ret,
                "train_return_last_window": (
                    float(hist[-1]["episode_return"])
                    if hist and "episode_return" in hist[-1]
                    else None
                ),
                "env_steps": cfg.total_env_steps,
                "wall_s": round(time.perf_counter() - t0, 1),
            }
            results.append(row)
            emit(row)

        if results:
            finals = sorted(r["final_return"] for r in results)
            n = len(finals)
            summary = {
                "suite_size": n,
                "mean_final_return": sum(finals) / n,
                "median_final_return": (
                    finals[n // 2]
                    if n % 2
                    else (finals[n // 2 - 1] + finals[n // 2]) / 2
                ),
                "total_wall_s": round(sum(r["wall_s"] for r in results), 1),
            }
            emit({"suite_summary": summary})
    finally:
        if sink:
            sink.close()
    return 0 if results else 1


if __name__ == "__main__":
    raise SystemExit(main())
