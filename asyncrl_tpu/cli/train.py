"""CLI entry: ``python -m asyncrl_tpu.cli.train <preset> [key=value ...]``.

The reference family drives training through per-workload run scripts
(SURVEY.md §1.2 L6); here one entry point + the preset registry covers all
workloads (BASELINE.json:6-12), with ``key=value`` overrides (SURVEY.md §5.6).
"""

from __future__ import annotations

import argparse
import json


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="asyncrl-tpu",
        description="Train an asyncrl_tpu agent from a workload preset.",
    )
    parser.add_argument("preset", help="preset name (see asyncrl_tpu.configs)")
    parser.add_argument(
        "overrides", nargs="*", help="config overrides as key=value"
    )
    parser.add_argument(
        "--steps", type=int, default=None, help="override total_env_steps"
    )
    parser.add_argument(
        "--eval-episodes", type=int, default=32,
        help="greedy-eval episodes after training (0 to skip)",
    )
    parser.add_argument(
        "--json", action="store_true", help="emit one JSON line per window"
    )
    parser.add_argument(
        "--jsonl", metavar="FILE", default=None,
        help="also append one JSON line per window to FILE",
    )
    parser.add_argument(
        "--logdir", metavar="DIR", default=None,
        help="also write TensorBoard scalar summaries under DIR",
    )
    parser.add_argument(
        "--profile", metavar="DIR", default=None,
        help="capture a jax.profiler trace of the training run into DIR "
        "(view with tensorboard --logdir DIR)",
    )
    args = parser.parse_args(argv)

    from asyncrl_tpu.api.factory import make_agent
    from asyncrl_tpu.cli.common import prepare_runtime, resolve_config

    cfg = resolve_config(args.preset, args.overrides, args.steps)
    prepare_runtime(cfg)

    agent = make_agent(cfg)

    from asyncrl_tpu.utils import metrics as metrics_mod

    sink = metrics_mod.MultiSink(
        metrics_mod.StdoutSink(as_json=args.json),
        metrics_mod.JsonlSink(args.jsonl) if args.jsonl else None,
        metrics_mod.TensorBoardSink(args.logdir) if args.logdir else None,
    )

    import jax

    try:
        if args.profile:
            jax.profiler.start_trace(args.profile)
        try:
            agent.train(callback=sink)
        finally:
            if args.profile:
                jax.profiler.stop_trace()
            sink.close()

        if args.eval_episodes:
            ret = agent.evaluate(num_episodes=args.eval_episodes)
            print(
                json.dumps(
                    {"eval_episodes": args.eval_episodes, "mean_return": ret}
                )
                if args.json
                else f"greedy eval over {args.eval_episodes} episodes: {ret:.1f}"
            )
    finally:
        close = getattr(agent, "close", None)
        if close is not None:
            close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
