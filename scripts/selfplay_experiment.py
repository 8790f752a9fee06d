"""Self-play payoff experiment (VERDICT round 2, Next #5).

The self-play ladder (Config.selfplay + JaxPongDuel-v0) exists to develop
stronger play than training directly against the scripted tracker. This
script tests that claim head-to-head: train one agent each way with
MATCHED env-frame budgets and identical hyperparameters, then evaluate
BOTH on the same metric — greedy play against the standard scripted
tracker (the 18.0-bar metric; the duel env's single-action ``step``
inherits the scripted opponent, so ``Trainer.evaluate`` measures exactly
this for the self-play agent too).

    python scripts/selfplay_experiment.py [frames] [key=value ...]

Prints a ``kind="experiment"`` entry with both scores. Interpretation
guidance (docs/ARCHITECTURE.md):
direct training exploits THE tracker; self-play learns general play that
must transfer — at small budgets direct usually wins the tracker metric,
so the ladder earns its keep only if this experiment shows otherwise.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from asyncrl_tpu.utils import runtime  # noqa: E402

# Paired runs on the TPU (or, asked for explicitly with ASYNCRL_FORCE_CPU=1,
# the CPU) — the comparison is within-platform either way, so both arms
# always share one device kind.
runtime.require_tpu("selfplay_experiment")
runtime.enable_compile_cache()

from asyncrl_tpu.api.trainer import Trainer
from asyncrl_tpu.configs import presets
from asyncrl_tpu.utils.config import override


def train_and_eval(cfg, label: str) -> dict:
    t0 = time.perf_counter()
    trainer = Trainer(cfg)
    last = {}

    def cb(m):
        last.update(m)
        line = {
            "arm": label,
            "env_steps": m["env_steps"],
            "episode_return": round(m["episode_return"], 2),
        }
        print(json.dumps(line), file=sys.stderr, flush=True)

    try:
        trainer.train(callback=cb)
        # Both arms score on the SAME metric: greedy vs the scripted
        # tracker (duel env single-action step keeps the scripted rival).
        score = trainer.evaluate(num_episodes=32)
    finally:
        trainer.close()
    return {
        "eval_vs_tracker": round(float(score), 2),
        "train_seconds": round(time.perf_counter() - t0, 1),
    }


def main() -> int:
    frames = 20_000_000
    overrides = []
    for a in sys.argv[1:]:
        if "=" in a:
            overrides.append(a)
        else:
            frames = int(a)

    base = presets.get("pong_impala").replace(
        total_env_steps=frames, updates_per_call=8
    )
    base = override(base, overrides)

    direct = train_and_eval(base, "direct")
    ladder = train_and_eval(
        base.replace(env_id="JaxPongDuel-v0", selfplay=True), "selfplay"
    )

    entry = {
        "kind": "experiment",
        "name": "selfplay_vs_direct",
        **runtime.device_entry(),
        "env_frames_each": frames,
        "direct": direct,
        "selfplay": ladder,
        "metric": "mean greedy return vs scripted tracker, 32 episodes",
    }
    print(json.dumps(entry))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
