"""Diagnose a trained Pong policy against the 18.0 bar: WHERE do points go?

Loads the latest checkpoint from a run dir, plays N greedy games against the
standard tracker, and reports the stats that separate plateaued (~+4) play
from oracle (~+19) play (scripts/pong_oracle.py):

- defense: points conceded per game, and the paddle-to-ball miss margin
  (how far away was the paddle when the ball got past?)
- offense: points won per game, the agent's contact-offset distribution
  (|offset| ~ 1 = edge hits = max spin; the oracle's winning exploit), and
  the tracker's miss margin on points won.

    python scripts/pong_diagnose.py runs/pong18 [games]

Prints one JSON line of aggregates.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# A pure-analysis tool: it measures the policy, not the hardware, and must
# not take the chip from a process that is training on it.
jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np

from asyncrl_tpu.configs import presets
from asyncrl_tpu.envs.pong import PADDLE_HALF, Pong
from asyncrl_tpu.models.networks import is_recurrent
from asyncrl_tpu.ops.normalize import normalizing_apply
from asyncrl_tpu.utils import checkpoint as ckpt_mod
from asyncrl_tpu.utils.config import override

MAX_STEPS = 3000


def load_params(run_dir: str, cfg):
    # create=False: a typo'd run dir must raise, not leave an empty
    # directory behind (the checkpoint.setup read-only-restore contract).
    with ckpt_mod.Checkpointer(run_dir, create=False) as ck:
        step = ck.latest_step()
    if step is None:
        raise SystemExit(f"no checkpoint under {run_dir}")
    from asyncrl_tpu.api.trainer import Trainer

    trainer = Trainer(cfg.replace(checkpoint_dir=""), restore=run_dir)
    return trainer, trainer.state.params, trainer.model, step


def diagnose(apply_fn, params, games: int, seed: int = 7):
    env = Pong()

    def one(key):
        st = env.init(key)

        def body(carry, k):
            st, done = carry
            obs = env.observe(st)
            logits = apply_fn(params, obs[None])[0][0]
            a = jnp.argmax(logits).astype(jnp.int32)
            st2, ts = env.step(st, a, k)
            rec = {
                "reward": jnp.where(done, 0.0, ts.reward),
                # last_obs is the un-reset end-of-step view.
                "ball_y_end": ts.last_obs[1],
                "agent_y_end": ts.last_obs[4],
                "opp_y_end": ts.last_obs[5],
                "alive": (~done).astype(jnp.float32),
            }
            st2 = jax.tree.map(lambda n_, o: jnp.where(done, o, n_), st2, st)
            return (st2, done | ts.done), rec

        keys = jax.random.split(key, MAX_STEPS)
        (_, _), recs = jax.lax.scan(body, (st, jnp.asarray(False)), keys)
        return recs

    keys = jax.random.split(jax.random.PRNGKey(seed), games)
    recs = jax.jit(jax.vmap(one))(keys)
    return {k: np.asarray(v) for k, v in recs.items()}


def main() -> int:
    from asyncrl_tpu.utils import runtime

    runtime.enable_compile_cache()
    run_dir = sys.argv[1] if len(sys.argv) > 1 else "runs/pong18"
    games = int(sys.argv[2]) if len(sys.argv) > 2 else 64
    cfg = presets.get("pong_impala")
    cfg = override(cfg, [a for a in sys.argv[3:] if "=" in a])

    trainer, params, model, step = load_params(run_dir, cfg)
    if is_recurrent(model):
        raise SystemExit(
            "pong_diagnose analyzes feed-forward policies only; use "
            "cli/play.py --save for recurrent trajectory dumps"
        )
    # Same normalized view the policy trained on (identity when stats are
    # None) — raw obs into a normalized-trained net would misdescribe it.
    apply_fn = normalizing_apply(model.apply, trainer.state.obs_stats)

    recs = diagnose(apply_fn, params, games)
    # vmap(one) stacks games on the LEADING axis: every rec is [games, T].
    rew = recs["reward"] * recs["alive"]
    won = (rew > 0).sum(axis=1)
    lost = (rew < 0).sum(axis=1)

    # Miss margin on conceded points: |ball_y - agent_y| - PADDLE_HALF at
    # the step the point was lost (ball got past the agent plane).
    lost_mask = rew < 0
    miss_margin = np.abs(recs["ball_y_end"] - recs["agent_y_end"]) - PADDLE_HALF
    win_mask = rew > 0
    win_margin = np.abs(recs["ball_y_end"] - recs["opp_y_end"]) - PADDLE_HALF

    out = {
        "checkpoint_step": step,
        "games": games,
        "mean_return": round(float((won - lost).mean()), 2),
        "points_won_per_game": round(float(won.mean()), 2),
        "points_lost_per_game": round(float(lost.mean()), 2),
        "concede_miss_margin_mean": round(
            float(miss_margin[lost_mask].mean()), 4
        )
        if lost_mask.any()
        else None,
        "concede_miss_margin_p90": round(
            float(np.quantile(miss_margin[lost_mask], 0.9)), 4
        )
        if lost_mask.any()
        else None,
        "win_opp_miss_margin_mean": round(
            float(win_margin[win_mask].mean()), 4
        )
        if win_mask.any()
        else None,
        "episode_len_mean": round(float(recs["alive"].sum(axis=1).mean()), 1),
    }
    print(json.dumps(out))
    trainer.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
