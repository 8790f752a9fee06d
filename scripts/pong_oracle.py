"""Feasibility oracle for the 18.0 Pong bar (VERDICT round 2, Missing #1).

The 18.0 mean-return target (BASELINE.json:2) is calibrated to sit ABOVE the
greedy-scripted ceiling (+14.8, tests/test_pong.py) — so before spending
wall-clock on long training runs, this script answers: can ANY policy
expressible from the 6-dim observation actually score >= 18 against the
standard tracker opponent?

It plays a one-ply lookahead oracle: while the ball approaches, enumerate
every paddle position reachable by contact time (the reachable set is the
0.05-step lattice around the current paddle y), simulate the full rally
forward with the EXACT env step math (ball advance, wall folds, paddle
bounce/spin, rate-limited tracker pursuit), and choose the contact point
whose return the tracker misses by the widest margin. This is not a
practical agent (63-way rollout sim per step) — it is an upper-bound probe
for learned play, and its per-decision structure (aim where the tracker
cannot arrive) is exactly what the RL agent must discover.

    python scripts/pong_oracle.py [games] [opponent]

Prints one JSON line: {"oracle_return": ..., "games": N, "opponent": ...}.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# A pure-analysis tool: CPU is always the right backend for it, and it must
# not take the chip from a process that is training on it.
jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np

from asyncrl_tpu.envs.pong import (
    AGENT_SPEED,
    AGENT_X,
    BALL_VX,
    MAX_SPIN,
    OPP_SPEED,
    OPP_X,
    PADDLE_HALF,
    PREDICTIVE_SPEED,
    Pong,
)

SIM_STEPS = 80  # > two court crossings at |vx| = 0.03 over 0.9 width
N_CANDIDATES = 63  # lattice offsets -31..31 around the current paddle y
DEADZONE = 0.026  # match reference_policy's hold band


def _deadzone(skip: int) -> float:
    """Hold band for the skip-quantized motion model — ONE definition
    shared by the rally sim and the emitted-action rule (they must agree
    or the oracle scores targets under a motion model it doesn't play):
    half a decision-move (moving when closer overshoots more than
    holding); skip=1 keeps the calibrated DEADZONE."""
    return DEADZONE if skip == 1 else skip * AGENT_SPEED / 2.0


def _sim_rally(ball, agent_y, opp_y, target, opp_speed, skip=1):
    """Exact forward sim of one rally with the agent parked toward
    ``target``: returns (our_miss, opp_miss, margin) where margin is the
    |ball_y - opp_y| - PADDLE_HALF gap at the opponent-plane crossing
    (positive = the tracker cannot reach the return).

    ``skip`` models frame-skip control (the ALE semantics the skip-4
    presets train under): the move/hold decision is recomputed only every
    ``skip`` core steps and held in between, so one decision displaces
    the paddle by skip x AGENT_SPEED — the coarse-control quantization
    whose greedy ceiling this oracle exists to bound. The hold band
    scales to half a decision-move (moving when closer than that
    overshoots more than holding); skip=1 keeps the original DEADZONE."""
    deadzone = _deadzone(skip)

    def body(carry, t):
        ball, ay, oy, adir, our_miss, opp_miss, margin, live = carry
        # Agent: direction re-decided once per DECISION (every skip core
        # steps), frozen in between — exactly what a frame-skipped action
        # stream can express.
        dy = target - ay
        new_dir = jnp.where(jnp.abs(dy) > deadzone, jnp.sign(dy), 0.0)
        adir = jnp.where(t % skip == 0, new_dir, adir)
        ay = jnp.clip(
            ay + adir * AGENT_SPEED,
            PADDLE_HALF,
            1.0 - PADDLE_HALF,
        )
        # Tracker: rate-limited pursuit of the ball's current y. Under
        # frame_skip the env quantizes the rival to one clipped pursuit
        # move per agent decision (envs/pong.py opponent_every) — mirror
        # that exactly or the oracle would bound the wrong game.
        opp_cap = opp_speed * skip
        opp_move = jnp.where(
            t % skip == 0,
            jnp.clip(ball[1] - oy, -opp_cap, opp_cap),
            0.0,
        )
        oy = jnp.clip(oy + opp_move, PADDLE_HALF, 1.0 - PADDLE_HALF)
        # Ball advance + wall fold (envs/pong.py step math).
        x = ball[0] + ball[2]
        y = ball[1] + ball[3]
        vx, vy = ball[2], ball[3]
        vy = jnp.where(y < 0.0, jnp.abs(vy), vy)
        y = jnp.where(y < 0.0, -y, y)
        vy = jnp.where(y > 1.0, -jnp.abs(vy), vy)
        y = jnp.where(y > 1.0, 2.0 - y, y)

        cross_agent = (x >= AGENT_X) & (vx > 0)
        cross_opp = (x <= OPP_X) & (vx < 0)
        agent_hit = cross_agent & (jnp.abs(y - ay) <= PADDLE_HALF)
        opp_hit = cross_opp & (jnp.abs(y - oy) <= PADDLE_HALF)

        our_miss = our_miss | (live & cross_agent & ~agent_hit)
        opp_miss = opp_miss | (live & cross_opp & ~opp_hit)
        margin = jnp.where(
            live & cross_opp, jnp.abs(y - oy) - PADDLE_HALF, margin
        )
        live = live & ~(cross_opp | (cross_agent & ~agent_hit))

        new_vx = jnp.where(
            agent_hit, -BALL_VX, jnp.where(opp_hit, BALL_VX, vx)
        )
        new_vy = jnp.where(
            agent_hit,
            MAX_SPIN * (y - ay) / PADDLE_HALF,
            jnp.where(opp_hit, MAX_SPIN * (y - oy) / PADDLE_HALF, vy),
        )
        new_x = jnp.where(
            agent_hit, 2.0 * AGENT_X - x, jnp.where(opp_hit, 2.0 * OPP_X - x, x)
        )
        ball = jnp.stack([new_x, y, new_vx, new_vy])
        return (ball, ay, oy, adir, our_miss, opp_miss, margin, live), None

    init = (
        ball,
        agent_y,
        opp_y,
        jnp.float32(0.0),
        jnp.asarray(False),
        jnp.asarray(False),
        jnp.float32(-1.0),
        jnp.asarray(True),
    )
    (_, _, _, _, our_miss, opp_miss, margin, _), _ = jax.lax.scan(
        body, init, jnp.arange(SIM_STEPS)
    )
    return our_miss, opp_miss, margin


def oracle_policy(obs: jax.Array, opp_speed: float, skip: int = 1) -> jax.Array:
    """One-ply lookahead: pick the reachable contact point whose return the
    tracker misses by the widest margin (motion model quantized to
    ``skip``-step decisions — see _sim_rally)."""
    ball = jnp.stack(
        [obs[0], obs[1], obs[2] * BALL_VX, obs[3] * MAX_SPIN]
    )
    ay, oy = obs[4], obs[5]
    deadzone = _deadzone(skip)

    ks = jnp.arange(N_CANDIDATES, dtype=jnp.float32) - (N_CANDIDATES // 2)
    targets = jnp.clip(
        ay + AGENT_SPEED * ks, PADDLE_HALF, 1.0 - PADDLE_HALF
    )

    def score(target):
        our_miss, opp_miss, margin = _sim_rally(
            ball, ay, oy, target, opp_speed, skip
        )
        return jnp.where(
            our_miss,
            -1e6 + margin,
            jnp.where(opp_miss, 1e3 + margin, margin),
        )

    scores = jax.vmap(score)(targets)
    best = targets[jnp.argmax(scores)]
    # Ball receding: park at the court center (serve-return readiness).
    target = jnp.where(ball[2] > 0, best, 0.5)
    dy = target - ay
    return jnp.where(
        dy > deadzone, 2, jnp.where(dy < -deadzone, 3, 0)
    ).astype(jnp.int32)


def play(env, policy_fn, n=32, seed=0, max_steps=3000):
    def one(key):
        st = env.init(key)

        def body(carry, k):
            st, total, done = carry
            obs = env.observe(st)
            a = policy_fn(obs, k)
            st2, ts = env.step(st, a, k)
            st2 = jax.tree.map(lambda n_, o: jnp.where(done, o, n_), st2, st)
            total = total + jnp.where(done, 0.0, ts.reward)
            return (st2, total, done | ts.done), None

        keys = jax.random.split(key, max_steps)
        (_, total, _), _ = jax.lax.scan(
            body, (st, 0.0, jnp.asarray(False)), keys
        )
        return total

    keys = jax.random.split(jax.random.PRNGKey(seed), n)
    return np.asarray(jax.jit(jax.vmap(one))(keys))


def main() -> int:
    games = int(sys.argv[1]) if len(sys.argv) > 1 else 32
    opponent = sys.argv[2] if len(sys.argv) > 2 else "tracker"
    skip = int(sys.argv[3]) if len(sys.argv) > 3 else 1
    # Episode cap in DECISIONS. The default 3000 is the repo's strict
    # scoring-rate cap; pass a larger cap to measure the win-margin
    # (ALE-semantics) ceiling — at skip-4 the default truncates after
    # 750 decisions, which conflates truncation with kinematics (the
    # round-5 retirement decision was re-measured at cap 6000, where
    # every game completes).
    cap = int(sys.argv[4]) if len(sys.argv) > 4 else 3000
    opp_speed = OPP_SPEED if opponent == "tracker" else PREDICTIVE_SPEED
    env = Pong(opponent, opponent_every=skip, max_steps=cap * skip)
    if skip > 1:
        # The skip-4 presets' semantics (envs/wrappers.py FrameSkip + the
        # decision-quantized rival the registry configures): each oracle
        # decision repeats for `skip` core steps — the ceiling this
        # measures is the one the pong_t2t_ale4 / pixel arms train under.
        from asyncrl_tpu.envs.wrappers import FrameSkip

        env = FrameSkip(env, skip)
    returns = play(
        env,
        lambda obs, k: oracle_policy(obs, opp_speed, skip),
        n=games,
        max_steps=cap,
    )
    out = {
        "oracle_return": round(float(returns.mean()), 2),
        "min": float(returns.min()),
        "max": float(returns.max()),
        "games": games,
        "opponent": opponent,
        "pong_max_steps": cap,
        **({"frame_skip": skip} if skip > 1 else {}),
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    main()
