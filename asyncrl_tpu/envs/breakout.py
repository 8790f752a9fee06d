"""Pure-JAX Breakout: second game of the Atari stand-in family (with
``envs/pong.py``) for the reference's Atari-57 IMPALA workload
(BASELINE.json:9) — ale-py is unavailable in this image (SURVEY.md §7.4 R1),
so the game is reimplemented as a functional JAX env that runs on the TPU,
vectorized under ``vmap`` like every Anakin env.

Game rules mirror ALE Breakout's structure: a 6x12 brick wall, row-scaled
points (1/1/4/4/7/7 from bottom to top, max score 288 per wall), 5 lives,
the 4-action ALE set (NOOP/FIRE/RIGHT/LEFT), and paddle-offset ball control
(hit position sets the outgoing horizontal velocity, which is the skill the
policy must learn to aim at remaining bricks). FIRE serves the ball after a
life is lost, as in the original; serving also happens automatically after
``AUTO_SERVE`` steps so a NOOP-only policy still generates transitions.

Two observation variants:

- ``JaxBreakout-v0`` — 78-dim vector (ball pos/vel, paddle x, lives, 72
  brick-alive bits); pairs with the MLP torso.
- ``JaxBreakoutPixels-v0`` — 84x84x4 stacked grayscale frames rendered
  on-device (paddle/ball/bricks via iota masks), Atari-preprocessing-shaped
  (SURVEY.md §3.3); pairs with the conv torsos.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from asyncrl_tpu.envs.core import Environment, EnvSpec, TimeStep
from asyncrl_tpu.envs.pixels import FrameStackPixels

ROWS, COLS = 6, 12
BRICK_TOP = 0.88  # top of the brick band
ROW_H = 0.04  # brick row height
BRICK_BOT = BRICK_TOP - ROWS * ROW_H  # 0.64
# numpy, not jnp: a module-level device array would initialize the jax
# backend at import (registry imports every builtin env — ANY
# `import asyncrl_tpu.envs` would then take the chip, before an entry
# point has chosen its platform). Converted to a traced constant at the
# use site.
ROW_POINTS = np.array([1.0, 1.0, 4.0, 4.0, 7.0, 7.0], np.float32)  # bottom→top

PADDLE_Y = 0.06  # paddle plane (bottom)
PADDLE_HALF = 0.075  # paddle half-width
PADDLE_SPEED = 0.05
BALL_SPEED_Y = 0.025  # constant |vy|
MAX_VX = 0.035  # |vx| from the outermost paddle hit
LIVES = 5
AUTO_SERVE = 8  # steps without FIRE before the serve happens anyway
MAX_STEPS = 3000
NUM_ACTIONS = 4  # ALE Breakout action set: NOOP/FIRE/RIGHT/LEFT
FRAME = 84


@struct.dataclass
class BreakoutState:
    ball: jax.Array  # [4] = x, y, vx, vy
    paddle_x: jax.Array  # scalar
    bricks: jax.Array  # [ROWS, COLS] bool, row 0 = bottom of the band
    lives: jax.Array  # int32
    held: jax.Array  # int32 steps the ball has been waiting on the paddle
    t: jax.Array  # int32 step count


def _action_dx(action: jax.Array) -> jax.Array:
    """ALE Breakout mapping: 2 = RIGHT (+x), 3 = LEFT (−x)."""
    return jnp.where(action == 2, 1.0, 0.0) - jnp.where(action == 3, 1.0, 0.0)


class Breakout(Environment):
    """Vector-observation Breakout (78-dim state)."""

    spec = EnvSpec(obs_shape=(4 + 2 + ROWS * COLS,), num_actions=NUM_ACTIONS)

    def init(self, key: jax.Array) -> BreakoutState:
        del key  # serve direction comes from the step-time key
        return BreakoutState(
            ball=jnp.array([0.5, PADDLE_Y + 0.02, 0.0, 0.0], jnp.float32),
            paddle_x=jnp.float32(0.5),
            bricks=jnp.ones((ROWS, COLS), bool),
            lives=jnp.int32(LIVES),
            held=jnp.zeros((), jnp.int32),
            t=jnp.zeros((), jnp.int32),
        )

    def observe(self, state: BreakoutState) -> jax.Array:
        b = state.ball
        return jnp.concatenate(
            [
                jnp.stack(
                    [
                        b[0],
                        b[1],
                        b[2] / MAX_VX,
                        b[3] / BALL_SPEED_Y,
                        state.paddle_x,
                        state.lives.astype(jnp.float32) / LIVES,
                    ]
                ),
                state.bricks.astype(jnp.float32).reshape(-1),
            ]
        )

    def step(
        self, state: BreakoutState, action: jax.Array, key: jax.Array
    ) -> tuple[BreakoutState, TimeStep]:
        serve_key, _ = jax.random.split(key)

        paddle_x = jnp.clip(
            state.paddle_x + PADDLE_SPEED * _action_dx(action),
            PADDLE_HALF,
            1.0 - PADDLE_HALF,
        )

        # Held ball rides the paddle until FIRE (action 1) or auto-serve.
        in_play = (state.ball[2] != 0.0) | (state.ball[3] != 0.0)
        held = jnp.where(in_play, 0, state.held + 1)
        serve = ~in_play & ((action == 1) | (held >= AUTO_SERVE))
        serve_vx = jax.random.uniform(
            serve_key, (), jnp.float32, -0.5 * MAX_VX, 0.5 * MAX_VX
        )
        ball = jnp.where(
            serve,
            jnp.stack(
                [paddle_x, PADDLE_Y + 0.02, serve_vx, jnp.float32(BALL_SPEED_Y)]
            ),
            state.ball,
        )
        ball = jnp.where(
            in_play | serve, ball, ball.at[0].set(paddle_x)
        )  # still held: ride the paddle

        # Ball advance + side/top wall bounces (mirror reflection).
        x = ball[0] + ball[2]
        y = ball[1] + ball[3]
        vx, vy = ball[2], ball[3]
        vx = jnp.where(x < 0.0, jnp.abs(vx), jnp.where(x > 1.0, -jnp.abs(vx), vx))
        x = jnp.where(x < 0.0, -x, jnp.where(x > 1.0, 2.0 - x, x))
        vy = jnp.where(y > 1.0, -jnp.abs(vy), vy)
        y = jnp.where(y > 1.0, 2.0 - y, y)

        # Brick collision: the cell the ball sits in, if inside the band.
        in_band = (y >= BRICK_BOT) & (y < BRICK_TOP)
        row = jnp.clip(
            jnp.floor((y - BRICK_BOT) / ROW_H).astype(jnp.int32), 0, ROWS - 1
        )
        col = jnp.clip(jnp.floor(x * COLS).astype(jnp.int32), 0, COLS - 1)
        hit_brick = in_band & state.bricks[row, col]
        bricks = state.bricks.at[row, col].set(
            jnp.where(hit_brick, False, state.bricks[row, col])
        )
        reward = jnp.where(
            hit_brick, jnp.asarray(ROW_POINTS)[row], 0.0
        ).astype(jnp.float32)
        vy = jnp.where(hit_brick, -vy, vy)

        # Paddle bounce: offset sets outgoing vx (the aiming mechanic).
        at_paddle = (y <= PADDLE_Y) & (vy < 0.0)
        offset = (x - paddle_x) / PADDLE_HALF
        paddle_hit = at_paddle & (jnp.abs(offset) <= 1.0)
        vy = jnp.where(paddle_hit, jnp.abs(vy), vy)
        vx = jnp.where(paddle_hit, MAX_VX * offset, vx)
        y = jnp.where(paddle_hit, 2.0 * PADDLE_Y - y, y)

        # Life lost: ball below the paddle plane without a hit.
        lost = at_paddle & ~paddle_hit
        lives = state.lives - lost.astype(jnp.int32)
        # Back to held-on-paddle serve state after a lost life.
        ball = jnp.where(
            lost,
            jnp.stack([paddle_x, jnp.float32(PADDLE_Y + 0.02), 0.0, 0.0]),
            jnp.stack([x, y, vx, vy]),
        )

        t = state.t + 1
        cleared = ~bricks.any()
        terminated = cleared | (lives <= 0)
        truncated = (t >= MAX_STEPS) & ~terminated
        done = terminated | truncated

        ended = BreakoutState(
            ball=ball, paddle_x=paddle_x, bricks=bricks, lives=lives,
            held=jnp.where(lost, 0, held), t=t,
        )
        fresh = self.init(key)
        new_state = jax.tree.map(lambda f, e: jnp.where(done, f, e), fresh, ended)
        ts = TimeStep(
            obs=self.observe(new_state),
            reward=reward,
            terminated=terminated,
            truncated=truncated,
            last_obs=self.observe(ended),
        )
        return new_state, ts


def render_court(
    ball_x: jax.Array,
    ball_y: jax.Array,
    paddle_x: jax.Array,
    bricks: jax.Array,
) -> jax.Array:
    """Paint the court to an [FRAME, FRAME] uint8 {0,1} image with iota
    masks (fuses into the rollout scan; SURVEY.md §3.3). Row 0 of the image
    is the TOP of the court (y=1) so bricks render at the top of the frame —
    note this is the INVERSE of the Pong renderer, which maps row 0 to court
    y=0 (immaterial there: Pong's court is vertically symmetric)."""
    rows_g = jax.lax.broadcasted_iota(jnp.float32, (FRAME, FRAME), 0) / (FRAME - 1)
    cols_g = jax.lax.broadcasted_iota(jnp.float32, (FRAME, FRAME), 1) / (FRAME - 1)
    y_g = 1.0 - rows_g  # court y of each pixel row
    half_w = 1.5 / FRAME

    ball = (jnp.abs(cols_g - ball_x) <= half_w) & (jnp.abs(y_g - ball_y) <= half_w)
    paddle = (jnp.abs(cols_g - paddle_x) <= PADDLE_HALF) & (
        jnp.abs(y_g - PADDLE_Y) <= half_w
    )

    # Brick pixels: map each pixel to its (row, col) cell, gather liveness.
    in_band = (y_g >= BRICK_BOT) & (y_g < BRICK_TOP)
    cell_r = jnp.clip(
        jnp.floor((y_g - BRICK_BOT) / ROW_H).astype(jnp.int32), 0, ROWS - 1
    )
    cell_c = jnp.clip(jnp.floor(cols_g * COLS).astype(jnp.int32), 0, COLS - 1)
    brick = in_band & bricks[cell_r, cell_c]

    return (ball | paddle | brick).astype(jnp.uint8)


def render(state: BreakoutState) -> jax.Array:
    return render_court(
        state.ball[0], state.ball[1], state.paddle_x, state.bricks
    )


class BreakoutPixels(FrameStackPixels):
    """Pixel-observation Breakout: 84x84x4 stacked frames, Atari-shaped.

    The vector ``last_obs`` layout for frame reconstruction: obs[0]=ball_x,
    obs[1]=ball_y, obs[4]=paddle_x, obs[6:]=brick-alive bits.
    """

    def __init__(
        self,
        frame_skip: int = 1,
        frame_pool: bool = False,
        sticky_actions: float = 0.0,
    ):
        super().__init__(
            Breakout(),
            render_state=render,
            render_last_obs=lambda lo: render_court(
                lo[0], lo[1], lo[4], lo[6:].reshape(ROWS, COLS) > 0.5
            ),
            frame=FRAME,
            frame_skip=frame_skip,
            frame_pool=frame_pool,
            sticky_actions=sticky_actions,
        )
