"""Shared 4-frame-stack pixel wrapper for the Atari stand-in games.

The TPU-native version of the reference's Atari preprocessing pipeline
(SURVEY.md §3.3: grayscale, 84x84, stack 4): a core vector-state game plus an
on-device iota-mask renderer become an Atari-shaped pixel env whose frames
fuse into the rollout scan. One implementation serves every game
(``envs/pong.py``, ``envs/breakout.py``, future additions), so the stacking /
auto-reset / truncation-bootstrap frame logic cannot diverge per game.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from flax import struct

from asyncrl_tpu.envs.core import Environment, EnvSpec, TimeStep


@struct.dataclass
class PixelState:
    core: Any
    frames: jax.Array  # [FRAME, FRAME, 4] most-recent-last


class FrameStackPixels(Environment):
    """84x84x4 uint8 stacked-frame observations over a vector-state core.

    ``render_state(core_state)`` paints the current frame;
    ``render_last_obs(vector_obs)`` reconstructs the true pre-reset final
    frame from the core's vector ``last_obs`` (used only for truncation
    bootstrapping — the post-reset stack is rebuilt from the fresh frame, so
    no pixels leak across episodes).
    """

    def __init__(
        self,
        core: Environment,
        render_state: Callable[[Any], jax.Array],
        render_last_obs: Callable[[jax.Array], jax.Array],
        frame: int = 84,
        frame_skip: int = 1,
        frame_pool: bool = False,
        sticky_actions: float = 0.0,
    ):
        """``frame_skip`` repeats the action over that many core steps per
        env step (rewards summed, frozen at episode end); ``frame_pool``
        additionally pushes the elementwise MAX of the last two rendered
        raw frames — the ALE flicker recipe (SURVEY.md §3.3). Pooling
        defaults OFF: these renderers never flicker, so the pooled frame is
        bit-identical to the last frame and the second render would be pure
        hot-loop cost; the knob exists for future flickering renderers and
        strict-parity runs. ``sticky_actions`` applies at the RAW frame
        level (each core step of the window redraws the stick — the
        Machado et al. 2018 / ALE semantics), which is why it lives here
        and not in an outer wrapper."""
        self._sticky = sticky_actions
        if sticky_actions > 0.0:
            from asyncrl_tpu.envs.wrappers import StickyActions

            self._core = StickyActions(core, sticky_actions)
            self._game = lambda s: s[0]  # sticky state = (inner, prev)
        else:
            self._core = core
            self._game = lambda s: s
        self._render = render_state
        self._render_last = render_last_obs
        self._skip = frame_skip
        self._pool = frame_pool and frame_skip > 1
        self.spec = EnvSpec(
            obs_shape=(frame, frame, 4),
            num_actions=core.spec.num_actions,
            obs_dtype=jnp.uint8,
        )

    def init(self, key: jax.Array) -> PixelState:
        core = self._core.init(key)
        with jax.named_scope("render"):
            frame = self._render(self._game(core))
        return PixelState(
            core=core, frames=jnp.repeat(frame[..., None], 4, axis=-1)
        )

    def observe(self, state: PixelState) -> jax.Array:
        return state.frames

    def step(
        self, state: PixelState, action: jax.Array, key: jax.Array
    ) -> tuple[PixelState, TimeStep]:
        if self._skip > 1:
            from asyncrl_tpu.envs.wrappers import frame_skip_scan

            new_core, ts, prev_core = frame_skip_scan(
                self._core, state.core, action, key, self._skip
            )
            with jax.named_scope("render"):
                frame = self._render(self._game(new_core))
            if self._pool:
                # ALE 2-frame max pool over the window's last two raw
                # frames. On an auto-reset boundary new_core is already the
                # fresh episode — skip pooling there (the done branch below
                # rebuilds the stack from the fresh frame anyway).
                with jax.named_scope("render"):
                    prev_frame = self._render(self._game(prev_core))
                pooled = jnp.maximum(frame, prev_frame)
                frame = jnp.where(ts.done, frame, pooled)
        else:
            new_core, ts = self._core.step(state.core, action, key)
            with jax.named_scope("render"):
                frame = self._render(self._game(new_core))
        shifted = jnp.concatenate(
            [state.frames[..., 1:], frame[..., None]], axis=-1
        )
        # Post-reset state gets a full stack of its own frame, exactly like
        # a fresh init — no leakage of the previous episode's pixels.
        frames = jnp.where(
            ts.done, jnp.repeat(frame[..., None], 4, axis=-1), shifted
        )
        with jax.named_scope("render"):
            last_frame = self._render_last(ts.last_obs)
        last_frames = jnp.concatenate(
            [state.frames[..., 1:], last_frame[..., None]], axis=-1
        )
        new_state = PixelState(core=new_core, frames=frames)
        return new_state, TimeStep(
            obs=frames,
            reward=ts.reward,
            terminated=ts.terminated,
            truncated=ts.truncated,
            last_obs=last_frames,
        )
