"""ALE-semantics knobs (envs/wrappers.py; SURVEY.md §3.3, VERDICT.md round
1, Next #7): frame-skip with end-of-episode freeze, 2-frame max pooling on
the pixel path, sticky actions, and the registry/config plumbing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import struct

from asyncrl_tpu.envs.core import Environment, EnvSpec, TimeStep
from asyncrl_tpu.envs.wrappers import (
    FrameSkip,
    StickyActions,
    frame_skip_scan,
)
from asyncrl_tpu.utils.config import Config


@struct.dataclass
class _CounterState:
    t: jax.Array
    last_action: jax.Array


class CounterEnv(Environment):
    """Deterministic toy: reward == the action taken each live step;
    terminates after ``horizon`` steps, auto-resets to t=0."""

    spec = EnvSpec(obs_shape=(1,), num_actions=3)

    def __init__(self, horizon=3):
        self.horizon = horizon

    def init(self, key):
        del key
        return _CounterState(
            t=jnp.zeros((), jnp.int32), last_action=jnp.zeros((), jnp.int32)
        )

    def observe(self, state):
        return state.t[None].astype(jnp.float32)

    def step(self, state, action, key):
        t = state.t + 1
        terminated = t >= self.horizon
        new = _CounterState(
            t=jnp.where(terminated, 0, t),
            last_action=jnp.asarray(action, jnp.int32),
        )
        return new, TimeStep(
            obs=self.observe(new),
            reward=jnp.asarray(action, jnp.float32),
            terminated=terminated,
            truncated=jnp.zeros((), bool),
            last_obs=t[None].astype(jnp.float32),
        )


def test_frame_skip_sums_rewards_and_freezes_at_done():
    env = CounterEnv(horizon=5)
    key = jax.random.PRNGKey(0)
    state = env.init(key)

    # Window entirely inside the episode: rewards sum over all 4 repeats.
    new_state, ts, prev = frame_skip_scan(env, state, 1, key, 4)
    assert float(ts.reward) == 4.0
    assert int(new_state.t) == 4 and not bool(ts.done)
    assert int(prev.t) == 3  # the state one live step before the last

    # Window crossing the episode end (t=4 -> done at t=5): only the live
    # step plays; the rest of the window is frozen, not leaked into the
    # next episode.
    new_state, ts, _ = frame_skip_scan(env, new_state, 1, key, 4)
    assert float(ts.reward) == 1.0
    assert bool(ts.terminated)
    assert int(new_state.t) == 0  # auto-reset state, untouched after done
    assert float(ts.last_obs[0]) == 5.0


def test_frame_skip_wrapper_contract():
    env = FrameSkip(CounterEnv(horizon=100), skip=4)
    assert env.spec.num_actions == 3
    state = env.init(jax.random.PRNGKey(0))
    state, ts = env.step(state, 2, jax.random.PRNGKey(1))
    assert float(ts.reward) == 8.0 and int(state.t) == 4
    with pytest.raises(ValueError, match="frame_skip"):
        FrameSkip(CounterEnv(), skip=1)


def test_sticky_actions_statistics_and_reset():
    env = StickyActions(CounterEnv(horizon=10_000), p=0.25)
    state = env.init(jax.random.PRNGKey(0))

    # Alternate actions 1, 2, 1, 2, ...: the executed action (recorded by
    # the env) repeats the PREVIOUS one with p=0.25.
    def body(carry, inp):
        state = carry
        i, key = inp
        action = 1 + (i % 2)
        state, ts = env.step(state, action, key)
        executed = state[0].last_action
        return state, (action, executed)

    n = 4000
    keys = jax.random.split(jax.random.PRNGKey(1), n)
    _, (intended, executed) = jax.lax.scan(
        body, state, (jnp.arange(n), keys)
    )
    stick_rate = float(jnp.mean((executed != intended).astype(jnp.float32)))
    # Under period-2 alternation a stick from a STALE slot lands back on
    # the intended action (invisible), so the visible-mismatch rate is the
    # stationary stale probability f*p with f = 1/(1+p): 0.25/1.25 = 0.2
    # exactly — not p itself. 5-sigma band around 0.2.
    assert 0.168 < stick_rate < 0.232, stick_rate

    # Stickiness must not leak across episode boundaries: after done, the
    # sticky slot resets to the no-op.
    short = StickyActions(CounterEnv(horizon=1), p=0.5)
    s = short.init(jax.random.PRNGKey(0))
    s, ts = short.step(s, 2, jax.random.PRNGKey(2))
    assert bool(ts.terminated) and int(s[1]) == 0

    with pytest.raises(ValueError, match="sticky_actions"):
        StickyActions(CounterEnv(), p=0.0)


def test_registry_applies_knobs():
    from asyncrl_tpu.envs import registry
    from asyncrl_tpu.envs.pixels import FrameStackPixels
    from asyncrl_tpu.envs.pong import PREDICTIVE_SPEED

    cfg = Config(frame_skip=4, sticky_actions=0.25)
    env = registry.make("CartPole-v1", cfg)
    # Sticky INSIDE skip: ALE redraws the stick at every raw frame of the
    # window, not once per agent decision.
    assert isinstance(env, FrameSkip)
    assert isinstance(env._env, StickyActions)

    # Pixel envs take both knobs internally (raw-frame stick draws +
    # pooling hooks); the generic wrappers must NOT stack on top.
    env = registry.make("JaxPongPixels-v0", cfg)
    assert isinstance(env, FrameStackPixels)
    assert env._skip == 4 and env._sticky == 0.25
    assert isinstance(env._core, StickyActions)

    env = registry.make("JaxPong-v0", Config(pong_opponent="predictive"))
    assert env._opponent == "predictive"
    assert env._opp_speed == PREDICTIVE_SPEED

    # No config (spec-only callers): no wrapping, no knobs.
    assert registry.make("CartPole-v1").__class__.__name__ == "CartPole"


def test_pixel_frame_skip_steps_and_pools():
    from asyncrl_tpu.envs.pong import PongPixels

    env = PongPixels(frame_skip=4)
    key = jax.random.PRNGKey(0)
    state = env.init(key)
    for i in range(3):
        state, ts = jax.jit(env.step)(state, 0, jax.random.PRNGKey(i))
    assert ts.obs.shape == (84, 84, 4) and ts.obs.dtype == jnp.uint8
    assert set(np.unique(np.asarray(ts.obs))) <= {0, 1}
    # 4 core steps ran per env step: the underlying game clock advanced 12.
    assert int(state.core.t) == 12


def test_ale_knobs_still_learn():
    """VERDICT 'Done = knobs on + still learns' — CI-sized proxy: IMPALA
    on CartPole with frame_skip=2 + sticky 0.25 still beats the random
    baseline clearly. (Pong/atari_impala learning with knobs is a
    bench-scale run of hours, not a unit
    test; this pins that the wrappers don't break gradient flow or
    episode accounting.)"""
    from asyncrl_tpu import make_agent

    agent = make_agent(
        env_id="CartPole-v1", algo="impala", num_envs=256, unroll_len=16,
        frame_skip=2, sticky_actions=0.25, precision="f32",
        learning_rate=1e-3, log_every=20, total_env_steps=1_500_000, seed=3,
    )
    hist = agent.train()
    ret = agent.evaluate(num_episodes=16, max_steps=250)
    assert np.isfinite(hist[-1]["loss"])
    # Returns stay in CORE-step units (frame_skip sums the +1s). Random
    # play scores ~22; the bar is set well above it but below clean-env
    # mastery — sticky actions at p=0.25 cap controllability, and the
    # EVAL env carries the same knobs.
    assert ret > 60, f"no learning with ALE knobs: eval {ret}"


def test_host_pool_refuses_unhonorable_knobs():
    """Native/gym pools can't implement the JAX-registry env knobs: an
    explicit choice refuses; 'auto' reroutes to the JAX pool."""
    from asyncrl_tpu.rollout.sebulba import JaxHostPool, make_host_pool

    cfg = Config(
        env_id="JaxPong-v0", host_pool="native", frame_skip=4,
        pong_opponent="predictive",
    )
    with pytest.raises(ValueError, match="cannot honor"):
        make_host_pool(cfg, num_envs=2, seed=0)

    pool = make_host_pool(cfg.replace(host_pool="auto"), num_envs=2, seed=0)
    assert isinstance(pool, JaxHostPool)


def test_frame_pool_reachable_from_config():
    """frame_pool is a real Config knob plumbed to the pixel envs (a doc
    claimed it before the plumbing existed — regression guard)."""
    from asyncrl_tpu.envs import registry

    env = registry.make(
        "JaxPongPixels-v0", Config(frame_skip=4, frame_pool=True)
    )
    assert env._pool is True
    state = env.init(jax.random.PRNGKey(0))
    state, ts = jax.jit(env.step)(state, 0, jax.random.PRNGKey(1))
    assert ts.obs.shape == (84, 84, 4)


def test_frame_skip_forwards_duel_protocol():
    """FrameSkip.step_duel == manually repeating step_duel with both
    actions held, frozen at the first done — and the mirror view passes
    through untouched. Non-duel envs must NOT grow the protocol."""
    from asyncrl_tpu.envs.cartpole import CartPole
    from asyncrl_tpu.envs.pong import DuelPong
    from asyncrl_tpu.envs.wrappers import FrameSkip

    assert not hasattr(FrameSkip(CartPole(), 2), "step_duel")

    env = DuelPong()
    wrapped = FrameSkip(env, 3)
    key = jax.random.PRNGKey(0)
    state = env.init(key)
    np.testing.assert_allclose(
        np.asarray(wrapped.observe_opponent(state)),
        np.asarray(env.observe_opponent(state)),
    )

    a, o = jnp.int32(1), jnp.int32(2)
    step_key = jax.random.PRNGKey(7)
    got_state, got_ts = wrapped.step_duel(state, a, o, step_key)

    keys = jax.random.split(step_key, 3)
    cur, ts = env.step_duel(state, a, o, keys[0])
    total = ts.reward
    done = ts.done
    for k in keys[1:]:
        nxt, ts2 = env.step_duel(cur, a, o, k)
        keep = float(np.logical_not(done))
        total = total + keep * ts2.reward
        if not bool(done):
            cur, ts = nxt, ts2
        done = np.logical_or(done, ts2.done)
    np.testing.assert_allclose(float(got_ts.reward), float(total), rtol=1e-6)
    for g, w in zip(jax.tree.leaves(got_state), jax.tree.leaves(cur)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6)


def test_sticky_actions_duel_per_paddle_independence():
    """Duel stickiness draws per paddle: state carries two prev slots that
    reset independently at episode ends, and executed actions differ from
    the requested ones at roughly rate p for EACH paddle."""
    from asyncrl_tpu.envs.pong import DuelPong
    from asyncrl_tpu.envs.wrappers import StickyActions

    env = StickyActions(DuelPong(), 0.25)
    assert hasattr(env, "step_duel")
    key = jax.random.PRNGKey(1)
    state = env.init(key)
    assert len(state) == 3  # (inner, prev_agent, prev_opp)

    # Alternate actions so a stick is visible as prev != requested.
    sticks_a = sticks_o = 0
    n = 400
    k = key
    for i in range(n):
        k, sub = jax.random.split(k)
        a = jnp.int32(1 + (i % 2))
        o = jnp.int32(2 - (i % 2))
        prev_a, prev_o = state[1], state[2]
        state, ts = env.step_duel(state, a, o, sub)
        # executed action is recorded in the new prev slots (unless done
        # reset them); compare against the requested ones.
        if not bool(ts.done):
            sticks_a += int(state[1] != a)
            sticks_o += int(state[2] != o)
    for rate in (sticks_a / n, sticks_o / n):
        assert 0.1 < rate < 0.45, f"sticky rate {rate} far from p=0.25"
