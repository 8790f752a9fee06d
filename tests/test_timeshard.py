"""Time-sharded recurrence solve vs the single-device solution (8-device
CPU mesh, time axis sharded — the SP-analogue test from SURVEY.md §5.7)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncrl_tpu.ops.scan import reverse_linear_scan
from asyncrl_tpu.ops.vtrace import vtrace
from asyncrl_tpu.parallel.mesh import make_mesh, shard_map
from asyncrl_tpu.parallel.timeshard import make_timesharded_solver


@pytest.mark.parametrize("T,B", [(8, 1), (64, 4), (128, 16)])
def test_timesharded_equals_local(T, B, devices):
    rng = np.random.default_rng(T + B)
    a = jnp.asarray(rng.uniform(0, 1, (T, B)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(T, B)).astype(np.float32))

    mesh = make_mesh((8,), ("sp",))
    solver = make_timesharded_solver(mesh, "sp")
    got = solver(a, b)
    expected = reverse_linear_scan(a, b)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), rtol=1e-4, atol=1e-5
    )


def test_timesharded_with_episode_cuts(devices):
    """Zeros in `a` (episode boundaries) must cut inflow across segments."""
    T, B = 32, 2
    rng = np.random.default_rng(0)
    a = rng.uniform(0.5, 1.0, (T, B)).astype(np.float32)
    a[5, 0] = 0.0
    a[17, 1] = 0.0  # cut exactly at a segment boundary region
    b = rng.normal(size=(T, B)).astype(np.float32)

    mesh = make_mesh((8,), ("sp",))
    solver = make_timesharded_solver(mesh, "sp")
    got = solver(jnp.asarray(a), jnp.asarray(b))
    expected = reverse_linear_scan(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), rtol=1e-4, atol=1e-5
    )


def test_vtrace_consistency_long_fragment(devices):
    """End-to-end: V-trace targets computed via the time-sharded solver path
    equal the standard vtrace() on a long fragment."""
    T, B = 256, 2
    rng = np.random.default_rng(1)
    behaviour = rng.normal(-1.0, 0.3, (T, B)).astype(np.float32)
    target = behaviour + rng.normal(0, 0.2, (T, B)).astype(np.float32)
    rewards = rng.normal(size=(T, B)).astype(np.float32)
    discounts = (0.99 * (rng.uniform(size=(T, B)) > 0.05)).astype(np.float32)
    values = rng.normal(size=(T, B)).astype(np.float32)
    bootstrap = rng.normal(size=(B,)).astype(np.float32)

    out = vtrace(*map(jnp.asarray,
                      (behaviour, target, rewards, discounts, values, bootstrap)))

    # Recompute the core recurrence through the sharded solver.
    rhos = np.exp(target - behaviour)
    cr = np.minimum(1.0, rhos)
    cc = np.minimum(1.0, rhos)
    vtp1 = np.concatenate([values[1:], bootstrap[None]], axis=0)
    deltas = cr * (rewards + discounts * vtp1 - values)

    mesh = make_mesh((8,), ("sp",))
    solver = make_timesharded_solver(mesh, "sp")
    vs_minus_v = solver(jnp.asarray(discounts * cc), jnp.asarray(deltas))
    vs = np.asarray(vs_minus_v) + values
    np.testing.assert_allclose(vs, np.asarray(out.vs), rtol=1e-4, atol=1e-4)


def test_shift_from_next_shard(devices):
    """x[t+1] across shard boundaries: last shard's tail gets the fill."""
    from jax.sharding import Mesh, PartitionSpec as P

    from asyncrl_tpu.parallel.timeshard import shift_from_next_shard

    mesh = Mesh(np.array(devices), ("sp",))
    T, B = 32, 3
    x = jnp.arange(T * B, dtype=jnp.float32).reshape(T, B)
    fill = jnp.full((B,), -1.0)

    out = jax.jit(
        shard_map(
            lambda x: shift_from_next_shard(x, fill, "sp"),
            mesh=mesh,
            in_specs=(P("sp"),),
            out_specs=P("sp"),
        )
    )(x)
    want = jnp.concatenate([x[1:], fill[None]], axis=0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_vtrace_timesharded_matches_single_device(devices):
    from jax.sharding import Mesh, PartitionSpec as P

    from asyncrl_tpu.ops.vtrace import VTraceOutput, vtrace
    from asyncrl_tpu.parallel.timeshard import vtrace_timesharded

    mesh = Mesh(np.array(devices), ("sp",))
    T, B = 64, 5
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    behaviour_logp = jax.random.normal(ks[0], (T, B)) * 0.1 - 1.0
    target_logp = jax.random.normal(ks[1], (T, B)) * 0.1 - 1.0
    rewards = jax.random.normal(ks[2], (T, B))
    discounts = jnp.full((T, B), 0.99) * (
        jax.random.uniform(ks[3], (T, B)) > 0.1
    )
    values = jax.random.normal(ks[4], (T, B))
    bootstrap = jnp.ones((B,)) * 0.3

    want = vtrace(
        behaviour_logp, target_logp, rewards, discounts, values, bootstrap
    )

    sharded = jax.jit(
        shard_map(
            lambda bl, tl, r, d, v: vtrace_timesharded(
                bl, tl, r, d, v, bootstrap, axis_name="sp"
            ),
            mesh=mesh,
            in_specs=(P("sp"),) * 5,
            out_specs=VTraceOutput(
                vs=P("sp"), pg_advantages=P("sp"), rho_clip_frac=P(),
                c_clip_frac=P(),
            ),
        )
    )(behaviour_logp, target_logp, rewards, discounts, values)

    np.testing.assert_allclose(
        np.asarray(sharded.vs), np.asarray(want.vs), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(sharded.pg_advantages),
        np.asarray(want.pg_advantages),
        rtol=1e-5,
        atol=1e-6,
    )
    np.testing.assert_allclose(
        float(sharded.rho_clip_frac), float(want.rho_clip_frac), rtol=1e-6
    )
    np.testing.assert_allclose(
        float(sharded.c_clip_frac), float(want.c_clip_frac), rtol=1e-6
    )


def test_gae_timesharded_matches_single_device(devices):
    from jax.sharding import Mesh, PartitionSpec as P

    from asyncrl_tpu.ops.gae import GAEOutput, gae
    from asyncrl_tpu.parallel.timeshard import gae_timesharded

    mesh = Mesh(np.array(devices), ("sp",))
    T, B = 40, 4
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    rewards = jax.random.normal(ks[0], (T, B))
    discounts = jnp.full((T, B), 0.97) * (
        jax.random.uniform(ks[1], (T, B)) > 0.05
    )
    values = jax.random.normal(ks[2], (T, B))
    bootstrap = jnp.ones((B,)) * -0.2

    want = gae(rewards, discounts, values, bootstrap, gae_lambda=0.9)
    sharded = jax.jit(
        shard_map(
            lambda r, d, v: gae_timesharded(
                r, d, v, bootstrap, gae_lambda=0.9, axis_name="sp"
            ),
            mesh=mesh,
            in_specs=(P("sp"),) * 3,
            out_specs=GAEOutput(advantages=P("sp"), returns=P("sp")),
        )
    )(rewards, discounts, values)

    np.testing.assert_allclose(
        np.asarray(sharded.advantages), np.asarray(want.advantages),
        rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(sharded.returns), np.asarray(want.returns),
        rtol=1e-5, atol=1e-6,
    )


def _ppo_rollout(T, B, obs_dim, seed=0):
    rng = np.random.default_rng(seed)
    from asyncrl_tpu.rollout.buffer import Rollout

    return Rollout(
        obs=jnp.asarray(rng.normal(size=(T, B, obs_dim)).astype(np.float32)),
        actions=jnp.asarray(rng.integers(0, 2, (T, B)).astype(np.int32)),
        behaviour_logp=jnp.asarray(
            rng.normal(-0.7, 0.1, (T, B)).astype(np.float32)
        ),
        rewards=jnp.asarray(rng.normal(size=(T, B)).astype(np.float32)),
        terminated=jnp.asarray(rng.uniform(size=(T, B)) < 0.1),
        truncated=jnp.zeros((T, B), bool),
        bootstrap_obs=jnp.asarray(
            rng.normal(size=(B, obs_dim)).astype(np.float32)
        ),
    )


def _assert_sp_matches_dp(cfg, ro):
    """One RolloutLearner.update on a dp-only vs a (dp x sp) mesh: the
    post-update params and loss must agree."""
    from asyncrl_tpu.envs.cartpole import CartPole
    from asyncrl_tpu.learn.rollout_learner import RolloutLearner
    from asyncrl_tpu.models.networks import build_model

    env = CartPole()
    model = build_model(cfg, env.spec)
    results = {}
    for name, shape, axes in [
        ("dp", (8,), ("dp",)),
        ("dp_sp", (2, 4), ("dp", "sp")),
    ]:
        mesh = make_mesh(shape, axes)
        learner = RolloutLearner(cfg, env.spec, model, mesh)
        state = learner.init_state(0)
        state, metrics = learner.update(state, learner.put_rollout(ro))
        results[name] = (
            jax.tree.leaves(jax.device_get(state.params)),
            float(metrics["loss"]),
        )

    for a, b in zip(results["dp"][0], results["dp_sp"][0]):
        np.testing.assert_allclose(a, b, rtol=5e-5, atol=1e-6)
    np.testing.assert_allclose(
        results["dp"][1], results["dp_sp"][1], rtol=5e-5
    )


@pytest.mark.parametrize("algo", ["a3c", "impala", "ppo", "qlearn"])
def test_rollout_learner_timesharded_equals_dp_only(algo, devices):
    """The HOST-FRAGMENT learner on a (dp x sp) mesh must produce the same
    post-update params as on a dp-only mesh — the end-to-end check that the
    time-axis side of learner._algo_loss (the distributed recurrences)
    matches the unsharded path (regression: this glue was once referenced but
    undefined, so any sp>1 mesh crashed with NameError at trace time)."""
    from asyncrl_tpu.envs.cartpole import CartPole
    from asyncrl_tpu.learn.rollout_learner import RolloutLearner
    from asyncrl_tpu.models.networks import build_model
    from asyncrl_tpu.utils.config import Config

    cfg = Config(
        algo=algo, unroll_len=8, num_envs=8, precision="f32",
        ppo_epochs=1, ppo_minibatches=1, actor_staleness=2,
        # qlearn additionally exercises the Huber branch on both paths.
        huber_delta=1.0 if algo == "qlearn" else 0.0,
    )
    _assert_sp_matches_dp(cfg, _ppo_rollout(8, 8, 4))


def test_rollout_learner_timesharded_multipass_equals_dp_only(devices):
    """Multi-epoch PPO on an sp mesh (the round-2 verdict's last time-shard
    hole): with ppo_minibatches=1 the shuffle is a no-op up to sample order
    inside one mean, so a (dp x sp) mesh must reproduce the dp-only params —
    proving the time_axis path of _ppo_multipass (distributed GAE + local
    slices) computes the same two full-batch passes."""
    from asyncrl_tpu.utils.config import Config

    cfg = Config(
        algo="ppo", unroll_len=8, num_envs=8, precision="f32",
        ppo_epochs=2, ppo_minibatches=1,
    )
    _assert_sp_matches_dp(cfg, _ppo_rollout(8, 8, 4))


def test_rollout_learner_timesharded_multipass_minibatched(devices):
    """Minibatched multipass PPO on the sp mesh: shuffled minibatches are
    time-stratified (each shard shuffles its local slice) so no exact
    unsharded twin exists — assert the step is deterministic, finite, and
    actually moves the params."""
    from asyncrl_tpu.envs.cartpole import CartPole
    from asyncrl_tpu.learn.rollout_learner import RolloutLearner
    from asyncrl_tpu.models.networks import build_model
    from asyncrl_tpu.utils.config import Config

    cfg = Config(
        algo="ppo", unroll_len=8, num_envs=8, precision="f32",
        ppo_epochs=2, ppo_minibatches=2,
    )
    env = CartPole()
    model = build_model(cfg, env.spec)
    ro = _ppo_rollout(8, 8, 4, seed=3)

    mesh = make_mesh((2, 4), ("dp", "sp"))
    learner = RolloutLearner(cfg, env.spec, model, mesh)
    state0 = learner.init_state(0)
    put = learner.put_rollout(ro)

    outs = []
    for _ in range(2):
        state, metrics = learner.update(state0, put)
        outs.append(
            (jax.tree.leaves(jax.device_get(state.params)),
             float(metrics["loss"]))
        )
    assert np.isfinite(outs[0][1])
    for a, b in zip(outs[0][0], outs[1][0]):
        np.testing.assert_array_equal(a, b)  # deterministic
    moved = any(
        not np.allclose(a, b)
        for a, b in zip(outs[0][0], jax.tree.leaves(jax.device_get(state0.params)))
    )
    assert moved
