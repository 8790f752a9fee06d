"""Learner/mesh tests on the 8-virtual-device CPU mesh (SURVEY.md §4):
the dp all-reduce must equal the single-device gradient on the full batch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from asyncrl_tpu.envs.cartpole import CartPole
from asyncrl_tpu.learn.learner import Learner, _algo_loss
from asyncrl_tpu.models.networks import build_model
from asyncrl_tpu.parallel.mesh import (
    DP_AXIS,
    axis_size,
    make_mesh,
    shard_map,
)
from asyncrl_tpu.rollout.buffer import Rollout
from asyncrl_tpu.utils.config import Config


def fixed_rollout(T=8, B=32, seed=0):
    rng = np.random.default_rng(seed)
    return Rollout(
        obs=jnp.asarray(rng.normal(size=(T, B, 4)).astype(np.float32)),
        actions=jnp.asarray(rng.integers(0, 2, (T, B)).astype(np.int32)),
        behaviour_logp=jnp.asarray(rng.normal(-0.7, 0.1, (T, B)).astype(np.float32)),
        rewards=jnp.asarray(rng.normal(size=(T, B)).astype(np.float32)),
        terminated=jnp.asarray(rng.uniform(size=(T, B)) < 0.1),
        truncated=jnp.zeros((T, B), bool),
        bootstrap_obs=jnp.asarray(rng.normal(size=(B, 4)).astype(np.float32)),
    )


@pytest.mark.parametrize("algo", ["a3c", "impala", "ppo"])
def test_sharded_grads_equal_full_batch_grads(algo, devices):
    """pmean(grad(loss(shard))) over 8 shards == grad(loss(full batch))."""
    cfg = Config(algo=algo, precision="f32")
    env = CartPole()
    model = build_model(cfg, env.spec)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4)))
    ro = fixed_rollout()

    grad_full = jax.grad(
        lambda p: _algo_loss(cfg, model.apply, p, ro)[0]
    )(params)

    mesh = make_mesh()

    def sharded_grad(p, r):
        # Same pattern as the learner: scale the per-shard loss by
        # 1/axis_size; the checked shard_map's transpose psums grads of
        # the replicated params (no explicit pmean — that would
        # double-reduce).
        return jax.grad(
            lambda q: _algo_loss(cfg, model.apply, q, r, axis_name=DP_AXIS)[0]
            / axis_size(DP_AXIS)
        )(p)

    ro_spec = Rollout(
        obs=P(None, DP_AXIS), actions=P(None, DP_AXIS),
        behaviour_logp=P(None, DP_AXIS), rewards=P(None, DP_AXIS),
        terminated=P(None, DP_AXIS), truncated=P(None, DP_AXIS),
        bootstrap_obs=P(DP_AXIS),
    )
    grad_sharded = jax.jit(
        shard_map(
            sharded_grad, mesh=mesh, in_specs=(P(), ro_spec), out_specs=P()
        )
    )(params, ro)

    flat_a = jax.tree.leaves(grad_full)
    flat_b = jax.tree.leaves(grad_sharded)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("algo", ["a3c", "impala"])
def test_learner_updates_on_8_device_mesh(algo, devices):
    cfg = Config(
        algo=algo, num_envs=32, unroll_len=8, precision="f32",
        actor_staleness=2,
    )
    env = CartPole()
    model = build_model(cfg, env.spec)
    learner = Learner(cfg, env, model, make_mesh())
    state = learner.init_state(seed=0)
    p0 = jax.device_get(state.params)

    for _ in range(3):
        state, metrics = learner.update(state)
    metrics = jax.device_get(metrics)
    assert int(state.update_step) == 3
    assert np.isfinite(metrics["loss"])
    p1 = jax.device_get(state.params)
    changed = any(
        not np.allclose(a, b)
        for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1))
    )
    assert changed, "params did not move after 3 updates"


@pytest.mark.parametrize("algo", ["a3c", "impala"])
def test_donated_state_steps_and_matches_undonated(algo, devices):
    """config.donate_buffers: a donated TrainState may not name one buffer
    twice (init_state once aliased params/actor_params — "Attempt to
    donate the same buffer twice in Execute()" on CPU and TPU alike), and
    donation changes where results land, never what they are."""
    losses = {}
    for donate in (True, False):
        cfg = Config(
            algo=algo, num_envs=16, unroll_len=4, precision="f32",
            actor_staleness=2 if algo == "impala" else 1,
            donate_buffers=donate,
        )
        env = CartPole()
        learner = Learner(cfg, env, build_model(cfg, env.spec), make_mesh())
        state = learner.init_state(0)
        out = []
        for _ in range(3):
            state, metrics = learner.update(state)
            out.append(float(metrics["loss"]))
        losses[donate] = out
    assert losses[True] == losses[False]


def test_learner_deterministic(devices):
    cfg = Config(algo="a3c", num_envs=16, unroll_len=8, precision="f32")
    env = CartPole()
    model = build_model(cfg, env.spec)

    def run():
        learner = Learner(cfg, env, model, make_mesh())
        state = learner.init_state(seed=7)
        for _ in range(2):
            state, _ = learner.update(state)
        return jax.device_get(state.params)

    pa, pb = run(), run()
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_impala_actor_staleness(devices):
    """With staleness k, actor_params must lag params until step % k == 0."""
    cfg = Config(
        algo="impala", num_envs=16, unroll_len=4, actor_staleness=2,
        precision="f32",
    )
    env = CartPole()
    model = build_model(cfg, env.spec)
    learner = Learner(cfg, env, model, make_mesh())
    state = learner.init_state(seed=0)

    state, _ = learner.update(state)  # step 1: 1 % 2 != 0 -> stale
    same = all(
        np.allclose(a, b)
        for a, b in zip(
            jax.tree.leaves(jax.device_get(state.params)),
            jax.tree.leaves(jax.device_get(state.actor_params)),
        )
    )
    assert not same, "actor params refreshed too early"

    state, _ = learner.update(state)  # step 2: refresh
    same = all(
        np.allclose(a, b)
        for a, b in zip(
            jax.tree.leaves(jax.device_get(state.params)),
            jax.tree.leaves(jax.device_get(state.actor_params)),
        )
    )
    assert same, "actor params not refreshed at staleness boundary"


def test_updates_per_call_matches_sequential():
    """K fused (scanned) updates must equal K sequential update calls
    bit-for-bit — same seeds, same state evolution, stacked [K] metrics."""
    import numpy as np

    from asyncrl_tpu.api.trainer import Trainer
    from asyncrl_tpu.utils.config import Config

    base = dict(
        env_id="CartPole-v1", algo="impala", num_envs=8, unroll_len=8,
        precision="f32",
    )
    t_seq = Trainer(Config(**base))
    t_fused = Trainer(Config(**base, updates_per_call=3))

    state = t_seq.state
    seq_losses = []
    for _ in range(3):
        state, m = t_seq.learner.update(state)
        seq_losses.append(float(m["loss"]))

    fused_state, fused_m = t_fused.learner.update(t_fused.state)
    assert np.asarray(fused_m["loss"]).shape == (3,)
    np.testing.assert_allclose(
        np.asarray(fused_m["loss"]), np.asarray(seq_losses), rtol=1e-6
    )
    for a, b in zip(
        jax.tree.leaves(state.params), jax.tree.leaves(fused_state.params)
    ):
        # Same math, but scanned vs standalone programs may fuse float
        # reductions differently on some backends: tolerance, not bitwise.
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        )
    assert int(fused_state.update_step) == 3

    # Trainer drain aggregates [K] metric stacks correctly.
    history = t_fused.train(
        total_env_steps=int(fused_state.update_step + 6)
        * t_fused.config.batch_steps_per_update
    )
    assert history and np.isfinite(history[-1]["loss"])


def test_rmsprop_optimizer_trains(devices):
    """optimizer="rmsprop" (the A3C-paper shared-statistics default,
    SURVEY.md:143): numerics match a hand-built optax chain on the same
    gradients, and the learner trains with it on the mesh."""
    import optax

    cfg = Config(
        algo="a3c", num_envs=16, unroll_len=8, precision="f32",
        optimizer="rmsprop", rmsprop_decay=0.95, rmsprop_eps=0.01,
    )
    from asyncrl_tpu.learn.learner import make_optimizer

    opt = make_optimizer(cfg)
    ref = optax.chain(
        optax.clip_by_global_norm(cfg.max_grad_norm),
        optax.rmsprop(cfg.learning_rate, decay=0.95, eps=0.01),
    )
    params = {"w": jnp.arange(4.0), "b": jnp.ones((2,))}
    grads = {"w": jnp.full((4,), 2.0), "b": jnp.array([-1.0, 3.0])}
    s1, s2 = opt.init(params), ref.init(params)
    for _ in range(3):
        u1, s1 = opt.update(grads, s1, params)
        u2, s2 = ref.update(grads, s2, params)
        for a, b in zip(jax.tree.leaves(u1), jax.tree.leaves(u2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    env = CartPole()
    model = build_model(cfg, env.spec)
    learner = Learner(cfg, env, model, make_mesh())
    state = learner.init_state(seed=0)
    p0 = jax.device_get(state.params)
    for _ in range(3):
        state, metrics = learner.update(state)
    assert np.isfinite(float(jax.device_get(metrics)["loss"]))
    p1 = jax.device_get(state.params)
    assert any(
        not np.allclose(a, b)
        for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1))
    )


def test_unknown_optimizer_rejected():
    cfg = Config(optimizer="sgd")
    from asyncrl_tpu.learn.learner import make_optimizer

    with pytest.raises(ValueError, match="unknown optimizer"):
        make_optimizer(cfg)


def test_grad_accum_matches_full_batch(devices):
    """grad_accum=4 must be the SAME training run as grad_accum=1 (equal
    env chunks + 1/n loss scaling => the summed chunk gradient is exactly
    the full-batch gradient; learner._chunk_envs docstring)."""
    base = Config(
        algo="impala", num_envs=32, unroll_len=8, precision="f32",
        actor_staleness=2,
    )
    env = CartPole()

    def run(cfg):
        model = build_model(cfg, env.spec)
        learner = Learner(cfg, env, model, make_mesh())
        state = learner.init_state(seed=3)
        for _ in range(3):
            state, metrics = learner.update(state)
        return jax.device_get(state.params), jax.device_get(metrics)

    p_full, m_full = run(base)
    p_acc, m_acc = run(base.replace(grad_accum=4))
    for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_acc)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-5, atol=1e-6
        )
    np.testing.assert_allclose(
        float(m_full["loss"]), float(m_acc["loss"]), rtol=1e-5
    )


def test_grad_accum_geometry_rejected(devices):
    env = CartPole()
    # 32 envs / 8 shards = 4 per shard: grad_accum=3 cannot chunk equally.
    cfg = Config(algo="impala", num_envs=32, grad_accum=3)
    with pytest.raises(ValueError, match="must divide the per-shard env"):
        Learner(cfg, env, build_model(cfg, env.spec), make_mesh())
    # PPO refuses grad_accum outright (single-pass included): advantage
    # normalization computes batch moments that chunking would localize;
    # ppo_minibatches is PPO's native microbatching knob.
    for extra in ({"ppo_epochs": 2}, {"ppo_epochs": 1, "ppo_minibatches": 1}):
        cfg = Config(algo="ppo", num_envs=32, grad_accum=2, **extra)
        with pytest.raises(ValueError, match="ppo_minibatches"):
            Learner(cfg, env, build_model(cfg, env.spec), make_mesh())


def test_entropy_anneal_schedule(devices):
    """entropy_coef_at: linear ramp init -> final over N updates, clamped;
    constant (and a plain float — bit-identical program) when off."""
    from asyncrl_tpu.learn.learner import entropy_coef_at

    cfg = Config(
        entropy_coef=0.02, entropy_coef_final=0.002,
        entropy_anneal_steps=100,
    )
    step = lambda n: jnp.asarray(n, jnp.int32)  # noqa: E731
    np.testing.assert_allclose(float(entropy_coef_at(cfg, step(0))), 0.02)
    np.testing.assert_allclose(
        float(entropy_coef_at(cfg, step(50))), 0.011, rtol=1e-6
    )
    np.testing.assert_allclose(
        float(entropy_coef_at(cfg, step(100))), 0.002, rtol=1e-6
    )
    np.testing.assert_allclose(
        float(entropy_coef_at(cfg, step(1000))), 0.002, rtol=1e-6
    )
    assert entropy_coef_at(cfg.replace(entropy_anneal_steps=0), step(7)) == 0.02


def test_entropy_anneal_changes_training(devices):
    """The annealed coefficient must actually reach the loss: with a huge
    final coef and a 2-step ramp, update 3's entropy metric must dominate
    the constant-coef run's."""
    base = Config(
        algo="impala", num_envs=16, unroll_len=8, precision="f32",
        entropy_coef=0.01,
    )
    env = CartPole()

    def entropy_loss_at_step3(cfg):
        model = build_model(cfg, env.spec)
        learner = Learner(cfg, env, model, make_mesh())
        state = learner.init_state(seed=0)
        for _ in range(3):
            state, metrics = learner.update(state)
        return float(jax.device_get(metrics)["loss"])

    plain = entropy_loss_at_step3(base)
    annealed = entropy_loss_at_step3(
        base.replace(entropy_coef_final=5.0, entropy_anneal_steps=2)
    )
    # Entropy bonus is SUBTRACTED from the loss: a coef of 5.0 at step 3
    # must push the loss far below the constant-0.01 run's.
    assert annealed < plain - 1.0, (annealed, plain)
