"""Differential testing across backends (SURVEY.md §8-Q7): the same
workload/hyperparameters must produce comparable learning on the TPU-native
(Anakin) path and the reference-architecture cpu_async path."""

import numpy as np
import pytest

from asyncrl_tpu import make_agent
from asyncrl_tpu.utils.config import Config


def matched_cfg(backend):
    return Config(
        env_id="CartPole-v1",
        algo="a3c",
        backend=backend,
        num_envs=8,
        unroll_len=20,
        actor_threads=4,
        host_pool="jax",
        learning_rate=1e-3,
        entropy_coef=0.01,
        gamma=0.99,
        precision="f32",
        log_every=20,
    )


@pytest.mark.slow
def test_backends_learn_comparably_on_matched_config():
    """Both backends clear the same learning bar on identical
    hyperparameters; neither path is a semantics fork of the other.
    (Loose bar by design: the backends differ in actor parallelism
    structure and PRNG streams, so trajectories — not semantics — differ.)
    """
    results = {}
    for backend in ("tpu", "cpu_async"):
        agent = make_agent(matched_cfg(backend))
        try:
            agent.train(total_env_steps=80_000)
            results[backend] = agent.evaluate(num_episodes=16, max_steps=500)
        finally:
            close = getattr(agent, "close", None)
            if close:
                close()

    for backend, ret in results.items():
        assert ret > 60.0, f"{backend} failed the learning bar: {results}"


def test_backends_share_loss_machinery_on_identical_fragment():
    """Bit-level: the Anakin Learner and the host-fragment RolloutLearner
    compute identical losses/gradient updates for the same fragment and
    params (they share _algo_loss; this pins it)."""
    import jax
    import jax.numpy as jnp

    from asyncrl_tpu.api.trainer import Trainer
    from asyncrl_tpu.learn.learner import _algo_loss
    from asyncrl_tpu.learn.rollout_learner import RolloutLearner
    from asyncrl_tpu.models.networks import build_model
    from asyncrl_tpu.envs import registry
    from asyncrl_tpu.parallel.mesh import make_mesh
    from asyncrl_tpu.rollout.buffer import Rollout
    from asyncrl_tpu.ops import distributions

    cfg = matched_cfg("tpu").replace(algo="impala")
    env = registry.make(cfg.env_id)
    model = build_model(cfg, env.spec)
    mesh = make_mesh((1,), ("dp",), devices=[jax.devices()[0]])

    rl = RolloutLearner(cfg, env.spec, model, mesh)
    state = rl.init_state(cfg.seed)

    T, B = cfg.unroll_len, 8
    rng = np.random.default_rng(7)
    rollout = Rollout(
        obs=rng.normal(size=(T, B, 4)).astype(np.float32),
        actions=rng.integers(0, 2, (T, B)).astype(np.int32),
        behaviour_logp=np.full((T, B), -0.69, np.float32),
        rewards=np.ones((T, B), np.float32),
        terminated=np.zeros((T, B), bool),
        truncated=np.zeros((T, B), bool),
        bootstrap_obs=rng.normal(size=(B, 4)).astype(np.float32),
    )
    dev_rollout = rl.put_rollout(rollout)
    _, metrics = rl.update(state, dev_rollout)

    dist = distributions.for_spec(env.spec)
    loss_direct, _ = _algo_loss(
        rl.config, model.apply, state.params,
        jax.tree.map(jnp.asarray, rollout), axis_name=None, dist=dist,
    )
    np.testing.assert_allclose(
        float(metrics["loss"]), float(loss_direct), rtol=1e-6
    )


# ------------------------------------------------- fused scan kernel

# Fused Pallas V-trace/GAE vs the lax reference (ops/pallas_scan.py):
# the device hot path's bit-exactness contract, exercised through the
# Pallas INTERPRETER so it gates on CPU CI. Both paths share the same
# FMA-fenced prologue (mul_no_fma), so "bit-identical" is literal —
# np.array_equal on the raw float bits, not allclose — across awkward
# geometries (time/batch lengths that are not multiples of any block),
# both input precisions, and the aux clip-fraction outputs.


def _vtrace_inputs(T, B, dtype, seed=0):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(  # noqa: E731
        rng.standard_normal(s).astype(np.float32), dtype=dtype
    )
    discounts = jnp.asarray(
        (0.99 * (rng.random((T, B)) > 0.1)).astype(np.float32), dtype=dtype
    )
    return dict(
        behaviour_logp=f(T, B),
        target_logp=f(T, B),
        rewards=f(T, B),
        discounts=discounts,
        values=f(T, B),
        bootstrap_value=f(B),
    )


@pytest.mark.parametrize("T,B", [(1, 1), (3, 5), (17, 9), (20, 8), (33, 2)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_vtrace_bit_identical_to_lax(T, B, dtype):
    import jax.numpy as jnp

    from asyncrl_tpu.ops.vtrace import vtrace

    kw = _vtrace_inputs(T, B, jnp.dtype(dtype), seed=T * 31 + B)
    # The fused path computes in f32 regardless of input dtype (bf16 is
    # upcast ONCE at entry — ops/pallas_scan.py), so the bit-identity
    # reference is the lax path on the same f32-upcast inputs.
    kw_f32 = {k: v.astype(jnp.float32) for k, v in kw.items()}
    ref = vtrace(**kw_f32, rho_clip=1.0, c_clip=1.0,
                 scan_impl="sequential", fused="lax")
    fused = vtrace(**kw, rho_clip=1.0, c_clip=1.0, fused="interpret")
    # Targets, advantages, AND the aux clip fractions: all four outputs
    # bit-equal (the kernel computes none of the prologue/epilogue
    # differently — clip fracs come from the same pre-kernel rhos).
    for name, a, b in zip(ref._fields, ref, fused):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            f"{name} diverged at T={T} B={B} {dtype}"
        )


@pytest.mark.parametrize("T,B", [(2, 3), (19, 7), (20, 8)])
def test_fused_gae_and_nstep_bit_identical_to_lax(T, B):
    import jax.numpy as jnp

    from asyncrl_tpu.ops.gae import gae, n_step_returns

    rng = np.random.default_rng(T * 13 + B)
    rewards = jnp.asarray(rng.standard_normal((T, B)).astype(np.float32))
    discounts = jnp.asarray(
        (0.99 * (rng.random((T, B)) > 0.1)).astype(np.float32)
    )
    values = jnp.asarray(rng.standard_normal((T, B)).astype(np.float32))
    boot = jnp.asarray(rng.standard_normal((B,)).astype(np.float32))

    ref = gae(rewards, discounts, values, boot, gae_lambda=0.95,
              scan_impl="sequential", fused="lax")
    fused = gae(rewards, discounts, values, boot, gae_lambda=0.95,
                fused="interpret")
    assert np.array_equal(np.asarray(ref.advantages),
                          np.asarray(fused.advantages))
    assert np.array_equal(np.asarray(ref.returns),
                          np.asarray(fused.returns))

    ref_r = n_step_returns(rewards, discounts, boot,
                           scan_impl="sequential", fused="lax")
    fused_r = n_step_returns(rewards, discounts, boot, fused="interpret")
    assert np.array_equal(np.asarray(ref_r), np.asarray(fused_r))


def test_fused_zero_length_trace_falls_back_to_lax():
    """T=0 fragments (a degenerate-but-legal geometry: the guard routes
    them to the lax path) return empty outputs instead of tripping a
    zero-sized Pallas grid."""
    import jax.numpy as jnp

    from asyncrl_tpu.ops.vtrace import vtrace

    kw = _vtrace_inputs(0, 4, jnp.float32)
    out = vtrace(**kw, fused="interpret")
    assert out.vs.shape == (0, 4) and out.pg_advantages.shape == (0, 4)


def test_fused_losses_bit_identical_through_loss_layer():
    """The loss layer threads fused_scan through to the ops: a3c and
    impala losses are bit-identical between fused="interpret" and the
    lax reference on the same fragment/params (the fused_ab bench
    probe's assertion, as a unit test)."""
    import jax
    import jax.numpy as jnp

    from asyncrl_tpu.envs import registry
    from asyncrl_tpu.learn.learner import _algo_loss
    from asyncrl_tpu.models.networks import build_model
    from asyncrl_tpu.ops import distributions
    from asyncrl_tpu.rollout.buffer import Rollout

    T, B = 20, 8
    rng = np.random.default_rng(11)
    rollout = jax.tree.map(
        jnp.asarray,
        Rollout(
            obs=rng.normal(size=(T, B, 4)).astype(np.float32),
            actions=rng.integers(0, 2, (T, B)).astype(np.int32),
            behaviour_logp=np.full((T, B), -0.69, np.float32),
            rewards=rng.normal(size=(T, B)).astype(np.float32),
            terminated=rng.random((T, B)) < 0.05,
            truncated=np.zeros((T, B), bool),
            bootstrap_obs=rng.normal(size=(B, 4)).astype(np.float32),
        ),
    )
    for algo in ("a3c", "impala"):
        cfg = matched_cfg("tpu").replace(
            algo=algo, scan_impl="sequential", fused_scan="lax"
        )
        env = registry.make(cfg.env_id)
        model = build_model(cfg, env.spec)
        dummy_obs = jnp.zeros((1, *env.spec.obs_shape), env.spec.obs_dtype)
        params = model.init(jax.random.PRNGKey(0), dummy_obs)
        dist = distributions.for_spec(env.spec)
        ref, _ = _algo_loss(
            cfg, model.apply, params, rollout, axis_name=None, dist=dist
        )
        fused, _ = _algo_loss(
            cfg.replace(fused_scan="interpret"), model.apply, params,
            rollout, axis_name=None, dist=dist,
        )
        assert np.array_equal(np.asarray(ref), np.asarray(fused)), algo


def test_fused_learner_trains_and_matches_lax_sequential():
    """The full Anakin learner with a fused kernel in the loss tail, on
    the 8-device mesh: the step must trace under shard_map and walk a
    bit-identical loss trajectory to the sequential lax path.

    The two arms compile DIFFERENT wrappers — the interpreter arm runs
    unchecked with an explicit gradient psum (learn/learner.py
    fused_smap_opts / reduce_grads), the lax arm checked with the
    implicit one — so equality here also pins that the explicit sum
    reproduces the implicit one (an unreduced gradient diverges from the
    reference at the second update)."""
    from asyncrl_tpu.api.trainer import Trainer
    from asyncrl_tpu.utils.config import Config

    def losses(**kw):
        cfg = Config(
            env_id="CartPole-v1", algo="impala", num_envs=8, unroll_len=8,
            precision="f32", log_every=1, **kw,
        )
        t = Trainer(cfg)
        try:
            hist = t.train(total_env_steps=3 * cfg.batch_steps_per_update)
            return [float(h["loss"]) for h in hist]
        finally:
            t.close()

    fused = losses(fused_scan="interpret")
    ref = losses(fused_scan="lax", scan_impl="sequential")
    assert fused and np.all(np.isfinite(fused))
    assert fused == ref


@pytest.mark.parametrize("fused_scan, resolved", [
    ("auto", "lax"), ("lax", "lax"), ("interpret", "interpret"),
    ("mosaic", ValueError),
])
def test_fused_scan_resolves_by_the_mesh_platform(fused_scan, resolved):
    """``fused_scan="auto"`` is the lax tail on a CPU mesh (the kernel is
    the TPU's), a concrete choice is kept, an unknown one is refused; and
    ``scan_impl="auto"`` is gone after the resolution either way."""
    from asyncrl_tpu.learn.learner import resolve_scan_impl
    from asyncrl_tpu.parallel.mesh import make_mesh

    cfg, mesh = matched_cfg("tpu").replace(fused_scan=fused_scan), make_mesh()
    if resolved is ValueError:
        with pytest.raises(ValueError, match="unknown fused_scan"):
            resolve_scan_impl(cfg, mesh)
        return
    got = resolve_scan_impl(cfg, mesh)
    assert (got.fused_scan, got.scan_impl) == (resolved, "associative")
