"""The plain float32 references against the program, at tiny sizes."""

import ast
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncrl_tpu.envs.core import EnvSpec
from asyncrl_tpu.models.networks import build_model
from asyncrl_tpu.ops import losses
from asyncrl_tpu.utils.config import Config
from benchmarks.reference import plain

REFERENCE_DIR = os.path.dirname(os.path.abspath(plain.__file__))
PIXELS = EnvSpec(obs_shape=(20, 20, 4), num_actions=5, obs_dtype=jnp.uint8)
VECTOR = EnvSpec(obs_shape=(6,), num_actions=6)


def test_reference_imports_nothing_of_the_code_under_test():
    for path in glob.glob(os.path.join(REFERENCE_DIR, "*.py")):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            assert not any(n.startswith("asyncrl_tpu") for n in names), path


def _model(torso: str, precision: str, spec):
    cfg = Config(algo="impala", torso=torso, channels=(4, 8),
                 hidden_sizes=(32, 32), precision=precision)
    model = build_model(cfg, spec)
    obs = jax.random.randint(
        jax.random.PRNGKey(1), (12, *spec.obs_shape), 0, 256
    ).astype(spec.obs_dtype)
    if spec.obs_dtype != jnp.uint8:
        obs = jax.random.uniform(jax.random.PRNGKey(1), (12, *spec.obs_shape),
                                 minval=-1, maxval=1)
    params = model.init(jax.random.PRNGKey(0), obs)
    return cfg, model, params, obs


@pytest.mark.parametrize("torso,spec", [("impala_cnn", PIXELS), ("mlp", VECTOR)])
def test_forward_agrees_with_the_program_in_float32(torso, spec):
    _, model, params, obs = _model(torso, "f32", spec)
    logits, value = model.apply(params, obs)
    ref_logits, ref_value = plain.FORWARDS[torso](params, obs)
    np.testing.assert_allclose(logits, ref_logits, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(value, ref_value, rtol=1e-4, atol=1e-4)


def test_a_bfloat16_forward_is_outside_the_float32_tolerance():
    """The tolerance is tight enough that computing in a lower precision
    than the configuration states would fail."""
    _, model, params, obs = _model("impala_cnn", "bf16_matmul", PIXELS)
    _, value = model.apply(params, obs)
    _, ref_value = plain.impala_cnn_forward(params, obs)
    gap = np.max(np.abs(np.asarray(value) - np.asarray(ref_value)))
    assert gap > 1e-4 * max(1.0, float(np.max(np.abs(ref_value))))


def test_chunked_forward_equals_whole():
    _, _, params, obs = _model("impala_cnn", "f32", PIXELS)
    whole = plain.impala_cnn_forward(params, obs)
    parts = plain.forward_in_chunks(plain.impala_cnn_forward, params, obs, 4)
    np.testing.assert_allclose(whole[0], parts[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(whole[1], parts[1], rtol=1e-5, atol=1e-5)


def _fragment(spec, T=7, B=5, seed=3):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    obs = jax.random.randint(k[0], (T + 1, B, *spec.obs_shape), 0, 256).astype(
        spec.obs_dtype
    )
    return {
        "obs": obs[:-1], "bootstrap_obs": obs[-1],
        "actions": jax.random.randint(k[1], (T, B), 0, spec.num_actions),
        "behaviour_logp": -jax.random.uniform(k[2], (T, B), minval=0.1, maxval=3),
        "rewards": jax.random.normal(k[3], (T, B)),
        "done": jax.random.bernoulli(k[4], 0.2, (T, B)),
    }


@pytest.mark.parametrize("scan_impl", ["sequential", "associative"])
def test_impala_loss_agrees_with_the_program(scan_impl):
    cfg, model, params, _ = _model("impala_cnn", "f32", PIXELS)
    frag = _fragment(PIXELS)
    T, B = frag["actions"].shape
    obs_all = jnp.concatenate([frag["obs"], frag["bootstrap_obs"][None]])
    logits, values = model.apply(params, obs_all)
    discounts = cfg.gamma * (1.0 - frag["done"].astype(jnp.float32))
    program, _ = losses.impala_loss(
        logits[:-1], values[:-1], frag["actions"], frag["behaviour_logp"],
        frag["rewards"], discounts, values[-1], value_coef=cfg.value_coef,
        entropy_coef=cfg.entropy_coef, scan_impl=scan_impl,
    )
    reference = plain.impala_loss(
        plain.impala_cnn_forward, params, frag, gamma=cfg.gamma,
        value_coef=cfg.value_coef, entropy_coef=cfg.entropy_coef, chunk=(T + 1) * B,
    )
    assert float(program) == pytest.approx(float(reference), rel=1e-4)


def test_vtrace_on_policy_is_the_n_step_return():
    """A property of the definition, not of the program: with rho = c = 1
    and no episode ends, vs_t is the discounted n-step return."""
    T, B, gamma = 6, 3, 0.9
    rewards = jax.random.normal(jax.random.PRNGKey(0), (T, B))
    values = jax.random.normal(jax.random.PRNGKey(1), (T, B))
    boot = jax.random.normal(jax.random.PRNGKey(2), (B,))
    logp = jnp.zeros((T, B))
    vs, _ = plain.vtrace_sequential(
        logp, logp, rewards, jnp.full((T, B), gamma), values, boot
    )
    ret = boot
    for t in reversed(range(T)):
        ret = rewards[t] + gamma * ret
        np.testing.assert_allclose(vs[t], ret, rtol=1e-5, atol=1e-5)
