"""The Kimi-Linear sequence policy (models/kimi_linear.py, ops/kda.py,
ops/moe.py, envs/token_task.py) against its plain reference
(benchmarks/reference/kimi_linear.py), on seeded random weights at the tiny
preset's sizes, in float32."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncrl_tpu import make_agent
from asyncrl_tpu.configs import presets
from asyncrl_tpu.envs import registry
from asyncrl_tpu.learn import learner as learner_mod
from asyncrl_tpu.models import kimi_linear
from asyncrl_tpu.models.networks import build_model, reset_core
from asyncrl_tpu.obs import introspect
from asyncrl_tpu.ops import distributions, kda, moe
from asyncrl_tpu.rollout.anakin import actor_init, unroll
from benchmarks.reference import kimi_linear as reference

TINY = kimi_linear.SHAPES["kimi_linear_tiny"]
CFG = presets.get("kimi_linear_tiny").replace(precision="f32", fused_scan="lax")


def dims_of(shape):
    return dataclasses.asdict(shape)


def plain_core(core):
    return [dict(layer) for layer in core.layers]


@pytest.fixture(scope="module")
def policy():
    env = registry.make(CFG.env_id, CFG)
    model = build_model(CFG, env.spec)
    variables = model.init(jax.random.PRNGKey(0))
    return env, model, variables


@pytest.fixture(scope="module")
def fragments(policy):
    """Two consecutive fragments of the program's own rollout (the second
    starts from a non-zero carry)."""
    env, model, variables = policy
    dist = distributions.for_config(CFG, env.spec)
    actor = actor_init(env, CFG.num_envs, jax.random.PRNGKey(1), model=model)
    roll = jax.jit(lambda a: unroll(
        model.apply, variables, env, a, CFG.unroll_len, dist=dist)[:2])
    actor, first = roll(actor)
    _, second = roll(actor)
    return first, second


def as_fragment(r):
    return {
        "obs": r.obs, "bootstrap_obs": r.bootstrap_obs, "actions": r.actions,
        "behaviour_logp": r.behaviour_logp, "rewards": r.rewards,
        "done": r.done, "init_core": plain_core(r.init_core),
    }


def test_token_task_draws_lengths_and_pays_for_the_repeated_prompt():
    env = registry.make("JaxTokenTask-v0", CFG)
    assert env.spec.num_actions == TINY.vocab and env.spec.obs_shape == ()
    assert (env.min_len, env.max_len) == (2, TINY.max_positions)
    big = registry.make(
        "JaxTokenTask-v0", presets.get("kimi_linear_rl"))
    assert (big.vocab, big.min_len, big.max_len) == (20480, 64, 1024)
    assert (big.min_prompt, big.max_prompt) == (8, 32)
    state = env.init(jax.random.PRNGKey(3))
    length, plen = int(state.length), int(state.prompt_len)
    assert env.min_len <= length <= env.max_len and 1 <= plen < length
    total, steps = 0.0, 0
    step = jax.jit(env.step)
    for t in range(1, length + 1):
        target = int(state.prompt[t % plen])
        state, ts = step(state, jnp.int32(target), jax.random.PRNGKey(t))
        steps += 1
        total += float(ts.reward)
        assert bool(ts.truncated) == (t == length)
    # every generated token matched: one reward per action past the prompt
    # (the episode's last action is judged too)
    assert total == length - plen + 1 and int(state.t) == 0


def test_the_second_fragment_starts_from_a_carry_with_boundaries(fragments):
    first, second = fragments
    assert float(jnp.sum(second.done)) > 0
    assert any(
        float(jnp.max(jnp.abs(leaf))) > 0
        for leaf in jax.tree.leaves(second.init_core)
    )


# (a) fragment form, loss and every gradient leaf against the reference
def test_fragment_form_loss_and_gradients_match_the_reference(policy, fragments):
    env, model, variables = policy
    _, r = fragments
    logits, values, _, _ = model.apply(
        variables, r.obs, r.done, r.init_core, method="fragment")
    ref_logits, ref_values, _ = reference.forward(
        variables, dims_of(TINY), r.obs, r.done, plain_core(r.init_core))
    np.testing.assert_allclose(logits, ref_logits, atol=2e-4)
    np.testing.assert_allclose(values, ref_values, atol=2e-4)

    def program_loss(v):
        return learner_mod._algo_loss(CFG, model.apply, v, r)[0]

    def reference_loss(v):
        return reference.impala_loss(
            v, dims_of(TINY), as_fragment(r), CFG.gamma, CFG.value_coef,
            CFG.entropy_coef, CFG.vtrace_rho_clip, CFG.vtrace_c_clip,
            env_block=4)[0]

    loss, grads = jax.jit(jax.value_and_grad(program_loss))(variables)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(reference_loss))(variables)
    assert abs(float(loss) - float(ref_loss)) <= 1e-4 * max(1, abs(float(ref_loss)))
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_flatten_with_path(ref_grads)[0])
    moved = 0
    for path, g in flat:
        ref = ref_flat[path]
        scale = float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(
            g, ref, atol=1e-3 * scale + 1e-6, err_msg=jax.tree_util.keystr(path))
        moved += scale > 0
    # the router's correction bias is a buffer: no gradient reaches it
    assert moved == len(flat) - sum("router_bias" in str(p) for p, _ in flat)


# (b) what the importance ratio is built from
def test_rollout_logp_through_the_carry_is_the_learners_recompute(policy, fragments):
    _, model, variables = policy
    for r in fragments:
        logp, _, _, _, aux = model.apply(
            variables, r.obs, r.done, r.init_core, r.actions, method="fragment")
        np.testing.assert_allclose(logp, r.behaviour_logp, atol=2e-5)
        assert float(aux["episode_resets"]) == float(jnp.sum(r.done))


# (c) two fragments through the carry against one 2T sequence from zero
@pytest.mark.parametrize("ends", [
    (0, 15, 20, 39, 40, 55, 79),  # first and last step, chunk edges
    (10, 31, 50, 70),  # an episode that spans the fragments
])
def test_two_fragments_with_the_carry_match_the_reference_over_2t(policy, ends):
    _, _, variables = policy
    shape = dataclasses.replace(TINY, chunk=16, block_tokens=80)
    model = kimi_linear.SeqPolicy(shape)
    T, B = 40, 4
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2 * T, B), 0, TINY.vocab)
    done = np.zeros((2 * T, B), bool)
    for b in range(B):  # each env its own boundaries, shifted
        for e in ends:
            done[min(e + (b if 0 < e < 2 * T - 1 and e not in (39, 40) else 0),
                     2 * T - 1), b] = True
    done = jnp.asarray(done)
    core0 = model.initial_core(B)
    frag = jax.jit(lambda v, t, d, c: model.apply(v, t, d, c, method="fragment"))
    l1, v1, core1, _ = frag(variables, tokens[:T], done[:T], core0)
    l2, v2, core2, _ = frag(variables, tokens[T:], done[T:], core1)
    ref_logits, ref_values, ref_core = jax.jit(
        lambda v, t, d: reference.forward(
            v, dims_of(shape), t, d, plain_core(core0))
    )(variables, tokens, done)
    np.testing.assert_allclose(jnp.concatenate([l1, l2]), ref_logits, atol=3e-4)
    np.testing.assert_allclose(jnp.concatenate([v1, v2]), ref_values, atol=3e-4)
    for mine, ref in zip(core2.layers, ref_core):
        if "S" in mine:
            np.testing.assert_allclose(mine["S"], ref["S"], atol=2e-4)
            np.testing.assert_allclose(mine["conv"], ref["conv"], atol=2e-4)
        else:
            np.testing.assert_array_equal(mine["len"], ref["len"])
            live = jnp.arange(shape.max_positions)[None, :, None] < ref["len"][:, None, None]
            np.testing.assert_allclose(
                jnp.where(live, mine["kv"], 0), jnp.where(live, ref["kv"], 0),
                atol=2e-4)


def test_step_form_through_reset_core_matches_the_fragment_form(policy):
    _, model, variables = policy
    T, B = 24, 3
    tokens = jax.random.randint(jax.random.PRNGKey(6), (T, B), 0, TINY.vocab)
    done = jnp.zeros((T, B), bool).at[5, 0].set(True).at[23, 1].set(True)

    def step(core, inputs):
        token, d = inputs
        logits, value, core = model.apply(variables, token, core)
        return reset_core(core, d), (logits, value)

    core_s, (logits_s, values_s) = jax.lax.scan(
        step, model.initial_core(B), (tokens, done))
    logits_f, values_f, core_f, _ = model.apply(
        variables, tokens, done, model.initial_core(B), method="fragment")
    np.testing.assert_allclose(logits_s, logits_f, atol=2e-4)
    np.testing.assert_allclose(values_s, values_f, atol=2e-4)
    np.testing.assert_array_equal(core_s.layers[2]["len"], core_f.layers[2]["len"])
    np.testing.assert_allclose(core_s.layers[0]["S"], core_f.layers[0]["S"], atol=2e-4)
    np.testing.assert_allclose(
        core_s.layers[1]["conv"], core_f.layers[1]["conv"], atol=2e-4)


# (d) the share ties to the model
@pytest.mark.parametrize("E, k, N, skew, path", [
    (8, 2, 64, 0.0, "dense"),  # a decode step's tokens: the buffer would hold them all
    (8, 2, 4096, 0.0, "dense"),  # the same, in blocks of 2,048 tokens
    (64, 2, 4096, 0.0, "gathered"),  # a fragment's tokens, a router in balance
    (64, 2, 4096, 10.0, "overflow"),  # every token sent to expert 0: no token dropped
])
def test_the_shares_and_the_shared_expert_once_sum_to_the_uncut_layer(E, k, N, skew, path):
    D, F = 32, 16
    keys = jax.random.split(jax.random.PRNGKey(7), 8)
    w = lambda key, *dims: jax.random.normal(key, dims) * dims[-2] ** -0.5
    full = {
        "router": w(keys[0], D, E),
        "router_bias": 0.02 * jax.random.normal(keys[1], (E,)).at[0].add(skew),
        "experts": {"gate": w(keys[2], E, D, F), "up": w(keys[3], E, D, F),
                    "down": w(keys[4], E, F, D)},
        "shared": {"gate": w(keys[5], D, F), "up": w(keys[6], D, F),
                   "down": w(keys[7], F, D)},
    }
    x = jax.random.normal(jax.random.PRNGKey(8), (N, D))
    dims = {"held_experts": tuple(range(E)), "top_k": k, "routed_scale": 2.446}
    uncut = reference.expert_layer(full, x, dims)

    ids, weights = moe.route(x, full["router"], full["router_bias"], k, 2.446)
    total = reference._swiglu(full["shared"], x, False)  # the shared expert, once
    halves = (tuple(range(E // 2)), tuple(range(E // 2, E)))
    loads = []
    for held in halves:
        rows = {n: full["experts"][n][jnp.asarray(held)] for n in ("gate", "up", "down")}
        part, load = jax.jit(moe.held_experts, static_argnums=(3, 4, 8))(
            x, ids, weights, held, E, rows["gate"], rows["up"], rows["down"],
            jnp.float32)
        total = total + part
        loads.append(load)
    np.testing.assert_allclose(total, uncut, atol=2e-4)
    assert int(jnp.sum(jnp.concatenate(loads))) == N * k  # no token dropped
    # the case takes the path it is named for: the buffer holds eight times
    # the mean load, in rows of 128
    buffer = -(-8 * N * k // (E * 128)) * 128
    fullest = int(jnp.max(jnp.concatenate(loads)))
    assert {"dense": buffer >= N, "gathered": fullest <= buffer < N,
            "overflow": buffer < fullest}[path]
    # and the reference's own share of it agrees with the program's
    first = halves[0]
    share = reference.expert_layer(
        {**full, "experts": {n: v[:len(first)] for n, v in full["experts"].items()}},
        x, {**dims, "held_experts": first})
    part, _ = moe.held_experts(
        x, ids, weights, first, E, *(full["experts"][n][:len(first)]
                                     for n in ("gate", "up", "down")),
        jnp.float32)
    np.testing.assert_allclose(
        part + reference._swiglu(full["shared"], x, False), share, atol=2e-4)


# (e) the chunked scan against the recurrence
@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_kda_matches_the_recurrence(chunk):
    T, B, H, dk = 50, 2, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(9), 7)
    norm = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = norm(jax.random.normal(keys[0], (T, B, H, dk))) * dk ** -0.5
    k = norm(jax.random.normal(keys[1], (T, B, H, dk)))
    v = jax.random.normal(keys[2], (T, B, H, dk))
    # decays from gentle to one that forgets the state in a token
    g = -jnp.exp(jax.random.uniform(keys[3], (T, B, H, dk), minval=-7.0, maxval=1.6))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (T, B, H)))
    done = jnp.zeros((T, B), bool).at[0, 0].set(True).at[15, 0].set(True) \
        .at[37, 1].set(True).at[T - 1, 1].set(True)
    S0 = jax.random.normal(keys[5], (B, H, dk, dk))

    def recurrence(S0, q, k, v, g, beta):
        def step(S, x):
            q, k, v, g, beta, d = x
            S, o = kda.kda_step(S, q, k, v, g, beta)
            return jnp.where(d[:, None, None, None], 0.0, S), o

        return jax.lax.scan(step, S0, (q, k, v, g, beta, done))

    def chunked(S0, q, k, v, g, beta):
        return kda.kda_chunk(S0, q, k, v, g, beta, done, chunk=chunk)

    S_r, o_r = recurrence(S0, q, k, v, g, beta)
    S_c, o_c = chunked(S0, q, k, v, g, beta)
    np.testing.assert_allclose(o_c, o_r, atol=2e-5)
    np.testing.assert_allclose(S_c, S_r, atol=2e-5)
    assert bool(jnp.all(S_c[1] == 0)) and bool(jnp.any(S_c[0] != 0))

    mix = jax.random.normal(keys[6], o_r.shape)
    scalar = lambda f: lambda *a: jnp.sum(f(*a)[1] * mix) + jnp.sum(f(*a)[0])
    grads_r = jax.grad(scalar(recurrence), argnums=range(6))(S0, q, k, v, g, beta)
    grads_c = jax.grad(scalar(chunked), argnums=range(6))(S0, q, k, v, g, beta)
    for a, b in zip(grads_c, grads_r):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.max(jnp.abs(b))) + 1e-6)


# (f) the preset trains on the normal path
def test_the_preset_trains_on_the_anakin_path_and_moves_the_policy():
    before = introspect.process_record()["kda_sites"]
    agent = make_agent(CFG)
    try:
        assert type(agent).__name__ == "Trainer"
        state = agent.state
        first = jax.device_get(state.params)
        losses = []
        for _ in range(3):
            state, metrics = agent.learner.update(state)
            losses.append(float(metrics["loss"]))
        assert np.all(np.isfinite(losses))
        # 4 of 8 experts held: about half of the assignments land here
        assert 0.3 < float(metrics["moe_local_frac"]) < 0.7
        assert float(metrics["moe_load_max"]) >= float(metrics["moe_load_mean"]) > 0
        assert float(metrics["episode_resets"]) > 0
        delta = sum(
            float(jnp.sum(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(first), jax.tree.leaves(state.params))
        )
        assert delta > 0
        assert int(state.update_step) == 3
    finally:
        agent.close()
    after = introspect.process_record()["kda_sites"]
    # the rollout lowered the one-token form, the learner the chunked one
    assert after["step"] > before["step"] and after["chunk"] > before["chunk"]


def test_only_policy_gradient_algorithms_over_the_vocabulary_build():
    env = registry.make(CFG.env_id, CFG)
    with pytest.raises(ValueError, match="seq_model"):
        build_model(CFG.replace(algo="qlearn"), env.spec)
    with pytest.raises(ValueError, match="seq_model"):
        build_model(CFG, registry.make("CartPole-v1").spec)
