"""The Kimi-Linear sequence policy (models/kimi_linear.py, ops/kda.py,
ops/moe.py, envs/token_task.py) against its plain reference
(benchmarks/reference/kimi_linear.py), on seeded random weights at the tiny
preset's sizes, in float32."""

import contextlib
import dataclasses
import functools
import math
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncrl_tpu import make_agent
from asyncrl_tpu.configs import presets
from asyncrl_tpu.envs import registry
from asyncrl_tpu.learn import learner as learner_mod
from asyncrl_tpu.models import kimi_linear
from asyncrl_tpu.models.networks import build_model, reset_core, settle_core
from asyncrl_tpu.models.seq_common import (
    F32,
    _cache_after,
    _dot,
    _env_block,
    _episode_mask,
    _rms_norm,
    _softmax,
    _to_blocks,
)
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
    # env 1 ended on the last token: its reset waits for the next read, and
    # whoever reads "S" instead settles the carry first
    np.testing.assert_array_equal(core_s.layers[0]["fresh"], done[-1])
    assert float(jnp.max(jnp.abs(core_s.layers[0]["S"][1]))) > 0
    core_s = settle_core(core_s)
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
    # a fragment's tokens under dense routing (2 of 8): a buffer an expert
    # would hold them all, so one buffer over the held experts (ISSUE 30)
    (8, 2, 4096, 0.0, "grouped"),
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
    loads, densely = [], []
    for held in halves:
        rows = {n: full["experts"][n][jnp.asarray(held)] for n in ("gate", "up", "down")}
        part, load, dense = jax.jit(moe.held_experts, static_argnums=(3, 4, 8))(
            x, ids, weights, held, E, rows["gate"], rows["up"], rows["down"],
            jnp.float32)
        total = total + part
        loads.append(load)
        densely.append(bool(dense))
    np.testing.assert_allclose(total, uncut, atol=2e-4)
    assert int(jnp.sum(jnp.concatenate(loads))) == N * k  # no token dropped
    # the case takes the path it is named for: the buffer holds eight times
    # the mean load, in rows of 128
    buffer = -(-8 * N * k // (E * 128)) * 128
    fullest = int(jnp.max(jnp.concatenate(loads)))
    assert {"dense": buffer >= N, "grouped": buffer >= N,
            "gathered": fullest <= buffer < N, "overflow": buffer < fullest}[path]
    # ... and says so (the share with the favoured expert overflows alone)
    assert {"dense": all(densely), "overflow": densely == [True, False]}.get(
        path, not any(densely))
    # and the reference's own share of it agrees with the program's
    first = halves[0]
    share = reference.expert_layer(
        {**full, "experts": {n: v[:len(first)] for n, v in full["experts"].items()}},
        x, {**dims, "held_experts": first})
    part, _, _ = moe.held_experts(
        x, ids, weights, first, E, *(full["experts"][n][:len(first)]
                                     for n in ("gate", "up", "down")),
        jnp.float32)
    np.testing.assert_allclose(
        part + reference._swiglu(full["shared"], x, False), share, atol=2e-4)


# (e) the chunked scan against the recurrence
def pair_kernels_on_a_cpu():
    """What a TPU lowers where the pair kernels fit, on a CPU: the platform's
    choice taken for it, the kernels in the Pallas interpreter."""
    stack = contextlib.ExitStack()
    stack.enter_context(mock.patch.object(
        kda.lax, "platform_dependent", lambda *a, tpu, default: tpu(*a)))
    for name in ("_kernel_pairs", "_kernel_pairs_bwd"):
        stack.enter_context(mock.patch.object(
            kda, name, functools.partial(getattr(kda, name), interpret=True)))
    return stack


@pytest.mark.parametrize("chunk, dk, pair_kernels", [
    (16, 16, False), (64, 16, False),
    (64, 128, True),  # the published width, the sub-chunk pairs by the kernels
])
def test_chunked_kda_matches_the_recurrence(chunk, dk, pair_kernels):
    T, B, H = 50, 2, 2
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

    mix = jax.random.normal(keys[6], (T, B, H, dk))
    scalar = lambda f: lambda *a: jnp.sum(f(*a)[1] * mix) + jnp.sum(f(*a)[0])
    S_r, o_r = recurrence(S0, q, k, v, g, beta)
    grads_r = jax.grad(scalar(recurrence), argnums=range(6))(S0, q, k, v, g, beta)
    before = introspect.process_record()["kda_sites"]
    with pair_kernels_on_a_cpu() if pair_kernels else contextlib.nullcontext():
        S_c, o_c = chunked(S0, q, k, v, g, beta)
        grads_c = jax.grad(scalar(chunked), argnums=range(6))(S0, q, k, v, g, beta)
    # narrow widths count the plain pairs where they are traced; where the
    # kernels fit, a site is counted when a program holding it is lowered
    assert (kda_sites_since(before)["pair"] == 0) == pair_kernels
    np.testing.assert_allclose(o_c, o_r, atol=2e-5)
    np.testing.assert_allclose(S_c, S_r, atol=2e-5)
    assert bool(jnp.all(S_c[1] == 0)) and bool(jnp.any(S_c[0] != 0))
    for a, b in zip(grads_c, grads_r):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.max(jnp.abs(b))) + 1e-6)


def pair_operands(B, H, S, C, dk, seed=13):
    """Targets, keys and cumulative decays as ``_chunk`` hands them to the
    pairs: the steepest decays forget a state in a token, so over a
    sub-chunk ``G`` falls by hundreds and an exponent formed above the
    diagonal (``G_i - G_t``, ``i > t``) overflows a float32."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    norm = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    tk = norm(jax.random.normal(keys[0], (B, H, S, C, dk)))
    k = norm(jax.random.normal(keys[1], (B, H, C, dk)))
    g = -jnp.exp(jax.random.uniform(keys[2], (B, H, C, dk), minval=-7.0, maxval=5.0))
    return tk, k, jnp.cumsum(g, axis=2)


@pytest.mark.parametrize("n, H", [(1, 3), (4, 2)])
def test_the_pair_kernel_is_the_plain_lines_to_float32_rounding(n, H):
    c = kda.SUB
    tk, k, G = pair_operands(2, H, 2, n * c, 128)
    assert float(jnp.max(G[:, :, 0] - G[:, :, c - 1])) > 128  # e^128 overflows
    mine = kda._kernel_pairs(tk, k, G, c, interpret=True)
    ref = kda._plain_pairs(tk, k, G, c)
    assert mine.shape == ref.shape == (2, H, 2, n, c, c) and mine.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(mine)))
    assert float(jnp.max(jnp.abs(mine - ref))) <= 1e-6 * float(jnp.max(jnp.abs(ref)))
    # nothing above the diagonal, something on and below it
    above = ~np.tril(np.ones((c, c), bool))
    assert not np.any(np.asarray(mine)[..., above]) and np.all(np.asarray(mine)[..., ~above])


@pytest.mark.parametrize("n, H", [(1, 3), (4, 2)])
def test_the_pair_kernels_backward_is_the_plain_lines_vjp(n, H):
    """For all four inputs (two targets, the keys, the decays), from a
    cotangent that is not masked: a pair above the diagonal takes none."""
    c = kda.SUB
    tk, k, G = pair_operands(2, H, 2, n * c, 128)
    dD = jax.random.normal(jax.random.PRNGKey(5), (2, H, 2, n, c, c))
    mine = kda._kernel_pairs_bwd(tk, k, G, dD, c, interpret=True)
    ref = jax.vjp(functools.partial(kda._plain_pairs, c=c), tk, k, G)[1](dD)
    for a, b in zip((mine[0][:, :, 0], mine[0][:, :, 1], *mine[1:]),
                    (ref[0][:, :, 0], ref[0][:, :, 1], *ref[1:])):
        assert a.shape == b.shape and a.dtype == jnp.float32
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-6 * float(jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("shape, dtype, c, fits", [
    ((16, 32, 2, 64, 128), jnp.float32, 16, True),  # kimi_linear_rl's, a block of envs
    ((2, 3, 2, 16, 128), jnp.float32, 16, True),  # a fragment of one sub-chunk
    ((1, 1, 1, 8, 256), jnp.float32, 8, True),
    ((2, 2, 2, 32, 16), jnp.float32, 16, False),  # kimi_linear_tiny's width
    ((16, 32, 2, 64, 128), jnp.bfloat16, 16, False),  # the decays are float32
    ((32, 2, 64, 128), jnp.float32, 16, False),  # [B, H, S, C, dk] only
    ((2, 2, 2, 4, 128), jnp.float32, 4, False),  # a sub-chunk under a tile
    ((16, 512, 2, 64, 128), jnp.float32, 16, False),  # one env's blocks over VMEM
    ((0, 32, 2, 64, 128), jnp.float32, 16, False),
])
def test_the_shapes_the_pair_kernels_take(shape, dtype, c, fits):
    assert kda._pairs_fit(shape, dtype, c) == fits


@pytest.mark.parametrize("dk", [16, 128])
def test_off_the_tpu_and_at_small_widths_the_pairs_are_the_plain_lines(dk):
    """By shape when traced (dk = 16), by platform when lowered (dk = 128,
    here a CPU): counted as ``"pair"``, no Mosaic call in the program, and
    the values and gradients of the lines as they stood."""
    T, B, H = 32, 2, 2
    keys = jax.random.split(jax.random.PRNGKey(4), 6)
    q, k, v = (jax.random.normal(key, (T, B, H, dk)) * dk ** -0.5 for key in keys[:3])
    g = -jnp.exp(jax.random.uniform(keys[3], (T, B, H, dk), minval=-7.0, maxval=1.6))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (T, B, H)))
    done = jnp.zeros((T, B), bool).at[9, 1].set(True)
    S0 = jnp.zeros((B, H, dk, dk))

    def loss(q, k, g):
        S, o = kda.kda_chunk(S0, q, k, v, g, beta, done, chunk=32)
        return jnp.sum(o ** 2) + jnp.sum(S)

    before = introspect.process_record()["kda_sites"]
    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(q, k, g)
    since = kda_sites_since(before)
    assert since["pair"] > 0 and since["pair_kernel"] == 0 and since["chunk"] == 1
    assert "tpu_custom_call" not in lowered.as_text()
    with mock.patch.object(kda, "_pairs_fit", lambda *a: False):  # the lines alone
        ref = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, g)
    for a, b in zip(jax.tree.leaves(lowered.compile()(q, k, g)), jax.tree.leaves(ref)):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-7)


# (e') the one-token recurrence as a kernel, and the reset taken on the read
def step_operands(B, H, d, seed=11):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    norm = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    S = jax.random.normal(keys[0], (B, H, d, d))
    q = norm(jax.random.normal(keys[1], (B, H, d))) * d ** -0.5
    k = norm(jax.random.normal(keys[2], (B, H, d)))
    v = jax.random.normal(keys[3], (B, H, d))
    g = -jnp.exp(jax.random.uniform(keys[4], (B, H, d), minval=-7.0, maxval=1.6))
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (B, H)))
    return S, q, k, v, g, beta


@pytest.mark.parametrize("B, H, fresh", [
    (3, 8, (False, True, False)),  # a slice of the published heads
    (2, 32, (True, False)),  # the published tile: 32 heads of [128, 128]
    (4, 16, (False, False, False, False)),
    (2, 8, (True, True)),
])
def test_the_step_kernel_is_the_plain_form_to_float32_rounding(B, H, fresh):
    operands = step_operands(B, H, 128)
    fresh = jnp.asarray(fresh)
    S_k, o_k = kda._kernel_step(*operands, fresh, interpret=True)
    S_p, o_p = kda._plain_step(*operands, fresh)
    for mine, ref in ((S_k, S_p), (o_k, o_p)):
        assert float(jnp.max(jnp.abs(mine - ref))) <= 1e-6 * float(jnp.max(jnp.abs(ref)))
    # a fresh env starts from zero whatever S held: its state is k u^T alone
    _, q, k, v, g, beta = operands
    from_zero = kda._plain_step(jnp.zeros_like(S_p), q, k, v, g, beta, None)[0]
    for b in np.flatnonzero(np.asarray(fresh)):
        np.testing.assert_allclose(S_k[b], from_zero[b], atol=1e-6)
    assert S_k.dtype == o_k.dtype == jnp.float32


@pytest.mark.parametrize("shape, dtype, fits", [
    ((64, 32, 128, 128), jnp.float32, True),  # kimi_linear_rl's
    ((1, 8, 256, 128), jnp.float32, True),
    ((8, 2, 16, 16), jnp.float32, False),  # kimi_linear_tiny's
    ((64, 32, 128, 64), jnp.float32, False),
    ((64, 12, 128, 128), jnp.float32, False),  # an [H, dk] block of half tiles
    ((64, 32, 128, 128), jnp.bfloat16, False),  # the state is float32
    ((64, 512, 128, 128), jnp.float32, False),  # one env's block over VMEM
    ((0, 32, 128, 128), jnp.float32, False),
])
def test_the_shapes_the_step_kernel_takes(shape, dtype, fits):
    assert kda._kernel_fits(shape, dtype) == fits


def kda_sites_since(before):
    now = introspect.process_record()["kda_sites"]
    return {k: now[k] - before[k] for k in now}


@pytest.mark.parametrize("d, differentiated", [
    (16, False), (16, True), (128, False), (128, True)])
def test_off_the_tpu_and_at_small_widths_the_step_is_the_plain_form(d, differentiated):
    """The fallback: by shape when the call is traced (d = 16), by platform
    when it is lowered (d = 128, here a CPU), differentiated or not, with
    the plain form's values and gradients, and counted as ``"step"``."""
    operands = step_operands(2, 8, d)
    fresh = jnp.asarray([True, False])
    mix = jax.random.normal(jax.random.PRNGKey(3), (2, 8, d))

    def scalar(f):
        return lambda *a: jnp.sum(f(*a, fresh)[1] * mix) + jnp.sum(f(*a, fresh)[0])

    wrap = (lambda f: jax.grad(scalar(f), argnums=range(6))) if differentiated \
        else (lambda f: lambda *a: f(*a, fresh))
    before = introspect.process_record()["kda_sites"]
    mine = jax.jit(wrap(kda.kda_step))(*operands)
    assert kda_sites_since(before) == {
        "step": 2 if differentiated else 1, "step_kernel": 0, "chunk": 0,
        "pair": 0, "pair_kernel": 0}
    ref = jax.jit(wrap(kda._plain_step))(*operands)
    for a, b in zip(mine, ref):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-7)


def test_the_kernels_vjp_is_the_plain_forms():
    """What a differentiated call on a TPU runs: the kernel forward (here in
    the interpreter), the plain form's backward."""
    operands = step_operands(2, 8, 128)
    fresh = jnp.asarray([False, True])
    mix = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 128))
    scalar = lambda f: lambda *a: jnp.sum(f(*a, fresh)[1] * mix) + jnp.sum(f(*a, fresh)[0])
    with mock.patch.object(
            kda, "_kernel_step", functools.partial(kda._kernel_step, interpret=True)):
        mine = jax.grad(scalar(kda._kernel_step_vjp), argnums=range(6))(*operands)
    ref = jax.grad(scalar(kda._plain_step), argnums=range(6))(*operands)
    for a, b in zip(mine, ref):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-7)
    # no gradient flows into a fresh env's old state
    assert float(jnp.max(jnp.abs(mine[0][1]))) == 0 < float(jnp.max(jnp.abs(mine[0][0])))


LAZY_RESET = kimi_linear.SeqCore.reset


def eager_reset(self, done):
    """What ``SeqCore.reset`` was before the reset moved to the read."""
    return LAZY_RESET(self, done).settle()


@pytest.fixture(scope="module")
def fragments_ending_on_a_done(policy):
    """Two consecutive fragments of the program's rollout, the first with
    episode ends inside it **and on its last token**, under the lazy reset
    and under an eager one. Run op by op: XLA's choice of fused
    multiply-adds follows the fusion, so two programs agree to the last
    bit only where neither is fused."""
    env, model, variables = policy
    dist = distributions.for_config(CFG, env.spec)

    def roll(actor):
        return unroll(model.apply, variables, env, actor, CFG.unroll_len, dist=dist)[:2]

    def rollouts(seed, roll=roll, n=2):
        actor = actor_init(env, CFG.num_envs, jax.random.PRNGKey(seed), model=model)
        out = []
        for _ in range(n):
            actor, r = roll(actor)
            out.append((actor, r))
        return out

    jitted = jax.jit(roll)
    seed = next(  # the env draws the lengths: the policy ends no episode
        s for s in range(1, 200)
        if bool(jnp.any(rollouts(s, jitted, 1)[0][1].done[-1]))
    )
    with jax.disable_jit():
        lazy = rollouts(seed)
        with mock.patch.object(kimi_linear.SeqCore, "reset", eager_reset):
            eager = rollouts(seed)
    return lazy, eager


def test_unroll_with_the_reset_on_the_read_is_the_eager_resets_to_the_last_bit(
        policy, fragments_ending_on_a_done):
    _, model, variables = policy
    lazy, eager = fragments_ending_on_a_done
    last = np.asarray(lazy[0][1].done[-1])
    inside = np.asarray(lazy[0][1].done[:-1])
    assert last.any() and not last.all() and inside.any()
    for (actor_l, r_l), (actor_e, r_e) in zip(lazy, eager):
        # the final carry, the next fragment's init_core, the log-probs
        for mine, ref in zip(jax.tree.leaves((actor_l.core, r_l)),
                             jax.tree.leaves((actor_e.core, r_e))):
            np.testing.assert_array_equal(mine, ref)
        resets = [
            float(model.apply(variables, r.obs, r.done, r.init_core, r.actions,
                              method="fragment")[4]["episode_resets"])
            for r in (r_l, r_e)
        ]
        assert resets[0] == resets[1] == float(jnp.sum(r_l.done))
        # nothing pending in what leaves unroll
        for layer in actor_l.core.layers:
            if "S" in layer:
                assert not bool(jnp.any(layer["fresh"]))
    # the carry that left the first fragment is exactly zero where its last
    # token ended an episode, and only there
    for layer in lazy[1][1].init_core.layers:
        if "S" in layer:
            gone = np.asarray(jnp.max(jnp.abs(layer["S"]), axis=(1, 2, 3))) == 0
            np.testing.assert_array_equal(gone, last)
            assert float(jnp.max(jnp.abs(layer["conv"][last]))) == 0


def test_the_fragment_form_from_a_settled_carry_is_the_scanned_step_form(
        policy, fragments_ending_on_a_done):
    """``kda_chunk`` takes the settled ``init_core`` as it is: the learner's
    recompute of both fragments' log-probs is the rollout's."""
    _, model, variables = policy
    lazy, _ = fragments_ending_on_a_done
    for _, r in lazy:
        logp, _, _, _, _ = model.apply(
            variables, r.obs, r.done, r.init_core, r.actions, method="fragment")
        np.testing.assert_allclose(logp, r.behaviour_logp, atol=2e-5)


def test_both_forms_read_a_carry_with_a_reset_pending_as_zero(policy):
    """A carry recorded mid-stream (``reset_core`` and no ``settle_core``)
    is never read stale: the fragment form takes ``fresh`` on its ``S0``,
    the step form on its read."""
    _, model, variables = policy
    T, B = 8, 3
    tokens = jax.random.randint(jax.random.PRNGKey(7), (T, B), 0, TINY.vocab)
    done = jnp.zeros((T, B), bool)
    core = model.initial_core(B)
    for t in range(4):  # a carry with something in it
        _, _, core = model.apply(variables, tokens[t], core)
    pending = reset_core(core, jnp.asarray([False, True, False]))
    settled = settle_core(pending)
    assert float(jnp.max(jnp.abs(pending.layers[0]["S"][1]))) > 0
    assert float(jnp.max(jnp.abs(settled.layers[0]["S"][1]))) == 0
    assert bool(jnp.all(pending.layers[0]["fresh"] == jnp.asarray([False, True, False])))
    # a second reset before any read keeps the first
    again = reset_core(pending, jnp.zeros((B,), bool))
    np.testing.assert_array_equal(again.layers[0]["fresh"], pending.layers[0]["fresh"])
    for form in (
        lambda c: model.apply(variables, tokens, done, c, method="fragment")[:3],
        lambda c: model.apply(variables, tokens[0], c),
    ):
        for mine, ref in zip(jax.tree.leaves(form(pending)),
                             jax.tree.leaves(form(settled))):
            np.testing.assert_array_equal(mine, ref)


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


# (h) the latent attention this model shares with models/moonlight.py
# (models/mla.py): with nothing rotated it is the body this file's model had
# before the two shared it, to the last bit. The body as it was, frozen:
def _frozen_project(p, x, shape, dtype):
    """Queries [..., H, nope + rope] and the latent row [..., lora + rope]
    (normed latent, then the shared unrotated key part) the cache holds."""
    q = _dot(x, p["q"], dtype).reshape(
        *x.shape[:-1], shape.mla_heads, shape.qk_nope + shape.qk_rope
    )
    kv = _dot(x, p["kv_a"], dtype)
    latent = jnp.concatenate([
        _rms_norm(kv[..., : shape.kv_lora], p["kv_norm"], shape.eps),
        kv[..., shape.kv_lora:],
    ], axis=-1)
    return q, latent.astype(dtype)


def _frozen_step(p, x, state, shape, dtype):
    """One token: write its latent row at ``len``, attend over the rows of
    the current episode with the up-projection absorbed into the query and
    the output (no per-position keys or values are formed)."""
    H, dn, lora = shape.mla_heads, shape.qk_nope, shape.kv_lora
    with jax.named_scope("mla"):
        q, latent = _frozen_project(p, x, shape, dtype)
        B = x.shape[0]
        cache = state["kv"].at[jnp.arange(B), state["len"]].set(latent)
        kv_b = p["kv_b"].reshape(lora, H, dn + shape.v_head).astype(dtype)
        q_lat = jnp.einsum(
            "bhd,lhd->bhl", q[..., :dn].astype(dtype), kv_b[..., :dn],
            preferred_element_type=F32,
        )
        scores = jnp.einsum(
            "bhl,bpl->bhp",
            jnp.concatenate([q_lat, q[..., dn:]], axis=-1).astype(dtype), cache,
            preferred_element_type=F32,
        ) / math.sqrt(dn + shape.qk_rope)
        mask = jnp.arange(cache.shape[1])[None, :] <= state["len"][:, None]
        probs = _softmax(scores, mask[:, None, :])
        ctx = jnp.einsum(
            "bhp,bpl->bhl", probs.astype(dtype), cache[..., :lora],
            preferred_element_type=F32,
        )
        out = jnp.einsum(
            "bhl,lhd->bhd", ctx.astype(dtype), kv_b[..., dn:],
            preferred_element_type=F32,
        )
        return (
            _dot(out.reshape(B, -1), p["o"], dtype),
            {"kv": cache, "len": state["len"] + 1},
        )


def _frozen_fragment(p, x, state, done, shape, dtype):
    """A fragment: keys and values materialised for the cached rows of the
    episode in progress and the fragment's own, causal softmax within the
    episode, in blocks of envs."""
    H, dn, lora = shape.mla_heads, shape.qk_nope, shape.kv_lora
    T, B, _ = x.shape
    L = state["kv"].shape[1]
    with jax.named_scope("mla"):
        q, latent = _frozen_project(p, x, shape, dtype)
        rows = jnp.concatenate(
            [state["kv"], jnp.moveaxis(latent, 0, 1)], axis=1
        )  # [B, L + T, lora + rope]
        mask, ends = _episode_mask(done, state["len"], L)  # [B, T, L + T]

        def attend(args):
            q, rows, mask = args  # [b, T, H, dn + rope], [b, L+T, .], [b, T, L+T]
            kv = _dot(rows[..., :lora], p["kv_b"], dtype).reshape(
                *rows.shape[:2], H, dn + shape.v_head
            )
            scores = jnp.einsum(
                "bthd,bphd->bhtp", q[..., :dn].astype(dtype),
                kv[..., :dn].astype(dtype), preferred_element_type=F32,
            ) + jnp.einsum(
                "bthr,bpr->bhtp", q[..., dn:].astype(dtype), rows[..., lora:],
                preferred_element_type=F32,
            )
            probs = _softmax(
                scores / math.sqrt(dn + shape.qk_rope), mask[:, None]
            )
            return jnp.einsum(
                "bhtp,bphd->bthd", probs.astype(dtype),
                kv[..., dn:].astype(dtype), preferred_element_type=F32,
            )

        n = B // _env_block(B, H * T * (L + T))
        out = jax.lax.map(
            jax.checkpoint(attend),
            tuple(
                _to_blocks(a, 0, n) for a in (jnp.moveaxis(q, 0, 1), rows, mask)
            ),
        ).reshape(B, T, -1)
        out = _dot(jnp.moveaxis(out, 0, 1), p["o"], dtype)

        src, length = _cache_after(done, ends, state["len"], L)
        cache = jnp.take_along_axis(rows, src[..., None], axis=1)
        return out, {"kv": cache, "len": length}



@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_shared_latent_attention_unrotated_is_the_frozen_body_to_the_bit(dtype):
    from asyncrl_tpu.models import mla

    s = TINY
    D, T, B = s.hidden, 12, 3
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 8))
    w = lambda *dims: jax.random.normal(next(keys), dims) * dims[-2] ** -0.5
    p = {"q": w(D, s.mla_heads * (s.qk_nope + s.qk_rope)),
         "kv_a": w(D, s.kv_lora + s.qk_rope),
         "kv_norm": 1.0 + 0.1 * jax.random.normal(next(keys), (s.kv_lora,)),
         "kv_b": w(s.kv_lora, s.mla_heads * (s.qk_nope + s.v_head)),
         "o": w(s.mla_heads * s.v_head, D)}
    x = jax.random.normal(next(keys), (T, B, D))
    state = {"kv": jax.random.normal(
                 next(keys), (B, s.max_positions, s.kv_lora + s.qk_rope)).astype(dtype),
             "len": jnp.asarray([0, 7, 20], jnp.int32)}
    done = jnp.zeros((T, B), bool).at[4, 1].set(True).at[11, 2].set(True)
    same = lambda a, b: jax.tree.all(jax.tree.map(
        lambda u, v: bool(jnp.array_equal(u, v)) and u.dtype == v.dtype, a, b))
    assert same(jax.jit(lambda *a: mla.fragment(*a, s, dtype))(p, x, state, done),
                jax.jit(lambda *a: _frozen_fragment(*a, s, dtype))(p, x, state, done))
    assert same(jax.jit(lambda *a: mla.step(*a, s, dtype))(p, x[0], state),
                jax.jit(lambda *a: _frozen_step(*a, s, dtype))(p, x[0], state))
    # and the gradient through the fragment form
    grad = lambda f: jax.jit(jax.grad(lambda p: jnp.sum(f(p, x, state, done, s, dtype)[0])))(p)
    assert same(grad(mla.fragment), grad(_frozen_fragment))
