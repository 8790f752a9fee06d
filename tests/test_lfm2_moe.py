"""The LFM2-MoE sequence policy (models/lfm2_moe.py, models/seq_common.py,
ops/moe.py) against its plain reference (benchmarks/reference/lfm2_moe.py),
on seeded random weights at the tiny preset's sizes, in float32; the expert
layer's grouped side against its dense side; and the other sequence policy
(models/kimi_linear.py) against the values it gave before this module
shared its trunk."""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncrl_tpu import make_agent
from asyncrl_tpu.configs import presets
from asyncrl_tpu.envs import registry
from asyncrl_tpu.learn import learner as learner_mod
from asyncrl_tpu.models import lfm2_moe, seq_common
from asyncrl_tpu.models.networks import build_model, reset_core, settle_core
from asyncrl_tpu.obs import introspect
from asyncrl_tpu.ops import distributions, moe
from asyncrl_tpu.rollout.anakin import actor_init, unroll
from benchmarks.reference import lfm2_moe as reference

TINY = lfm2_moe.SHAPES["lfm2_moe_tiny"]
CFG = presets.get("lfm2_moe_tiny").replace(precision="f32", fused_scan="lax")
HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True, scope="module")
def small_tiles():
    """Tiles of 8 rows on the grouped side, so that the tiny preset's blocks
    of 128 tokens take it (at the chip's 512 they would be computed
    densely, as a decode step's few tokens are)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "TILE", 8)
        yield


def dims_of(shape):
    return dataclasses.asdict(shape)


def plain_core(core):
    return [dict(layer) for layer in core.layers]


@pytest.fixture(scope="module")
def policy():
    env = registry.make(CFG.env_id, CFG)
    model = build_model(CFG, env.spec)
    assert isinstance(model, lfm2_moe.Lfm2Policy)
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


def test_the_carry_holds_a_conv_tail_and_a_cache_and_each_resets_its_own_way(policy):
    _, model, _ = policy
    core = model.initial_core(3)
    assert [sorted(layer) for layer in core.layers] == [
        ["conv"], ["k", "len", "v"], ["conv"]]
    assert core.layers[0]["conv"].shape == (3, 2, TINY.hidden)
    assert core.layers[1]["k"].shape == (3, TINY.max_positions, 2 * 16)
    full = jax.tree.map(lambda x: jnp.ones_like(x), core)
    done = jnp.asarray([False, True, False])
    after = reset_core(full, done)
    np.testing.assert_array_equal(after.layers[1]["len"], [1, 0, 1])
    # the rows stay: the length empties the cache
    assert bool(jnp.all(after.layers[1]["k"] == 1))
    assert float(jnp.max(jnp.abs(after.layers[0]["conv"][1]))) == 0
    assert bool(jnp.all(after.layers[2]["conv"][0] == 1))
    # nothing is ever pending in this carry
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool(jnp.all(a == b)), settle_core(after), after))


# (a) fragment form, loss and every gradient leaf against the reference.
# Tolerances: float32 sums in another order (blocks of envs, the grouped
# experts, the fused conv); bfloat16 products move logits by 1e-2.
def test_fragment_form_loss_and_gradients_match_the_reference(policy, fragments):
    env, model, variables = policy
    _, r = fragments
    assert float(jnp.sum(r.done)) > 0
    logits, values, _, _ = model.apply(
        variables, r.obs, r.done, r.init_core, method="fragment")
    ref_logits, ref_values, _ = reference.forward(
        variables, dims_of(TINY), r.obs, r.done, plain_core(r.init_core))
    np.testing.assert_allclose(logits, ref_logits, atol=2e-4)
    np.testing.assert_allclose(values, ref_values, atol=2e-4)
    low_logits, _, _ = reference.forward(
        variables, dims_of(TINY), r.obs, r.done, plain_core(r.init_core), low=True)
    assert float(jnp.max(jnp.abs(low_logits - ref_logits))) > 2e-3

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
    # the router's expert bias is a buffer: no gradient reaches it
    assert moved == len(flat) - sum("router_bias" in str(p) for p, _ in flat)


# (b) what the importance ratio is built from
def test_rollout_logp_through_the_carry_is_the_learners_recompute(policy, fragments):
    _, model, variables = policy
    for r in fragments:
        logp, _, _, _, aux = model.apply(
            variables, r.obs, r.done, r.init_core, r.actions, method="fragment")
        np.testing.assert_allclose(logp, r.behaviour_logp, atol=2e-5)
        assert float(aux["episode_resets"]) == float(jnp.sum(r.done))
        # 2 expert layers x 4 held experts' loads; a decode step aside, the
        # tiny blocks (128 tokens, 4 of 8 held, top 2) take the grouped side
        assert float(aux["moe_local_assignments"]) == pytest.approx(
            float(aux["moe_load_mean"]) * 8)
        assert float(aux["moe_dense_blocks"]) == 0
        assert 1 <= float(aux["gqa_rows_attended"]) <= TINY.max_positions


def boundaries(ends, T, B):
    done = np.zeros((2 * T, B), bool)
    for b in range(B):  # each env its own boundaries, shifted
        for e in ends:
            shift = b if 0 < e < 2 * T - 1 and e not in (T - 1, T) else 0
            done[min(e + shift, 2 * T - 1), b] = True
    return jnp.asarray(done)


# (c) two fragments through the carry against one 2T sequence from zero
@pytest.mark.parametrize("ends", [
    (0, 15, 20, 23, 24, 35, 47),  # first and last step; one ends exactly at the boundary
    (10, 30, 40),  # an episode that spans the fragments
])
def test_two_fragments_with_the_carry_match_the_reference_over_2t(policy, ends):
    """RoPE positions continue across the boundary and the conv window
    reads the tail: an episode that crosses it gives the reference's logits
    (which counts positions from the episode's start over one 2T sequence)."""
    _, _, variables = policy
    shape = dataclasses.replace(TINY, block_tokens=48)
    model = lfm2_moe.Lfm2Policy(shape)
    T, B = 24, 4
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2 * T, B), 0, TINY.vocab)
    done = boundaries(ends, T, B)
    crossing = ~np.asarray(done[T - 1])
    assert crossing.any() if ends[0] == 10 else not crossing.any()
    core0 = model.initial_core(B)
    frag = jax.jit(lambda v, t, d, c: model.apply(v, t, d, c, method="fragment"))
    l1, v1, core1, _ = frag(variables, tokens[:T], done[:T], core0)
    l2, v2, core2, _ = frag(variables, tokens[T:], done[T:], core1)
    if ends[0] == 10:  # the carry holds the episode in progress
        assert int(jnp.min(core1.layers[1]["len"])) > 0
        assert float(jnp.max(jnp.abs(core1.layers[0]["conv"]))) > 0
    ref_logits, ref_values, ref_core = jax.jit(
        lambda v, t, d: reference.forward(
            v, dims_of(shape), t, d, plain_core(core0))
    )(variables, tokens, done)
    np.testing.assert_allclose(jnp.concatenate([l1, l2]), ref_logits, atol=3e-4)
    np.testing.assert_allclose(jnp.concatenate([v1, v2]), ref_values, atol=3e-4)
    for mine, ref in zip(core2.layers, ref_core):
        if "conv" in mine:
            np.testing.assert_allclose(mine["conv"], ref["conv"], atol=2e-4)
        else:
            np.testing.assert_array_equal(mine["len"], ref["len"])
            live = (jnp.arange(shape.max_positions)[None, :, None]
                    < ref["len"][:, None, None])
            for name in ("k", "v"):
                np.testing.assert_allclose(
                    jnp.where(live, mine[name], 0), jnp.where(live, ref[name], 0),
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
    core_s = settle_core(core_s)
    logits_f, values_f, core_f, _ = model.apply(
        variables, tokens, done, model.initial_core(B), method="fragment")
    np.testing.assert_allclose(logits_s, logits_f, atol=2e-4)
    np.testing.assert_allclose(values_s, values_f, atol=2e-4)
    np.testing.assert_array_equal(core_s.layers[1]["len"], core_f.layers[1]["len"])
    np.testing.assert_array_equal(core_f.layers[1]["len"], [18, 0, 24])
    for i in (0, 2):
        np.testing.assert_allclose(
            core_s.layers[i]["conv"], core_f.layers[i]["conv"], atol=2e-4)
    live = (jnp.arange(TINY.max_positions)[None, :, None]
            < core_f.layers[1]["len"][:, None, None])
    for name in ("k", "v"):
        np.testing.assert_allclose(
            jnp.where(live, core_s.layers[1][name], 0),
            jnp.where(live, core_f.layers[1][name], 0), atol=2e-4)


# (d) positions
def test_an_episode_that_resets_inside_a_fragment_starts_again_at_position_0(policy):
    """The tokens from a reset at step t on give what the same tokens give
    as a fragment of their own from an empty carry; with the positions not
    restarted (the same tokens 9 rows into an episode) they do not."""
    _, model, variables = policy
    T, B, t = 20, 2, 9
    tokens = jax.random.randint(jax.random.PRNGKey(8), (T, B), 0, TINY.vocab)
    done = jnp.zeros((T, B), bool).at[t - 1].set(True)
    frag = lambda tok, d: model.apply(
        variables, tok, d, model.initial_core(B), method="fragment")
    whole, _, core, _ = frag(tokens, done)
    alone, _, core_alone, _ = frag(tokens[t:], done[t:])
    np.testing.assert_allclose(whole[t:], alone, atol=2e-4)
    np.testing.assert_array_equal(core.layers[1]["len"], core_alone.layers[1]["len"])
    unbroken, _, _, _ = frag(tokens, jnp.zeros_like(done))
    assert float(jnp.max(jnp.abs(unbroken[t:] - alone))) > 1e-2


@pytest.mark.parametrize("how", [
    {"theta": 1e4},  # another rotary base
    {"qk_norm": False},  # the per-head norms of q and k left out
    {"conv_gate": False},  # the conv's output gate left out
    {"held": [0, 1, 2]},  # one held expert's part left out
])
def test_a_wrong_reference_is_far_from_the_program(policy, fragments, how):
    _, model, variables = policy
    _, r = fragments
    logits, _, _, _ = model.apply(
        variables, r.obs, r.done, r.init_core, method="fragment")
    wrong, _, _ = reference.forward(
        variables, dims_of(TINY), r.obs, r.done, plain_core(r.init_core), **how)
    assert float(jnp.max(jnp.abs(logits - wrong))) > 100 * 2e-4


# (e) the share ties to the model
def expert_weights(E, D, F, skew):
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    w = lambda key, *dims: jax.random.normal(key, dims) * dims[-2] ** -0.5
    return {
        "router": w(keys[0], D, E),
        "router_bias": (0.02 * jax.random.normal(keys[1], (E,))).at[:3].add(skew),
        "experts": {"gate": w(keys[2], E, D, F), "up": w(keys[3], E, D, F),
                    "down": w(keys[4], E, F, D)},
    }


@pytest.mark.parametrize("N, skew, sides", [
    (16, 0.0, {"dense"}),  # a decode step's tokens
    (2048, 0.0, {"grouped"}),  # a fragment's tokens, a router in balance
    # every token sent to experts 0, 1 and 2: their share's 3 N assignments
    # overflow its buffer (2 N) and it is computed densely, the other shares
    # stay grouped; no token dropped
    (2048, 10.0, {"grouped", "overflow"}),
])
def test_the_four_shares_sum_to_the_uncut_layer(N, skew, sides):
    E, k, D, F, tile = 32, 4, 32, 16, 16
    full = expert_weights(E, D, F, skew)
    x = jax.random.normal(jax.random.PRNGKey(8), (N, D))
    dims = {"held_experts": tuple(range(E)), "top_k": k, "routed_scale": 1.0}
    uncut = reference.expert_layer(full, x, dims)

    ids, weights = moe.route(x, full["router"], full["router_bias"], k, 1.0, 1e-6)
    total, loads, took = 0.0, [], set()
    rows = moe.grouped_rows(N, k, 8, E, tile)
    # a function of this test's own: nothing of it is lowered yet
    layer = jax.jit(lambda *a: moe.held_experts(*a), static_argnums=(3, 4, 8, 9))
    before = introspect.process_record()["moe_sites"]
    layer.lower(x, ids, weights, tuple(range(8)), E, *(
        full["experts"][n][:8] for n in ("gate", "up", "down")), jnp.float32, tile)
    after = introspect.process_record()["moe_sites"]
    # which side a call is built with is known when it is lowered
    assert {k for k in after if after[k] > before[k]} == (
        {"dense"} if sides == {"dense"} else {"grouped"})
    for first in range(0, E, 8):
        held = tuple(range(first, first + 8))
        share = {n: full["experts"][n][first:first + 8] for n in ("gate", "up", "down")}
        part, load, dense = layer(
            x, ids, weights, held, E, share["gate"], share["up"], share["down"],
            jnp.float32, tile)
        total = total + part
        loads.append(load)
        fits = int(jnp.sum(-(-load // tile) * tile)) <= rows
        if 3 * rows > 2 * 8 * N:
            took.add("dense")
            assert bool(dense)
        else:
            took.add("grouped" if fits else "overflow")
            assert bool(dense) == (not fits)
        # the reference's own share of it agrees with the program's
        mine = reference.expert_layer(
            {**full, "experts": share}, x, {**dims, "held_experts": held})
        np.testing.assert_allclose(part, mine, atol=2e-4)
    np.testing.assert_allclose(total, uncut, atol=2e-4)
    assert int(jnp.sum(jnp.concatenate(loads))) == N * k  # no token dropped
    assert took == sides


# (f) the grouped side against the dense side
def loads_case(name, N, k, n_held, rows, tile):
    """Expert ids [N, k] (held: 0..n_held-1, not held: from n_held on) that
    put the case's load on the held experts."""
    away = n_held + np.arange(N * k).reshape(N, k) % 4
    ids = away.copy()
    if name == "one_empty":  # expert 3 gets nothing, the others a few each
        for e in (0, 1, 2):
            ids[e::7, 0] = e
    elif name == "all_on_one":  # every token chooses expert 2
        ids[:, 0] = 2
    else:  # a total just under / just over the buffer's bound
        room = rows - n_held * tile  # whole tiles but for the padding
        total = room if name == "just_under" else rows + 1
        flat = ids.reshape(-1)
        flat[:total] = np.arange(total) % n_held
        # a token chooses an expert once: k consecutive slots hold k different
        assert k <= n_held
    return jnp.asarray(ids, jnp.int32)


@pytest.mark.parametrize("case, N", [
    ("one_empty", 512), ("all_on_one", 512), ("just_under", 512), ("just_over", 512),
    ("just_over", 4096),  # the dense side in blocks of 2,048 tokens
])
def test_the_grouped_side_is_the_dense_side_to_float32_rounding(case, N):
    k, E, D, F, tile = 4, 32, 32, 16, 16
    held = tuple(range(8))
    full = expert_weights(8, D, F, 0.0)["experts"]
    rows = moe.grouped_rows(N, k, 8, E, tile)
    ids = loads_case(case, N, k, 8, rows, tile)
    weights = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(2), (N, k)))
    x = jax.random.normal(jax.random.PRNGKey(3), (N, D))
    mix = jax.random.normal(jax.random.PRNGKey(4), (N, D))

    def layer(x, weights, full, tile):
        out, load, dense = moe.held_experts(
            x, ids, weights, held, E, full["gate"], full["up"], full["down"],
            jnp.float32, tile)
        return jnp.sum(out * mix), (out, load, dense)

    run = jax.jit(jax.value_and_grad(layer, argnums=(0, 1, 2), has_aux=True),
                  static_argnums=3)
    (_, (out, load, dense)), grads = run(x, weights, full, tile)
    # a tile so large that the buffer is not worth having: the dense side
    (_, (ref, ref_load, ref_dense)), ref_grads = run(x, weights, full, 8 * N)
    assert bool(ref_dense)
    assert bool(dense) == (case == "just_over")
    np.testing.assert_array_equal(load, ref_load)
    assert int(jnp.sum(load)) == int(jnp.sum(ids < 8))  # no token dropped
    if case == "one_empty":
        assert int(load[3]) == 0
    if case == "all_on_one":
        assert int(load[2]) == N
    np.testing.assert_allclose(out, ref, atol=1e-5 * float(jnp.max(jnp.abs(ref))))
    for mine, theirs in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(
            mine, theirs, atol=1e-5 * float(jnp.max(jnp.abs(theirs))) + 1e-7)


def test_the_routers_renormalising_sum_is_the_models_own():
    """Kimi's arithmetic as it was (no epsilon), this family's by an
    argument of ``route``."""
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    kernel = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    bias = jnp.zeros((6,))
    ids, plain_w = moe.route(x, kernel, bias, 2, 2.0)
    ids_e, eps_w = moe.route(x, kernel, bias, 2, 2.0, 0.5)
    np.testing.assert_array_equal(ids, ids_e)
    np.testing.assert_allclose(jnp.sum(plain_w, axis=-1), 2.0, rtol=1e-6)
    scores = jnp.take_along_axis(jax.nn.sigmoid(x @ kernel), ids, axis=-1)
    np.testing.assert_allclose(
        eps_w, 2.0 * scores / (jnp.sum(scores, -1, keepdims=True) + 0.5), rtol=1e-5)


# (g) the other sequence policy is untouched
def test_kimi_linear_tiny_gives_the_values_it_gave_before_the_trunk_was_shared():
    """``tests/data/kimi_linear_tiny_parent.json``: the fragment loss, each
    gradient leaf's sum and absolute sum, and the ``moe_load_*`` counters of
    ``kimi_linear_tiny`` taken on the parent commit (PR 29) on this CPU
    backend, as float hex. The rollout's log-probs and the counters bit for
    bit: the refactor moved code and changed no arithmetic. The loss and the
    gradients to float32 rounding of the same sums (1e-5 of the loss, 1e-4
    of a leaf's absolute sum: they were bit for bit until the dense side of
    ``ops/moe.py`` began to add the held experts' parts one after the other
    in place of one product contracted over experts and width, which is
    the same sum in another order; a bfloat16 product moves them by 1e-2)."""
    with open(os.path.join(HERE, "data", "kimi_linear_tiny_parent.json")) as f:
        parent = json.load(f)
    cfg = presets.get("kimi_linear_tiny").replace(precision="f32", fused_scan="lax")
    env = registry.make(cfg.env_id, cfg)
    model = build_model(cfg, env.spec)
    variables = model.init(jax.random.PRNGKey(0))
    dist = distributions.for_config(cfg, env.spec)
    actor = actor_init(env, cfg.num_envs, jax.random.PRNGKey(1), model=model)
    roll = jax.jit(lambda a: unroll(
        model.apply, variables, env, a, cfg.unroll_len, dist=dist)[:2])
    actor, _ = roll(actor)
    _, r = roll(actor)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda v: learner_mod._algo_loss(cfg, model.apply, v, r), has_aux=True)
    )(variables)
    assert float(jnp.sum(r.behaviour_logp)).hex() == parent["behaviour_logp_sum"]
    assert float(loss) == pytest.approx(float.fromhex(parent["loss"]), rel=1e-5)
    for name, value in parent["aux"].items():
        assert float(metrics[name]).hex() == value, name
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    assert len(flat) == len(parent["grads"])
    for path, g in flat:
        total, size = map(float.fromhex, parent["grads"][jax.tree_util.keystr(path)])
        assert float(jnp.sum(jnp.abs(g))) == pytest.approx(size, rel=1e-4), path
        assert abs(float(jnp.sum(g)) - total) <= 1e-4 * size, path
    # the carry's type is the shared one, under its old name too
    from asyncrl_tpu.models import kimi_linear
    assert kimi_linear.SeqCore is seq_common.SeqCore


# (h) what build_model refuses
def test_only_policy_gradient_algorithms_over_the_vocabulary_within_the_cache_build():
    env = registry.make(CFG.env_id, CFG)
    with pytest.raises(ValueError, match="seq_model"):
        build_model(CFG.replace(algo="qlearn"), env.spec)
    with pytest.raises(ValueError, match="seq_model"):
        build_model(CFG, registry.make("CartPole-v1").spec)
    wrong_vocab = CFG.replace(token_task=(32, 2, 32, 1, 2))
    with pytest.raises(ValueError, match="vocabulary"):
        build_model(wrong_vocab, registry.make(CFG.env_id, wrong_vocab).spec)
    too_long = CFG.replace(token_task=(64, 2, 33, 1, 2))
    with pytest.raises(ValueError, match="positions"):
        build_model(too_long, registry.make(CFG.env_id, too_long).spec)
    with pytest.raises(ValueError, match="unknown seq_model"):
        build_model(CFG.replace(seq_model="no_such"), env.spec)


# (i) the preset trains on the normal path
def test_the_preset_trains_on_the_anakin_path_and_moves_the_policy():
    before = introspect.process_record()["moe_sites"]
    # 4 envs x 32 tokens a device: a block the grouped side takes
    agent = make_agent(CFG.replace(num_envs=4 * len(jax.devices())))
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
        assert float(metrics["moe_local_assignments"]) > 0
        assert float(metrics["moe_dense_blocks"]) == 0
        assert float(metrics["gqa_rows_attended"]) >= 1
        assert float(metrics["episode_resets"]) > 0
        delta = sum(
            float(jnp.sum(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(first), jax.tree.leaves(state.params))
        )
        assert delta > 0
        assert int(state.update_step) == 3
    finally:
        agent.close()
    after = introspect.process_record()["moe_sites"]
    # the rollout's few tokens lowered the dense side, the learner the grouped
    assert after["dense"] > before["dense"] and after["grouped"] > before["grouped"]
    assert after["gathered"] == before["gathered"]


# (j) what a profile of the step reads
def test_the_step_names_the_scopes_a_profile_reads():
    """The new mixers' scopes and the expert layer's are on the ops of the
    rollout (one-token forms) and of the learner, forward and backward
    (``*_device_ms`` count both), and Kimi's names are not on this step."""
    cfg = CFG.replace(num_envs=4 * len(jax.devices()), unroll_len=32,
                      fused_scan="interpret")
    agent = make_agent(cfg)
    try:
        text = agent.learner._step.lower(agent.state).compile().as_text()
    finally:
        agent.close()
    names = re.findall(r'op_name="([^"]+)"', text)
    components = {c for name in names for c in name.split("/")}
    for scope in ("rollout", "loss_and_grad", "actor_forward", "env_step",
                  "conv_mixer", "gqa", "moe", "moe_router", "moe_experts",
                  "lm_head", "core_reset"):
        assert scope in components, scope
    assert not components & {"kda", "kda_step", "kda_chunk", "mla"}

    def some(*parts):
        return any(all(p in n for p in parts) for n in names)

    for scope in ("/conv_mixer/", "/gqa/", "/moe/moe_router/", "/moe/moe_experts/",
                  "/lm_head/"):
        assert some("/rollout/", "/actor_forward/", scope), scope
        assert some("/loss_and_grad/", scope), scope
        # the backward pass keeps the scopes
        assert some("/loss_and_grad/", "transpose(", scope), scope
    assert some("/rollout/", "/core_reset/")
    # the one-token attention's own scope inside ``gqa``: ``gqa_device_ms``
    # still holds it, and a reader can select it by name (the learner's
    # bootstrap token is differentiated, and reads ``jvp(gqa)/gqa_step``)
    assert some("/rollout/", "/actor_forward/", "/gqa/gqa_step/")
    assert some("/loss_and_grad/", "gqa)/gqa_step/")
    assert not some("/loss_and_grad/", "transpose(", "gqa_step")


# (j) the rule that keeps the update's rollout its replay's to the last bit


def _dot_generals(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _dot_generals(sub)


@pytest.mark.parametrize("preset", ["lfm2_moe_tiny", "kimi_linear_tiny"])
def test_no_product_on_the_rollouts_path_contracts_two_axes(preset):
    """A product over two contracted axes is tiled, and so summed, by what
    else the program holds in VMEM: the rollout inside the step and the same
    rollout alone then sample other tokens (PERF.md, PR 30). The one-token
    form of both sequence policies, as ``unroll`` calls it."""
    cfg = presets.get(preset).replace(precision="f32", fused_scan="lax")
    env = registry.make(cfg.env_id, cfg)
    model = build_model(cfg, env.spec)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    core = jax.eval_shape(lambda: model.initial_core(4))
    tokens = jax.ShapeDtypeStruct((4,), jnp.int32)
    jaxpr = jax.make_jaxpr(model.apply)(variables, tokens, core)
    products = list(_dot_generals(jaxpr.jaxpr))
    assert len(products) >= 10
    for eqn in products:
        (lhs, rhs), _ = eqn.params["dimension_numbers"]
        assert len(lhs) == len(rhs) == 1, eqn
