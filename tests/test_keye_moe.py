"""The Keye sequence policy (models/keye_moe.py, ops/dsa.py, the softmax side
of ops/moe.py ``route``, the model's own loss term through learn/learner.py)
against its plain reference (benchmarks/reference/keye_moe.py), on seeded
random weights at the tiny preset's sizes (a top-k of 8 under episodes of
12-32 tokens, so that the selection prunes), in float32."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncrl_tpu import make_agent
from asyncrl_tpu.configs import presets
from asyncrl_tpu.envs import registry
from asyncrl_tpu.learn import learner as learner_mod
from asyncrl_tpu.models import keye_moe, lfm2_moe, seq_common
from asyncrl_tpu.models.networks import build_model, reset_core, settle_core
from asyncrl_tpu.ops import distributions, moe
from asyncrl_tpu.rollout.anakin import actor_init, unroll
from benchmarks.reference import keye_moe as reference

TINY = keye_moe.SHAPES["keye_moe_tiny"]
DIMS = dataclasses.asdict(TINY)
CFG = presets.get("keye_moe_tiny").replace(precision="f32", fused_scan="lax")
ROWS = ("k", "v", "ki")


@pytest.fixture(scope="module")
def policy():
    env = registry.make(CFG.env_id, CFG)
    model = build_model(CFG, env.spec)
    assert isinstance(model, keye_moe.KeyePolicy)
    return env, model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def fragments(policy):
    """Three consecutive fragments of the program's own rollout from empty
    caches: 48 steps, longer than any episode, so every env crosses a
    boundary and the later fragments start from caches longer than top-k."""
    env, model, variables = policy
    dist = distributions.for_config(CFG, env.spec)
    actor = actor_init(env, CFG.num_envs, jax.random.PRNGKey(1), model=model)
    roll = jax.jit(lambda a: unroll(
        model.apply, variables, env, a, CFG.unroll_len, dist=dist)[:2])
    out = []
    for _ in range(3):
        actor, r = roll(actor)
        out.append(r)
    return out


def history_of(fragments):
    """The reference's view of the last of ``fragments``: every token and
    flag since the caches were empty."""
    r = fragments[-1]
    return {
        "history_obs": jnp.concatenate([f.obs for f in fragments]),
        "history_done": jnp.concatenate([f.done for f in fragments]),
        "bootstrap_obs": r.bootstrap_obs, "actions": r.actions,
        "behaviour_logp": r.behaviour_logp, "rewards": r.rewards, "done": r.done,
    }


def rows_close(mine, theirs, atol=2e-5):
    for a, b in zip(mine, theirs):
        np.testing.assert_array_equal(a["len"], b["len"])
        live = (np.arange(a["k"].shape[1])[None] < np.asarray(b["len"])[:, None])[..., None]
        for name in ROWS:
            np.testing.assert_allclose(
                np.where(live, a[name], 0), np.where(live, b[name], 0), atol=atol,
                err_msg=name)


def test_the_carry_holds_three_kinds_of_row_under_one_len(policy):
    _, model, _ = policy
    core = model.initial_core(3)
    assert [sorted(layer) for layer in core.layers] == [["k", "ki", "len", "v"]] * 2
    assert core.layers[0]["k"].shape == (3, TINY.max_positions, 2 * 16)
    assert core.layers[0]["ki"].shape == (3, TINY.max_positions, TINY.index_dim)
    full = jax.tree.map(lambda x: jnp.ones_like(x), core)
    after = reset_core(full, jnp.asarray([False, True, False]))
    np.testing.assert_array_equal(after.layers[1]["len"], [1, 0, 1])
    # the rows stay, all three kinds: the one length empties the cache
    assert all(bool(jnp.all(after.layers[1][n] == 1)) for n in ROWS)
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool(jnp.all(a == b)), settle_core(after), after))


# (a) the three forms: one token through the cache = the fragment form = the
# reference's full forward, on fragments that start from caches longer than
# top-k and hold boundaries
def test_step_form_fragment_form_and_reference_agree_past_top_k(policy, fragments):
    _, model, variables = policy
    history = history_of(fragments)
    assert float(jnp.sum(history["history_done"])) >= CFG.num_envs
    r = fragments[-1]
    assert int(jnp.max(r.init_core.layers[0]["len"])) > TINY.index_top_k
    logits, values, core, aux = model.apply(
        variables, r.obs, r.done, r.init_core, method="fragment")
    # one token at a time through the carry, resets as the rollout applies them
    c, stepped = r.init_core, []
    step = jax.jit(model.apply)
    for t in range(r.obs.shape[0]):
        lg, _, c = step(variables, r.obs[t], c)
        c = reset_core(c, r.done[t])
        stepped.append(lg)
    np.testing.assert_allclose(jnp.stack(stepped), logits, atol=2e-4)
    rows_close([dict(x) for x in settle_core(c).layers], [dict(x) for x in core.layers])
    # the reference: no cache, the whole history
    T = r.obs.shape[0]
    view = reference.evaluate(variables, DIMS, history, 4)
    tokens = jnp.concatenate([history["history_obs"], r.bootstrap_obs[None]])
    done = jnp.concatenate([history["history_done"], jnp.zeros_like(r.done[:1])])
    ref_logits, ref_values = reference.forward(variables, DIMS, tokens, done)
    np.testing.assert_allclose(logits, ref_logits[-T - 1:-1], atol=2e-4)
    np.testing.assert_allclose(values, ref_values[-T - 1:-1], atol=2e-4)
    np.testing.assert_allclose(values, view["values"], atol=2e-4)
    rows_close([dict(x) for x in r.init_core.layers], view["core_before"])
    rows_close([dict(x) for x in core.layers], view["core"])
    assert float(aux["indexer_kl"]) == pytest.approx(float(view["indexer_kl"]), rel=1e-4)
    for name in ("dsa_rows_scored", "dsa_rows_selected", "dsa_pruned_share"):
        assert float(aux[name]) == pytest.approx(float(view[name]), rel=1e-6), name
    # each env's blocks ran over its cached rows rounded up to a rung (an
    # eighth, a quarter, a half or all of the 32) and the fragment's own
    held = np.stack([np.asarray(layer["len"]) for layer in r.init_core.layers])
    rung = np.select([held <= 4, held <= 8, held <= 16], [4, 8, 16], 32)
    assert float(aux["dsa_rows_computed"]) == pytest.approx(float(np.mean(rung + T)))
    assert float(aux["dsa_rows_scored"]) < float(aux["dsa_rows_computed"]) <= (
        TINY.max_positions + T)
    assert float(aux["dsa_pruned_share"]) > 0.25
    assert float(aux["dsa_rows_selected"]) <= TINY.index_top_k < float(aux["dsa_rows_scored"])
    # the rollout's log-prob is the learner's recompute
    logp = model.apply(variables, r.obs, r.done, r.init_core, r.actions,
                       method="fragment")[0]
    np.testing.assert_allclose(logp, r.behaviour_logp, atol=2e-5)
    np.testing.assert_allclose(logp, view["logp"], atol=2e-4)
    # bfloat16 throughout is far from it
    low = reference.evaluate(variables, DIMS, history, 4, low=True)
    assert float(jnp.max(jnp.abs(low["logp"] - view["logp"]))) > 2e-3


# (b) the selection against an exact top-k
def test_the_fragment_forms_selection_is_the_references(policy, fragments):
    _, model, variables = policy
    r = fragments[-1]
    chosen = model.apply(variables, r.obs, r.done, r.init_core, method="selected")
    assert [c.shape for c in chosen] == [
        (CFG.num_envs, CFG.unroll_len, TINY.max_positions + CFG.unroll_len)] * 2
    view = reference.evaluate(
        variables, DIMS, history_of(fragments), 4, program_chosen=chosen,
        keep_chosen=True)
    for s in view["selection"]:
        # in float32 the two agree but for near-ties: a row or two a query,
        # within a thousandth of the chosen scores' spread of the k-th
        assert float(s["size_differs"]) == 0
        assert float(s["extra_max"]) <= 2 and float(s["gap"]) <= 1e-3
    # a reference that keeps half the rows is seen
    wrong = reference.evaluate(
        variables, DIMS, history_of(fragments), 4, program_chosen=chosen, topk=4)
    assert all(float(s["size_differs"]) > 0 for s in wrong["selection"])


# (c) loss and every gradient leaf against the reference; the indexer's
# leaves receive L_I's gradient only and the main leaves none of it
def test_loss_and_gradients_match_the_reference_and_keep_apart(policy, fragments):
    _, model, variables = policy
    r = fragments[0]  # from empty caches: the history is the fragment
    history = history_of(fragments[:1])
    assert float(jnp.sum(r.done)) > 0

    def program(v):
        loss, metrics = learner_mod._algo_loss(CFG, model.apply, v, r)
        return loss, metrics

    def ref_loss(v):
        return reference.impala_loss(
            v, DIMS, history, CFG.gamma, CFG.value_coef, CFG.entropy_coef,
            CFG.vtrace_rho_clip, CFG.vtrace_c_clip, env_block=4)[0]

    (loss, metrics), grads = jax.jit(jax.value_and_grad(program, has_aux=True))(variables)
    ref, ref_grads = jax.jit(jax.value_and_grad(ref_loss))(variables)
    assert abs(float(loss) - float(ref)) <= 1e-4 * max(1, abs(float(ref)))
    assert float(metrics["indexer_kl"]) > 0
    assert seq_common.MODEL_LOSS not in metrics
    # the model's term is in the loss the learner differentiates
    impala = metrics["pg_loss"] + CFG.value_coef * metrics["value_loss"] - (
        CFG.entropy_coef * metrics["entropy"])
    assert float(loss) == pytest.approx(float(impala + metrics["indexer_kl"]), rel=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_flatten_with_path(ref_grads)[0])
    for path, g in flat:
        want = ref_flat[path]
        scale = float(jnp.max(jnp.abs(want)))
        assert scale > 0, path  # a gradient reaches every leaf
        np.testing.assert_allclose(
            g, want, atol=1e-3 * scale + 1e-6, err_msg=jax.tree_util.keystr(path))

    # apart: the gradient of L_I alone, and of the IMPALA loss alone
    def parts(v):
        logp, entropy, values, _, aux = model.apply(
            v, r.obs, r.done, r.init_core, r.actions, method="fragment")
        return aux[seq_common.MODEL_LOSS], jnp.sum(logp) + jnp.sum(values) + jnp.sum(entropy)

    of_kl = jax.grad(lambda v: parts(v)[0])(variables)
    of_rest = jax.grad(lambda v: parts(v)[1])(variables)
    for path, g in jax.tree_util.tree_flatten_with_path(of_kl)[0]:
        indexer = "'index'" in jax.tree_util.keystr(path)
        assert (float(jnp.max(jnp.abs(g))) > 0) == indexer, path
    for path, g in jax.tree_util.tree_flatten_with_path(of_rest)[0]:
        indexer = "'index'" in jax.tree_util.keystr(path)
        assert (float(jnp.max(jnp.abs(g))) > 0) != indexer, path
    # and the reference's gradient of the last layer's indexer is L_I's
    view = reference.evaluate(variables, DIMS, history, 4)
    last = of_kl["params"]["layer_1"]["dsa"]["index"]
    for name, g in view["indexer_gradient"].items():
        np.testing.assert_allclose(
            last[name], g, atol=1e-3 * float(jnp.max(jnp.abs(g))) + 1e-7, err_msg=name)


@pytest.mark.parametrize("how", [
    {"topk": 4}, {"relu": False}, {"drop_index_head": 1}, {"router": "sigmoid"},
    {"held": [0, 1, 2]},
])
def test_a_wrong_reference_is_far_from_the_program(policy, fragments, how):
    _, model, variables = policy
    r = fragments[-1]
    logp = model.apply(variables, r.obs, r.done, r.init_core, r.actions,
                       method="fragment")[0]
    history = history_of(fragments)
    right = reference.evaluate(variables, DIMS, history, 4)
    wrong = reference.evaluate(variables, DIMS, history, 4, **how)
    assert float(jnp.max(jnp.abs(logp - right["logp"]))) < 2e-4
    far = float(jnp.max(jnp.abs(logp - wrong["logp"])))
    kl = abs(float(wrong["indexer_kl"]) - float(right["indexer_kl"]))
    assert far > 2e-3 or kl > 1e-3 * float(right["indexer_kl"]), (far, kl)


# (d) the router
def test_the_softmax_router_is_the_references_and_the_sigmoid_one_is_as_it_was():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
    kernel = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    ids, weights = moe.route(x, kernel, None, 4, 1.0, 0.0, "softmax")
    g = jax.nn.softmax(x @ kernel, axis=-1)
    top, top_ids = jax.lax.top_k(g, 4)
    np.testing.assert_array_equal(ids, top_ids)
    np.testing.assert_allclose(weights, top / jnp.sum(top, -1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(weights, axis=-1), 1.0, rtol=1e-6)
    # the sigmoid side, with and without its bias, by the lines it had
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    s = jax.nn.sigmoid(jnp.matmul(x, kernel, precision=jax.lax.Precision.HIGHEST))
    for b in (bias, jnp.zeros_like(bias)):
        ids, weights = moe.route(x, kernel, b, 4, 2.5, 1e-6)
        _, want = jax.lax.top_k(s + b, 4)
        np.testing.assert_array_equal(ids, want)
        chosen = jnp.take_along_axis(s, want, axis=-1)
        np.testing.assert_array_equal(
            weights, 2.5 * chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-6))
    with pytest.raises(ValueError, match="router score"):
        moe.route(x, kernel, None, 4, 1.0, 0.0, "tanh")
    # the policies say which: the class attribute ``SeqPolicyBase`` passes on
    assert keye_moe.KeyePolicy.ROUTE_SCORE == "softmax"
    assert lfm2_moe.Lfm2Policy.ROUTE_SCORE == seq_common.SeqPolicyBase.ROUTE_SCORE == "sigmoid"


# (e) the shares add up: 8 chips' experts of one layer, attention counted once
@pytest.mark.parametrize("N, side", [(16, "dense"), (4096, "gathered")])
def test_the_eight_shares_sum_to_the_uncut_layer(N, side):
    E, k, D, F = 128, 8, 32, 16
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    w = lambda key, *dims: jax.random.normal(key, dims) * dims[-2] ** -0.5
    full = {"router": w(keys[0], D, E), "experts": {
        "gate": w(keys[1], E, D, F), "up": w(keys[2], E, D, F), "down": w(keys[3], E, F, D)}}
    x = jax.random.normal(jax.random.PRNGKey(8), (N, D))
    dims = {"held_experts": tuple(range(E)), "top_k": k, "routed_scale": 1.0}
    uncut = reference.expert_layer(full, x, dims)
    ids, weights = moe.route(x, full["router"], None, k, 1.0, 0.0, "softmax")
    layer = jax.jit(lambda *a: moe.held_experts(*a), static_argnums=(3, 4, 8))
    from asyncrl_tpu.obs import introspect
    before = introspect.process_record()["moe_sites"]
    total, loads = 0.0, []
    for first in range(0, E, 16):
        held = tuple(range(first, first + 16))
        share = {n: full["experts"][n][first:first + 16] for n in ("gate", "up", "down")}
        part, load, _ = layer(x, ids, weights, held, E, share["gate"], share["up"],
                              share["down"], jnp.float32)
        total = total + part
        loads.append(load)
        mine = reference.expert_layer(
            {**full, "experts": share}, x, {**dims, "held_experts": held})
        np.testing.assert_allclose(part, mine, atol=2e-4)
    after = introspect.process_record()["moe_sites"]
    assert {n for n in after if after[n] > before[n]} == {side}
    np.testing.assert_allclose(total, uncut, atol=2e-4)
    assert int(jnp.sum(jnp.concatenate(loads))) == N * k  # no token dropped


# (f) what build_model refuses, and the preset on the normal path
def test_the_policy_builds_within_its_cache_only():
    env = registry.make(CFG.env_id, CFG)
    too_long = CFG.replace(token_task=(64, 12, 33, 1, 2))
    with pytest.raises(ValueError, match="positions"):
        build_model(too_long, registry.make(CFG.env_id, too_long).spec)
    with pytest.raises(ValueError, match="unknown seq_model.*keye_moe_4l"):
        build_model(CFG.replace(seq_model="no_such"), env.spec)
    full = presets.get("keye_moe_rl")
    shape = keye_moe.SHAPES[full.seq_model]
    assert full.token_task == (18992, 2048, 8192, 32, 128)
    assert (full.num_envs, full.unroll_len, full.actor_staleness) == (16, 512, 2)
    assert shape.max_positions == 8192 > shape.index_top_k == 2048
    assert shape.vocab == 18992 and len(shape.held_experts) == 16


def test_the_preset_trains_on_the_anakin_path_and_the_indexer_learns():
    agent = make_agent(CFG.replace(num_envs=2 * len(jax.devices())))
    try:
        assert type(agent).__name__ == "Trainer"
        state = agent.state
        first = jax.device_get(state.params)
        seen = []
        for _ in range(4):
            state, metrics = agent.learner.update(state)
            seen.append({k: float(np.ravel(v)[0]) for k, v in metrics.items()})
        assert all(np.isfinite(m["loss"]) for m in seen)
        assert seq_common.MODEL_LOSS not in seen[-1]
        assert all(m["indexer_kl"] > 0 for m in seen)
        # from the third update on the caches are longer than top-k
        assert seen[-1]["dsa_pruned_share"] > 0.25
        assert seen[-1]["dsa_rows_selected"] <= TINY.index_top_k < seen[-1]["dsa_rows_scored"]
        assert 0.3 < seen[-1]["moe_local_frac"] < 0.7
        assert seen[-1]["episode_resets"] > 0
        moved = jax.tree.map(
            lambda a, b: float(jnp.sum(jnp.abs(a - b))), first,
            jax.device_get(state.params))
        index = moved["params"]["layer_1"]["dsa"]["index"]
        assert all(v > 0 for v in index.values()), index  # only L_I moves these
        assert all(v > 0 for v in jax.tree.leaves(moved))
        assert int(state.update_step) == 4
    finally:
        agent.close()


# (g) what a profile of the step reads
def test_the_step_names_the_scopes_a_profile_reads():
    cfg = CFG.replace(num_envs=2 * len(jax.devices()), fused_scan="interpret")
    agent = make_agent(cfg)
    try:
        text = agent.learner._step.lower(agent.state).compile().as_text()
    finally:
        agent.close()
    names = re.findall(r'op_name="([^"]+)"', text)
    components = {c for name in names for c in name.split("/")}
    for scope in ("rollout", "loss_and_grad", "actor_forward", "gqa", "dsa_index",
                  "dsa_select", "dsa_attend", "moe", "moe_router", "moe_experts",
                  "lm_head", "core_reset"):
        assert scope in components, scope
    assert not components & {"kda", "conv_mixer", "mla", "gqa_step"}

    def some(*parts):
        return any(all(p in n for p in parts) for n in names)

    for scope in ("/dsa_index/", "/dsa_select/", "/dsa_attend/"):
        # inside ``gqa``, in the rollout and in the learner
        assert some("/rollout/", "/actor_forward/", "/gqa/", scope), scope
        assert some("/loss_and_grad/", "gqa", scope), scope
    # the backward pass keeps the scopes
    assert some("/loss_and_grad/", "transpose(", "/dsa_index/")
    assert some("/loss_and_grad/", "transpose(", "/dsa_attend/")
