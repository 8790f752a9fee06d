"""The Granite 4.0-H sequence policy (models/granite_h.py: Mamba-2 mixers on
ops/ssd.py, one NoPE grouped-query attention layer, a dense SwiGLU in every
layer, muP multipliers and a tied head) against its plain reference
(benchmarks/reference/granite_h.py: no cache, the recurrence one token at a
time over each env's whole history), on seeded random weights at the tiny
preset's sizes (episodes of 12-32 tokens over fragments of 16 in chunks of
8, so the state outlives fragments and episodes end inside chunks), in
float32; and the other sequence policies' programs as they were."""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncrl_tpu import make_agent
from asyncrl_tpu.configs import presets
from asyncrl_tpu.envs import registry
from asyncrl_tpu.learn import learner as learner_mod
from asyncrl_tpu.models import granite_h, seq_common
from asyncrl_tpu.models.networks import build_model, reset_core, settle_core
from asyncrl_tpu.obs import introspect
from asyncrl_tpu.ops import distributions, ssd
from asyncrl_tpu.rollout.anakin import actor_init, unroll
from benchmarks.reference import granite_h as reference

TINY = granite_h.SHAPES["granite_h_tiny"]
DIMS = dataclasses.asdict(TINY)
CFG = presets.get("granite_h_tiny").replace(precision="f32", fused_scan="lax")


@pytest.fixture(scope="module")
def policy():
    env = registry.make(CFG.env_id, CFG)
    model = build_model(CFG, env.spec)
    assert isinstance(model, granite_h.GraniteHPolicy)
    return env, model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def fragments(policy):
    """Three consecutive fragments of the program's own rollout from an
    empty carry: 48 steps, longer than any episode, so every env crosses a
    boundary, inside a fragment and across one."""
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
    flag since the carry was empty."""
    r = fragments[-1]
    return {
        "history_obs": jnp.concatenate([f.obs for f in fragments]),
        "history_done": jnp.concatenate([f.done for f in fragments]),
        "bootstrap_obs": r.bootstrap_obs, "actions": r.actions,
        "behaviour_logp": r.behaviour_logp, "rewards": r.rewards, "done": r.done,
    }


def as_carry(core):
    return [dict(layer) for layer in core.layers]


def test_the_carry_is_a_state_a_mamba_layer_and_a_cache_the_attention_layer(policy):
    _, model, _ = policy
    core = model.initial_core(3)
    assert [sorted(layer) for layer in core.layers] == [
        ["S", "conv", "fresh"], ["k", "len", "v"], ["S", "conv", "fresh"]]
    assert core.layers[0]["S"].shape == (3, TINY.mamba_heads, TINY.mamba_head_dim,
                                         TINY.mamba_state)
    assert core.layers[0]["conv"].shape == (
        3, 3, TINY.mamba_heads * TINY.mamba_head_dim + 2 * TINY.mamba_state)
    full = seq_common.SeqCore(tuple(
        {**layer, "fresh": jnp.zeros(3, bool)} if "fresh" in layer else layer
        for layer in jax.tree.map(jnp.ones_like, core).layers))
    after = reset_core(full, jnp.asarray([False, True, False]))
    # a reset zeroes the conv tail and the length, and leaves the state to
    # its next read (``fresh``)
    np.testing.assert_array_equal(after.layers[0]["fresh"], [False, True, False])
    np.testing.assert_array_equal(after.layers[1]["len"], [1, 0, 1])
    assert bool(jnp.all(after.layers[0]["S"] == 1))
    np.testing.assert_array_equal(jnp.sum(after.layers[2]["conv"], axis=(1, 2)) > 0,
                                  [True, False, True])
    settled = settle_core(after)
    np.testing.assert_array_equal(jnp.sum(settled.layers[0]["S"], axis=(1, 2, 3)) > 0,
                                  [True, False, True])
    assert not bool(jnp.any(settled.layers[0]["fresh"]))


# (a) one token through the carry = the fragment form = the reference's
# whole-history forward, over a fragment boundary and resets inside chunks
def test_step_form_fragment_form_and_reference_agree(policy, fragments):
    _, model, variables = policy
    history = history_of(fragments)
    r = fragments[-1]
    T = r.obs.shape[0]
    assert int(jnp.min(jnp.sum(history["history_done"], axis=0))) >= 1
    assert int(jnp.max(r.init_core.layers[1]["len"])) > 0
    assert float(jnp.sum(r.done[:-1])) > 0
    logits, values, core, aux = model.apply(
        variables, r.obs, r.done, r.init_core, method="fragment")
    c, stepped = r.init_core, []
    step = jax.jit(model.apply)
    for t in range(T):
        lg, _, c = step(variables, r.obs[t], c)
        c = reset_core(c, r.done[t])
        stepped.append(lg)
    np.testing.assert_allclose(jnp.stack(stepped), logits, atol=2e-5)
    stepped_carry = as_carry(settle_core(c))
    for kind, a, b in zip(TINY.layers, stepped_carry, as_carry(core)):
        if kind.startswith("mamba"):
            np.testing.assert_allclose(a["S"], b["S"], atol=2e-5)
            np.testing.assert_allclose(a["conv"], b["conv"], atol=2e-5)
        else:
            np.testing.assert_array_equal(a["len"], b["len"])
    # the reference: no cache, the whole history, one token at a time
    tokens = jnp.concatenate([history["history_obs"], r.bootstrap_obs[None]])
    done = jnp.concatenate([history["history_done"], jnp.zeros_like(r.done[:1])])
    ref_logits, ref_values = reference.forward(variables, DIMS, tokens, done)
    np.testing.assert_allclose(logits, ref_logits[-T - 1:-1], atol=2e-5)
    np.testing.assert_allclose(values, ref_values[-T - 1:-1], atol=2e-5)
    # the carries, before the fragment and after it, rebuilt by the reference
    view = reference.evaluate(variables, DIMS, history, 4)
    for mine, theirs in ((as_carry(r.init_core), view["core_before"]),
                         (as_carry(core), view["core"])):
        gaps = reference.carry_gap(mine, theirs, DIMS)
        for k in ("S", "conv", "rows"):
            assert float(jnp.max(gaps[k])) < 1e-5, (k, gaps[k])
        assert int(jnp.sum(gaps["len"])) == 0
    assert float(aux["gqa_rows_attended"]) == pytest.approx(
        float(view["gqa_rows_attended"]), rel=1e-6)
    assert float(aux["ssd_chunk_resets"]) == pytest.approx(
        float(view["ssd_chunk_resets"]), rel=1e-6)
    assert 0 < float(aux["ssd_chunk_resets"]) < 1
    # and bfloat16 throughout is far from it
    low, _ = reference.forward(variables, DIMS, tokens, done, low=True)
    assert float(jnp.max(jnp.abs(low[-T - 1:-1] - logits))) > 2e-3


# (b) the learner's log-probs are the rollout's, and the reference's
def test_the_fragment_forms_log_probs_are_the_behaviour_log_probs(policy, fragments):
    _, model, variables = policy
    for r in fragments:
        logp, entropy, values, _, _ = model.apply(
            variables, r.obs, r.done, r.init_core, r.actions, method="fragment")
        np.testing.assert_allclose(logp, r.behaviour_logp, atol=2e-5)
        assert bool(jnp.all(entropy > 0))
    view = reference.evaluate(variables, DIMS, history_of(fragments), 4)
    np.testing.assert_allclose(view["logp"], fragments[-1].behaviour_logp, atol=2e-5)


# (c) the chunked scan is the recurrence looped, with boundaries inside
# chunks, at chunk ends and across fragments, and so is its gradient
@pytest.mark.parametrize("chunk", [8, 5, 64])
def test_ssd_chunk_is_ssd_step_looped(chunk):
    T, b, H, P, N = 24, 3, 2, 4, 8
    k = iter(jax.random.split(jax.random.PRNGKey(4), 8))
    u = jax.random.normal(next(k), (T, b, H, P))
    delta = jax.nn.softplus(jax.random.normal(next(k), (T, b, H)))
    log_a = -jnp.exp(jax.random.normal(next(k), (T, b, H))) * delta
    B, C = (jax.random.normal(next(k), (T, b, N)) for _ in range(2))
    S0 = jax.random.normal(next(k), (b, H, P, N))
    done = jnp.zeros((T, b), bool).at[3, 0].set(True).at[7, 1].set(True) \
        .at[15, 1].set(True).at[20, 2].set(True).at[23, 0].set(True)

    def looped(S0, u, delta, log_a, B, C):
        S, ys = S0, []
        for t in range(T):
            S, y = ssd.ssd_step(S, u[t], delta[t], log_a[t], B[t], C[t])
            ys.append(y)
            S = jnp.where(done[t][:, None, None, None], 0.0, S)
        return S, jnp.stack(ys)

    chunked = lambda *a: ssd.ssd_chunk(*a, done, chunk=chunk)
    S_ref, y_ref = looped(S0, u, delta, log_a, B, C)
    S, y = chunked(S0, u, delta, log_a, B, C)
    np.testing.assert_allclose(y, y_ref, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(S, S_ref, atol=2e-5, rtol=1e-5)
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)[1])) + jnp.sum(f(*a)[0] ** 2)
    g, g_ref = (jax.grad(loss(f), argnums=(0, 1, 2, 3, 4, 5))(S0, u, delta, log_a, B, C)
                for f in (chunked, looped))
    for a, r in zip(g, g_ref):
        np.testing.assert_allclose(a, r, atol=1e-4, rtol=1e-4)
    inside, chunks = ssd.chunk_boundaries(done, chunk)
    n = min(chunk, T)
    expect = sum(1 for t, e in ((3, 0), (7, 1), (15, 1), (20, 2), (23, 0))
                 if t % n != n - 1 and t != T - 1)
    assert (float(inside), float(chunks)) == (expect, b * -(-T // n))


# (d) the loss and every leaf's gradient, the tied embedding's among them,
# over a first fragment (from an empty carry, so the reference's history is
# the fragment and nothing before it is a constant of one side only); and
# over a later one the gradient of the leaves a plain reference can afford:
# those after the last layer and the last Mamba layer's own
def test_loss_and_gradients_match_the_reference(policy, fragments):
    _, model, variables = policy
    loss_of = lambda r: jax.jit(jax.value_and_grad(
        lambda v: learner_mod._algo_loss(CFG, model.apply, v, r), has_aux=True))
    first = history_of(fragments[:1])
    (loss, _), grads = loss_of(fragments[0])(variables)

    def ref_loss(v):
        view = reference.evaluate(v, DIMS, first, 4)
        t = reference._loss_terms(first, view, CFG.gamma, CFG.vtrace_rho_clip,
                                  CFG.vtrace_c_clip)
        return t["pg_loss"] + CFG.value_coef * t["value_loss"] - CFG.entropy_coef * t["entropy"]

    ref, ref_grads = jax.jit(jax.value_and_grad(ref_loss))(variables)
    assert abs(float(loss) - float(ref)) <= 1e-4 * max(1, abs(float(ref)))
    mine = dict(jax.tree_util.tree_flatten_with_path(grads)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(ref_grads)[0]:
        scale = float(jnp.max(jnp.abs(g)))
        assert scale > 0, path
        np.testing.assert_allclose(mine[path], g, atol=2e-3 * scale,
                                   err_msg=jax.tree_util.keystr(path))
    # the head is the embedding: its gradient is the head's and the input's
    assert "head" not in variables["params"]

    r = fragments[-1]
    history = history_of(fragments)
    (loss, metrics), grads = loss_of(r)(variables)
    ref_loss, view = reference.impala_loss(
        variables, DIMS, history, CFG.gamma, CFG.value_coef, CFG.entropy_coef,
        CFG.vtrace_rho_clip, CFG.vtrace_c_clip, env_block=4)
    assert abs(float(loss) - float(ref_loss)) <= 1e-4 * max(1, abs(float(ref_loss)))
    for k in ("value_loss", "entropy", "pg_loss"):
        assert float(metrics[k]) == pytest.approx(float(view[k]), rel=1e-4, abs=1e-6), k
    tail = reference.tail_gradient(variables, DIMS, history, view, CFG.value_coef,
                                   CFG.entropy_coef, env_block=4)
    last = f"layer_{reference.last_mamba(DIMS)}"
    got = {**{k: grads["params"][k] for k in reference.TAIL},
           "ssd": {k: grads["params"][last]["mamba"][k] for k in reference.SSD_LEAVES}}
    theirs = {**tail, "ssd": view["ssd_gradient"]}
    mine = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(theirs)[0]:
        scale = float(jnp.max(jnp.abs(g)))
        assert scale > 0, path
        np.testing.assert_allclose(mine[path], g, atol=1e-3 * scale,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("how", [
    {"state_low": True},
    {"dt_bias": False},
    {"residual": 1.0},
    {"conv_bias": False},
])
def test_a_wrong_reference_is_far_from_the_program(policy, fragments, how):
    _, model, variables = policy
    r = fragments[-1]
    logits = model.apply(variables, r.obs, r.done, r.init_core, method="fragment")[0]
    history = history_of(fragments)
    tokens = jnp.concatenate([history["history_obs"], r.bootstrap_obs[None]])
    done = jnp.concatenate([history["history_done"], jnp.zeros_like(r.done[:1])])
    T = r.obs.shape[0]
    wrong, _ = reference.forward(variables, DIMS, tokens, done, **how)
    # the logits, or the state the carry holds (a state kept in bfloat16
    # loses what a slow head adds, which the logits of 48 tokens hardly see):
    # the program reads 1e-5 of either from the right reference
    view = reference.evaluate(variables, DIMS, history, 4, **how)
    gaps = reference.carry_gap(as_carry(r.init_core), view["core_before"], DIMS)
    far = max(float(jnp.max(jnp.abs(wrong[-T - 1:-1] - logits))), float(jnp.max(gaps["S"])))
    assert far > 1e-3, far


# (e) the preset on the normal path, and what build_model refuses
def test_the_preset_trains_on_the_anakin_path(policy):
    env, _, _ = policy
    too_long = CFG.replace(token_task=(64, 12, 33, 1, 2))
    with pytest.raises(ValueError, match="positions"):
        build_model(too_long, registry.make(CFG.env_id, too_long).spec)
    with pytest.raises(ValueError, match="unknown seq_model.*granite_h_10l"):
        build_model(CFG.replace(seq_model="no_such"), env.spec)
    full = presets.get("granite_h_rl")
    assert full.token_task == (12544, 128, 2048, 16, 64)
    assert (full.num_envs, full.unroll_len, full.actor_staleness) == (16, 256, 2)
    assert (full.optimizer, full.donate_buffers) == ("rmsprop", True)
    assert granite_h.SHAPES[full.seq_model].max_positions == 2048
    agent = make_agent(CFG.replace(num_envs=2 * len(jax.devices())))
    try:
        assert type(agent).__name__ == "Trainer"
        state = agent.state
        first = jax.device_get(state.params)
        resets = 0.0
        for _ in range(3):
            state, metrics = agent.learner.update(state)
            m = {k: float(np.ravel(v)[0]) for k, v in metrics.items()}
            resets += m["episode_resets"]
        assert np.isfinite(m["loss"]) and resets > 0
        assert not {k for k in m if k.startswith("moe_")}  # no expert layer
        assert 1 <= m["gqa_rows_attended"] <= TINY.max_positions
        assert 0 <= m["ssd_chunk_resets"] <= 1
        moved = jax.tree.map(lambda a, b: float(jnp.sum(jnp.abs(a - b))),
                             first, jax.device_get(state.params))
        for path, v in jax.tree_util.tree_flatten_with_path(moved)[0]:
            assert v > 0, path
    finally:
        agent.close()


# (f) what a profile of the step reads, and the sites it counts
def test_the_step_names_the_scopes_a_profile_reads():
    agent = make_agent(CFG.replace(num_envs=2 * len(jax.devices())))
    before = introspect.process_record()["ssd_sites"]
    try:
        text = agent.learner._step.lower(agent.state).compile().as_text()
    finally:
        agent.close()
    now = introspect.process_record()["ssd_sites"]
    counted = {k: now[k] - before[k] for k in now}
    # two Mamba layers: the rollout's and the bootstrap token's one-token
    # sites; the learner's chunked sites, forward and rematerialised
    assert counted["step"] >= 4 and counted["chunk"] >= 2, counted
    names = re.findall(r'op_name="([^"]+)"', text)

    def some(*parts):
        return any(all(p in n for p in parts) for n in names)

    assert some("/rollout/", "/actor_forward/", "/mamba/", "/ssd_step/")
    assert some("/loss_and_grad/", "/mamba/", "/ssd_chunk/")
    assert some("/loss_and_grad/", "transpose(", "/ssd_chunk/")
    assert some("/rollout/", "/gqa/") and some("/loss_and_grad/", "/gqa/")
    components = {c for name in names for c in name.split("/")}
    assert not components & {"kda", "conv_mixer", "mla", "moe", "dsa_index"}


# (g) the trunk's multipliers, the tied head and the skipped expert counters
# left the other sequence policies' programs as they were: the lowered text
# of each one's two forms at its tiny preset, before this model was added;
# the fragments' since the fragment form counts ``moe_step_idle_share``
# (2 tokens a step choosing 2 of 8 experts engage ``ops/moe.py skips_idle``)
BEFORE = {
    "kimi_linear_tiny.step": "104b034a2d989a2e09b2a0a4c1caf64369b1fdbd82851cfa79711d312e5ab1c8",
    "kimi_linear_tiny.fragment": "f1b754323cc134c6ed337a86568eb8ea51be8dba444570008814dfe2d05679ef",
    "lfm2_moe_tiny.step": "5789b23e8401d8aef106c5925749ef7872863c01788b0816246bb716468c3172",
    "lfm2_moe_tiny.fragment": "2d20da6defdc3a3469c57df9ce690b4c63c88afc06f01354484320989c6c6009",
    "keye_moe_tiny.step": "1daf3b4dca5f70d43ee511e31c389753c80eca6c54701893b378268d4ad75f81",
    "keye_moe_tiny.fragment": "bb23a75a39650a3d0d7d76a7c1797fb53c55304dafe75aa5e3527d2ebc0a634d",
    "moonlight_tiny.step": "36571948f922d327f34b76535f734797be08ed9e85ed5ead505dd47f5e3b3e53",
    "moonlight_tiny.fragment": "751934849243ae899294e5ca055d397255f5130f1c42cf53948df3219017f087",
}


def lowered_texts(preset):
    cfg = presets.get(preset)
    model = build_model(cfg, registry.make(cfg.env_id, cfg).spec)
    B, T = 2, cfg.unroll_len
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    core = jax.eval_shape(lambda: model.initial_core(B))
    ints = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    step = jax.jit(model.apply).lower(variables, ints(B), core).as_text()
    frag = jax.jit(lambda v, o, d, c, a: model.apply(v, o, d, c, a, method="fragment")).lower(
        variables, ints(T, B), jax.ShapeDtypeStruct((T, B), bool), core, ints(T, B)).as_text()
    return {"step": step, "fragment": frag}


@pytest.mark.parametrize("preset", [
    "kimi_linear_tiny", "lfm2_moe_tiny", "keye_moe_tiny", "moonlight_tiny"])
def test_the_other_policies_lower_to_the_programs_they_had(preset):
    for form, text in lowered_texts(preset).items():
        assert hashlib.sha256(text.encode()).hexdigest() == BEFORE[f"{preset}.{form}"], form


def test_the_attention_multiplier_folds_into_the_queries_exactly():
    """``ops/gqa.py`` divides its scores by sqrt(dh): a multiplier of 1/64
    at dh = 64 is the queries times 1/8, a power of two, exact in float."""
    shape = granite_h.SHAPES["granite_h_10l"]
    scale = shape.attention_multiplier * shape.head_dim ** 0.5
    assert scale == 0.125
    D = 8
    p = {n: jax.random.normal(jax.random.PRNGKey(i), (D, w))
         for i, (n, w) in enumerate((("q", 32 * 64), ("k", 8 * 64), ("v", 8 * 64)))}
    x = jax.random.normal(jax.random.PRNGKey(9), (3, D))
    q, k, v = seq_common._gqa_project(p, x, jnp.zeros(3, jnp.int32), shape, jnp.float32)
    plain = (x @ p["q"]).reshape(3, 32, 64)
    np.testing.assert_array_equal(q, jax.jit(lambda a: a * 0.125)(
        seq_common._dot(x, p["q"], jnp.float32).reshape(3, 32, 64)))
    np.testing.assert_allclose(q * 8.0 / 64.0, plain / 64.0, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(k, seq_common._dot(x, p["k"], jnp.float32))
