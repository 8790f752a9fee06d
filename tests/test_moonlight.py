"""The Moonlight sequence policy (models/moonlight.py on models/mla.py's latent
attention with decoupled RoPE, the sigmoid side of ops/moe.py with two shared
experts in one SwiGLU) against its plain reference
(benchmarks/reference/moonlight.py: no cache, the non-absorbed form, the
published rotation), on seeded random weights at the tiny preset's sizes
(episodes of 12-32 tokens over fragments of 16, so caches outlive fragments
and positions restart inside them), in float32."""

import dataclasses
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncrl_tpu import make_agent
from asyncrl_tpu.configs import presets
from asyncrl_tpu.envs import registry
from asyncrl_tpu.learn import learner as learner_mod
from asyncrl_tpu.models import mla, moonlight, seq_common
from asyncrl_tpu.models.networks import build_model, reset_core, settle_core
from asyncrl_tpu.obs import introspect
from asyncrl_tpu.ops import distributions, moe
from asyncrl_tpu.rollout.anakin import actor_init, unroll
from benchmarks.reference import moonlight as reference

TINY = moonlight.SHAPES["moonlight_tiny"]
DIMS = dataclasses.asdict(TINY)
CFG = presets.get("moonlight_tiny").replace(precision="f32", fused_scan="lax")


@pytest.fixture(scope="module")
def policy():
    env = registry.make(CFG.env_id, CFG)
    model = build_model(CFG, env.spec)
    assert isinstance(model, moonlight.MoonlightPolicy)
    return env, model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def fragments(policy):
    """Three consecutive fragments of the program's own rollout from empty
    caches: 48 steps, longer than any episode, so every env crosses a
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
    flag since the caches were empty."""
    r = fragments[-1]
    return {
        "history_obs": jnp.concatenate([f.obs for f in fragments]),
        "history_done": jnp.concatenate([f.done for f in fragments]),
        "bootstrap_obs": r.bootstrap_obs, "actions": r.actions,
        "behaviour_logp": r.behaviour_logp, "rewards": r.rewards, "done": r.done,
    }


def as_carry(core):
    return [dict(layer) for layer in core.layers]


def test_the_carry_is_a_latent_cache_a_layer(policy):
    _, model, _ = policy
    core = model.initial_core(3)
    assert [sorted(layer) for layer in core.layers] == [["kv", "len"]] * 3
    assert core.layers[0]["kv"].shape == (3, TINY.max_positions, TINY.kv_lora + TINY.qk_rope)
    full = jax.tree.map(jnp.ones_like, core)
    after = reset_core(full, jnp.asarray([False, True, False]))
    np.testing.assert_array_equal(after.layers[2]["len"], [1, 0, 1])
    assert bool(jnp.all(after.layers[2]["kv"] == 1))  # the length empties it
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool(jnp.all(a == b)), settle_core(after), after))


# (a) one token through the cache = the fragment form = the reference's full
# forward, over a fragment boundary and episode resets inside fragments
def test_step_form_fragment_form_and_reference_agree(policy, fragments):
    _, model, variables = policy
    history = history_of(fragments)
    r = fragments[-1]
    T = r.obs.shape[0]
    # the fragment starts from caches the episodes before it left, and
    # episodes end inside it
    assert int(jnp.min(jnp.sum(history["history_done"], axis=0))) >= 1
    assert int(jnp.max(r.init_core.layers[0]["len"])) > 0
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
    for a, b in zip(as_carry(settle_core(c)), as_carry(core)):
        np.testing.assert_array_equal(a["len"], b["len"])
        live = (np.arange(a["kv"].shape[1])[None] < np.asarray(b["len"])[:, None])[..., None]
        np.testing.assert_allclose(np.where(live, a["kv"], 0), np.where(live, b["kv"], 0),
                                   atol=2e-5)
    # the reference: no cache, the whole history, the published rotation
    pub = reference.published(variables, DIMS)
    tokens = jnp.concatenate([history["history_obs"], r.bootstrap_obs[None]])
    done = jnp.concatenate([history["history_done"], jnp.zeros_like(r.done[:1])])
    ref_logits, ref_values = reference.forward(pub, DIMS, tokens, done)
    np.testing.assert_allclose(logits, ref_logits[-T - 1:-1], atol=2e-5)
    np.testing.assert_allclose(values, ref_values[-T - 1:-1], atol=2e-5)
    # the caches, before the fragment and after it, rebuilt from the rows
    view = reference.evaluate(pub, DIMS, history, 4, carries={
        "before": as_carry(r.init_core), "after": as_carry(core)})
    for when in ("before", "after"):
        gaps = view["carry_gaps"][when]
        assert float(jnp.max(gaps["rows"])) < 1e-5 and int(jnp.sum(gaps["len"])) == 0
    assert float(aux["mla_rows_attended"]) == pytest.approx(
        float(view["mla_rows_attended"]), rel=1e-6)
    assert float(aux["mla_rows_cached"]) == pytest.approx(
        float(view["mla_rows_cached"]), rel=1e-6)
    # every env's block up-projected its cache's whole capacity and the fragment
    assert float(aux["mla_rows_expanded"]) == TINY.max_positions + T
    assert float(aux["mla_rows_cached"]) + 1 <= float(aux["mla_rows_attended"]) < (
        float(aux["mla_rows_cached"]) + T)
    # the columns as the program orders them are not the published rotation's
    unpermuted, _ = reference.forward(variables, DIMS, tokens, done)
    assert float(jnp.max(jnp.abs(unpermuted[-T - 1:-1] - logits))) > 1e-2
    # and bfloat16 throughout is far from it
    low, _ = reference.forward(pub, DIMS, tokens, done, low=True)
    assert float(jnp.max(jnp.abs(low[-T - 1:-1] - logits))) > 2e-3


# (b) the rotation's two pairings are one function under a fixed permutation
@pytest.mark.parametrize("theta", [50000.0, 1e4])
def test_the_rope_pairings_agree_under_the_permutation(theta):
    d = 64
    x = jax.random.normal(jax.random.PRNGKey(3), (5, 7, 3, d))
    order = reference._half_order(d)
    np.testing.assert_array_equal(order[:4], [0, 2, 4, 6])
    np.testing.assert_array_equal(order[32:36], [1, 3, 5, 7])
    # the program's rotate-half over the published columns in the half
    # order; at thousands of radians the two sides' float32 frequencies
    # (theta^(-i/32) against 1 / theta^(2i/64)) part by an ulp of the angle
    for high, atol in ((64, 2e-5), (8192, 4e-3)):
        pos = jax.random.randint(jax.random.PRNGKey(high), (5, 7), 0, high)
        np.testing.assert_allclose(
            seq_common._rotate(x[..., order], pos, theta),
            reference.rope_published(x, pos, theta), atol=atol)
    published = reference.rope_published(x, pos, theta)
    # pair i of the published rotation is (x[2i], x[2i+1]) at theta^(-2i/d)
    i, p = 5, pos[2, 3]
    angle = float(p) * theta ** (-2 * i / d)
    a, b = x[2, 3, 1, 2 * i], x[2, 3, 1, 2 * i + 1]
    got = published[2, 3, 1]
    np.testing.assert_allclose(
        [got[i], got[i + d // 2]],
        [a * np.cos(angle) - b * np.sin(angle), b * np.cos(angle) + a * np.sin(angle)],
        atol=2e-3)  # an angle of thousands of radians in float32
    # and the parameters' columns go there and back
    variables = {"params": {"layer_0": {"mla": {
        "q": jax.random.normal(jax.random.PRNGKey(5), (4, 2 * (3 + d))),
        "kv_a": jax.random.normal(jax.random.PRNGKey(6), (4, 6 + d))}}}}
    dims = {"hidden": 4, "mla_heads": 2, "qk_nope": 3, "qk_rope": d, "kv_lora": 6,
            "layers": ["mla+dense"]}
    cols = reference.rope_columns(variables["params"]["layer_0"]["mla"], dims)
    back = reference.rope_columns(
        reference.published(variables, dims)["params"]["layer_0"]["mla"], dims)
    for k, v in reference.program_order(back, dims).items():
        np.testing.assert_array_equal(v, cols[k])


# (c) the loss, and the gradients a plain reference can afford: the leaves
# after the last layer and the last layer's rope columns
def test_loss_and_the_last_layers_gradients_match_the_reference(policy, fragments):
    _, model, variables = policy
    r = fragments[-1]
    history = history_of(fragments)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda v: learner_mod._algo_loss(CFG, model.apply, v, r), has_aux=True))(
            variables)
    pub = reference.published(variables, DIMS)
    ref_loss, view = reference.impala_loss(
        pub, DIMS, history, CFG.gamma, CFG.value_coef, CFG.entropy_coef,
        CFG.vtrace_rho_clip, CFG.vtrace_c_clip, env_block=4)
    assert abs(float(loss) - float(ref_loss)) <= 1e-4 * max(1, abs(float(ref_loss)))
    for k in ("value_loss", "entropy", "pg_loss"):
        assert float(metrics[k]) == pytest.approx(float(view[k]), rel=1e-4, abs=1e-6), k
    tail = reference.tail_gradient(pub, DIMS, history, view, CFG.value_coef,
                                   CFG.entropy_coef, env_block=4)
    last = f"layer_{len(TINY.layers) - 1}"
    mine = {**{k: grads["params"][k] for k in reference.TAIL},
            "rope": reference.rope_columns(grads["params"][last]["mla"], DIMS)}
    theirs = {**tail, "rope": reference.program_order(view["rope_gradient"], DIMS)}
    for path, g in jax.tree_util.tree_flatten_with_path(theirs)[0]:
        got = dict(jax.tree_util.tree_flatten_with_path(mine)[0])[path]
        scale = float(jnp.max(jnp.abs(g)))
        assert scale > 0, path
        np.testing.assert_allclose(got, g, atol=1e-3 * scale,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("how", [
    {"rope": False},  # Kimi-Linear's NoPE
    {"theta": 1e4},
    {"shared": 1},  # one shared expert of 1,408 where there are two
    {"held": [0, 1, 2]},
])
def test_a_wrong_reference_is_far_from_the_program(policy, fragments, how):
    _, model, variables = policy
    r = fragments[-1]
    logits = model.apply(variables, r.obs, r.done, r.init_core, method="fragment")[0]
    history = history_of(fragments)
    tokens = jnp.concatenate([history["history_obs"], r.bootstrap_obs[None]])
    done = jnp.concatenate([history["history_done"], jnp.zeros_like(r.done[:1])])
    T = r.obs.shape[0]
    pub = reference.published(variables, DIMS)
    wrong, _ = reference.forward(pub, DIMS, tokens, done, **how)
    assert float(jnp.max(jnp.abs(wrong[-T - 1:-1] - logits))) > 1e-2


# (d) the shares add up: 8 chips' experts of one layer at the published
# routing (6 of 64, sigmoid + correction bias, renormalised and scaled), the
# shared experts counted once
@pytest.mark.parametrize("N, side", [(12, "dense"), (4096, "gathered")])
def test_the_eight_shares_and_the_shared_experts_once_sum_to_the_uncut_layer(N, side):
    E, k, D, F = 64, 6, 32, 16
    keys = jax.random.split(jax.random.PRNGKey(9), 8)
    w = lambda key, *dims: jax.random.normal(key, dims) * dims[-2] ** -0.5
    full = {"router": w(keys[0], D, E),
            "router_bias": 0.02 * jax.random.normal(keys[1], (E,)),
            "experts": {"gate": w(keys[2], E, D, F), "up": w(keys[3], E, D, F),
                        "down": w(keys[4], E, F, D)},
            "shared": {"gate": w(keys[5], D, 2 * F), "up": w(keys[6], D, 2 * F),
                       "down": w(keys[7], 2 * F, D)}}
    x = jax.random.normal(jax.random.PRNGKey(10), (N, D))
    dims = {"held_experts": tuple(range(E)), "top_k": k, "routed_scale": 2.446,
            "shared_ffn": 2 * F, "expert_ffn": F}
    uncut = reference.expert_layer(full, x, dims)
    ids, weights = moe.route(x, full["router"], full["router_bias"], k, 2.446)
    layer = jax.jit(lambda *a: moe.held_experts(*a), static_argnums=(3, 4, 8))
    before = introspect.process_record()["moe_sites"]
    total = seq_common._swiglu(full["shared"], x, jnp.float32)  # once, on every chip
    for first in range(0, E, 8):
        held = tuple(range(first, first + 8))
        share = {n: full["experts"][n][first:first + 8] for n in ("gate", "up", "down")}
        part, load, _ = layer(x, ids, weights, held, E, share["gate"], share["up"],
                              share["down"], jnp.float32)
        total = total + part
    after = introspect.process_record()["moe_sites"]
    assert {n for n in after if after[n] > before[n]} == {side}
    np.testing.assert_allclose(total, uncut, atol=3e-4)


# (e) the preset on the normal path, and what build_model refuses
def test_the_preset_trains_on_the_anakin_path(policy):
    env, _, _ = policy
    too_long = CFG.replace(token_task=(64, 12, 33, 1, 2))
    with pytest.raises(ValueError, match="positions"):
        build_model(too_long, registry.make(CFG.env_id, too_long).spec)
    with pytest.raises(ValueError, match="unknown seq_model.*moonlight_5l"):
        build_model(CFG.replace(seq_model="no_such"), env.spec)
    full = presets.get("moonlight_rl")
    assert full.token_task == (20480, 2048, 8192, 32, 128)
    assert (full.num_envs, full.unroll_len, full.actor_staleness) == (16, 512, 2)
    assert (full.optimizer, full.donate_buffers) == ("rmsprop", True)
    assert moonlight.SHAPES[full.seq_model].max_positions == 8192
    agent = make_agent(CFG.replace(num_envs=2 * len(jax.devices())))
    try:
        assert type(agent).__name__ == "Trainer"
        state = agent.state
        first = jax.device_get(state.params)
        for _ in range(3):
            state, metrics = agent.learner.update(state)
        m = {k: float(np.ravel(v)[0]) for k, v in metrics.items()}
        assert np.isfinite(m["loss"]) and m["episode_resets"] > 0
        assert 0.3 < m["moe_local_frac"] < 0.7  # 4 of 8 held
        assert m["mla_rows_expanded"] == TINY.max_positions + CFG.unroll_len
        assert 1 <= m["mla_rows_attended"] <= TINY.max_positions
        moved = jax.tree.map(lambda a, b: float(jnp.sum(jnp.abs(a - b))),
                             first, jax.device_get(state.params))
        # every leaf but the routers' correction biases (buffers)
        for path, v in jax.tree_util.tree_flatten_with_path(moved)[0]:
            assert (v > 0) != ("router_bias" in jax.tree_util.keystr(path)), path
    finally:
        agent.close()


# (f) what a profile of the step reads
def test_the_step_names_the_scopes_a_profile_reads():
    agent = make_agent(CFG.replace(num_envs=2 * len(jax.devices())))
    try:
        text = agent.learner._step.lower(agent.state).compile().as_text()
    finally:
        agent.close()
    names = re.findall(r'op_name="([^"]+)"', text)

    def some(*parts):
        return any(all(p in n for p in parts) for n in names)

    # the one-token form in the rollout, the fragment form's two in the learner
    assert some("/rollout/", "/actor_forward/", "/mla/", "/mla_step/")
    assert some("/loss_and_grad/", "/mla/", "/mla_expand/")
    assert some("/loss_and_grad/", "/mla/", "/mla_attend/")
    assert some("/loss_and_grad/", "transpose(", "/mla_attend/")
    assert some("/moe/", "/moe_router/") and some("/moe/", "/moe_experts/")
    components = {c for name in names for c in name.split("/")}
    assert not components & {"kda", "conv_mixer", "gqa", "dsa_index"}


def test_the_one_token_form_lowers_to_the_kernel_a_profile_reads():
    """Lowered for a TPU (no chip and no libtpu: the Mosaic kernel is
    serialised here), one token at Moonlight's attention widths over its
    8,192-row cache takes ``ops/latent.py``'s kernel: one Mosaic call named
    ``mla_step`` under ``/mla/mla_step/`` (``mla_step_device_ms`` and
    ``moonlight_mla_device_ms`` read that path, ``fused_vtrace_device_us``
    another name), counted by ``mla_sites``. The compile of a scan of such
    steps is in ``tests/test_max_pool.py``."""
    model = moonlight.MoonlightPolicy(dataclasses.replace(
        moonlight.SHAPES["moonlight_5l"], hidden=256, vocab=512,
        layers=("mla+dense",), dense_ffn=256), compute_dtype=jnp.bfloat16)
    B = 16
    variables, core = jax.eval_shape(
        lambda: (model.init(jax.random.key(0)), model.initial_core(B)))
    before = introspect.process_record()["mla_sites"]
    text = jax.jit(model.apply).trace(
        variables, jax.ShapeDtypeStruct((B,), jnp.int32), core).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    now = introspect.process_record()["mla_sites"]
    assert {k: now[k] - before[k] for k in now} == {"step": 0, "step_kernel": 1}
    (call,) = [line for line in text.splitlines() if "@tpu_custom_call" in line]
    assert 'kernel_name = "mla_step"' in call
    (loc,) = re.findall(r"loc\((#loc\d+)\)\s*$", call)
    name = re.search(rf"^{loc} = loc\(\"([^\"]+)\"", text, flags=re.M).group(1)
    assert "/mla/mla_step/" in name and name.endswith("mla_step/pallas_call"), name


# (g) the fragment form over each block's rung of the cache (models/mla.py):
# a cache longer than the fragment is computed up to the smallest of an
# eighth, a quarter, a half or all of its rows that holds what the block's
# mask admits, and the fragment's rows
def ladder_case(lengths, boundaries=(), L=TINY.max_positions, T=16, seed=20):
    """A layer's weights, a fragment of ``T`` tokens and a carry whose envs
    hold ``lengths`` cached rows, an episode ending at each ``(t, env)``
    of ``boundaries``."""
    s = dataclasses.replace(TINY, max_positions=L)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    w = lambda *dims: jax.random.normal(next(keys), dims) * dims[-2] ** -0.5
    B = len(lengths)
    x = jax.random.normal(next(keys), (T, B, s.hidden))
    state = {"kv": jax.random.normal(next(keys), (B, L, s.kv_lora + s.qk_rope)),
             "len": jnp.asarray(lengths, jnp.int32)}
    done = jnp.zeros((T, B), bool)
    for t, b in boundaries:
        done = done.at[t, b].set(True)
    return s, mla.weights(w, s.hidden, s), x, state, done


def one_rung(L, T):
    """The ladder of a cache no longer than the fragment: every row, the
    form as it was before the ladder."""
    return (L,)


# Envs holding 0 rows (an empty cache), 3 and 4 (an eighth of 32, the edge
# included), 7 (a quarter), 12 and 9 (a half; the 9 with an episode ending
# inside the fragment) and 30 (the whole), and one env whose episode ends
# on the fragment's first token.
LADDER_LENGTHS = [0, 3, 4, 7, 12, 9, 30, 16]
LADDER_RUNGS = [4, 4, 4, 8, 16, 16, 32, 16]
LADDER_BOUNDARIES = ((5, 5), (11, 5), (0, 7))


@pytest.mark.parametrize("theta", [TINY.rope_theta, None])
@pytest.mark.parametrize("per_env", [True, False])
def test_the_fragment_form_over_its_rung_is_the_whole_rows_form(theta, per_env):
    """Outputs, carry, the gradient of every operand and the rows counted,
    with one env a block (each env on its own rung: all four of them) and
    with the preset's one block of all envs (the longest env's rung)."""
    lengths = LADDER_LENGTHS if per_env else [0, 3, 9]
    s, p, x, state, done = ladder_case(
        lengths, LADDER_BOUNDARIES if per_env else ((4, 2),))
    T, B = done.shape
    assert mla._rungs(s.max_positions, T) == (4, 8, 16, 32)
    blocks = (lambda B, T, L, heads: B) if per_env else mla._blocks
    form = lambda *a: mla.fragment(*a, s, jnp.float32, theta)

    def run(ladder):
        with mock.patch.object(mla, "_rungs", ladder), \
                mock.patch.object(mla, "_blocks", blocks):
            mix = jax.random.normal(jax.random.PRNGKey(21), (T, B, s.hidden))
            out, after = jax.jit(form)(p, x, state, done)
            grads = jax.jit(jax.grad(
                lambda p, x, kv: jnp.sum(form(p, x, {**state, "kv": kv}, done)[0] * mix),
                argnums=(0, 1, 2)))(p, x, state["kv"])
            return out, after, grads, jax.jit(
                lambda st, d: mla.counters(st, d, s))(state, done)

    out, after, grads, counted = run(mla._rungs)
    ref_out, ref_after, ref_grads, ref_counted = run(one_rung)
    np.testing.assert_allclose(out, ref_out, atol=1e-5 * float(jnp.max(jnp.abs(ref_out))))
    for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(ref_after)):
        np.testing.assert_array_equal(a, b)
    for path, g in jax.tree_util.tree_flatten_with_path(ref_grads)[0]:
        got = dict(jax.tree_util.tree_flatten_with_path(grads)[0])[path]
        np.testing.assert_allclose(got, g, atol=1e-5 * float(jnp.max(jnp.abs(g))) + 1e-7,
                                   err_msg=jax.tree_util.keystr(path))
    # the rows past a rung get no gradient, as they got none before
    dkv = np.asarray(grads[2])
    for b, n in enumerate(lengths):
        assert not np.any(dkv[b, n:])
    rungs = LADDER_RUNGS if per_env else [16] * B
    assert float(counted["mla_rows_computed"]) == sum(c + T for c in rungs)
    assert float(ref_counted["mla_rows_computed"]) == B * (s.max_positions + T)
    assert float(counted["mla_rows_expanded"]) == B * (s.max_positions + T)
    for k in ("mla_rows_attended", "mla_rows_cached"):
        assert float(counted[k]) == float(ref_counted[k])


def test_the_rung_follows_the_blocks_mask():
    """The rung of a block is the smallest that holds the cached rows its
    mask admits for any query, whichever env and query admits them: two
    envs a block, each block landing on a rung's edge or past it."""
    L, T = 32, 8
    lengths = jnp.asarray([0, 0, 3, 4, 5, 0, 16, 2, 0, 17, 32, 1], jnp.int32)
    done = jnp.zeros((T, 12), bool).at[0, 9].set(True)  # ends on its first token
    mask, _ = seq_common._episode_mask(done, lengths, L)
    rungs = mla._rungs(L, T)
    got = mla._rung_index(mask.reshape(6, 2, T, L + T), rungs)
    np.testing.assert_array_equal(got, [0, 0, 1, 2, 3, 3])
    # a mask that admits no cached row for any query needs the first rung,
    # and a ladder of one rung is always its rung
    np.testing.assert_array_equal(
        mla._rung_index(mask.at[..., :L].set(False).reshape(6, 2, T, L + T), rungs), 0)
    np.testing.assert_array_equal(mla._rung_index(mask.reshape(6, 2, T, L + T), (L,)), 0)
    # only rows the mask admits count: a cache row past len is not a row held
    wide = mask.at[4, :, 20].set(True)  # env 4 (5 rows) with row 20 admitted
    np.testing.assert_array_equal(
        mla._rung_index(wide.reshape(6, 2, T, L + T), rungs), [0, 0, 3, 2, 3, 3])


def test_the_update_counts_the_rows_each_block_computed(policy, fragments):
    """``aux["mla_rows_computed"]``: the mean over envs and layers of the
    rung + T of each env's block, from the fragment's masks as the trunk
    blocks its envs; no more than the rows the form is handed. The first
    fragment starts from empty caches (the first rung), the last from
    caches of up to 18 rows (the whole)."""
    _, model, variables = policy
    T, B = fragments[0].done.shape
    L = TINY.max_positions
    rungs = np.asarray(mla._rungs(L, T))
    b = seq_common._env_block(B, T, TINY.block_tokens)  # envs a layer block
    assert mla._blocks(b, T, L, TINY.mla_heads) == 1  # one attention block in it
    means = []
    for r in fragments:
        aux = model.apply(variables, r.obs, r.done, r.init_core, method="fragment")[3]
        computed = []
        for layer in r.init_core.layers:
            mask = np.asarray(seq_common._episode_mask(r.done, layer["len"], L)[0])
            for block in mask.reshape(B // b, b, T, L + T):
                held = max([i + 1 for i in range(L) if block[..., i].any()], default=0)
                computed += [rungs[np.argmax(rungs >= held)] + T] * b
        assert float(aux["mla_rows_computed"]) == pytest.approx(float(np.mean(computed)))
        assert float(aux["mla_rows_computed"]) <= float(aux["mla_rows_expanded"]) == L + T
        means.append(float(aux["mla_rows_computed"]))
    assert means[0] == rungs[0] + T and means[-1] == L + T


def test_the_rungs_are_traced_once_a_program_not_once_a_layer(policy, fragments):
    """The branch functions are shared by the layers: lowering the learner's
    loss through three latent-attention layers traces each rung's body once
    (four traces, not twelve), from four cached functions."""
    _, model, variables = policy
    r = fragments[-1]
    traced = []
    body = mla._attend

    def counting(*a, **k):
        traced.append(a[1].shape[1])  # the rows a trace computes over
        return body(*a, **k)

    mla._branch.cache_clear()
    with mock.patch.object(mla, "_attend", counting):
        jax.jit(jax.value_and_grad(
            lambda v: learner_mod._algo_loss(CFG, model.apply, v, r)[0])).lower(variables)
    T = r.obs.shape[0]
    assert sorted(traced) == [c + T for c in mla._rungs(TINY.max_positions, T)]
    info = mla._branch.cache_info()
    assert (info.currsize, info.misses, info.hits) == (4, 4, 4 * (len(TINY.layers) - 1))


def test_the_ladders_residuals_are_the_whole_rows():
    """What the fragment form's VJP keeps: no array whose shape follows a
    rung (the switch would keep every rung's, zero-filled), and no more
    bytes than the whole rows' form keeps but the rungs' indices. Shapes
    with no size in common with a rung: 40 cached rows, rungs of 5, 10, 20
    and 40, a fragment of 8."""
    s, p, x, state, done = ladder_case([0, 6, 40], ((3, 1),), L=40, T=8)
    T = done.shape[0]
    rungs = mla._rungs(40, T)
    assert rungs == (5, 10, 20, 40)
    sliced = {n for c in rungs[:-1] for n in (c, c + T)}

    def kept(ladder):
        with mock.patch.object(mla, "_rungs", ladder):
            _, vjp = jax.vjp(lambda p, x, kv: mla.fragment(
                p, x, {**state, "kv": kv}, done, s, jnp.float32, s.rope_theta)[0],
                p, x, state["kv"])
        return [np.asarray(a) for a in jax.tree.leaves(vjp)]

    mine, whole = kept(mla._rungs), kept(one_rung)
    assert not [a.shape for a in mine if sliced & set(a.shape)]
    ints = sum(a.nbytes for a in mine if a.dtype == np.int32)
    assert sum(a.nbytes for a in mine) - ints <= sum(a.nbytes for a in whole)
