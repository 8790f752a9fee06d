"""The process record (obs/introspect.py ``phase`` / ``process_record``),
armed spans in the profiler's trace (obs/trace.py), tracing on the Anakin
trainer, and the device scopes a profile reads (rollout/anakin.py,
envs/pixels.py, models/networks.py).
"""

import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from asyncrl_tpu import make_agent, obs
from asyncrl_tpu.configs import presets
from asyncrl_tpu.obs import flightrec, introspect, registry, trace
from asyncrl_tpu.obs import spans as span_names
from asyncrl_tpu.utils.config import Config

BACKEND = "/jax/core/compile/backend_compile_duration"
SETUP_PHASES = [
    span_names.SETUP_ENV, span_names.SETUP_MODEL, span_names.SETUP_MESH,
    span_names.SETUP_LEARNER, span_names.SETUP_INIT_STATE,
    span_names.SETUP_CHECKPOINT, span_names.SETUP_AGENT,
]


@pytest.fixture(autouse=True)
def _clean_obs_state():
    trace.configure(False)
    flightrec.disarm()
    registry.registry().reset()
    yield
    trace.configure(False)
    flightrec.disarm()
    registry.registry().reset()


def _tiny(**overrides) -> Config:
    return Config(env_id="CartPole-v1", algo="impala", backend="tpu",
                  num_envs=8, unroll_len=4, hidden_sizes=(8,), **overrides)


def _compiles_of(record, fun):
    return [c for c in record["compiles"] if c[0] == BACKEND and c[1] == fun]


# ------------------------------------------------------------ the record


def test_listener_records_a_fresh_jit_once():
    introspect.process_record()  # first use registers the listener

    def fresh_probe_fn(x):
        return x * 2 + 1

    f = jax.jit(fresh_probe_fn)
    f(jnp.ones(3)).block_until_ready()
    first = _compiles_of(introspect.process_record(), "jit(fresh_probe_fn)")
    assert len(first) == 1
    event, fun, t_end, duration = first[0]
    assert duration > 0 and t_end > duration  # perf_counter stamps
    f(jnp.ones(3)).block_until_ready()
    again = _compiles_of(introspect.process_record(), "jit(fresh_probe_fn)")
    assert again == first  # a steady call reports nothing


def test_events_inside_a_phase_are_placed_in_it():
    def phase_probe_in(x):
        return x + 3

    def phase_probe_out(x):
        return x - 3

    with introspect.phase("setup.test_probe"):
        jax.jit(phase_probe_in)(jnp.ones(2)).block_until_ready()
    jax.jit(phase_probe_out)(jnp.ones(2)).block_until_ready()
    record = introspect.process_record()
    name, t0, t1 = [p for p in record["phases"] if p[0] == "setup.test_probe"][-1]
    inside = {c[1] for c in record["compiles"] if t0 <= c[2] <= t1}
    assert "jit(phase_probe_in)" in inside
    assert "jit(phase_probe_out)" not in inside
    assert _compiles_of(record, "jit(phase_probe_out)")[0][2] > t1


def test_obs_setup_leaves_the_record_intact():
    with introspect.phase("setup.test_survivor"):
        jax.jit(lambda x: x * 5)(jnp.ones(2)).block_until_ready()
    before = introspect.process_record()
    handle = obs.setup(_tiny())  # resets the registry and the agent's log
    handle.shutdown()
    after = introspect.process_record()
    assert after["phases"][: len(before["phases"])] == before["phases"]
    assert after["compiles"][: len(before["compiles"])] == before["compiles"]
    assert ("setup.test_survivor" in {p[0] for p in after["phases"]})


def test_cap_drops_oldest_and_counts():
    record = introspect._ProcessRecord(cap=4)
    for i in range(6):
        record.on_event(BACKEND, 0.5, fun_name=f"jit(f{i})")
    record.on_event("/jax/some/other_duration", 0.5, fun_name="ignored")
    for i in range(5):
        record.add_phase(f"setup.p{i}", float(i), float(i) + 1)
    snap = record.snapshot()
    assert [c[1] for c in snap["compiles"]] == [f"jit(f{i})" for i in range(2, 6)]
    assert [p[0] for p in snap["phases"]] == [f"setup.p{i}" for i in range(1, 5)]
    assert snap["dropped"] == 3


# ---------------------------------------------------- disarmed and armed


def test_disarmed_phase_builds_no_ring():
    assert trace.span(span_names.LEARNER_UPDATE) is trace.span("anything")
    with introspect.phase("setup.test_disarmed"):
        pass
    assert trace.snapshots() == [] and trace.stats() == {}
    assert "setup.test_disarmed" in {
        p[0] for p in introspect.process_record()["phases"]}


def test_armed_phase_and_compiles_are_spans():
    trace.configure(True)

    def armed_probe_fn(x):
        return x * 7

    with introspect.phase("setup.test_armed"):
        jax.jit(armed_probe_fn)(jnp.ones(2)).block_until_ready()
    (snap,) = trace.snapshots()
    by_name = {}
    for s in snap["spans"]:
        by_name.setdefault(s[0], []).append(s)
    (phase,) = by_name["setup.test_armed"]
    backend = [s for s in by_name[span_names.COMPILE_BACKEND]
               if s[3] == {"fun": "jit(armed_probe_fn)"}]
    assert len(backend) == 1
    assert phase[1] <= backend[0][1] < backend[0][2] <= phase[2]
    assert span_names.COMPILE_TRACE in by_name
    assert span_names.COMPILE_LOWER in by_name
    assert not any(span_names.is_wait(n) for n in by_name)


# ------------------------------------------------- the Anakin trainer


def test_make_agent_marks_its_phases_and_the_first_update_only():
    n0 = len(introspect.process_record()["phases"])
    agent = make_agent(_tiny())
    try:
        phases = introspect.process_record()["phases"][n0:]
        assert [p[0] for p in phases] == SETUP_PHASES
        spans = {p[0]: (p[1], p[2]) for p in phases}
        a0, a1 = spans[span_names.SETUP_AGENT]
        assert all(a0 <= t0 <= t1 <= a1 for t0, t1 in spans.values())
        i0, i1 = spans[span_names.SETUP_INIT_STATE]
        programs = [c for c in introspect.process_record()["compiles"]
                    if c[0] == BACKEND and i0 <= c[2] <= i1]
        assert programs  # init_state asks the backend for programs
        state = agent.state
        for _ in range(3):
            state, _ = agent.learner.update(state)
        later = [p[0] for p in introspect.process_record()["phases"][n0:]]
        assert later == [*SETUP_PHASES, span_names.SETUP_FIRST_UPDATE]
        assert trace.snapshots() == []  # config.trace is off: no ring
    finally:
        agent.close()


def test_anakin_trainer_honours_config_trace(tmp_path):
    agent = make_agent(_tiny(trace=True, run_dir=str(tmp_path), log_every=2,
                             eval_every=2, eval_episodes=2))
    try:
        assert trace.enabled()
        history = agent.train(total_env_steps=4 * 32)
    finally:
        agent.close()
    names = [s[0] for snap in trace.snapshots() for s in snap["spans"]]
    assert names.count(span_names.LEARNER_UPDATE) == 4
    assert names.count(span_names.SETUP_FIRST_UPDATE) == 1
    assert names.count(span_names.LEARNER_METRICS) == 2
    assert names.count(span_names.LEARNER_EVAL) == 2
    assert span_names.SETUP_INIT_STATE in names
    assert span_names.COMPILE_BACKEND in names
    # close() exported the rings; the history dicts gained no key
    assert glob.glob(os.path.join(str(tmp_path), "trace-*.json"))
    assert not [k for k in history[0] if k.startswith(("trace_", "setup"))]


def test_armed_spans_are_events_of_the_profilers_trace(tmp_path):
    from benchmarks import xplane

    agent = make_agent(_tiny(trace=True, run_dir=str(tmp_path / "run")))
    try:
        state, _ = agent.learner.update(agent.state)  # compiled outside
        jax.block_until_ready(state)
        jax.profiler.start_trace(str(tmp_path / "profile"))
        try:
            with jax.profiler.TraceAnnotation("test.outer"):
                state, _ = agent.learner.update(state)
                jax.block_until_ready(state)
        finally:
            jax.profiler.stop_trace()
    finally:
        agent.close()
    (path,) = glob.glob(str(tmp_path / "profile" / "**" / "*.xplane.pb"),
                        recursive=True)
    found = 0
    for plane in xplane.read_xspace(path):
        for line in plane.lines:
            outer = [e for e in line.events if e.name == "test.outer"]
            inner = [e for e in line.events
                     if e.name == span_names.LEARNER_UPDATE]
            for o in outer:
                found += sum(
                    o.start_ps <= e.start_ps and e.end_ps <= o.end_ps
                    for e in inner
                )
    assert found == 1


# ---------------------------------------------------------- device scopes


def test_impala_cnn_param_paths_are_what_they_were():
    from asyncrl_tpu.models.networks import ImpalaCNN

    params = jax.eval_shape(
        lambda: ImpalaCNN(channels=(4, 8)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 16, 16, 4), jnp.uint8)))
    paths = sorted(
        "/".join(k.key for k in path)
        for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]
    )
    expected = [f"params/{m}/{leaf}"
                for m in ("Conv_0", "Conv_1", "Dense_0")
                for leaf in ("bias", "kernel")]
    expected += [f"params/ResidualBlock_{b}/Conv_{c}/{leaf}"
                 for b in range(4) for c in range(2)
                 for leaf in ("bias", "kernel")]
    assert paths == sorted(expected)


def test_the_compiled_step_names_the_scopes_a_profile_reads():
    cfg = presets.get("atari_impala").replace(
        num_envs=len(jax.devices()), unroll_len=2, updates_per_call=1,
        channels=(4, 8), fused_scan="interpret")
    agent = make_agent(cfg)
    try:
        text = agent.learner._step.lower(agent.state).compile().as_text()
    finally:
        agent.close()
    components = {
        c for name in re.findall(r'op_name="([^"]+)"', text)
        for c in name.split("/")
    }
    # as benchmarks/xplane.py in_scope matches them: whole path components
    # (``render`` is the outermost scope inside the vmapped env step)
    # ``optimizer`` and ``publish`` (the refresh of ``actor_params``): the
    # step's two scopes after the gradient, side by side and not nested
    for scope in ("rollout", "loss_and_grad", "actor_forward", "env_step",
                  "vmap(render)", "section0", "section1", "max_pool",
                  "optimizer", "publish"):
        assert scope in components, scope
    names = re.findall(r'op_name="([^"]+)"', text)
    assert any("/publish/" in n and "select_n" in n for n in names)
    # siblings: no op is under two of the step's four scopes, so their times
    # and ``update_rest_device_ms`` add up to the busy time, none twice
    step_scopes = ("rollout", "loss_and_grad", "optimizer", "publish")
    assert not any(sum(f"/{s}/" in f"/{n}/" for s in step_scopes) > 1
                   for n in names)
    assert any("/rollout/" in n and "/actor_forward/" in n and "/section0/" in n
               for n in names)
    assert any("/env_step/vmap(render)/" in n for n in names)
    assert any("/loss_and_grad/" in n and "/section0/max_pool/" in n for n in names)
    # the pool has a custom_vjp: its backward keeps the call site's scopes
    # (max_pool_device_ms counts forward and backward)
    assert any("/loss_and_grad/transpose(" in n and "/section0/max_pool/" in n
               for n in names)


def test_the_sequence_policy_step_names_the_scopes_a_profile_reads():
    """The same check for ``kimi_linear_rl``'s layers (forward and backward
    ops), and the record of which form each KDA site lowered as."""
    before = introspect.process_record()["kda_sites"]
    cfg = presets.get("kimi_linear_tiny").replace(
        num_envs=len(jax.devices()), unroll_len=16, fused_scan="interpret")
    agent = make_agent(cfg)
    try:
        text = agent.learner._step.lower(agent.state).compile().as_text()
    finally:
        agent.close()
    names = re.findall(r'op_name="([^"]+)"', text)
    components = {c for name in names for c in name.split("/")}
    for scope in ("rollout", "loss_and_grad", "actor_forward", "env_step",
                  "kda", "kda_step", "kda_chunk", "mla", "moe", "moe_router",
                  "moe_experts", "lm_head", "core_reset"):
        assert scope in components, scope

    def some(*parts):
        return any(all(p in n for p in parts) for n in names)

    # the rollout runs the one-token forms, the learner the fragment forms
    assert some("/rollout/", "/actor_forward/", "/kda/kda_step/")
    assert some("/rollout/", "/actor_forward/", "/mla/")
    assert some("/rollout/", "/actor_forward/", "/moe/moe_router/")
    # a decode step's few tokens take the expert layer's dense side, under
    # the name ``moe_sites`` counts it by
    assert some("/rollout/", "/actor_forward/", "/moe/moe_experts/moe_dense/")
    assert some("/rollout/", "/actor_forward/", "/lm_head/")
    assert some("/rollout/", "/core_reset/")
    assert not some("/rollout/", "kda_chunk")
    assert some("/loss_and_grad/", "/kda/kda_chunk/")
    assert some("/loss_and_grad/", "/moe/moe_experts/")
    # the backward pass keeps the scopes (the *_device_ms metrics count it)
    for scope in ("/kda/kda_chunk/", "/mla/", "/moe/moe_experts/", "/lm_head/"):
        assert some("/loss_and_grad/", "transpose(", scope), scope
    after = introspect.process_record()["kda_sites"]
    assert after["step"] > before["step"] and after["chunk"] > before["chunk"]


@pytest.mark.parametrize("E, k, N, tile, side", [
    # the sizes that reach each side in tests/test_kimi_linear.py and
    # tests/test_lfm2_moe.py: a tiny step's few tokens lower the dense one
    (64, 2, 4096, None, "gathered"),
    (32, 4, 2048, 16, "grouped"),
])
def test_the_expert_layers_sides_keep_their_scopes_forward_and_backward(
        E, k, N, tile, side):
    """``moe_gathered`` / ``moe_grouped`` inside ``moe_experts``, and
    ``moe_dense`` behind the same ``lax.cond``: on the backward ops too
    (``moe_gathered_device_ms`` and ``moe_dense_device_ms`` count them)."""
    from asyncrl_tpu.ops import moe

    D, F, held = 32, 16, tuple(range(8))
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(keys[0], (N, D))
    ids, weights = moe.route(x, jax.random.normal(keys[1], (D, E)), None, k, 1.0)
    gate, up = (jax.random.normal(key, (8, D, F)) for key in keys[2:4])
    down = jax.random.normal(keys[4], (8, F, D))

    def loss(x, gate, up, down):
        # under the layer's scope, as the models call it: autodiff wraps the
        # outermost name (``jvp(moe)``) and leaves the inner ones whole
        with jax.named_scope("moe"):
            out, _, _ = moe.held_experts(
                x, ids, weights, held, E, gate, up, down, jnp.float32, tile)
        return jnp.sum(out)

    before = introspect.process_record()["moe_sites"]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        x, gate, up, down).compile().as_text()
    after = introspect.process_record()["moe_sites"]
    assert {s for s in after if after[s] > before[s]} == {side}
    names = re.findall(r'op_name="([^"]+)"', text)
    for scope in (f"moe_{side}", "moe_dense"):  # the side, and its overflow
        inside = [n for n in names if "/moe_experts/cond/branch_" in n
                  and f"/{scope}/" in n]
        assert inside, scope
        assert any("transpose(" in n for n in inside), scope
    other = {"gathered": "moe_grouped", "grouped": "moe_gathered"}[side]
    assert not any(f"/{other}/" in n for n in names)


def test_kda_sites_count_the_one_token_form_once_per_site_and_program():
    """``kda_sites``: ``step_kernel`` where the Pallas kernel was lowered,
    ``step`` where the plain form was: by shape when traced, by platform
    when lowered (sites of one shape are still counted each: the site's
    lowering is not cached), nothing on a steady call."""
    from asyncrl_tpu.ops import kda

    def operands(d):
        z = jnp.zeros((2, 8, d))
        return jnp.zeros((2, 8, d, d)), z, z, z, z, jnp.zeros((2, 8))

    def two_sites(S, *xs):
        S, o = kda.kda_step(S, *xs)
        return kda.kda_step(S, *xs, jnp.asarray([True, False]))[0], o

    def since(before):
        now = introspect.process_record()["kda_sites"]
        return {k: now[k] - before[k] for k in now}

    none = dict.fromkeys(
        ("step", "step_kernel", "chunk", "pair", "pair_kernel"), 0)
    for d in (16, 128):  # the tiny preset's width; the published one, on a CPU
        before = introspect.process_record()["kda_sites"]
        step = jax.jit(two_sites)
        step(*operands(d))
        assert since(before) == {**none, "step": 2}, d
        step(*operands(d))  # a steady call counts nothing
        assert since(before) == {**none, "step": 2}, d
