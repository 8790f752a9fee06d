"""The ``keye_moe_rl`` cell: its counts against hand-counted tiny shapes, its
loop end to end on the CPU at the tiny preset through ``benchmarks.run.main``
(a warm-in, then ``correct`` true; false when the reference is fed another
top-k or a sigmoid router, and when the bfloat16 reference stands in the
program's place), its metric files, and its configuration and traffic files against
the program and the catalog."""

import ast
import dataclasses
import json
import os
import re
import types

import jax
import numpy as np
import pytest

from asyncrl_tpu.envs import registry
from asyncrl_tpu.models import keye_moe
from benchmarks import (
    device, episode_draw, keye_counts, keye_readers, program_record, readers, run)
from benchmarks.loops import anakin_keye, common

CELL = "keye_moe_rl.anakin_16x512"
SCOPES = {
    "dsa_index_device_ms": "dsa_index", "dsa_select_device_ms": "dsa_select",
    "dsa_attend_device_ms": "dsa_attend", "keye_gqa_device_ms": "gqa",
    "keye_moe_experts_device_ms": "moe_experts", "keye_lm_head_device_ms": "lm_head",
}
COUNTERS = {
    "dsa_rows_scored": "dsa_rows_scored", "dsa_rows_selected": "dsa_rows_selected",
    "dsa_pruned_share": "dsa_pruned_share", "indexer_kl": "indexer_kl",
    "keye_moe_load_max_over_mean": "moe_load_max_over_mean",
}
NEW_METRICS = {*SCOPES, *COUNTERS, "keye_step_mfu", "keye_rollout_hbm_roofline"}
ACCEPTED_CELLS = ["atari_impala.dp1", "atari_impala.dp4",
                  "kimi_linear_rl.anakin_64x256", "lfm2_moe_rl.anakin_128x256"]
HAND = {  # a shape small enough to count by hand
    "hidden": 4, "vocab": 10, "layers": ["dsa+moe", "dsa+moe"],
    "heads": 4, "kv_heads": 2, "head_dim": 3, "rope_theta": 1e7,
    "index_heads": 2, "index_dim": 2, "index_top_k": 3,
    "expert_ffn": 2, "num_experts": 8, "held_experts": [0, 1],
    "top_k": 2, "routed_scale": 1.0, "max_positions": 8, "eps": 1e-6,
    "block_tokens": 64, "query_block": 4,
}


def test_counts_of_a_shape_counted_by_hand():
    d = HAND
    # attention at 3 selected rows: q 4*12, k and v 4*6 each, o 12*4; 4 heads x 3 x (3 + 3)
    assert keye_counts.attention_flops(d, 3) == 2 * (48 + 24 + 24 + 48) + 2 * 4 * 3 * 6
    # the indexer at 5 scored rows: q 4*4, k 4*2, w 4*2; 2 heads x 5 x (2 + 1)
    assert keye_counts.indexer_flops(d, 5) == 2 * (16 + 8 + 8) + 2 * 2 * 5 * 3
    assert keye_counts.expert_flops(d) == 2 * 3 * 4 * 2
    fwd = keye_counts.forward_flops_per_token(d, 5, 3, 0.5)
    # a layer: attention 432, indexer 124, router 2*4*8, half an assignment
    assert fwd == 2 * (432 + 124 + 64 + 24) + 2 * 4 * 11
    assert keye_counts.train_flops_per_update(d, 7, 5, 3, 0.5) == 7 * 4 * fwd
    p = keye_counts.parameters(d)
    attention, indexer = 48 + 24 + 24 + 6 + 48, 16 + 8 + 4 + 8
    assert p["attention"] == 2 * attention and p["indexer"] == 2 * indexer
    assert p["experts"] == 2 * 48
    assert p["layers"] == 2 * (8 + attention + indexer + 32 + 48)
    assert p["total"] == p["layers"] + 40 + 40 + 5 + 4
    # weights but the embedding at 2 bytes + 3 embedding rows; per layer and
    # env 6 indexer keys of 2 and 4 rows of keys and of values of 2 x 3, at 2 bytes
    assert keye_counts.decode_bytes_per_step(d, 3, 5, 3) == (
        (p["total"] - 40) * 2 + 3 * 4 * 4 + 2 * 3 * (6 * 4 + 4 * 2 * 12))


def test_counts_agree_with_the_tree_the_program_builds():
    for name, shape in keye_moe.SHAPES.items():
        built = jax.eval_shape(
            keye_moe.KeyePolicy(shape).init, jax.random.PRNGKey(0))
        n = sum(x.size for x in jax.tree.leaves(built))
        assert keye_counts.parameters(dataclasses.asdict(shape))["total"] == n, name


@pytest.fixture(scope="module")
def spec():
    return run.Spec(os.path.join(run.ROOT, "BENCHMARK.json"), [run.BENCH_DIR])


def test_the_configuration_file_is_the_published_config_and_the_cut(spec):
    doc = spec.load("configs", "keye_moe_rl")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert doc["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value and doc[key] != value
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (doc["num_hidden_layers"], doc["num_experts"], doc["vocab_size"]) == (
        4, 16, 18992)
    cfg = run.program_config(doc, spec.load("traffic", "anakin_16x512"), 3)
    shape = keye_moe.SHAPES[cfg.seq_model]
    # no width is cut: every width the program builds is the published one
    sa = doc["sa_config"]
    assert (shape.hidden, shape.expert_ffn) == (
        doc["hidden_size"], doc["moe_intermediate_size"])
    assert (shape.heads, shape.kv_heads, shape.head_dim) == (
        doc["num_attention_heads"], doc["num_key_value_heads"], doc["head_dim"])
    assert (shape.index_heads, shape.index_dim, shape.index_top_k) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])
    assert sa["indexer_num_kv_heads"] == 1
    assert (shape.rope_theta, shape.eps) == (doc["rope_theta"], doc["rms_norm_eps"])
    assert (shape.num_experts, shape.top_k, shape.routed_scale) == (
        doc["published"]["num_experts"], doc["num_experts_per_tok"], 1.0)
    assert doc["norm_topk_prob"] and doc["mlp_only_layers"] == []
    assert len(shape.held_experts) == doc["num_experts"] >= 8
    assert len(shape.layers) == doc["num_hidden_layers"] >= 4
    assert set(shape.layers) == {"dsa+moe"}  # every layer of the one kind
    assert shape.vocab == doc["vocab_size"] == doc["published"]["vocab_size"] // 8
    assert doc["held_here"] == {
        "layers": [0, 1, 2, 3], "experts": list(range(16)), "vocab_rows": [0, 18992]}
    assert "8 chips" in doc["deployment"]
    assert doc["parameters"] == keye_counts.parameters(doc["model"])
    assert doc["parameters"]["total"] == 465393153
    assert 0.64 < doc["parameters"]["experts"] / doc["parameters"]["total"] < 0.66
    for key in ("qk_norm", "indexer", "indexer_loss", "sa_chunks", "mrope",
                "attention", "router", "embedding_and_head", "weights",
                "value_head", "optimizer", "kv_cache", "blocks", "precision",
                "actor_staleness", "described_as"):
        assert doc["assumed"][key]
    # the traffic is the parameters the cell was asked with, and the files
    # agree with the program (the loop refuses to run otherwise)
    assert (cfg.num_envs, cfg.unroll_len, cfg.updates_per_call) == (16, 512, 1)
    assert cfg.actor_staleness == 2 and cfg.optimizer == "rmsprop"
    env = registry.make(cfg.env_id, cfg)
    assert (env.vocab, env.min_len, env.max_len, env.min_prompt, env.max_prompt) == (
        18992, 2048, 8192, 32, 128)
    assert shape.max_positions == env.max_len > shape.index_top_k
    # the warm-in is as long as the longest episode: every cache is in its
    # steady state when the window opens
    assert doc["warm_in_fragments"] * cfg.unroll_len == env.max_len
    anakin_keye.check_files_agree(cfg, doc)
    with pytest.raises(SystemExit, match="model record"):
        anakin_keye.check_files_agree(
            cfg, {"model": {**doc["model"], "hidden": 128}})
    with pytest.raises(SystemExit, match="parameters"):
        anakin_keye.check_files_agree(cfg, {**doc, "parameters": {}})


def test_every_new_metric_resolves_to_a_reader_in_the_new_cell_only(spec):
    mine = {m["name"]: m for m in spec.doc["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == NEW_METRICS
    for name in NEW_METRICS:
        read, params = spec.reader(name)
        assert callable(read) and isinstance(params, dict)
        assert mine[name]["moves"] == "env_frames_per_s"
    for name, scope in SCOPES.items():
        assert spec.reader(name) == (readers.scope_device_ms, {"scope": scope})
        assert mine[name]["source"] == "device_trace"
    for name, key in COUNTERS.items():
        assert spec.reader(name) == (readers.counter, {"key": key})
        assert mine[name]["source"] == "program_counter"
    assert spec.reader("keye_step_mfu")[0] is keye_readers.keye_step_mfu
    assert spec.reader("keye_rollout_hbm_roofline")[0] is (
        keye_readers.keye_rollout_hbm_roofline)
    in_cell = {m["name"] for m in spec.metrics_of("per_layer", CELL)}
    assert NEW_METRICS <= in_cell
    # the accepted metrics without a list read this cell as they read the
    # others; those of the CNN and of the other sequence policies stay away
    assert {"rollout_device_ms", "loss_and_grad_device_ms", "hbm_peak_gb",
            "device_idle_share", "actor_forward_device_ms", "env_step_device_ms",
            "fused_vtrace_roofline", "step_trace_lower_s", "make_agent_s",
            "make_agent_programs"} <= in_cell
    assert not in_cell & {"render_device_ms", "model_flops_util", "seq_step_mfu",
                          "kda_device_ms", "lfm2_step_mfu", "gqa_device_ms",
                          "moe_experts_roofline"}
    # and no cell but this one reports the new metrics
    for cell in (w["name"] for w in spec.doc["workloads"]):
        if cell != CELL:
            assert not NEW_METRICS & {
                m["name"] for m in spec.metrics_of("per_layer", cell)}


def test_make_agent_programs_is_read_in_every_cell(spec):
    """PR 35: the count comes from the harness's own log of the backend's
    events, which no cap cuts, so the entry lists no cells again (ISSUE 32
    gave it the four that read a number then) and Keye's cell reads it too."""
    entry = next(m for m in spec.doc["per_layer"] if m["name"] == "make_agent_programs")
    read, params = spec.reader("make_agent_programs")
    assert read is program_record.programs_in_phase
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "Entry" and entry["moves"] == "setup_s"
    assert params["phase"].startswith("setup.")
    assert "workloads" not in entry
    assert [w["name"] for w in spec.doc["workloads"]] == [*ACCEPTED_CELLS, CELL]
    for cell in (*ACCEPTED_CELLS, CELL):
        assert entry in spec.metrics_of("per_layer", cell)


def test_a_rehearsal_under_an_accepted_cells_name_prints_the_five_program_metrics(
        tmp_path, monkeypatch, capsys):
    """What ``test_benchmark_program_metrics.py``'s rehearsal holds, under the
    name of an accepted cell and under a throwaway name: since PR 35
    ``make_agent_programs`` lists no cells, so both report the five."""
    program = ("make_agent_s", "init_state_s", "make_agent_programs",
               "step_trace_lower_s", "step_load_s")
    for kind in ("configs", "traffic"):
        (tmp_path / kind).mkdir()
    n_dev = len(jax.devices())
    (tmp_path / "configs" / "tiny_anakin.json").write_text(json.dumps({
        "loop": "anakin", "preset": "atari_impala", "reference_chunk": 9 * n_dev,
        "overrides": {"updates_per_call": 2, "fused_scan": "interpret",
                      "channels": [4, 8], "precision": "f32"}}))
    (tmp_path / "traffic" / "tiny_job.json").write_text(json.dumps(
        {"overrides": {"num_envs": n_dev, "unroll_len": 8}}))
    real = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    by_name = {m["name"]: m for m in real["per_layer"]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        **real, "configs": [],
        "workloads": [{"name": name, "config": "tiny_anakin", "traffic": "tiny_job",
                       "chips": 1, "why": "test"}
                      for name in (ACCEPTED_CELLS[0], "tiny.job")],
        "per_layer": [by_name[n] for n in program]}))
    monkeypatch.setattr(device, "require_chips", lambda chips: {
        "platform": jax.devices()[0].platform, "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()), "cache_dir": None})
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path / "_out"))
    args = ["--spec", str(tmp_path / "BENCHMARK.json"), "--data-root", str(tmp_path),
            "--seed", "2400000017", "--seconds", "1", "--trace", "1"]
    assert run.main([*args, "--workload", ACCEPTED_CELLS[0]]) == 0
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    assert line["correct"] is True, captured.err[-2000:]
    assert set(line["metrics"]) == set(program)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["metrics"]["make_agent_programs"]["unit"] == "count"
    phases = ast.literal_eval(
        re.search(r"set-up phases \(s\): (\{.*\})", captured.err).group(1))
    assert abs(got["make_agent_s"] - phases["make_agent"]) < 0.5
    assert 0 < got["init_state_s"] <= got["make_agent_s"]
    assert got["make_agent_programs"] >= 1
    assert got["make_agent_programs"] == int(got["make_agent_programs"])
    assert got["step_trace_lower_s"] > 0 and got["step_load_s"] > 0
    assert got["step_trace_lower_s"] + got["step_load_s"] <= phases["warm_call"]
    # a cell of any other name reports the five too, the count of programs
    # from the log of THIS run's harness: a second agent of one process
    # finds its programs built
    assert run.main([*args, "--workload", "tiny.job"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line["metrics"]) == set(program)
    again = line["metrics"]["make_agent_programs"]["value"]
    assert 0 <= again <= got["make_agent_programs"]


def test_the_shares_read_a_trace_and_give_nothing_without_one():
    dims = dataclasses.asdict(keye_moe.SHAPES["keye_moe_4l"])
    ms = {"rollout": 1500.0}
    chip = types.SimpleNamespace(scope_ps=lambda s: ms.get(s, 0.0) * 1e9 * 2)
    ev = {
        "trace": types.SimpleNamespace(devices=[chip], busy_s=5.0, window_s=5.5),
        "traced_updates": 2, "chips": 1, "peaks": device.peaks("TPU v5 lite"),
        "geometry": {"num_envs": 16, "unroll_len": 512},
        "keye": {"dims": dims, "scored": 2560.0, "selected": 1700.0,
                 "held_per_token": 1.0},
    }
    flops = keye_counts.train_flops_per_update(dims, 8192, 2560.0, 1700.0, 1.0)
    assert keye_readers.keye_step_mfu(ev) == pytest.approx(
        100 * 2 * flops / 5.0 / 197e12)
    assert keye_readers.keye_rollout_hbm_roofline(ev) == pytest.approx(
        100 * keye_counts.decode_bytes_per_step(dims, 16, 2560.0, 1700.0) * 512
        / 819e9 * 1e3 / 1500.0)
    for value in (keye_readers.keye_step_mfu(ev),
                  keye_readers.keye_rollout_hbm_roofline(ev)):
        assert 0 < value < 100
    # the weights are most of what a decode step must move, the selected
    # rows next, the indexer's keys least
    p = keye_counts.parameters(dims)
    step = keye_counts.decode_bytes_per_step(dims, 16, 2560.0, 1700.0)
    assert 0.7 < (p["total"] - p["embed"]) * 2 / step < 0.85
    assert 4 * 16 * 1701 * 2048 > 10 * 4 * 16 * 2561 * 128
    # a program without the policy (the parent's), a run without a trace, or
    # a trace without the scope: nothing, and nothing raised
    for lacking in ({**ev, "trace": None},
                    {k: v for k, v in ev.items() if k != "keye"}):
        assert keye_readers.keye_step_mfu(lacking) is None
        assert keye_readers.keye_rollout_hbm_roofline(lacking) is None
    ms.clear()
    assert keye_readers.keye_rollout_hbm_roofline(ev) is None


# ------------------------------------------- the mix's draw of episodes


def test_the_committed_episode_seed_is_the_smallest_the_rule_admits(spec):
    """ISSUE 35's rule: the mean rows behind a token over the 8 x 512 steps
    that follow the warm-in, from cold, within 2% of the length law's
    stationary mean; one vmapped simulation over the candidates up to the
    committed one."""
    mix = spec.load("traffic", "anakin_16x512")
    doc = spec.load("configs", "keye_moe_rl")
    cfg = run.program_config(doc, mix, 3)
    env = registry.make(cfg.env_id, cfg)
    assert "episode_seed" not in mix["overrides"]  # no field of the program's
    assert cfg.num_envs == 16 and cfg.unroll_len == 512
    last = 8 * cfg.unroll_len
    seed, rows = episode_draw.smallest_seed(
        env, cfg.num_envs, 1, doc["warm_in_fragments"] * cfg.unroll_len + last,
        last, 0.02, candidates=mix["episode_seed"] + 1)
    assert seed == mix["episode_seed"] == len(rows) - 1
    assert episode_draw.stationary_rows(env.min_len, env.max_len) == 2560
    assert abs(rows[seed] - 2560) <= 51.2
    assert (np.abs(rows[:seed] - 2560) > 51.2).all()
    # the draws it passed over are the spread the cell had across --seeds
    assert rows[:seed].min() < 2300 and rows[:seed].max() > 2900


def test_rows_behind_counts_positions_from_the_done_flags():
    done = np.zeros((7, 2), bool)
    done[2, 0] = done[4, 1] = done[5, 1] = True
    # env 0: 0 1 2 | 0 1 2 3; env 1: 0 1 2 3 4 | 0 | 0
    assert float(episode_draw.rows_behind(done, 7)) == pytest.approx((9 + 10) / 14)
    assert float(episode_draw.rows_behind(done, 2)) == pytest.approx((2 + 3 + 0 + 0) / 4)


def test_two_seeds_of_the_parameters_meet_one_draw_of_episodes():
    """Two ``program_config``s that differ in ``--seed``: through the helper
    their env batches are one, their parameters are not, the carries stay the
    ones ``make_agent`` built; 2 x 512 steps of the program's ``unroll`` under
    either policy end their episodes where the simulation does, on other
    tokens."""
    from asyncrl_tpu import make_agent

    n_dev, T, episode_seed = len(jax.devices()), 16, 7
    docs = ({"preset": "keye_moe_tiny", "overrides": {"precision": "f32"}},
            {"overrides": TINY_MIX(n_dev)})
    done, tokens, actors, params = [], [], [], []
    for seed in (5, 2400000013):
        cfg = run.program_config(*docs, seed)
        agent = make_agent(cfg)
        try:
            built = agent.state.actor
            # the helper's draw is init_state's own: with the run's seed it
            # gives back the env batch make_agent built, placed the same
            same = common.with_episode_seed(built, agent.env, n_dev, seed)
            for a, b in zip(jax.tree.leaves((same.env_state, same.obs, same.keys)),
                            jax.tree.leaves((built.env_state, built.obs, built.keys))):
                assert np.array_equal(a, b) and a.sharding == b.sharding
            actor = common.with_episode_seed(built, agent.env, n_dev, episode_seed)
            assert all(a is b for a, b in zip(
                jax.tree.leaves(actor.core), jax.tree.leaves(built.core)))
            assert jax.tree.leaves(actor.core)  # the policy has a carry
            actors.append(jax.device_get((actor.env_state, actor.obs, actor.keys)))
            params.append(jax.device_get(agent.state.params))
            roll = anakin_keye.unroll_program(agent, cfg)
            flags, seen = [], []
            for _ in range(2 * 512 // T):
                actor, r = roll(agent.state.actor_params, actor)
                flags.append(np.asarray(r.done))
                seen.append(np.asarray(r.obs))
            done.append(np.concatenate(flags))
            tokens.append(np.concatenate(seen))
        finally:
            agent.close()
    for a, b in zip(jax.tree.leaves(actors[0]), jax.tree.leaves(actors[1])):
        assert np.array_equal(a, b)
    assert not all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(params[0]), jax.tree.leaves(params[1])))
    assert np.array_equal(done[0], done[1]) and 30 < done[0].sum()
    assert not np.array_equal(tokens[0], tokens[1])
    simulated = episode_draw.simulate(
        agent.env, 2 * n_dev, n_dev, episode_seed, 2 * 512)
    assert np.array_equal(np.asarray(simulated), done[0])


# ------------------------------------------- the loop, on the CPU, tiny


TINY_MIX = lambda n_dev: {"num_envs": 2 * n_dev, "unroll_len": 16,
                          "token_task": [64, 12, 32, 1, 2]}


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    for kind in ("configs", "traffic"):
        (tmp_path / kind).mkdir()
    n_dev = len(jax.devices())
    shape = keye_moe.SHAPES["keye_moe_tiny"]
    model = dataclasses.asdict(shape)

    def write(how, episode_seed=None, **more):
        (tmp_path / "traffic" / "tiny_tokens.json").write_text(json.dumps({
            **({} if episode_seed is None else {"episode_seed": episode_seed}),
            "overrides": TINY_MIX(n_dev)}))
        (tmp_path / "configs" / "tiny_keye.json").write_text(json.dumps({
            "name": "tiny_keye", "loop": "anakin_keye", "preset": "keye_moe_tiny",
            "overrides": {"precision": "f32", "updates_per_call": 1},
            "model": model, "parameters": keye_counts.parameters(model),
            "reference_env_block": n_dev // 2 or 1, "warm_in_fragments": 2,
            "reference_how": how, **more}))

    real = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        **real, "configs": [],
        "workloads": [{"name": CELL, "config": "tiny_keye",
                       "traffic": "tiny_tokens", "chips": 1, "why": "test"}]}))

    def on_the_cpu(chips):
        devices = jax.devices()
        return {"platform": devices[0].platform, "kind": devices[0].device_kind,
                "count": len(devices), "cache_dir": None}

    monkeypatch.setattr(device, "require_chips", on_the_cpu)
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path / "_out"))
    args = ["--spec", str(tmp_path / "BENCHMARK.json"), "--data-root",
            str(tmp_path), "--workload", CELL, "--seconds", "1"]
    return write, args


def _last_line(capsys) -> dict:
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    line["stderr"] = captured.err
    return line


@pytest.mark.parametrize("trace", [0, 1])
def test_the_loop_rehearsed_end_to_end_is_correct(tiny, capsys, monkeypatch, trace):
    """The untraced run on a mix without an ``episode_seed`` (the actor state
    stays the one ``make_agent`` built), the traced one on a mix with one:
    the boundaries it then counts, update by update, are those the
    simulation of that seed counts without any policy."""
    write, args = tiny
    episode_seed = 7 if trace else None
    write({}, episode_seed=episode_seed)
    drawn = []
    helper = common.with_episode_seed
    monkeypatch.setattr(common, "with_episode_seed", lambda *a: (
        drawn.append(a[-1]), helper(*a))[1])
    # a large seed: the driver's are a little over 2**31
    assert run.main([*args, "--seed", "2400000013", "--trace", str(trace)]) == 0
    line = _last_line(capsys)
    stderr = line.pop("stderr")
    assert drawn == ([episode_seed] if trace else [])
    counted = ast.literal_eval(re.search(
        r"by update, from the warm-up call: episode boundaries (\[.*?\])",
        stderr).group(1))
    assert len(counted) == line["attempted"] + 1
    if trace:
        n_dev, T = len(jax.devices()), 16
        env = registry.make("JaxTokenTask-v0", run.program_config(
            {"preset": "keye_moe_tiny"}, {"overrides": TINY_MIX(n_dev)}, 0))
        done = np.asarray(episode_draw.simulate(
            env, 2 * n_dev, n_dev, episode_seed, (2 + len(counted)) * T))
        # after the warm-in's two fragments, a fragment an update
        assert counted == done.reshape(-1, T, 2 * n_dev).sum((1, 2))[2:].tolist()
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device",
                          "compared"]
    assert line["correct"] is True, stderr[-3000:]
    # every reading that decided it beside its limit: in the line, and as the
    # last lines on stderr
    compared = line["compared"]
    assert {"replay_gap", "rows_before_l0", "rows_after_l1", "select_gap_l0",
            "select_extra_l1", "select_size_l0", "logp_mean", "logp_rms", "kl",
            "value_loss", "entropy", "indexer_kl", "dsa_rows_scored",
            "grad_head", "step_indexer", "loss", "leaves_stuck",
            "compiles_in_window", "updates_not_executed"} <= set(compared)
    assert all(value <= limit for value, limit in compared.values())
    last = stderr.strip().splitlines()[-len(compared):]
    assert [ln.split()[2].rstrip(":") for ln in last] == list(compared)
    assert all(ln.startswith("benchmarks: compared ") for ln in last)
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert "after a warm-in of 2 fragments" in stderr
    assert "indexer-key rows up to len" in stderr and "selection" in stderr
    if trace:
        # no chip, so no device trace: only the counters have something to read
        assert set(COUNTERS) <= set(line["metrics"])
        assert not set(line["metrics"]) & {
            *SCOPES, "keye_step_mfu", "keye_rollout_hbm_roofline"}
        got = {k: line["metrics"][k]["value"] for k in COUNTERS}
        # the warm-in did its work: queries have more rows than they may attend
        assert got["dsa_pruned_share"] > 0.25
        assert got["dsa_rows_selected"] <= 8 < got["dsa_rows_scored"]
        assert got["indexer_kl"] > 0
    else:
        assert set(line["metrics"]) == {"env_frames_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())


# (the five wrong references against the program itself, without the loop:
# tests/test_keye_moe.py)
@pytest.mark.parametrize("how", [
    {"topk": 4},  # half the rows
    {"router": "sigmoid"},  # the other two policies' router
])
def test_a_wrong_reference_is_not_correct(tiny, capsys, how):
    write, args = tiny
    write(how)
    assert run.main([*args, "--seed", "5", "--trace", "0"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is False
    assert "not correct" in line["stderr"]
    over = [k for k, (value, limit) in line["compared"].items() if not value <= limit]
    assert over and list(line)[-2:] == ["compared", "stderr"]


def test_the_reference_in_bfloat16_in_the_programs_place_is_not_correct(
        tiny, capsys):
    """The precision control: the reference in bfloat16 throughout, held to
    the float32 reference by the loop's own limits in the program's place."""
    write, args = tiny
    write({}, stand_in={"low": True})
    assert run.main([*args, "--seed", "5", "--trace", "0"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is False
    assert "A CONTROL, not the program" in line["stderr"]
    # the update's own rollout is still held to its replay, and was it
    assert "did not train on the replayed fragment" not in line["stderr"]
