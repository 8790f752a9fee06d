"""The ``moonlight_rl`` cell: its counts against hand-counted tiny shapes, its
loop end to end on the CPU at the tiny preset through ``benchmarks.run.main``
(a warm-in, then ``correct`` true; false when the reference rotates nothing,
rotates at another base or keeps one shared expert of two, and when the
bfloat16 reference stands in the program's place), its metric files, and its
configuration and traffic files against the program and the catalog."""

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
from asyncrl_tpu.models import moonlight
from benchmarks import (
    device, episode_draw, moonlight_counts, moonlight_readers, program_record, readers,
    run)
from benchmarks.loops import anakin_moonlight, common

CELL = "moonlight_rl.anakin_16x512"
SCOPES = {
    "moonlight_mla_device_ms": "mla", "mla_step_device_ms": "mla_step",
    "mla_expand_device_ms": "mla_expand", "mla_attend_device_ms": "mla_attend",
    "moonlight_moe_device_ms": "moe",
}
COUNTERS = {"mla_rows_attended": "mla_rows_attended",
            "mla_rows_expanded": "mla_rows_expanded"}
NEW_METRICS = {*SCOPES, *COUNTERS, "moonlight_step_mfu", "moonlight_rollout_hbm_roofline"}
# The model's config.json as published (the model catalog's row) and its source.
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408, "moe_layer_freq": 1,
    "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 2,
    "norm_topk_prob": True, "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16,
    "num_nextn_predict_layers": 0, "q_lora_rank": None, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 50000,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 163840,
}
SOURCE = "https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json"
HAND = {  # a shape small enough to count by hand
    "hidden": 4, "vocab": 10, "layers": ["mla+dense", "mla+moe"],
    "mla_heads": 2, "qk_nope": 3, "qk_rope": 2, "v_head": 3, "kv_lora": 5,
    "rope_theta": 50000.0, "dense_ffn": 6, "expert_ffn": 2, "shared_ffn": 4,
    "num_experts": 8, "held_experts": [0, 1], "top_k": 2, "routed_scale": 2.446,
    "max_positions": 8, "eps": 1e-5, "block_tokens": 64,
}


def test_counts_of_a_shape_counted_by_hand():
    d = HAND
    # q 4*2*5, kv_a 4*7, o 6*4
    assert moonlight_counts.projection_flops(d) == 2 * (40 + 28 + 24)
    # kv_b: 5 into 2 heads x (3 + 3)
    assert moonlight_counts.up_projection_flops(d) == 2 * 5 * 12
    # 2 heads x 7 rows x (3 + 2 + 3)
    assert moonlight_counts.attention_flops(d, 7) == 2 * 2 * 7 * 8
    # 2 heads x (3*5 into the latent, 7 x (5 + 2) scores, 7 x 5 values, 5*3 out)
    assert moonlight_counts.absorbed_flops(d, 7) == 2 * 2 * (15 + 49 + 35 + 15)
    assert moonlight_counts.ffn_flops(d, "mla+dense", 0.5) == 2 * 3 * 4 * 6
    # router 4*8, shared 3*4*4, half an assignment of 3*4*2
    assert moonlight_counts.ffn_flops(d, "mla+moe", 0.5) == 2 * (32 + 48 + 12)
    rest = 2 * (40 + 28 + 24) * 2 + 2 * 72 + 2 * 92 + 2 * 4 * 11
    assert moonlight_counts.rollout_flops(d, 3, 7, 0.5) == 3 * (rest + 2 * 2 * 2 * 114)
    # 2 envs x 3 tokens; each env's 4 cached + 3 rows up-projected once a layer
    learner = 6 * (rest + 2 * 2 * 2 * 7 * 8) + 2 * 2 * (4 + 3) * 120
    assert moonlight_counts.learner_forward_flops(d, 2, 3, 7, 4, 0.5) == learner
    assert moonlight_counts.train_flops_per_update(d, 2, 3, 7, 4, 0.5) == (
        moonlight_counts.rollout_flops(d, 6, 7, 0.5) + 3 * learner)
    p = moonlight_counts.parameters(d)
    attention = 40 + 28 + 5 + 60 + 24
    assert p["attention"] == 2 * attention and p["dense"] == 72
    assert p["experts"] == 2 * 3 * 4 * 2 and p["shared"] == 48 and p["router"] == 40
    assert p["layers"] == 2 * (8 + attention) + 72 + 48 + 48 + 40
    assert p["total"] == p["layers"] + 40 + 40 + 5 + 4
    # weights but the embedding at 2 bytes + 3 embedding rows; per layer and
    # env 8 latent rows of 7 at 2 bytes
    assert moonlight_counts.decode_bytes_per_step(d, 3, 7) == (
        (p["total"] - 40) * 2 + 3 * 4 * 4 + 2 * 3 * 8 * 14)


def test_counts_agree_with_the_tree_the_program_builds():
    for name, shape in moonlight.SHAPES.items():
        built = jax.eval_shape(
            moonlight.MoonlightPolicy(shape).init, jax.random.PRNGKey(0))
        n = sum(x.size for x in jax.tree.leaves(built))
        assert moonlight_counts.parameters(dataclasses.asdict(shape))["total"] == n, name


@pytest.fixture(scope="module")
def spec():
    return run.Spec(os.path.join(run.ROOT, "BENCHMARK.json"), [run.BENCH_DIR])


def test_the_configuration_file_is_the_published_config_and_the_cut(spec):
    doc = spec.load("configs", "moonlight_rl")
    assert doc["source"] == SOURCE
    for key, value in PUBLISHED.items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value and doc[key] != value
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (doc["num_hidden_layers"], doc["n_routed_experts"], doc["vocab_size"]) == (
        5, 8, 20480)
    cfg = run.program_config(doc, spec.load("traffic", "anakin_16x512_v20480"), 3)
    shape = moonlight.SHAPES[cfg.seq_model]
    # no width is cut: every width the program builds is the published one
    assert (shape.hidden, shape.dense_ffn, shape.expert_ffn) == (
        doc["hidden_size"], doc["intermediate_size"], doc["moe_intermediate_size"])
    assert shape.shared_ffn == doc["n_shared_experts"] * doc["moe_intermediate_size"]
    assert (shape.mla_heads, shape.qk_nope, shape.qk_rope, shape.v_head, shape.kv_lora) == (
        doc["num_attention_heads"], doc["qk_nope_head_dim"], doc["qk_rope_head_dim"],
        doc["v_head_dim"], doc["kv_lora_rank"])
    assert doc["q_lora_rank"] is None and doc["num_key_value_heads"] == shape.mla_heads
    assert (shape.rope_theta, shape.eps) == (doc["rope_theta"], doc["rms_norm_eps"])
    assert (shape.num_experts, shape.top_k, shape.routed_scale) == (
        doc["published"]["n_routed_experts"], doc["num_experts_per_tok"],
        doc["routed_scaling_factor"])
    assert (doc["scoring_func"], doc["topk_method"], doc["n_group"], doc["topk_group"]) == (
        "sigmoid", "noaux_tc", 1, 1)
    assert len(shape.held_experts) == doc["n_routed_experts"] >= 8
    assert shape.layers == ("mla+dense",) * doc["first_k_dense_replace"] + (
        "mla+moe",) * (doc["num_hidden_layers"] - 1)
    assert shape.vocab == doc["vocab_size"] == doc["published"]["vocab_size"] // 8
    assert shape.max_positions == doc["max_position_embeddings"]
    assert doc["held_here"] == {
        "layers": [0, 1, 2, 3, 4], "experts": list(range(8)), "vocab_rows": [0, 20480]}
    assert "8 chips" in doc["deployment"]
    assert doc["parameters"] == moonlight_counts.parameters(doc["model"])
    assert doc["parameters"]["total"] == 568486657
    for key in ("rope_scaling", "rope_pairing", "attention", "kv_cache", "router",
                "shared_experts", "seq_aux", "mtp", "ep_size", "embedding_and_head",
                "weights", "value_head", "optimizer", "blocks", "precision", "env_id",
                "actor_staleness", "described_as"):
        assert doc["assumed"][key]
    # the traffic is the parameters the cell was asked with, and the files
    # agree with the program (the loop refuses to run otherwise)
    assert (cfg.num_envs, cfg.unroll_len, cfg.updates_per_call) == (16, 512, 1)
    assert cfg.actor_staleness == 2 and cfg.optimizer == "rmsprop" and cfg.donate_buffers
    env = registry.make(cfg.env_id, cfg)
    assert (env.vocab, env.min_len, env.max_len, env.min_prompt, env.max_prompt) == (
        20480, 2048, 8192, 32, 128)
    assert shape.max_positions == env.max_len
    # the warm-in is as long as the longest episode
    assert doc["warm_in_fragments"] * cfg.unroll_len == env.max_len
    anakin_moonlight.check_files_agree(cfg, doc)
    with pytest.raises(SystemExit, match="model record"):
        anakin_moonlight.check_files_agree(
            cfg, {"model": {**doc["model"], "hidden": 128}})
    with pytest.raises(SystemExit, match="parameters"):
        anakin_moonlight.check_files_agree(cfg, {**doc, "parameters": {}})


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
    assert spec.reader("moonlight_step_mfu")[0] is moonlight_readers.moonlight_step_mfu
    assert spec.reader("moonlight_rollout_hbm_roofline")[0] is (
        moonlight_readers.moonlight_rollout_hbm_roofline)
    in_cell = {m["name"] for m in spec.metrics_of("per_layer", CELL)}
    assert NEW_METRICS <= in_cell
    # the accepted metrics without a list read this cell as they read the
    # others; those of the CNN and of the other sequence policies stay away
    assert {"rollout_device_ms", "loss_and_grad_device_ms", "hbm_peak_gb",
            "device_idle_share", "actor_forward_device_ms", "env_step_device_ms",
            "fused_vtrace_roofline", "step_trace_lower_s", "make_agent_s",
            "optimizer_device_ms", "publish_device_ms", "update_rest_device_ms",
            "setup_checkpoint_s"} <= in_cell
    assert not in_cell & {"render_device_ms", "model_flops_util", "seq_step_mfu",
                          "mla_device_ms", "moe_device_ms", "keye_step_mfu",
                          "lfm2_step_mfu", "gqa_step_device_ms", "prefetch_wait_device_ms"}
    for cell in (w["name"] for w in spec.doc["workloads"]):
        if cell != CELL:
            assert not NEW_METRICS & {
                m["name"] for m in spec.metrics_of("per_layer", cell)}


def test_make_agent_programs_is_read_in_the_new_cell(spec):
    """What ``test_benchmark_keye.py``'s case of every cell holds, for this
    cell: the entry lists no cells, so a cell that a later PR adds reads it."""
    entry = next(m for m in spec.doc["per_layer"] if m["name"] == "make_agent_programs")
    assert spec.reader("make_agent_programs")[0] is program_record.programs_in_phase
    assert "workloads" not in entry
    names = [w["name"] for w in spec.doc["workloads"]]
    assert names[-1] == CELL and names.count(CELL) == 1
    assert [w["chips"] for w in spec.doc["workloads"] if w["name"] == CELL] == [1]
    for cell in names:
        assert entry in spec.metrics_of("per_layer", cell)


def test_the_shares_read_a_trace_and_give_nothing_without_one():
    dims = dataclasses.asdict(moonlight.SHAPES["moonlight_5l"])
    ms = {"rollout": 1500.0}
    chip = types.SimpleNamespace(scope_ps=lambda s: ms.get(s, 0.0) * 1e9 * 2)
    ev = {
        "trace": types.SimpleNamespace(devices=[chip], busy_s=5.0, window_s=5.5),
        "traced_updates": 2, "chips": 1, "peaks": device.peaks("TPU v5 lite"),
        "geometry": {"num_envs": 16, "unroll_len": 512},
        "moonlight": {"dims": dims, "attended": 2560.0, "cached": 2300.0,
                      "held_per_token": 0.75},
    }
    flops = moonlight_counts.train_flops_per_update(dims, 16, 512, 2560.0, 2300.0, 0.75)
    assert moonlight_readers.moonlight_step_mfu(ev) == pytest.approx(
        100 * 2 * flops / 5.0 / 197e12)
    per_step = moonlight_counts.decode_bytes_per_step(dims, 16, 2560.0)
    assert moonlight_readers.moonlight_rollout_hbm_roofline(ev) == pytest.approx(
        100 * per_step * 512 / 819e9 * 1e3 / 1500.0)
    for value in (moonlight_readers.moonlight_step_mfu(ev),
                  moonlight_readers.moonlight_rollout_hbm_roofline(ev)):
        assert 0 < value < 100
    # the weights are most of what a decode step must move, the latent rows
    # of the episodes the rest; the cache's capacity would be 3.2 x those rows
    p = moonlight_counts.parameters(dims)
    assert 0.75 < (p["total"] - p["embed"]) * 2 / per_step < 0.85
    # a program without the policy (the parent's), a run without a trace, or
    # a trace without the scope: nothing, and nothing raised
    for lacking in ({**ev, "trace": None},
                    {k: v for k, v in ev.items() if k != "moonlight"}):
        assert moonlight_readers.moonlight_step_mfu(lacking) is None
        assert moonlight_readers.moonlight_rollout_hbm_roofline(lacking) is None
    ms.clear()
    assert moonlight_readers.moonlight_rollout_hbm_roofline(ev) is None


def test_the_committed_episode_seed_is_the_smallest_the_rule_admits(spec):
    """The mix's rule at its vocabulary: the mean rows behind a token
    over the 8 x 512 steps that follow the warm-in, from cold, within 2% of
    the length law's stationary mean. The lengths do not read the
    vocabulary, so the draw is ``anakin_16x512``'s."""
    mix = spec.load("traffic", "anakin_16x512_v20480")
    doc = spec.load("configs", "moonlight_rl")
    cfg = run.program_config(doc, mix, 3)
    env = registry.make(cfg.env_id, cfg)
    assert "episode_seed" not in mix["overrides"]
    last = 8 * cfg.unroll_len
    seed, rows = episode_draw.smallest_seed(
        env, cfg.num_envs, 1, doc["warm_in_fragments"] * cfg.unroll_len + last,
        last, 0.02, candidates=mix["episode_seed"] + 1)
    assert seed == mix["episode_seed"] == 24 == len(rows) - 1
    assert abs(rows[seed] - 2560) <= 51.2
    assert (np.abs(rows[:seed] - 2560) > 51.2).all()


# ------------------------------------------- the loop, on the CPU, tiny


TINY_MIX = lambda n_dev: {"num_envs": 2 * n_dev, "unroll_len": 16,
                          "token_task": [64, 12, 32, 1, 2]}


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    for kind in ("configs", "traffic"):
        (tmp_path / kind).mkdir()
    n_dev = len(jax.devices())
    model = dataclasses.asdict(moonlight.SHAPES["moonlight_tiny"])

    def write(how, **more):
        (tmp_path / "traffic" / "tiny_tokens.json").write_text(json.dumps({
            "episode_seed": 7, "overrides": TINY_MIX(n_dev)}))
        (tmp_path / "configs" / "tiny_moonlight.json").write_text(json.dumps({
            "name": "tiny_moonlight", "loop": "anakin_moonlight",
            "preset": "moonlight_tiny",
            "overrides": {"precision": "f32", "updates_per_call": 1},
            "model": model, "parameters": moonlight_counts.parameters(model),
            "reference_env_block": n_dev // 2 or 1, "warm_in_fragments": 2,
            "reference_how": how, **more}))

    real = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        **real, "configs": [],
        "workloads": [{"name": CELL, "config": "tiny_moonlight",
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


def test_the_loop_rehearsed_end_to_end_is_correct(tiny, capsys, monkeypatch):
    write, args = tiny
    write({})

    class NoProfiler:  # the CPU's profile says nothing a metric reads
        def __init__(self, out_dir):
            pass

        start = stop = load = lambda self: None

    monkeypatch.setattr(common, "Profiler", NoProfiler)
    # a large seed: the benchmark's run a little over 2**31
    assert run.main([*args, "--seed", "2400000013", "--trace", "1"]) == 0
    line = _last_line(capsys)
    stderr = line.pop("stderr")
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device",
                          "compared"]
    assert line["correct"] is True, stderr[-3000:]
    compared = line["compared"]
    assert {"replay_gap", "rows_before_l0", "rows_after_l2", "logp_mean", "logp_rms",
            "kl", "value_loss", "entropy", "mla_rows_attended", "mla_rows_cached",
            "mla_rows_expanded", "grad_head", "grad_rope", "step_rope", "loss",
            "leaves_stuck", "leaves_unreached", "compiles_in_window",
            "updates_not_executed"} <= set(compared)
    assert all(value <= limit for value, limit in compared.values())
    last = stderr.strip().splitlines()[-len(compared):]
    assert [ln.split()[2].rstrip(":") for ln in last] == list(compared)
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert "after a warm-in of 2 fragments" in stderr
    # no device trace: only the counters have something to read
    assert set(line["metrics"]) & NEW_METRICS == set(COUNTERS)
    got = {k: line["metrics"][k]["value"] for k in COUNTERS}
    assert got["mla_rows_expanded"] == 32 + 16
    assert 1 < got["mla_rows_attended"] < got["mla_rows_expanded"]
    by_update = ast.literal_eval(re.search(
        r"mla_rows_attended (\[.*?\])", stderr).group(1))
    assert len(by_update) == line["attempted"] + 1


@pytest.mark.parametrize("how, stand_in", [
    ({"rope": False}, None),  # Kimi-Linear's NoPE
    ({"theta": 1e4}, None),
    ({"shared": 1}, None),  # one shared expert of 1,408 where there are two
    ({}, {"low": True}),  # the reference in bfloat16 in the program's place
])
def test_a_control_is_not_correct(tiny, capsys, how, stand_in):
    write, args = tiny
    write(how, **({"stand_in": stand_in} if stand_in else {}))
    assert run.main([*args, "--seed", "5", "--trace", "0"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is False
    assert "not correct" in line["stderr"]
    over = [k for k, (value, limit) in line["compared"].items() if not value <= limit]
    assert over and list(line)[-2:] == ["compared", "stderr"]
    # the update's own rollout is still held to its replay, and was it
    assert "did not train on the replayed fragment" not in line["stderr"]
    if stand_in:
        assert "A CONTROL, not the program" in line["stderr"]
    else:  # the rows a wrong reference rebuilds are not the program's
        assert any(k.startswith("rows_") for k in over), over
