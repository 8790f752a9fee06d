"""The sequence-policy cell: its counts against hand-counted tiny shapes,
its loop end to end on the CPU at the tiny preset through
``benchmarks.run.main`` (``correct`` true; false when the reference is fed
a wrong decay or a wrong held-expert set, and false when the program's
update is not the one its configuration says), its metric files, and its
configuration and traffic files against the program."""

import dataclasses
import json
import os
import types

import jax
import pytest

from asyncrl_tpu.configs import presets
from asyncrl_tpu.envs import registry
from asyncrl_tpu.learn import learner as learner_mod
from asyncrl_tpu.models import kimi_linear
from asyncrl_tpu.models.networks import build_model
from benchmarks import device, readers, run, seq_counts, seq_readers
from benchmarks.loops import anakin_seq

CELL = "kimi_linear_rl.anakin_64x256"
NEW_METRICS = {
    "seq_step_mfu", "kda_device_ms", "kda_step_device_ms",
    "kda_chunk_device_ms", "mla_device_ms", "moe_device_ms",
    "lm_head_device_ms", "rollout_hbm_roofline", "kda_step_roofline",
    "moe_load_max_over_mean", "episode_resets_per_update",
}
HAND = {  # a shape small enough to count by hand
    "hidden": 4, "vocab": 10, "layers": ["kda+dense", "mla+moe"],
    "kda_heads": 2, "kda_head_dim": 3, "mla_heads": 2, "qk_nope": 3,
    "qk_rope": 1, "v_head": 2, "kv_lora": 5, "dense_ffn": 6, "expert_ffn": 2,
    "num_experts": 8, "held_experts": [0, 1], "top_k": 2, "routed_scale": 1.0,
    "max_positions": 8, "conv_width": 4, "low_rank": 2, "eps": 1e-5,
    "chunk": 16, "block_tokens": 64,
}


def test_counts_of_a_shape_counted_by_hand():
    d = HAND
    # KDA, n = 6: qkv 4*18, f and g pairs 2*(4*2 + 2*6), beta 4*2, o 6*4
    assert seq_counts.kda_projection_flops(d) == 2 * (72 + 40 + 8 + 24) + 2 * 4 * 18
    assert seq_counts.kda_step_flops(d) == 7 * 2 * 9
    assert seq_counts.kda_chunk_flops(d) == 2 * (2 * 16 * 15 + 6 * 9)
    # MLA at 5 attended positions: q 4*2*4, kv_a 4*6, kv_b 5*2*5, o 2*2*4
    assert seq_counts.mla_flops(d, 5) == 2 * (32 + 24 + 50 + 16) + 2 * 2 * 5 * 6
    assert seq_counts.ffn_flops(d, "dense", 0.5) == 2 * 3 * 4 * 6
    # router 4*8, the shared expert and half an assignment on held experts
    assert seq_counts.ffn_flops(d, "moe", 0.5) == 2 * 32 + 1.5 * 2 * 3 * 4 * 2
    fwd = seq_counts.forward_flops_per_token(d, 5, 0.5, "step")
    assert fwd == 2 * 4 * 11 + (432 + 126) + 144 + 364 + 136
    assert seq_counts.train_flops_per_update(d, 7, 5, 0.5) == 7 * (
        fwd + 3 * seq_counts.forward_flops_per_token(d, 5, 0.5, "fragment"))
    p = seq_counts.parameters(d)
    kda = 72 + 72 + 40 + 6 + 2 + 8 + 3 + 24
    mla = 32 + 24 + 5 + 50 + 16
    assert p["layers"] == (8 + kda + 72) + (8 + mla + 32 + 8 + 3 * 24)
    assert p["total"] == p["layers"] + 40 + 40 + 5 + 4
    # the state (2 heads of 3 x 3) and the conv's tail (3 rows of 18) an env,
    # float32, read and written
    assert seq_counts.kda_carry_bytes(d, 3) == 2 * 4 * 1 * 3 * (2 * 9 + 3 * 18)
    # one of each: weights but the embedding at 2 bytes + 3 embedding rows,
    # the KDA carry, 6 latent rows of 6
    assert seq_counts.decode_bytes_per_step(d, 3, 5) == (
        (p["total"] - 40) * 2 + 3 * 4 * 4 + 432 + 2 * 4 * 3 * 3 * 18 + 3 * 6 * 6 * 2)
    # a fixed length l attends (l + 1) / 2 positions on average
    assert seq_counts.mean_attended_positions(9, 9.0000001) == pytest.approx(5.0)


def test_counts_agree_with_the_tree_the_program_builds():
    for name, shape in kimi_linear.SHAPES.items():
        built = jax.eval_shape(
            kimi_linear.SeqPolicy(shape).init, jax.random.PRNGKey(0))
        n = sum(x.size for x in jax.tree.leaves(built))
        assert seq_counts.parameters(dataclasses.asdict(shape))["total"] == n, name


@pytest.fixture(scope="module")
def spec():
    return run.Spec(os.path.join(run.ROOT, "BENCHMARK.json"), [run.BENCH_DIR])


def test_the_configuration_file_is_the_published_config_and_the_cut(spec):
    doc = spec.load("configs", "kimi_linear_rl")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert doc["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value and doc[key] != value
        else:
            assert doc[key] == value, key
    assert (doc["num_hidden_layers"], doc["num_experts"], doc["vocab_size"]) == (
        5, 8, 20480)
    cfg = run.program_config(doc, spec.load("traffic", "anakin_64x256"), 3)
    shape = kimi_linear.SHAPES[cfg.seq_model]
    # no width is cut: every width the program builds is the published one
    lin = row["config"]["linear_attn_config"]
    assert (shape.hidden, shape.dense_ffn, shape.expert_ffn, shape.kv_lora) == (
        doc["hidden_size"], doc["intermediate_size"],
        doc["moe_intermediate_size"], doc["kv_lora_rank"])
    assert (shape.kda_heads, shape.kda_head_dim, shape.conv_width) == (
        lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"])
    assert (shape.mla_heads, shape.qk_nope, shape.qk_rope, shape.v_head) == (
        doc["num_attention_heads"], doc["qk_nope_head_dim"],
        doc["qk_rope_head_dim"], doc["v_head_dim"])
    assert (shape.num_experts, shape.top_k, shape.routed_scale) == (
        doc["published"]["num_experts"], doc["num_experts_per_token"],
        doc["routed_scaling_factor"])
    assert len(shape.held_experts) == doc["num_experts"]
    assert len(shape.layers) == doc["num_hidden_layers"]
    assert shape.vocab == doc["vocab_size"] == doc["published"]["vocab_size"] // 8
    assert [k.startswith("mla") for k in shape.layers] == [
        i in lin["full_attn_layers"] for i in range(1, 6)]
    assert shape.layers[0].endswith("dense") and doc["first_k_dense_replace"] == 1
    assert doc["parameters"] == seq_counts.parameters(doc["model"])
    assert doc["parameters"]["total"] == 602436737
    # the traffic is the parameters the cell was asked with, and the files
    # agree with the program (the loop refuses to run otherwise)
    assert (cfg.num_envs, cfg.unroll_len, cfg.updates_per_call) == (64, 256, 1)
    env = registry.make(cfg.env_id, cfg)
    assert (env.vocab, env.min_len, env.max_len, env.min_prompt, env.max_prompt) == (
        20480, 64, 1024, 8, 32)
    anakin_seq.check_files_agree(cfg, doc)


def test_every_new_metric_resolves_to_a_reader_in_the_new_cell_only(spec):
    mine = {m["name"]: m for m in spec.doc["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == NEW_METRICS
    for name in NEW_METRICS:
        read, params = spec.reader(name)
        assert callable(read) and isinstance(params, dict)
    in_cell = {m["name"] for m in spec.metrics_of("per_layer", CELL)}
    assert NEW_METRICS <= in_cell
    # the accepted metrics with nothing to read here stay with their cells
    assert not in_cell & {"render_device_ms", "section0_device_ms",
                          "max_pool_device_ms", "model_flops_util"}
    assert {"rollout_device_ms", "loss_and_grad_device_ms", "hbm_peak_gb",
            "device_idle_share", "fused_vtrace_roofline"} <= in_cell


@pytest.mark.parametrize("name, scope", [
    ("render_device_ms", "vmap(render)"),
    ("section0_device_ms", "section0"),
    ("max_pool_device_ms", "max_pool"),
])
def test_a_cnn_scope_metric_keeps_its_reader_and_lists_the_cnn_cells(
        spec, name, scope):
    """What ``test_metric_resolves_to_its_reader`` holds of these three,
    with the list ISSUE 26 gave them in place of "every cell"."""
    entry = next(m for m in spec.doc["per_layer"] if m["name"] == name)
    read, params = spec.reader(name)
    assert read is readers.scope_device_ms and params == {"scope": scope}
    assert entry["source"] == "device_trace"
    assert entry["moves"] == "env_frames_per_s"
    assert entry["workloads"] == ["atari_impala.dp1", "atari_impala.dp4"]


def test_the_shares_read_a_trace_and_give_nothing_without_one():
    dims = dataclasses.asdict(kimi_linear.SHAPES["kimi_linear_5l"])
    ms = {"rollout": 900.0, "kda_step": 400.0}
    chip = types.SimpleNamespace(scope_ps=lambda s: ms.get(s, 0.0) * 1e9 * 2)
    ev = {
        "trace": types.SimpleNamespace(devices=[chip], busy_s=2.6, window_s=3.0),
        "traced_updates": 2, "chips": 1, "peaks": device.peaks("TPU v5 lite"),
        "geometry": {"num_envs": 64, "unroll_len": 256},
        "seq": {"dims": dims, "attended": 272.5, "held_per_token": 0.25},
    }
    flops = seq_counts.train_flops_per_update(dims, 16384, 272.5, 0.25)
    assert seq_readers.seq_step_mfu(ev) == pytest.approx(
        100 * 2 * flops / 2.6 / 197e12)
    assert 0 < seq_readers.seq_step_mfu(ev) < 100
    carry = seq_counts.kda_carry_bytes(dims, 64)
    assert carry == 2 * 4 * 4 * 64 * (32 * 128 * 128 + 3 * 3 * 32 * 128)
    assert seq_readers.kda_step_roofline(ev) == pytest.approx(
        100 * carry * 256 / 819e9 * 1e3 / 400.0)
    assert seq_readers.rollout_hbm_roofline(ev) == pytest.approx(
        100 * seq_counts.decode_bytes_per_step(dims, 64, 272.5) * 256
        / 819e9 * 1e3 / 900.0)
    for value in (seq_readers.kda_step_roofline(ev),
                  seq_readers.rollout_hbm_roofline(ev)):
        assert 0 < value < 100
    # a program without the policy, or a run without a trace: nothing
    for lacking in ({**ev, "trace": None}, {k: v for k, v in ev.items() if k != "seq"}):
        assert seq_readers.seq_step_mfu(lacking) is None
        assert seq_readers.kda_step_roofline(lacking) is None
        assert seq_readers.rollout_hbm_roofline(lacking) is None


# ------------------------------------------- the loop, on the CPU, tiny


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    for kind in ("configs", "traffic"):
        (tmp_path / kind).mkdir()
    n_dev = len(jax.devices())
    shape = kimi_linear.SHAPES["kimi_linear_tiny"]

    def write(how):
        (tmp_path / "configs" / "tiny_seq.json").write_text(json.dumps({
            "name": "tiny_seq", "loop": "anakin_seq", "preset": "kimi_linear_tiny",
            "overrides": {"precision": "f32", "updates_per_call": 1},
            "model": dataclasses.asdict(shape), "reference_env_block": n_dev // 2,
            "reference_how": how}))

    (tmp_path / "traffic" / "tiny_tokens.json").write_text(json.dumps({
        "overrides": {"num_envs": n_dev, "unroll_len": 32,
                      "token_task": [64, 2, 32, 1, 2]}}))
    real = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        **real, "configs": [],
        "workloads": [{"name": CELL, "config": "tiny_seq",
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
def test_the_loop_rehearsed_end_to_end_is_correct(tiny, capsys, trace):
    write, args = tiny
    write({})
    assert run.main([*args, "--seed", "2400000011", "--trace", str(trace)]) == 0
    line = _last_line(capsys)
    stderr = line.pop("stderr")
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True, stderr[-3000:]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert "plain float32 reference" in stderr and "behaviour_logp" in stderr
    if trace:
        # no chip, so no device trace: only the counters have something to read
        assert {"moe_load_max_over_mean", "episode_resets_per_update"} <= set(
            line["metrics"])
        assert not set(line["metrics"]) & {
            "seq_step_mfu", "kda_step_roofline", "rollout_hbm_roofline",
            "kda_device_ms"}
        assert line["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
        assert line["metrics"]["episode_resets_per_update"]["value"] > 0
    else:
        assert set(line["metrics"]) == {"env_frames_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("how", [
    {"decay_scale": 1.25},  # a wrong decay in every KDA layer
    {"held": [0, 1, 2]},  # one held expert's part left out
])
def test_a_wrong_reference_is_not_correct(tiny, capsys, how):
    write, args = tiny
    write(how)
    assert run.main([*args, "--seed", "5", "--trace", "0"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is False
    assert "not correct" in line["stderr"]


def _twice_the_rate(config):
    return learner_mod.optax.chain(
        learner_mod.optax.clip_by_global_norm(config.max_grad_norm),
        learner_mod.optax.rmsprop(
            2 * config.learning_rate, decay=config.rmsprop_decay,
            eps=config.rmsprop_eps),
    )


def _value_head_left_out(config):
    return learner_mod.optax.chain(
        _real_optimizer(config),
        learner_mod.optax.masked(
            learner_mod.optax.set_to_zero(),
            lambda p: jax.tree_util.tree_map_with_path(
                lambda path, _: "value" in jax.tree_util.keystr(path), p)),
    )


_real_optimizer = learner_mod.make_optimizer


@pytest.mark.parametrize("optimizer, reason", [
    (_twice_the_rate, "step on 'head'"),
    (_value_head_left_out, "takes: [\"['params']['value']['bias']\", \"['params']['value']['kernel']"),
])
def test_an_update_that_is_not_the_configured_one_is_not_correct(
        tiny, capsys, monkeypatch, optimizer, reason):
    """The program at fault, not the reference: its first update is held to
    the step the optimizer's rule makes of the reference's gradient."""
    write, args = tiny
    write({})
    monkeypatch.setattr(learner_mod, "make_optimizer", optimizer)
    assert run.main([*args, "--seed", "6", "--trace", "0"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is False
    assert reason in line["stderr"]


def test_the_configuration_must_be_the_model_the_program_builds():
    cfg = presets.get("kimi_linear_tiny")
    model = json.loads(json.dumps(
        dataclasses.asdict(kimi_linear.SHAPES["kimi_linear_tiny"])))
    anakin_seq.check_files_agree(cfg, {"model": model})
    with pytest.raises(SystemExit, match="model record"):
        anakin_seq.check_files_agree(cfg, {"model": {**model, "hidden": 128}})


def test_the_mix_sets_the_task_and_the_model_holds_it_to_its_positions():
    """The env's parameters are the mix's ``token_task`` override, and a
    sequence policy refuses episodes longer than the positions it holds."""
    cfg = presets.get("kimi_linear_tiny").replace(token_task=(64, 3, 16, 2, 4))
    env = registry.make(cfg.env_id, cfg)
    assert (env.vocab, env.min_len, env.max_len, env.min_prompt, env.max_prompt) == (
        64, 3, 16, 2, 4)
    assert env.spec.max_episode_steps == 16
    build_model(cfg, env.spec)
    too_long = cfg.replace(token_task=(64, 3, 33, 2, 4))
    with pytest.raises(ValueError, match="positions"):
        build_model(too_long, registry.make(cfg.env_id, too_long).spec)
