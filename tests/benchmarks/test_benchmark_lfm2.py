"""The ``lfm2_moe_rl`` cell: its counts against hand-counted tiny shapes, its
loop end to end on the CPU at the tiny preset through ``benchmarks.run.main``
(``correct`` true; false when the reference is fed a wrong rotary base, no
q/k norm, no conv gate or a wrong held-expert set), its metric files, and
its configuration and traffic files against the program."""

import dataclasses
import json
import os
import types

import jax
import pytest

from asyncrl_tpu.envs import registry
from asyncrl_tpu.models import lfm2_moe
from benchmarks import device, lfm2_counts, lfm2_readers, readers, run
from benchmarks.loops import anakin_lfm2
from benchmarks.reference import lfm2_moe as reference

CELL = "lfm2_moe_rl.anakin_128x256"
NEW_METRICS = {
    "conv_mixer_device_ms", "gqa_device_ms", "moe_experts_device_ms",
    "lfm2_step_mfu", "lfm2_rollout_hbm_roofline", "moe_experts_roofline",
    "moe_dense_blocks", "gqa_rows_attended",
    # the accepted entries of these four list the other sequence cell only
    "lfm2_moe_device_ms", "lfm2_lm_head_device_ms",
    "lfm2_moe_load_max_over_mean", "lfm2_episode_resets_per_update",
}
HAND = {  # a shape small enough to count by hand
    "hidden": 4, "vocab": 10, "layers": ["conv+dense", "gqa+moe"],
    "heads": 4, "kv_heads": 2, "head_dim": 3, "rope_theta": 1e6,
    "dense_ffn": 6, "expert_ffn": 2, "num_experts": 8, "held_experts": [0, 1],
    "top_k": 2, "routed_scale": 1.0, "max_positions": 8, "conv_width": 3,
    "eps": 1e-5, "block_tokens": 64,
}


@pytest.fixture(autouse=True, scope="module")
def small_tiles():
    """Tiles of 8 rows on the expert layer's grouped side, so that the tiny
    preset's blocks of 128 tokens take it as the cell's blocks do."""
    from asyncrl_tpu.ops import moe

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "TILE", 8)
        yield


def test_counts_of_a_shape_counted_by_hand():
    d = HAND
    # conv: in 4*12, out 4*4, two gates of 4, a conv of 3 taps over 4
    assert lfm2_counts.conv_flops(d) == 2 * (48 + 16) + 8 + 2 * 3 * 4
    # attention at 5 rows: q 4*12, k and v 4*6 each, o 12*4; 4 heads x 5 x (3 + 3)
    assert lfm2_counts.gqa_flops(d, 5) == 2 * (48 + 24 + 24 + 48) + 2 * 4 * 5 * 6
    assert lfm2_counts.ffn_flops(d, "dense", 0.5) == 2 * 3 * 4 * 6
    # router 4*8 and half an assignment on held experts; no shared expert
    assert lfm2_counts.expert_flops(d) == 2 * 3 * 4 * 2
    assert lfm2_counts.ffn_flops(d, "moe", 0.5) == 2 * 32 + 0.5 * 48
    fwd = lfm2_counts.forward_flops_per_token(d, 5, 0.5)
    assert fwd == 2 * 4 * 11 + 160 + 144 + 528 + 88
    assert lfm2_counts.train_flops_per_update(d, 7, 5, 0.5) == 7 * 4 * fwd
    p = lfm2_counts.parameters(d)
    conv, gqa = 48 + 12 + 16, 48 + 24 + 24 + 6 + 48
    assert p["layers"] == (8 + conv + 72) + (8 + gqa + 32 + 8 + 2 * 24)
    assert p["experts"] == 48
    assert p["total"] == p["layers"] + 40 + 40 + 5 + 4
    # weights but the embedding at 2 bytes + 3 embedding rows; 6 rows of keys
    # and of values of 2 x 3 at 2 bytes an env; the conv's 2 x 4 tail, float32,
    # read and written
    assert lfm2_counts.decode_bytes_per_step(d, 3, 5) == (
        (p["total"] - 40) * 2 + 3 * 4 * 4 + 3 * 6 * 2 * 12 + 2 * 4 * 3 * 2 * 4)


def test_counts_agree_with_the_tree_the_program_builds():
    for name, shape in lfm2_moe.SHAPES.items():
        built = jax.eval_shape(
            lfm2_moe.Lfm2Policy(shape).init, jax.random.PRNGKey(0))
        n = sum(x.size for x in jax.tree.leaves(built))
        assert lfm2_counts.parameters(dataclasses.asdict(shape))["total"] == n, name


@pytest.fixture(scope="module")
def spec():
    return run.Spec(os.path.join(run.ROOT, "BENCHMARK.json"), [run.BENCH_DIR])


def test_the_configuration_file_is_the_published_config_and_the_cut(spec):
    doc = spec.load("configs", "lfm2_moe_rl")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "LFM2-8B-A1B")
    assert doc["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value and doc[key] != value
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert (doc["num_hidden_layers"], doc["num_dense_layers"], doc["num_experts"],
            doc["vocab_size"]) == (5, 1, 8, 16384)
    cfg = run.program_config(doc, spec.load("traffic", "anakin_128x256"), 3)
    shape = lfm2_moe.SHAPES[cfg.seq_model]
    # no width is cut: every width the program builds is the published one
    assert (shape.hidden, shape.dense_ffn, shape.expert_ffn) == (
        doc["hidden_size"], doc["intermediate_size"], doc["moe_intermediate_size"])
    assert (shape.heads, shape.kv_heads, shape.head_dim) == (
        doc["num_attention_heads"], doc["num_key_value_heads"],
        doc["hidden_size"] // doc["num_attention_heads"])
    assert (shape.conv_width, shape.rope_theta, shape.eps) == (
        doc["conv_L_cache"], doc["rope_theta"], doc["norm_eps"])
    assert (shape.num_experts, shape.top_k, shape.routed_scale) == (
        doc["published"]["num_experts"], doc["num_experts_per_tok"],
        doc["routed_scaling_factor"])
    assert len(shape.held_experts) == doc["num_experts"] >= 8
    assert len(shape.layers) == doc["num_hidden_layers"]
    assert shape.vocab == doc["vocab_size"] == doc["published"]["vocab_size"] // 4
    # the held layers are published layers 1-5, one whole period among them
    held = doc["held_here"]["layers"]
    assert held == [1, 2, 3, 4, 5]
    assert [k.split("+")[0] for k in shape.layers] == [
        {"conv": "conv", "full_attention": "gqa"}[doc["layer_types"][i]] for i in held]
    assert [k.endswith("dense") for k in shape.layers] == [
        i < row["config"]["num_dense_layers"] for i in held]
    assert doc["parameters"] == lfm2_counts.parameters(doc["model"])
    assert doc["parameters"]["total"] == 541376769
    assert 0.64 < doc["parameters"]["experts"] / doc["parameters"]["total"] < 0.66
    for key in ("head_dim", "qk_norm", "rope", "conv", "router", "router_bias",
                "embedding_and_head", "final_norm", "weights", "optimizer",
                "kv_cache", "described_as"):
        assert doc["assumed"][key]
    # the traffic is the parameters the cell was asked with, and the files
    # agree with the program (the loop refuses to run otherwise)
    assert (cfg.num_envs, cfg.unroll_len, cfg.updates_per_call) == (128, 256, 1)
    assert cfg.actor_staleness == 2 and cfg.optimizer == "rmsprop"
    env = registry.make(cfg.env_id, cfg)
    assert (env.vocab, env.min_len, env.max_len, env.min_prompt, env.max_prompt) == (
        16384, 128, 2048, 16, 64)
    assert shape.max_positions == env.max_len
    anakin_lfm2.check_files_agree(cfg, doc)
    with pytest.raises(SystemExit, match="model record"):
        anakin_lfm2.check_files_agree(
            cfg, {"model": {**doc["model"], "hidden": 128}})


def test_every_new_metric_resolves_to_a_reader_in_the_new_cell_only(spec):
    mine = {m["name"]: m for m in spec.doc["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == NEW_METRICS
    for name in NEW_METRICS:
        read, params = spec.reader(name)
        assert callable(read) and isinstance(params, dict)
        assert mine[name]["moves"] == "env_frames_per_s"
    for name, scope in (("conv_mixer_device_ms", "conv_mixer"),
                        ("gqa_device_ms", "gqa"),
                        ("moe_experts_device_ms", "moe_experts"),
                        ("lfm2_moe_device_ms", "moe"),
                        ("lfm2_lm_head_device_ms", "lm_head")):
        assert spec.reader(name) == (readers.scope_device_ms, {"scope": scope})
    in_cell = {m["name"] for m in spec.metrics_of("per_layer", CELL)}
    assert NEW_METRICS <= in_cell
    # the accepted metrics without a list read this cell as they read the
    # others; those of the CNN and of the other sequence policy stay away
    assert {"rollout_device_ms", "loss_and_grad_device_ms", "hbm_peak_gb",
            "device_idle_share", "actor_forward_device_ms", "env_step_device_ms",
            "fused_vtrace_roofline", "step_trace_lower_s"} <= in_cell
    assert not in_cell & {"render_device_ms", "model_flops_util", "seq_step_mfu",
                          "kda_device_ms", "mla_device_ms", "kda_step_roofline"}
    # and no cell but this one reports the new metrics
    for cell in (w["name"] for w in spec.doc["workloads"]):
        if cell != CELL:
            assert not NEW_METRICS & {
                m["name"] for m in spec.metrics_of("per_layer", cell)}


def test_the_shares_read_a_trace_and_give_nothing_without_one():
    dims = dataclasses.asdict(lfm2_moe.SHAPES["lfm2_moe_5l"])
    ms = {"rollout": 900.0, "moe_experts": 700.0}
    chip = types.SimpleNamespace(scope_ps=lambda s: ms.get(s, 0.0) * 1e9 * 2)
    ev = {
        "trace": types.SimpleNamespace(devices=[chip], busy_s=5.0, window_s=5.5),
        "traced_updates": 2, "chips": 1, "peaks": device.peaks("TPU v5 lite"),
        "geometry": {"num_envs": 128, "unroll_len": 256},
        "lfm2": {"dims": dims, "attended": 540.0, "held_per_token": 1.0,
                 "local_assignments": 4 * 32768.0},
    }
    flops = lfm2_counts.train_flops_per_update(dims, 32768, 540.0, 1.0)
    assert lfm2_readers.lfm2_step_mfu(ev) == pytest.approx(
        100 * 2 * flops / 5.0 / 197e12)
    assert lfm2_readers.lfm2_rollout_hbm_roofline(ev) == pytest.approx(
        100 * lfm2_counts.decode_bytes_per_step(dims, 128, 540.0) * 256
        / 819e9 * 1e3 / 900.0)
    assert lfm2_readers.moe_experts_roofline(ev) == pytest.approx(
        100 * 4 * 4 * 32768 * 6 * 2048 * 1792 / 197e12 * 1e3 / 700.0)
    for value in (lfm2_readers.lfm2_step_mfu(ev),
                  lfm2_readers.lfm2_rollout_hbm_roofline(ev),
                  lfm2_readers.moe_experts_roofline(ev)):
        assert 0 < value < 100
    # the experts are most of what a decode step must move
    p = lfm2_counts.parameters(dims)
    assert 0.55 < p["experts"] * 2 / lfm2_counts.decode_bytes_per_step(
        dims, 128, 540.0) < 0.75
    # a program without the policy (the parent's), a run without a trace, or
    # a trace without the scope: nothing, and nothing raised
    for lacking in ({**ev, "trace": None},
                    {k: v for k, v in ev.items() if k != "lfm2"}):
        assert lfm2_readers.lfm2_step_mfu(lacking) is None
        assert lfm2_readers.lfm2_rollout_hbm_roofline(lacking) is None
        assert lfm2_readers.moe_experts_roofline(lacking) is None
    ms.clear()
    assert lfm2_readers.lfm2_rollout_hbm_roofline(ev) is None
    assert lfm2_readers.moe_experts_roofline(ev) is None


# ------------------------------------------- the loop, on the CPU, tiny


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    for kind in ("configs", "traffic"):
        (tmp_path / kind).mkdir()
    n_dev = len(jax.devices())
    shape = lfm2_moe.SHAPES["lfm2_moe_tiny"]

    def write(how, **more):
        (tmp_path / "configs" / "tiny_lfm2.json").write_text(json.dumps({
            "name": "tiny_lfm2", "loop": "anakin_lfm2", "preset": "lfm2_moe_tiny",
            "overrides": {"precision": "f32", "updates_per_call": 1},
            "model": dataclasses.asdict(shape), "reference_env_block": n_dev // 2 or 1,
            "reference_how": how, **more}))

    (tmp_path / "traffic" / "tiny_tokens.json").write_text(json.dumps({
        "overrides": {"num_envs": 4 * n_dev, "unroll_len": 32,
                      "token_task": [64, 2, 32, 1, 2]}}))
    real = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        **real, "configs": [],
        "workloads": [{"name": CELL, "config": "tiny_lfm2",
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
    # a large seed: the driver's are a little over 2**31
    assert run.main([*args, "--seed", "2400000013", "--trace", str(trace)]) == 0
    line = _last_line(capsys)
    stderr = line.pop("stderr")
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True, stderr[-3000:]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert "plain float32 reference" in stderr and "behaviour_logp" in stderr
    assert "conv tails by layer" in stderr and "key and value rows" in stderr
    if trace:
        # no chip, so no device trace: only the counters have something to read
        assert {"moe_dense_blocks", "gqa_rows_attended",
                "lfm2_moe_load_max_over_mean",
                "lfm2_episode_resets_per_update"} <= set(line["metrics"])
        assert not set(line["metrics"]) & {
            "lfm2_step_mfu", "moe_experts_roofline", "lfm2_rollout_hbm_roofline",
            "gqa_device_ms", "conv_mixer_device_ms", "moe_experts_device_ms",
            "lfm2_moe_device_ms", "lfm2_lm_head_device_ms"}
        assert line["metrics"]["moe_dense_blocks"]["value"] == 0
        assert line["metrics"]["gqa_rows_attended"]["value"] >= 1
    else:
        assert set(line["metrics"]) == {"env_frames_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("how", [
    {"theta": 1e4},  # another rotary base: the keys' rotation
    {"qk_norm": False},  # the per-head norms of q and k left out
    {"conv_gate": False},  # the conv's output gate C left out
    {"conv_in_gate": False},  # the conv's input gate B left out
    {"held": [0, 1, 2]},  # one held expert's part left out
])
def test_a_wrong_reference_is_not_correct(tiny, capsys, how):
    write, args = tiny
    write(how)
    assert run.main([*args, "--seed", "5", "--trace", "0"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is False
    assert "not correct" in line["stderr"]


@pytest.mark.parametrize("low", [True, *([part] for part in reference.PARTS)])
def test_the_reference_in_bfloat16_in_the_programs_place_is_not_correct(
        tiny, capsys, low):
    """The precision control: the reference with its products, and with
    each part the configuration keeps in float32, in bfloat16, held to the
    float32 reference by the loop's own limits in the program's place."""
    write, args = tiny
    write({}, stand_in={"low": low})
    assert run.main([*args, "--seed", "5", "--trace", "0"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is False
    assert "A CONTROL, not the program" in line["stderr"]
    # the update's own rollout is still held to its replay, and was it
    assert "did not train on the replayed fragment" not in line["stderr"]
