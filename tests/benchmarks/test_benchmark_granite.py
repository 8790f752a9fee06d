"""The ``granite_h_rl`` cell: its counts against a shape counted by hand, its
loop end to end on the CPU at the tiny preset through ``benchmarks.run.main``
(a warm-in, then ``correct`` true; false when the reference keeps the state
in bfloat16, leaves ``dt_bias`` out of delta, takes a residual multiplier of
1 or leaves the conv's bias out, and when the bfloat16 reference stands in
the program's place), its metric files, and its configuration and traffic
files against the program and the catalog."""

import dataclasses
import json
import os
import types

import jax
import numpy as np
import pytest

from asyncrl_tpu.envs import registry
from asyncrl_tpu.models import granite_h
from benchmarks import (
    device, episode_draw, granite_counts, granite_readers, program_record, readers, run)
from benchmarks.loops import anakin_granite, common

CELL = "granite_h_rl.anakin_16x256"
MOONLIGHT = "moonlight_rl.anakin_16x512"
KIMI, LFM2, KEYE = ("kimi_linear_rl.anakin_64x256", "lfm2_moe_rl.anakin_128x256",
                    "keye_moe_rl.anakin_16x512")
# Accepted entries that read this cell too, appended to the cells they listed:
# name -> (those cells, reader (None: one of its own), params, layer)
JOINED = {
    "lm_head_device_ms": ([KIMI], readers.scope_device_ms, {"scope": "lm_head"},
                          "Models (sequence policy)"),
    "episode_resets_per_update": ([KIMI], readers.counter,
                                  {"key": "episode_resets_per_update"},
                                  "Envs + Rollout (Anakin)"),
    "gqa_device_ms": ([LFM2], readers.scope_device_ms, {"scope": "gqa"},
                      "Models (sequence policy)"),
    "gqa_rows_attended": ([LFM2], readers.counter, {"key": "gqa_rows_attended"},
                          "Models (sequence policy)"),
    "gqa_step_device_ms": ([LFM2, KEYE], readers.scope_device_ms, {"scope": "gqa_step"},
                           "Models (sequence policy)"),
    "prefetch_wait_device_ms": ([KIMI, LFM2, KEYE], None,
                                {"ops": ["copy-done", "slice-done"], "scope": "rollout"},
                                "Envs + Rollout (Anakin)"),
}
# What the accepted cells' own tests pinned as the entries that list their
# cell alone (their cases are strict xfails in tests/conftest.py SUPERSEDED
# since JOINED), less what JOINED took
ALONE = {
    KIMI: {"seq_step_mfu", "kda_device_ms", "kda_step_device_ms", "kda_chunk_device_ms",
           "mla_device_ms", "moe_device_ms", "rollout_hbm_roofline",
           "kda_step_roofline", "moe_load_max_over_mean"},
    LFM2: {"conv_mixer_device_ms", "moe_experts_device_ms", "lfm2_step_mfu",
           "lfm2_rollout_hbm_roofline", "moe_experts_roofline", "moe_dense_blocks",
           "lfm2_moe_device_ms", "lfm2_lm_head_device_ms",
           "lfm2_moe_load_max_over_mean", "lfm2_episode_resets_per_update"},
}
SCOPES = {"mamba_device_ms": "mamba", "ssd_step_device_ms": "ssd_step",
          "ssd_chunk_device_ms": "ssd_chunk"}
COUNTERS = {"ssd_chunk_resets": "ssd_chunk_resets"}
SHARES = {"granite_rollout_hbm_roofline": granite_readers.granite_rollout_hbm_roofline,
          "granite_step_mfu": granite_readers.granite_step_mfu}
NEW_METRICS = {*SCOPES, *COUNTERS, *SHARES}
SOURCE = "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"
# The model's config.json as the model catalog's row gives it.
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 8192,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352,
}
HAND = {  # a shape small enough to count by hand
    "hidden": 4, "vocab": 10, "layers": ["mamba+dense", "gqa+dense"],
    "heads": 2, "kv_heads": 1, "head_dim": 3, "mamba_heads": 2, "mamba_head_dim": 3,
    "mamba_state": 5, "ffn": 6, "max_positions": 8, "attention_multiplier": 0.25,
    "embedding_multiplier": 12.0, "residual_multiplier": 0.22, "logits_scaling": 8.0,
    "rope_theta": None, "conv_width": 4, "chunk": 4, "eps": 1e-5, "block_tokens": 64,
}


def test_counts_of_a_shape_counted_by_hand():
    d = HAND
    # inner 6, xBC 16; in 4 x (6 + 16 + 2), conv 4 x 16, out 6 x 4, SwiGLU 3 x 4 x 6
    assert granite_counts.projection_flops(d, "mamba+dense") == 2 * (96 + 64 + 24 + 72)
    # q 4 x 6, k, v 4 x 3 each, o 6 x 4
    assert granite_counts.projection_flops(d, "gqa+dense") == 2 * (24 + 24 + 24 + 72)
    assert granite_counts.ssd_step_flops(d) == 4 * 2 * 3 * 5
    # Q = 4: C B^T 4 x 5, pairs 4 x 6, S0 read and state handed 2 x 6 x 5
    assert granite_counts.ssd_chunk_flops(d, 16) == 2 * (20 + 24 + 60)
    assert granite_counts.ssd_chunk_flops(d, 2) == 2 * (10 + 12 + 60)
    assert granite_counts.attention_flops(d, 7) == 4 * 2 * 3 * 7
    rest = 2 * (256 + 144) + 2 * 4 * 11
    assert granite_counts.rollout_flops(d, 3, 7) == 3 * (rest + 120 + 168)
    learner = 6 * (rest + 2 * 104 + 168)
    assert granite_counts.learner_forward_flops(d, 6, 16, 7) == learner
    assert granite_counts.train_flops_per_update(d, 2, 3, 7) == (
        granite_counts.rollout_flops(d, 6, 7)
        + 3 * granite_counts.learner_forward_flops(d, 6, 3, 7))
    p = granite_counts.parameters(d)
    mamba = 96 + 64 + 16 + 6 + 6 + 24  # in, conv, its bias, A_log/dt_bias/D, norm, out
    assert p["mamba"] == mamba and p["attention"] == 72 and p["ffn"] == 2 * 72
    assert p["layers"] == 2 * (8 + 72) + mamba + 72
    assert p["total"] == p["layers"] + 40 + 5 + 4
    # the state 6 x 5 and the tail 3 x 16, read and written, float32, 3 envs
    assert granite_counts.ssd_carry_bytes(d, 3) == 2 * 4 * 3 * (30 + 48)
    # every weight at 2 bytes, 3 touched rows, the carry, 8 rows of k and v
    assert granite_counts.decode_bytes_per_step(d, 3, 7) == (
        p["total"] * 2 + 3 * 4 * 4 + 2 * 4 * 3 * 78 + 3 * 8 * 2 * 3 * 2)


def test_counts_agree_with_the_tree_the_program_builds():
    for name, shape in granite_h.SHAPES.items():
        built = jax.eval_shape(
            granite_h.GraniteHPolicy(shape).init, jax.random.PRNGKey(0))
        n = sum(x.size for x in jax.tree.leaves(built))
        assert granite_counts.parameters(dataclasses.asdict(shape))["total"] == n, name
    assert granite_counts.parameters(
        dataclasses.asdict(granite_h.SHAPES["granite_h_10l"]))["total"] == 772162497


@pytest.fixture(scope="module")
def spec():
    return run.Spec(os.path.join(run.ROOT, "BENCHMARK.json"), [run.BENCH_DIR])


def test_the_configuration_file_is_the_catalogs_row_and_the_cut(spec):
    doc = spec.load("configs", "granite_h_rl")
    assert doc["source"] == SOURCE
    for key, value in PUBLISHED.items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value and doc[key] != value, key
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers", "layer_types", "vocab_size"]
    assert doc["num_hidden_layers"] == 10 and doc["vocab_size"] == 12544
    assert doc["layer_types"] == PUBLISHED["layer_types"][:10]
    cfg = run.program_config(doc, spec.load("traffic", "anakin_16x256_v12544"), 3)
    shape = granite_h.SHAPES[cfg.seq_model]
    # no width is cut: every width the program builds is the published one
    assert shape.hidden == doc["hidden_size"]
    assert shape.ffn == doc["shared_intermediate_size"] == doc["intermediate_size"]
    assert (shape.heads, shape.kv_heads, shape.head_dim) == (
        doc["num_attention_heads"], doc["num_key_value_heads"],
        doc["hidden_size"] // doc["num_attention_heads"])
    assert (shape.mamba_heads, shape.mamba_head_dim, shape.mamba_state) == (
        doc["mamba_n_heads"], doc["mamba_d_head"], doc["mamba_d_state"])
    assert shape.mamba_heads * shape.mamba_head_dim == doc["mamba_expand"] * doc["hidden_size"]
    assert (shape.conv_width, shape.chunk, shape.eps) == (
        doc["mamba_d_conv"], doc["mamba_chunk_size"], doc["rms_norm_eps"])
    assert (shape.attention_multiplier, shape.embedding_multiplier,
            shape.residual_multiplier, shape.logits_scaling) == (
        doc["attention_multiplier"], doc["embedding_multiplier"],
        doc["residual_multiplier"], doc["logits_scaling"])
    assert shape.rope_theta is None and doc["position_embedding_type"] == "nope"
    assert doc["num_local_experts"] == 0 and doc["tie_word_embeddings"]
    kinds = {"mamba": "mamba+dense", "attention": "gqa+dense"}
    assert shape.layers == tuple(kinds[k] for k in doc["layer_types"])
    assert shape.vocab == doc["vocab_size"] == doc["published"]["vocab_size"] // 8
    assert doc["held_here"] == {"layers": list(range(10)), "vocab_rows": [0, 12544]}
    assert "8 chips" in doc["deployment"] and "four stages" in doc["deployment"]
    assert doc["parameters"] == granite_counts.parameters(doc["model"])
    assert doc["parameters"]["total"] == 772162497
    for key in ("layers_kept", "mamba", "mamba_init", "chunked_scan", "attention",
                "kv_cache", "ffn", "multipliers", "embedding_and_head", "weights",
                "value_head", "optimizer", "blocks", "precision", "env_id",
                "actor_staleness", "described_as"):
        assert doc["assumed"][key]
    # the traffic is the parameters the cell was asked with, and the files
    # agree with the program (the loop refuses to run otherwise)
    assert (cfg.num_envs, cfg.unroll_len, cfg.updates_per_call) == (16, 256, 1)
    assert cfg.actor_staleness == 2 and cfg.optimizer == "rmsprop" and cfg.donate_buffers
    env = registry.make(cfg.env_id, cfg)
    assert (env.vocab, env.min_len, env.max_len, env.min_prompt, env.max_prompt) == (
        12544, 128, 2048, 16, 64)
    assert shape.max_positions == env.max_len
    # the warm-in is as many steps as the cache holds rows
    assert doc["warm_in_fragments"] * cfg.unroll_len == shape.max_positions
    anakin_granite.check_files_agree(cfg, doc)
    with pytest.raises(SystemExit, match="model record"):
        anakin_granite.check_files_agree(cfg, {"model": {**doc["model"], "hidden": 128}})
    with pytest.raises(SystemExit, match="parameters"):
        anakin_granite.check_files_agree(cfg, {**doc, "parameters": {}})


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
    for name, read in SHARES.items():
        assert spec.reader(name)[0] is read and mine[name]["unit"] == "%"
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
                          "kda_device_ms", "moe_device_ms", "moonlight_step_mfu",
                          "lfm2_step_mfu", "keye_gqa_device_ms", "mla_step_device_ms"}
    assert set(JOINED) <= in_cell
    for cell in (w["name"] for w in spec.doc["workloads"]):
        if cell != CELL:
            assert not NEW_METRICS & {
                m["name"] for m in spec.metrics_of("per_layer", cell)}
    (config,) = [c for c in spec.doc["configs"] if c["name"] == "granite_h_rl"]
    assert config["reduced"] == ["num_hidden_layers", "layer_types", "vocab_size"]


@pytest.mark.parametrize("name", list(JOINED))
def test_an_accepted_metric_reads_this_cell_after_its_own(spec, name):
    """The attention layer, the head, the episode boundaries and the token
    loop's prefetch waits are the same program's here as in the cells the
    entry listed: the entry lists this cell after them, with its reader."""
    cells, reader, params, layer = JOINED[name]
    entry = next(m for m in spec.doc["per_layer"] if m["name"] == name)
    read, got = spec.reader(name)
    assert got == params
    if reader is None:  # a reader of its own, beside the metric's file
        assert read.__module__ == f"benchmarks_layer_metric_{name}"
    else:
        assert read is reader
    assert entry["workloads"] == [*cells, CELL]
    assert (entry["layer"], entry["better"], entry["moves"]) == (
        layer, "lower", "env_frames_per_s")
    assert entry["source"] == (
        "program_counter" if reader is readers.counter else "device_trace")
    reported = {w["name"] for w in spec.doc["workloads"]
                if entry in spec.metrics_of("per_layer", w["name"])}
    assert reported == {*cells, CELL}


@pytest.mark.parametrize("cell", [KIMI, LFM2])
def test_an_accepted_cells_own_entries_stay_its_own(spec, cell):
    """What ``test_benchmark_seq.py`` and ``test_benchmark_lfm2.py`` held of
    the entries that list their cell alone, with JOINED's now listing this
    cell too: each resolves to a reader, reads in that cell and in no other."""
    alone = {m["name"] for m in spec.doc["per_layer"] if m.get("workloads") == [cell]}
    assert alone == ALONE[cell]
    joined = {n for n, (cells, *_) in JOINED.items() if cells == [cell]}
    in_cell = {m["name"] for m in spec.metrics_of("per_layer", cell)}
    assert alone | joined <= in_cell
    for name in alone:
        read, params = spec.reader(name)
        assert callable(read) and isinstance(params, dict)
        entry = next(m for m in spec.doc["per_layer"] if m["name"] == name)
        assert entry["moves"] == "env_frames_per_s"
    for other in (w["name"] for w in spec.doc["workloads"]):
        if other != cell:
            assert not alone & {m["name"] for m in spec.metrics_of("per_layer", other)}
    assert {"rollout_device_ms", "loss_and_grad_device_ms", "hbm_peak_gb",
            "device_idle_share", "fused_vtrace_roofline"} <= in_cell
    assert not in_cell & {"render_device_ms", "section0_device_ms",
                          "max_pool_device_ms", "model_flops_util"}


@pytest.mark.parametrize("cell", [MOONLIGHT, CELL])
def test_make_agent_programs_is_read_in_the_seventh_cell(spec, cell):
    """What ``test_benchmark_moonlight.py``'s case held for its cell, for it
    and for this one: the entry lists no cells, so a cell that a later PR
    adds reads it; this cell is the last, on one chip."""
    entry = next(m for m in spec.doc["per_layer"] if m["name"] == "make_agent_programs")
    assert spec.reader("make_agent_programs")[0] is program_record.programs_in_phase
    assert "workloads" not in entry
    names = [w["name"] for w in spec.doc["workloads"]]
    assert names[-1] == CELL and names[-2] == MOONLIGHT and names.count(cell) == 1
    assert [w["chips"] for w in spec.doc["workloads"] if w["name"] == cell] == [1]
    for name in names:
        assert entry in spec.metrics_of("per_layer", name)


def test_the_shares_read_a_trace_and_give_nothing_without_one():
    dims = dataclasses.asdict(granite_h.SHAPES["granite_h_10l"])
    ms = {"rollout": 900.0, "ssd_step": 200.0}
    chip = types.SimpleNamespace(scope_ps=lambda s: ms.get(s, 0.0) * 1e9 * 2)
    ev = {
        "trace": types.SimpleNamespace(devices=[chip], busy_s=5.0, window_s=5.5),
        "traced_updates": 2, "chips": 1, "peaks": device.peaks("TPU v5 lite"),
        "geometry": {"num_envs": 16, "unroll_len": 256},
        "granite": {"dims": dims, "attended": 544.0},
    }
    flops = granite_counts.train_flops_per_update(dims, 16, 256, 544.0)
    assert granite_readers.granite_step_mfu(ev) == pytest.approx(
        100 * 2 * flops / 5.0 / 197e12)
    per_step = granite_counts.decode_bytes_per_step(dims, 16, 544.0)
    assert granite_readers.granite_rollout_hbm_roofline(ev) == pytest.approx(
        100 * per_step * 256 / 819e9 * 1e3 / 900.0)
    carry = granite_counts.ssd_carry_bytes(dims, 16)
    for read in SHARES.values():
        assert 0 < read(ev) < 100
    # the weights are most of what a decode step moves, the nine layers'
    # states (2.2 MB an env and layer, read and written) most of the rest
    p = granite_counts.parameters(dims)
    assert 0.7 < p["total"] * 2 / per_step < 0.8
    assert 0.2 < carry / per_step < 0.3
    # a program without the policy (the parent's), a run without a trace, or
    # a trace without the scope: nothing, and nothing raised
    for lacking in ({**ev, "trace": None}, {k: v for k, v in ev.items() if k != "granite"}):
        for read in SHARES.values():
            assert read(lacking) is None
    ms.clear()
    assert granite_readers.granite_rollout_hbm_roofline(ev) is None


def test_the_references_rounding_is_bfloat16s_and_passes_the_gradient():
    """``reference/granite_h.py _round``: the nearest bfloat16, ties to even,
    by the bits (a convert there and back may be left out on a TPU), with
    the gradient of the identity, as a convert's: the bfloat16 reference's
    gradients are the reference's, not zero."""
    from benchmarks.reference import granite_h as reference

    x = jax.random.normal(jax.random.PRNGKey(3), (4096,)) * 100.0
    ties = np.array([1 + 2 ** -8, 1 + 3 * 2 ** -8, -(1 + 2 ** -8)], np.float32)
    for a in (x, jax.numpy.asarray(ties)):
        np.testing.assert_array_equal(
            np.asarray(reference._round(a)),
            np.asarray(a.astype(jax.numpy.bfloat16).astype(np.float32)))
    np.testing.assert_array_equal(np.asarray(reference._round(ties)), [1, 1 + 2 ** -6, -1])
    g = jax.grad(lambda a: jax.numpy.sum(reference._round(a) * a))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(reference._round(x) + x), rtol=1e-6)


def test_the_committed_episode_seed_is_the_smallest_the_rule_admits(spec):
    """The mix's rule: the mean rows behind a token over the 8 x 256 steps
    that follow the warm-in, from cold, within 2% of the length law's
    stationary mean, (128 + 2,048) / 4 = 544."""
    mix = spec.load("traffic", "anakin_16x256_v12544")
    doc = spec.load("configs", "granite_h_rl")
    cfg = run.program_config(doc, mix, 3)
    env = registry.make(cfg.env_id, cfg)
    assert "episode_seed" not in mix["overrides"]
    last = 8 * cfg.unroll_len
    seed, rows = episode_draw.smallest_seed(
        env, cfg.num_envs, 1, doc["warm_in_fragments"] * cfg.unroll_len + last,
        last, 0.02, candidates=mix["episode_seed"] + 1)
    assert seed == mix["episode_seed"] == 8 == len(rows) - 1
    assert abs(rows[seed] - 544) <= 10.88
    assert (np.abs(rows[:seed] - 544) > 10.88).all()


# ------------------------------------------- the loop, on the CPU, tiny


TINY_MIX = lambda n_dev: {"num_envs": 2 * n_dev, "unroll_len": 16,
                          "token_task": [64, 12, 32, 1, 2]}


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    for kind in ("configs", "traffic"):
        (tmp_path / kind).mkdir()
    n_dev = len(jax.devices())
    model = dataclasses.asdict(granite_h.SHAPES["granite_h_tiny"])

    def write(how, **more):
        (tmp_path / "traffic" / "tiny_tokens.json").write_text(json.dumps({
            "episode_seed": 7, "overrides": TINY_MIX(n_dev)}))
        (tmp_path / "configs" / "tiny_granite.json").write_text(json.dumps({
            "name": "tiny_granite", "loop": "anakin_granite",
            "preset": "granite_h_tiny",
            "overrides": {"precision": "f32", "updates_per_call": 1},
            "model": model, "parameters": granite_counts.parameters(model),
            "reference_env_block": n_dev // 2 or 1, "warm_in_fragments": 2,
            "reference_how": how, **more}))

    real = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        **real, "configs": [],
        "workloads": [{"name": CELL, "config": "tiny_granite",
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
    assert {"replay_gap", "state_before_m0", "state_after_m1", "conv_after_m1",
            "rows_before_a0", "rows_after_a0", "logp_mean", "logp_rms", "kl",
            "value_loss", "entropy", "gqa_rows_attended", "ssd_chunk_resets",
            "grad_final_norm", "grad_value", "grad_ssd", "step_ssd", "loss",
            "leaves_stuck", "leaves_unreached", "compiles_in_window",
            "updates_not_executed"} <= set(compared)
    assert all(value <= limit for value, limit in compared.values())
    last = stderr.strip().splitlines()[-len(compared):]
    assert [ln.split()[2].rstrip(":") for ln in last] == list(compared)
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert "after a warm-in of 2 fragments" in stderr
    # no device trace: only the counter has something to read
    assert set(line["metrics"]) & NEW_METRICS == set(COUNTERS)
    assert 0 <= line["metrics"]["ssd_chunk_resets"]["value"] < 1


@pytest.mark.parametrize("how, stand_in", [
    ({"state_low": True}, None),  # the state kept in bfloat16
    ({"dt_bias": False}, None),
    ({"residual": 1.0}, None),
    ({"conv_bias": False}, None),
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
    else:  # the states a wrong reference rebuilds are not the program's
        assert any(k.startswith("state_") for k in over), over
