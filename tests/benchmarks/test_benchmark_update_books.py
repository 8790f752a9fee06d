"""The eight per-layer metrics that close the books on an update's device
time (ISSUE 36): each resolves by name to the reader and the cells it was
given, the two readers of their own on a hand-built trace, and a CPU
rehearsal of the set-up phase's metric through the real command path."""

import json
import os

import jax
import pytest

from benchmarks import device, program_record, readers, run, xplane

ATARI = ["atari_impala.dp1", "atari_impala.dp4"]
KIMI, LFM2, KEYE = ("kimi_linear_rl.anakin_64x256", "lfm2_moe_rl.anakin_128x256",
                    "keye_moe_rl.anakin_16x512")
EVERY = [*ATARI, KIMI, LFM2, KEYE]
LEARN, ROLLOUT, MODELS = "Learn", "Envs + Rollout (Anakin)", "Models (sequence policy)"
STEP_SCOPES = ["rollout", "loss_and_grad", "optimizer", "publish"]
# name: (reader, or None for one of its own; params; cells; layer)
TABLE = {
    "optimizer_device_ms": (readers.scope_device_ms, {"scope": "optimizer"},
                            EVERY, LEARN),
    "publish_device_ms": (readers.scope_device_ms, {"scope": "publish"},
                          EVERY, LEARN),
    "update_rest_device_ms": (None, {"scopes": STEP_SCOPES, "needs": ["publish"]},
                              EVERY, LEARN),
    "prefetch_wait_device_ms": (
        None, {"ops": ["copy-done", "slice-done"], "scope": "rollout"},
        [KIMI, LFM2, KEYE], ROLLOUT),
    "gqa_step_device_ms": (readers.scope_device_ms, {"scope": "gqa_step"},
                           [LFM2, KEYE], MODELS),
    "moe_dense_device_ms": (readers.scope_device_ms, {"scope": "moe_dense"},
                            [KIMI, LFM2, KEYE], MODELS),
    "moe_gathered_device_ms": (readers.scope_device_ms, {"scope": "moe_gathered"},
                               [KIMI, KEYE], MODELS),
    "setup_checkpoint_s": (program_record.phase_seconds,
                           {"phase": "setup.checkpoint"}, EVERY, "Entry"),
}


@pytest.fixture(scope="module")
def spec():
    return run.Spec(os.path.join(run.ROOT, "BENCHMARK.json"), [run.BENCH_DIR])


@pytest.mark.parametrize("name", list(TABLE))
def test_metric_resolves_to_its_reader_in_its_cells(spec, name):
    reader, params, cells, layer = TABLE[name]
    entry = next(m for m in spec.doc["per_layer"] if m["name"] == name)
    read, got = spec.reader(name)
    assert got == params
    if reader is None:  # a reader of its own, beside the metric's file
        assert read.__module__ == f"benchmarks_layer_metric_{name}"
    else:
        assert read is reader
    assert entry["layer"] == layer and entry["better"] == "lower"
    if name == "setup_checkpoint_s":
        assert (entry["source"], entry["moves"], entry["unit"]) == (
            "program_span", "setup_s", "s")
    else:
        assert (entry["source"], entry["moves"], entry["unit"]) == (
            "device_trace", "env_frames_per_s", "ms")
    reported = {w["name"] for w in spec.doc["workloads"]
                if entry in spec.metrics_of("per_layer", w["name"])}
    if cells == EVERY:
        # a metric of every cell lists none, so the cells that later PRs
        # add report it too: no fewer than today's
        assert "workloads" not in entry and set(EVERY) <= reported
    else:
        # the sequence cells' tests pin the entries that list their cell alone
        assert entry["workloads"] == cells and reported == set(cells)
        assert len(cells) > 1


def test_the_entries_are_there_once_and_their_scopes_are_the_programs():
    import inspect

    from asyncrl_tpu.learn import learner
    from asyncrl_tpu.obs import spans
    from asyncrl_tpu.ops import gqa, moe

    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    assert set(TABLE) <= set(names) and len(set(names)) == len(names)
    # every scope a metric of the table reads is one the program opens
    for module, scopes in ((learner, STEP_SCOPES),
                           (moe, ("moe_dense", "moe_gathered")),
                           (gqa, ("gqa_step",))):
        source = inspect.getsource(module)
        for scope in scopes:
            assert f'jax.named_scope("{scope}")' in source, scope
    assert spans.SETUP_CHECKPOINT == TABLE["setup_checkpoint_s"][1]["phase"]


# --------------------------------------------- the two readers of their own

US = 1_000_000  # picoseconds


def _chip(ops, index=0):
    """A chip's ops ``(name, path, start us, duration us)`` in a window of
    100 us."""
    events = [xplane.Event(f"%{name} = f32[8] op(...)", start * US, dur * US,
                           {"tf_op": path}) for name, path, start, dur in ops]
    return xplane.DeviceTrace(index, events, [], 0, 100 * US)


def _evidence(*chips, updates=2):
    return {"trace": xplane.Trace(list(chips), []), "traced_updates": updates}


W = "jit(train_step)/"
STEP = [
    ("while.1", W + "rollout/while", 0, 30),  # spans its body: 4 us its own
    ("fusion.1", W + "rollout/while/body/actor_forward/dot", 0, 20),
    ("slice-done.7", W + "rollout/while", 20, 6),
    ("fusion.2", W + "loss_and_grad/jvp(M)/dot", 30, 40),
    ("all-reduce-done.3", W + "psum", 70, 4),
    ("fusion.4", W + "optimizer/mul", 74, 2),
    ("select_fusion.5", W + "publish/jit(_where)/select_n", 76, 8),
    ("copy-done.9", "", 84, 3),  # at the program's edge, under no scope
    ("copy.8", "state.params['head']", 87, 5),
]


def test_update_rest_is_the_busy_time_no_scope_names(spec):
    read, params = spec.reader("update_rest_device_ms")
    ev = _evidence(_chip(STEP))
    # busy 0..92; rollout 30, loss_and_grad 40, optimizer 2, publish 8: the
    # all-reduce's wait, the copy and its done are left, over two updates
    assert read(ev, **params) == pytest.approx((92 - 80) / 2 * 1e-3)
    by_scope = sum(readers.scope_device_ms(ev, scope=s) for s in params["scopes"])
    assert by_scope + read(ev, **params) == pytest.approx(92 / 2 * 1e-3)
    # the mean over chips, as the scope metrics take it
    both = _evidence(_chip(STEP), _chip(STEP[:4] + STEP[6:7], index=1))
    assert read(both, **params) == pytest.approx((12 + 0) / 2 / 2 * 1e-3)
    # a scope that reads nothing counts 0: a step whose optimizer XLA
    # labelled no op with has that time under another name already
    no_optimizer = [op for op in STEP if "optimizer" not in op[1]]
    assert read(_evidence(_chip(no_optimizer)), **params) == pytest.approx(
        (90 - 78) / 2 * 1e-3)
    # an op under two of the scopes is taken off once, not twice
    nested = [*STEP, ("fusion.6", W + "rollout/while/body/optimizer/mul", 92, 4)]
    assert read(_evidence(_chip(nested)), **params) == pytest.approx(
        (96 - 84) / 2 * 1e-3)
    # the four scopes of the real step are siblings: the sum closes because
    # no path holds two of them
    assert not any(sum(f"/{s}/" in f"/{path}/" for s in params["scopes"]) > 1
                   for _, path, _, _ in STEP)
    # a program that does not label its publish (every commit before this
    # metric) has the publish in the remainder: another quantity, no number.
    # So has one whose publish is no op (train_step's else arm: on-policy,
    # or actor_staleness <= 1): the metric presupposes the select
    parent = [(n, p.replace("publish/", ""), s, d) for n, p, s, d in STEP]
    assert read(_evidence(_chip(parent)), **params) is None
    else_arm = [op for op in STEP if "publish" not in op[1]]
    assert read(_evidence(_chip(else_arm)), **params) is None
    assert read({"trace": None, "traced_updates": 2}, **params) is None
    assert read({**ev, "traced_updates": 0}, **params) is None


def test_prefetch_wait_is_the_rollouts_done_ops_and_no_collectives(spec):
    read, params = spec.reader("prefetch_wait_device_ms")
    ops = [*STEP,
           ("copy-done.253", W + "rollout/while", 92, 2),
           ("all-reduce-done.11", W + "rollout/while", 94, 1),  # a collective
           ("copy-done.30", W + "loss_and_grad/jvp()/while", 95, 1),
           ("copy-start.253", W + "rollout/while", 96, 1)]  # not a wait
    ev = _evidence(_chip(ops))
    # slice-done.7 (6 us) and copy-done.253 (2 us) of the rollout, per update
    assert read(ev, **params) == pytest.approx((6 + 2) / 2 * 1e-3)
    assert read(ev, ops=["slice-done"], scope="rollout") == pytest.approx(3e-3)
    assert read(ev, ops=["copy-done"], scope="loss_and_grad") == pytest.approx(0.5e-3)
    # nothing to read: no such op under the scope, no trace
    assert read(_evidence(_chip(STEP[3:])), **params) is None
    assert read({"trace": None, "traced_updates": 2}, **params) is None


# ------------------------------------------------- the rehearsal on the CPU


@pytest.fixture
def throwaway(tmp_path, monkeypatch):
    """A tiny Anakin cell whose ``BENCHMARK.json`` lists ``make_agent_s``
    and the eight entries; the metrics' own files are the real ones."""
    for kind in ("configs", "traffic"):
        (tmp_path / kind).mkdir()
    n_dev = len(jax.devices())
    (tmp_path / "configs" / "tiny_anakin.json").write_text(json.dumps({
        "loop": "anakin", "preset": "atari_impala", "reference_chunk": 9 * n_dev,
        "overrides": {"updates_per_call": 2, "fused_scan": "interpret",
                      "channels": [4, 8], "precision": "f32"}}))
    (tmp_path / "traffic" / "tiny_job.json").write_text(json.dumps(
        {"overrides": {"num_envs": n_dev, "unroll_len": 8}}))
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    listed = [{k: v for k, v in real[n].items() if k != "workloads"}
              for n in ("make_agent_s", *TABLE)]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "-m", "benchmarks.run"], "paths": ["benchmarks"],
        "run_seconds": 2, "configs": [],
        "workloads": [{"name": "tiny.job", "config": "tiny_anakin",
                       "traffic": "tiny_job", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": n, "unit": "x", "better": "higher", "bound": 0.05,
             "source": "host_clock"} for n in ("env_frames_per_s", "setup_s")],
        "per_layer": listed,
    }))

    def on_the_cpu(chips):
        d = jax.devices()[0]
        return {"platform": d.platform, "kind": d.device_kind,
                "count": len(jax.devices()), "cache_dir": None}

    monkeypatch.setattr(device, "require_chips", on_the_cpu)
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path / "_out"))
    return ["--spec", str(tmp_path / "BENCHMARK.json"), "--data-root", str(tmp_path)]


def test_rehearsal_prints_the_checkpoint_phase_inside_make_agent(throwaway, capsys):
    assert run.main([*throwaway, "--workload", "tiny.job", "--seed",
                     "3600000017", "--seconds", "1", "--trace", "1"]) == 0
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    assert line["correct"] is True, captured.err[-2000:]
    # no chip, so no device trace: the seven device metrics stay out, and
    # their readers did not raise
    assert set(line["metrics"]) == {"make_agent_s", "setup_checkpoint_s"}
    assert line["metrics"]["setup_checkpoint_s"]["unit"] == "s"
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < got["setup_checkpoint_s"] <= got["make_agent_s"]
