"""The harness end to end on the CPU at tiny sizes, through the real
command path (``benchmarks.run.main``) with only the device rule stubbed,
here in the test; and ``BENCHMARK.json`` itself."""

import json
import os
import re

import jax
import pytest

from benchmarks import device, run

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _metric(name, unit="x", moves="env_frames_per_s", source="program_counter"):
    return {"name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "test", "moves": moves}


@pytest.fixture
def throwaway(tmp_path, monkeypatch):
    """A configuration, a mix, a metric with a reader of its own and a
    ``BENCHMARK.json`` that names them: files alone, in a temp directory."""
    for kind in ("configs", "traffic", "layer_metrics"):
        (tmp_path / kind).mkdir()

    def write(rel, doc):
        (tmp_path / rel).write_text(doc if isinstance(doc, str) else json.dumps(doc))

    n_dev = len(jax.devices())
    write("configs/tiny_anakin.json", {
        "loop": "anakin", "preset": "atari_impala", "reference_chunk": 9 * n_dev,
        "overrides": {"updates_per_call": 2, "fused_scan": "interpret",
                      "channels": [4, 8], "precision": "f32"}})
    write("traffic/tiny_job.json", {"overrides": {"num_envs": n_dev, "unroll_len": 8}})
    write("layer_metrics/updates_seen.json", {"params": {"scale": 2}})
    write("layer_metrics/updates_seen.py",
          "def read(ev, scale):\n"
          "    return ev['geometry']['updates_per_call'] * scale\n")
    write("layer_metrics/never_there.json", {"reader": "counter",
                                             "params": {"key": "no_such"}})
    write("BENCHMARK.json", {
        "command": ["python3", "-m", "benchmarks.run"], "paths": ["benchmarks"],
        "run_seconds": 2,
        "configs": [],
        "workloads": [
            {"name": "tiny.job", "config": "tiny_anakin", "traffic": "tiny_job",
             "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": n, "unit": "x", "better": "higher", "bound": 0.05,
             "source": "host_clock"}
            for n in ("env_frames_per_s", "setup_s")],
        "per_layer": [
            _metric("updates_seen"), _metric("never_there"),
            _metric("compile_cache_added"), _metric("rollout_device_ms")],
    })

    def on_the_cpu(chips):
        devices = jax.devices()
        return {"platform": devices[0].platform, "kind": devices[0].device_kind,
                "count": len(devices), "cache_dir": None}

    monkeypatch.setattr(device, "require_chips", on_the_cpu)
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path / "_out"))
    return ["--spec", str(tmp_path / "BENCHMARK.json"), "--data-root", str(tmp_path)]


def _last_line(capsys) -> dict:
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    line["stderr"] = captured.err
    return line


def test_anakin_rehearsal_traced_with_a_throwaway_metric(throwaway, capsys):
    assert run.main([*throwaway, "--workload", "tiny.job", "--seed", "3",
                     "--seconds", "1", "--trace", "1"]) == 0
    line = _last_line(capsys)
    line.pop("stderr")
    # no chip, so no device trace: busy_s, window_s and breakdown stay out
    assert set(line) == CONTRACT_KEYS
    assert set(line["device"]) == DEVICE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    # the throw-away metric's own reader ran; readers that found nothing
    # (no trace on a CPU, no such counter) left their metrics out
    assert line["metrics"]["updates_seen"] == {"value": 4, "unit": "x"}
    assert set(line["metrics"]) == {"updates_seen", "compile_cache_added"}


def test_anakin_rehearsal_end_to_end(throwaway, capsys):
    assert run.main([*throwaway, "--workload", "tiny.job", "--seed", "4",
                     "--seconds", "1", "--trace", "0"]) == 0
    line = _last_line(capsys)
    stderr = line.pop("stderr")
    assert set(line) == CONTRACT_KEYS
    assert set(line["device"]) == DEVICE_KEYS
    assert line["correct"] is True, stderr[-2000:]
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert set(line["metrics"]) == {"env_frames_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    # the first update's loss was held to the plain reference in set-up
    assert "plain float32 reference" in stderr


def test_no_tpu_is_a_refusal_without_a_result(capsys):
    with pytest.raises(SystemExit) as e:
        device.require_chips(1)
    assert e.value.code == 4
    captured = capsys.readouterr()
    assert captured.out == "" and "no TPU" in captured.err


def test_peaks_are_by_exact_device_kind():
    v5e = device.peaks("TPU v5 lite")
    assert v5e["flops_per_s_bf16"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["source"]
    for kind in ("TPU v5", "cpu", "TPU v5 lite "):
        with pytest.raises(KeyError):
            device.peaks(kind)


# ----------------------------------------------------- BENCHMARK.json itself


@pytest.fixture(scope="module")
def spec():
    return run.Spec(os.path.join(run.ROOT, "BENCHMARK.json"), [run.BENCH_DIR])


def test_benchmark_json_is_consistent_with_its_files(spec):
    doc = spec.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in doc["configs"]}
    for c in doc["configs"]:
        assert NAME.match(c["name"])
        assert any(c["file"].startswith(p + "/") for p in doc["paths"])
        assert os.path.exists(os.path.join(run.ROOT, c["file"]))
        assert spec.load("configs", c["name"])["reduced"] == c["reduced"]
    cells = {w["name"] for w in doc["workloads"]}
    assert {w["config"] for w in doc["workloads"]} == set(configs)
    for w in doc["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        loop = spec.load("configs", w["config"])["loop"]
        assert os.path.exists(os.path.join(run.BENCH_DIR, "loops", loop + ".py"))
        spec.load("traffic", w["traffic"])
    four = [w for w in doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(doc["workloads"]) // 4)
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in doc["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in doc["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        read, params = spec.reader(m["name"])
        assert callable(read) and isinstance(params, dict)
        # a per-layer metric is reported only where the metric it moves is
        moved_in = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= moved_in
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in cells:
        names = {m["name"] for m in spec.metrics_of("end_to_end", cell)}
        assert "setup_s" in names and len(names) >= 2
        assert spec.metrics_of("per_layer", cell)


def test_every_cell_resolves_to_a_program_config(spec):
    for w in spec.doc["workloads"]:
        cfg = run.program_config(
            spec.load("configs", w["config"]), spec.load("traffic", w["traffic"]), 7
        )
        assert cfg.seed == 7 and cfg.algo == "impala"
        assert cfg.eval_every == 0 and cfg.checkpoint_dir == ""
