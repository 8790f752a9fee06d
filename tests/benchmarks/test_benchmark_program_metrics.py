"""The ten per-layer metrics that read the program's own record and its
device scopes: each resolves by name, the program readers on a hand-built
record, and a CPU rehearsal through the real command path."""

import ast
import json
import os
import re

import jax
import pytest

from asyncrl_tpu.obs import introspect
from benchmarks import device, program_record, readers, run

TRACE, LOWER, BACKEND = (program_record.TRACE, program_record.LOWER,
                         program_record.BACKEND)
PROGRAM_METRICS = {
    "make_agent_s": (program_record.phase_seconds, "program_span"),
    "init_state_s": (program_record.phase_seconds, "program_span"),
    "make_agent_programs": (program_record.programs_in_phase, "program_counter"),
    "step_trace_lower_s": (program_record.compile_seconds_in_phase, "program_span"),
    "step_load_s": (program_record.compile_seconds_in_phase, "program_span"),
}
SCOPE_METRICS = {
    "actor_forward_device_ms": "actor_forward",
    "env_step_device_ms": "env_step",
    "render_device_ms": "vmap(render)",
    "section0_device_ms": "section0",
    "max_pool_device_ms": "max_pool",
}


@pytest.fixture(scope="module")
def spec():
    return run.Spec(os.path.join(run.ROOT, "BENCHMARK.json"), [run.BENCH_DIR])


@pytest.mark.parametrize("name", [*PROGRAM_METRICS, *SCOPE_METRICS])
def test_metric_resolves_to_its_reader(spec, name):
    entry = next(m for m in spec.doc["per_layer"] if m["name"] == name)
    read, params = spec.reader(name)
    if name in PROGRAM_METRICS:
        reader, source = PROGRAM_METRICS[name]
        assert read is reader and entry["source"] == source
        assert entry["layer"] == "Entry" and entry["moves"] == "setup_s"
        assert params["phase"].startswith("setup.")
    else:
        assert read is readers.scope_device_ms
        assert params == {"scope": SCOPE_METRICS[name]}
        assert entry["source"] == "device_trace"
        assert entry["moves"] == "env_frames_per_s"
    assert "workloads" not in entry  # every cell


# ------------------------------------------- the readers on a hand-built record


def _record(dropped=0):
    return {
        "phases": [
            ("setup.agent", 1.0, 2.0),  # an earlier agent of the process
            ("setup.agent", 10.0, 20.0),
            ("setup.first_update", 30.0, 40.0),
            ("setup.agent", 110.0, 120.0),  # built after the window opened
        ],
        "compiles": [
            (BACKEND, "jit(early)", 1.5, 0.25),
            (TRACE, "inner", 12.0, 1.0),      # [11, 12] nested in ...
            (TRACE, "outer", 13.0, 3.0),      # ... [10, 13]
            (LOWER, "jit(outer)", 14.0, 1.0),
            (BACKEND, "jit(outer)", 16.0, 2.0),
            (BACKEND, "jit(a)", 17.0, 0.5),
            (BACKEND, "jit(b)", 18.0, 0.5),
            (BACKEND, "jit(outside)", 25.0, 4.0),  # between the phases
            (TRACE, "step", 33.0, 5.0),       # began before its phase: clipped
            (BACKEND, "jit(step)", 39.0, 6.0),
        ],
        "dropped": dropped,
    }


@pytest.fixture
def handbuilt(monkeypatch):
    def install(record):
        monkeypatch.setattr(introspect, "process_record", lambda: record)
        # the harness's own log of the backend's events is never cut
        return {"window": (100.0, 110.0), "counters": {"backend_compile_stamps": [
            c[2] for c in _record()["compiles"] if c[0] == BACKEND]}}

    return install


def test_program_readers_on_a_handbuilt_record(handbuilt):
    ev = handbuilt(_record())
    # the last phase of its name that ended before the window opened
    assert program_record.phase_seconds(ev, "setup.agent") == 10.0
    assert program_record.phase_seconds(ev, "setup.first_update") == 10.0
    # three programs inside setup.agent, the fourth between the phases
    assert program_record.programs_in_phase(ev, "setup.agent") == 3
    assert program_record.programs_in_phase(ev, "setup.first_update") == 1
    # nested traces count once: [10, 13] and [13, 14], not 1 + 3 + 1
    assert program_record.compile_seconds_in_phase(
        ev, "setup.agent", [TRACE, LOWER]) == 4.0
    assert program_record.compile_seconds_in_phase(
        ev, "setup.agent", [BACKEND]) == 3.0
    # an event that began before its phase counts from the phase's start
    assert program_record.compile_seconds_in_phase(
        ev, "setup.first_update", [TRACE, LOWER]) == 3.0
    assert program_record.compile_seconds_in_phase(
        ev, "setup.first_update", [BACKEND]) == 6.0


def test_program_readers_give_none_where_there_is_nothing_to_read(
        handbuilt, monkeypatch):
    ev = handbuilt(_record())
    assert program_record.phase_seconds(ev, "setup.never_ran") is None
    assert program_record.programs_in_phase(ev, "setup.never_ran") is None
    assert program_record.compile_seconds_in_phase(
        ev, "setup.never_ran", [TRACE]) is None
    # a phase that ended only after the window opened is another agent's
    assert program_record.phase_seconds({"window": (5.0, 6.0)}, "setup.first_update") is None
    # the cap dropped events and the record no longer reaches back to the
    # phase's start: its seconds stand, the seconds of its events cannot be
    # summed, and its programs are counted all the same (Kimi's cell read
    # nothing there from PR 30 to PR 34): from the harness's own log
    cut = _record(dropped=7)
    cut["compiles"] = cut["compiles"][4:]
    ev = handbuilt(cut)
    assert program_record.phase_seconds(ev, "setup.agent") == 10.0
    assert program_record.programs_in_phase(ev, "setup.agent") == 3
    assert program_record.compile_seconds_in_phase(
        ev, "setup.agent", [BACKEND]) is None
    assert program_record.programs_in_phase(ev, "setup.first_update") == 1
    # an evidence without that log (a loop that arms none) counts nothing
    assert program_record.programs_in_phase(
        {"window": ev["window"]}, "setup.agent") is None
    # a program without the record (the parent commit under this benchmark)
    monkeypatch.delattr(introspect, "process_record")
    for read, params in (
        (program_record.phase_seconds, {"phase": "setup.agent"}),
        (program_record.programs_in_phase, {"phase": "setup.agent"}),
        (program_record.compile_seconds_in_phase,
         {"phase": "setup.agent", "events": [BACKEND]}),
    ):
        assert read({"window": (100.0, 110.0)}, **params) is None


# ------------------------------------------------- the rehearsal on the CPU


@pytest.fixture
def throwaway(tmp_path, monkeypatch):
    """A tiny Anakin cell whose ``BENCHMARK.json`` lists the five program
    metrics and the five scope metrics; the metrics' own files are the
    real ones, found under ``benchmarks/`` after the temp directory."""
    for kind in ("configs", "traffic"):
        (tmp_path / kind).mkdir()
    n_dev = len(jax.devices())
    (tmp_path / "configs" / "tiny_anakin.json").write_text(json.dumps({
        "loop": "anakin", "preset": "atari_impala", "reference_chunk": 9 * n_dev,
        "overrides": {"updates_per_call": 2, "fused_scan": "interpret",
                      "channels": [4, 8], "precision": "f32"}}))
    (tmp_path / "traffic" / "tiny_job.json").write_text(json.dumps(
        {"overrides": {"num_envs": n_dev, "unroll_len": 8}}))
    real = {m["name"]: m for m in json.load(
        open(os.path.join(run.ROOT, "BENCHMARK.json")))["per_layer"]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "-m", "benchmarks.run"], "paths": ["benchmarks"],
        "run_seconds": 2, "configs": [],
        "workloads": [{"name": "tiny.job", "config": "tiny_anakin",
                       "traffic": "tiny_job", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": n, "unit": "x", "better": "higher", "bound": 0.05,
             "source": "host_clock"} for n in ("env_frames_per_s", "setup_s")],
        "per_layer": [real[n] for n in (*PROGRAM_METRICS, *SCOPE_METRICS)],
    }))

    def on_the_cpu(chips):
        d = jax.devices()[0]
        return {"platform": d.platform, "kind": d.device_kind,
                "count": len(jax.devices()), "cache_dir": None}

    monkeypatch.setattr(device, "require_chips", on_the_cpu)
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path / "_out"))
    return ["--spec", str(tmp_path / "BENCHMARK.json"), "--data-root", str(tmp_path)]


def test_rehearsal_prints_the_five_program_metrics(throwaway, capsys):
    assert run.main([*throwaway, "--workload", "tiny.job", "--seed",
                     "2400000017", "--seconds", "1", "--trace", "1"]) == 0
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    assert line["correct"] is True, captured.err[-2000:]
    # no chip, so no device trace: the five scope metrics stay out
    assert set(line["metrics"]) == set(PROGRAM_METRICS)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    units = {k: v["unit"] for k, v in line["metrics"].items()}
    assert units["make_agent_programs"] == "count" and units["make_agent_s"] == "s"
    # the program's own make_agent against the harness's, timed from outside
    phases = ast.literal_eval(re.search(r"set-up phases \(s\): (\{.*\})", captured.err).group(1))
    assert abs(got["make_agent_s"] - phases["make_agent"]) < 0.5
    assert 0 < got["init_state_s"] <= got["make_agent_s"]
    assert got["make_agent_programs"] >= 1
    assert got["make_agent_programs"] == int(got["make_agent_programs"])
    # the harness's own log and the program's record count the same programs
    record = introspect.process_record()
    t0, t1 = [(a, b) for n, a, b in record["phases"] if n == "setup.agent"][-1]
    if not record["dropped"]:
        assert got["make_agent_programs"] == sum(
            1 for c in record["compiles"] if c[0] == BACKEND and t0 <= c[2] <= t1)
    # the first update call traced, lowered and compiled the step inside
    # the harness's warm-up call
    assert got["step_trace_lower_s"] > 0 and got["step_load_s"] > 0
    assert got["step_trace_lower_s"] + got["step_load_s"] <= phases["warm_call"]
