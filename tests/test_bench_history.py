"""bench_history: the local run log. These tests pin the properties its
writers rely on — atomic appends, corrupted-file tolerance, provenance
stamps — and the rule that replaced the remembered-number rider: a
benchmark with no chip fails, it does not fall back."""

import json
import os
import subprocess
import sys

import pytest

from asyncrl_tpu.utils import bench_history

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_record_appends_and_stamps(tmp_path):
    path = str(tmp_path / "hist.json")
    e1 = bench_history.record(
        {"kind": "throughput", "preset": "a", "platform": "tpu"}, path=path
    )
    assert e1["ts"].endswith("Z")
    bench_history.record(
        {"kind": "throughput", "preset": "b", "platform": "cpu"}, path=path
    )
    entries = bench_history.load(path)
    assert [e["preset"] for e in entries] == ["a", "b"]
    # File is plain JSON anyone can read directly.
    with open(path) as f:
        assert json.load(f) == entries


def test_load_tolerates_missing_and_corrupt(tmp_path):
    path = str(tmp_path / "hist.json")
    assert bench_history.load(path) == []
    with open(path, "w") as f:
        f.write("{not json")
    assert bench_history.load(path) == []
    # A corrupt file is replaced wholesale on the next record, not crashed on.
    bench_history.record({"kind": "throughput", "platform": "tpu"}, path=path)
    assert len(bench_history.load(path)) == 1


def test_bench_without_a_chip_exits_nonzero_and_prints_no_metric():
    """`python bench.py` where JAX finds no TPU, and nobody asked for the
    CPU: nonzero exit, the reason on stderr, nothing on stdout — a CPU
    number must never reach a consumer parsing the one JSON line."""
    env = {k: v for k, v in os.environ.items() if k != "ASYNCRL_FORCE_CPU"}
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py")],
        env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120, cwd=_ROOT,
    )
    assert proc.returncode == 4
    assert "no TPU" in proc.stderr
    assert proc.stdout == ""


def test_require_tpu_runs_on_cpu_only_when_asked(monkeypatch, capsys):
    """The one device rule (utils/runtime.py): refuse without a TPU;
    ASYNCRL_FORCE_CPU=1 is the explicit opt-in, announced on stderr."""
    from asyncrl_tpu.utils import runtime

    monkeypatch.delenv("ASYNCRL_FORCE_CPU", raising=False)
    with pytest.raises(SystemExit) as e:
        runtime.require_tpu("tool")
    assert e.value.code == 4
    assert "tool: no TPU" in capsys.readouterr().err
    monkeypatch.setenv("ASYNCRL_FORCE_CPU", "1")
    assert runtime.require_tpu("tool") == "cpu"
    assert "running on CPU" in capsys.readouterr().err
    assert bench_history.device_entry()["platform"] == "cpu"


def test_record_stamps_harness_provenance(tmp_path):
    """VERDICT round 2 Weak #1: every entry carries captured_by; record()
    stamps "harness" (it runs inside the measuring process) unless the
    caller explicitly says otherwise (manual backfills)."""
    path = str(tmp_path / "hist.json")
    e = bench_history.record(
        {"kind": "throughput", "platform": "tpu"}, path=path
    )
    assert e["captured_by"] == "harness"
    e2 = bench_history.record(
        {"kind": "throughput", "platform": "tpu", "captured_by": "manual"},
        path=path,
    )
    assert e2["captured_by"] == "manual"


def test_atomic_write_leaves_no_tmp_droppings(tmp_path):
    path = str(tmp_path / "hist.json")
    for i in range(3):
        bench_history.record(
            {"kind": "throughput", "platform": "tpu", "i": i}, path=path
        )
    assert sorted(os.listdir(tmp_path)) == ["hist.json"]


def test_resolve_bench_config_platform_aware_fusion():
    """The headline's fused-dispatch default: K=512 on an accelerator,
    K=8 on an explicit CPU run (a K=512 CPU call outlives any caller
    timeout), explicit overrides always win."""
    import bench

    assert bench.resolve_bench_config(
        "pong_impala", [], on_cpu=False
    ).updates_per_call == 512
    assert bench.resolve_bench_config(
        "pong_impala", [], on_cpu=True
    ).updates_per_call == 8
    assert bench.resolve_bench_config(
        "pong_impala", ["updates_per_call=64"], on_cpu=True
    ).updates_per_call == 64
    # cartpole widens its env batch to saturate a chip; other overrides
    # still apply on top.
    cfg = bench.resolve_bench_config(
        "cartpole_impala", ["unroll_len=16"], on_cpu=False
    )
    assert cfg.num_envs == 8192 and cfg.unroll_len == 16
