"""bench.py entry-point decision logic (the driver-run round-end artifact:
its output shape and provenance labeling must not regress).

The heavy measurement path is stubbed; these tests pin main()'s routing —
driver mode vs explicit preset, the overrides refusal, and that a failed
leg fails the run. The device rule runs for real, under the explicit
ASYNCRL_FORCE_CPU=1 opt-in."""

import json

import pytest


@pytest.fixture(autouse=True)
def _explicit_cpu(monkeypatch):
    monkeypatch.setenv("ASYNCRL_FORCE_CPU", "1")


def test_driver_mode_refuses_overrides(monkeypatch):
    import bench

    monkeypatch.setattr("sys.argv", ["bench.py", "num_envs=4096"])
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 2


def test_explicit_preset_passes_overrides(monkeypatch, capsys):
    import bench

    calls = []
    monkeypatch.setattr(
        bench,
        "measure_preset",
        lambda name, ov: calls.append((name, ov))
        or {"metric": name, "value": 1, "unit": "frames/sec"},
    )
    monkeypatch.setattr("sys.argv", ["bench.py", "pong_impala", "num_envs=64"])
    bench.main()
    assert calls == [("pong_impala", ["num_envs=64"])]
    out = json.loads(capsys.readouterr().out.strip())
    assert "pixel_flagship" not in out  # single-measurement mode


def test_fused_ab_mode_routes_with_overrides(monkeypatch, capsys):
    """`bench.py fused_ab [k=v ...]` routes to the device-hot-path A/B
    probe (never to measure_preset — there is no preset by that name)."""
    import bench

    calls = []
    monkeypatch.setattr(
        bench,
        "measure_fused_ab",
        lambda ov: calls.append(ov)
        or {"metric": "fused_ab", "fused_speedup": 1.0, "unit": "frames/sec"},
    )
    monkeypatch.setattr("sys.argv", ["bench.py", "fused_ab", "num_envs=32"])
    bench.main()
    assert calls == [["num_envs=32"]]
    out = json.loads(capsys.readouterr().out.strip())
    assert out["metric"] == "fused_ab"


def test_driver_mode_measures_both_flagships_or_fails(monkeypatch, capsys):
    """Driver mode measures the vector headline AND the pixel rider, on
    whatever platform the device rule admitted; a pixel leg that fails —
    a refusal exit included — fails the run and prints nothing."""
    import bench

    measured = []

    def fake_measure(name, ov):
        measured.append((name, ov))
        return {"metric": name, "value": 123, "unit": "frames/sec"}

    monkeypatch.setattr(bench, "measure_preset", fake_measure)
    monkeypatch.setattr("sys.argv", ["bench.py"])
    bench.main()
    assert measured == [
        ("pong_impala", []),
        ("atari_impala", ["updates_per_call=8", "num_envs=256"]),
    ]
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 123 and out["pixel_flagship"]["value"] == 123
    # Nothing rides along that this run did not measure.
    assert set(out) == {"metric", "value", "unit", "pixel_flagship"}

    def pixel_refuses(name, ov):
        if name == "atari_impala":
            raise SystemExit(1)
        return {"metric": name, "value": 123, "unit": "frames/sec"}

    monkeypatch.setattr(bench, "measure_preset", pixel_refuses)
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 1
    assert capsys.readouterr().out == ""
