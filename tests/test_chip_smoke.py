"""chip_smoke.py on the CPU: the command itself must only refuse cleanly,
and the three leg functions get their rehearsal here at tiny sizes with
``fused_scan="interpret"`` (the chip run is the real thing; this keeps
its control flow and its assertions from rotting between chip runs)."""

import os
import subprocess
import sys
import types

import pytest

import chip_smoke
from asyncrl_tpu.configs import presets

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_without_a_chip_and_starts_no_leg():
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120, cwd=_ROOT,
    )
    assert proc.returncode not in (0, None)
    assert "no TPU" in proc.stderr
    # No result line, no leg line, nothing measured.
    assert proc.stdout == ""


def test_leg_anakin_rehearsal():
    cfg = presets.get("atari_impala").replace(
        num_envs=8, updates_per_call=2, unroll_len=4, fused_scan="interpret",
    )
    facts = chip_smoke.leg_anakin(cfg, windows=3, expect_mosaic=False)
    assert facts["updates"] == 6 and facts["fused_scan"] == "interpret"
    assert facts["env_batch_devices"] == 8 and facts["all_reduces"] > 0
    # Nothing compiled for the CPU calls into Mosaic.
    assert facts["mosaic_calls"] == 0


def test_leg_serve_rehearsal():
    # device_queue forced on: "auto" only picks it on a TPU, and the leg
    # must see the queue hand fragments to updates.
    cfg = presets.get("pong_serve").replace(
        fused_scan="interpret", device_queue="on",
    )
    facts = chip_smoke.leg_serve(cfg, updates=12, quota=(12, 2))
    assert facts["act_200"] >= 12 and facts["evaluate_200"] >= 2
    assert facts["generations_seen"] > 1
    assert facts["devq_enqueued"] >= facts["updates"] >= 12


def test_leg_native_rehearsal():
    cfg = presets.get("pendulum_native_ppo").replace(
        num_envs=32, unroll_len=8, fused_scan="interpret",
    )
    facts = chip_smoke.leg_native(cfg, updates=3)
    assert facts["updates"] >= 3 and facts["fused_scan"] == "interpret"


def _result(**kw):
    base = dict(fallback=False, stale=False, generation=3, raw={})
    return types.SimpleNamespace(**{**base, **kw})


def test_assertions_fire_on_doctored_inputs():
    good = [_result() for _ in range(4)]
    chip_smoke.check_gateway_results(good, 4, "/v1/act")
    with pytest.raises(chip_smoke.SmokeFailure, match="fallback"):
        chip_smoke.check_gateway_results(
            good + [_result(fallback=True, generation=-1)], 4, "/v1/act"
        )
    with pytest.raises(chip_smoke.SmokeFailure, match="stale"):
        chip_smoke.check_gateway_results(
            good + [_result(stale=True)], 4, "/v1/act"
        )
    with pytest.raises(chip_smoke.SmokeFailure, match="only 4"):
        chip_smoke.check_gateway_results(good, 5, "/v1/act")

    ok_hlo = 'custom_call_target="tpu_custom_call" ... all-reduce(...)'
    assert chip_smoke.check_step_program(ok_hlo, 4, True) == (1, 1)
    with pytest.raises(chip_smoke.SmokeFailure, match="Mosaic"):
        chip_smoke.check_step_program("fusion ... all-reduce(...)", 4, True)
    with pytest.raises(chip_smoke.SmokeFailure, match="all-reduce"):
        chip_smoke.check_step_program('"tpu_custom_call"', 4, True)
    chip_smoke.check_step_program('"tpu_custom_call"', 1, True)

    clean = {"actor_restarts": 0, "server_restarts": 0, "gateway_restarts": 0}
    chip_smoke.check_restarts(clean)
    with pytest.raises(chip_smoke.SmokeFailure, match="server_restarts=1"):
        chip_smoke.check_restarts({**clean, "server_restarts": 1})
    with pytest.raises(chip_smoke.SmokeFailure, match="no 'gateway_restarts'"):
        chip_smoke.check_restarts({"actor_restarts": 0, "server_restarts": 0})
    with pytest.raises(chip_smoke.SmokeFailure, match="non-finite"):
        chip_smoke.check_finite_losses(
            [{"loss": float("nan"), "grad_norm": 1.0, "env_steps": 8}]
        )
