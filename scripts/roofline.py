"""Roofline / MFU analysis for a training config (VERDICT.md round 1,
Next #2: "report fps plus a roofline/MFU estimate and dispatch-vs-compute
breakdown").

    python scripts/roofline.py [preset] [key=value ...]

Method:
- FLOPs per fused update call come from XLA's own cost model
  (``compiled.cost_analysis()['flops']``) — the compiler's count for the
  exact program that runs, not a hand-derived formula.
- Achieved FLOP/s = flops_per_call * calls / elapsed, measured with the
  same D2H-read sync discipline as bench.py.
- MFU = achieved / peak for the device kind (bf16 peak table below; the
  number is labeled n/a on CPU).
- Dispatch-vs-compute: fps measured at updates_per_call=1 vs the
  configured fusion. The gap is the per-call host dispatch amortized
  away by fusion.

One JSON line per run, appended to the local run log (kind="roofline").
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import timed_update_window  # noqa: E402
from asyncrl_tpu.utils import runtime  # noqa: E402

# Dense peak FLOP/s by device kind prefix (bf16 for TPUs). Sources: public
# cloud TPU spec sheets; extend as kinds appear.
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,  # v5e bf16
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
    "TPU v6": 918e12,  # trillium bf16
}


def peak_for(device_kind: str) -> float | None:
    for prefix, peak in PEAK_FLOPS.items():
        if device_kind.startswith(prefix):
            return peak
    return None


def measure(cfg, preset_name: str) -> dict:
    import jax

    from asyncrl_tpu.api.trainer import Trainer

    import math

    trainer = Trainer(cfg)
    state = trainer.state

    # XLA's FLOP count for the exact compiled update program. The AOT
    # executable is ALSO what the timed window runs (an AOT compile does
    # not populate the jit dispatch cache, and the pixel IMPALA-CNN
    # program takes tens of seconds to build cold — one compile per
    # measure(), not two).
    compiled = trainer.learner._step.lower(state).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    flops_per_call = float(cost.get("flops", float("nan")))
    if math.isnan(flops_per_call):
        # Backend without a flops estimate: null, never NaN — the ledger
        # must stay strict JSON.
        flops_per_call = None

    state, calls, elapsed = timed_update_window(
        lambda s: compiled(s), state, cfg.updates_per_call, min_seconds=3.0
    )
    frames = calls * cfg.updates_per_call * cfg.num_envs * cfg.unroll_len
    fps = frames / elapsed
    achieved = (
        flops_per_call * calls / elapsed
        if flops_per_call is not None
        else None
    )

    dev = jax.devices()[0]
    peak = peak_for(dev.device_kind)
    return {
        "preset": preset_name,
        "device_kind": dev.device_kind,
        "num_envs": cfg.num_envs,
        "unroll_len": cfg.unroll_len,
        "updates_per_call": cfg.updates_per_call,
        "frames_per_sec": round(fps),
        "flops_per_call": flops_per_call,
        "achieved_tflops": (
            round(achieved / 1e12, 3) if achieved is not None else None
        ),
        "mfu": (
            round(achieved / peak, 4)
            if peak and achieved is not None
            else None
        ),
        "seconds_per_call": round(elapsed / calls, 5),
    }


def main() -> int:
    args = sys.argv[1:]
    overrides = [a for a in args if "=" in a]
    names = [a for a in args if "=" not in a]
    preset_name = names[0] if names else "atari_impala"

    runtime.require_tpu("roofline")
    runtime.enable_compile_cache()

    from asyncrl_tpu.configs import presets
    from asyncrl_tpu.utils import bench_history
    from asyncrl_tpu.utils.config import override

    cfg = override(presets.get(preset_name), overrides)
    if cfg.backend != "tpu":
        print(
            f"roofline: effective backend={cfg.backend!r}; this analysis "
            "times the Anakin update program — host backends are measured "
            "by scripts/bench_matrix.py",
            file=sys.stderr,
        )
        return 2

    fused = measure(cfg, preset_name)
    if cfg.updates_per_call > 1:
        # Dispatch-vs-compute: the SAME geometry without fusion. The fps
        # gap is pure per-call latency (identical math per update).
        unfused = measure(cfg.replace(updates_per_call=1), preset_name)
        dispatch_overhead = round(
            max(
                0.0,
                unfused["seconds_per_call"]
                - fused["seconds_per_call"] / cfg.updates_per_call,
            ),
            5,
        )
        unfused_fps = unfused["frames_per_sec"]
    else:
        # K=1: nothing to compare against — record the fields as
        # UNMEASURED (null), never as a fabricated zero-overhead datapoint.
        dispatch_overhead = None
        unfused_fps = None

    result = {
        "kind": "roofline",
        **bench_history.device_entry(),
        **fused,
        "unfused_frames_per_sec": unfused_fps,
        "dispatch_overhead_s_per_update": dispatch_overhead,
        "compute_s_per_update": round(
            fused["seconds_per_call"] / max(cfg.updates_per_call, 1), 5
        ),
    }
    try:
        bench_history.record(result)
    except OSError as e:
        print(f"roofline: could not persist: {e}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
