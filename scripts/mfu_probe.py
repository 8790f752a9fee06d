"""Pixel-path MFU probe (VERDICT round 3, Next #2): the flagship CNN config
measured 1.45% MFU and is compute-bound (dispatch amortized away), so the
question is WHERE the update's 0.114 s go and what the achievable ceiling
is. This script answers it on the real chip with two measurements:

1. **Geometry sweep** — full fused update at (256, 512 envs; 256x64
   unroll; 1024-env fit geometry): does a bigger per-step conv batch lift
   the MXU utilization the way the roofline predicts?
2. **Phase split** — the update is rollout (T sequential policy forwards
   + env physics + rendering, batch B) followed by the learner pass (one
   T*B-batch forward/backward). Each phase is compiled and timed
   standalone with XLA's own FLOP count, attributing both the seconds and
   the FLOPs. A rollout-dominated step bounds MFU by the env/render VPU
   work, not the convs — a different fix (wider batch, smaller T) than a
   learner-dominated one (layout/dtype/channel-width).

One ``kind="mfu_probe"`` run-log entry carries every row.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
sys.path.insert(0, _HERE)
from asyncrl_tpu.utils import runtime  # noqa: E402
from roofline import measure, peak_for  # noqa: E402


def _flops_of(compiled) -> float | None:
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    flops = float(cost.get("flops", float("nan")))
    return None if math.isnan(flops) else flops


def _timed_calls(fn, sync, min_seconds: float = 2.0, warmup: int = 2):
    """Time ``fn()`` repeatedly; ``sync(out)`` does a D2H read, the sync
    every timing boundary in the repo uses (bench.py sync note)."""
    for _ in range(warmup):
        sync(fn())
    calls = 0
    t0 = time.perf_counter()
    while True:
        sync(fn())
        calls += 1
        if time.perf_counter() - t0 >= min_seconds and calls >= 3:
            break
    return calls, time.perf_counter() - t0


def phase_split(cfg) -> dict:
    """Rollout-only vs learner-only timing + FLOPs for one geometry, on a
    plain single-device jit (no shard_map; representative, not identical,
    of the 1-chip sharded program)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from asyncrl_tpu.api.trainer import Trainer
    from asyncrl_tpu.learn.learner import _algo_loss, entropy_coef_at
    from asyncrl_tpu.ops import distributions
    from asyncrl_tpu.ops.normalize import normalizing_apply
    from asyncrl_tpu.rollout.anakin import unroll

    cfg = cfg.replace(updates_per_call=1)
    trainer = Trainer(cfg)
    env, state = trainer.env, trainer.state
    dist = distributions.for_config(cfg, env.spec)
    napply = normalizing_apply(trainer.model.apply, state.obs_stats)

    def rollout_only(params, actor):
        actor, ro, _ = unroll(
            napply, params, env, actor, cfg.unroll_len, dist=dist,
            reward_scale=cfg.reward_scale, step_cost=cfg.step_cost,
        )
        return actor, ro

    def learn_only(params, actor_params, ro):
        def scaled(p, frag):
            loss, metrics = _algo_loss(
                cfg, napply, p, frag, axis_name=None, dist=dist,
                target_params=actor_params,
                entropy_coef=entropy_coef_at(cfg, state.update_step),
            )
            return loss, (loss, metrics)

        (_, _), grads = jax.value_and_grad(scaled, has_aux=True)(
            params, ro
        )
        return grads

    ro_c = jax.jit(rollout_only).lower(state.params, state.actor).compile()
    _, rollout = ro_c(state.params, state.actor)
    ln_c = (
        jax.jit(learn_only)
        .lower(state.params, state.actor_params, rollout)
        .compile()
    )

    def sync_ro(out):
        np.asarray(jax.device_get(out[1].rewards[0, 0]))

    def sync_ln(grads):
        leaf = jax.tree.leaves(grads)[0]
        np.asarray(jax.device_get(leaf.ravel()[0]))

    ro_calls, ro_s = _timed_calls(
        lambda: ro_c(state.params, state.actor), sync_ro
    )
    ln_calls, ln_s = _timed_calls(
        lambda: ln_c(state.params, state.actor_params, rollout), sync_ln
    )

    dev = jax.devices()[0]
    peak = peak_for(dev.device_kind)
    rows = {}
    for name, compiled, calls, secs in (
        ("rollout", ro_c, ro_calls, ro_s),
        ("learner", ln_c, ln_calls, ln_s),
    ):
        flops = _flops_of(compiled)
        s_per = secs / calls
        achieved = flops / s_per if flops is not None else None
        rows[name] = {
            "seconds_per_call": round(s_per, 5),
            "flops_per_call": flops,
            "achieved_tflops": (
                round(achieved / 1e12, 3) if achieved is not None else None
            ),
            "mfu": (
                round(achieved / peak, 4)
                if peak and achieved is not None
                else None
            ),
        }
    total = rows["rollout"]["seconds_per_call"] + rows["learner"]["seconds_per_call"]
    rows["rollout_fraction_of_step"] = round(
        rows["rollout"]["seconds_per_call"] / total, 3
    )
    trainer.close()
    return rows


def main() -> int:
    args = sys.argv[1:]
    overrides = [a for a in args if "=" in a]
    names = [a for a in args if "=" not in a]
    preset_name = names[0] if names else "atari_impala"

    runtime.require_tpu("mfu_probe")
    runtime.enable_compile_cache()

    from asyncrl_tpu.configs import presets
    from asyncrl_tpu.utils import bench_history
    from asyncrl_tpu.utils.config import override

    base = override(
        presets.get(preset_name).replace(updates_per_call=8, num_envs=256),
        overrides,
    )

    # Variants scale RELATIVE to the base geometry (overridable, so a CPU
    # smoke test can run the same code path on toy shapes): wider conv
    # batch (2x/4x envs — the 4x needs the grad_accum+remat fit, matching
    # the 1024-env BASELINE geometry on chip) and a longer unroll (bigger
    # learner batch at the same per-step conv batch).
    # A caller's `timeout` sends SIGTERM, which would normally kill the
    # process without banking anything; convert it to SystemExit
    # so the finally-block below records whatever rows completed.
    import signal

    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    nv = base.num_envs
    sweep = []
    split = {"skipped": True}
    completed = False
    try:
        for label, variant in (
            (f"{nv}envs", base),
            (f"{2 * nv}envs", base.replace(num_envs=2 * nv)),
            (
                f"{4 * nv}envs_fit",
                base.replace(num_envs=4 * nv, grad_accum=4, remat=True),
            ),
            # The MXU lane-utilization experiment (docs/MFU.md): channel
            # widths 64/128/128 raise the conv N-dimension ceiling from
            # ~22% to ~100% of the 128-wide array. If the analysis is
            # right, this variant's MFU is ~4x the base at similar
            # fps-per-FLOP — evidence that the base MFU is architecture-
            # bound, not scheduling-bound. Wide activations are ~4x the
            # narrow ones (same footprint as the narrow 4x-envs
            # geometry), so it needs the same grad_accum+remat fit.
            (
                "wide_torso_fit",
                base.replace(
                    channels=(64, 128, 128), grad_accum=4, remat=True
                ),
            ),
        ):
            try:
                row = measure(variant, preset_name)
            except Exception as e:  # per-variant OOM must not kill the probe
                sweep.append({"label": label, "error": str(e)[:300]})
                continue
            row["label"] = label
            sweep.append(row)
            print(json.dumps(row))

        try:
            split = phase_split(base)
            print(json.dumps(split))
        except Exception as e:  # the sweep rows must get banked regardless
            split = {"error": str(e)[:300]}
            print(f"mfu_probe: phase split failed: {e}", file=sys.stderr)
        completed = True
    finally:
        # Bank whatever exists — a timeout mid-probe loses only the
        # in-flight variant, not the completed measurements. An
        # interrupted probe exits nonzero and may be retried, so the
        # retry's FULL row would sit next to this one: partial=true lets
        # consumers prefer the complete row (ADVICE r4 — no silent dupes).
        if sweep:
            entry = {
                "kind": "mfu_probe",
                "preset": preset_name,
                **bench_history.device_entry(),
                "sweep": sweep,
                "phase_split_base": split,
                **({} if completed else {"partial": True}),
            }
            try:
                bench_history.record(entry)
            except OSError as e:
                print(f"mfu_probe: could not persist: {e}", file=sys.stderr)
    print(json.dumps({"ok": True, "rows": len(sweep)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
