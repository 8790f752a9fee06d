"""Benchmark harness: prints ONE JSON line with the north-star metric.

Metric (BASELINE.json:2): env frames/sec for the IMPALA V-trace configuration
on TPU. ``vs_baseline`` is the ratio against the driver-set target of
1,000,000 env fps (BASELINE.md — the reference itself has no recorded
published numbers; see SURVEY.md §0/§6).

Usage: python bench.py [preset] [key=value ...]
Default (no preset) = driver mode: measures BOTH flagships — the vector
Pong headline (pong_impala; dispatch-amortized MLP) and, riding in the
``pixel_flagship`` key with equal prominence, the pixel-path CNN flagship
(atari_impala — the reference's real PongNoFrameskip-v4 shape). Explicit
preset = that one measurement only.

Runs on the TPU or exits nonzero (``utils.runtime.require_tpu``): no probe,
no fallback, no remembered number. ``ASYNCRL_FORCE_CPU=1`` asks for a CPU
run explicitly; every metric label then carries ``cpu``. A failed leg is
a failed run.
"""

from __future__ import annotations

import json
import os
import sys


def jnp_abs_sum(x):
    import jax.numpy as jnp

    return jnp.sum(jnp.abs(x.astype(jnp.float32)))


def timed_update_window(
    update,
    state,
    updates_per_call: int,
    warmup: int = 3,
    min_seconds: float = 2.0,
    min_calls: int = 10,
):
    """Shared measurement harness (bench.py + scripts/bench_matrix.py — ONE
    copy, so a sync-discipline fix can never drift between them).

    SYNC DISCIPLINE: every timing boundary reads the device-side update
    counter off the tail of the dependency chain — a D2H read, which is
    both the sync and the execution guard's evidence. On this runtime
    ``jax.block_until_ready`` blocks just as well (v5e, jax 0.9.0, chip
    run of PR 21: five atari_impala calls — 40 ms to dispatch, 4.51 s by
    block_until_ready, +1 ms for the D2H read after it; 4.52 s and +1 ms
    in the other order), so the read is kept for the counter, not as a
    workaround.

    Time-targeted window: run for >= ``min_seconds`` of wall clock (and >=
    ``min_calls`` calls): a fixed small iteration count makes a window of
    a few ms on fast configs, which per-call dispatch jitter dominates.

    Returns ``(state, timed_calls, elapsed_seconds)``. Raises RuntimeError
    if the device-side update counter disagrees with the number of updates
    dispatched (the counter cannot ack work that never ran).
    """
    import time

    from asyncrl_tpu.utils.checkpoint import _step_of

    def sync(s) -> int:
        return _step_of(s)  # D2H read: forces all queued work

    # Counter base: the state may be non-fresh (checkpoint auto-resume), so
    # the guard compares counter DELTA, not the absolute value.
    base = sync(state)
    for _ in range(warmup):
        state, _ = update(state)
    sync(state)

    timed = 0
    t0 = time.perf_counter()
    while True:
        state, _ = update(state)
        timed += 1
        if timed % min_calls == 0:
            executed = sync(state)
            if time.perf_counter() - t0 >= min_seconds:
                break
    elapsed = time.perf_counter() - t0

    dispatched = (warmup + timed) * updates_per_call
    if executed - base != dispatched:
        raise RuntimeError(
            f"device executed {executed - base} updates, "
            f"dispatched {dispatched}"
        )
    return state, timed, elapsed


def resolve_bench_config(preset_name: str, overrides: list[str], on_cpu: bool):
    """Effective config for one headline measurement (unit-tested: this is
    the driver-run entry point's decision logic).

    - cartpole geometry widens to saturate a chip;
    - the fused-dispatch default: the bench fuses K updates per jitted
      call (updates_per_call — identical training semantics) so per-call
      host dispatch does not cap the MLP presets. K=512 on the accelerator
      is unmeasured on this runtime (ROADMAP A5 redoes the K sweep); an
      explicit CPU run keeps K=8 — one K=512 call is ~75 s of CPU work,
      which blows any caller's timeout before the first timed window
      completes. Explicit overrides always win.
    """
    from asyncrl_tpu.configs import presets
    from asyncrl_tpu.utils.config import override

    cfg = presets.get(preset_name)
    if preset_name == "cartpole_impala":
        cfg = cfg.replace(num_envs=8192)
    if not any(o.startswith("updates_per_call=") for o in overrides):
        cfg = cfg.replace(updates_per_call=8 if on_cpu else 512)
    return override(cfg, overrides)


def measure_preset(preset_name: str, overrides: list[str]) -> dict:
    """Measure one Anakin preset's fused-update throughput; returns the
    headline dict ({metric, value, unit, vs_baseline}). Raises SystemExit
    on a non-tpu backend or integrity failure (unchanged semantics)."""
    import jax

    from asyncrl_tpu.api.trainer import Trainer

    cfg = resolve_bench_config(
        preset_name, overrides, jax.devices()[0].platform == "cpu"
    )
    if cfg.backend != "tpu":
        # Checked on the EFFECTIVE config (preset + overrides): this
        # harness times the Anakin learner's bare update loop; a
        # host-backend config measured that way would record a
        # wrong-architecture fps entry. The pipeline-aware harness
        # handles those.
        print(
            f"bench: effective backend={cfg.backend!r}; measure host "
            "backends with scripts/bench_matrix.py (pipeline-aware) "
            "instead",
            file=sys.stderr,
        )
        sys.exit(2)

    trainer = Trainer(cfg)
    state = trainer.state
    # Real copies: with donate_buffers=true the update donates state's
    # buffers, and an aliasing snapshot would be deleted from under us.
    params0 = jax.tree.map(lambda x: x.copy(), state.params)

    try:
        state, timed, elapsed = timed_update_window(
            trainer.learner.update, state, cfg.updates_per_call
        )
    except RuntimeError as e:
        print(
            f"bench: {e}; refusing to report a throughput number",
            file=sys.stderr,
        )
        sys.exit(1)

    # Execution-integrity guard: a throughput number is only reported for
    # training that actually moved the params.
    import numpy as np

    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(
            lambda a, b: float(jnp_abs_sum(a - b)), state.params, params0
        ),
    )
    if not np.isfinite(delta) or delta == 0.0:
        print(
            f"bench: integrity check failed (param delta {delta}); "
            "refusing to report a throughput number",
            file=sys.stderr,
        )
        sys.exit(1)

    fps = timed * cfg.updates_per_call * cfg.num_envs * cfg.unroll_len / elapsed

    from asyncrl_tpu.utils import bench_history

    dev = bench_history.device_entry()
    bench_history.record_throughput(preset_name, cfg, fps)

    result = {
        "metric": f"env_frames_per_sec ({preset_name}, "
        f"{cfg.num_envs} envs x {cfg.unroll_len} unroll x "
        f"{cfg.updates_per_call} fused updates/call, "
        f"{dev['device_kind']} x{dev['device_count']})",
        "value": round(fps),
        "unit": "frames/sec",
        "vs_baseline": round(fps / bench_history.NORTH_STAR_FPS, 3),
    }
    return result


def measure_fused_ab(overrides: list[str]) -> dict:
    """A/B the fused Pallas V-trace scan against the lax path on one
    identical Anakin config (``python bench.py fused_ab [key=value ...]``)
    — the device-hot-path sibling of scripts/perf_smoke.sh's overlap_ab.

    Two claims, checked separately because they pin different references:

    - **Loss bit-identity**: the fused kernel's contract is bit-equality
      against the SEQUENTIAL lax scan (ops/pallas_scan.py; the
      associative production scan rounds differently by design), so the
      identity arm runs ``fused_scan="lax", scan_impl="sequential"`` and
      the losses must match to the bit on the shared seed.
    - **Throughput**: the perf bar is against the PRODUCTION lax path
      (``fused_scan="lax"`` with the default scan_impl resolution) —
      beating a deliberately-slow reference would be a hollow win. On an
      accelerator the fused arm must not be slower beyond
      ASYNCRL_FUSED_AB_TOLERANCE (default 1.10x, the perf_smoke noise
      convention); the CPU interpreter arm only reports (the Pallas
      interpreter is an emulator — its fps is not evidence either way).

    Records one kind="device_hot_path" probe="fused_ab" ledger row.
    """
    import jax
    import numpy as np

    from asyncrl_tpu.api.trainer import Trainer
    from asyncrl_tpu.envs import registered
    from asyncrl_tpu.utils import bench_history

    on_cpu = jax.devices()[0].platform == "cpu"
    fused_mode = "interpret" if on_cpu else "pallas"
    tolerance = float(os.environ.get("ASYNCRL_FUSED_AB_TOLERANCE", "1.10"))
    preset_name = (
        "pong_impala" if "JaxPong-v0" in registered() else "cartpole_impala"
    )
    cfg = resolve_bench_config(preset_name, overrides, on_cpu)
    if on_cpu:
        # The interpreter arm runs the kernel as a Python emulation: keep
        # the CPU geometry small enough that the probe finishes inside a
        # CI window. Explicit overrides win, as everywhere in bench.py.
        if not any(o.startswith("num_envs=") for o in overrides):
            cfg = cfg.replace(num_envs=64)
        if not any(o.startswith("updates_per_call=") for o in overrides):
            cfg = cfg.replace(updates_per_call=4)
    if cfg.backend != "tpu":
        print(
            f"bench: fused_ab needs the Anakin backend, got "
            f"{cfg.backend!r}",
            file=sys.stderr,
        )
        sys.exit(2)

    def losses_of(arm_cfg, calls: int = 3):
        trainer = Trainer(arm_cfg)
        state = trainer.state
        out = []
        for _ in range(calls):
            state, metrics = trainer.learner.update(state)
            out.append(np.asarray(jax.device_get(metrics["loss"])))
        return np.stack(out), trainer, state

    # Identity arm: fused vs the sequential lax reference, same seed.
    fused_losses, fused_trainer, fused_state = losses_of(
        cfg.replace(fused_scan=fused_mode)
    )
    seq_losses, _, _ = losses_of(
        cfg.replace(fused_scan="lax", scan_impl="sequential")
    )
    if not np.array_equal(fused_losses, seq_losses):
        print(
            "bench: fused_ab FAILED — fused losses diverged from the "
            f"sequential lax reference (max abs diff "
            f"{np.max(np.abs(fused_losses - seq_losses))})",
            file=sys.stderr,
        )
        sys.exit(1)

    # Throughput arms: fused (continuing the warm trainer) vs the
    # PRODUCTION lax path, both through the shared sync-disciplined
    # window.
    _, timed_f, elapsed_f = timed_update_window(
        fused_trainer.learner.update, fused_state, cfg.updates_per_call
    )
    lax_losses, lax_trainer, lax_state = losses_of(
        cfg.replace(fused_scan="lax")
    )
    _, timed_l, elapsed_l = timed_update_window(
        lax_trainer.learner.update, lax_state, cfg.updates_per_call
    )
    per_call = cfg.updates_per_call * cfg.num_envs * cfg.unroll_len
    fps_fused = timed_f * per_call / elapsed_f
    fps_lax = timed_l * per_call / elapsed_l

    if not on_cpu and fps_fused * tolerance < fps_lax:
        print(
            f"bench: fused_ab FAILED — fused path slower "
            f"({fps_fused:,.0f} vs {fps_lax:,.0f} fps, "
            f"tolerance {tolerance}x)",
            file=sys.stderr,
        )
        sys.exit(1)

    dev = bench_history.device_entry()
    bench_history.record({
        "kind": "device_hot_path",
        "probe": "fused_ab",
        "preset": preset_name,
        **dev,
        "num_envs": cfg.num_envs,
        "unroll_len": cfg.unroll_len,
        "updates_per_call": cfg.updates_per_call,
        "fused_impl": fused_mode,
        "fps_fused": round(fps_fused),
        "fps_lax": round(fps_lax),
        "fused_speedup": round(fps_fused / fps_lax, 3),
        "losses_bit_identical": True,
    })
    return {
        "metric": f"fused_ab ({preset_name}, {cfg.num_envs} envs x "
        f"{cfg.unroll_len} unroll x {cfg.updates_per_call} fused "
        f"updates/call, {fused_mode}, {dev['device_kind']} "
        f"x{dev['device_count']})",
        "fps_fused": round(fps_fused),
        "fps_lax": round(fps_lax),
        "fused_speedup": round(fps_fused / fps_lax, 3),
        "losses_bit_identical": True,
        "unit": "frames/sec",
    }


# Dual-flagship driver mode (VERDICT r3 Next #3/Weak #2): the vector-Pong
# number alone overstates the framework (its MLP is trivial — the win is
# dispatch amortization), so the no-preset invocation measures BOTH
# flagships and reports the pixel-path (CNN, the reference's real Atari
# shape) with equal prominence, at the geometry of the only pixel rows
# on record so rows stay comparable round to round.
PIXEL_FLAGSHIP_PRESET = "atari_impala"
PIXEL_FLAGSHIP_OVERRIDES = ["updates_per_call=8", "num_envs=256"]


def main() -> None:
    from asyncrl_tpu.utils import runtime

    runtime.require_tpu("bench")
    runtime.enable_compile_cache()
    from asyncrl_tpu.envs import registered

    args = sys.argv[1:]
    preset_name = None
    overrides = []
    for a in args:
        if "=" in a:
            overrides.append(a)
        else:
            preset_name = a

    if preset_name == "fused_ab":
        print(json.dumps(measure_fused_ab(overrides)))
        return

    if preset_name is not None:
        print(json.dumps(measure_preset(preset_name, overrides)))
        return

    if overrides:
        # Driver mode's whole point is round-to-round comparable flagship
        # geometry; silently reshaping the vector headline (while the
        # pixel rider ignores the same overrides) would record a
        # non-standard row under the standard label. Overrides belong to
        # explicit single-preset runs.
        print(
            "bench: key=value overrides require naming a preset "
            "(driver mode measures the fixed flagship geometry)",
            file=sys.stderr,
        )
        sys.exit(2)

    # Driver mode: both flagships, vector headline + pixel rider at its
    # fixed geometry (override a pixel run explicitly via
    # `python bench.py atari_impala ...`). Either leg failing — a refusal
    # exit included — fails the run: nothing is printed.
    vector_preset = (
        "pong_impala" if "JaxPong-v0" in registered() else "cartpole_impala"
    )
    result = measure_preset(vector_preset, overrides)
    result["pixel_flagship"] = measure_preset(
        PIXEL_FLAGSHIP_PRESET, list(PIXEL_FLAGSHIP_OVERRIDES)
    )
    print(json.dumps(result))


if __name__ == "__main__":
    main()
