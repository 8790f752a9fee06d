"""Throughput matrix: one JSON line PER WORKLOAD (unlike bench.py, whose
contract is a single line for the driver). Usage:

    python scripts/bench_matrix.py [preset ...] [key=value ...]

Defaults to a representative slice of every workload family: vector/pixel
Atari stand-ins, procedural gridworlds, on-TPU physics locomotion, and the
CartPole smoke. Each preset runs the same measurement discipline as
bench.py — D2H-read sync boundaries, a time-targeted >=2s window, and the
device-side update-counter execution guard — at the preset's own geometry.
"""

from __future__ import annotations

import json
import os
import sys

# Shared measurement harness (sync discipline, execution guard) lives in
# bench.py at the repo root — ONE copy for both entry points.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import timed_update_window  # noqa: E402
from asyncrl_tpu.utils import runtime  # noqa: E402

DEFAULT_PRESETS = [
    "cartpole_impala",
    "cartpole_qlearn",
    "pong_impala",
    # The full 1024-env pixel geometry needs the r3 memory fit: the naive
    # backward's conv activations want 21.3G on a 15.75G v5e (measured
    # OOM 2026-07-31); env-chunked grad accumulation + block remat fit it.
    "atari_impala+fit",
    "procgen_ppo",
    "halfcheetah_ppo",
    "brax_ant_ppo",
    # Population row (api/population.py): K fused seeds advancing in one
    # program, with fused multi-update calls (VERDICT r2 Next #4's ledger
    # evidence). fps counts frames across ALL members.
    "pong_impala+pop4",
    # Host-actor (Sebulba/cpu_async) rows: measured over the live pipeline
    # (actor threads + device learner), not a bare update loop. The
    # inference_server variant quantifies the batched-dispatch win.
    "pendulum_native_ppo",
    "pendulum_native_ppo+server",
    "mujoco_ant_ppo",
    "cartpole_a3c_cpu",
]

# Named variants: "<preset>+server" etc. map to extra overrides;
# "<preset>+popN" runs an N-member population of the preset.
VARIANTS = {
    "+server": ["inference_server=true"],
    # Memory fit for the full-geometry pixel preset (see DEFAULT_PRESETS).
    "+fit": ["grad_accum=4", "remat=true"],
}


def split_variant(name: str) -> tuple[str, list[str], int | None]:
    import re

    m = re.search(r"\+pop(\d+)$", name)
    if m:
        # Fused dispatch is the population's amortization story on a
        # high-latency link (VERDICT r2 Next #4): default the row to K=8,
        # overridable by explicit updates_per_call= args (applied after).
        return name[: m.start()], ["updates_per_call=8"], int(m.group(1))
    for suffix, extra in VARIANTS.items():
        if name.endswith(suffix):
            return name[: -len(suffix)], list(extra), None
    return name, [], None


def bench_host(preset_name: str, cfg, min_seconds: float = 8.0) -> dict:
    """Pipeline throughput for host-backend presets: train() for a wall
    window and average the steady-state metric-window fps (first window
    dropped — it pays the jit compiles). This measures what a user gets —
    actor threads, queue, learner dispatch overlapped — not a bare device
    loop."""
    import time

    from asyncrl_tpu.api.factory import make_agent

    agent = make_agent(cfg)
    windows: list[float] = []
    t0 = time.perf_counter()

    class _Done(Exception):
        pass

    def cb(m):
        windows.append(m["fps"])
        if time.perf_counter() - t0 > min_seconds and len(windows) >= 5:
            raise _Done

    try:
        agent.train(total_env_steps=1 << 40, callback=cb)
    except _Done:
        pass
    finally:
        agent.close()
    if len(windows) < 2:
        raise RuntimeError(f"only {len(windows)} metric windows in window")
    fps = sum(windows[1:]) / len(windows[1:])

    from asyncrl_tpu.utils import bench_history

    dev = bench_history.device_entry()
    bench_history.record_throughput(preset_name, cfg, fps)
    return {
        "preset": preset_name,
        "env_id": cfg.env_id,
        "backend": cfg.backend,
        "host_pool": cfg.host_pool,
        "inference_server": cfg.inference_server,
        "actor_threads": cfg.actor_threads,
        "num_envs": cfg.num_envs,
        "unroll_len": cfg.unroll_len,
        "updates_per_call": cfg.updates_per_call,
        "frames_per_sec": round(fps),
        "device": f"{dev['device_kind']} x{dev['device_count']}",
    }


def bench_population(preset_name: str, cfg, pop_size: int) -> dict:
    """Population throughput: frames/sec across ALL members of a K-fused
    population advancing in one program (same sync/guard discipline)."""
    import jax

    from asyncrl_tpu.api.population import PopulationTrainer

    pop = PopulationTrainer(cfg, pop_size)
    params0 = jax.tree.map(lambda x: x.copy(), pop.state.params)
    state, timed, elapsed = timed_update_window(
        lambda s: pop._step(s, pop.member_seeds),
        pop.state,
        cfg.updates_per_call,
    )
    pop.state = state

    import numpy as np

    delta = sum(
        float(jax.numpy.sum(jax.numpy.abs(a - b)))
        for a, b in zip(
            jax.tree.leaves(state.params), jax.tree.leaves(params0)
        )
    )
    if not (np.isfinite(delta) and delta > 0.0):
        raise RuntimeError(f"param delta {delta}: training did not move")
    fps = (
        timed
        * cfg.updates_per_call
        * pop_size
        * cfg.num_envs
        * cfg.unroll_len
        / elapsed
    )

    from asyncrl_tpu.utils import bench_history

    dev = bench_history.device_entry()
    bench_history.record_throughput(preset_name, cfg, fps)
    pop.close()
    return {
        "preset": preset_name,
        "env_id": cfg.env_id,
        "pop_size": pop_size,
        "num_envs": cfg.num_envs,
        "unroll_len": cfg.unroll_len,
        "updates_per_call": cfg.updates_per_call,
        "frames_per_sec": round(fps),
        "device": f"{dev['device_kind']} x{dev['device_count']}",
    }


def bench_one(preset_name: str, overrides: list[str]) -> dict:
    import jax

    from asyncrl_tpu.api.trainer import Trainer
    from asyncrl_tpu.configs import presets
    from asyncrl_tpu.utils.config import override

    base_name, extra, pop_size = split_variant(preset_name)
    cfg = override(presets.get(base_name), extra + overrides)
    if pop_size is not None:
        return bench_population(preset_name, cfg, pop_size)
    if cfg.backend in ("sebulba", "cpu_async"):
        return bench_host(preset_name, cfg)
    trainer = Trainer(cfg)
    state = trainer.state
    params0 = jax.tree.map(lambda x: x.copy(), state.params)

    state, timed, elapsed = timed_update_window(
        trainer.learner.update, state, cfg.updates_per_call
    )

    import numpy as np

    delta = sum(
        float(jax.numpy.sum(jax.numpy.abs(a - b)))
        for a, b in zip(
            jax.tree.leaves(state.params), jax.tree.leaves(params0)
        )
    )
    # Same refusal policy as bench.py: don't emit an fps figure training
    # didn't earn (frozen params = dropped/ineffective executions).
    if not (np.isfinite(delta) and delta > 0.0):
        raise RuntimeError(f"param delta {delta}: training did not move")
    fps = timed * cfg.updates_per_call * cfg.num_envs * cfg.unroll_len / elapsed

    from asyncrl_tpu.utils import bench_history

    dev = bench_history.device_entry()
    bench_history.record_throughput(preset_name, cfg, fps)
    return {
        "preset": preset_name,
        "env_id": cfg.env_id,
        "num_envs": cfg.num_envs,
        "unroll_len": cfg.unroll_len,
        "frames_per_sec": round(fps),
        "device": f"{dev['device_kind']} x{dev['device_count']}",
    }


def main() -> int:
    runtime.require_tpu("bench_matrix")
    runtime.enable_compile_cache()
    args = sys.argv[1:]
    overrides = [a for a in args if "=" in a]
    names = [a for a in args if "=" not in a] or DEFAULT_PRESETS
    failed = 0
    for name in names:
        try:
            print(json.dumps(bench_one(name, overrides)), flush=True)
        except Exception as e:
            failed += 1
            print(
                json.dumps(
                    {"preset": name, "error": f"{type(e).__name__}: {e}"}
                ),
                flush=True,
            )
    # Nonzero on any failed row: a caller must not record success for
    # rows that never landed.
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
