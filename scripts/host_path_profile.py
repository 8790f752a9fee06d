"""Host-path (Sebulba) performance identity: the host backend had one
measured number (943 fps, pendulum_native_ppo, on the previous runtime)
but no stated model of what it SHOULD achieve. This profiler measures the
three component rates that bound a host pipeline and records them with
the derived identity:

    pipeline_fps <= min(pool_ceiling, batch_size * inference_rate)

- **pool_ceiling**: raw C++ envpool stepping rate (random actions, no
  learner, no inference) — the host-simulation bound.
- **inference_rate**: calls/sec of the jitted policy forward at the
  per-thread batch size — the action-service bound: every env step
  pays one dispatch + D2H round trip to the device. Unmeasured on the
  current runtime (ROADMAP A2).
- **pipeline_fps**: the assembled SebulbaTrainer, measured briefly.

One ``kind="host_path"`` ledger row carries all three plus the derived
bound fraction. The inference rate is labeled with the platform it was
served on (ASYNCRL_FORCE_CPU=1 for the explicit CPU run).

    python scripts/host_path_profile.py [preset] [key=value ...]
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from asyncrl_tpu.utils import runtime  # noqa: E402


def pool_ceiling(env_id: str, num_envs: int, seconds: float = 2.0) -> dict:
    """Raw native-pool step rate with random actions (no policy)."""
    from asyncrl_tpu.envs import native_pool

    pool = native_pool.NativeEnvPool(env_id, num_envs, seed=0)
    try:
        rng = np.random.default_rng(0)

        def actions():
            if pool.continuous:
                return rng.uniform(
                    -1, 1, (num_envs, pool.action_dim)
                ).astype(np.float32)
            return rng.integers(0, pool.num_actions, num_envs, np.int32)

        pool.reset()
        for _ in range(3):
            pool.step(actions())
        steps = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            pool.step(actions())
            steps += 1
        elapsed = time.perf_counter() - t0
    finally:
        pool.close()
    return {
        "env_id": env_id,
        "num_envs": num_envs,
        "pool_fps": round(steps * num_envs / elapsed),
    }


def inference_rate(cfg, batch: int, seconds: float = 2.0) -> dict:
    """Jitted greedy/sample policy forward rate at the per-thread batch."""
    import jax

    from asyncrl_tpu.api.sebulba_trainer import SebulbaTrainer

    trainer = SebulbaTrainer(cfg.replace(total_env_steps=0))
    try:
        infer = trainer._inference_fn
        params = trainer._store.get()[0]
        obs = np.zeros((batch, *trainer.spec.obs_shape), np.float32)
        key = jax.random.PRNGKey(0)
        out = infer(params, obs, key)
        np.asarray(jax.device_get(jax.tree.leaves(out)[0]))  # real sync
        calls = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            out = infer(params, obs, key)
            np.asarray(jax.device_get(jax.tree.leaves(out)[0]))
            calls += 1
        elapsed = time.perf_counter() - t0
    finally:
        trainer.close()
    return {
        "batch": batch,
        "calls_per_sec": round(calls / elapsed, 1),
        "served_fps": round(calls * batch / elapsed),
    }


def pipeline_fps(cfg, seconds: float = 30.0) -> dict:
    """Assembled-pipeline throughput over a short training burst."""
    from asyncrl_tpu.api.factory import make_agent

    fps_log: list[float] = []
    t0 = time.perf_counter()

    class _Enough(Exception):
        pass

    def cb(m):
        fps_log.append(m["fps"])
        if time.perf_counter() - t0 > seconds:
            raise _Enough

    trainer = make_agent(cfg)
    try:
        trainer.train(callback=cb)
    except _Enough:
        pass
    finally:
        trainer.close()
    # First window includes compile; steady state is the tail.
    tail = fps_log[1:] or fps_log
    return {
        "windows": len(fps_log),
        "pipeline_fps": round(float(np.mean(tail))) if tail else None,
    }


def main() -> int:
    args = sys.argv[1:]
    overrides = [a for a in args if "=" in a]
    names = [a for a in args if "=" not in a]
    preset_name = names[0] if names else "pendulum_native_ppo"

    runtime.require_tpu("host_path_profile")
    runtime.enable_compile_cache()

    from asyncrl_tpu.configs import presets
    from asyncrl_tpu.utils import bench_history
    from asyncrl_tpu.utils.config import override

    cfg = override(presets.get(preset_name), overrides)
    if cfg.backend not in ("sebulba", "cpu_async"):
        print(
            f"host_path_profile: preset {preset_name!r} is not a host "
            "backend",
            file=sys.stderr,
        )
        return 2

    per_thread = cfg.num_envs // cfg.actor_threads
    pool = pool_ceiling(cfg.env_id, cfg.num_envs)
    print(json.dumps(pool))
    infer = inference_rate(cfg, per_thread)
    print(json.dumps(infer))
    pipe = pipeline_fps(cfg)
    print(json.dumps(pipe))

    # The identity: per-thread actors serve per_thread envs per inference
    # call; actor_threads of them share the host. The bound is the
    # smaller of host simulation and action service.
    bound = min(pool["pool_fps"], infer["served_fps"] * cfg.actor_threads)
    entry = {
        "kind": "host_path",
        "preset": preset_name,
        **bench_history.device_entry(),
        "num_envs": cfg.num_envs,
        "actor_threads": cfg.actor_threads,
        "pool_fps": pool["pool_fps"],
        "inference_batch": infer["batch"],
        "inference_calls_per_sec": infer["calls_per_sec"],
        "inference_served_fps": infer["served_fps"],
        "pipeline_fps": pipe["pipeline_fps"],
        "component_bound_fps": bound,
        "bound_fraction": (
            round(pipe["pipeline_fps"] / bound, 3)
            if pipe["pipeline_fps"] and bound
            else None
        ),
    }
    try:
        entry = bench_history.record(entry)
    except OSError as e:
        print(f"host_path_profile: could not persist: {e}", file=sys.stderr)
    print(json.dumps(entry))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
