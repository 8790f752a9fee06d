#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the system still starts on the TPU.

    python3 chip_smoke.py        # from the root of a checkout, on a TPU host

One process drives the three main paths once, through the entry points a
user calls (``asyncrl_tpu.make_agent`` over ``configs.presets``), at the
widths the presets define, with seeded random weights:

- leg A — the Anakin trainer on the widest model (``atari_impala``,
  IMPALA-CNN 16/32/32 on 84x84x4, 256 envs x 8 fused updates per call);
- leg B — the Sebulba host path with the serve core and the gateway
  (``pong_serve`` as written) answering wire requests while it trains;
- leg C — the native C++ host pool under multipass PPO
  (``pendulum_native_ppo``), the pool built on this machine.

Every leg carries hard assertions (:class:`SmokeFailure`); any failure is
a nonzero exit and no result line. Without a TPU the script refuses: it
never switches platform and starts no leg. On success the last line of
stdout is one JSON object, ``{"ok": true, "device": {...}}``, with the
device as JAX reports it. The per-leg seconds it prints are smoke
timings — what a cold (or cache-warm) start costs — never a speed claim.

tests/test_chip_smoke.py rehearses the three leg functions on the CPU at
tiny sizes with ``fused_scan="interpret"``.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import threading
import time

# The string XLA names a Mosaic (compiled Pallas TPU) kernel by, in both
# the lowered StableHLO and the compiled HLO.
MOSAIC_CALL = "tpu_custom_call"
RESTART_KEYS = ("actor_restarts", "server_restarts", "gateway_restarts")


class SmokeFailure(AssertionError):
    """A leg's hard assertion did not hold."""


def check(condition, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


def check_finite_losses(windows: list[dict]) -> None:
    import math

    check(windows, "no metrics window completed")
    for w in windows:
        check(
            math.isfinite(w["loss"]) and math.isfinite(w["grad_norm"]),
            f"non-finite loss/grad_norm in window at env_steps="
            f"{w['env_steps']}: loss={w['loss']} grad_norm={w['grad_norm']}",
        )


def check_restarts(window: dict, keys=RESTART_KEYS) -> None:
    """The supervisor turns actor/server/gateway crashes into restarts and
    the run still exits 0 — so a clean run is one whose counters are 0."""
    for key in keys:
        check(key in window, f"final window carries no {key!r} counter")
        check(window[key] == 0, f"{key}={window[key]} in the final window")


def check_gateway_results(results: list, min_count: int, what: str) -> None:
    """With the device dead the gateway still answers 200 — from a held
    lease (``stale``) or a constant action (``fallback``, generation -1).
    A served answer is one that is neither."""
    check(
        len(results) >= min_count,
        f"only {len(results)} {what} responses (need {min_count})",
    )
    for r in results:
        check(not r.fallback, f"{what}: fallback answer served: {r.raw}")
        check(not r.stale, f"{what}: stale answer served: {r.raw}")
        check(r.generation >= 0, f"{what}: generation {r.generation}: {r.raw}")


def check_step_program(hlo: str, n_dev: int, expect_mosaic: bool):
    """Read the compiled program, not ``config.fused_scan``: the fused
    kernel is in the step iff the step calls into Mosaic, and a step over
    several devices must all-reduce the gradients. Returns the two
    counts."""
    mosaic_calls = hlo.count(MOSAIC_CALL)
    all_reduces = hlo.count("all-reduce")
    if expect_mosaic:
        check(mosaic_calls > 0, "compiled step has no Mosaic custom call")
    if n_dev > 1:
        check(all_reduces > 0, f"no all-reduce in a {n_dev}-device step")
    return mosaic_calls, all_reduces


def _param_delta(a, b) -> float:
    import jax
    import jax.numpy as jnp

    return float(
        sum(
            jnp.sum(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
        )
    )


def _timings(stamps: list[float], t0: float, updates_per_window: int) -> dict:
    """Smoke timings from the window-close stamps: seconds to the first
    completed window (compile included) and steady seconds per update."""
    out = {"first_window_s": round(stamps[0] - t0, 2),
           "updates_per_window": updates_per_window}
    if len(stamps) > 1:
        out["steady_s_per_update"] = round(
            (stamps[-1] - stamps[0])
            / ((len(stamps) - 1) * updates_per_window),
            4,
        )
    return out


# ------------------------------------------------------------------ leg A


def leg_anakin(config, windows: int = 4, expect_mosaic: bool = True) -> dict:
    """Anakin trainer: ``windows`` train() windows of one fused call each."""
    import jax
    import numpy as np

    from asyncrl_tpu import make_agent

    config = config.replace(log_every=1)
    agent = make_agent(config)
    try:
        params0 = jax.tree.map(lambda x: x.copy(), agent.state.params)
        step0 = int(agent.state.update_step)
        stamps: list[float] = []
        t0 = time.perf_counter()
        history = agent.train(
            total_env_steps=agent.env_steps
            + windows * config.updates_per_call * config.batch_steps_per_update,
            callback=lambda w: stamps.append(time.perf_counter()),
        )
        check(len(history) == windows, f"{len(history)} windows, not {windows}")
        check_finite_losses(history)
        delta = _param_delta(agent.state.params, params0)
        check(delta > 0 and delta == delta, f"params did not move ({delta})")
        dispatched = windows * config.updates_per_call
        executed = int(agent.state.update_step) - step0
        check(
            executed == dispatched,
            f"device executed {executed} updates, dispatched {dispatched}",
        )
        n_dev = agent.mesh.devices.size
        mosaic_calls, all_reduces = check_step_program(
            agent.learner._step.lower(agent.state).compile().as_text(),
            n_dev, expect_mosaic,
        )
        obs = agent.state.actor.obs
        check(
            len(obs.sharding.device_set) == n_dev,
            f"env batch on {len(obs.sharding.device_set)} of {n_dev} devices",
        )
        # Un-reduced gradients do not crash: each device just trains its
        # own copy. The replicas of a replicated param must be one value.
        replicas = [
            np.asarray(shard.data)
            for shard in jax.tree.leaves(agent.state.params)[0].addressable_shards
        ]
        check(
            all(np.array_equal(replicas[0], r) for r in replicas[1:]),
            "param replicas differ across devices",
        )
        return {
            "preset_env": config.env_id,
            "fused_scan": agent.learner.config.fused_scan,
            "mosaic_calls": mosaic_calls,
            "all_reduces": all_reduces,
            "env_batch_shard": list(obs.addressable_shards[0].data.shape),
            "env_batch_devices": n_dev,
            "updates": executed,
            "last_loss": round(history[-1]["loss"], 5),
            "param_delta": round(delta, 4),
            **_timings(stamps, t0, config.updates_per_call),
        }
    finally:
        agent.close()


# ------------------------------------------------------------------ leg B


def _gateway_load(agent, quota, stop: threading.Event, out: dict) -> None:
    """Closed-loop wire client: /v1/act as tenant ``gold`` (degrades to
    ``stale``), every tenth call /v1/evaluate as an unnamed tenant (the
    ``*`` class, degrades to ``fallback``). No retries — every attempt's
    answer is recorded."""
    import numpy as np

    from asyncrl_tpu.serve.client import GatewayClient

    while agent._gateway_port is None and not stop.is_set():
        time.sleep(0.005)
    url = f"http://127.0.0.1:{agent._gateway_port}"
    gold = GatewayClient(url, tenant="gold", deadline_ms=30_000, retries=0)
    anon = GatewayClient(url, deadline_ms=30_000, retries=0)
    obs = np.zeros((2, *agent.spec.obs_shape), np.float32)
    n_act, n_eval = quota
    i = 0
    while not stop.is_set():
        evaluate = i % 10 == 9
        try:
            if evaluate:
                out["evaluate"].append(anon.evaluate(obs))
            else:
                out["act"].append(gold.act(obs))
        # Any failure is the finding; the leg fails on a non-empty list.
        except Exception as e:  # noqa: BLE001
            out["errors"].append(f"{type(e).__name__}: {e}")
        i += 1
        if len(out["act"]) >= n_act and len(out["evaluate"]) >= n_eval:
            out["quota_met"].set()


def leg_serve(
    config, updates: int = 120, quota: tuple[int, int] = (36, 4),
    expect_device_queue: str = "on",
) -> dict:
    """Sebulba host path + serve core + gateway: train ``updates`` learner
    updates while a wire client sends at least ``quota`` (act, evaluate)
    requests to the ephemeral port."""
    import jax
    import numpy as np

    from asyncrl_tpu import make_agent

    config = config.replace(log_every=1)
    agent = make_agent(config)
    try:
        check(
            agent.config.device_queue == expect_device_queue,
            f"device_queue resolved to {agent.config.device_queue!r}, "
            f"expected {expect_device_queue!r}",
        )
        frame_steps = agent._envs_per_actor * config.unroll_len
        target = agent.env_steps + updates * frame_steps
        out = {"act": [], "evaluate": [], "errors": [],
               "quota_met": threading.Event()}
        stop = threading.Event()
        load = threading.Thread(
            target=_gateway_load, args=(agent, quota, stop, out),
            name="smoke-loadgen", daemon=True,
        )
        stamps: list[float] = []

        def on_window(w):
            stamps.append(time.perf_counter())
            if w["env_steps"] >= target - frame_steps and not stop.is_set():
                # Quiesce the client BEFORE train() tears the gateway
                # down (a request cut off by teardown would read as a
                # failure) — after giving it a bounded chance to finish
                # its quota with the pipeline still up.
                out["quota_met"].wait(timeout=120)
                stop.set()
                load.join(timeout=60)

        t0 = time.perf_counter()
        load.start()
        try:
            history = agent.train(total_env_steps=target, callback=on_window)
        finally:
            stop.set()
            load.join(timeout=60)
        check(not load.is_alive(), "load generator did not stop")
        check_finite_losses(history)
        check(not out["errors"], f"gateway request failures: {out['errors'][:3]}")
        check_gateway_results(out["act"], quota[0], "/v1/act")
        check_gateway_results(out["evaluate"], quota[1], "/v1/evaluate")
        generations = {r.generation for r in out["act"] + out["evaluate"]}
        check(
            len(generations) > 1,
            f"no weight generation swap seen over the wire: {generations}",
        )
        last = history[-1]
        check_restarts(last)
        check(
            last.get("gateway_fallback_served", -1) == 0,
            f"gateway_fallback_served={last.get('gateway_fallback_served')}",
        )
        check(agent._errors.empty(), "an actor thread reported an error")
        devq = agent._device_queue
        if expect_device_queue == "on":
            check(
                devq is not None and devq.enqueued >= agent._updates > 0,
                "the device queue handed no fragment to an update",
            )
        # The learner's own transfer (what the drain calls per fragment)
        # must spread a host fragment over every device of the mesh.
        from asyncrl_tpu.rollout import staging

        frag = agent.learner.put_rollout(jax.tree.map(
            lambda s: np.zeros(s.shape, s.dtype),
            staging.fragment_template(
                agent.config, agent.spec, agent.model, agent._envs_per_actor
            ),
        ))
        n_dev = agent.mesh.devices.size
        check(
            len(frag.obs.sharding.device_set) == n_dev,
            f"fragment on {len(frag.obs.sharding.device_set)} of {n_dev} "
            "devices",
        )
        # Where the host path's inference runs (a plain jit: it follows
        # its params' placement). Reported, not asserted.
        actions, _, _ = agent._inference_fn(
            agent._published(agent.state),
            np.zeros((2, *agent.spec.obs_shape), agent.spec.obs_dtype),
            jax.random.PRNGKey(0),
        )
        return {
            "fused_scan": agent.learner.config.fused_scan,
            "device_queue": agent.config.device_queue,
            "devq_enqueued": devq.enqueued if devq is not None else 0,
            "updates": agent._updates,
            "act_200": len(out["act"]),
            "evaluate_200": len(out["evaluate"]),
            "generations_seen": len(generations),
            "gateway_requests": last.get("gateway_requests"),
            "fragment_obs_shard": list(
                frag.obs.addressable_shards[0].data.shape),
            "fragment_devices": n_dev,
            "inference_devices": len(actions.sharding.device_set),
            "last_loss": round(last["loss"], 5),
            **_timings(stamps, t0, 1),
        }
    finally:
        agent.close()


# ------------------------------------------------------------------ leg C


def leg_native(config, updates: int = 6) -> dict:
    """Native C++ pool + multipass PPO through RolloutLearner."""
    from asyncrl_tpu import make_agent
    from asyncrl_tpu.envs import native_pool

    config = config.replace(log_every=1)
    t_build = time.perf_counter()
    native_pool.load_library()  # builds here if this machine has no artifact
    build_s = time.perf_counter() - t_build
    agent = make_agent(config)
    try:
        frame_steps = agent._envs_per_actor * config.unroll_len
        stamps: list[float] = []
        t0 = time.perf_counter()
        history = agent.train(
            total_env_steps=agent.env_steps + updates * frame_steps,
            callback=lambda w: stamps.append(time.perf_counter()),
        )
        check(
            agent._updates >= updates,
            f"{agent._updates} learner updates, wanted {updates}",
        )
        check_finite_losses(history)
        check_restarts(history[-1], ("actor_restarts", "server_restarts"))
        check(agent._errors.empty(), "an actor thread reported an error")
        return {
            "fused_scan": agent.learner.config.fused_scan,
            "native_lib": os.path.basename(native_pool._lib_path()),
            "native_build_s": round(build_s, 2),
            "updates": agent._updates,
            "last_loss": round(history[-1]["loss"], 5),
            **_timings(stamps, t0, 1),
        }
    finally:
        agent.close()


# -------------------------------------------------------------------- main


def _versions() -> dict:
    from importlib import metadata

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = "not installed"
    return out


def main() -> int:
    try:
        import jax

        from asyncrl_tpu.configs import presets
        from asyncrl_tpu.utils import runtime
    except ImportError as e:
        print(f"chip_smoke: cannot import the program: {e}", file=sys.stderr)
        return 2
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU: {e}", file=sys.stderr)
        return 3
    dev = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if dev["platform"] != "tpu":
        print(
            f"chip_smoke: no TPU (jax reports platform={dev['platform']!r}); "
            "refusing — this check only means something on the chip",
            file=sys.stderr,
        )
        return 3

    cache_dir = runtime.enable_compile_cache()
    entries0 = runtime.cache_entries(cache_dir)
    print(json.dumps({"device": dev, "versions": _versions(),
                      "compile_cache": cache_dir,
                      "cache_entries_before": entries0}), flush=True)

    # Leg C must build its library on THIS machine: the tree may have been
    # copied here with another machine's native/build/ inside it.
    shutil.rmtree(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "native", "build"),
        ignore_errors=True,
    )
    legs = (
        ("A anakin atari_impala", lambda: leg_anakin(
            presets.get("atari_impala").replace(
                num_envs=256, updates_per_call=8))),
        ("B sebulba+gateway pong_serve", lambda: leg_serve(
            presets.get("pong_serve"))),
        ("C native pool pendulum_native_ppo", lambda: leg_native(
            presets.get("pendulum_native_ppo"))),
    )
    failed = []
    for name, run in legs:
        t0 = time.perf_counter()
        try:
            facts = run()
        # A leg that dies for ANY reason is a failed leg; the next legs
        # still run so one call reports every failure.
        except Exception as e:  # noqa: BLE001
            import traceback

            traceback.print_exc()
            failed.append(name)
            print(json.dumps({"leg": name, "ok": False,
                              "error": f"{type(e).__name__}: {e}"[:2000]}),
                  flush=True)
            continue
        print(json.dumps({"leg": name, "ok": True,
                          "leg_s": round(time.perf_counter() - t0, 1),
                          "smoke_timings_not_a_speed_claim": True, **facts}),
              flush=True)

    entries1 = runtime.cache_entries(cache_dir)
    print(json.dumps({"compile_cache": cache_dir,
                      "cache_entries_before": entries0,
                      "cache_entries_after": entries1,
                      "cache_entries_added": entries1 - entries0}),
          flush=True)
    if failed:
        print(f"chip_smoke: FAILED legs: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
