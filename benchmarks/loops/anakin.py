"""Measuring loop for Anakin configurations: rollout and learner are one
device program (``Trainer.learner.update``), K updates fused in a call.

End to end: env frames consumed by completed updates between two syncs
that read the device-side update counter (``bench.py
timed_update_window``'s discipline, copied here: the yardstick may not
change when the program does). One call stays in flight while the host
waits for the one before it, as a training loop that drains metrics every
few calls does, so the chip never waits for the host's read.
"""

from __future__ import annotations

import math
import sys
import time

from benchmarks.loops import common


def first_fragment(agent, cfg):
    """The fragment the next update will train on: the program's own
    ``unroll`` from the same actor state under the same behaviour params
    (the step's ``rollout`` scope, replayed outside it)."""
    import jax

    from asyncrl_tpu.ops import distributions
    from asyncrl_tpu.rollout.anakin import unroll

    dist = distributions.for_config(cfg, agent.env.spec)

    @jax.jit
    def roll(params, actor):
        _, r, _ = unroll(
            agent.model.apply, params, agent.env, actor, cfg.unroll_len,
            dist=dist, reward_scale=cfg.reward_scale, step_cost=cfg.step_cost,
        )
        return {
            "obs": r.obs, "bootstrap_obs": r.bootstrap_obs,
            "actions": r.actions, "behaviour_logp": r.behaviour_logp,
            "rewards": r.rewards, "done": r.done,
        }

    return roll(agent.state.actor_params, agent.state.actor)


def run(*, cell, config_doc, traffic_doc, make_config, seed, seconds, trace,
        trace_seconds, out_dir, dev, t_process):
    import jax
    import numpy as np

    from asyncrl_tpu import make_agent

    cfg = make_config()
    if cfg.normalize_obs or cfg.normalize_returns or cfg.algo != "impala":
        raise SystemExit("benchmarks: the anakin loop's reference is IMPALA "
                         "without running normalisation")
    counters = common.Counters(dev)
    reasons: list[str] = []
    phases = common.Phases(t_process)
    phases.mark("imports")

    agent = make_agent(cfg)
    phases.mark("make_agent")
    try:
        K = cfg.updates_per_call
        frames_per_call = K * cfg.batch_steps_per_update
        n_dev = agent.mesh.devices.size
        update = agent.learner.update
        state = agent.state
        params0 = jax.tree.map(lambda x: x.copy(), state.params)
        step0 = int(state.update_step)

        # ---- set-up: the reference's view of the first update, then the
        # one warm-up call that compiles (or loads) the cell's program.
        fragment = jax.block_until_ready(first_fragment(agent, cfg))
        phases.mark("first_fragment")
        state, metrics = update(state)
        all_metrics = [metrics]
        loss_program = float(np.ravel(jax.device_get(metrics["loss"]))[0])
        phases.mark("warm_call")
        loss_reference = common.reference_impala_loss(
            cfg, config_doc, params0, fragment
        )
        tol = common.loss_tolerance(cfg)
        print(f"benchmarks: first update's loss {loss_program!r}, plain "
              f"float32 reference {loss_reference!r} (tolerance {tol})",
              file=sys.stderr)
        if not abs(loss_program - loss_reference) <= tol * max(
            1.0, abs(loss_reference)
        ):
            reasons.append(
                f"first update's loss {loss_program!r} vs plain float32 "
                f"reference {loss_reference!r} (tolerance {tol})"
            )
        del fragment
        phases.mark("reference_loss")
        phases.report()

        def sync(s) -> int:
            return int(s.update_step)  # D2H read: all queued work is done

        sync(state)
        profiler = common.Profiler(out_dir) if trace else None
        window_s = trace_seconds if trace else seconds
        calls = 1  # the warm-up

        # ---- the measured window (in a traced run: the traced seconds)
        if profiler:
            profiler.start()
        t_start = time.perf_counter()
        with common.annotate("bench.window", trace):
            behind = state
            while time.perf_counter() - t_start < window_s:
                with common.annotate("bench.update_call", trace):
                    state, metrics = update(state)
                all_metrics.append(metrics)
                calls += 1
                with common.annotate("bench.sync", trace):
                    sync(behind)  # the call before the one just dispatched
                behind = state
            with common.annotate("bench.sync", trace):
                executed = sync(state)
        t_end = time.perf_counter()
        if profiler:
            profiler.stop()
        timed_calls = calls - 1
        elapsed = t_end - t_start

        # ---- correct?
        if executed - step0 != calls * K:
            reasons.append(
                f"device executed {executed - step0} updates, "
                f"dispatched {calls * K}"
            )
        drained = jax.device_get(all_metrics)
        if not all(
            np.all(np.isfinite(m["loss"])) and np.all(np.isfinite(m["grad_norm"]))
            for m in drained
        ):
            reasons.append("a loss or gradient norm is not finite")
        delta = common.param_delta(state.params, params0)
        if not (delta > 0 and math.isfinite(delta)):
            reasons.append(f"params did not move (delta {delta})")
        counted = counters.read(t_start, t_end)
        in_window = counted["compiles_in_window"]
        if in_window:
            reasons.append(f"{in_window} compilation(s) inside the window")
        if n_dev > 1:
            reasons.extend(common.check_replicas(state, n_dev))

        fps = timed_calls * frames_per_call / elapsed
        spec = agent.env.spec
        evidence = {
            "trace": profiler.load() if profiler else None,
            "counters": {
                **counted,
                "loss_reference_gap": abs(loss_program - loss_reference),
            },
            "chips": n_dev,
            "window": (t_start, t_end),
            "geometry": {
                "num_envs": cfg.num_envs, "unroll_len": cfg.unroll_len,
                "updates_per_call": K, "rollout_on_device": True,
            },
            "model": {
                "torso": cfg.torso, "channels": list(cfg.channels),
                "hidden_sizes": list(cfg.hidden_sizes),
                "obs_shape": list(spec.obs_shape),
                "num_actions": spec.num_actions,
            },
        }
        if trace:
            evidence["traced_updates"] = timed_calls * K
        return {
            "correct": not reasons,
            "reasons": reasons,
            "attempted": timed_calls * K,
            "failed": 0,
            "end_to_end": {
                "env_frames_per_s": fps,
                "setup_s": t_start - t_process,
            },
            "evidence": evidence,
        }
    finally:
        agent.close()
