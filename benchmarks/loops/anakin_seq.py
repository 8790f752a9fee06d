"""Measuring loop for a token-level sequence policy on the Anakin path:
rollout (one token at a time through the carry) and learner (the model's
fragment form) are one device program, ``Trainer.learner.update``.

The timed window, the in-flight call, the sync discipline and the
``bench.*`` annotations are ``loops/anakin.py``'s, line for line: tokens
consumed by completed updates between two syncs that read the device-side
update counter, one call in flight while the host waits for the one before.
One difference is forced: this step runs donated (its state is 7.9 GB), so
the state of the call before is gone once the next is dispatched, and the
host waits on that call's loss instead (a result, never donated); the
window still closes on the update counter of the last state.

The set-up is this loop's own, and what decides ``correct`` in it is what
the first call of the timed program, the warm-up ``update``, gives back:

- the carry it leaves (the KDA states of its own rollout) against the plain
  reference's states after the same tokens;
- its ``metrics`` (value loss, entropy, the learner's mean log-prob through
  ``kl``, episode boundaries; the policy-gradient term and the loss too
  where the configuration says ``f32``) against the reference's;
- the step it takes on the leaves after the last layer (final norm, head,
  value head) against the step the optimizer's rule makes of the
  reference's gradient of those leaves, which needs no backward pass
  through the layers; and that every leaf a gradient reaches moved.

The model holds 2.4 GB of float32 parameters and the update donates them,
so the reference reads them BEFORE that call, and nothing here copies them
on the device (the leaves after the last layer, 189 MB, wait on the host).
The reference needs the fragment the update will train on before the
update makes it: the program's own ``unroll`` is replayed from the same
actor state under the same behaviour params. That the update then trained
on that very fragment is not assumed: the KDA states its rollout leaves
have to be the replay's to the last bit, and its count of episode
boundaries the replay's, or nothing is compared and the run is not correct.
``behaviour_logp`` is read from the replay on that condition.
"""

from __future__ import annotations

import sys
import time

from benchmarks.loops import common

# Limits of the comparisons that decide ``correct``. Under
# ``precision="f32"`` every one of them is rounding (F32_TOL and the two
# beside it). Under bfloat16 products (the cell) each lies between two
# readings at the cell's own widths on the chip, in PERF.md's table (PR 26):
# the largest the program gives over its seeds, and what a reference gives
# that is wrong (every KDA decay x 1.1; one held expert of eight left out)
# or computed in bfloat16 throughout.
#
# KDA_STATE_TOL: |S - S_reference| / |S_reference| of the states the
#   update's rollout leaves in the carry, by KDA layer from the first. The
#   state is what the configuration keeps in float32 through hundreds of
#   tokens. The first layer reads exact inputs (embedding rows) and the
#   second one layer's rounding, so their gaps are steady to 2% and their
#   limits are the ones a state or decay kept in bfloat16 fails; deeper
#   layers inherit more rounding and theirs refuse a wrong layer below.
# LOGP_MEAN_TOL, LOGP_RMS_TOL: mean and root mean square over [T, B] of
#   |behaviour_logp - the reference's log-prob of the same action|, nats:
#   the one-token form through the carry.
# KL_TOL: |kl - kl_reference| of the update's metrics, the learner's SIGNED
#   mean log-prob gap: its noise is 1e-4, so it refuses a gross fault only.
# VALUE_LOSS_TOL, ENTROPY_TOL: |the update's metric - the reference's| /
#   max(1e-6, |the reference's|). A fresh policy is near uniform: the
#   entropy moves with nothing but the head's own arithmetic.
# HEAD_GRAD_TOL, TAIL_GRAD_TOL: | |g| - |g_reference| | / |g_reference| of
#   the head, and of the final norm and the value head: the clipped
#   gradient's magnitude as the optimizer's second moment keeps it after
#   the first update, against the reference's gradient of those leaves
#   clipped by the update's own norm. The head's is linear in the
#   advantages and reads 0.052-0.076 over nine seeds (their V-trace ratios,
#   below), 0.16-0.17 under either wrong reference; the small leaves' swing
#   with the seed, and their limit lies between the reading and 1.
# TAIL_STEP_TOL: |step - reference step| / |reference step| over the same
#   leaves: direction and rate. 1 is what leaves left unchanged, or a step
#   twice as long, read. A step is a few float32 ulps of the parameter it
#   moves (rate 1e-4), so the stored parameter rounds it: the reference's
#   step is rounded the same way, and what is left is the pairs that the
#   two gradients' difference sends to different neighbours.
# The loss and its policy-gradient term decide under ``f32`` only: under
#   bfloat16 products V-trace's clipped ratios min(1, rho), each within
#   0.02 of 1 and never above, compound over the ~100 tokens the discount
#   reaches. The run prints how far the reference's own loss moves when
#   its ratios are set to exactly 1 (0.1-0.75), beside the gap (0.004-0.1).
F32_TOL = 1e-4
KDA_STATE_TOL = (0.005, 0.0075, 0.02, 0.035)
LOGP_MEAN_TOL, LOGP_RMS_TOL, KL_TOL = 0.015, 0.03, 2e-3
VALUE_LOSS_TOL, ENTROPY_TOL = 0.04, 1e-4
HEAD_GRAD_TOL, TAIL_GRAD_TOL, TAIL_GRAD_TOL_F32 = 0.13, 0.3, 1e-3
TAIL_STEP_TOL, TAIL_STEP_TOL_F32 = 0.5, 1e-2

TAIL = ("final_norm", "head", "value")  # the leaves after the last layer
FROZEN = "router_bias"  # a buffer: no gradient reaches it


def first_fragment(agent, cfg):
    """The fragment the next update will train on, replayed: the program's
    own ``unroll`` from the same actor state under the same behaviour
    params (the step's ``rollout`` scope, outside it), as the ``Rollout``
    the learner reads (``init_core`` included), and the KDA states the
    replay leaves in the carry."""
    import jax

    from asyncrl_tpu.ops import distributions
    from asyncrl_tpu.rollout.anakin import unroll

    dist = distributions.for_config(cfg, agent.env.spec)

    @jax.jit
    def roll(params, actor):
        actor, r, _ = unroll(
            agent.model.apply, params, agent.env, actor, cfg.unroll_len,
            dist=dist, reward_scale=cfg.reward_scale, step_cost=cfg.step_cost,
        )
        return r, kda_states(actor.core)

    return roll(agent.state.actor_params, agent.state.actor)


def kda_states(core) -> list:
    return [layer["S"] for layer in core.layers if "S" in layer]


def reference_view(r) -> dict:
    """The replayed ``Rollout`` as the plain reference reads it."""
    return {
        "obs": r.obs, "bootstrap_obs": r.bootstrap_obs,
        "actions": r.actions, "behaviour_logp": r.behaviour_logp,
        "rewards": r.rewards, "done": r.done,
        "init_core": [dict(layer) for layer in r.init_core.layers],
    }


def reference_program(cfg, dims, env_block: int, how: dict):
    """``(params, replayed fragment) -> (scalars, log-prob [T, B], KDA
    states, gradient of the leaves after the last layer)``: the plain
    reference's view of the update that trains on that fragment."""
    import jax.numpy as jnp

    from benchmarks.reference import kimi_linear as reference

    def reference_view_of(p, r):
        view = reference_view(r)
        loss, ref = reference.impala_loss(
            p, dims, view, cfg.gamma, cfg.value_coef, cfg.entropy_coef,
            cfg.vtrace_rho_clip, cfg.vtrace_c_clip, env_block=env_block,
            **how,
        )
        tail = reference.tail_gradient(
            p, dims, view, ref, cfg.value_coef, cfg.entropy_coef,
            env_block=env_block, **how,
        )
        # the same loss where rollout and learner agree to the last bit
        # (every importance ratio 1): how far the ratios alone move it
        on_policy = reference.loss_of(
            {**view, "behaviour_logp": ref["logp"]}, ref, cfg.gamma,
            cfg.value_coef, cfg.entropy_coef, cfg.vtrace_rho_clip,
            cfg.vtrace_c_clip,
        )
        scalars = {
            "loss": loss, "loss_on_policy": on_policy,
            "kl": jnp.mean(r.behaviour_logp - ref["logp"]),
            **{k: ref[k] for k in ("pg_loss", "value_loss", "entropy")},
        }
        return scalars, ref["logp"], ref["kda_states"], tail

    return reference_view_of


def _by_leaf(fn, *trees) -> dict:
    """{leaf's path: ``fn`` of that leaf of each tree}, computed on the
    device, read as float64."""
    import jax
    import numpy as np

    paths, _ = jax.tree_util.tree_flatten_with_path(trees[0])
    out = jax.jit(lambda *ts: [
        fn(*leaves) for leaves in zip(*(jax.tree.leaves(t) for t in ts))
    ])(*trees)
    return dict(zip(
        (jax.tree_util.keystr(path) for path, _ in paths),
        np.asarray(jax.device_get(out), np.float64),
    ))


def leaf_hashes(params) -> dict:
    """{leaf's path: (two hashes of its entries' bits, whether all are
    finite)}. A step of one ulp in one entry of 47 million changes both
    hashes; it changes no float32 sum of the leaf."""
    import jax
    import jax.numpy as jnp

    def hashes(x):
        bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32).ravel()
        odd = 2 * jnp.arange(bits.size, dtype=jnp.uint32) + 1
        return jnp.stack([
            jnp.sum(bits), jnp.sum(bits * odd),  # modulo 2**32
            jnp.all(jnp.isfinite(x)).astype(jnp.uint32),
        ])

    return _by_leaf(hashes, params)


def steps_float32_takes(cfg, params, nu) -> dict:
    """{leaf's path: (the second moment's sum, how many entries the
    optimizer's rule certainly moved)}: a step of the rate's few 1e-4 of the
    clipped gradient can be less than float32 adds to the entry it belongs
    to (a decay bias of -5 with a gradient of 1e-6), so a leaf has to have
    moved only where half its step, either way, still changes an entry."""
    import jax.numpy as jnp

    def count(p, n):
        half = 0.5 * cfg.learning_rate * jnp.sqrt(
            n / (1.0 - cfg.rmsprop_decay)
        ) * jnp.reciprocal(jnp.sqrt(n + cfg.rmsprop_eps))
        return jnp.stack([
            jnp.sum(n), jnp.sum((p + half != p) & (p - half != p))
        ]).astype(jnp.float32)

    return _by_leaf(count, params, nu)


def rel(a, b) -> float:
    """|a - b| / |b| over whole arrays (a list of them: over all)."""
    import numpy as np

    a, b = ([x] if isinstance(x, np.ndarray) else list(x) for x in (a, b))
    gap = sum(float(np.sum(np.square(x - y, dtype=np.float64))) for x, y in zip(a, b))
    return (gap / sum(float(np.sum(np.square(y, dtype=np.float64))) for y in b)) ** 0.5


def reference_update(cfg, params, grads, grad_norm: float):
    """What the configured optimizer makes of ``grads`` from a fresh state,
    written out (clip by the global norm; RMSProp with epsilon inside the
    root and a second moment that starts at zero): (the clipped gradient's
    magnitude, the step as the float32 ``params`` take it)."""
    import jax
    import numpy as np

    clip = min(1.0, cfg.max_grad_norm / grad_norm)

    def step(p, g):
        g = np.asarray(g, np.float64) * clip
        step = -cfg.learning_rate * g / np.sqrt(
            (1.0 - cfg.rmsprop_decay) * g * g + cfg.rmsprop_eps
        )
        return (p + step.astype(np.float32)).astype(np.float64) - p

    return (jax.tree.map(lambda g: np.abs(np.asarray(g, np.float64)) * clip, grads),
            jax.tree.map(step, params, grads))


def second_moment(opt_state):
    """RMSProp's second moment in the chain's state: after the first update
    from zero it is (1 - decay) x the clipped gradient squared, kept at
    float32's relative precision however small the step."""
    import jax

    has = lambda x: hasattr(x, "nu")
    (rms,) = [x for x in jax.tree.leaves(opt_state, is_leaf=has) if has(x)]
    return rms.nu


def check_files_agree(cfg, config_doc) -> None:
    """The configuration's ``model`` record is the shape the program
    builds."""
    import dataclasses
    import json

    from asyncrl_tpu.models.kimi_linear import SHAPES

    built = json.loads(json.dumps(dataclasses.asdict(SHAPES[cfg.seq_model])))
    if built != config_doc["model"]:
        raise SystemExit(
            f"benchmarks: configs/{config_doc.get('name')}.json's model record "
            f"is not SHAPES[{cfg.seq_model!r}]"
        )


def run(*, cell, config_doc, traffic_doc, make_config, seed, seconds, trace,
        trace_seconds, out_dir, dev, t_process):
    import jax
    import numpy as np

    from asyncrl_tpu import make_agent
    from benchmarks import seq_counts

    cfg = make_config()
    if (cfg.normalize_obs or cfg.normalize_returns or cfg.algo != "impala"
            or not cfg.seq_model or cfg.optimizer != "rmsprop"
            or cfg.lr_schedule != "constant" or cfg.entropy_anneal_steps
            or not cfg.introspect):
        raise SystemExit("benchmarks: the anakin_seq loop's reference is a "
                         "sequence policy under IMPALA without normalisation, "
                         "stepped by RMSProp at a constant rate, with the "
                         "update's diagnostics on")
    counters = common.Counters(dev)
    reasons: list[str] = []
    phases = common.Phases(t_process)
    phases.mark("imports")

    agent = make_agent(cfg)
    phases.mark("make_agent")
    try:
        check_files_agree(cfg, config_doc)
        dims = config_doc["model"]
        how = config_doc.get("reference_how", {})
        env_block = int(config_doc.get("reference_env_block", 8))
        K = cfg.updates_per_call
        frames_per_call = K * cfg.batch_steps_per_update
        n_dev = agent.mesh.devices.size
        update = agent.learner.update
        state = agent.state
        step0 = int(state.update_step)
        sums0 = leaf_hashes(state.params)
        tail0 = jax.device_get({k: state.params["params"][k] for k in TAIL})

        # ---- set-up: the reference's view of the first update from the
        # live state, then the one warm-up call that compiles (or loads)
        # the cell's program, donates that state, and is held to that view.
        fragment, kda_replay = first_fragment(agent, cfg)
        kda_replay = jax.device_get(kda_replay)
        boundaries = int(np.sum(np.asarray(fragment.done)))
        behaviour_logp = np.asarray(fragment.behaviour_logp, np.float64)
        phases.mark("first_fragment")

        ref, logp_reference, kda_reference, tail_grad = jax.device_get(
            jax.jit(reference_program(cfg, dims, env_block, how))(
                state.params, fragment
            )
        )
        del fragment
        ref = {k: float(v) for k, v in ref.items()}
        gap = np.abs(behaviour_logp - logp_reference)
        logp_gap = {"mean": float(gap.mean()), "rms": float(np.sqrt(np.mean(gap ** 2))),
                    "max": float(gap.max())}
        phases.mark("reference_loss")

        state, metrics = update(state)
        all_metrics = [metrics]
        got = {k: float(np.ravel(v)[0]) for k, v in jax.device_get(metrics).items()}
        kda_update = jax.device_get(kda_states(state.actor.core))
        replay_gap = rel(kda_update, kda_replay)
        state_gaps = [rel(a, b) for a, b in zip(kda_update, kda_reference)]
        del kda_update, kda_replay, kda_reference
        nu = second_moment(state.opt_state)
        sums1 = leaf_hashes(state.params)
        taken = steps_float32_takes(cfg, state.params, nu)
        tail1, nu = jax.device_get((
            {k: state.params["params"][k] for k in TAIL},
            {k: nu["params"][k] for k in TAIL},
        ))
        step_taken = jax.tree.map(
            lambda new, old: new.astype(np.float64) - old, tail1, tail0
        )
        grad_taken = jax.tree.map(
            lambda n: np.sqrt(n.astype(np.float64) / (1.0 - cfg.rmsprop_decay)), nu
        )
        grad_reference, step_reference = reference_update(
            cfg, tail0, tail_grad, got["grad_norm"]
        )
        by_group = lambda a, b: {
            k: rel(jax.tree.leaves(a[k]), jax.tree.leaves(b[k])) for k in TAIL
        }
        grad_gaps = by_group(grad_taken, grad_reference)
        step_gaps = by_group(step_taken, step_reference)
        del tail0, tail1, nu, tail_grad, step_taken, step_reference
        del grad_taken, grad_reference
        phases.mark("warm_call")

        f32 = cfg.precision == "f32"
        if not f32 and len(state_gaps) > len(KDA_STATE_TOL):
            raise SystemExit("benchmarks: the anakin_seq loop has limits for "
                             f"{len(KDA_STATE_TOL)} KDA layers under bfloat16 products")
        resets = got["episode_resets"] * n_dev  # the metric is a mean over chips
        relative = lambda k: abs(got[k] - ref[k]) / max(1e-6, abs(ref[k]))
        loss_gap = abs(got["loss"] - ref["loss"]) / max(1.0, abs(ref["loss"]))
        pg_gap = abs(got["pg_loss"] - ref["pg_loss"]) / max(1.0, abs(ref["pg_loss"]))
        print(f"benchmarks: the first update against the plain float32 "
              f"reference on the fragment it trained on. KDA states its "
              f"rollout left, |update - replay| / |replay| {replay_gap!r}, "
              f"|update - reference| / |reference| by layer {state_gaps}; "
              f"episode boundaries {resets!r} (replay "
              f"{boundaries}); behaviour_logp against the reference's "
              f"log-prob of the same actions, nats: {logp_gap}; metrics "
              f"(update, reference): "
              f"{ {k: (got[k], ref[k]) for k in ('value_loss', 'entropy', 'kl', 'pg_loss', 'loss')} }"
              f"; loss gap {loss_gap!r} of max(1, |loss|), and the "
              f"reference's own loss with every importance ratio 1: "
              f"{ref['loss_on_policy']!r}; on the leaves after the last layer, "
              f"the clipped gradient's magnitude in the optimizer's second "
              f"moment against the reference's, |.| / |reference|: {grad_gaps}"
              f", and |step - reference step| / |reference step|: "
              f"{step_gaps} (gradient norm {got['grad_norm']!r})",
              file=sys.stderr)

        def hold(what, value, limit, limit_f32=F32_TOL):
            limit = limit_f32 if f32 else limit
            if not value <= limit:
                reasons.append(f"{what}: {value!r} (limit {limit})")

        if not (replay_gap <= 1e-6 and resets == boundaries):
            reasons.append(
                f"the first update did not train on the replayed fragment "
                f"(KDA states after its rollout {replay_gap!r} of their norm "
                f"from the replay's, {resets!r} episode "
                f"boundaries against {boundaries}): nothing of it can be "
                f"held against the reference"
            )
        else:
            for i, (gap, limit) in enumerate(zip(state_gaps, KDA_STATE_TOL)):
                hold(f"KDA layer {i}'s state after the update's rollout, of its "
                     f"norm from the reference's", gap, limit)
            hold("behaviour_logp vs the reference's log-prob of the same "
                 "actions, mean gap in nats", logp_gap["mean"], LOGP_MEAN_TOL)
            hold("behaviour_logp vs the reference's log-prob of the same "
                 "actions, rms gap in nats", logp_gap["rms"], LOGP_RMS_TOL)
            hold("the learner's mean log-prob vs the reference's (the update's "
                 "kl against the reference's), nats",
                 abs(got["kl"] - ref["kl"]), KL_TOL)
            hold("the update's value loss vs the reference's, relative",
                 relative("value_loss"), VALUE_LOSS_TOL)
            hold("the update's entropy vs the reference's, relative",
                 relative("entropy"), ENTROPY_TOL)
            for k in TAIL:
                hold(f"the gradient of {k!r} as the optimizer's second moment "
                     f"keeps it vs the reference's, clipped, relative",
                     grad_gaps[k], HEAD_GRAD_TOL if k == "head" else TAIL_GRAD_TOL,
                     TAIL_GRAD_TOL_F32)
                hold(f"the update's step on {k!r} vs the reference's gradient "
                     f"stepped by the optimizer's rule, relative",
                     step_gaps[k], TAIL_STEP_TOL, TAIL_STEP_TOL_F32)
            if f32:
                hold("the update's policy-gradient term vs the reference's, "
                     "of max(1, |term|)", pg_gap, None)
                hold("the update's loss vs the reference's, of max(1, |loss|)",
                     loss_gap, None)
        # every leaf but the buffers: a gradient reached it, and it moved
        # where its step is one float32 can take; the buffers stayed
        still = {k for k in sums0 if np.array_equal(sums1[k], sums0[k])}
        frozen = {k for k in sums0 if FROZEN in k}
        unreached = sorted(k for k in sums0 if not taken[k][0] > 0)
        stuck = sorted(k for k in still - frozen if taken[k][1] > 0)
        if sorted(frozen) != unreached or stuck or frozen - still:
            reasons.append(
                f"after the first update: no gradient reached {unreached} "
                f"(the buffers are {sorted(frozen)}); did not move although "
                f"their step is one float32 takes: {stuck}; buffers that "
                f"moved: {sorted(frozen - still)}"
            )
        phases.report()

        def sync(s) -> int:
            return int(s.update_step)  # D2H read: all queued work is done

        def wait(m) -> None:
            jax.device_get(m["loss"])  # D2H read: that call is done

        sync(state)
        profiler = common.Profiler(out_dir) if trace else None
        window_s = trace_seconds if trace else seconds
        calls = 1  # the warm-up

        # ---- the measured window (in a traced run: the traced seconds)
        if profiler:
            profiler.start()
        t_start = time.perf_counter()
        with common.annotate("bench.window", trace):
            behind = metrics
            while time.perf_counter() - t_start < window_s:
                with common.annotate("bench.update_call", trace):
                    state, metrics = update(state)
                all_metrics.append(metrics)
                calls += 1
                with common.annotate("bench.sync", trace):
                    wait(behind)  # the call before the one just dispatched
                behind = metrics
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
        if not all(h[2] for h in leaf_hashes(state.params).values()):
            reasons.append("params are not finite")
        counted = counters.read(t_start, t_end)
        in_window = counted["compiles_in_window"]
        if in_window:
            reasons.append(f"{in_window} compilation(s) inside the window")

        fps = timed_calls * frames_per_call / elapsed
        timed = drained[1:] or drained
        mean_of = lambda key: float(np.mean([np.mean(m[key]) for m in timed]))
        evidence = {
            "trace": profiler.load() if profiler else None,
            "counters": {
                **counted,
                "loss_reference_gap": abs(got["loss"] - ref["loss"]),
                "loss_on_policy_shift": abs(ref["loss_on_policy"] - ref["loss"]),
                "value_loss_reference_gap": relative("value_loss"),
                "logp_reference_gap_mean": logp_gap["mean"],
                "kda_state_reference_gap": max(state_gaps),
                "kda_state_reference_gap_first": state_gaps[0],
                "tail_grad_reference_gap": max(grad_gaps.values()),
                "tail_step_reference_gap": max(step_gaps.values()),
                "leaves_moved_by_first_update": len(sums0) - len(still),
                "moe_load_max_over_mean": float(np.mean([
                    np.mean(m["moe_load_max"]) / np.mean(m["moe_load_mean"])
                    for m in timed
                ])),
                "episode_resets_per_update": mean_of("episode_resets"),
            },
            "chips": n_dev,
            "window": (t_start, t_end),
            "geometry": {
                "num_envs": cfg.num_envs, "unroll_len": cfg.unroll_len,
                "updates_per_call": K, "rollout_on_device": True,
            },
            "seq": {
                "dims": dims,
                "attended": seq_counts.mean_attended_positions(
                    agent.env.min_len, agent.env.max_len
                ),
                "held_per_token": mean_of("moe_local_frac") * dims["top_k"],
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
