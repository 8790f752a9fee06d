"""Measuring loop of the ``moonlight_rl`` configuration on the Anakin path:
rollout (one token at a time through the carry: a latent cache a layer,
attended with the up-projection absorbed) and learner (the model's fragment
form: every cached and fragment row up-projected into keys and values) are
one device program, ``Trainer.learner.update``.

The timed window, the in-flight call, the sync discipline and the
``bench.*`` annotations are ``loops/anakin_seq.py``'s (the step runs donated,
so the host waits on the loss of the call before); the draw of episodes from
the mix's ``episode_seed`` and the warm-in are ``loops/anakin_keye.py``'s:
set-up advances every env by ``warm_in_fragments`` fragments of the
program's own ``unroll``, parameters untouched, so the window opens on
caches of thousands of rows, and keeps every step's token and flag, because
the reference has no cache and is given each env's whole history.

WHAT DECIDES ``correct``: what the first call of the timed program gives
back after the warm-in, against the plain reference
(``benchmarks/reference/moonlight.py``: no cache, the non-absorbed form, the
published rotation, handed the program's parameters with the rope columns
in the published order) on the fragment that update trained on, replayed
beforehand by the same ``unroll``:

- the carry after the warm-in and after the fragment, by layer: the normed
  latent and the rotated rope key of every row up to ``len`` (each apart,
  the larger gap held), and ``len``, against the reference's rebuilt from
  the history;
- ``behaviour_logp`` (the one-token form through the cache) and the
  update's metrics (value loss, entropy, ``kl``, episode boundaries,
  ``mla_rows_attended``, ``mla_rows_cached``, ``mla_rows_expanded``)
  against the reference's;
- the step taken on the leaves after the last layer and on the last layer's
  rope columns of ``q`` and ``kv_a`` (what the rotation's gradient passes
  through) against the optimizer's rule on the reference's gradient; a
  gradient reaching every leaf but the routers' buffers; every leaf moving
  whose step float32 can take.

That the update trained on the replayed fragment is not assumed: the carry
its rollout leaves has to be the replay's to the last bit, and its count of
episode boundaries the replay's, or nothing is compared and the run is not
correct.
"""

from __future__ import annotations

import sys
import time

from benchmarks.loops import common
from benchmarks.loops.anakin_keye import unroll_program
from benchmarks.loops.anakin_lfm2 import carry_of
from benchmarks.loops.anakin_seq import (
    F32_TOL,
    FROZEN,
    TAIL,
    leaf_hashes,
    reference_update,
    reference_view,
    rel,
    second_moment,
    steps_float32_takes,
)

# Limits of the comparisons that decide ``correct``. Under ``precision="f32"``
# every one of them is rounding (F32_TOL and those named *_F32). Under
# bfloat16 products (the cell) each lies between two readings at the cell's
# own widths on the chip, in PERF.md's table of them: the largest the program
# gives over its seeds, and what a control gives. The controls: a reference
# that is wrong (nothing rotated, Kimi-Linear's NoPE; the rotary base 10,000;
# one shared expert of 1,408 where there are two), held against the program;
# and the reference computed in bfloat16 throughout IN THE PROGRAM'S PLACE
# (``"stand_in": {"low": true}`` in a copy of the configuration file), held
# against the float32 reference by the same ``hold``s. Each control is not
# ``correct`` by one limit or more.
#
# ROWS_TOL: |rows - reference| / |reference| of a layer's cached rows up to
#   ``len``, the normed latent and the rotated rope key each apart (the
#   larger of the two), before and after the fragment, by layer from the
#   first. The cache holds bfloat16 (a row's rounding alone is 0.3% of it);
#   the first two layers' rows read the dense layer's rounding; from the
#   third on they inherit the expert layers' near-ties in the top 6 too. A
#   rope key rotated at another angle, or not at all, is off by its size.
# LOGP_MEAN_TOL, LOGP_RMS_TOL: mean and root mean square over [T, B] of
#   |behaviour_logp - the reference's log-prob of the same action|, nats.
# KL_TOL: |kl - kl_reference| of the update's metrics.
# VALUE_LOSS_TOL, ENTROPY_TOL: |the update's metric - the reference's| /
#   max(1e-6, |the reference's|).
# GRAD_TOL: | |g| - |g_reference| | / |g_reference| by group of leaves: the
#   clipped gradient's magnitude as the optimizer's second moment keeps it
#   after the first update, against the reference's gradient clipped by the
#   update's own norm. "rope": the last layer's rope columns of q and kv_a.
# STEP_TOL: |step - reference step| / |reference step| over the same
#   leaves: 1 is what leaves left unchanged read.
# The counters are counts of the traffic (exact but for float32 sums).
ROWS_TOL = (0.0045, 0.006, 0.03, 0.04, 0.05)
LOGP_MEAN_TOL, LOGP_RMS_TOL, KL_TOL = 0.018, 0.045, 1e-3
VALUE_LOSS_TOL, ENTROPY_TOL = 0.015, 3e-5
GRAD_TOL = {"head": 0.16, "final_norm": 0.1, "value": 0.11, "rope": 0.2}
GRAD_TOL_F32 = 1e-3
STEP_TOL, STEP_TOL_F32 = 0.5, 1e-2
COUNTERS = ("mla_rows_attended", "mla_rows_cached")

GROUPS = (*TAIL, "rope")


def rope_leaves(params, dims):
    """The last layer's rope columns of ``q`` and ``kv_a``, as the program
    orders them."""
    from benchmarks.reference import moonlight as reference

    last = params["params"][f"layer_{len(dims['layers']) - 1}"]["mla"]
    return reference.rope_columns(last, dims)


def carry_gaps(mine: list, theirs: list, dims: dict):
    """By layer, on the device: the largest ``|rows - theirs| / |theirs|`` of
    the latent and the rope key up to ``theirs``' ``len``, and the envs
    whose ``len`` differs."""
    from benchmarks.reference import moonlight as reference

    return reference.carry_gaps(*reference.carry_gap(mine, theirs, dims))


def reference_program(cfg, dims, env_block: int, how: dict, stand_in=None):
    """``(params, history tokens and flags [Th, B], the replayed fragment,
    the replay's carry) -> (scalars, log-prob [T, B], carry gaps before and
    after the fragment, gradients of the leaves after the last layer and of
    the last layer's rope columns (the program's order), None)``: the plain
    reference's view of the update that trains on that fragment.

    With ``stand_in`` (a control: ``reference_how``'s keys, e.g. ``{"low":
    true}``) the reference computed that way is put in the program's place:
    its carries and (the last result) what else the program would have given
    back are what is held against the reference's."""
    import jax.numpy as jnp

    from benchmarks.reference import moonlight as reference

    def view_of(p, view, how, **kw):
        loss, ref = reference.impala_loss(
            p, dims, view, cfg.gamma, cfg.value_coef, cfg.entropy_coef,
            cfg.vtrace_rho_clip, cfg.vtrace_c_clip, env_block=env_block,
            **how, **kw,
        )
        tail = reference.tail_gradient(
            p, dims, view, ref, cfg.value_coef, cfg.entropy_coef,
            env_block=env_block, **how,
        )
        return loss, ref, {**tail, "rope": reference.program_order(
            ref["rope_gradient"], dims)}

    def reference_view_of(params, history_obs, history_done, r, carry):
        p = reference.published(params, dims)
        view = {**reference_view(r), "history_obs": history_obs,
                "history_done": history_done}
        carries = {"before": view.pop("init_core"), "after": carry}
        other = None
        if stand_in is None:
            loss, ref, grads = view_of(p, view, how, carries=carries)
        else:
            _, theirs, their_grads = view_of(
                p, view, stand_in, carry_dtype=carry[0]["kv"].dtype)
            loss, ref, grads = view_of(
                p, view, how,
                carries={"before": theirs["core_before"], "after": theirs["core"]})
            other = {"logp": theirs["logp"], "grads": their_grads,
                     **{k: theirs[k] for k in ("value_loss", "entropy")}}
        on_policy = reference.loss_of(
            {**view, "behaviour_logp": ref["logp"]}, ref, cfg.gamma,
            cfg.value_coef, cfg.entropy_coef, cfg.vtrace_rho_clip,
            cfg.vtrace_c_clip,
        )
        scalars = {
            "loss": loss, "loss_on_policy": on_policy,
            "kl": jnp.mean(r.behaviour_logp - ref["logp"]),
            **{k: ref[k] for k in ("pg_loss", "value_loss", "entropy", *COUNTERS)},
        }
        return scalars, ref["logp"], ref["carry_gaps"], grads, other

    return reference_view_of


def check_files_agree(cfg, config_doc) -> None:
    """The configuration's ``model`` record is the shape the program builds,
    and its ``parameters`` what ``moonlight_counts`` counts of it."""
    import dataclasses
    import json

    from asyncrl_tpu.models.moonlight import SHAPES
    from benchmarks import moonlight_counts

    built = json.loads(json.dumps(dataclasses.asdict(SHAPES[cfg.seq_model])))
    if built != config_doc["model"]:
        raise SystemExit(
            f"benchmarks: configs/{config_doc.get('name')}.json's model record "
            f"is not SHAPES[{cfg.seq_model!r}]"
        )
    if moonlight_counts.parameters(built) != config_doc.get("parameters"):
        raise SystemExit(
            f"benchmarks: configs/{config_doc.get('name')}.json's parameters "
            f"are not moonlight_counts.parameters of its model record"
        )


def run(*, cell, config_doc, traffic_doc, make_config, seed, seconds, trace,
        trace_seconds, out_dir, dev, t_process):
    import jax
    import numpy as np

    from asyncrl_tpu import make_agent

    cfg = make_config()
    if (cfg.normalize_obs or cfg.normalize_returns or cfg.algo != "impala"
            or not cfg.seq_model or cfg.optimizer != "rmsprop"
            or cfg.lr_schedule != "constant" or cfg.entropy_anneal_steps
            or not cfg.introspect):
        raise SystemExit("benchmarks: the anakin_moonlight loop's reference is "
                         "a sequence policy under IMPALA without normalisation, "
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
        stand_in = config_doc.get("stand_in")  # a control, never a cell's
        env_block = int(config_doc.get("reference_env_block", 1))
        warm_in = int(config_doc.get("warm_in_fragments", 16))
        K = cfg.updates_per_call
        frames_per_call = K * cfg.batch_steps_per_update
        n_dev = agent.mesh.devices.size
        update = agent.learner.update
        state = agent.state
        step0 = int(state.update_step)
        sums0 = leaf_hashes(state.params)
        leaves_of = lambda tree: {
            **{k: tree["params"][k] for k in TAIL}, "rope": rope_leaves(tree, dims)}
        held0 = jax.device_get(leaves_of(state.params))

        # ---- the traffic's draw (as loops/anakin_keye.py)
        episode_seed = traffic_doc.get("episode_seed")
        if episode_seed is not None:
            agent.state = state = state.replace(actor=common.with_episode_seed(
                state.actor, agent.env, n_dev, int(episode_seed)))

        # ---- set-up: the warm-in, on the actor state the update will start
        # from; every step's token and flag is kept for the reference
        roll = unroll_program(agent, cfg)
        placed = jax.tree.map(lambda a: a.sharding, state.actor)
        actor, history = state.actor, []
        for _ in range(warm_in):
            actor, r = roll(state.actor_params, actor)
            history.append(jax.device_get((r.obs, r.done)))
            del r
        # the agent holds the state the update will donate; the cold actor
        # state (its empty caches) has no owner left
        agent.state = state = state.replace(actor=jax.device_put(actor, placed))
        del actor
        phases.mark("warm_in")

        # ---- the fragment the first update will train on, replayed
        after, fragment = roll(state.actor_params, state.actor)
        carry_replay = carry_of(after.core)
        del after
        history.append(jax.device_get((fragment.obs, fragment.done)))
        history_obs, history_done = (
            np.concatenate([h[i] for h in history], axis=0) for i in (0, 1))
        boundaries = int(np.sum(history[-1][1]))
        behaviour_logp = np.asarray(fragment.behaviour_logp, np.float64)
        phases.mark("first_fragment")

        ref, logp_reference, reference_gaps, grads, other = jax.device_get(
            jax.jit(reference_program(cfg, dims, env_block, how, stand_in))(
                state.params, history_obs, history_done, fragment, carry_replay,
            )
        )
        del fragment
        # the replay's carry waits on the host, out of the update's way
        carry_replay = jax.device_get(carry_replay)
        ref = {k: float(v) for k, v in ref.items()}
        if other:  # the stand-in's log-prob of the actions in the rollout's place
            rollout_logp = behaviour_logp
            behaviour_logp = np.asarray(other["logp"], np.float64)
        gap = np.abs(behaviour_logp - logp_reference)
        logp_gap = {"mean": float(gap.mean()), "rms": float(np.sqrt(np.mean(gap ** 2))),
                    "max": float(gap.max())}
        phases.mark("reference_loss")

        state, metrics = update(state)
        all_metrics = [metrics]
        got = {k: float(np.ravel(v)[0]) for k, v in jax.device_get(metrics).items()}
        if other:
            got.update({k: float(other[k]) for k in ("value_loss", "entropy")})
            got["kl"] = float(np.mean(rollout_logp - behaviour_logp))
        # what the update's rollout left is held to the replay's, on the device
        replay_gaps = jax.device_get(jax.jit(lambda a, b: carry_gaps(a, b, dims))(
            carry_of(state.actor.core), carry_replay))
        del carry_replay
        replay_gap = float(replay_gaps["rows"].max())
        row_gaps = {k: [float(g) for g in reference_gaps[k]["rows"]]
                    for k in ("before", "after")}
        part_gaps = {f"{part}_{k}": [float(g) for g in reference_gaps[k][part]]
                     for part in ("c_kv", "k_pe") for k in ("before", "after")}
        len_differs = int(sum(reference_gaps[k]["len"].sum() for k in row_gaps)
                          + replay_gaps["len"].sum())
        nu = second_moment(state.opt_state)
        sums1 = leaf_hashes(state.params)
        taken = steps_float32_takes(cfg, state.params, nu)
        held1, nu = jax.device_get((leaves_of(state.params), leaves_of(nu)))
        step_taken = jax.tree.map(
            lambda new, old: new.astype(np.float64) - old, held1, held0
        )
        grad_taken = jax.tree.map(
            lambda n: np.sqrt(n.astype(np.float64) / (1.0 - cfg.rmsprop_decay)), nu
        )
        grad_reference, step_reference = reference_update(
            cfg, held0, grads, got["grad_norm"]
        )
        if other:  # what the optimizer's rule makes of the stand-in's gradient
            grad_taken, step_taken = reference_update(
                cfg, held0, other["grads"], got["grad_norm"]
            )
        by_group = lambda a, b: {
            k: rel(jax.tree.leaves(a[k]), jax.tree.leaves(b[k])) for k in GROUPS
        }
        grad_gaps = by_group(grad_taken, grad_reference)
        step_gaps = by_group(step_taken, step_reference)
        del held0, held1, nu, grads, step_taken, step_reference
        del grad_taken, grad_reference
        phases.mark("warm_call")

        f32 = cfg.precision == "f32"
        if not f32 and len(dims["layers"]) > len(ROWS_TOL):
            raise SystemExit("benchmarks: the anakin_moonlight loop has limits "
                             f"for {len(ROWS_TOL)} layers under bfloat16 products")
        resets = got["episode_resets"] * n_dev  # the metric is a mean over chips
        relative = lambda k: abs(got[k] - ref[k]) / max(1e-6, abs(ref[k]))
        loss_gap = abs(got["loss"] - ref["loss"]) / max(1.0, abs(ref["loss"]))
        pg_gap = abs(got["pg_loss"] - ref["pg_loss"]) / max(1.0, abs(ref["pg_loss"]))
        expanded = dims["max_positions"] + cfg.unroll_len
        if other:
            print(f"benchmarks: A CONTROL, not the program: the reference under "
                  f"{stand_in} stands in the program's place below (carries, "
                  f"behaviour_logp, value loss, entropy, kl, the gradients and "
                  f"the steps)", file=sys.stderr)
        print(f"benchmarks: the first update after a warm-in of {warm_in} "
              f"fragments against the plain float32 reference on the fragment "
              f"it trained on. The carry its rollout left, |update - replay| / "
              f"|replay| {replay_gap!r}; |replay - reference| / |reference| of "
              f"the cached rows up to len by layer (the larger of the latent's "
              f"and the rope key's), before the fragment {row_gaps['before']} "
              f"and after it {row_gaps['after']}, each apart {part_gaps}, envs "
              f"whose len differs {len_differs}; episode boundaries {resets!r} "
              f"(replay {boundaries}); behaviour_logp against the reference's "
              f"log-prob of the same actions, nats: {logp_gap}; metrics "
              f"(update, reference): "
              f"{ {k: (got[k], ref[k]) for k in ('value_loss', 'entropy', 'kl', 'pg_loss', 'loss', *COUNTERS)} }"
              f", mla_rows_expanded {got['mla_rows_expanded']!r} (the cache's "
              f"capacity + T: {expanded}); loss gap {loss_gap!r} of max(1, "
              f"|loss|), and the reference's own loss with every importance "
              f"ratio 1: {ref['loss_on_policy']!r}; on the leaves after the last "
              f"layer and the last layer's rope columns, the clipped gradient's "
              f"magnitude in the optimizer's second moment against the "
              f"reference's, |.| / |reference|: {grad_gaps}, and |step - "
              f"reference step| / |reference step|: {step_gaps} (gradient norm "
              f"{got['grad_norm']!r}); expert layers: local assignments "
              f"{got['moe_local_assignments']!r}, dense blocks "
              f"{got['moe_dense_blocks']!r}", file=sys.stderr)

        compared: dict[str, list] = {}  # short name -> [reading, limit]

        def hold(name, what, value, limit, limit_f32=F32_TOL):
            limit = limit_f32 if f32 else limit
            compared[name] = [value, limit]
            if not value <= limit:
                reasons.append(f"{what}: {value!r} (limit {limit})")

        compared.update({
            "replay_gap": [replay_gap, 1e-6],
            "boundaries_gap": [abs(resets - boundaries), 0],
            "len_differs": [len_differs, 0],
        })
        if not (replay_gap <= 1e-6 and resets == boundaries and not len_differs):
            reasons.append(
                f"the first update did not train on the replayed fragment, "
                f"or the cache's lengths are not the reference's (the carry "
                f"after its rollout {replay_gap!r} of its norm from the "
                f"replay's, {resets!r} episode boundaries against "
                f"{boundaries}, {len_differs} envs whose len differs): "
                f"nothing of it can be held against the reference"
            )
        else:
            for when, gaps in row_gaps.items():
                for i, (gap, limit) in enumerate(zip(gaps, ROWS_TOL)):
                    hold(f"rows_{when}_l{i}",
                         f"layer {i}'s cached rows (latent, rope key) {when} "
                         f"the fragment, of their norm from the reference's",
                         gap, limit)
            hold("logp_mean",
                 "behaviour_logp vs the reference's log-prob of the same "
                 "actions, mean gap in nats", logp_gap["mean"], LOGP_MEAN_TOL)
            hold("logp_rms",
                 "behaviour_logp vs the reference's log-prob of the same "
                 "actions, rms gap in nats", logp_gap["rms"], LOGP_RMS_TOL)
            hold("kl",
                 "the learner's mean log-prob vs the reference's (the update's "
                 "kl against the reference's), nats",
                 abs(got["kl"] - ref["kl"]), KL_TOL)
            hold("value_loss",
                 "the update's value loss vs the reference's, relative",
                 relative("value_loss"), VALUE_LOSS_TOL)
            hold("entropy", "the update's entropy vs the reference's, relative",
                 relative("entropy"), ENTROPY_TOL)
            for k in COUNTERS:
                hold(k, f"the update's {k} vs the reference's, relative",
                     relative(k), 1e-5, 1e-5)
            hold("mla_rows_expanded",
                 "the update's mla_rows_expanded vs the cache's capacity + T",
                 abs(got["mla_rows_expanded"] - expanded), 0, 0)
            for k in GROUPS:
                hold(f"grad_{k}",
                     f"the gradient of {k!r} as the optimizer's second moment "
                     f"keeps it vs the reference's, clipped, relative",
                     grad_gaps[k], GRAD_TOL[k], GRAD_TOL_F32)
                hold(f"step_{k}",
                     f"the update's step on {k!r} vs the reference's gradient "
                     f"stepped by the optimizer's rule, relative",
                     step_gaps[k], STEP_TOL, STEP_TOL_F32)
            if f32:
                hold("pg_loss",
                     "the update's policy-gradient term vs the reference's, "
                     "of max(1, |term|)", pg_gap, None)
                hold("loss",
                     "the update's loss vs the reference's, of max(1, |loss|)",
                     loss_gap, None)
        # every leaf: a gradient reached it but the routers' buffers, and it
        # moved where its step is one float32 can take
        still = {k for k in sums0 if np.array_equal(sums1[k], sums0[k])}
        unreached = sorted(k for k in sums0
                           if not taken[k][0] > 0 and FROZEN not in k)
        stuck = sorted(k for k in still if taken[k][1] > 0)
        compared.update({"leaves_unreached": [len(unreached), 0],
                         "leaves_stuck": [len(stuck), 0]})
        if unreached or stuck:
            reasons.append(
                f"after the first update: no gradient reached {unreached}; "
                f"did not move although their step is one float32 takes: {stuck}"
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
        by_update = lambda key, scale=1: [
            float(np.mean(m[key])) * scale for m in drained]
        print(f"benchmarks: by update, from the warm-up call: episode "
              f"boundaries {[round(x) for x in by_update('episode_resets', n_dev)]}"
              f" (the mix's draw: episode_seed {episode_seed}), mla_rows_attended "
              f"{by_update('mla_rows_attended')}, loss {by_update('loss')} (the "
              f"parameters': --seed {seed})", file=sys.stderr)
        counted = counters.read(t_start, t_end)
        in_window = counted["compiles_in_window"]
        if in_window:
            reasons.append(f"{in_window} compilation(s) inside the window")
        compared.update({
            "updates_not_executed": [abs(calls * K - (executed - step0)), 0],
            "compiles_in_window": [in_window, 0],
        })

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
                "rows_reference_gap": max(map(max, row_gaps.values())),
                "grad_reference_gap": max(grad_gaps.values()),
                "step_reference_gap": max(step_gaps.values()),
                "leaves_moved_by_first_update": len(sums0) - len(still),
                "moe_load_max_over_mean": float(np.mean([
                    np.mean(m["moe_load_max"]) / np.mean(m["moe_load_mean"])
                    for m in timed
                ])),
                "episode_resets_per_update": mean_of("episode_resets"),
                "moe_local_assignments": mean_of("moe_local_assignments"),
                **{k: mean_of(k) for k in (*COUNTERS, "mla_rows_expanded")},
            },
            "chips": n_dev,
            "window": (t_start, t_end),
            "geometry": {
                "num_envs": cfg.num_envs, "unroll_len": cfg.unroll_len,
                "updates_per_call": K, "rollout_on_device": True,
            },
            "moonlight": {
                "dims": dims,
                "attended": mean_of("mla_rows_attended"),
                "cached": mean_of("mla_rows_cached"),
                # assignments on held experts, a token and expert layer
                "held_per_token": mean_of("moe_local_frac") * dims["top_k"],
            },
        }
        if trace:
            evidence["traced_updates"] = timed_calls * K
        return {
            "correct": not reasons,
            "reasons": reasons,
            "compared": compared,
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
