"""Measuring loop of the ``granite_h_rl`` configuration on the Anakin path:
rollout (one token at a time through the carry: a state-space state and a
conv tail a Mamba layer, a K/V cache the attention layer) and learner (the
model's fragment form: the chunked scan) are one device program,
``Trainer.learner.update``.

The timed window, the in-flight call, the sync discipline and the
``bench.*`` annotations are ``loops/anakin_seq.py``'s (the step runs donated,
so the host waits on the loss of the call before); the draw of episodes from
the mix's ``episode_seed`` and the warm-in are ``loops/anakin_keye.py``'s:
set-up advances every env by ``warm_in_fragments`` fragments of the
program's own ``unroll`` (as many steps as the cache holds rows), parameters
untouched, and keeps every step's token and flag, because the reference has
no cache and is given each env's whole history.

WHAT DECIDES ``correct``: what the first call of the timed program gives
back after the warm-in, against the plain reference
(``benchmarks/reference/granite_h.py``: no cache, Mamba-2 by its one-token
recurrence, attention over the whole episode) on the fragment that update
trained on, replayed beforehand by the same ``unroll``:

- the carry after the warm-in and after the fragment, by layer: every Mamba
  layer's state and conv tail, and the attention layer's key and value rows
  up to ``len`` and ``len``, against the reference's rebuilt from the
  history;
- ``behaviour_logp`` (the one-token form through the carry) and the
  update's metrics (value loss, entropy, ``kl``, episode boundaries,
  ``gqa_rows_attended``, ``ssd_chunk_resets``) against the reference's;
- the gradient of the leaves after the last layer and of the last Mamba
  layer's ``A_log``, ``dt_bias`` and ``D`` (which only the recurrence's
  gradient reaches) as the optimizer's second moment keeps it, and the
  step taken on the value head, against the optimizer's rule on the
  reference's gradient; a gradient reaching every leaf; every leaf moving
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
    leaf_hashes,
    reference_update,
    reference_view,
    second_moment,
    steps_float32_takes,
)

# Limits of the comparisons that decide ``correct``. Under ``precision="f32"``
# every one of them is rounding (F32_TOL and those named *_F32). Under
# bfloat16 products (the cell) each lies between two readings at the cell's
# own widths on the chip, in PERF.md's table of them (§6): the largest the
# program gives over its seeds, and what the reference computed in
# bfloat16 throughout gives IN THE PROGRAM'S PLACE (``"stand_in": {"low":
# true}`` in a copy of the configuration file, held against the float32
# reference by the same ``hold``s). The other controls are references that
# are wrong, held against the program: the state rounded to bfloat16 after
# every token, delta without dt_bias, residual_multiplier 1, the conv's bias
# left out. Each control is not ``correct`` by several limits.
#
# A limit of None is not held under bfloat16 products: the bfloat16
# reference reads under three times the program's largest there, so no
# limit between the two would both pass every seed and refuse it (printed
# on stderr all the same; held at the *_F32 limit under ``precision="f32"``).
#
# STATE_TOL, CONV_TOL: |x - reference| / |reference| of a Mamba layer's
#   state and of its conv tail, before and after the fragment, by Mamba
#   layer from the first. The state is float32 in the program and reads
#   the in-projection's bfloat16 products (0.0030-0.0043 on every layer);
#   a state kept in bfloat16 loses what the slow heads add, an increment
#   under 2^-8 of the state (0.10-0.20). The first layer's conv tail holds
#   the in-projection of the normed embedding alone: its products' rounding
#   (0.0023-0.0024), where activations in bfloat16 read 0.0037: not held.
# ROWS_TOL: the same of the attention layer's key and value rows up to
#   ``len`` (the larger of the two): bfloat16 rows of the layer's input.
# LOGP_MEAN_TOL, LOGP_RMS_TOL: mean and root mean square over [T, B] of
#   |behaviour_logp - the reference's log-prob of the same action|, nats.
# KL_TOL: |kl - kl_reference| of the update's metrics.
# VALUE_LOSS_TOL, ENTROPY_TOL: |the update's metric - the reference's| /
#   max(1e-6, |the reference's|).
# GRAD_TOL: | |g| - |g_reference| | / |g_reference| by group of leaves: the
#   clipped gradient's magnitude as the optimizer's second moment keeps it
#   after the first update, against the reference's gradient clipped by the
#   update's own norm. "ssd": the last Mamba layer's A_log, dt_bias, D
#   (0.0022-0.0025 / 0.0028-0.0030 / 0.0035-0.0055 over the seeds; 0.030 /
#   0.030 / 0.058 under bfloat16 throughout).
# STEP_TOL: |step - reference step| / |reference step| over the same
#   leaves: 1 is what leaves left unchanged read. RMSProp's first step is
#   near lr * sign(g), so a gradient's smallest entries flip sign under
#   bfloat16 products: the final norm reads 0.03-0.10 over the seeds and
#   0.14 under bfloat16 throughout, the last Mamba layer's own leaves 0-0.35
#   and 0.45: neither is held. The value head's step is its gradient's
#   (0.0028-0.0030; 0.031).
# The counters are counts of the traffic (exact but for float32 sums).
STATE_TOL = (0.01,) * 9
CONV_TOL = (None,) + (0.006,) * 8
ROWS_TOL = 0.008
LOGP_MEAN_TOL, LOGP_RMS_TOL, KL_TOL = 2e-6, 3e-6, 2e-6
VALUE_LOSS_TOL, ENTROPY_TOL = 0.0013, 0.007
GRAD_TOL = {"final_norm": 0.01, "value": 0.01, "ssd": 0.02}
GRAD_TOL_F32 = 1e-3
STEP_TOL = {"final_norm": None, "value": 0.01, "ssd": None}
STEP_TOL_F32 = 1e-2
COUNTERS = ("gqa_rows_attended", "ssd_chunk_resets")


def rel(a: list, b: list) -> float:
    """|a - b| / |b| over a list of arrays; where |b| is 0, 0 if a is b and
    infinite if not (a wrong reference's step can be 0 on a whole group)."""
    import numpy as np

    gap = sum(float(np.sum(np.square(x - y, dtype=np.float64))) for x, y in zip(a, b))
    norm = sum(float(np.sum(np.square(y, dtype=np.float64))) for y in b)
    if norm == 0.0:
        return 0.0 if gap == 0.0 else float("inf")
    return (gap / norm) ** 0.5


def groups_of(tree, dims) -> dict:
    """The leaves held to the reference's gradient, by group: those after
    the last layer, and the last Mamba layer's own (``"ssd"``)."""
    from benchmarks.reference import granite_h as reference

    params = tree["params"]
    mamba = params[f"layer_{reference.last_mamba(dims)}"]["mamba"]
    return {**{k: params[k] for k in reference.TAIL},
            "ssd": {k: mamba[k] for k in reference.SSD_LEAVES}}


def carry_gaps(mine: list, theirs: list, dims: dict):
    """By layer, on the device: ``reference.carry_gap``'s readings."""
    from benchmarks.reference import granite_h as reference

    return reference.carry_gap(mine, theirs, dims)


def reference_program(cfg, dims, env_block: int, how: dict, stand_in=None):
    """``(params, history tokens and flags [Th, B], the replayed fragment,
    the replay's carry) -> (scalars, log-prob [T, B], carry gaps before and
    after the fragment, gradients of the held groups, None)``: the plain
    reference's view of the update that trains on that fragment.

    With ``stand_in`` (a control: ``reference_how``'s keys, e.g. ``{"low":
    true}``) the reference computed that way is put in the program's place:
    its carries and (the last result) what else the program would have given
    back are what is held against the reference's."""
    import jax.numpy as jnp

    from benchmarks.reference import granite_h as reference

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
        return loss, ref, {**tail, "ssd": ref["ssd_gradient"]}

    def reference_view_of(params, history_obs, history_done, r, carry):
        view = {**reference_view(r), "history_obs": history_obs,
                "history_done": history_done}
        mine = {"before": view.pop("init_core"), "after": carry}
        loss, ref, grads = view_of(params, view, how)
        other = None
        if stand_in is not None:
            _, theirs, their_grads = view_of(
                params, view, stand_in,
                carry_dtype=next(c["k"].dtype for c in carry if "k" in c))
            mine = {"before": theirs["core_before"], "after": theirs["core"]}
            other = {"logp": theirs["logp"], "grads": their_grads,
                     **{k: theirs[k] for k in ("value_loss", "entropy")}}
        gaps = {"before": carry_gaps(mine["before"], ref["core_before"], dims),
                "after": carry_gaps(mine["after"], ref["core"], dims)}
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
        return scalars, ref["logp"], gaps, grads, other

    return reference_view_of


def check_files_agree(cfg, config_doc) -> None:
    """The configuration's ``model`` record is the shape the program builds,
    and its ``parameters`` what ``granite_counts`` counts of it."""
    import dataclasses
    import json

    from asyncrl_tpu.models.granite_h import SHAPES
    from benchmarks import granite_counts

    built = json.loads(json.dumps(dataclasses.asdict(SHAPES[cfg.seq_model])))
    if built != config_doc["model"]:
        raise SystemExit(
            f"benchmarks: configs/{config_doc.get('name')}.json's model record "
            f"is not SHAPES[{cfg.seq_model!r}]"
        )
    if granite_counts.parameters(built) != config_doc.get("parameters"):
        raise SystemExit(
            f"benchmarks: configs/{config_doc.get('name')}.json's parameters "
            f"are not granite_counts.parameters of its model record"
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
        raise SystemExit("benchmarks: the anakin_granite loop's reference is "
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
        warm_in = int(config_doc.get("warm_in_fragments", 8))
        n_mamba = sum(kind.startswith("mamba") for kind in dims["layers"])
        K = cfg.updates_per_call
        frames_per_call = K * cfg.batch_steps_per_update
        n_dev = agent.mesh.devices.size
        update = agent.learner.update
        state = agent.state
        step0 = int(state.update_step)
        sums0 = leaf_hashes(state.params)
        held0 = jax.device_get(groups_of(state.params, dims))

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
        replay_gap = float(max(replay_gaps[k].max() for k in ("S", "conv", "rows")))
        gaps_of = lambda part: {
            k: [float(g) for g in reference_gaps[k][part]] for k in ("before", "after")}
        state_gaps, conv_gaps, row_gaps = gaps_of("S"), gaps_of("conv"), gaps_of("rows")
        len_differs = int(sum(reference_gaps[k]["len"].sum() for k in ("before", "after"))
                          + replay_gaps["len"].sum())
        nu = second_moment(state.opt_state)
        sums1 = leaf_hashes(state.params)
        taken = steps_float32_takes(cfg, state.params, nu)
        held1, nu = jax.device_get((groups_of(state.params, dims), groups_of(nu, dims)))
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
            k: rel(jax.tree.leaves(a[k]), jax.tree.leaves(b[k])) for k in GRAD_TOL
        }
        grad_gaps = by_group(grad_taken, grad_reference)
        step_gaps = by_group(step_taken, step_reference)
        del held0, held1, nu, grads, step_taken, step_reference
        del grad_taken, grad_reference
        phases.mark("warm_call")

        f32 = cfg.precision == "f32"
        if not f32 and n_mamba > len(STATE_TOL):
            raise SystemExit("benchmarks: the anakin_granite loop has limits "
                             f"for {len(STATE_TOL)} Mamba layers under bfloat16 products")
        resets = got["episode_resets"] * n_dev  # the metric is a mean over chips
        relative = lambda k: abs(got[k] - ref[k]) / max(1e-6, abs(ref[k]))
        loss_gap = abs(got["loss"] - ref["loss"]) / max(1.0, abs(ref["loss"]))
        pg_gap = abs(got["pg_loss"] - ref["pg_loss"]) / max(1.0, abs(ref["pg_loss"]))
        if other:
            print(f"benchmarks: A CONTROL, not the program: the reference under "
                  f"{stand_in} stands in the program's place below (carries, "
                  f"behaviour_logp, value loss, entropy, kl, the gradients and "
                  f"the steps)", file=sys.stderr)
        print(f"benchmarks: the first update after a warm-in of {warm_in} "
              f"fragments against the plain float32 reference on the fragment "
              f"it trained on. The carry its rollout left, |update - replay| / "
              f"|replay| {replay_gap!r}; |replay - reference| / |reference| by "
              f"Mamba layer of the state {state_gaps} and of the conv tail "
              f"{conv_gaps}, of the attention layer's key and value rows up to "
              f"len {row_gaps} (before the fragment, after it), envs whose len "
              f"differs {len_differs}; episode boundaries {resets!r} (replay "
              f"{boundaries}); behaviour_logp against the reference's log-prob "
              f"of the same actions, nats: {logp_gap}; metrics (update, "
              f"reference): "
              f"{ {k: (got[k], ref[k]) for k in ('value_loss', 'entropy', 'kl', 'pg_loss', 'loss', *COUNTERS)} }"
              f"; loss gap {loss_gap!r} of max(1, |loss|), and the reference's "
              f"own loss with every importance ratio 1: {ref['loss_on_policy']!r}"
              f"; on the leaves after the last layer and the last Mamba layer's "
              f"A_log, dt_bias and D, the clipped gradient's magnitude in the "
              f"optimizer's second moment against the reference's, |.| / "
              f"|reference|: {grad_gaps}, and |step - reference step| / |reference "
              f"step|: {step_gaps} (gradient norm {got['grad_norm']!r})",
              file=sys.stderr)

        compared: dict[str, list] = {}  # short name -> [reading, limit]

        def hold(name, what, value, limit, limit_f32=F32_TOL):
            limit = limit_f32 if f32 else limit
            if limit is None:  # no upper reading: printed above, not held
                return
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
            for when in ("before", "after"):
                for i, (gap, limit) in enumerate(zip(state_gaps[when], STATE_TOL)):
                    hold(f"state_{when}_m{i}",
                         f"Mamba layer {i}'s state {when} the fragment, of its "
                         f"norm from the reference's", gap, limit)
                for i, (gap, limit) in enumerate(zip(conv_gaps[when], CONV_TOL)):
                    hold(f"conv_{when}_m{i}",
                         f"Mamba layer {i}'s conv tail {when} the fragment, of "
                         f"its norm from the reference's", gap, limit)
                for i, gap in enumerate(row_gaps[when]):
                    hold(f"rows_{when}_a{i}",
                         f"attention layer {i}'s key and value rows {when} the "
                         f"fragment, of their norm from the reference's",
                         gap, ROWS_TOL)
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
            for k in GRAD_TOL:
                hold(f"grad_{k}",
                     f"the gradient of {k!r} as the optimizer's second moment "
                     f"keeps it vs the reference's, clipped, relative",
                     grad_gaps[k], GRAD_TOL[k], GRAD_TOL_F32)
                hold(f"step_{k}",
                     f"the update's step on {k!r} vs the reference's gradient "
                     f"stepped by the optimizer's rule, relative",
                     step_gaps[k], STEP_TOL[k], STEP_TOL_F32)
            if f32:
                hold("pg_loss",
                     "the update's policy-gradient term vs the reference's, "
                     "of max(1, |term|)", pg_gap, None)
                hold("loss",
                     "the update's loss vs the reference's, of max(1, |loss|)",
                     loss_gap, None)
        # every leaf: a gradient reached it, and it moved where its step is
        # one float32 can take
        still = {k for k in sums0 if np.array_equal(sums1[k], sums0[k])}
        unreached = sorted(k for k in sums0 if not taken[k][0] > 0)
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
              f" (the mix's draw: episode_seed {episode_seed}), ssd_chunk_resets "
              f"{by_update('ssd_chunk_resets')}, gqa_rows_attended "
              f"{by_update('gqa_rows_attended')}, loss {by_update('loss')} (the "
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
                "state_reference_gap": max(map(max, state_gaps.values())),
                "grad_reference_gap": max(grad_gaps.values()),
                "step_reference_gap": max(step_gaps.values()),
                "leaves_moved_by_first_update": len(sums0) - len(still),
                "episode_resets_per_update": mean_of("episode_resets"),
                **{k: mean_of(k) for k in COUNTERS},
            },
            "chips": n_dev,
            "window": (t_start, t_end),
            "geometry": {
                "num_envs": cfg.num_envs, "unroll_len": cfg.unroll_len,
                "updates_per_call": K, "rollout_on_device": True,
            },
            "granite": {"dims": dims, "attended": mean_of("gqa_rows_attended")},
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
