"""Measuring loop of the ``keye_moe_rl`` configuration on the Anakin path:
rollout (one token at a time through the carry: a K/V cache and the
indexer's key rows, the top-k selection every step) and learner (the model's
fragment form) are one device program, ``Trainer.learner.update``.

The timed window, the in-flight call, the sync discipline and the
``bench.*`` annotations are ``loops/anakin_seq.py``'s (the step runs donated,
so the host waits on the loss of the call before). Three things are this
loop's own.

THE DRAW. Where the mix states an ``episode_seed``, the env batch's states
and key chains are that seed's (``loops/common.py with_episode_seed``) and
not ``--seed``'s, which goes on seeding the parameters: the episode lengths,
and with them the rows the rollout's attention reads, are the mix's.

THE WARM-IN. Set-up advances every env by ``warm_in_fragments`` fragments of
the program's own ``unroll`` before the first update, parameters untouched,
and hands the actor state it ends on to the learner's state: the window
opens in the traffic's steady state (caches of thousands of rows, more than
half of the queries pruned by the selection) and not on empty caches, where
every row is selected and the indexer decides nothing. It keeps the tokens
and ``done`` flags of every step: the reference has no cache and is given
each env's whole history.

WHAT DECIDES ``correct``: what the first call of the timed program gives
back after the warm-in, against the plain reference
(``benchmarks/reference/keye_moe.py``) on the fragment that update trained
on, replayed beforehand by the same ``unroll``:

- the carry after the warm-in and after the fragment, by layer: key, value
  and indexer-key rows up to ``len`` (the largest gap of the three kinds),
  and ``len``, against the reference's rebuilt from the history;
- ``behaviour_logp`` (the one-token form through cache and selection) and
  the update's metrics (value loss, entropy, ``kl``, ``indexer_kl``, episode
  boundaries) against the reference's;
- the selection itself, as scores and not as indices: the fragment form's
  own selection on the replayed fragment (``KeyePolicy.selected``, outside
  the timed program) in the reference's coordinates; every row chosen by one
  and not by the other has a reference score within ``SELECT_GAP_TOL`` of the
  reference's 2,048th (in units of the chosen scores' spread), their number a
  query stays under ``SELECT_EXTRA_TOL``, and the two sets are of one size;
- the step taken on the leaves after the last layer and on the last layer's
  indexer leaves (which only ``L_I`` reaches) against the optimizer's rule on
  the reference's gradient; a gradient reaching every leaf; every leaf
  moving whose step float32 can take.

That the update trained on the replayed fragment is not assumed: the carry
its rollout leaves has to be the replay's to the last bit, and its count of
episode boundaries the replay's, or nothing is compared and the run is not
correct.
"""

from __future__ import annotations

import sys
import time

from benchmarks.loops import common
from benchmarks.loops.anakin_lfm2 import carry_of
from benchmarks.loops.anakin_seq import (
    F32_TOL,
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
# own widths on the chip, in PERF.md's table (PR 32): the largest the program
# gives over its seeds, and what a control gives. The controls: a reference
# that is wrong (topk 1,024; no relu; one indexer head's weight dropped;
# sigmoid in the router's place; one held expert of sixteen left out), held
# against the program; and the reference computed in bfloat16 throughout IN
# THE PROGRAM'S PLACE (``"stand_in": {"low": true}`` in a copy of the
# configuration file), held against the float32 reference by the same
# ``hold``s. Each control is not ``correct`` by one limit or more.
#
# ROWS_TOL: |rows - reference| / |reference| of a layer's key, value and
#   indexer-key rows up to ``len`` (the largest of the three), before and
#   after the fragment, by layer from the first. The first layer's rows read
#   exact inputs (embedding rows); deeper ones inherit the layers' rounding
#   and, past 2,048 rows, the near-ties of the selections below them.
# LOGP_MEAN_TOL, LOGP_RMS_TOL: mean and root mean square over [T, B] of
#   |behaviour_logp - the reference's log-prob of the same action|, nats.
# KL_TOL: |kl - kl_reference| of the update's metrics.
# VALUE_LOSS_TOL, ENTROPY_TOL, INDEXER_KL_TOL: |the update's metric - the
#   reference's| / max(1e-6, |the reference's|).
# SELECT_GAP_TOL, SELECT_EXTRA_TOL: see the module docstring; by layer. The
#   first layer's indexer reads exact inputs, so its selection differs from
#   the reference's by the rounding of its own products alone; a deeper
#   layer's inherits the layers' rounding and the selections below it, and a
#   few queries' inputs differ by far more than the typical one's (both are
#   maxima over 8,192 queries).
# GRAD_TOL: | |g| - |g_reference| | / |g_reference| by group of leaves: the
#   clipped gradient's magnitude as the optimizer's second moment keeps it
#   after the first update, against the reference's gradient clipped by the
#   update's own norm. "indexer": the last layer's indexer leaves.
# STEP_TOL: |step - reference step| / |reference step| over the same leaves:
#   1 is what leaves left unchanged read.
ROWS_TOL = (0.0045, 0.006, 0.008, 0.010)
LOGP_MEAN_TOL, LOGP_RMS_TOL, KL_TOL = 0.007, 0.014, 5e-4
VALUE_LOSS_TOL, ENTROPY_TOL, INDEXER_KL_TOL = 0.018, 3e-5, 4e-4
SELECT_GAP_TOL, SELECT_EXTRA_TOL = (0.15, 2.0, 2.0, 2.0), (40, 260, 260, 260)
SELECT_GAP_TOL_F32, SELECT_EXTRA_TOL_F32 = 1e-3, 2
GRAD_TOL = {"head": 0.064, "final_norm": 0.042, "value": 0.05, "indexer": 0.02}
GRAD_TOL_F32 = 1e-3
STEP_TOL, STEP_TOL_F32 = 0.5, 1e-2

GROUPS = (*TAIL, "indexer")


def unroll_program(agent, cfg):
    """``(behaviour params, actor state) -> (actor state, Rollout)``: the
    program's own ``unroll`` (the step's ``rollout`` scope, outside it)."""
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
        return actor, r

    return roll


def carry_gaps(mine: list, theirs: list):
    """By layer, on the device: the largest ``|rows - theirs| / |theirs|``
    over a cache's kinds of row up to ``theirs``' ``len``, and the envs whose
    ``len`` differs."""
    from benchmarks.reference import keye_moe as reference

    return reference.carry_gaps(*reference.carry_gap(mine, theirs))


def indexer_leaves(params, dims):
    """The last layer's indexer leaves: what only ``L_I`` reaches."""
    return params["params"][f"layer_{len(dims['layers']) - 1}"]["dsa"]["index"]


def reference_program(cfg, dims, env_block: int, how: dict, stand_in=None):
    """``(params, history tokens and flags [Th, B], the replayed fragment,
    the replay's carry, the program's selection by layer) -> (scalars,
    log-prob [T, B], carry gaps before and after the fragment, gradients of
    the leaves after the last layer and of the last layer's indexer, the
    selection's gaps by layer, None)``: the plain reference's view of the
    update that trains on that fragment.

    With ``stand_in`` (a control: ``reference_how``'s keys, e.g. ``{"low":
    true}``) the reference computed that way is put in the program's place:
    its carries, its selection and (the last result) what else the program
    would have given back are what is held against the reference's."""
    import jax.numpy as jnp

    from benchmarks.reference import keye_moe as reference

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
        return loss, ref, {**tail, "indexer": ref["indexer_gradient"]}

    def reference_view_of(p, history_obs, history_done, r, carry, chosen):
        view = {**reference_view(r), "history_obs": history_obs,
                "history_done": history_done}
        carries = {"before": view.pop("init_core"), "after": carry}
        other = None
        if stand_in is None:
            loss, ref, grads = view_of(
                p, view, how, program_chosen=chosen, carries=carries)
        else:
            _, theirs, their_grads = view_of(
                p, view, stand_in, keep_chosen=True,
                carry_dtype=carry[0]["k"].dtype)
            loss, ref, grads = view_of(
                p, view, how, history_chosen=theirs["chosen"],
                carries={"before": theirs["core_before"], "after": theirs["core"]})
            other = {"logp": theirs["logp"], "grads": their_grads,
                     **{k: theirs[k] for k in ("value_loss", "entropy", "indexer_kl")}}
        on_policy = reference.loss_of(
            {**view, "behaviour_logp": ref["logp"]}, ref, cfg.gamma,
            cfg.value_coef, cfg.entropy_coef, cfg.vtrace_rho_clip,
            cfg.vtrace_c_clip,
        ) + ref["indexer_kl"]
        scalars = {
            "loss": loss, "loss_on_policy": on_policy,
            "kl": jnp.mean(r.behaviour_logp - ref["logp"]),
            **{k: ref[k] for k in (
                "pg_loss", "value_loss", "entropy", "indexer_kl",
                "dsa_rows_scored", "dsa_rows_selected", "dsa_pruned_share")},
        }
        return scalars, ref["logp"], ref["carry_gaps"], grads, ref["selection"], other

    return reference_view_of


def check_files_agree(cfg, config_doc) -> None:
    """The configuration's ``model`` record is the shape the program builds,
    and its ``parameters`` what ``keye_counts`` counts of it."""
    import dataclasses
    import json

    from asyncrl_tpu.models.keye_moe import SHAPES
    from benchmarks import keye_counts

    built = json.loads(json.dumps(dataclasses.asdict(SHAPES[cfg.seq_model])))
    if built != config_doc["model"]:
        raise SystemExit(
            f"benchmarks: configs/{config_doc.get('name')}.json's model record "
            f"is not SHAPES[{cfg.seq_model!r}]"
        )
    if keye_counts.parameters(built) != config_doc.get("parameters"):
        raise SystemExit(
            f"benchmarks: configs/{config_doc.get('name')}.json's parameters "
            f"are not keye_counts.parameters of its model record"
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
        raise SystemExit("benchmarks: the anakin_keye loop's reference is a "
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
            **{k: tree["params"][k] for k in TAIL},
            "indexer": indexer_leaves(tree, dims),
        }
        held0 = jax.device_get(leaves_of(state.params))

        # ---- the traffic's draw: a mix that states an ``episode_seed`` has
        # its env batch start from that seed's states and key chains, so the
        # cell meets the same episode lengths at the same steps whatever
        # ``--seed`` the parameters have (the kernels' time follows them)
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
        # the agent holds the state the update will donate, as before: the
        # cold actor state (1.14 GB of empty caches) has no owner left
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
        chosen = jax.jit(lambda p, r: agent.model.apply(
            p, r.obs, r.done, r.init_core, method="selected"
        ))(state.params, fragment)
        phases.mark("first_fragment")

        ref, logp_reference, reference_gaps, grads, selection, other = jax.device_get(
            jax.jit(reference_program(cfg, dims, env_block, how, stand_in))(
                state.params, history_obs, history_done, fragment, carry_replay,
                chosen,
            )
        )
        del fragment, chosen
        # the replay's carry waits on the host: beside the update's 8.2 GB
        # of scratch and 6.7 GB of state its 1.14 GB would not be safe
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
            got.update({k: float(other[k]) for k in ("value_loss", "entropy", "indexer_kl")})
            got["kl"] = float(np.mean(rollout_logp - behaviour_logp))
        # what the update's rollout left is held to the replay's, on the device
        replay_gaps = jax.device_get(jax.jit(carry_gaps)(
            carry_of(state.actor.core), carry_replay))
        del carry_replay
        replay_gap = float(replay_gaps["rows"].max())
        row_gaps = {k: [float(g) for g in reference_gaps[k]["rows"]]
                    for k in ("before", "after")}
        len_differs = int(sum(reference_gaps[k]["len"].sum() for k in row_gaps)
                          + replay_gaps["len"].sum())
        selection = [{k: float(v) for k, v in s.items()} for s in selection]
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
            raise SystemExit("benchmarks: the anakin_keye loop has limits for "
                             f"{len(ROWS_TOL)} layers under bfloat16 products")
        resets = got["episode_resets"] * n_dev  # the metric is a mean over chips
        relative = lambda k: abs(got[k] - ref[k]) / max(1e-6, abs(ref[k]))
        loss_gap = abs(got["loss"] - ref["loss"]) / max(1.0, abs(ref["loss"]))
        pg_gap = abs(got["pg_loss"] - ref["pg_loss"]) / max(1.0, abs(ref["pg_loss"]))
        if other:
            print(f"benchmarks: A CONTROL, not the program: the reference under "
                  f"{stand_in} stands in the program's place below (carries, "
                  f"selection, behaviour_logp, value loss, entropy, kl, "
                  f"indexer_kl, the gradients and the steps)", file=sys.stderr)
        print(f"benchmarks: the first update after a warm-in of {warm_in} "
              f"fragments against the plain float32 reference on the fragment "
              f"it trained on. The carry its rollout left, |update - replay| / "
              f"|replay| {replay_gap!r}; |replay - reference| / |reference| of "
              f"the key, value and indexer-key rows up to len by layer, before "
              f"the fragment {row_gaps['before']} and after it "
              f"{row_gaps['after']}, envs whose len differs {len_differs}; "
              f"episode boundaries {resets!r} (replay {boundaries}); the "
              f"fragment form's selection against the reference's by layer "
              f"(gap: distance from the reference's k-th score in spreads; "
              f"rows a query chose that the reference did not): {selection}; "
              f"behaviour_logp against the reference's log-prob of the same "
              f"actions, nats: {logp_gap}; metrics (update, reference): "
              f"{ {k: (got[k], ref[k]) for k in ('value_loss', 'entropy', 'kl', 'indexer_kl', 'pg_loss', 'loss', 'dsa_rows_scored', 'dsa_rows_selected', 'dsa_pruned_share')} }"
              f"; loss gap {loss_gap!r} of max(1, |loss|), and the "
              f"reference's own loss with every importance ratio 1: "
              f"{ref['loss_on_policy']!r}; on the leaves after the last layer "
              f"and the last layer's indexer, the clipped gradient's magnitude "
              f"in the optimizer's second moment against the reference's, "
              f"|.| / |reference|: {grad_gaps}, and |step - reference step| / "
              f"|reference step|: {step_gaps} (gradient norm "
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
                         f"layer {i}'s key, value and indexer-key rows {when} "
                         f"the fragment, of their norm from the reference's",
                         gap, limit)
            for i, s in enumerate(selection):
                hold(f"select_gap_l{i}",
                     f"layer {i}'s selection: a row chosen by one side only, "
                     f"from the reference's k-th score, in spreads", s["gap"],
                     SELECT_GAP_TOL[i], SELECT_GAP_TOL_F32)
                hold(f"select_extra_l{i}",
                     f"layer {i}'s selection: rows a query chose that the "
                     f"reference did not", s["extra_max"], SELECT_EXTRA_TOL[i],
                     SELECT_EXTRA_TOL_F32)
                hold(f"select_size_l{i}",
                     f"layer {i}'s selection: queries whose set is not the "
                     f"reference's size", s["size_differs"], 0, 0)
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
            hold("indexer_kl",
                 "the update's indexer_kl vs the reference's, relative",
                 relative("indexer_kl"), INDEXER_KL_TOL)
            for k in ("dsa_rows_scored", "dsa_rows_selected", "dsa_pruned_share"):
                if not other:  # counts of the traffic: exact but for float32 sums
                    hold(k, f"the update's {k} vs the reference's, relative",
                         relative(k), 1e-5, 1e-5)
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
        # every leaf: a gradient reached it (the policy has no buffer), and
        # it moved where its step is one float32 can take
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
              f" (the mix's draw: episode_seed {episode_seed}), dsa_rows_scored "
              f"{by_update('dsa_rows_scored')}, loss {by_update('loss')} (the "
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
                "selection_reference_gap": max(s["gap"] for s in selection),
                "grad_reference_gap": max(grad_gaps.values()),
                "step_reference_gap": max(step_gaps.values()),
                "leaves_moved_by_first_update": len(sums0) - len(still),
                "moe_load_max_over_mean": float(np.mean([
                    np.mean(m["moe_load_max"]) / np.mean(m["moe_load_mean"])
                    for m in timed
                ])),
                "episode_resets_per_update": mean_of("episode_resets"),
                "moe_local_assignments": mean_of("moe_local_assignments"),
                **{k: mean_of(k) for k in (
                    "dsa_rows_scored", "dsa_rows_selected", "dsa_pruned_share",
                    "indexer_kl")},
            },
            "chips": n_dev,
            "window": (t_start, t_end),
            "geometry": {
                "num_envs": cfg.num_envs, "unroll_len": cfg.unroll_len,
                "updates_per_call": K, "rollout_on_device": True,
            },
            "keye": {
                "dims": dims,
                "scored": mean_of("dsa_rows_scored"),
                "selected": mean_of("dsa_rows_selected"),
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
