"""Measuring loop of the ``lfm2_moe_rl`` configuration on the Anakin path:
rollout (one token at a time through the carry: conv tails and a K/V cache)
and learner (the model's fragment form) are one device program,
``Trainer.learner.update``.

The timed window, the in-flight call, the sync discipline and the
``bench.*`` annotations are ``loops/anakin_seq.py``'s, line for line (the
step runs donated, so the host waits on the loss of the call before), and
so is the shape of what decides ``correct``: what the first call of the
timed program, the warm-up ``update``, gives back, against the plain
reference (``benchmarks/reference/lfm2_moe.py``) on the fragment that
update trained on, replayed by the program's own ``unroll`` beforehand:

- the carry its rollout leaves, by layer: the conv tails, and the key and
  value rows up to ``len`` with ``len`` itself, against the reference's
  after the same tokens (the reference counts positions from each
  episode's start, so a wrong rotation, norm or position shows in the keys);
- ``behaviour_logp`` against the reference's log-prob of the same actions
  (mean and rms): the one-token form through the carry;
- its ``metrics`` (value loss, entropy, the learner's mean log-prob through
  ``kl``, episode boundaries; the policy-gradient term and the loss too
  where the configuration says ``f32``);
- the step it takes on the leaves after the last layer against the step the
  optimizer's rule makes of the reference's gradient of those leaves, and
  the gradient's magnitude as RMSProp's second moment keeps it; that a
  gradient reached every leaf but the router's buffer, and that every leaf
  moved whose step float32 can take.

That the update trained on the replayed fragment is not assumed: the carry
its rollout leaves has to be the replay's to the last bit (1e-6 of its
norm is the limit), and its count of episode boundaries the replay's, or
nothing is compared and the run is not correct.
"""

from __future__ import annotations

import sys
import time

from benchmarks.loops import common
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

# Limits of the comparisons that decide ``correct``. Under
# ``precision="f32"`` every one of them is rounding (F32_TOL and the two
# beside it). Under bfloat16 products (the cell) each lies between two
# readings at the cell's own widths on the chip, in PERF.md's table (PR 30):
# the largest the program gives over its seeds, and what a control gives.
# The controls: a reference that is wrong (one held expert of eight left
# out; theta = 1e4; the q/k norms dropped; the conv's gate C or its gate B
# dropped), held against the program; and the reference computed in
# bfloat16 throughout IN THE PROGRAM'S PLACE (``"stand_in": {"low": true}``
# in a copy of the configuration file), held against the float32 reference
# by the same ``hold``s. Each control is not ``correct`` by one limit or
# more; no limit is here that no control exceeds.
#
# CONV_TAIL_TOL: |tail - tail_reference| / |tail_reference| of the conv
#   tails the update's rollout leaves in the carry, by conv layer from the
#   first. The first reads exact inputs (embedding rows), deeper ones
#   inherit the layers' rounding.
# KV_ROWS_TOL: the same of the key rows and of the value rows up to ``len``
#   (the larger of the two), the attention layer's cache: what refuses
#   rotary angles formed in bfloat16 (the first update's positions are
#   under 256, all bfloat16s; later ones are not, and no update but the
#   first is held).
# LOGP_MEAN_TOL, LOGP_RMS_TOL: mean and root mean square over [T, B] of
#   |behaviour_logp - the reference's log-prob of the same action|, nats.
# KL_TOL: |kl - kl_reference| of the update's metrics, the learner's SIGNED
#   mean log-prob gap.
# VALUE_LOSS_TOL, ENTROPY_TOL: |the update's metric - the reference's| /
#   max(1e-6, |the reference's|). The entropy of a fresh policy is log V to
#   six digits whatever the layers do (no wrong reference moves it by more
#   than 1.1e-5): what its limit refuses is the head's log-softmax in
#   bfloat16.
# TAIL_GRAD_TOL: | |g| - |g_reference| | / |g_reference| by leaf after the
#   last layer: the clipped gradient's magnitude as the optimizer's second
#   moment keeps it after the first update, against the reference's
#   gradient of those leaves clipped by the update's own norm.
# TAIL_STEP_TOL: |step - reference step| / |reference step| over the same
#   leaves: 1 is what leaves left unchanged, or a step twice as long, read;
#   precision hardly moves it (RMSProp's first step is near lr * sign(g)),
#   so its limit stands between the first reading and 1.
# The loss and its policy-gradient term decide under ``f32`` only (PR 26's
#   finding on V-trace's clipped ratios under bfloat16 products).
CONV_TAIL_TOL = (0.0043, 0.03, 0.04, 0.055)
KV_ROWS_TOL = 0.012
LOGP_MEAN_TOL, LOGP_RMS_TOL, KL_TOL = 0.016, 0.04, 2e-3
VALUE_LOSS_TOL, ENTROPY_TOL = 0.065, 3e-5
TAIL_GRAD_TOL = {"head": 0.17, "final_norm": 0.06, "value": 0.05}
TAIL_GRAD_TOL_F32 = 1e-3
TAIL_STEP_TOL, TAIL_STEP_TOL_F32 = 0.5, 1e-2


def carry_of(core) -> list:
    """The carry as plain dicts, one a layer."""
    return [dict(layer) for layer in core.layers]


def first_fragment(agent, cfg):
    """The fragment the next update will train on, replayed: the program's
    own ``unroll`` from the same actor state under the same behaviour
    params (the step's ``rollout`` scope, outside it), as the ``Rollout``
    the learner reads (``init_core`` included), and the carry the replay
    leaves."""
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
        return r, carry_of(actor.core)

    return roll(agent.state.actor_params, agent.state.actor)


def carry_gaps(mine: list, theirs: list):
    """By layer, on the device: a conv layer's ``|tail - theirs| / |theirs|``;
    an attention layer's largest such gap over its key rows and its value
    rows up to ``theirs``' ``len``, and the envs whose ``len`` differs."""
    import jax.numpy as jnp

    def gap(a, b, live=None):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if live is not None:
            a, b = jnp.where(live, a, 0.0), jnp.where(live, b, 0.0)
        return jnp.sqrt(jnp.sum(jnp.square(a - b)) / jnp.maximum(jnp.sum(b * b), 1e-30))

    conv, rows, lens = [], [], []
    for a, b in zip(mine, theirs):
        if "k" not in b:
            conv.append(gap(a["conv"], b["conv"]))
            continue
        live = (jnp.arange(b["k"].shape[1])[None, :] < b["len"][:, None])[..., None]
        rows.append(jnp.maximum(gap(a["k"], b["k"], live), gap(a["v"], b["v"], live)))
        lens.append(jnp.sum(a["len"] != b["len"]))
    return {"conv": jnp.stack(conv), "rows": jnp.stack(rows), "len": jnp.stack(lens)}


def reference_program(cfg, dims, env_block: int, how: dict, stand_in=None):
    """``(params, replayed fragment, the replay's carry) -> (scalars,
    log-prob [T, B], the replay's carry against the reference's by layer,
    gradient of the leaves after the last layer, None)``: the plain
    reference's view of the update that trains on that fragment.

    With ``stand_in`` (a control: ``reference_how``'s keys, e.g. ``{"low":
    true}``) the reference computed that way is put in the program's place:
    its carry is what is held against the reference's, and the last result
    holds what else the program would have given back (its log-prob of the
    fragment's actions, value loss, entropy and the gradient of the leaves
    after the last layer)."""
    import jax.numpy as jnp

    from benchmarks.reference import lfm2_moe as reference

    def view_of(p, view, how):
        loss, ref = reference.impala_loss(
            p, dims, view, cfg.gamma, cfg.value_coef, cfg.entropy_coef,
            cfg.vtrace_rho_clip, cfg.vtrace_c_clip, env_block=env_block,
            **how,
        )
        tail = reference.tail_gradient(
            p, dims, view, ref, cfg.value_coef, cfg.entropy_coef,
            env_block=env_block, **how,
        )
        return loss, ref, tail

    def reference_view_of(p, r, carry):
        view = reference_view(r)
        loss, ref, tail = view_of(p, view, how)
        other = None
        if stand_in is not None:
            _, theirs, their_tail = view_of(p, view, stand_in)
            carry = theirs["core"]
            other = {"logp": theirs["logp"], "tail": their_tail,
                     "value_loss": theirs["value_loss"], "entropy": theirs["entropy"]}
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
        return scalars, ref["logp"], carry_gaps(carry, ref["core"]), tail, other

    return reference_view_of


def check_files_agree(cfg, config_doc) -> None:
    """The configuration's ``model`` record is the shape the program
    builds."""
    import dataclasses
    import json

    from asyncrl_tpu.models.lfm2_moe import SHAPES

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

    cfg = make_config()
    if (cfg.normalize_obs or cfg.normalize_returns or cfg.algo != "impala"
            or not cfg.seq_model or cfg.optimizer != "rmsprop"
            or cfg.lr_schedule != "constant" or cfg.entropy_anneal_steps
            or not cfg.introspect):
        raise SystemExit("benchmarks: the anakin_lfm2 loop's reference is a "
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
        env_block = int(config_doc.get("reference_env_block", 4))
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
        fragment, carry_replay = first_fragment(agent, cfg)
        boundaries = int(np.sum(np.asarray(fragment.done)))
        behaviour_logp = np.asarray(fragment.behaviour_logp, np.float64)
        phases.mark("first_fragment")

        ref, logp_reference, reference_gaps, tail_grad, other = jax.device_get(
            jax.jit(reference_program(cfg, dims, env_block, how, stand_in))(
                state.params, fragment, carry_replay
            )
        )
        del fragment
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
            got["value_loss"] = float(other["value_loss"])
            got["entropy"] = float(other["entropy"])
            got["kl"] = float(np.mean(rollout_logp - behaviour_logp))
        # the replay's carry waited on the device beside the update (0.54 GB
        # of rows); what the update's rollout left is held to it there
        replay_gaps = jax.device_get(jax.jit(carry_gaps)(
            carry_of(state.actor.core), carry_replay))
        del carry_replay
        replay_gap = float(max(replay_gaps["conv"].max(), replay_gaps["rows"].max()))
        conv_gaps = [float(g) for g in reference_gaps["conv"]]
        row_gaps = [float(g) for g in reference_gaps["rows"]]
        len_differs = int(reference_gaps["len"].sum() + replay_gaps["len"].sum())
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
        if other:  # what the optimizer's rule makes of the stand-in's gradient
            grad_taken, step_taken = reference_update(
                cfg, tail0, other["tail"], got["grad_norm"]
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
        if not f32 and len(conv_gaps) > len(CONV_TAIL_TOL):
            raise SystemExit("benchmarks: the anakin_lfm2 loop has limits for "
                             f"{len(CONV_TAIL_TOL)} conv layers under bfloat16 products")
        resets = got["episode_resets"] * n_dev  # the metric is a mean over chips
        relative = lambda k: abs(got[k] - ref[k]) / max(1e-6, abs(ref[k]))
        loss_gap = abs(got["loss"] - ref["loss"]) / max(1.0, abs(ref["loss"]))
        pg_gap = abs(got["pg_loss"] - ref["pg_loss"]) / max(1.0, abs(ref["pg_loss"]))
        if other:
            print(f"benchmarks: A CONTROL, not the program: the reference under "
                  f"{stand_in} stands in the program's place below (carry, "
                  f"behaviour_logp, value loss, entropy, kl, the gradient and "
                  f"the step of the leaves after the last layer)", file=sys.stderr)
        print(f"benchmarks: the first update against the plain float32 "
              f"reference on the fragment it trained on. The carry its "
              f"rollout left, |update - replay| / |replay| {replay_gap!r}; "
              f"|replay - reference| / |reference| of the conv tails by layer "
              f"{conv_gaps}, of the key and value rows up to len by attention "
              f"layer {row_gaps}, envs whose len differs {len_differs}; "
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
              f"{step_gaps} (gradient norm {got['grad_norm']!r}); expert "
              f"layers: local assignments {got['moe_local_assignments']!r}, "
              f"dense blocks {got['moe_dense_blocks']!r}, rows a query "
              f"attended {got['gqa_rows_attended']!r}",
              file=sys.stderr)

        def hold(what, value, limit, limit_f32=F32_TOL):
            limit = limit_f32 if f32 else limit
            if not value <= limit:
                reasons.append(f"{what}: {value!r} (limit {limit})")

        if not (replay_gap <= 1e-6 and resets == boundaries and not len_differs):
            reasons.append(
                f"the first update did not train on the replayed fragment, "
                f"or the cache's lengths are not the reference's (the carry "
                f"after its rollout {replay_gap!r} of its norm "
                f"from the replay's, {resets!r} episode "
                f"boundaries against {boundaries}, {len_differs} envs whose "
                f"len differs): nothing of it can be "
                f"held against the reference"
            )
        else:
            for i, (gap, limit) in enumerate(zip(conv_gaps, CONV_TAIL_TOL)):
                hold(f"conv layer {i}'s tail after the update's rollout, of its "
                     f"norm from the reference's", gap, limit)
            for i, gap in enumerate(row_gaps):
                hold(f"attention layer {i}'s key and value rows after the "
                     f"update's rollout, of their norm from the reference's",
                     gap, KV_ROWS_TOL)
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
                     grad_gaps[k], TAIL_GRAD_TOL[k], TAIL_GRAD_TOL_F32)
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
                "conv_tail_reference_gap": max(conv_gaps),
                "kv_rows_reference_gap": max(row_gaps),
                "tail_grad_reference_gap": max(grad_gaps.values()),
                "tail_step_reference_gap": max(step_gaps.values()),
                "leaves_moved_by_first_update": len(sums0) - len(still),
                "moe_load_max_over_mean": float(np.mean([
                    np.mean(m["moe_load_max"]) / np.mean(m["moe_load_mean"])
                    for m in timed
                ])),
                "episode_resets_per_update": mean_of("episode_resets"),
                "moe_local_assignments": mean_of("moe_local_assignments"),
                "moe_dense_blocks": mean_of("moe_dense_blocks"),
                "gqa_rows_attended": mean_of("gqa_rows_attended"),
            },
            "chips": n_dev,
            "window": (t_start, t_end),
            "geometry": {
                "num_envs": cfg.num_envs, "unroll_len": cfg.unroll_len,
                "updates_per_call": K, "rollout_on_device": True,
            },
            "lfm2": {
                "dims": dims,
                "attended": mean_of("gqa_rows_attended"),
                # assignments on held experts, a token and expert layer
                "held_per_token": mean_of("moe_local_frac") * dims["top_k"],
                # a chip's, an update (the metric is a mean over chips)
                "local_assignments": mean_of("moe_local_assignments"),
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
