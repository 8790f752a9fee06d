"""Wall-clock-to-target runner: the north-star OUTCOME measurement
(BASELINE.md: wall-clock to 18.0 mean Pong reward, target < 10 min on TPU;
VERDICT.md round 1, Missing #2). Trains a preset until the in-training
greedy eval reaches the target return, then prints a ``time_to_target``
record as one JSON line on stdout.

    python scripts/run_to_target.py pong_impala \
        [--target 18.0] [--budget-seconds 3600] [key=value ...]

Wall clock is measured from the moment ``train()`` is entered (compile
time included — that is what a user actually waits). The run refuses to
record a success unless training truly hit the target; a budget exhaustion
is recorded too (kind="time_to_target", reached=false) so failed attempts
are visible history, not silence.

Success protocol (VERDICT r4 Next #3): an in-training eval crossing the
target is only a CANDIDATE — with ``eval_episodes=32`` and per-episode std
0.8–3.0, a true-mean-17.9 policy can luck across a single eval. The run
confirms every crossing with an independent fresh-seed eval of
``--confirm-episodes`` (default 64, floored at 64) episodes before banking
``reached=true``; the row records both numbers. A crossing that fails
confirmation resumes training (the budget clock never stops) and is
counted in the row's ``unconfirmed_crossings``.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from asyncrl_tpu.utils import runtime  # noqa: E402


class _Crossed(Exception):
    """In-training eval crossed the target: stop and confirm."""


class _BudgetExhausted(Exception):
    """Wall-clock budget spent: stop and record reached=false."""


# Confirmation evals must be independent of the in-training eval stream
# (Trainer.evaluate defaults to seed=1234 — the same episodes every time);
# a fixed distinct base keeps the protocol reproducible while each retry
# within a session still sees fresh episodes.
CONFIRM_SEED_BASE = 97_531


def main() -> int:
    args = sys.argv[1:]
    target_return = 18.0  # BASELINE.json:2 Pong target
    budget_seconds = 3600.0
    confirm_episodes = 64
    overrides = []
    preset_name = "pong_impala"
    it = iter(args)
    for a in it:
        if a in ("--target", "--budget-seconds", "--confirm-episodes"):
            try:
                value = float(next(it))
            except (StopIteration, ValueError):
                print(f"usage: {a} <number>", file=sys.stderr)
                return 2
            if a == "--target":
                target_return = value
            elif a == "--budget-seconds":
                budget_seconds = value
            else:
                # The protocol floor is 64 (VERDICT r4 Weak #2): fewer
                # episodes would re-open the single-lucky-eval hole the
                # confirmation exists to close.
                confirm_episodes = max(64, int(value))
        elif "=" in a:
            overrides.append(a)
        else:
            preset_name = a

    # TPU or refuse: a silent CPU session would pollute a TPU
    # checkpoint_dir's accumulated clock. An explicit CPU run
    # (ASYNCRL_FORCE_CPU=1) is valid evidence; its row says platform=cpu.
    runtime.require_tpu("run_to_target")
    runtime.enable_compile_cache()

    from asyncrl_tpu.api.factory import make_agent
    from asyncrl_tpu.configs import presets
    from asyncrl_tpu.utils.config import override

    cfg = presets.get(preset_name)
    if cfg.eval_every <= 0:
        # Eval cadence drives target detection; check roughly every ~2s of
        # training (eval_every counts update CALLS, aligned to log_every).
        cfg = cfg.replace(eval_every=cfg.log_every, eval_episodes=32)
    cfg = override(cfg, overrides)

    # Cross-session accumulation (VERDICT.md round 2, Next #1): with a
    # checkpoint_dir, Trainer auto-resumes training state bit-exact, and the
    # wall clock accumulates through a sidecar — so a target reached on the
    # Nth session records the TOTAL training time, not one session's slice.
    # (The clock is training-only wall time: the gaps between sessions are
    # not training and do not count.)
    elapsed_path = (
        os.path.join(cfg.checkpoint_dir, "run_to_target_elapsed.json")
        if cfg.checkpoint_dir
        else None
    )
    prior = {
        "seconds": 0.0,
        "sessions": 0,
        "fps_sum": 0.0,
        "fps_n": 0,
        # Which platforms contributed sessions (a checkpoint can resume
        # on another platform — TPU sessions then CPU ones). The
        # wall-clock accumulation stays honest either way, but mean_fps
        # blends platforms, so the entry must say so.
        "platforms": [],
        # Crossings rejected by the confirmation eval in PRIOR sessions
        # (this session's count is confirm["failed"]): the final row's
        # provenance must count every rejected crossing on the arm.
        "unconfirmed_crossings": 0,
    }
    # Prior time counts only when there is actually a checkpoint to resume
    # from — a stale sidecar next to deleted checkpoints must not credit a
    # fresh run with old wall time.
    sidecar_names = {
        os.path.basename(elapsed_path),
        os.path.basename(elapsed_path) + ".tmp",
    } if elapsed_path else set()
    has_checkpoint = cfg.checkpoint_dir and any(
        e not in sidecar_names
        for e in (
            os.listdir(cfg.checkpoint_dir)
            if os.path.isdir(cfg.checkpoint_dir)
            else []
        )
    )
    if elapsed_path and has_checkpoint and os.path.exists(elapsed_path):
        try:
            with open(elapsed_path) as f:
                loaded = json.load(f)
            prior.update({k: loaded[k] for k in prior if k in loaded})
        except (OSError, json.JSONDecodeError, TypeError, KeyError):
            loaded = {}
            print(
                "run_to_target: unreadable elapsed sidecar; counting this "
                "session only",
                file=sys.stderr,
            )
        else:
            if loaded.get("reached", False):
                print(
                    "run_to_target: this checkpoint_dir already holds a "
                    "COMPLETED time-to-target measurement; resuming it "
                    "would record a bogus instant success. Clear the "
                    "directory to start a new measurement.",
                    file=sys.stderr,
                )
                return 3
            print(
                f"run_to_target: resuming after {prior['sessions']} prior "
                f"session(s), {prior['seconds']:.0f}s accumulated",
                file=sys.stderr,
            )

    # The completed-measurement refusal above must run BEFORE backend init:
    # a refusal should be instant and side-effect-free, not pay an
    # accelerator bring-up and an orbax auto-restore first.
    # make_agent dispatches on cfg.backend — a sebulba/cpu_async preset must
    # be measured on ITS architecture, not silently retimed on Anakin.
    trainer = make_agent(cfg)
    dev = runtime.device_entry()
    status = {"reached": False, "seconds": None, "eval_return": None}
    # Confirmation state lives next to status because save_elapsed (a
    # closure called on every metrics drain) persists the failed-crossing
    # count: a SIGKILL'd session's rejected lucky crossing must survive
    # into the next session's row, not vanish with the process.
    confirm = {"return": None, "failed": 0}
    fps_log: list[float] = []
    t0 = time.perf_counter()

    def total_elapsed() -> float:
        return prior["seconds"] + time.perf_counter() - t0

    def save_elapsed(reached: bool = False) -> None:
        # Atomic (tmp + rename), and OSError-tolerant: a full/read-only
        # checkpoint volume must degrade the accumulation, never abort
        # the measurement itself.
        if not elapsed_path:
            return
        payload = {
            "seconds": round(total_elapsed(), 1),
            "sessions": prior["sessions"] + 1,
            "fps_sum": prior["fps_sum"] + sum(fps_log),
            "fps_n": prior["fps_n"] + len(fps_log),
            "platforms": sorted(
                set(prior["platforms"]) | {dev["platform"]}
            ),
            "unconfirmed_crossings": (
                prior["unconfirmed_crossings"] + confirm["failed"]
            ),
        }
        if reached:
            payload["reached"] = True
        try:
            tmp = elapsed_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, elapsed_path)
        except OSError as e:
            print(
                f"run_to_target: could not persist elapsed sidecar: {e}",
                file=sys.stderr,
            )

    def on_metrics(agg: dict) -> None:
        fps_log.append(agg["fps"])
        ev = agg.get("eval_return")
        if ev is not None:
            status["eval_return"] = round(ev, 3)
        line = {
            "t": round(total_elapsed(), 1),
            "env_steps": agg["env_steps"],
            "episode_return": round(agg["episode_return"], 2),
            "fps": round(agg["fps"]),
        }
        if ev is not None:
            line["eval_return"] = round(ev, 2)
        print(json.dumps(line), file=sys.stderr, flush=True)
        # Learning curve persisted WITH the run, not only in the (tmp-
        # resident, reboot-mortal) supervisor log: the committed run dir
        # then carries the eval trajectory across sessions as evidence.
        if cfg.checkpoint_dir:
            try:
                with open(
                    os.path.join(cfg.checkpoint_dir, "metrics.jsonl"), "a"
                ) as f:
                    f.write(json.dumps(line) + "\n")
            except OSError:
                pass  # read-only volume: stderr already has the line
        # Persist accumulated wall time on every drain, not just at exit: a
        # SIGKILL'd session's checkpointed training progress survives, so
        # its wall time must survive too (else a later session records an
        # understated time-to-target).
        save_elapsed()
        if ev is not None and ev >= target_return:
            # Candidate only: the crossing's wall clock is frozen here, but
            # reached=true is banked ONLY if the independent confirmation
            # eval below agrees (VERDICT r4 Next #3).
            status["crossing_seconds"] = round(total_elapsed(), 1)
            raise _Crossed
        if total_elapsed() > budget_seconds:
            status["seconds"] = round(total_elapsed(), 1)
            raise _BudgetExhausted

    try:
        while True:
            try:
                trainer.train(callback=on_metrics)
                if status["seconds"] is None:
                    # total_env_steps ran out before target or budget: the
                    # attempt's duration and last eval are still evidence,
                    # not silence.
                    status["seconds"] = round(total_elapsed(), 1)
                break
            except _BudgetExhausted:
                break
            except _Crossed:
                crossing_seconds = status.pop("crossing_seconds")
                # Each crossing gets its own confirmation verdict: a stale
                # value from an earlier rejected crossing must not pair
                # with THIS crossing's numbers in the final row (e.g. when
                # this confirmation attempt crashes below).
                confirm["return"] = None
                # Fresh-seed confirmation, independent of the in-training
                # eval stream. Retries cycle through 8 seeds (params have
                # moved between retries, so reuse is sound) — unbounded
                # fresh seeds would grow SebulbaTrainer's per-(episodes,
                # seed) eval-pool cache linearly with failed crossings.
                seed = CONFIRM_SEED_BASE + (confirm["failed"] % 8)
                try:
                    confirm["return"] = float(
                        trainer.evaluate(
                            num_episodes=confirm_episodes, seed=seed
                        )
                    )
                except Exception as e:
                    # The confirmation eval is bigger than the in-training
                    # one (64 episodes vs 32) — on a memory-edge geometry
                    # it can fail where training did not. The attempt must
                    # still become a visible reached=false row with the
                    # crossing's provenance, not a crash with no entry
                    # ("failed attempts are visible history").
                    status["confirm_error"] = str(e)[:300]
                    status["seconds"] = crossing_seconds
                    print(
                        f"run_to_target: confirmation eval failed: {e}",
                        file=sys.stderr,
                    )
                    break
                print(
                    json.dumps(
                        {
                            "confirm_return": round(confirm["return"], 3),
                            "confirm_episodes": confirm_episodes,
                            "confirm_seed": seed,
                            "crossing_eval": status["eval_return"],
                            "t": crossing_seconds,
                        }
                    ),
                    file=sys.stderr,
                    flush=True,
                )
                if confirm["return"] >= target_return:
                    status.update(reached=True, seconds=crossing_seconds)
                    break
                confirm["failed"] += 1
                # Persist the rejection NOW: a SIGKILL before the resumed
                # training's next metrics drain must not lose it.
                save_elapsed()
                print(
                    "run_to_target: crossing NOT confirmed "
                    f"({confirm['return']:.2f} < {target_return}); "
                    "resuming training",
                    file=sys.stderr,
                )
                # The confirmation eval's wall time stays on the clock (the
                # user waited through it); it may itself exhaust the budget.
                if total_elapsed() > budget_seconds:
                    status["seconds"] = round(total_elapsed(), 1)
                    break
    finally:
        save_elapsed()
        trainer.close()

    entry = {
        "kind": "time_to_target",
        "preset": preset_name,
        # The env actually trained (an override can retarget a preset —
        # e.g. the CPU recipe probe runs pong_pixels_t2t's economics on
        # the VECTOR env; without this field that row would read as a
        # pixel-path result).
        "env_id": cfg.env_id,
        **dev,
        "target_return": target_return,
        "reached": status["reached"],
        "seconds": status["seconds"],
        "eval_return": status["eval_return"],
        # Confirmation provenance (VERDICT r4 Next #3): a reached=true row
        # carries BOTH the in-training crossing eval (eval_return) and the
        # independent fresh-seed confirmation; crossings that failed
        # confirmation are counted, not hidden.
        **(
            {
                "confirm_return": round(confirm["return"], 3),
                "confirm_episodes": confirm_episodes,
            }
            if confirm["return"] is not None
            else {}
        ),
        **(
            {
                "unconfirmed_crossings": (
                    prior["unconfirmed_crossings"] + confirm["failed"]
                )
            }
            if prior["unconfirmed_crossings"] + confirm["failed"]
            else {}
        ),
        **(
            {"confirm_error": status["confirm_error"]}
            if "confirm_error" in status
            else {}
        ),
        "num_envs": cfg.num_envs,
        "unroll_len": cfg.unroll_len,
        "updates_per_call": cfg.updates_per_call,
        # The episode-cap bar this target was measured under (VERDICT r3
        # Weak #4): 3000 = the repo's scoring-rate bar, 27000 =
        # ALE-faithful win-margin semantics.
        **(
            {"pong_max_steps": cfg.pong_max_steps}
            if "JaxPong" in cfg.env_id
            else {}
        ),
        # Decisions-per-core-frame context: a skip-4 row's seconds/fps
        # count agent decisions, 4 core frames each.
        **({"frame_skip": cfg.frame_skip} if cfg.frame_skip != 1 else {}),
        # Consistent with "seconds": averaged over ALL accumulated sessions
        # (window-fps mean, weights carried through the sidecar).
        "mean_fps": round(
            (prior["fps_sum"] + sum(fps_log))
            / max(prior["fps_n"] + len(fps_log), 1)
        ),
    }
    if prior["sessions"]:
        entry["resumed_sessions"] = prior["sessions"]
    session_platforms = sorted(set(prior["platforms"]) | {dev["platform"]})
    if len(session_platforms) > 1:
        # A cross-platform resume: seconds are wall-clock-honest, but the
        # fps average blends device speeds — the row must carry the
        # blend's provenance (the top-level platform field only names the
        # FINAL session's device).
        entry["platforms"] = session_platforms
        entry["mean_fps_mixed_platforms"] = True
    if status["reached"]:
        # Mark the measurement finished. A rerun in this dir would resume
        # the already-trained checkpoint and "reach" the target in seconds
        # — deleting the sidecar would let that record as a bogus fresh
        # time_to_target, so instead the marker makes a rerun refuse
        # (clear the checkpoint dir to start a new measurement).
        save_elapsed(reached=True)
    print(json.dumps(entry))
    return 0 if status["reached"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
