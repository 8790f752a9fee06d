#!/usr/bin/env bash
# Replay smoke: the operator-facing gate for the device replay ring +
# IMPACT-mode learner (ISSUE 14; learn/replay.py), in two acts:
#
#   1. IDENTITY — replay_slabs=0 must be the pre-PR program: two
#      replay-off runs on a fixed seed (after a discarded in-process
#      warm-up, the elastic_smoke discipline) must be BIT-IDENTICAL on
#      losses, and neither run's windows may carry any replay key
#      (reuse_*, target_kl, replay_fill_frac, learner_stall_trend).
#   2. DUTY CYCLE — a replay-on run (same workload, same seed, same
#      fixed env-step budget) must drive learner_stall_frac STRICTLY
#      below the replay-off run's (the ISSUE-14 gate; the measured
#      reduction ratio is recorded — the acceptance target is >= 2x),
#      with the greedy eval return within noise of the off run's
#      (>= half; both recorded verbatim), and every window carrying the
#      replay telemetry.
#
# Usage: scripts/replay_smoke.sh                   # CPU, ~1-2 min
#        ASYNCRL_SMOKE_UPDATES=400 scripts/replay_smoke.sh
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
# Act 2's fixed env-step budget, in learner update-equivalents. The
# default is solve-scale for this box (~100k env steps, ~15-25s/run):
# below ~300 the greedy eval of a still-near-uniform policy is noise and
# the sample-efficiency comparison meaningless.
UPDATES="${ASYNCRL_SMOKE_UPDATES:-800}"
OUT_DIR="$(mktemp -d)"
trap 'rm -rf "$OUT_DIR"' EXIT

# ---------------------------------------------------------------- act 1
# Identity: replay off twice, fixed seed, bit-identical + zero keys.
python - "$OUT_DIR" <<'EOF'
import json
import sys

import numpy as np

from asyncrl_tpu import make_agent
from asyncrl_tpu.utils.config import Config

out_dir = sys.argv[1]
NUM_ENVS, UNROLL, UPDATES = 16, 8, 24
REPLAY_KEYS = (
    "replay_fill_frac", "reuse_p50", "reuse_p95", "reuse_max",
    "target_lag_mean", "target_kl", "learner_stall_trend",
)


def run():
    cfg = Config(
        env_id="CartPole-v1", algo="impala", backend="sebulba",
        host_pool="jax", num_envs=NUM_ENVS, actor_threads=1,
        unroll_len=UNROLL, precision="f32", log_every=4, seed=3,
        # Frozen behaviour params: losses must be seed-deterministic
        # for the identity assertion (no publish-timing race).
        actor_staleness=1_000_000,
    )
    agent = make_agent(cfg)
    try:
        history = agent.train(total_env_steps=UPDATES * NUM_ENVS * UNROLL)
        target_none = agent.state.target_params is None
    finally:
        agent.close()
    return history, target_none


run()  # discarded warm-up: both measured arms run on a warm jit cache
h1, t1 = run()
h2, t2 = run()
losses_a = np.asarray([h["loss"] for h in h1])
losses_b = np.asarray([h["loss"] for h in h2])
if not np.array_equal(losses_a, losses_b):
    sys.exit(
        "replay_smoke FAILED: replay-off losses diverged across two "
        "fixed-seed runs"
    )
leaked = sorted({k for h in h1 + h2 for k in h if k in REPLAY_KEYS})
if leaked:
    sys.exit(
        f"replay_smoke FAILED: replay-off run leaked {leaked} into the "
        "window snapshot"
    )
if not (t1 and t2):
    sys.exit(
        "replay_smoke FAILED: replay-off learner carries a target "
        "network (replay-shaped state was traced with the ring off)"
    )
print(
    f"replay_smoke act 1: replay-off bit-identical across "
    f"{len(losses_a)} windows, zero replay keys, no target net"
)
with open(f"{out_dir}/identity.json", "w") as f:
    json.dump({"windows": len(losses_a)}, f)
EOF

# ---------------------------------------------------------------- act 2
# Duty cycle: replay on vs off at the SAME fixed env-step budget.
python - "$UPDATES" "$OUT_DIR" <<'EOF'
import json
import sys
import time

import numpy as np

from asyncrl_tpu import make_agent
from asyncrl_tpu.utils.config import Config

updates, out_dir = int(sys.argv[1]), sys.argv[2]
NUM_ENVS, UNROLL = 16, 8
steps = updates * NUM_ENVS * UNROLL
REPLAY_KEYS = (
    "replay_fill_frac", "reuse_p50", "reuse_p95", "target_kl",
    "learner_stall_trend",
)


def run(budget=steps, **kw):
    cfg = Config(
        env_id="CartPole-v1", algo="impala", backend="sebulba",
        host_pool="jax", num_envs=NUM_ENVS, actor_threads=1,
        unroll_len=UNROLL, precision="f32", log_every=8, seed=3,
        actor_staleness=1, **kw,
    )
    agent = make_agent(cfg)
    try:
        t0 = time.perf_counter()
        history = agent.train(total_env_steps=budget)
        elapsed = time.perf_counter() - t0
        eval_return = agent.evaluate(num_episodes=32)
    finally:
        agent.close()
    stall = float(np.mean([h["learner_stall_frac"] for h in history]))
    return history, stall, eval_return, budget / elapsed


# Discarded warm-ups for BOTH arms (each act runs in its own process,
# and the two arms compile different programs): the measured runs must
# not pay jit-compile wall time into their stall/fps accounting.
tiny = 8 * NUM_ENVS * UNROLL
run(budget=tiny)
run(budget=tiny, replay_slabs=4, replay_passes=3, target_update_period=16)
hist_off, stall_off, eval_off, fps_off = run()
hist_on, stall_on, eval_on, fps_on = run(
    replay_slabs=4, replay_passes=3, target_update_period=16
)

missing = [k for k in REPLAY_KEYS if k not in hist_on[-1]]
if missing:
    sys.exit(
        f"replay_smoke FAILED: replay-on windows are missing {missing}"
    )
if not stall_on < stall_off:
    sys.exit(
        f"replay_smoke FAILED: learner_stall_frac did not drop under "
        f"replay (off {stall_off:.3f} vs on {stall_on:.3f})"
    )
ratio = stall_off / max(stall_on, 1e-9)
if not np.isfinite(eval_on) or eval_on < 0.5 * eval_off:
    sys.exit(
        f"replay_smoke FAILED: replay-on eval return regressed beyond "
        f"noise (off {eval_off:.1f} vs on {eval_on:.1f} at {steps} env "
        "steps)"
    )
print(
    f"replay_smoke act 2: stall {stall_off:.3f} -> {stall_on:.3f} "
    f"({ratio:.2f}x reduction; acceptance target >= 2x), eval "
    f"{eval_off:.1f} -> {eval_on:.1f} at {steps} fixed env steps, "
    f"reuse_p50 {hist_on[-1]['reuse_p50']:.1f}, fill "
    f"{hist_on[-1]['replay_fill_frac']:.2f}"
)
with open(f"{out_dir}/replay.json", "w") as f:
    json.dump({
        "env_steps": steps,
        "stall_off": stall_off,
        "stall_on": stall_on,
        "stall_reduction": ratio,
        "eval_off": eval_off,
        "eval_on": eval_on,
        "fps_off": fps_off,
        "fps_on": fps_on,
        "reuse_p50": hist_on[-1]["reuse_p50"],
        "reuse_p95": hist_on[-1]["reuse_p95"],
        "replay_fill_frac": hist_on[-1]["replay_fill_frac"],
    }, f)
EOF

# -------------------------------------------------------------- summary
python - "$OUT_DIR" <<'EOF'
import json
import sys

out_dir = sys.argv[1]
replay = json.load(open(f"{out_dir}/replay.json"))
print(
    f"replay_smoke OK: stall {replay['stall_off']:.3f} -> "
    f"{replay['stall_on']:.3f} ({replay['stall_reduction']:.2f}x), eval "
    f"{replay['eval_off']:.1f} -> {replay['eval_on']:.1f}, fps "
    f"{replay['fps_off']:,.0f} -> {replay['fps_on']:,.0f}"
)
EOF
