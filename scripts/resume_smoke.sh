#!/usr/bin/env bash
# Resume smoke: the operator-facing gate for durable runs
# (asyncrl_tpu/runtime/durability.py), in three acts:
#
#   1. CONTROL — an uninterrupted run to the target (the A side of the
#      A/B; also the in-process JIT warm-up for the timed acts).
#   2. PREEMPT + RESUME — the same run in a child process is killed with
#      a real `kill -TERM` mid-train; the gate asserts the child exited
#      with the distinct EXIT_DRAINED code (86 — the drain completed and
#      the final checkpoint is durable), then resumes it via
#      ASYNCRL_RESUME=1 (the no-code-change knob) to the SAME target,
#      gating on: completion, update counters monotone across the
#      boundary, ONE continuous timeseries (second meta segment, resume
#      marker, env_steps never regressing, the drain's partial-window
#      flush stamped drain_preempt), finite losses, and /healthz — read
#      over HTTP from the live endpoint — ok at the end.
#   3. ROLLBACK — an injected nonfinite-loss burst (corrupt chaos kind)
#      must trigger the quarantine→rollback path and the run must return
#      to /healthz ok and a finite loss WITHOUT human intervention.
#
# Usage: scripts/resume_smoke.sh                  # CPU, ~3 min
#        ASYNCRL_SMOKE_UPDATES=48 scripts/resume_smoke.sh
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
# The preempt child runs from a script file in $OUT_DIR, so the repo
# root must be on sys.path explicitly (nothing installs the package).
export PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"
UPDATES="${ASYNCRL_SMOKE_UPDATES:-24}"
OUT_DIR="$(mktemp -d)"
trap 'rm -rf "$OUT_DIR"' EXIT

# ---------------------------------------------------------------- act 1
# Control: the uninterrupted A side (doubles as the JIT warm-up).
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

cfg = Config(
    env_id="CartPole-v1", algo="impala", backend="sebulba",
    host_pool="jax", num_envs=NUM_ENVS, actor_threads=2,
    unroll_len=UNROLL, precision="f32", log_every=4, seed=3,
)
agent = make_agent(cfg)
try:
    t0 = time.perf_counter()
    history = agent.train(total_env_steps=steps)
    elapsed = time.perf_counter() - t0
    if not np.isfinite(history[-1]["loss"]):
        sys.exit("resume_smoke FAILED: control run loss went non-finite")
    control = {
        "fps": steps / elapsed,
        "updates": agent._updates,
        "final_loss": float(history[-1]["loss"]),
    }
finally:
    agent.close()
with open(f"{out_dir}/control.json", "w") as f:
    json.dump(control, f)
print(f"resume_smoke: control run {control['updates']} updates, "
      f"{control['fps']:,.0f} fps")
EOF

# ---------------------------------------------------------------- act 2
# Preempt: a child process killed with a REAL SIGTERM mid-train must
# drain (exit 86), then resume to the same target.
RUN_DIR="$OUT_DIR/run"
CK_DIR="$OUT_DIR/ck"
cat > "$OUT_DIR/train_child.py" <<'EOF'
import sys

from asyncrl_tpu import make_agent
from asyncrl_tpu.utils.config import Config

steps, ck_dir, run_dir = int(sys.argv[1]), sys.argv[2], sys.argv[3]
cfg = Config(
    env_id="CartPole-v1", algo="impala", backend="sebulba",
    host_pool="jax", num_envs=16, actor_threads=2, unroll_len=8,
    precision="f32", log_every=4, seed=3,
    checkpoint_dir=ck_dir, checkpoint_every=4,
    run_dir=run_dir, obs_http_port=-1,
    # This 1-core box's scheduler noise must not degrade the verdict the
    # resumed run is gated on (the gate is about the DRAIN protocol).
    health_stall_frac=1.0, health_fps_collapse=0.0,
    drain_grace_s=60.0,
)
agent = make_agent(cfg)
try:
    agent.train(total_env_steps=steps)  # SIGTERM raises PreemptedExit
finally:
    agent.close()
print("resume_smoke child: ran to completion (was never preempted)")
EOF

STEPS=$((UPDATES * 16 * 8))
python "$OUT_DIR/train_child.py" "$STEPS" "$CK_DIR" "$RUN_DIR" &
CHILD=$!
# Kill once the run is genuinely mid-train: the first periodic
# checkpoint manifest proves updates are flowing.
DEADLINE=$((SECONDS + 240))
until compgen -G "$CK_DIR/manifest-*.json" > /dev/null; do
    if ! kill -0 "$CHILD" 2>/dev/null || ((SECONDS > DEADLINE)); then
        echo "resume_smoke FAILED: child never reached its first checkpoint"
        exit 1
    fi
    sleep 0.5
done
sleep 1
kill -TERM "$CHILD"
set +e
wait "$CHILD"
RC=$?
set -e
if [[ "$RC" != 86 ]]; then
    echo "resume_smoke FAILED: preempted child exited $RC, expected the"
    echo "EXIT_DRAINED code 86 (drain completed, final checkpoint durable)"
    exit 1
fi
echo "resume_smoke: SIGTERM'd child drained and exited 86"

# Resume via the env knob to the SAME target; gate in-process.
ASYNCRL_RESUME=1 python - "$STEPS" "$CK_DIR" "$RUN_DIR" "$OUT_DIR" <<'EOF'
import json
import sys
import time
import urllib.request

import numpy as np

from asyncrl_tpu import make_agent
from asyncrl_tpu.utils.config import Config

steps, ck_dir, run_dir, out_dir = (
    int(sys.argv[1]), sys.argv[2], sys.argv[3], sys.argv[4])
cfg = Config(
    env_id="CartPole-v1", algo="impala", backend="sebulba",
    host_pool="jax", num_envs=16, actor_threads=2, unroll_len=8,
    precision="f32", log_every=4, seed=3,
    checkpoint_dir=ck_dir, checkpoint_every=4,
    run_dir=run_dir, obs_http_port=-1,
    health_stall_frac=1.0, health_fps_collapse=0.0,
    drain_grace_s=60.0,
)
agent = make_agent(cfg)
try:
    run_state = (agent._ckpt.restore_meta or {}).get("run_state")
    if not run_state:
        sys.exit("resume_smoke FAILED: drained checkpoint carried no "
                 "run_state metadata")
    restored = int(run_state["updates"])
    if restored < 1:
        sys.exit("resume_smoke FAILED: resumed at zero updates")
    restored_env_steps = agent.env_steps
    t0 = time.perf_counter()
    history = agent.train(total_env_steps=steps)
    elapsed = time.perf_counter() - t0
    if agent.env_steps < steps:
        sys.exit("resume_smoke FAILED: resumed run stopped short of the "
                 f"target ({agent.env_steps} < {steps})")
    if agent._updates <= restored:
        sys.exit("resume_smoke FAILED: update counter did not advance "
                 "monotonically across the resume boundary")
    losses = [h["loss"] for h in history]
    if not np.all(np.isfinite(losses)):
        sys.exit("resume_smoke FAILED: non-finite loss after resume")
    url = f"http://127.0.0.1:{agent._obs.http.port}/healthz"
    verdict = json.load(urllib.request.urlopen(url, timeout=5))
    if verdict["status"] != "ok":
        sys.exit(f"resume_smoke FAILED: /healthz not ok after resume: "
                 f"{verdict}")
    resumed = {
        "fps": (steps - restored_env_steps) / elapsed,
        "updates_restored": restored,
        "updates_final": agent._updates,
        "final_loss": float(losses[-1]),
    }
finally:
    agent.close()

# One continuous timeseries: two meta segments (preempted + resumed),
# exactly one resume marker, env_steps monotone, and the drain's final
# partial-window flush stamped drain_preempt.
metas = resumes = preempt_flushes = 0
env_steps_series = []
with open(f"{run_dir}/timeseries.jsonl") as f:
    for line in f:
        doc = json.loads(line)
        if doc.get("kind") == "meta":
            metas += 1
        elif doc.get("kind") == "sample":
            window = doc["window"]
            env_steps_series.append(window.get("env_steps", 0.0))
            if window.get("drain_preempt"):
                preempt_flushes += 1
        elif (doc.get("kind") == "event"
                and doc.get("event", {}).get("event_type") == "resume"):
            resumes += 1
if metas != 2 or resumes != 1 or preempt_flushes != 1:
    sys.exit(f"resume_smoke FAILED: timeseries segments malformed "
             f"(metas={metas}, resume_markers={resumes}, "
             f"drain_flushes={preempt_flushes})")
if env_steps_series != sorted(env_steps_series):
    sys.exit("resume_smoke FAILED: env_steps regressed across the resume "
             "boundary — counters are not monotone")
print(f"resume_smoke: resumed {restored} -> {resumed['updates_final']} "
      "updates, timeseries continuous, /healthz ok")
with open(f"{out_dir}/resumed.json", "w") as f:
    json.dump(resumed, f)
EOF

# ---------------------------------------------------------------- act 3
# Rollback: an injected nonfinite-loss burst must quarantine, roll back
# to the last-good checkpoint, and return to /healthz ok on its own.
python - "$UPDATES" "$OUT_DIR" <<'EOF'
import json
import sys
import urllib.request

import numpy as np

from asyncrl_tpu import make_agent
from asyncrl_tpu.obs import registry as obs_registry
from asyncrl_tpu.utils.config import Config

updates, out_dir = int(sys.argv[1]), sys.argv[2]
NUM_ENVS, UNROLL = 16, 4
steps = max(updates, 26) * NUM_ENVS * UNROLL

cfg = Config(
    env_id="CartPole-v1", algo="a3c", backend="sebulba",
    host_pool="jax", num_envs=NUM_ENVS, actor_threads=2,
    unroll_len=UNROLL, precision="f32", log_every=2, seed=3,
    checkpoint_dir=f"{out_dir}/rollback_ck", checkpoint_every=2,
    rollback_bad_windows=2, rollback_max_attempts=3,
    obs_http_port=-1, health_stall_frac=1.0, health_fps_collapse=0.0,
    fault_spec="actor.queue_put:corrupt:1.0:0:max=12,after=16",
)
agent = make_agent(cfg)
try:
    history = agent.train(total_env_steps=steps)
    last = history[-1]
    restores = obs_registry.counter("rollback_restores").value()
    quarantines = obs_registry.counter("rollback_quarantine").value()
    skips = last.get("nonfinite_skips", 0.0)
    if restores < 1:
        sys.exit("resume_smoke FAILED: injected divergence never rolled "
                 "back")
    if quarantines < 1:
        sys.exit("resume_smoke FAILED: divergence was not quarantined "
                 "before the rollback")
    if skips < 1:
        sys.exit("resume_smoke FAILED: the NaN-guard never skipped a "
                 "poisoned update")
    if not np.isfinite(last["loss"]):
        sys.exit("resume_smoke FAILED: loss still non-finite after the "
                 "rollback recovered")
    url = f"http://127.0.0.1:{agent._obs.http.port}/healthz"
    verdict = json.load(urllib.request.urlopen(url, timeout=5))
    if verdict["status"] != "ok":
        sys.exit(f"resume_smoke FAILED: /healthz did not recover after "
                 f"the rollback: {verdict}")
    print(f"resume_smoke: rollback probe — {int(restores)} restore(s), "
          f"{int(skips)} NaN-guard skip(s), /healthz ok")
    rollback = {"restores": int(restores), "nan_skips": int(skips)}
finally:
    agent.close()
with open(f"{out_dir}/rollback.json", "w") as f:
    json.dump(rollback, f)
EOF

# -------------------------------------------------------------- summary
python - "$OUT_DIR" <<'EOF'
import json
import sys

out_dir = sys.argv[1]
control = json.load(open(f"{out_dir}/control.json"))
resumed = json.load(open(f"{out_dir}/resumed.json"))
rollback = json.load(open(f"{out_dir}/rollback.json"))
print(
    f"resume_smoke OK: control {control['fps']:,.0f} fps / "
    f"{control['updates']} updates; preempted run resumed "
    f"{resumed['updates_restored']} -> {resumed['updates_final']} updates; "
    f"rollback probe {rollback['restores']} restore(s)"
)
EOF
