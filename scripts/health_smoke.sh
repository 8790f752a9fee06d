#!/usr/bin/env bash
# Health smoke: the operator-facing gate for the run-health telemetry
# layer (obs/timeseries.py, obs/health.py, obs/http.py, obs doctor).
#
# Two checks, driven through the public config surface the way a
# cluster health probe would drive it:
#
#   1. LIVE DEGRADE/RECOVER — a short traced run with a crash storm
#      injected via utils/faults.py (both actors' first step) and the
#      exposition endpoint on an ephemeral port: /healthz must answer
#      503/degraded-or-critical while the storm is inside the verdict
#      TTL and 200/ok again after it ages out; /metrics must scrape in
#      Prometheus format mid-run.
#   2. DOCTOR CLEAN — `python -m asyncrl_tpu.obs doctor` over a clean
#      recorded run_dir: must exit 0 and say CLEAN.
#
# Usage: scripts/health_smoke.sh                    # CPU, ~1-2 min
#        ASYNCRL_SMOKE_UPDATES=64 scripts/health_smoke.sh
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
UPDATES="${ASYNCRL_SMOKE_UPDATES:-24}"
WORK_DIR="$(mktemp -d /tmp/health_smoke.XXXXXX)"
trap 'rm -rf "$WORK_DIR"' EXIT

python - "$UPDATES" "$WORK_DIR" <<'EOF'
import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

from asyncrl_tpu import make_agent
from asyncrl_tpu.utils.config import Config

updates = int(sys.argv[1])
work_dir = sys.argv[2]


def get(url):
    try:
        with urllib.request.urlopen(url, timeout=5) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def run(run_dir, fault_spec, scrape):
    cfg = Config(
        env_id="CartPole-v1", algo="a3c", backend="sebulba",
        host_pool="jax", num_envs=16, actor_threads=2, unroll_len=4,
        precision="f32", log_every=2, seed=7,
        trace=True, run_dir=run_dir, obs_http_port=(-1 if scrape else 0),
        # The degrade/recover assertion is about the CRASH STORM verdict;
        # this run's windows are ~100ms, where a scheduler hiccup halves
        # fps and fires the (orthogonal) fps_collapse detector — a dip
        # landing on the final windows read as "never recovered". 0
        # disables that one detector so the gate tests what it claims.
        health_fps_collapse=0.0,
        health_window_ttl=2, fault_spec=fault_spec,
    )
    agent = make_agent(cfg)
    statuses = []

    def cb(window):
        if not scrape:
            return
        base = f"http://127.0.0.1:{agent._obs.http.port}"
        code, body = get(f"{base}/healthz")
        statuses.append((code, json.loads(body)["status"]))
        if len(statuses) == 1:
            code, body = get(f"{base}/metrics")
            assert code == 200 and b"# TYPE asyncrl_fps gauge" in body, (
                "health_smoke FAILED: /metrics did not scrape in "
                "Prometheus format"
            )

    steps = updates * 16 * 4
    try:
        history = agent.train(total_env_steps=steps, callback=cb)
    finally:
        agent.close()
    return history, statuses


# --- 1. live degrade/recover under an injected crash storm -----------
faulted_dir = os.path.join(work_dir, "faulted")
history, statuses = run(
    faulted_dir, "actor.step:crash:1:0:max=2", scrape=True
)
print(f"health_smoke: /healthz over {len(statuses)} windows: "
      f"{[s for _, s in statuses]}")
bad = [i for i, (code, s) in enumerate(statuses) if s != "ok"]
if not bad:
    sys.exit(
        "health_smoke FAILED: /healthz never degraded under the "
        "injected crash storm"
    )
if statuses[bad[0]][0] != 503:
    sys.exit("health_smoke FAILED: degraded verdict did not answer 503")
if not any(s == "ok" for code, s in statuses[bad[-1] + 1:]):
    sys.exit(
        "health_smoke FAILED: /healthz never recovered after the storm "
        f"aged out (statuses {statuses})"
    )
if not history[0].get("health_events"):
    sys.exit(
        "health_smoke FAILED: the storm window's sample carries no "
        "health_events (shared-snapshot drift?)"
    )
print("health_smoke: live degrade/recover OK "
      f"(degraded windows {bad}, recovered after)")

# --- 2. doctor over a clean run ---------------------------------------
clean_dir = os.path.join(work_dir, "clean")
history, _ = run(clean_dir, "", scrape=False)
run_fps = max(w["fps"] for w in history)


proc = subprocess.run(
    [sys.executable, "-m", "asyncrl_tpu.obs", "doctor", clean_dir],
    capture_output=True, text=True,
)
print(f"health_smoke: doctor (clean run) rc={proc.returncode}")
sys.stdout.write(proc.stdout)
sys.stderr.write(proc.stderr)
if proc.returncode != 0 or "CLEAN" not in proc.stdout:
    sys.exit("health_smoke FAILED: doctor flagged a clean run")

print(f"health_smoke OK: degrade/recover + doctor verdict "
      f"(clean fps {run_fps:,.0f})")
EOF
