#!/usr/bin/env bash
# Trace smoke: run a short CPU sebulba pipeline with tracing ON, validate
# the exported Perfetto JSON against the schema (python -m asyncrl_tpu.obs
# validate) and print the stall-attribution report. What tracing costs is
# the benchmark's to say (PERF.md), not a CPU run's.
#
# Usage: scripts/trace_smoke.sh                    # CPU, under a minute
#        ASYNCRL_SMOKE_UPDATES=64 scripts/trace_smoke.sh
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
UPDATES="${ASYNCRL_SMOKE_UPDATES:-24}"
RUN_DIR="$(mktemp -d /tmp/trace_smoke.XXXXXX)"
trap 'rm -rf "$RUN_DIR"' EXIT

python - "$UPDATES" "$RUN_DIR" <<'EOF'
import glob
import sys
import time

from asyncrl_tpu import make_agent
from asyncrl_tpu.configs import presets

updates = int(sys.argv[1])
run_dir = sys.argv[2]
NUM_ENVS, UNROLL = 16, 16
steps = updates * NUM_ENVS * UNROLL


def run():
    cfg = presets.get("pong_impala").replace(
        backend="sebulba", host_pool="jax", num_envs=NUM_ENVS,
        actor_threads=1, unroll_len=UNROLL, precision="f32", log_every=4,
        seed=3, hidden_sizes=(64, 64), actor_staleness=1_000_000,
        trace=True, run_dir=run_dir,
    )
    agent = make_agent(cfg)
    try:
        agent.train(total_env_steps=NUM_ENVS * UNROLL)  # jit warm-up
        t0 = time.perf_counter()
        history = agent.train(total_env_steps=NUM_ENVS * UNROLL + steps)
        elapsed = time.perf_counter() - t0
    finally:
        agent.close()
    fps = steps / elapsed
    last = history[-1]
    print(
        f"trace_smoke: fps={fps:12,.0f}  "
        f"spans={int(last.get('trace_spans', 0))}  "
        f"dropped={int(last.get('trace_dropped_spans', 0))}"
    )


run()

traces = sorted(glob.glob(f"{run_dir}/trace-*.json"))
if not traces:
    sys.exit("trace_smoke FAILED: traced run exported no trace-*.json")
print(f"trace_smoke: {len(traces)} trace export(s); validating + reporting "
      f"on {traces[-1]}")

from asyncrl_tpu.obs.__main__ import main as obs_main

if obs_main(["validate", traces[-1]]) != 0:
    sys.exit("trace_smoke FAILED: exported trace violates the schema")
if obs_main(["report", traces[-1]]) != 0:
    sys.exit("trace_smoke FAILED: obs report errored on the export")
print("trace_smoke OK: export validates and reports")
EOF
