#!/usr/bin/env bash
# Introspect smoke: A/B the training-introspection layer (ISSUE 8;
# obs/introspect.py + the loss-aux diagnostics) on/off on a tiny
# pong_impala-shaped sebulba run:
#
#   1. IDENTITY — losses must be bit-identical on a fixed seed with
#      introspection on vs off (the diagnostics are aux-only device
#      reductions; they must never perturb the update).
#   2. FUNCTION — the ON run's windows must carry the introspection keys
#      (staleness percentiles, kl, explained_variance, compiles) and the
#      OFF run's must not (off = the pre-ISSUE-8 surface).
#
# Usage: scripts/introspect_smoke.sh                  # CPU, under a minute
#        ASYNCRL_SMOKE_UPDATES=64 scripts/introspect_smoke.sh
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
UPDATES="${ASYNCRL_SMOKE_UPDATES:-24}"

python - "$UPDATES" <<'EOF'
import sys
import time

import numpy as np

from asyncrl_tpu import make_agent
from asyncrl_tpu.configs import presets

updates = int(sys.argv[1])
NUM_ENVS, UNROLL = 16, 16
steps = updates * NUM_ENVS * UNROLL

INTROSPECT_KEYS = (
    "staleness_p50", "staleness_p95", "staleness_max",
    "kl", "explained_variance", "compiles", "mem_host_rss_bytes",
)


def run(introspect: bool):
    cfg = presets.get("pong_impala").replace(
        backend="sebulba", host_pool="jax", num_envs=NUM_ENVS,
        actor_threads=1, unroll_len=UNROLL, precision="f32", log_every=4,
        seed=3, hidden_sizes=(64, 64),
        # Frozen behaviour params: losses must be seed-deterministic for
        # the identity assertion (no publish-timing race).
        actor_staleness=1_000_000,
        introspect=introspect,
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
    losses = [h["loss"] for h in history]
    last = history[-1]
    label = "introspect=on " if introspect else "introspect=off"
    print(
        f"introspect_smoke {label}: fps={fps:12,.0f}  "
        f"compiles={int(last.get('compiles', 0))}  "
        f"staleness_p95={last.get('staleness_p95', '-')}  "
        f"kl={last.get('kl', '-')}"
    )
    return losses, last


losses_off, last_off = run(False)
losses_on, last_on = run(True)

if not np.array_equal(np.asarray(losses_on), np.asarray(losses_off)):
    sys.exit(
        "introspect_smoke FAILED: introspect on/off losses diverged on a "
        "fixed seed — the diagnostics aux perturbed the update"
    )
print(f"introspect_smoke: losses identical across {len(losses_on)} windows")

missing = [k for k in INTROSPECT_KEYS if k not in last_on]
if missing:
    sys.exit(
        f"introspect_smoke FAILED: ON run's window is missing {missing}"
    )
leaked = [k for k in INTROSPECT_KEYS if k in last_off]
if leaked:
    sys.exit(
        f"introspect_smoke FAILED: OFF run's window leaked {leaked}"
    )
print("introspect_smoke OK: ON windows carry the introspection keys, "
      "OFF windows do not")
EOF
