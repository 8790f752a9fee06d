#!/usr/bin/env bash
# Serve smoke: the continuous-batching serve core (config.serve=True,
# asyncrl_tpu/serve/) on a short CPU sebulba run. Whether it is faster
# than the legacy InferenceServer is a cell's to say (ROADMAP A2, C3).
# Gates:
#   - latency: the serve core's p95 serve latency must stay within
#     ASYNCRL_SERVE_P95_MS (default 250 ms — generous for a shared CI
#     box; tighten on real serving hardware),
#   - liveness: the serve run must export p50/p95/p99 latency and at
#     least one dispatch through the metrics window.
#
# Usage: scripts/serve_smoke.sh                    # CPU, under a minute
#        ASYNCRL_SMOKE_UPDATES=64 scripts/serve_smoke.sh
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
UPDATES="${ASYNCRL_SMOKE_UPDATES:-24}"
P95_BUDGET_MS="${ASYNCRL_SERVE_P95_MS:-250}"

python - "$UPDATES" "$P95_BUDGET_MS" <<'EOF'
import sys
import time

from asyncrl_tpu import make_agent
from asyncrl_tpu.configs import presets

updates = int(sys.argv[1])
p95_budget_ms = float(sys.argv[2])
NUM_ENVS, UNROLL, THREADS = 16, 16, 2
steps = updates * NUM_ENVS * UNROLL


def run():
    cfg = presets.get("pong_impala").replace(
        backend="sebulba", host_pool="jax", num_envs=NUM_ENVS,
        actor_threads=THREADS, unroll_len=UNROLL, precision="f32",
        log_every=4, seed=3, hidden_sizes=(64, 64),
        actor_staleness=1_000_000, inference_server=True, serve=True,
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
    lat = {
        q: float(last.get(f"serve_latency_ms_{q}", 0.0))
        for q in ("p50", "p95", "p99")
    }
    print(
        f"serve_smoke serve-core: fps={fps:12,.0f}  "
        f"p50={lat['p50']:.1f}ms p95={lat['p95']:.1f}ms "
        f"p99={lat['p99']:.1f}ms  "
        f"dispatch_full={int(last.get('serve_dispatch_full', 0))} "
        f"deadline={int(last.get('serve_dispatch_deadline', 0))}"
    )
    return last, lat


last_serve, lat = run()

# Liveness gate: the serve run must have exported the latency taxonomy
# and dispatched through the continuous-batching scheduler.
for key in ("serve_latency_ms_p50", "serve_latency_ms_p95",
            "serve_latency_ms_p99"):
    if key not in last_serve:
        sys.exit(f"serve_smoke FAILED: {key} missing from metrics window")
dispatches = last_serve.get("serve_dispatch_full", 0) + last_serve.get(
    "serve_dispatch_deadline", 0
)
if dispatches <= 0:
    sys.exit("serve_smoke FAILED: serve core recorded no dispatches")

if lat["p95"] > p95_budget_ms:
    sys.exit(
        f"serve_smoke FAILED: p95 serve latency {lat['p95']:.1f}ms over "
        f"budget {p95_budget_ms:.0f}ms"
    )
print(
    f"serve_smoke OK: p95 {lat['p95']:.1f}ms <= {p95_budget_ms:.0f}ms"
)
EOF
