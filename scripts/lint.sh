#!/usr/bin/env bash
# Lint gate for asyncrl-tpu: ruff (curated rule set in pyproject.toml)
# plus the framework-aware static passes (python -m asyncrl_tpu.analysis:
# lock discipline, JAX purity, donation safety, thread ownership,
# deadlock/lock-order, device contracts, config contracts, protocol
# typestate, async-signal safety, SPMD sharding contracts, multi-host
# collective congruence, Pallas DMA discipline, deadline flow, token
# refund, time-unit soundness, lockset race detection). The default
# package run covers EVERY
# subpackage — asyncrl_tpu/obs/ (span rings, flight recorder) included,
# so its guarded-by/thread-entry annotations gate like the rest of the
# concurrency substrate. Focused gates beyond the package run live in
# the GATES manifest below — one loop, no hand-maintained command
# blocks: the entry points (scripts/*.py, chip_smoke.py, __graft_entry__.py)
# under configflow + the SPMD passes + the wire-budget trio (a smoke
# script that sleeps a millisecond value or drops a deadline guard gates
# here), and the serve/kernel files whose gating must survive any future
# package file-set edit.
#
#   scripts/lint.sh            # lint the package + script entries (CI gate)
#   scripts/lint.sh --fast     # warm-cache mode: a full analyzer cache hit
#                              # replays the manifest AND skips the ruff
#                              # re-run — the gate stays sub-second on an
#                              # unchanged tree (the verify skill's loop).
#                              # The skip keys on the PACKAGE manifest, so
#                              # ruff findings in tests/, scripts/, or
#                              # chip_smoke.py edits are deferred to the
#                              # next full run — CI uses plain lint.sh.
#   scripts/lint.sh path.py    # lint specific files (fixtures exit nonzero)
#
# The package run is incremental (--cache-dir .analysis-cache: a second
# consecutive run with no edits replays the manifest without re-parsing)
# and machine-readable (--format json into lint_report.json, stable
# finding IDs). The scripts run caches separately
# (.analysis-cache-scripts): manifests key on the pass tuple, so sharing
# one cache dir would invalidate both manifests every run. Both runs exit
# nonzero on any finding NOT grandfathered in
# asyncrl_tpu/analysis/baseline.json — new findings gate PRs while
# baselined ones burn down explicitly. ruff is optional at runtime (not
# vendored in the training image); the analysis passes always run and
# always gate.
set -u
cd "$(dirname "$0")/.."

fast=0
if [ "${1:-}" = "--fast" ]; then
    fast=1
    shift
fi

run_ruff() {
    if command -v ruff >/dev/null 2>&1; then
        ruff check asyncrl_tpu tests scripts chip_smoke.py || rc=1
    elif python -c "import ruff" >/dev/null 2>&1; then
        python -m ruff check asyncrl_tpu tests scripts chip_smoke.py || rc=1
    else
        echo "lint.sh: ruff not installed; skipping ruff (analysis passes still gate)" >&2
    fi
}

rc=0
if [ "$#" -gt 0 ]; then
    # Explicit paths: plain text, no cache (fixture runs must not pollute
    # or consult the package manifest).
    run_ruff
    python -m asyncrl_tpu.analysis "$@" || rc=1
    exit $rc
fi

python -m asyncrl_tpu.analysis \
    --cache-dir .analysis-cache \
    --format json --stats \
    > lint_report.json || rc=1

# The race pass must have RUN on the package and found nothing: a
# report where the `races` key is missing means the pass silently fell
# out of the run (a regression the zero-findings exit code would hide).
python - <<'EOF' || rc=1
import json
import sys

with open("lint_report.json") as fh:
    per_pass = json.load(fh)["stats"]["findings_per_pass"]
if per_pass.get("races") != 0:
    print(
        "lint.sh: expected findings_per_pass['races'] == 0, got "
        f"{per_pass.get('races')!r}", file=sys.stderr,
    )
    sys.exit(1)
EOF

# Focused gates, ONE manifest: "name|passes|paths". Each entry gets its
# own cache dir (.analysis-cache-<name>) because manifests key on the
# (file set, pass tuple) pair — sharing a dir would invalidate both
# manifests on every run (the PR-11 scripts-manifest lesson).
#
# - scripts: every repo entry point under configflow (CFG003: smoke
#   scripts can't invent unregistered ASYNCRL_* env vars), the SPMD
#   passes (a launch script that builds its mesh before
#   jax.distributed.initialize, or an unpaired DMA — HSY002/PAL001 and
#   friends), the wire-budget trio (deadline flow, token refund,
#   time-unit soundness: a script that feeds an ms value to time.sleep
#   gates here), and the race pass (a script that spawns a bare
#   Thread against undeclared shared state gates here).
# - fleet: the replicated serving tier is lease-protocol and lock-order
#   critical (held serve-stale anchors, replica rebuild under the fleet
#   tick, the probe/readmit typestate) — gated explicitly so a future
#   baseline or package file-set edit can never silently un-gate it.
# - kernels: the PR-17 device hot path contracts (Pallas DMA start/wait
#   in the scan kernels, the devq-lease typestate in the HBM rollout
#   queue), explicit for the same un-gating reason.
# - requests: the request hop journal's budget arithmetic (deadline flow
#   into budget_remaining_ms, ms-vs-s unit soundness, the rate-token
#   refund protocol its gateway call sites participate in) — gated
#   explicitly so the wire-tracing layer can never silently drift out of
#   the deadline/refund contract set.
GATES=(
    "scripts|configflow,sharding,hostsync,pallas,deadlines,refund,units,races|scripts/*.py chip_smoke.py __graft_entry__.py"
    "fleet|protocols,deadlock|asyncrl_tpu/serve/fleet.py"
    "kernels|pallas,sharding,protocols|asyncrl_tpu/ops/pallas_scan.py asyncrl_tpu/ops/max_pool.py asyncrl_tpu/rollout/device_queue.py"
    "requests|deadlines,refund,units,protocols|asyncrl_tpu/obs/requests.py"
)
for gate in "${GATES[@]}"; do
    name="${gate%%|*}"
    rest="${gate#*|}"
    passes="${rest%%|*}"
    paths="${rest#*|}"
    pass_args=()
    for p in ${passes//,/ }; do
        pass_args+=(--pass "$p")
    done
    # $paths is a glob-bearing word list on purpose (scripts/*.py).
    # shellcheck disable=SC2086
    python -m asyncrl_tpu.analysis "${pass_args[@]}" \
        --cache-dir ".analysis-cache-$name" $paths || rc=1
done

if [ "$fast" -eq 1 ] && [ "$rc" -eq 0 ] && python - <<'EOF'
import json
import sys

try:
    with open("lint_report.json") as fh:
        stats = json.load(fh)["stats"]
except Exception:
    sys.exit(1)
sys.exit(0 if stats.get("cache") == "warm" else 1)
EOF
then
    echo "lint.sh: --fast analyzer cache warm; skipping ruff re-run" >&2
else
    run_ruff
fi
exit $rc
