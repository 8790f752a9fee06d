"""Local run log: every measuring entry point and smoke script appends its
timestamped row to ``BENCH_HISTORY.json`` at the repo root (git-ignored;
created on first write) so ``obs doctor`` can compare a run against this
machine's earlier ones. It is not the speed record — that is the driver's
``PERF_LEDGER.jsonl`` — and nothing reads a number back out of it into a
benchmark result.

Record schema (one JSON object per entry, newest last):

    {
      "ts": "2026-07-30T12:34:56Z",     # UTC capture time
      "kind": "throughput" | "time_to_target" | "roofline"
              | "kernel_validation"   # real-chip kernel gate (validate_pallas_tpu)
              | "experiment"          # A/B arms (e.g. selfplay_vs_direct)
              | "diagnosis"           # checkpoint play analysis (pong_diagnose;
                                      # carries analysis_platform, not device
                                      # fields — the analysis host is not the
                                      # training hardware)
              | "feasibility",        # target-reachability probe (pong_oracle;
                                      # analysis_platform likewise)
      "preset": "pong_impala",
      "platform": "tpu" | "cpu",
      "device_kind": "TPU v5 lite",
      "device_count": 1,
      "captured_by": "harness" | "manual",  # provenance (VERDICT r2 Weak #1):
            # "harness" = written by a benchmark entry point from a live
            # measurement in the same process; "manual" = backfilled by hand
            # from secondary evidence (commit messages, logs). Manual entries
            # are history, never headline material.
      ... kind-specific fields (fps / geometry, or target / seconds) ...
    }

The file is a plain JSON list so anyone can read it directly; writes are
atomic (tmp + rename) so a crashed run can't truncate history.
"""

from __future__ import annotations

import datetime
import json
import os
import tempfile

# bench.py sits at the repo root; this module at <root>/asyncrl_tpu/utils/.
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
HISTORY_PATH = os.path.join(_REPO_ROOT, "BENCH_HISTORY.json")


def _default_path() -> str:
    """Ledger path, resolved at CALL time: ASYNCRL_BENCH_HISTORY redirects
    every read/write — for tests and for validation/smoke runs whose rows
    must not mix into the log a later ``obs doctor`` compares against.
    Read per call, not at import, so
    setting the variable after an early `import bench` still redirects."""
    return os.environ.get("ASYNCRL_BENCH_HISTORY") or HISTORY_PATH


def _utc_now_iso() -> str:
    return (
        datetime.datetime.now(datetime.timezone.utc)
        .replace(microsecond=0)
        .isoformat()
        .replace("+00:00", "Z")
    )


def load(path: str | None = None) -> list[dict]:
    path = path or _default_path()
    try:
        with open(path) as f:
            entries = json.load(f)
    except (OSError, json.JSONDecodeError):
        return []
    return entries if isinstance(entries, list) else []


def record(entry: dict, path: str | None = None) -> dict:
    """Append ``entry`` (stamped with UTC time and, unless the caller says
    otherwise, ``captured_by="harness"`` — this function runs inside the
    measuring process) to the history file."""
    path = path or _default_path()
    stamped = {"ts": _utc_now_iso(), "captured_by": "harness", **entry}
    entries = load(path) + [stamped]
    fd, tmp = tempfile.mkstemp(
        dir=os.path.dirname(path), prefix=".bench_history_"
    )
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(entries, f, indent=1)
            f.write("\n")
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return stamped


def device_entry() -> dict:
    """Platform/device fields for the current JAX backend."""
    import jax

    d = jax.devices()[0]
    return {
        "platform": d.platform,
        "device_kind": d.device_kind,
        "device_count": jax.device_count(),
    }


NORTH_STAR_FPS = 1_000_000.0  # BASELINE.json:5 (v4-8 target)


def record_throughput(preset: str, cfg, fps: float) -> dict | None:
    """Shared throughput-record schema for bench.py / bench_matrix.py —
    one copy, so the baseline constant and field set can never drift.
    Returns the stamped entry, or None if the ledger was unwritable (a
    read-only checkout must not kill a benchmark that already ran)."""
    import sys

    entry = {
        "kind": "throughput",
        "preset": preset,
        **device_entry(),
        "num_envs": cfg.num_envs,
        "unroll_len": cfg.unroll_len,
        "updates_per_call": cfg.updates_per_call,
        "frames_per_sec": round(fps),
        "vs_baseline": round(fps / NORTH_STAR_FPS, 3),
    }
    try:
        return record(entry)
    except OSError as e:
        print(f"bench_history: could not persist: {e}", file=sys.stderr)
        return None
