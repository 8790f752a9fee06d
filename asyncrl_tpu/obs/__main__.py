"""CLI: ``python -m asyncrl_tpu.obs <report|validate|doctor> ...``.

``report`` prints the per-stage time shares, wait-vs-compute breakdown,
and stall-attribution table for an exported trace (``trace-*.json``) or a
flight-recorder dump (``flightrec-*.json`` — its embedded ``trace``
section is analyzed). ``validate`` checks the trace_event schema
(``obs.export.validate_trace``) and exits 1 on any violation — the gate
``scripts/trace_smoke.sh`` runs. ``doctor`` replays a recorded run_dir's
timeseries + forensics into a health report (detector timeline,
bottleneck attribution) and exits 1 when a detector fired — the gate
``scripts/health_smoke.sh`` runs.
``explain`` renders request hop journals from a run_dir's
``requests.jsonl`` as budget waterfalls — one journal by trace id, or
the ``--worst N`` set (non-200 verdicts first, then by latency); exits 2
when the file or the trace id is missing.
"""

from __future__ import annotations

import argparse
import json
import sys

from asyncrl_tpu.obs import doctor as doctor_mod
from asyncrl_tpu.obs import export as export_mod
from asyncrl_tpu.obs import flightrec, report
from asyncrl_tpu.obs import requests as requests_mod


def _load_trace_doc(path: str) -> tuple[dict, bool]:
    """(trace document, came-from-flightrec) for ``path``."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except OSError as e:
        raise SystemExit(f"{path}: cannot read trace file — {e}")
    except json.JSONDecodeError as e:
        raise SystemExit(f"{path}: not valid JSON — {e}")
    if isinstance(doc, dict) and doc.get("schema") == flightrec.SCHEMA:
        trace_doc = doc.get("trace")
        if not trace_doc:
            raise SystemExit(
                f"{path}: flight-recorder dump has no trace section "
                "(tracing was disabled when it was recorded)"
            )
        return trace_doc, True
    return doc, False


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m asyncrl_tpu.obs",
        description="pipeline-trace reporting and schema validation",
    )
    sub = parser.add_subparsers(dest="cmd", required=True)
    p_report = sub.add_parser(
        "report",
        help="per-stage time shares + stall attribution for a trace or "
        "flight-recorder JSON",
    )
    p_report.add_argument("file", help="trace-*.json or flightrec-*.json")
    p_validate = sub.add_parser(
        "validate", help="validate a trace export against the schema"
    )
    p_validate.add_argument("file", help="trace-*.json or flightrec-*.json")
    p_doctor = sub.add_parser(
        "doctor",
        help="offline run-health report for a recorded run_dir "
        "(detector timeline + bottleneck attribution; exits 1 when a "
        "detector fired)",
    )
    p_doctor.add_argument(
        "run_dir", help="run directory holding timeseries.jsonl"
    )
    p_explain = sub.add_parser(
        "explain",
        help="request budget waterfalls from a run_dir's requests.jsonl "
        "(one trace id, or --worst N; exits 2 when missing)",
    )
    p_explain.add_argument(
        "trace_id", nargs="?", default=None,
        help="wire trace id (X-Trace-Id) of the journal to render; omit "
        "with --worst to rank instead",
    )
    p_explain.add_argument(
        "run_dir", help="run directory holding requests.jsonl"
    )
    p_explain.add_argument(
        "--worst", type=int, default=0,
        help="render the N worst journals (non-200 first, then by "
        "latency) instead of one trace id",
    )
    args = parser.parse_args(argv)

    if args.cmd == "explain":
        text, code = requests_mod.explain(
            args.run_dir, trace_id=args.trace_id, worst=args.worst
        )
        print(text, file=sys.stderr if code == 2 else sys.stdout)
        return code

    if args.cmd == "doctor":
        text, code = doctor_mod.diagnose(args.run_dir)
        print(text, file=sys.stderr if code == 2 else sys.stdout)
        return code

    doc, from_flightrec = _load_trace_doc(args.file)
    if args.cmd == "validate":
        # A flight dump with a quiet lookback window legitimately holds
        # zero spans; only a full run export must contain them.
        errors = export_mod.validate_trace(
            doc, require_spans=not from_flightrec
        )
        for err in errors:
            print(f"{args.file}: {err}", file=sys.stderr)
        if errors:
            print(
                f"{args.file}: INVALID ({len(errors)} schema violation(s))",
                file=sys.stderr,
            )
            return 1
        events = len(doc.get("traceEvents", []))
        print(f"{args.file}: valid {export_mod.SCHEMA} ({events} events)")
        return 0

    print(report.render(report.analyze(doc)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
