"""Run one cell of the benchmark once and print one line of JSON.

    python3 -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. Everything that belongs to one cell is data found by name: the
cell in ``BENCHMARK.json``; its configuration in ``configs/<name>.json``
(which names the measuring loop, ``loops/<loop>.py``); its traffic mix in
``traffic/<name>.json``; each per-layer metric in
``layer_metrics/<name>.json`` (with a reader of its own in ``<name>.py``
where it needs one). Nothing here branches on what identifies a cell.

With ``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics and a breakdown of the traced window;
last in it, where the cell's loop gives them, the readings ``correct`` was
decided by, each beside its limit (``compared``).
"""

from __future__ import annotations

import argparse
import functools
import importlib
import importlib.util
import json
import math
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
OUT_DIR = os.path.join(BENCH_DIR, "_out")  # git-ignored: traces, run dirs
TRACE_SECONDS = 3.0  # of the window, in a traced run


def process_start() -> float:
    """``perf_counter`` reading at which this process started (from
    /proc, so the interpreter's own start-up counts as set-up)."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    started = ticks / os.sysconf("SC_CLK_TCK")
    age = time.clock_gettime(time.CLOCK_BOOTTIME) - started
    return time.perf_counter() - age


class Spec:
    """``BENCHMARK.json`` and the data files it names. ``data_roots`` are
    searched in order, so a test can bring files of its own."""

    def __init__(self, spec_file: str, data_roots: list[str]):
        with open(spec_file) as f:
            self.doc = json.load(f)
        self.data_roots = data_roots

    def find(self, kind: str, name: str, ext: str = ".json") -> str | None:
        for root in self.data_roots:
            path = os.path.join(root, kind, name + ext)
            if os.path.exists(path):
                return path
        return None

    def load(self, kind: str, name: str) -> dict:
        path = self.find(kind, name)
        if path is None:
            raise SystemExit(
                f"benchmarks: no {kind}/{name}.json under {self.data_roots}"
            )
        with open(path) as f:
            return json.load(f)

    def cell(self, name: str) -> dict:
        for cell in self.doc["workloads"]:
            if cell["name"] == name:
                return cell
        raise SystemExit(
            f"benchmarks: no workload {name!r} in BENCHMARK.json; have "
            f"{[c['name'] for c in self.doc['workloads']]}"
        )

    def metrics_of(self, section: str, cell: str) -> list[dict]:
        return [
            m for m in self.doc[section]
            if "workloads" not in m or cell in m["workloads"]
        ]

    def reader(self, name: str):
        """The metric's reader and its parameters."""
        doc = self.load("layer_metrics", name)
        own = self.find("layer_metrics", name, ".py")
        if own is not None:
            spec = importlib.util.spec_from_file_location(
                f"benchmarks_layer_metric_{name}", own
            )
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module.read, doc.get("params", {})
        from benchmarks import readers

        return getattr(readers, doc["reader"]), doc.get("params", {})


def program_config(config_doc: dict, traffic_doc: dict, seed: int,
                   extra: dict | None = None):
    """The program's ``Config``: the preset, then the configuration's, the
    mix's and the run's overrides, then the seed."""
    from asyncrl_tpu.configs import presets

    cfg = presets.get(config_doc["preset"])
    overrides = {
        **config_doc.get("overrides", {}),
        **traffic_doc.get("overrides", {}),
        **(extra or {}),
    }
    overrides = {
        k: tuple(v) if isinstance(v, list) else v for k, v in overrides.items()
    }
    return cfg.replace(**overrides, seed=seed)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--spec", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--data-root", action="append", default=[],
                    help="searched before benchmarks/ for data files")
    args = ap.parse_args(argv)
    t_process = process_start()

    spec = Spec(args.spec, [*args.data_root, BENCH_DIR])
    cell = spec.cell(args.workload)
    config_doc = spec.load("configs", cell["config"])
    traffic_doc = spec.load("traffic", cell["traffic"])

    from benchmarks import device

    dev = device.require_chips(cell["chips"])
    loop = importlib.import_module(f"benchmarks.loops.{config_doc['loop']}")
    out_dir = os.path.join(OUT_DIR, cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    result = loop.run(
        cell=cell, config_doc=config_doc, traffic_doc=traffic_doc,
        make_config=functools.partial(
            program_config, config_doc, traffic_doc, args.seed
        ),
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        trace_seconds=min(TRACE_SECONDS, args.seconds), out_dir=out_dir,
        dev=dev, t_process=t_process,
    )

    wanted = spec.metrics_of(
        "per_layer" if args.trace else "end_to_end", cell["name"]
    )
    metrics = {}
    if args.trace:
        ev = result["evidence"]
        ev["peaks"] = device.peaks(dev["kind"]) if ev.get("trace") else {}
        for m in wanted:
            read, params = spec.reader(m["name"])
            value = read(ev, **params)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in wanted:
            if m["name"] in result["end_to_end"]:
                metrics[m["name"]] = {
                    "value": result["end_to_end"][m["name"]], "unit": m["unit"]
                }

    device_out = {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"],
        "memory_peak_bytes": device.memory_peak_bytes(),
    }
    line = {
        "correct": bool(result["correct"]),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": metrics,
        "device": device_out,
    }
    if args.trace and result["evidence"].get("trace") is not None:
        trace = result["evidence"]["trace"]
        device_out["busy_s"] = trace.busy_s
        device_out["window_s"] = trace.window_s
        line["breakdown"] = {
            "device_ops": [list(x) for x in trace.devices[0].top_ops(10)],
            "idle_gaps": [list(x) for x in trace.idle_by_annotation(10)],
        }
    for reason in result.get("reasons", []):
        print(f"benchmarks: not correct: {reason}", file=sys.stderr)
    # what a loop compared, each reading beside its limit: the last lines on
    # stderr, and the line's last key (a loop that gives none adds none); a
    # reading that is not finite goes as text, so the line stays JSON
    compared = {
        name: [x if math.isfinite(x) else repr(x) for x in pair]
        for name, pair in result.get("compared", {}).items()
    }
    for name, (value, limit) in compared.items():
        print(f"benchmarks: compared {name}: {value!r} (limit {limit!r})",
              file=sys.stderr)
    if compared:
        line["compared"] = compared
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
