#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print one JSON result line.

    python3 bench/run_cell.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

It loads, warms up, measures for ``--seconds``, checks the output of the
timed path against the plain reference, and prints as its last line
``{"correct", "attempted", "failed", "metrics", "device", ...,
"checks"}``: with ``--trace 0`` the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window. It exits non-zero with no result when JAX finds no TPU or fewer
chips than the cell asks for.

``--rehearse`` runs the same path on the CPU at tiny sizes (Pallas
interpreted) to check control flow and the result line's shape; its
numbers are printed under ``rehearsal`` and never as metrics.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes; prints no metrics")
    ap.add_argument("--trace-summary", default=None,
                    help="with --trace 1: also write the reduced trace "
                         "(device time per operation and program) here")
    return ap.parse_args(argv)


def main(argv=None, t_start: float = T_START) -> int:
    args = parse(argv)
    from bench import harness, tracereduce

    if not (ROOT / "src" / "repro").is_dir():
        print("run_cell: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        devs = harness.require_chips(
            cell.chips, "cpu" if args.rehearse else "tpu")
        peaks = None if args.rehearse else harness.peaks(devs[0].device_kind)
    except (harness.NoChip, KeyError) as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 2
    if not args.rehearse:
        harness.enable_compile_cache()
    clock = harness.CompileClock()
    kind = importlib.import_module(f"bench.traffic.{cell.traffic['kind']}")
    runner = importlib.import_module(f"bench.{kind.RUNNER}")
    out = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                     clock, t_start, devs, rehearse=args.rehearse)

    correct = all(c["ok"] for c in out["checks"].values())
    breakdown = None
    if args.trace:
        summary = None
        if out["trace_dir"] is not None:
            summary = tracereduce.reduce_dir(out["trace_dir"])
            shutil.rmtree(out["trace_dir"], ignore_errors=True)
        if summary is not None and args.trace_summary:
            Path(args.trace_summary).parent.mkdir(parents=True,
                                                  exist_ok=True)
            Path(args.trace_summary).write_text(json.dumps(
                {k: v for k, v in summary.items() if k != "module_events"}))
        rec = dict(out["rec"], trace=summary, peaks=peaks)
        values = harness.read_per_layer(cell, rec)
        if summary is not None:
            out["device"].update(busy_s=summary["busy_s"],
                                 window_s=summary["window_s"])
            breakdown = summary["breakdown"]
    else:
        values = {m["name"]: {"value": float(out["metrics"][m["name"]]),
                              "unit": m["unit"]}
                  for m in cell.end_to_end if m["name"] in out["metrics"]}
    extra = dict(out.get("extra", {}))
    if args.rehearse:
        extra["rehearsal"] = {k: v["value"] for k, v in values.items()}
        values = {}
    harness.finish(correct, out["attempted"], out["failed"], values,
                   out["device"], out["checks"], breakdown, extra)
    return 0


if __name__ == "__main__":
    sys.exit(main())
