#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, taken on the chip
at each cell's own size, in one process (programs compile once).

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--controls fp8] \
        [--faults half_batch,no_exchange] \
        [--seconds 20] [--out FILE]

For each of ``--seeds``: the program's numbers against the float32
reference, through the same path a benchmark run takes (a serving cell
runs a window of ``--seconds`` at the cell's own load first). For each
of ``--control-seeds``: the controls' numbers (the reference in the
program's place at each of ``--controls``, ``fp8`` or ``int8``) and, for
a training cell, those of the planted
``--faults`` (``half_batch``: half of each row left out of the loss;
``no_exchange``: the exchange between workers left out). Prints one JSON line per seed and writes them all to
``--out``. The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default="fp8")
    ap.add_argument("--faults", default="half_batch,no_exchange")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from bench import harness, serve_cell, train_cell
    cell = harness.load_cell(args.workload)
    try:
        devs = harness.require_chips(
            cell.chips, "cpu" if args.rehearse else "tpu")
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    if not args.rehearse:
        harness.enable_compile_cache()
    clock = harness.CompileClock()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    train = cell.traffic["kind"] == "diloco_train"
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps({k: v for k, v in row.items() if k != "detail"}),
              flush=True)

    for seed in seeds:
        t0 = time.perf_counter()
        if train:
            out = train_cell.run(cell, seed, 0.0, False, clock,
                                 time.perf_counter(), devs,
                                 rehearse=args.rehearse)
        else:
            out = serve_cell.run(cell, seed, args.seconds, False, clock,
                                 time.perf_counter(), devs,
                                 rehearse=args.rehearse,
                                 precisions=("f32", "fp8"))
        gc.collect()
        emit({"seed": seed, "kind": "program", "numbers": out["numbers"],
              "extra": out.get("extra", {}), "detail": out.get("detail"),
              "seconds": time.perf_counter() - t0})
    for seed in controls:
        t0 = time.perf_counter()
        if train:
            variants = [(c, None) for c in args.controls.split(",") if c] \
                + [("f32", f) for f in args.faults.split(",") if f]
            got = train_cell.control_readings(cell, seed, devs,
                                              rehearse=args.rehearse,
                                              variants=variants)
            for name, numbers in got.items():
                detail = numbers.pop("detail", None)
                emit({"seed": seed, "kind": name, "numbers": numbers,
                      "detail": detail,
                      "seconds": time.perf_counter() - t0})
        gc.collect()
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
