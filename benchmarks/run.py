"""Benchmark harness — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (plus a header comment).

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run table2 fig5
    PYTHONPATH=src python -m benchmarks.run sync --json
    PYTHONPATH=src python -m benchmarks.run recovery --json --smoke

``--json``: modules exposing ``run_json()`` additionally contribute a
machine-readable payload, written to the module's ``JSON_PATH``
(default ``BENCH_sync.json``) — the perf trajectory files future PRs
diff against. ``--smoke``: modules whose ``run``/``run_json`` accept a
``smoke`` kwarg run at CI-sized scale.
"""
from __future__ import annotations

import inspect
import json
import sys
import traceback

MODULES = [
    ("table2", "benchmarks.table2_compute_util"),
    ("bandwidth", "benchmarks.bandwidth_reduction"),
    ("fig3", "benchmarks.fig3_allreduce_dist"),
    ("fig5", "benchmarks.fig5_resilience"),
    ("convergence", "benchmarks.convergence_diloco_vs_dp"),
    ("quant", "benchmarks.quant_quality"),
    ("kernels", "benchmarks.kernel_bench"),
    ("sync", "benchmarks.sync_bench"),
    ("recovery", "benchmarks.recovery_bench"),
    ("serve", "benchmarks.serve_bench"),
    ("rl", "benchmarks.rl_bench"),
]

JSON_PATH = "BENCH_sync.json"


def _call(fn, smoke: bool):
    if smoke and "smoke" in inspect.signature(fn).parameters:
        return fn(smoke=True)
    return fn()


def main() -> None:
    args = sys.argv[1:]
    json_mode = "--json" in args
    smoke = "--smoke" in args
    want = {a for a in args if not a.startswith("-")}
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("# name,us_per_call,derived")
    failed = []
    payloads: dict[str, dict] = {}
    for key, modname in MODULES:
        if want and key not in want:
            continue
        try:
            mod = __import__(modname, fromlist=["run"])
            if json_mode and hasattr(mod, "run_json"):
                rows, part = _call(mod.run_json, smoke)
                path = getattr(mod, "JSON_PATH", JSON_PATH)
                payloads.setdefault(path, {}).update(part)
            else:
                rows = _call(mod.run, smoke)
            for row in rows:
                print(row, flush=True)
        except Exception:
            failed.append(key)
            traceback.print_exc()
    for path, payload in payloads.items():
        with open(path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"# wrote {path}", file=sys.stderr)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
