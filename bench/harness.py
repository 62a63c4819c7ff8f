"""What every cell shares: the spec files, the device and its peaks, the
compile clock, the compile cache, seeds, per-layer metric readers and
the result line."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# JAX's persistent compilation cache, at a fixed path inside the checkout
# (the path is part of the cache key); JAX_COMPILATION_CACHE_DIR wins
CACHE_DIR = ROOT / ".jax_cache"
# profiler traces of --trace 1 runs; read, reduced and deleted in the run
TRACE_DIR = ROOT / ".bench_traces"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# -- spec ---------------------------------------------------------------------


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    end_to_end: list      # the end-to-end metric entries this cell reports
    per_layer: list       # the per-layer metric entries this cell reports


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = load_json(root / conf["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _reports(m, name, names)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


# -- device -------------------------------------------------------------------


def check_sizes(config: dict, program: dict) -> None:
    """Refuse a configuration file whose numbers differ from the ones the
    program was built with (``program``: the file's keys)."""
    bad = {k: (config[k], v) for k, v in program.items()
           if k in config and config[k] != v}
    if bad:
        raise ValueError(f"{config.get('name', 'configuration')}: file and "
                         f"program differ (file, program): {bad}")


def require_chips(n: int, platform: str = "tpu"):
    """The JAX devices of this run; raises ``NoChip`` off the accelerator
    or with fewer than ``n`` chips (there is no fallback to the CPU)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"needs a {platform}, JAX found {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"needs {n} chips, found {len(devs)}")
    return devs[:n]


def peaks(device_kind: str, path: Path = BENCH / "peaks.json") -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    table = load_json(path)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def device_record(devs) -> dict:
    d = devs[0]
    peak = 0
    for dev in devs:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def enable_compile_cache() -> str:
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


class CompileClock:
    """Seconds JAX reports for tracing, lowering, compiling and reading
    programs from the persistent cache, and the number of backend
    compiles and cache reads."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_reads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.EVENTS[0]:
            self.compiles += 1
        if event == self.EVENTS[3]:
            self.cache_reads += 1

    def mark(self) -> tuple[float, int]:
        """(seconds so far, backend compiles and cache reads so far)."""
        return self.seconds, self.compiles + self.cache_reads


# -- seeds --------------------------------------------------------------------


def seed_key(seed: int, *salt: int):
    """A PRNG key from any whole ``seed`` (more than 32 bits allowed)
    and integer salts."""
    import jax
    key = jax.random.key(0)
    s = int(seed)
    for part in (s & 0xFFFFFFFF, (s >> 32) & 0xFFFFFFFF, s < 0, *salt):
        key = jax.random.fold_in(key, int(part) & 0xFFFFFFFF)
    return key


def seed_rng(seed: int, *salt: int):
    import numpy as np
    return np.random.default_rng([abs(int(seed)), int(seed < 0), *salt])


# -- per-layer metric readers --------------------------------------------------


def metric_reader(name: str):
    """The ``read`` of ``bench/metrics/<name>.py``; a metric split by the
    cells it moves (``device_idle.train``, ``device_idle.serve``) may
    share one reader, ``bench/metrics/<name up to its first dot>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(cell: Cell, rec: dict) -> dict:
    """Run each per-layer metric's reader on the traced run's record; a
    reader that finds nothing returns None and its metric is left out."""
    out = {}
    for m in cell.per_layer:
        v = metric_reader(m["name"])(rec)
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# -- output -------------------------------------------------------------------


def info(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def finish(correct: bool, attempted: int, failed: int, metrics: dict,
           device: dict, checks: dict, breakdown: dict | None = None,
           extra: dict | None = None) -> dict:
    """Print the compared numbers beside their limits as the last lines
    on stderr, then the result line (``checks`` its last key) as the
    last line on stdout."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if extra:
        line.update(extra)
    line["checks"] = checks
    for name, c in checks.items():
        print(f"[check] {name} = {c['value']!r} (limit {c['limit']!r}, "
              f"{'ok' if c['ok'] else 'FAIL'})", file=sys.stderr,
              flush=True)
    print(json.dumps(line), flush=True)
    return line


def now() -> float:
    return time.perf_counter()
