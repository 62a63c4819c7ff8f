"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

``extract`` turns ``jax.profiler.ProfileData`` into plain event lists:
per device, its operations (line ``XLA Ops``) and its programs (line
``XLA Modules``), and the host's spans. ``summarize`` reduces those over
the measured window (the host span ``bench.window``): busy time as the
union of operation intervals, device time per operation name (of the
operations that contain no other: a loop's own event is left out) and
per program, the idle gaps, and the breakdown of the top operations and the
longest gaps, each gap labelled by what the host was doing in it (the
innermost ``bench.*`` span and the innermost other host event around
the gap's middle). Times are in seconds, averaged over the devices.
"""
from __future__ import annotations

import re
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
_MODULE_ID = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: Path) -> Path | None:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    return found[-1] if found else None


def op_name(raw: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return raw.split(" = ", 1)[0].lstrip("%")


def _events(line, name=lambda n: n):
    for e in line.events:
        yield name(e.name), float(e.start_ns), float(e.start_ns
                                                     + e.duration_ns)


def leaves(events):
    """The events that contain no other event of the list (a loop's own
    event spans the operations of its body)."""
    ev = sorted(events, key=lambda x: (x[1], -x[2]))
    out = []
    for i, (n, s, e) in enumerate(ev):
        if i + 1 < len(ev) and ev[i + 1][1] < e and ev[i + 1][2] <= e:
            continue
        out.append((n, s, e))
    return out


def extract(pd) -> dict:
    """``{"devices": [{"name", "ops", "modules"}], "host": [...]}``,
    every event a ``(name, start_ns, end_ns)`` tuple."""
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {l.name: l for l in plane.lines}
            if OPS_LINE not in lines:
                continue
            devices.append({
                "name": plane.name,
                "ops": list(_events(lines[OPS_LINE], op_name)),
                "modules": [(_MODULE_ID.sub("", n), s, e) for n, s, e in
                            (_events(lines[MODULES_LINE])
                             if MODULES_LINE in lines else ())]})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    devices.sort(key=lambda d: d["name"])
    return {"devices": devices, "host": host}


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(events, w0: float, w1: float):
    for n, s, e in events:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            yield n, s, e


def window_of(host) -> tuple[float, float] | None:
    spans = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    return max(spans, key=lambda x: x[1] - x[0]) if spans else None


def _innermost(events, t: float) -> str | None:
    best = None
    for n, s, e in events:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else None


def gap_label(host, t: float) -> str:
    ours = [x for x in host if x[0].startswith("bench.")
            and x[0] != WINDOW_SPAN]
    other = [x for x in host if not x[0].startswith("bench.")]
    a, b = _innermost(ours, t), _innermost(other, t)
    return "/".join(x for x in (a or "bench.window", b) if x)


def summarize(ex: dict, top: int = 10) -> dict | None:
    """The window's numbers, or None when the trace has no device or no
    window."""
    w = window_of(ex["host"])
    if w is None or not ex["devices"]:
        return None
    w0, w1 = w
    n_dev = len(ex["devices"])
    busy, ops, modules, module_events = 0.0, {}, {}, []
    gaps = []
    for i, dev in enumerate(ex["devices"]):
        clipped = list(clip(dev["ops"], w0, w1))
        u = union((s, e) for _, s, e in clipped)
        busy += sum(e - s for s, e in u)
        for n, s, e in leaves(clipped):
            ops[n] = ops.get(n, 0.0) + (e - s) / n_dev
        for n, s, e in clip(dev["modules"], w0, w1):
            modules[n] = modules.get(n, 0.0) + (e - s) / n_dev
            if i == 0:
                module_events.append((n, s, e))
        if i == 0:
            edges = [w0] + [x for se in u for x in se] + [w1]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    ns = 1e-9
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[gap_label(ex["host"], (s + e) / 2), (e - s) * ns]
            for s, e in gaps[:top]]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    spans = [(n, s * ns, e * ns) for n, s, e in ex["host"]
             if n.startswith("bench.") and s >= w0 and e <= w1]
    return {"window_s": (w1 - w0) * ns, "busy_s": busy / n_dev * ns,
            "ops_s": {k: v * ns for k, v in ops.items()},
            "modules_s": {k: v * ns for k, v in modules.items()},
            "module_events": [(n, s * ns, e * ns)
                              for n, s, e in module_events],
            "spans": spans,
            "breakdown": {"device_ops": [[k, v * ns] for k, v in top_ops],
                          "idle_gaps": idle}}


def time_matching(seconds_by_name: dict, pattern: str) -> float | None:
    """Summed seconds of the names that match ``pattern``; None when no
    name matches."""
    rx = re.compile(pattern)
    hits = [v for k, v in seconds_by_name.items() if rx.search(k)]
    return sum(hits) if hits else None


def reduce_dir(trace_dir: Path) -> dict | None:
    from jax.profiler import ProfileData
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return summarize(extract(ProfileData.from_file(str(path))))
