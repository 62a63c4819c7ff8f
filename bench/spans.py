#!/usr/bin/env python3
"""The program's own spans (``repro.*``, src/repro/tracing.py) in a
reduced trace: device idle time charged to them, and idle gaps labelled
with them.

``idle_by_span`` splits each idle gap of device 0 in the window at the
edges of the ``repro.*`` host spans and charges each piece to the
innermost span open over it (``"(none)"`` where none is); the values
sum to the window less device 0's busy time. ``gap_label`` is
``tracereduce.gap_label`` with the innermost ``repro.*`` span between
its two parts, where one is open. ``summarize`` is
``tracereduce.summarize`` with both: every key it has keeps its value,
except the idle gaps' labels, which gain the span.

    python3 bench/spans.py --workload <name> --seed <n> --seconds <s> \
        --trace 1 --trace-summary <file>

is ``bench/run_cell.py`` with this reduction: the result line's idle
gaps carry the span, and the summary file also holds ``idle_by_span``
and ``span_s`` (host seconds and count of each span).
"""
from __future__ import annotations

import bisect
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import tracereduce  # noqa: E402

_summarize = tracereduce.summarize
REPRO = "repro."
NONE = "(none)"


def _spans(host):
    return [x for x in host if x[0].startswith(REPRO)]


def _gaps(dev, w0: float, w1: float) -> list[tuple[float, float]]:
    u = tracereduce.union(
        (s, e) for _, s, e in tracereduce.clip(dev["ops"], w0, w1))
    edges = [w0] + [x for se in u for x in se] + [w1]
    return [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
            if edges[j + 1] > edges[j]]


def _segments(spans, w0: float, w1: float):
    """Cut points from ``w0`` to ``w1`` at every span edge, and for each
    segment between two neighbours the innermost span open over it."""
    pts = sorted({w0, w1} | {x for _, s, e in spans for x in (s, e)
                             if w0 < x < w1})
    by_start = sorted(spans, key=lambda x: x[1])
    labels, live, j = [], [], 0
    for a in pts[:-1]:
        while j < len(by_start) and by_start[j][1] <= a:
            live.append(by_start[j])
            j += 1
        live = [x for x in live if x[2] > a]
        best = min(live, key=lambda x: x[2] - x[1], default=None)
        labels.append(best[0] if best else NONE)
    return pts, labels


def idle_by_span(ex: dict) -> dict | None:
    """Seconds of device 0's idle time in the window per innermost
    ``repro.*`` span; None without a window or a device."""
    w = tracereduce.window_of(ex["host"])
    if w is None or not ex["devices"]:
        return None
    pts, labels = _segments(_spans(ex["host"]), *w)
    out: dict[str, float] = {}
    for g0, g1 in _gaps(ex["devices"][0], *w):
        i = bisect.bisect_right(pts, g0) - 1
        while i < len(labels) and pts[i] < g1:
            part = min(g1, pts[i + 1]) - max(g0, pts[i])
            out[labels[i]] = out.get(labels[i], 0.0) + part * 1e-9
            i += 1
    return out


def gap_label(host, t: float) -> str:
    """``<bench span>/<repro span>/<other host event>`` around ``t``;
    the middle part only where a ``repro.*`` span is open."""
    bench = [x for x in host if x[0].startswith("bench.")
             and x[0] != tracereduce.WINDOW_SPAN]
    other = [x for x in host if not x[0].startswith(("bench.", REPRO))]
    parts = (tracereduce._innermost(bench, t) or tracereduce.WINDOW_SPAN,
             tracereduce._innermost(_spans(host), t),
             tracereduce._innermost(other, t))
    return "/".join(x for x in parts if x)


def span_seconds(ex: dict) -> dict:
    """Host seconds and count of each ``repro.*`` span inside the
    window."""
    w0, w1 = tracereduce.window_of(ex["host"]) or (0.0, 0.0)
    out: dict[str, list] = {}
    for n, s, e in _spans(ex["host"]):
        if s >= w0 and e <= w1:
            acc = out.setdefault(n, [0.0, 0])
            acc[0] += (e - s) * 1e-9
            acc[1] += 1
    return {n: {"s": v[0], "count": v[1]} for n, v in out.items()}


def summarize(ex: dict, top: int = 10) -> dict | None:
    s = _summarize(ex, top)
    if s is None:
        return None
    w = tracereduce.window_of(ex["host"])
    gaps = sorted(_gaps(ex["devices"][0], *w), key=lambda g: g[0] - g[1])
    s["breakdown"]["idle_gaps"] = [
        [gap_label(ex["host"], (a + b) / 2), (b - a) * 1e-9]
        for a, b in gaps[:top]]
    s["idle_by_span"] = idle_by_span(ex)
    s["span_s"] = span_seconds(ex)
    return s


def main(argv=None) -> int:
    """``run_cell.main`` with this module's ``summarize`` as the
    reducer's."""
    import time
    t_start = time.perf_counter()
    from bench import run_cell
    tracereduce.summarize = summarize
    return run_cell.main(argv, t_start)


if __name__ == "__main__":
    sys.exit(main())
