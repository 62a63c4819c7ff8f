"""Mean wall time of an outer step (the host span bench.outer_step)
less the device time of its inner-phase program: the outer boundary
(sync, feed, bookkeeping) as the step sees it."""

INNER = "jit__inner_phase"


def read(rec):
    t = rec.get("trace")
    if not t:
        return None
    steps = [(s, e) for n, s, e in t["spans"] if n == "bench.outer_step"]
    inner = [(s, e) for n, s, e in t["module_events"] if n == INNER]
    if not steps or not inner:
        return None
    out = []
    for s0, s1 in steps:
        dev = sum(min(e, s1) - max(s, s0) for s, e in inner
                  if e > s0 and s < s1)
        out.append(s1 - s0 - dev)
    return 1e3 * sum(out) / len(out)
