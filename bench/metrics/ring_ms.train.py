"""Device time of the ring hop programs (``jit_ring_*``: the reduce-
scatter hops, the fused first hop, the all-gather prologue and hops of
core/ring_reduce.py) per outer step of the traced window."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench.tracereduce import time_matching  # noqa: E402

RING = r"^jit_ring_"


def read(rec):
    t, tr = rec.get("trace"), rec.get("train")
    if not t or not tr or tr["steps"] <= 0:
        return None
    dev = time_matching(t["modules_s"], RING)
    if not dev:
        return None
    return 1e3 * dev / tr["steps"]
