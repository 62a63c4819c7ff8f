"""Roofline share of the Pallas flash-decode kernel: the least time of
the window's decode attention (bytes bound: K and V of each decoded
token's live context, its query and output, bench/counts.py, over the
HBM bandwidth) over the kernel's device time."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench.tracereduce import time_matching  # noqa: E402

KERNEL = r"^_flash_decode_call"


def read(rec):
    t, sv, pk = rec.get("trace"), rec.get("serve"), rec.get("peaks")
    if not t or not sv or not pk:
        return None
    dev = time_matching(t["ops_s"], KERNEL)
    if not dev:
        return None
    return 100.0 * sv["flash_decode_bytes"] / pk["hbm_bytes_per_s"] / dev
