"""Roofline share of the int8 ring codec kernels: the least time of the
codec work of the window's outer syncs (bytes bound: the bytes of
bench/counts.int8_ring_codec_bytes over the HBM bandwidth) over the
device time of the codec's Pallas kernels."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench.tracereduce import time_matching  # noqa: E402

KERNELS = r"^(vmap_jit_)?_(range|encode_hist|decode)_call"


def read(rec):
    t, tr, pk = rec.get("trace"), rec.get("train"), rec.get("peaks")
    if not t or not tr or not pk:
        return None
    dev = time_matching(t["ops_s"], KERNELS)
    if not dev:
        return None
    least = tr["steps"] * tr["codec_bytes_per_step"] / pk["hbm_bytes_per_s"]
    return 100.0 * least / dev
