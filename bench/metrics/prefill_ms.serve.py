"""Device time of the prefill programs per request admitted in the
traced window."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench.tracereduce import time_matching  # noqa: E402

PREFILL = r"^jit__lambda"


def read(rec):
    t, sv = rec.get("trace"), rec.get("serve")
    if not t or not sv or sv["prefills"] <= 0:
        return None
    dev = time_matching(t["modules_s"], PREFILL)
    if not dev:
        return None
    return 1e3 * dev / sv["prefills"]
