"""The harness's whole path on the CPU at tiny sizes (Pallas
interpreted): set-up, window, the reference check and the result
line's shape. Its numbers are never metrics."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run_cell  # noqa: E402


def _last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,trace", [
    ("train.mamba2-130m.h100", 0),
    ("serve.internlm2-1.8b.rollout", 1),
    ("serve.internlm2-1.8b.longprompt", 0),
])
def test_rehearsal_result_line(workload, trace, capsys):
    rc = run_cell.main(["--workload", workload, "--seed", "3000000019",
                        "--seconds", "1", "--trace", str(trace),
                        "--rehearse"])
    assert rc == 0
    line = _last_line(capsys)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert line["metrics"] == {}          # CPU numbers are never metrics
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert "setup_s" in line["rehearsal"] or trace
    for c in line["checks"].values():
        assert set(c) == {"value", "limit", "ok"}
