"""The controls of ``correct`` at a size a test run can hold: the plain
reference computed one step below the bfloat16 the configurations
state (int8 for the training cell, the v5e's own lower-precision matmul
path; fp8 for the serving cells) must come out as not correct through
the cell's own comparison and limits, where the program comes out
correct. On the chip, at the cells' own sizes, the controls' readings
set the upper end of each limit (PERF.md, section 6)."""
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import compare, harness, serve_cell, train_cell  # noqa: E402

SEED = 3000000041


def test_train_control_reads_apart():
    cell = harness.load_cell("train.mamba2-130m.h100")
    limits = cell.traffic["limits"]
    devs = jax.devices()
    prog = train_cell.run(cell, SEED, 0.0, False, harness.CompileClock(),
                          harness.now(), devs, rehearse=True)["numbers"]
    ctrl = train_cell.control_readings(cell, SEED, devs, rehearse=True,
                                       variants=(("int8", None),))["int8"]
    ctrl.pop("detail")
    assert all(c["ok"] for c in compare.checks(prog, limits).values()), prog
    assert not all(c["ok"] for c in compare.checks(ctrl, limits).values()), \
        ctrl


@pytest.mark.parametrize("workload", ["serve.internlm2-1.8b.longprompt"])
def test_serve_control_reads_apart(workload):
    cell = harness.load_cell(workload)
    out = serve_cell.run(cell, SEED, 1.0, False, harness.CompileClock(),
                         harness.now(), jax.devices(), rehearse=True,
                         precisions=("f32", "fp8"))["numbers"]
    assert out["logit_gap.fp8"] >= 3.0 * max(out["logit_gap"], 1e-2), out
