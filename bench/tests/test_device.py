"""The harness refuses to measure anywhere but on the chip: an unknown
device kind has no peaks, a CPU platform and a checkout without the
program give a non-zero exit and no result line."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, run_cell  # noqa: E402


def test_peaks_known_and_unknown():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        harness.peaks("cpu")


def test_cpu_platform_is_refused(capsys):
    with pytest.raises(harness.NoChip):
        harness.require_chips(1)
    rc = run_cell.main(["--workload", "train.mamba2-130m.h100",
                        "--seed", "1", "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""
    assert "needs a tpu" in out.err


def test_every_cell_loads():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.metric_reader(m["name"])), m["name"]


def test_checkout_without_program_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run_cell.py", "--workload",
         "train.mamba2-130m.h100", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120, env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
