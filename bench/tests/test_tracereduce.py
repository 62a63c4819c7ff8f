"""The trace reducer and the trace-based metric readers, on a small
synthetic XSpace (text proto) whose answers are worked out by hand."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, tracereduce  # noqa: E402

# Device 0 (times in ns; line timestamp 1000):
#   ops  fusion.1 [2000, 4000)  _flash_decode_call.9 [3000, 5000)  fusion.1 [8000, 9000)
#        _encode_hist_call.1 [10000, 10500) — starts after the window ends
#   modules  jit__inner_phase [1000, 6000)  jit__lambda [7500, 9500)
# Host: bench.window [1000, 10000); bench.outer_step [1000, 6000) and
#   [6000, 10000); a host event "PjitFunction(_chunk_fn)" [6000, 7000)
# Busy = [2000, 5000) + [8000, 9000) = 4000 ns of a 9000 ns window.
# Idle gaps: [1000, 2000) 1000, [5000, 8000) 3000, [9000, 10000) 1000.
XSPACE = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 9000000 duration_ps: 500000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 5 offset_ps: 6500000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "%_flash_decode_call.9 = f32[16,8,8,128] custom-call(s32[16] %fusion.166)" } }
  event_metadata { key: 3 value { id: 3 name: "jit__inner_phase(7)" } }
  event_metadata { key: 4 value { id: 4 name: "_encode_hist_call.1" } }
  event_metadata { key: 5 value { id: 5 name: "jit__lambda(12)" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.outer_step" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(_chunk_fn)" } }
}
'''
NS = 1e-9


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    ex = tracereduce.extract(pd)
    assert [d["name"] for d in ex["devices"]] == ["/device:TPU:0"]
    return tracereduce.summarize(ex)


def test_union_merges_overlaps():
    assert tracereduce.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [
        (1, 4), (5, 8)]


def test_busy_window_and_idle(summary):
    assert summary["window_s"] == pytest.approx(9000 * NS)
    assert summary["busy_s"] == pytest.approx(4000 * NS)
    gaps = summary["breakdown"]["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([3000 * NS, 1000 * NS,
                                                  1000 * NS])
    # the longest gap's middle (6500 ns) lies in the second outer step
    # and after the host's chunk call
    assert gaps[0][0] == "bench.outer_step/PjitFunction(_chunk_fn)"


def test_gap_label_names_host_event():
    host = [("bench.window", 0, 100), ("bench.outer_step", 10, 50),
            ("PjitFunction(f)", 20, 30), ("outer", 0, 100)]
    assert tracereduce.gap_label(host, 25) == \
        "bench.outer_step/PjitFunction(f)"
    assert tracereduce.gap_label(host, 70) == "bench.window/outer"


def test_per_op_and_per_module_sums(summary):
    ops = summary["ops_s"]
    assert ops["fusion.1"] == pytest.approx(3000 * NS)
    assert ops["_flash_decode_call.9"] == pytest.approx(2000 * NS)
    assert "_encode_hist_call.1" not in ops          # outside the window
    mods = summary["modules_s"]
    assert mods == pytest.approx({"jit__inner_phase": 5000 * NS,
                                  "jit__lambda": 2000 * NS})
    assert tracereduce.time_matching(ops, r"fusion|_flash_decode") == \
        pytest.approx(5000 * NS)
    assert tracereduce.time_matching(ops, r"nothing") is None
    top = summary["breakdown"]["device_ops"]
    assert top[0] == ["fusion.1", pytest.approx(3000 * NS)]


def _reader(name):
    return harness.metric_reader(name)


def test_readers_on_the_trace(summary):
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    rec = {"trace": summary, "chips": 1, "peaks": peaks,
           "setup": {"compile_s": 2.5},
           "train": {"steps": 2, "codec_bytes_per_step": 10,
                     "tokens_per_s": 100.0, "flops_per_token": 1e9},
           "serve": {"window_s": 2.0, "model_flops": 4e11, "prefills": 2,
                     "flash_decode_bytes": 1000}}
    for name in ("device_idle.train", "device_idle.serve"):
        assert _reader(name)(rec) == pytest.approx(100 * (1 - 4000 / 9000))
    # steps 5000 ns and 4000 ns; inner phase covers the first wholly
    assert _reader("boundary_ms.train")(rec) == pytest.approx(
        1e3 * (0 + 4000 * NS) / 2)
    assert _reader("mfu.train")(rec) == pytest.approx(10.0)
    assert _reader("mfu.serve")(rec) == pytest.approx(20.0)
    assert _reader("prefill_ms.serve")(rec) == pytest.approx(
        1e3 * 2000 * NS / 2)
    # 1000 bytes at 1e9 B/s = 1 us over 2000 ns of "_flash_decode_call.9"
    assert _reader("flash_decode_roofline.serve")(rec) == pytest.approx(
        50.0)
    assert _reader("int8_codec_roofline.train")(rec) is None  # no codec op
    assert _reader("compile_s")(rec) == 2.5


def test_readers_find_nothing_without_a_trace():
    rec = {"trace": None, "chips": 1, "peaks": None,
           "setup": {"compile_s": 1.0}, "train": None, "serve": None}
    for name in ("device_idle.serve", "boundary_ms.train", "mfu.train",
                 "prefill_ms.serve", "flash_decode_roofline.serve",
                 "int8_codec_roofline.train", "mfu.serve"):
        assert _reader(name)(rec) is None, name


def test_no_window_means_no_summary():
    ex = {"devices": [{"name": "/device:TPU:0", "ops": [("a", 0, 1)],
                       "modules": []}], "host": []}
    assert tracereduce.summarize(ex) is None


def test_op_names_and_loop_events():
    assert tracereduce.op_name(
        "%fusion.12 = bf16[16,8]{1,0} fusion(bf16[16,8] %p.1)") == \
        "fusion.12"
    assert tracereduce.op_name("_flash_decode_call.9") == \
        "_flash_decode_call.9"
    ev = [("while.1", 0, 10), ("fusion.2", 1, 3), ("fusion.3", 4, 6),
          ("copy.4", 12, 13)]
    assert [n for n, _, _ in tracereduce.leaves(ev)] == [
        "fusion.2", "fusion.3", "copy.4"]
    ex = {"devices": [{"name": "/device:TPU:0", "ops": ev, "modules": []}],
          "host": [("bench.window", 0, 20)]}
    s = tracereduce.summarize(ex)
    assert s["busy_s"] == pytest.approx(11e-9)
    assert "while.1" not in s["ops_s"]
    assert s["ops_s"]["fusion.2"] == pytest.approx(2e-9)
