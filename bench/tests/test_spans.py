"""The program's spans in a reduced trace (bench/spans.py) and the
ring-time reader, on a small synthetic XSpace whose answers are worked
out by hand."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, spans, tracereduce  # noqa: E402

# Device 0 (times in ns; line timestamp 1000):
#   ops fusion.1 [2000, 5000), fusion.2 [8000, 9000)
#   module jit_ring_ag_hop(3) [8000, 9000)
# Host: bench.window [1000, 10000); bench.outer_step [1000, 6000) and
#   [6000, 10000); "PjitFunction(_chunk_fn)" [6000, 7000); spans
#   repro.train.outer_step [1000, 6000) > repro.train.inner_phase
#   [1000, 1400), repro.train.outer_sync [4000, 6000) > repro.sync.hop
#   [5500, 5800); repro.train.outer_step [6000, 9200) >
#   repro.train.bookkeeping [7000, 7600).
# Idle gaps [1000, 2000), [5000, 8000), [9000, 10000): 5000 ns of a
# 9000 ns window, charged
#   inner_phase [1000, 1400)                                  400
#   outer_step  [1400, 2000) [6000, 7000) [7600, 8000) [9000, 9200)
#                                                            2200
#   outer_sync  [5000, 5500) [5800, 6000)                     700
#   sync.hop    [5500, 5800)                                  300
#   bookkeeping [7000, 7600)                                  600
#   (none)      [9200, 10000)                                 800
XSPACE = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 7000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_ring_ag_hop(3)" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 5 offset_ps: 0 duration_ps: 400000 }
    events { metadata_id: 6 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 7 offset_ps: 4500000 duration_ps: 300000 }
    events { metadata_id: 4 offset_ps: 5000000 duration_ps: 3200000 }
    events { metadata_id: 8 offset_ps: 6000000 duration_ps: 600000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.outer_step" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(_chunk_fn)" } }
  event_metadata { key: 4 value { id: 4 name: "repro.train.outer_step" } }
  event_metadata { key: 5 value { id: 5 name: "repro.train.inner_phase" } }
  event_metadata { key: 6 value { id: 6 name: "repro.train.outer_sync" } }
  event_metadata { key: 7 value { id: 7 name: "repro.sync.hop" } }
  event_metadata { key: 8 value { id: 8 name: "repro.train.bookkeeping" } }
}
'''
NS = 1e-9


@pytest.fixture(scope="module")
def ex():
    from jax.profiler import ProfileData
    return tracereduce.extract(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(XSPACE)))


def test_idle_by_span(ex):
    got = spans.idle_by_span(ex)
    assert got == pytest.approx({
        "repro.train.inner_phase": 400 * NS,
        "repro.train.outer_step": 2200 * NS,
        "repro.train.outer_sync": 700 * NS,
        "repro.sync.hop": 300 * NS,
        "repro.train.bookkeeping": 600 * NS,
        "(none)": 800 * NS})
    s = tracereduce.summarize(ex)
    assert sum(got.values()) == pytest.approx(s["window_s"] - s["busy_s"])


def test_idle_by_span_without_spans_is_all_none(ex):
    bare = dict(ex, host=[x for x in ex["host"]
                          if not x[0].startswith("repro.")])
    assert spans.idle_by_span(bare) == pytest.approx({"(none)": 5000 * NS})
    assert spans.idle_by_span(dict(ex, devices=[])) is None
    assert spans.idle_by_span(dict(ex, host=[])) is None


def test_gap_label_puts_the_span_in_the_middle():
    host = [("bench.window", 0, 100), ("bench.outer_step", 10, 50),
            ("PjitFunction(f)", 20, 30), ("outer", 0, 100),
            ("repro.train.outer_sync", 15, 40), ("repro.sync.hop", 22, 28)]
    assert spans.gap_label(host, 25) == \
        "bench.outer_step/repro.sync.hop/PjitFunction(f)"
    assert spans.gap_label(host, 35) == \
        "bench.outer_step/repro.train.outer_sync/outer"
    # no repro span open: the reducer's own two-part label
    for t in (12, 70):
        assert spans.gap_label(host, t) == tracereduce.gap_label(
            host[:4], t)


def test_summarize_adds_and_keeps(ex):
    base = tracereduce.summarize(ex)
    s = spans.summarize(ex)
    for k, v in base.items():
        if k != "breakdown":
            assert s[k] == v, k
    assert s["breakdown"]["device_ops"] == base["breakdown"]["device_ops"]
    assert [g[1] for g in s["breakdown"]["idle_gaps"]] == [
        g[1] for g in base["breakdown"]["idle_gaps"]]
    assert [g[0] for g in s["breakdown"]["idle_gaps"]] == [
        "bench.outer_step/repro.train.outer_step/PjitFunction(_chunk_fn)",
        "bench.outer_step/repro.train.outer_step",
        "bench.outer_step"]
    assert s["idle_by_span"] == spans.idle_by_span(ex)
    assert s["span_s"]["repro.train.outer_step"] == {
        "s": pytest.approx(8200 * NS), "count": 2}
    assert s["span_s"]["repro.sync.hop"] == {
        "s": pytest.approx(300 * NS), "count": 1}


def test_ring_ms_reader(ex):
    read = harness.metric_reader("ring_ms.train")
    rec = {"trace": spans.summarize(ex), "train": {"steps": 2}}
    assert read(rec) == pytest.approx(1e3 * 1000 * NS / 2)
    rec["trace"]["modules_s"]["jit_ring_rs_fused_hop"] = 3000 * NS
    rec["trace"]["modules_s"]["jit__inner_phase"] = 9000 * NS
    assert read(rec) == pytest.approx(1e3 * 4000 * NS / 2)


def test_ring_ms_reader_finds_nothing():
    read = harness.metric_reader("ring_ms.train")
    assert read({"trace": None, "train": None}) is None
    # the ring hops named jit__lambda, as before the programs had names
    t = {"modules_s": {"jit__lambda": 1e-3, "jit__inner_phase": 1.0}}
    assert read({"trace": t, "train": {"steps": 2}}) is None
    assert read({"trace": {"modules_s": {"jit_ring_ag_hop": 1e-3}},
                 "train": {"steps": 0}}) is None


def test_main_runs_the_cell_through_this_reduction(monkeypatch):
    from bench import run_cell
    monkeypatch.setattr(tracereduce, "summarize", tracereduce.summarize)
    seen = {}

    def fake_main(argv, t_start):
        seen["argv"] = argv
        seen["summarize"] = tracereduce.summarize
        return 0

    monkeypatch.setattr(run_cell, "main", fake_main)
    argv = ["--workload", "w", "--seed", "1", "--seconds", "1",
            "--trace", "1"]
    assert spans.main(argv) == 0
    assert seen == {"argv": argv, "summarize": spans.summarize}
