"""Spans of the trainer, the outer sync and the serve engine in a
profiler trace: their names, their nesting, their args, and the named
programs of the ring hops."""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import CONFIGS
from repro.core import diloco as dl
from repro.core import ring_reduce as rr
from repro.models.registry import get_model
from repro.serving.engine import Request, bucket_len, make_engine

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import tracereduce  # noqa: E402

PARENT = {
    "train.outer_step": None,
    "train.feed": "train.outer_step",
    "train.inner_phase": "train.outer_step",
    "train.outer_sync": "train.outer_step",
    "sync.pseudograds": "train.outer_sync",
    "sync.hop": "train.outer_sync",
    "sync.apply": "train.outer_sync",
    "train.readback": "train.outer_step",
    "train.bookkeeping": "train.outer_step",
    "engine.step": None,
    "engine.admit": "engine.step",
    "engine.prefill": "engine.admit",
    "engine.install": "engine.admit",
    "engine.decode_chunk": "engine.step",
    "engine.readback": "engine.step",
    "engine.drain": "engine.step",
}


def _traced(tmp_path, fn):
    """Run ``fn`` under a profiler trace; return the host events as
    ``tracereduce.extract`` gives them and the ``repro.*`` spans as
    ``(name without the prefix, start_ns, end_ns, args)``."""
    with jax.profiler.trace(str(tmp_path)):
        fn()
    pd = ProfileData.from_file(str(tracereduce.find_xplane(tmp_path)))
    host = tracereduce.extract(pd)["host"]
    spans = [(e.name[len("repro."):], e.start_ns,
              e.start_ns + e.duration_ns, dict(e.stats))
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    assert sorted({(n, s, e) for n, s, e, _ in spans}) == sorted(
        {(n[len("repro."):], s, e) for n, s, e in host
         if n.startswith("repro.")})
    return host, spans


def _parent(spans, i):
    """Name of the innermost other span that contains span ``i``."""
    _, s, e, _ = spans[i]
    best = None
    for j, (n, s2, e2, _) in enumerate(spans):
        if j != i and s2 <= s and e <= e2 and (
                best is None or e2 - s2 < best[1]):
            best = (n, e2 - s2)
    return best and best[0]


def _check_nesting(spans):
    for i, (name, *_) in enumerate(spans):
        assert name in PARENT, name
        assert _parent(spans, i) == PARENT[name], name


class _SteppedRing:
    """The outer sync staged hop by hop and applied at once."""

    def begin(self, *args, **kw):
        return dl.begin_outer_sync_sim(*args, **kw)


def test_trainer_spans_hops_and_ring_programs(tmp_path):
    from repro.core.fault_tolerance import ClusterSimulator
    from repro.data.pipeline import DataConfig
    from repro.train.loop import ElasticTrainer, TrainerConfig

    cfg = CONFIGS["mamba2-130m"].reduced()
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    k = 2
    tr = ElasticTrainer(
        model,
        TrainerConfig(diloco=dl.DiLoCoConfig(inner_steps=2, quant="int8"),
                      inner_lr=3e-3, max_workers=k),
        DataConfig(vocab=cfg.vocab, seq_len=32, batch_per_worker=1,
                   total_steps=32),
        params, ClusterSimulator(list(range(k))),
        sync_backend=_SteppedRing())
    tr.run(1)                                  # compile outside the trace
    host, spans = _traced(tmp_path, lambda: tr.run(2))
    _check_nesting(spans)
    names = [n for n, *_ in spans]
    for name in PARENT:
        if name.startswith(("train.", "sync.")):
            assert name in names, name
    steps = [a for n, _, _, a in spans if n == "train.outer_step"]
    assert steps == [{"step": 1}, {"step": 2}]
    # one span per wire hop: 2 (k - 1) of them in each outer step
    hops = sorted((s, a["hop"], a["kind"]) for n, s, _, a in spans
                  if n == "sync.hop")
    assert [(h, kind) for _, h, kind in hops] == [
        (0, "rs_fused"), (1, "ag")] * 2
    # the hops run the named ring programs, never an anonymous one
    hop_spans = [(s, e) for n, s, e, _ in spans if n == "sync.hop"]
    in_hops = {n for n, s, e in host
               if n.startswith("PjitFunction(") and
               any(s0 <= s and e <= e1 for s0, e1 in hop_spans)}
    assert {"PjitFunction(ring_rs_fused_hop)", "PjitFunction(ring_ag_init)",
            "PjitFunction(ring_ag_hop)"} <= in_hops
    assert "PjitFunction(<lambda>)" not in in_hops


@pytest.mark.parametrize("kind,name", [
    ("rs", "ring_rs_hop"), ("rs_fused", "ring_rs_fused_hop"),
    ("ag_init", "ring_ag_init"), ("ag", "ring_ag_hop")])
def test_ring_hop_programs_are_named(kind, name):
    assert rr._hop_jit(kind, 3, 8, 8, 1, rr.RingConfig()).__name__ == name


def test_ring_hop_kind_must_be_known():
    with pytest.raises(ValueError):
        rr._hop_jit("bogus", 3, 8, 8, 1, rr.RingConfig())


@pytest.mark.parametrize("engine", ["continuous", "paged"])
def test_engine_spans_and_request_ids(tmp_path, engine):
    cfg = CONFIGS["internlm2-1.8b"].reduced()
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    eng = make_engine(engine, model, params, batch_slots=2, max_len=64,
                      decode_chunk=4, batch_admit=False)
    lens = (5, 12, 30)

    def serve(rid0):
        for i, n in enumerate(lens):
            eng.submit(Request(rid=rid0 + i,
                               prompt=np.arange(2, 2 + n, dtype=np.int32),
                               max_new_tokens=6))
        eng.run_until_drained()

    serve(0)                                   # compile outside the trace
    _, spans = _traced(tmp_path, lambda: serve(10))
    _check_nesting(spans)
    names = {n for n, *_ in spans}
    want = {n for n in PARENT if n.startswith("engine.")}
    if engine == "paged":        # admits through its own splice path
        want.discard("engine.install")
    assert names == want
    prefills = sorted((a["rid"], a["width"]) for n, _, _, a in spans
                      if n == "engine.prefill")
    assert prefills == [(10 + i, bucket_len(n)) for i, n in enumerate(lens)]
    installs = sorted((a["rid"], a["slot"]) for n, _, _, a in spans
                      if n == "engine.install")
    if engine == "continuous":
        assert [r for r, _ in installs] == [10, 11, 12]
        assert {s for _, s in installs} <= {0, 1}
    assert eng.stats["prefills"] == 2 * len(lens)
    assert "admit_batch_max" not in eng.stats
