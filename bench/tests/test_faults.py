"""A run with the timed path broken underneath must come out with
``correct`` false: the harness's whole path on the CPU at tiny sizes
(no chip), with one fault planted in the program for each test.

Training cells: a step that returns its state unchanged; half of each
row left out of the loss (the mean over the rest); the exchange between
the workers left out (each applies its own pseudo-gradient). Serving
cells: a served token altered where it is produced; a decode step that
leaves the KV cache as it was.
"""
import json
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run_cell  # noqa: E402

TRAIN = "train.mamba2-130m.h100"
SERVE = "serve.internlm2-1.8b.longprompt"


def _run(workload, capsys) -> dict:
    rc = run_cell.main(["--workload", workload, "--seed", "3000000029",
                        "--seconds", "1", "--trace", "0", "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _frozen_inner_phase(self, params, opt_state, batches, active):
    h, k = batches["tokens"].shape[:2]
    return params, opt_state, jnp.zeros((h, k), jnp.float32)


def _half_rows(orig):
    def loss(logits, targets, mask=None, z_weight=2e-4):
        m = jnp.ones(targets.shape, jnp.float32) if mask is None else mask
        m = m.at[..., targets.shape[-1] // 2:].set(0.0)
        return orig(logits, targets, m, z_weight)
    return loss


def _own_rows(op):
    """The ring's result with the exchange left out: each row keeps its
    own pseudo-gradient."""
    return op.xs.astype(jnp.float32)


def _shifted(orig):
    def sample(logits, *a, **kw):
        tok = orig(logits, *a, **kw)
        return (tok + 1) % logits.shape[-1]
    return sample


def _stale(cache, k, v, rolling=False):
    return cache


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "no_exchange"])
def test_train_fault_is_caught(fault, monkeypatch, capsys):
    from repro.core import ring_reduce
    from repro.models import common
    from repro.train.loop import ElasticTrainer
    if fault == "unchanged":
        monkeypatch.setattr(ElasticTrainer, "_inner_phase",
                            _frozen_inner_phase)
    elif fault == "half_batch":
        monkeypatch.setattr(common, "cross_entropy_max_z",
                            _half_rows(common.cross_entropy_max_z))
    else:
        monkeypatch.setattr(ring_reduce.RingSyncOp, "finish", _own_rows)
    line = _run(TRAIN, capsys)
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("fault", ["token_altered", "cache_unchanged"])
def test_serve_fault_is_caught(fault, monkeypatch, capsys):
    from repro.models import attention
    from repro.serving import engine
    if fault == "token_altered":
        monkeypatch.setattr(engine, "sample_tokens",
                            _shifted(engine.sample_tokens))
    else:
        monkeypatch.setattr(attention, "cache_update", _stale)
    line = _run(SERVE, capsys)
    assert line["correct"] is False, line["checks"]
