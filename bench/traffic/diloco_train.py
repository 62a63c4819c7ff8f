"""The DiLoCo training feed: token rows for every (inner step, worker),
made on the device from ``--seed`` in one jitted call per outer step.

Rows are drawn from a mixture of Zipf-like token streams (one source per
row, a marker token first), copied from the synthetic INTELLECT-1
mixture of ``repro.data.pipeline.TokenPipeline``; every (seed, step,
worker, row) gives a different row. The traffic file fixes the sources,
the sequence length, the rows per worker and the trainer's settings.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import harness

RUNNER = "train_cell"
MARKERS = 8      # token ids below this are source markers


def _rows(key, n_rows: int, seq_len: int, vocab: int, weights, zipf_a):
    ks, kt = jax.random.split(key)
    src = jax.random.choice(ks, len(weights), (n_rows,),
                            p=jnp.asarray(weights))
    a = jnp.asarray(zipf_a, jnp.float32)[src]
    u = jax.random.uniform(kt, (n_rows, seq_len + 1), minval=1e-6,
                           maxval=1.0)
    ranks = jnp.floor(u ** (-1.0 / a[:, None])) % (vocab - MARKERS)
    toks = (ranks + MARKERS).astype(jnp.int32)
    return toks.at[:, 0].set(src.astype(jnp.int32))


def _batches(key, step0, *, h: int, k: int, rows: int, seq_len: int,
             vocab: int, weights, zipf_a):
    def one(i, w):
        kk = jax.random.fold_in(jax.random.fold_in(key, step0 + i), w)
        return _rows(kk, rows, seq_len, vocab, weights, zipf_a)

    toks = jax.vmap(lambda i: jax.vmap(lambda w: one(i, w))(
        jnp.arange(k)))(jnp.arange(h))              # (h, k, rows, S+1)
    return {"tokens": toks[..., :-1], "targets": toks[..., 1:],
            "mask": jnp.ones(toks[..., 1:].shape, jnp.float32)}


class Feed:
    """``batch_provider(global_step, h, k)`` for ``ElasticTrainer``:
    the stacked (h, k, rows, seq) batch of one outer step."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        src = traffic["sources"]
        w = [float(s["weight"]) for s in src]
        self._fn = jax.jit(functools.partial(
            _batches, rows=int(traffic["batch_per_worker"]),
            seq_len=int(traffic["seq_len"]), vocab=int(vocab),
            weights=tuple(x / sum(w) for x in w),
            zipf_a=tuple(float(s["zipf_a"]) for s in src)),
            static_argnames=("h", "k"))
        self.key = harness.seed_key(seed, 0xDA7A)

    def __call__(self, global_step: int, h: int, k: int) -> dict:
        return self._fn(self.key, jnp.int32(global_step), h=h, k=k)
