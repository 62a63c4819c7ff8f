"""Int8 ring-all-reduce with fp32 accumulation (INTELLECT-1 §2.2).

The paper's scheme, mapped to TPU collectives:

  * ring reduce-scatter then ring all-gather, built from static
    ``jax.lax.ppermute`` steps inside a ``shard_map`` manual region —
    the TPU analogue of the paper's IP-based Gloo ring;
  * every transmitted chunk is quantized to int8 (6-sigma clip +
    bucket-mean codebook, 1 KiB sideband per chunk-hop) while the running
    reduction stays fp32 — ``Q(a)+Q(b) != Q(a+b)``;
  * in the all-gather phase each reduced chunk is quantized ONCE by its
    owner and the codes are forwarded verbatim, so every worker
    (including the owner) dequantizes identical bytes -> all DiLoCo
    replicas apply bit-identical outer updates;
  * the ring order is a static permutation produced by the bandwidth-
    aware topology solver (``core.topology``); changing it recompiles,
    matching the paper's occasional ring re-ordering;
  * elastic weighting: each contribution is pre-scaled by a per-worker
    weight (0 for dead/joining workers) and the final average divides by
    the total live weight (paper §2.4: joiners enter with zero
    pseudo-gradient; failed workers are excluded from the average).

Fused + bucketed sync engine (see ``docs/sync_pipeline.md``):

  * each per-hop chunk is split into ``RingConfig.buckets`` sub-buckets
    with independent codebooks and independent ``ppermute``s, so the
    quantization of bucket ``i+1`` is data-independent of the transfer
    of bucket ``i`` and the compiler can overlap compress and
    communicate (the paper's pipelined all-reduce);
  * the reduce-scatter accumulation runs through the fused
    ``ops.dequantize_add`` (decode + accumulate in one memory pass);
  * when the caller provides ``fused_src=(anchor_flat, theta_flat)``
    the FIRST reduce-scatter hop quantizes straight off the model
    buffers via the fused ``ops.quantize_pseudograd`` (anchor - theta,
    scaled by the elastic weight, encoded in a single HBM trip) instead
    of re-reading the materialized pseudo-gradient.

Two implementations share all chunk/quant helpers and are tested for
exact equivalence:
  * ``ring_all_reduce``          — per-device, inside shard_map;
  * ``simulate_ring_all_reduce`` — stacked (k, D) single-process mirror
    (``vmap`` over workers, ``fori_loop`` over hops), used by the CPU
    cluster simulator and the unit tests.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as qops
from repro.kernels.ref import NUM_BUCKETS
from repro.tracing import span


@dataclasses.dataclass(frozen=True)
class RingConfig:
    quant: str = "int8"          # 'int8' | 'fp32' (paper baseline) | 'int4'
    impl: str = "jnp"            # quant backend: 'jnp' | 'pallas'
    average: bool = True
    buckets: int = 1             # sub-buckets per chunk-hop (pipelining)
    fused: bool = True           # fused dequantize_add / pseudograd tx


def _bytes_per_elem(quant: str) -> float:
    return {"int8": 1.0, "int4": 0.5, "fp32": 4.0}[quant]


def ring_hop_bytes(numel: int, n_workers: int, quant: str = "int8",
                   buckets: int = 1) -> float:
    """Per-worker bytes of ONE wire hop (every hop carries one chunk of
    ``buckets`` sub-buckets plus their codebook sidebands; the chunk is
    rounded up so padding rides the wire too)."""
    if n_workers <= 1:
        return 0.0
    chunk = -(-numel // n_workers)
    chunk = -(-chunk // buckets) * buckets
    payload = chunk * _bytes_per_elem(quant)
    sideband = 0 if quant == "fp32" else 4 * NUM_BUCKETS * buckets
    return float(payload + sideband)


def ring_wire_bytes(numel: int, n_workers: int, quant: str = "int8",
                    buckets: int = 1) -> int:
    """Per-worker bytes on the wire for one all-reduce (both phases):
    2·(n−1) hops of :func:`ring_hop_bytes` each."""
    if n_workers <= 1:
        return 0
    return int(2 * (n_workers - 1)
               * ring_hop_bytes(numel, n_workers, quant, buckets))


# -- chunk/bucket helpers -----------------------------------------------------


def _pad_to_chunks(x: jnp.ndarray, n: int,
                   buckets: int = 1) -> tuple[jnp.ndarray, int, int]:
    """Pad the last dim so it splits into ``n`` chunks of ``buckets``
    equal sub-buckets. Returns (padded, chunk, bucket_size)."""
    size = x.shape[-1]
    chunk = -(-size // n)
    bsize = -(-chunk // buckets)
    chunk = bsize * buckets
    pad = n * chunk - size
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x, chunk, bsize


def _get_bucket(acc: jnp.ndarray, idx, b: int, chunk: int,
                bsize: int) -> jnp.ndarray:
    return jax.lax.dynamic_slice_in_dim(
        acc, idx * chunk + b * bsize, bsize, axis=-1)


def _set_bucket(acc: jnp.ndarray, idx, b: int, val: jnp.ndarray,
                chunk: int, bsize: int) -> jnp.ndarray:
    return jax.lax.dynamic_update_slice_in_dim(
        acc, val, idx * chunk + b * bsize, axis=-1)


def _tx_quant(val: jnp.ndarray, cfg: RingConfig):
    """Quantize a bucket for transmission -> (payload pytree, dequant fn)."""
    if cfg.quant == "fp32":
        return (val,), lambda p: p[0]
    if cfg.quant == "int4":
        from repro.core import compression
        q = compression.quantize4(val)
        return tuple(q), lambda p: compression.dequantize4(
            compression.Quantized4(*p), val.shape)
    q = qops.quantize(val, impl=cfg.impl)
    return tuple(q), lambda p: qops.dequantize(
        qops.Quantized(*p), impl=cfg.impl)


def _rx_add(payload, deq, acc_val: jnp.ndarray, cfg: RingConfig):
    """Reduce-scatter accumulate: fused decode+add on the int8 path."""
    if cfg.fused and cfg.quant == "int8":
        return qops.dequantize_add(qops.Quantized(*payload), acc_val,
                                   impl=cfg.impl)
    return acc_val + deq(payload)


def _int8_deq(cfg: RingConfig):
    return lambda p: qops.dequantize(qops.Quantized(*p), impl=cfg.impl)


# -- chunk-norm sideband (contribution admission / localization) -------------


def chunk_norms(xs, buckets: int = 1) -> np.ndarray:
    """Per-(chunk, bucket) L2 norms of stacked contributions.

    ``xs``: (k, D) per-worker rows. Returns (k, k * buckets) float64 —
    one column per wire sub-bucket, laid out exactly like the ring's
    chunk geometry (same ceil-div padding as :func:`_pad_to_chunks`), so
    an admission layer can localize WHICH chunk of WHICH slot carries
    garbage. Pure host-side numpy: the simulator and the distributed
    path compute bit-identical sidebands from their (bit-identical)
    retained pseudo-gradients.
    """
    rows = np.asarray(xs, dtype=np.float64)
    k, size = rows.shape
    nb = max(1, buckets)
    chunk = -(-size // k)
    bsize = -(-chunk // nb)
    chunk = bsize * nb
    pad = k * chunk - size
    if pad:
        rows = np.concatenate([rows, np.zeros((k, pad))], axis=1)
    safe = np.nan_to_num(rows, nan=0.0, posinf=0.0, neginf=0.0)
    sq = safe.reshape(k, k * nb, bsize)
    return np.sqrt(np.sum(sq * sq, axis=2))


# -- distributed ring (inside shard_map, manual over `axis_name`) ------------


def ring_all_reduce(x: jnp.ndarray, axis_name: str,
                    ring_order: Sequence[int] | None = None,
                    cfg: RingConfig = RingConfig(),
                    weight: jnp.ndarray | None = None,
                    fused_src=None) -> jnp.ndarray:
    """All-reduce (mean by default) of flat fp32 ``x`` over ``axis_name``.

    Must be called inside a shard_map region where ``axis_name`` is a
    manual axis. ``ring_order`` is the static bandwidth-optimized
    permutation of axis indices (defaults to the identity ring).
    ``fused_src=(anchor_flat, theta_flat)`` (both shaped like ``x``,
    with ``x == anchor_flat - theta_flat``) routes the first-hop
    transmit through the fused pseudo-gradient quantizer.
    """
    n = jax.lax.axis_size(axis_name)
    orig_size = x.shape[-1]
    x = x.astype(jnp.float32)
    if weight is None:
        weight = jnp.float32(1.0)
    total_w = jax.lax.psum(weight, axis_name)
    if n == 1:
        out = x * weight / jnp.maximum(total_w, 1e-20) if cfg.average else x
        return out[..., :orig_size]

    order = tuple(ring_order) if ring_order is not None else tuple(range(n))
    assert sorted(order) == list(range(n)), "ring order must be a permutation"
    inv = np.argsort(np.asarray(order))  # axis index -> ring position
    perm_fwd = [(order[p], order[(p + 1) % n]) for p in range(n)]
    pos = jnp.asarray(inv)[jax.lax.axis_index(axis_name)]

    nb = max(1, cfg.buckets)
    acc, chunk, bsize = _pad_to_chunks(x * weight, n, nb)
    use_fused_tx = (fused_src is not None and cfg.fused
                    and cfg.quant == "int8")
    if use_fused_tx:
        a_flat, t_flat = fused_src
        pad = acc.shape[-1] - orig_size
        a_flat = jnp.pad(a_flat.astype(jnp.float32), (0, pad))
        t_flat = jnp.pad(t_flat.astype(jnp.float32), (0, pad))

    def shift(payload):
        return tuple(jax.lax.ppermute(p, axis_name, perm_fwd)
                     for p in payload)

    # Phase 1: reduce-scatter (n-1 hops, fp32 accumulation). All buckets
    # of a hop are quantized before any is shifted: bucket i+1's encode
    # has no data dependency on bucket i's ppermute, so the scheduler
    # overlaps compression with transmission (pipelined all-reduce).
    for s in range(n - 1):
        send_idx = (pos - s) % n
        recv_idx = (pos - s - 1) % n
        staged = []
        for b in range(nb):
            if s == 0 and use_fused_tx:
                start = send_idx * chunk + b * bsize
                a_c = jax.lax.dynamic_slice_in_dim(a_flat, start, bsize)
                t_c = jax.lax.dynamic_slice_in_dim(t_flat, start, bsize)
                q = qops.quantize_pseudograd(a_c, t_c, scale=weight,
                                             impl=cfg.impl)
                staged.append((tuple(q), _int8_deq(cfg)))
            else:
                staged.append(_tx_quant(
                    _get_bucket(acc, send_idx, b, chunk, bsize), cfg))
        for b, (payload, deq) in enumerate(staged):
            payload = shift(payload)
            acc_val = _get_bucket(acc, recv_idx, b, chunk, bsize)
            acc = _set_bucket(acc, recv_idx, b,
                              _rx_add(payload, deq, acc_val, cfg),
                              chunk, bsize)

    # Phase 2: all-gather. The owner quantizes its reduced chunk ONCE and
    # everyone (owner included) dequantizes the same forwarded codes.
    own_idx = (pos + 1) % n
    staged = []
    for b in range(nb):
        payload, deq = _tx_quant(
            _get_bucket(acc, own_idx, b, chunk, bsize), cfg)
        acc = _set_bucket(acc, own_idx, b, deq(payload), chunk, bsize)
        staged.append((payload, deq))
    for s in range(n - 1):
        recv_idx = (pos - s) % n
        staged = [(shift(payload), deq) for payload, deq in staged]
        for b, (payload, deq) in enumerate(staged):
            acc = _set_bucket(acc, recv_idx, b, deq(payload), chunk, bsize)

    out = acc[..., :orig_size]
    if cfg.average:
        out = out / jnp.maximum(total_w, 1e-20)
    return out


# -- single-process mirror (stacked workers) ---------------------------------


def _row_deq(cfg: RingConfig, bsize: int):
    """Row-wise dequant fn for (k, bsize) stacked payloads (static per
    (cfg, bsize) so the all-gather hops can rebuild it without carrying
    closures through jit boundaries)."""
    if cfg.quant == "fp32":
        return lambda p: p[0]
    if cfg.quant == "int4":
        from repro.core import compression
        return lambda p: jax.vmap(
            lambda pk, bk: compression.dequantize4(
                compression.Quantized4(pk, bk), (bsize,)))(*p)
    return lambda p: jax.vmap(
        lambda c, bk: qops.dequantize(qops.Quantized(c, bk),
                                      impl=cfg.impl))(*p)


def _quant_rows(vals: jnp.ndarray, cfg: RingConfig):
    """Row-wise transmit quantization of (k, bsize) stacked buckets ->
    (payload tuple of stacked arrays, row-wise dequant fn). vmap over
    workers is bit-identical to per-row calls on XLA:CPU (tested)."""
    bsize = vals.shape[-1]
    if cfg.quant == "fp32":
        return (vals,), _row_deq(cfg, bsize)
    if cfg.quant == "int4":
        from repro.core import compression
        q = jax.vmap(compression.quantize4)(vals)
        return tuple(q), _row_deq(cfg, bsize)
    q = jax.vmap(lambda v: qops.quantize(v, impl=cfg.impl))(vals)
    return tuple(q), _row_deq(cfg, bsize)


def _rx_add_rows(payload, deq, acc_vals: jnp.ndarray, cfg: RingConfig):
    if cfg.fused and cfg.quant == "int8":
        return jax.vmap(lambda c, bk, a: qops.dequantize_add(
            qops.Quantized(c, bk), a, impl=cfg.impl))(*payload, acc_vals)
    return acc_vals + deq(payload)


def _get_bucket_rows(accs, idxs, b: int, chunk: int, bsize: int):
    return jax.vmap(lambda a, i: jax.lax.dynamic_slice_in_dim(
        a, i * chunk + b * bsize, bsize, axis=-1))(accs, idxs)


def _set_bucket_rows(accs, idxs, b: int, vals, chunk: int, bsize: int):
    return jax.vmap(lambda a, i, v: jax.lax.dynamic_update_slice_in_dim(
        a, v, i * chunk + b * bsize, axis=-1))(accs, idxs, vals)


def _roll1(payload):
    """Position p receives from position p-1."""
    return tuple(jnp.roll(p, 1, axis=0) for p in payload)


# -- hop bodies (shared by the one-shot simulator, RingSyncOp, and the
#    distributed per-hop shard_map programs in train.step) -------------------
#
# ``k`` is always the RING size; the row count is ``positions.shape[0]``
# (all k positions in the simulator, ONE row per device inside a manual
# shard_map region, where ``positions = inv[axis_index][None]`` and
# ``shift`` is a ``ppermute`` along the ring instead of ``jnp.roll``).
# vmap over one row is bit-identical to the stacked vmap on XLA:CPU
# (tested), which is what makes the distributed path hop-for-hop
# bit-identical to the simulator there. On TPU, XLA may reduce a k-row
# batch in another order than one row, so the jnp and int4 codecs'
# ranges can differ in the last bit between the two paths (measured
# for the range); the Pallas int8 codec does every reduction inside
# its kernels in a fixed order, so its rows do not depend on the
# batch (``chip_smoke.py --four-chips`` checks the anchor).


def _rs_hop_rows(s, accs, k: int, chunk: int, bsize: int, nb: int,
                 cfg: RingConfig, fused_operands=None, *,
                 positions=None, shift=_roll1):
    """One reduce-scatter hop across the given ring positions/buckets.
    ``fused_operands=(a_flat, t_pos, w_pos)`` routes the transmit
    through the fused pseudo-gradient quantizer (hop 0 only)."""
    if positions is None:
        positions = jnp.arange(k)
    send_idx = (positions - s) % k
    recv_idx = (positions - s - 1) % k
    staged = []
    for b in range(nb):
        if fused_operands is not None:
            a_flat, t_pos, w_pos = fused_operands
            starts = send_idx * chunk + b * bsize
            a_rows = jax.vmap(lambda i: jax.lax.dynamic_slice_in_dim(
                a_flat, i, bsize, axis=-1))(starts)
            t_rows = jax.vmap(
                lambda t, i: jax.lax.dynamic_slice_in_dim(
                    t, i, bsize, axis=-1))(t_pos, starts)
            q = jax.vmap(lambda a, t, w: qops.quantize_pseudograd(
                a, t, scale=w, impl=cfg.impl))(a_rows, t_rows, w_pos)
            staged.append((tuple(q), _row_deq(cfg, bsize)))
        else:
            staged.append(_quant_rows(
                _get_bucket_rows(accs, send_idx, b, chunk, bsize), cfg))
    for b, (payload, deq) in enumerate(staged):
        payload = shift(payload)
        acc_vals = _get_bucket_rows(accs, recv_idx, b, chunk, bsize)
        accs = _set_bucket_rows(
            accs, recv_idx, b,
            _rx_add_rows(payload, deq, acc_vals, cfg),
            chunk, bsize)
    return accs


def _ag_init_rows(accs, k: int, chunk: int, bsize: int, nb: int,
                  cfg: RingConfig, *, positions=None):
    """All-gather prologue: every owner quantizes its reduced chunk ONCE
    (per bucket); the codes are then forwarded verbatim so every worker
    decodes identical bytes. Returns (accs, per-bucket payloads)."""
    if positions is None:
        positions = jnp.arange(k)
    own_idx = (positions + 1) % k
    payloads = []
    for b in range(nb):
        vals = _get_bucket_rows(accs, own_idx, b, chunk, bsize)
        payload, deq = _quant_rows(vals, cfg)
        accs = _set_bucket_rows(accs, own_idx, b, deq(payload),
                                chunk, bsize)
        payloads.append(payload)
    return accs, tuple(payloads)


def _ag_hop_rows(s, accs, payloads, k: int, chunk: int, bsize: int,
                 nb: int, cfg: RingConfig, *, positions=None, shift=_roll1):
    """One all-gather hop: shift every bucket's forwarded codes one
    position and decode in place. Buckets write disjoint regions, so
    hop-major order here equals the bucket-major order bit-for-bit."""
    if positions is None:
        positions = jnp.arange(k)
    recv_idx = (positions - s) % k
    deq = _row_deq(cfg, bsize)
    new_payloads = []
    for b in range(nb):
        payload = shift(payloads[b])
        accs = _set_bucket_rows(accs, recv_idx, b, deq(payload),
                                chunk, bsize)
        new_payloads.append(payload)
    return accs, tuple(new_payloads)


def simulate_ring_all_reduce(xs: jnp.ndarray,
                             ring_order: Sequence[int] | None = None,
                             cfg: RingConfig = RingConfig(),
                             weights: jnp.ndarray | None = None,
                             fused_src=None) -> jnp.ndarray:
    """Exact single-process mirror of ``ring_all_reduce``.

    ``xs``: (k, D) stacked per-worker vectors. Returns (k, D) results —
    identical across workers (and bit-identical to the distributed path,
    which the tests assert). Workers are handled by ``vmap`` and the
    hop loops by ``lax.fori_loop`` — no per-hop Python copies of the
    stacked accumulator. ``fused_src=(anchor_flat, thetas)`` mirrors the
    distributed fused first-hop transmit (``anchor_flat``: (D,) shared,
    ``thetas``: (k, D) per-worker).
    """
    k, orig_size = xs.shape
    xs = xs.astype(jnp.float32)
    if weights is None:
        weights = jnp.ones((k,), jnp.float32)
    total_w = jnp.sum(weights)
    if k == 1:
        out = xs * weights[:, None] / jnp.maximum(total_w, 1e-20) \
            if cfg.average else xs
        return out

    order = tuple(ring_order) if ring_order is not None else tuple(range(k))
    assert sorted(order) == list(range(k))
    perm = np.asarray(order)
    inv = np.argsort(perm)  # worker w sits at ring position inv[w]

    nb = max(1, cfg.buckets)
    # accs indexed by RING POSITION p: acc[p] belongs to worker order[p]
    w_pos = weights[jnp.asarray(perm)]
    accs = xs[perm] * w_pos[:, None]
    accs, chunk, bsize = _pad_to_chunks(accs, k, nb)

    use_fused_tx = (fused_src is not None and cfg.fused
                    and cfg.quant == "int8")
    if use_fused_tx:
        a_flat, thetas = fused_src
        pad = accs.shape[-1] - orig_size
        a_flat = jnp.pad(a_flat.astype(jnp.float32), (0, pad))
        t_pos = jnp.pad(thetas.astype(jnp.float32)[perm],
                        [(0, 0), (0, pad)])

    # Phase 1: reduce-scatter. Hop 0 is peeled so the fused
    # pseudo-gradient transmit (different payload source) stays out of
    # the uniform fori_loop body.
    fused_ops = (a_flat, t_pos, w_pos) if use_fused_tx else None
    accs = _rs_hop_rows(0, accs, k, chunk, bsize, nb, cfg, fused_ops)
    if k > 2:
        accs = jax.lax.fori_loop(
            1, k - 1,
            lambda s, a: _rs_hop_rows(s, a, k, chunk, bsize, nb, cfg),
            accs)

    # Phase 2: all-gather with forwarded codes; owners quantize once,
    # then one fori_loop over hops with every bucket's payload riding
    # the carry (hop-major == the per-bucket order bit-for-bit: buckets
    # write disjoint regions).
    accs, payloads = _ag_init_rows(accs, k, chunk, bsize, nb, cfg)
    accs, _ = jax.lax.fori_loop(
        0, k - 1,
        lambda s, c: _ag_hop_rows(s, c[0], c[1], k, chunk, bsize, nb,
                                  cfg),
        (accs, payloads))

    out_pos = accs[..., :orig_size]
    if cfg.average:
        out_pos = out_pos / jnp.maximum(total_w, 1e-20)
    # out[worker w] lives at ring position inv[w]
    return out_pos[jnp.asarray(inv)]


# -- hop-steppable simulation (overlapped outer sync) ------------------------


_HOP_JIT: dict = {}


def _hop_jit(kind: str, k: int, chunk: int, bsize: int, nb: int,
             cfg: RingConfig):
    """Per-hop jitted wrappers, cached on the static ring geometry so
    repeated outer steps reuse compilations. ``s`` rides as a traced
    scalar: one compilation serves every hop index."""
    key = (kind, k, chunk, bsize, nb, cfg)
    fn = _HOP_JIT.get(key)
    if fn is None:
        # named functions: their programs read jit_ring_<kind> in a trace
        def ring_rs_hop(s, a):
            return _rs_hop_rows(s, a, k, chunk, bsize, nb, cfg)

        def ring_rs_fused_hop(s, a, af, tp, wp):
            return _rs_hop_rows(s, a, k, chunk, bsize, nb, cfg,
                                (af, tp, wp))

        def ring_ag_init(a):
            return _ag_init_rows(a, k, chunk, bsize, nb, cfg)

        def ring_ag_hop(s, a, p):
            return _ag_hop_rows(s, a, p, k, chunk, bsize, nb, cfg)

        body = {"rs": ring_rs_hop, "rs_fused": ring_rs_fused_hop,
                "ag_init": ring_ag_init, "ag": ring_ag_hop}
        if kind not in body:
            raise ValueError(kind)
        fn = _HOP_JIT[key] = jax.jit(body[kind])
    return fn


class RingSyncOp:
    """Host-steppable mirror of :func:`simulate_ring_all_reduce`.

    The same reduce-scatter / all-gather hop math, split at WIRE-HOP
    granularity so a training loop can dispatch one hop between each
    inner-phase scan chunk and hide the ring under compute (the paper's
    overlapped outer sync). ``step()`` dispatches the next hop (async
    on device), ``finish()`` drains the remainder and returns the
    reduced (k, D) result — bit-identical to the one-shot simulator,
    which the tests assert.

    The op RETAINS its inputs (``xs``, ``weights``, ``fused_src``): a
    worker dying mid-overlap leaves the accumulator torn (it already
    absorbed hops that assumed the dead worker would keep forwarding),
    so recovery must re-reduce from the retained pseudo-gradients over
    the survivors — :meth:`restart` — never apply the partial state.
    """

    def __init__(self, xs: jnp.ndarray,
                 ring_order: Sequence[int] | None = None,
                 cfg: RingConfig = RingConfig(),
                 weights: jnp.ndarray | None = None,
                 fused_src=None):
        k, orig_size = xs.shape
        self.k, self.orig_size = k, orig_size
        self.cfg = cfg
        self.xs = xs.astype(jnp.float32)
        self.weights = (jnp.ones((k,), jnp.float32) if weights is None
                        else weights)
        self.ring_order = (tuple(ring_order) if ring_order is not None
                           else tuple(range(k)))
        self.fused_src = fused_src
        self.hops_done = 0
        self._out: jnp.ndarray | None = None
        self._total_w = jnp.sum(self.weights)
        if k == 1:
            self.hops_total = 0
            out = self.xs * self.weights[:, None] / jnp.maximum(
                self._total_w, 1e-20) if cfg.average else self.xs
            self._out = out
            return

        assert sorted(self.ring_order) == list(range(k)), \
            "ring order must be a permutation"
        perm = np.asarray(self.ring_order)
        self._inv = jnp.asarray(np.argsort(perm))
        nb = max(1, cfg.buckets)
        w_pos = self.weights[jnp.asarray(perm)]
        accs = self.xs[perm] * w_pos[:, None]
        accs, chunk, bsize = _pad_to_chunks(accs, k, nb)
        self._accs = accs
        self._chunk, self._bsize, self._nb = chunk, bsize, nb
        self._w_pos = w_pos
        self._fused0 = (fused_src is not None and cfg.fused
                        and cfg.quant == "int8")
        if self._fused0:
            a_flat, thetas = fused_src
            pad = accs.shape[-1] - orig_size
            self._a_flat = jnp.pad(a_flat.astype(jnp.float32), (0, pad))
            self._t_pos = jnp.pad(thetas.astype(jnp.float32)[perm],
                                  [(0, 0), (0, pad)])
        self._payloads = None
        # wire hops: (k-1) reduce-scatter + (k-1) all-gather forwards
        # (the owner-quantize prologue is compute-only and rides with
        # the first all-gather hop)
        self.hops_total = 2 * (k - 1)

    @property
    def pending(self) -> bool:
        return self.hops_done < self.hops_total

    def step(self) -> bool:
        """Dispatch ONE wire hop (async device work); returns True iff
        a hop was dispatched."""
        if self._out is not None or not self.pending:
            return False
        i, k = self.hops_done, self.k
        args = (self.k, self._chunk, self._bsize, self._nb, self.cfg)
        if i < k - 1:
            kind = "rs_fused" if i == 0 and self._fused0 else "rs"
        else:
            kind = "ag"
        with span("sync.hop", hop=i, kind=kind):
            if kind == "rs_fused":
                self._accs = _hop_jit("rs_fused", *args)(
                    jnp.int32(0), self._accs, self._a_flat,
                    self._t_pos, self._w_pos)
            elif kind == "rs":
                self._accs = _hop_jit("rs", *args)(
                    jnp.int32(i), self._accs)
            else:
                s = i - (k - 1)
                if s == 0:
                    self._accs, self._payloads = _hop_jit(
                        "ag_init", *args)(self._accs)
                self._accs, self._payloads = _hop_jit("ag", *args)(
                    jnp.int32(s), self._accs, self._payloads)
        self.hops_done += 1
        return True

    def finish(self) -> jnp.ndarray:
        """Drain any remaining hops and return the (k, D) reduced
        result (identical rows across workers)."""
        if self._out is None:
            while self.pending:
                self.step()
            out_pos = self._accs[..., :self.orig_size]
            if self.cfg.average:
                out_pos = out_pos / jnp.maximum(self._total_w, 1e-20)
            self._out = out_pos[self._inv]
            self._accs = self._payloads = None  # free the in-flight state
        return self._out

    def restart(self, weights: jnp.ndarray) -> jnp.ndarray:
        """Torn-reduction fallback: synchronously re-reduce the RETAINED
        inputs under ``weights`` (dead workers zeroed), discarding the
        partial accumulator. Returns the (k, D) reduced result."""
        return simulate_ring_all_reduce(
            self.xs, ring_order=self.ring_order, cfg=self.cfg,
            weights=weights, fused_src=self.fused_src)

    def norm_sideband(self) -> np.ndarray:
        """(k, k * buckets) per-chunk norm sideband of the retained
        inputs (:func:`chunk_norms`) for the admission layer."""
        return chunk_norms(self.xs, self.cfg.buckets)
