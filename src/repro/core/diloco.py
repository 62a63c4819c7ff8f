"""DiLoCo (Distributed Low-Communication) outer optimization — the heart
of PRIME (INTELLECT-1 §2.1, Alg. 1).

Each DiLoCo worker runs H inner AdamW steps, then all workers synchronize
*pseudo-gradients* ``delta_i = anchor - theta_i`` through the int8 ring
all-reduce and apply a shared Nesterov outer step:

    delta = (1/sum w) * sum_i  w_i (anchor - theta_i)      (elastic weights)
    anchor' = NesterovSGD(anchor, delta)
    theta_i <- anchor'                                      (all workers)

Two synchronization paths, sharing all math:
  * ``outer_sync``     — per-device, inside a shard_map region manual over
    the DiLoCo mesh axis ('pod' across pods, 'data' within one);
  * ``outer_sync_sim`` — stacked (k, ...) single-process mirror used by
    the CPU cluster simulator / examples / tests.

The outer step runs on the **SyncEngine** (``core.sync_engine``): the
anchor is kept as a persistent flat fp32 buffer (``OuterState.anchor_flat``)
so the pseudo-gradient is one subtract off the buffer instead of a
flatten of two pytrees, the outer Nesterov update runs in flat space,
and the flat (anchor, theta) pair feeds the ring's fused first-hop
transmit quantizer. See ``docs/sync_pipeline.md`` for the dataflow.

The anchor is kept in fp32 (it is the paper's CPU-offloaded master copy;
on TPU it can live in ``pinned_host`` memory — see
``sharding.plans.outer_state_sharding``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core import compression
from repro.core.ring_reduce import (RingConfig, RingSyncOp,
                                    ring_all_reduce, ring_wire_bytes,
                                    simulate_ring_all_reduce)
from repro.core.sync_engine import SyncEngine
from repro.kernels import ops as qops
from repro.optim.nesterov import NesterovSGD, NesterovState
from repro.tracing import span


class SyncAbortedError(RuntimeError):
    """An in-flight outer sync was aborted (trainer teardown or a
    rejected/discarded reduction); its result must never be applied."""


@dataclasses.dataclass(frozen=True)
class DiLoCoConfig:
    inner_steps: int = 100          # H (paper: 100; DiLoCo paper: up to 500)
    outer_lr: float = 0.7
    outer_momentum: float = 0.9
    quant: str = "int8"             # 'int8' | 'fp32' | 'int4'
    quant_impl: str = "jnp"         # 'jnp' | 'pallas'
    sync_buckets: int = 1           # sub-buckets per ring chunk-hop
    fused_sync: bool = True         # fused tx/rx kernels in the ring
    # 'none'    — synchronous outer step (the ring is a barrier between
    #             inner phases; the paper's fallback mode);
    # 'delayed' — the quantized ring runs UNDER the next inner phase
    #             (hops dispatched between scan chunks) and the reduced
    #             pseudo-gradient is applied one phase late (the
    #             paper's overlapped outer sync, §2.2 utilization).
    overlap: str = "none"
    error_feedback: bool = False    # beyond-paper (see core.compression)
    host_offload_outer: bool = False  # TPU-only placement flag
    # hierarchical reduce (paper's ElasticDeviceMesh split): each device
    # rings only its intra-node slice over the WAN (DiLoCo) axis and the
    # full vector is rebuilt intra-node — per-device WAN bytes / n_local.
    # Distributed backend only (train.step.DistSyncBackend); codebooks
    # become per-slice, so results are bit-identical to the PER-SLICE
    # simulator rather than the flat one (tested).
    hierarchical: bool = False

    @property
    def ring(self) -> RingConfig:
        return RingConfig(quant=self.quant, impl=self.quant_impl,
                          buckets=self.sync_buckets,
                          fused=self.fused_sync)

    @property
    def outer_opt(self) -> NesterovSGD:
        return NesterovSGD(lr=self.outer_lr, momentum=self.outer_momentum)


class OuterState(NamedTuple):
    anchor: Any                # fp32 pytree: theta at the last outer step
    opt: NesterovState         # fp32 outer momentum
    residual: Any              # fp32 flat EF residual (zeros if disabled)
    outer_step: jnp.ndarray
    anchor_flat: Any = None    # persistent flat fp32 anchor (SyncEngine);
    #                            None -> re-derived from ``anchor``.  Must
    #                            match the local view of ``anchor`` (i.e.
    #                            leave it None inside shard_map regions
    #                            where the anchor leaves are shards).


def init_outer_state(params, cfg: DiLoCoConfig) -> OuterState:
    anchor = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    eng = SyncEngine.for_tree(anchor)
    opt = cfg.outer_opt.init(anchor)
    n = eng.numel if cfg.error_feedback else 0
    if cfg.error_feedback and cfg.overlap == "delayed":
        # two-slot residual: the delayed overlap interleaves two anchor
        # lineages (see finish_outer_sync_sim), so EF keeps one residual
        # per lineage — boundary t reads/writes slot t % 2 only
        residual = jnp.zeros((2, n), jnp.float32)
    else:
        residual = jnp.zeros((n,), jnp.float32)
    return OuterState(anchor, opt, residual, jnp.zeros((), jnp.int32),
                      eng.flatten(anchor))


def init_outer_state_sim(params_one_worker, cfg: DiLoCoConfig,
                         k: int) -> OuterState:
    """Outer state for the stacked single-process simulator: shared
    anchor/momentum, per-worker EF residuals ((2, k, n) under the
    delayed overlap — one slot per interleaved lineage)."""
    st = init_outer_state(params_one_worker, cfg)
    n = st.residual.shape[-1]
    if st.residual.ndim == 2:
        return st._replace(residual=jnp.zeros((2, k, n), jnp.float32))
    return st._replace(residual=jnp.zeros((k, n), jnp.float32))


def _ef_roundtrip(pg: jnp.ndarray, cfg: DiLoCoConfig) -> jnp.ndarray:
    """Quantize/dequantize roundtrip used by error feedback."""
    if cfg.quant == "int8":
        q = qops.quantize(pg, impl=cfg.quant_impl)
        return qops.dequantize(q, impl=cfg.quant_impl)
    q = compression.quantize4(pg)
    return compression.dequantize4(q, pg.shape)


def _pseudograd(params, state: OuterState, cfg: DiLoCoConfig):
    """Flat fp32 pseudo-gradient (+EF residual) off the persistent
    anchor buffer. Returns (pg, new_residual, theta_flat, anchor_flat)."""
    eng = SyncEngine.for_tree(params)
    p_flat = eng.flatten(params)
    a_flat = (state.anchor_flat if state.anchor_flat is not None
              else eng.flatten(state.anchor))
    pg = a_flat - p_flat
    new_residual = state.residual
    if cfg.error_feedback:
        if state.residual.ndim == 2:
            # two-slot (delayed cfg on the synchronous distributed
            # path): outer_step advances once per sync, so its parity
            # alternates slots — each lineage's residual round-trips
            # through its own slot
            slot = jnp.mod(state.outer_step, 2)
            res = jax.lax.dynamic_index_in_dim(
                state.residual, slot, 0, keepdims=False)
            pg = pg + res
            deq = _ef_roundtrip(pg, cfg)
            new_residual = jax.lax.dynamic_update_index_in_dim(
                state.residual, pg - deq, slot, 0)
            pg = deq
        else:
            pg = pg + state.residual
            deq = _ef_roundtrip(pg, cfg)
            new_residual = pg - deq
            pg = deq
    return pg, new_residual, p_flat, a_flat


def _apply_outer(reduced_pg_flat, params, state: OuterState,
                 cfg: DiLoCoConfig, new_residual, a_flat):
    """Flat-space outer Nesterov step + a single unflatten per output
    tree (bit-identical to the per-leaf formulation)."""
    eng = SyncEngine.for_tree(state.anchor)
    m_flat = eng.flatten(state.opt.momentum)
    new_a_flat, new_m_flat = cfg.outer_opt.update_flat(
        reduced_pg_flat, m_flat, a_flat)
    new_anchor = eng.unflatten(new_a_flat)
    new_opt = NesterovState(eng.unflatten(new_m_flat))
    new_params = eng.unflatten(new_a_flat, like=params)
    return new_params, OuterState(new_anchor, new_opt, new_residual,
                                  state.outer_step + 1, new_a_flat)


def _fused_src_ok(cfg: DiLoCoConfig) -> bool:
    """The fused first-hop transmit sends quantize(w*(anchor-theta))
    straight off the model buffers — only valid when the wire payload IS
    the raw pseudo-gradient (no EF rewrite) and the ring is int8."""
    return cfg.fused_sync and cfg.quant == "int8" and \
        not cfg.error_feedback


# -- distributed path (inside shard_map, manual over `axis_name`) ------------


def outer_sync(params, state: OuterState, cfg: DiLoCoConfig,
               axis_name: str, ring_order: Sequence[int] | None = None,
               weight: jnp.ndarray | None = None):
    """One DiLoCo outer step for this worker. Returns (params', state')."""
    pg, new_residual, p_flat, a_flat = _pseudograd(params, state, cfg)
    fused_src = (a_flat, p_flat) if _fused_src_ok(cfg) else None
    reduced = ring_all_reduce(pg, axis_name, ring_order=ring_order,
                              cfg=cfg.ring, weight=weight,
                              fused_src=fused_src)
    return _apply_outer(reduced, params, state, cfg, new_residual, a_flat)


# -- single-process simulation (stacked workers) ------------------------------


def _sim_pseudograds(stacked_params, state: OuterState,
                     cfg: DiLoCoConfig, ef_slot: int = 0):
    """Shared boundary front half of the sim outer step: stacked flat
    pseudo-gradients (+EF rewrite) off the persistent anchor buffer.
    Returns (k, any_params, a_flat, pgs, new_residuals, fused_src).

    With a two-slot residual buffer ((2, k, n): EF + delayed overlap)
    only row ``ef_slot`` is read, and ``new_residuals`` is that slot's
    (k, n) replacement — the caller commits it via
    :func:`_commit_residual` so the write lands on the CURRENT state.

    The anchor flatten is hoisted out of the worker dimension (the seed
    re-flattened the full anchor pytree once per worker inside a vmap);
    per-worker work is a single vmapped flatten + subtract.
    """
    with span("sync.pseudograds"):
        k = jax.tree.leaves(stacked_params)[0].shape[0]
        any_params = jax.tree.map(lambda p: p[0], stacked_params)
        eng = SyncEngine.for_tree(any_params)

        a_flat = (state.anchor_flat if state.anchor_flat is not None
                  else eng.flatten(state.anchor))
        p_flats = jax.vmap(eng.flatten)(stacked_params)
        pgs = a_flat[None, :] - p_flats
        new_residuals = state.residual
        if cfg.error_feedback:
            two_slot = state.residual.ndim == 3
            res = state.residual[ef_slot] if two_slot else state.residual
            pgs = pgs + res
            deqs = jax.vmap(lambda pg: _ef_roundtrip(pg, cfg))(pgs)
            new_residuals = pgs - deqs
            pgs = deqs

    fused_src = (a_flat, p_flats) if _fused_src_ok(cfg) else None
    return k, any_params, a_flat, pgs, new_residuals, fused_src


def _commit_residual(state: OuterState, new_residuals, ef_slot: int):
    """Merge a boundary's EF residual into the state's buffer. In
    two-slot mode only the boundary's OWN slot is written — and it is
    written against the residual buffer as it stands at commit time,
    not the begin-time snapshot, so an interleaved commit of the other
    lineage is never clobbered (this is what makes EF safe under the
    delayed overlap)."""
    if state.residual.ndim == 3:
        return state.residual.at[ef_slot].set(new_residuals)
    return new_residuals


def outer_sync_sim(stacked_params, state: OuterState, cfg: DiLoCoConfig,
                   ring_order: Sequence[int] | None = None,
                   weights: jnp.ndarray | None = None,
                   ef_slot: int = 0):
    """Mirror of ``outer_sync`` over stacked (k, ...) worker params with a
    SHARED outer state. Residuals are per-worker when EF is on."""
    k, any_params, a_flat, pgs, new_residuals, fused_src = \
        _sim_pseudograds(stacked_params, state, cfg, ef_slot=ef_slot)
    reduced = simulate_ring_all_reduce(pgs, ring_order=ring_order,
                                       cfg=cfg.ring, weights=weights,
                                       fused_src=fused_src)
    res = _commit_residual(state, new_residuals, ef_slot)
    # every worker's reduced copy is identical -> apply outer once
    new_params, new_state = _apply_outer(
        reduced[0], any_params, state._replace(residual=res),
        cfg, res, a_flat)
    stacked_new = jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (k,) + p.shape), new_params)
    return stacked_new, new_state


# -- overlapped outer sync (begin / finish pair, sim path) -------------------


class OuterSyncHandle:
    """One boundary's outer sync in flight (sim path).

    Created by :func:`begin_outer_sync_sim` at an outer boundary: the
    pseudo-gradients are computed and the first-hop quantization can be
    dispatched immediately; the remaining ring hops are dispatched by
    the trainer between inner-phase scan chunks (``step()``), and the
    reduced result is applied with a one-phase delay by
    :func:`finish_outer_sync_sim`. ``cfg.overlap == 'none'`` degenerates
    to begin+finish back-to-back at the same boundary, which is
    bit-identical to :func:`outer_sync_sim` (the ring op is bit-exact
    against the one-shot simulator and the apply path is shared).

    The handle retains the pseudo-gradient rows: when a participant
    dies mid-overlap the torn partial reduction is discarded and
    :func:`resync_outer_sim` re-reduces the retained rows over the
    survivors.
    """

    def __init__(self, op: RingSyncOp, cfg: DiLoCoConfig, a_flat,
                 new_residuals, weights, k: int, ef_slot: int = 0):
        self.op = op
        self.cfg = cfg
        # the anchor SNAPSHOT the pseudo-gradients are rooted at: the
        # delayed apply lands on this snapshot (see
        # finish_outer_sync_sim for why), so the handle must carry it
        # across the interleaved apply of the previous boundary
        self.a_flat = a_flat
        # EF residual produced at begin time. Two-slot mode: the (k, n)
        # replacement for residual slot ``ef_slot`` only — committed
        # into the commit-time state by _commit_residual, never as a
        # whole-buffer overwrite (a begin-time snapshot of the buffer
        # would resurrect the other lineage's stale residual)
        self.new_residuals = new_residuals
        self.ef_slot = ef_slot
        self.weights = weights
        self.k = k
        self.aborted = False

    def step(self) -> bool:
        """Dispatch the next ring hop; True iff one was dispatched."""
        if self.aborted:
            return False
        return self.op.step()

    @property
    def hops_total(self) -> int:
        return 0 if self.aborted else self.op.hops_total

    @property
    def hops_done(self) -> int:
        return 0 if self.aborted else self.op.hops_done

    def abort(self) -> None:
        """Discard this boundary's sync: drop the staged accumulators
        and retained inputs so nothing can be applied. Further
        ``finish``/``resync`` raises :class:`SyncAbortedError`."""
        self.aborted = True
        self.op = None

    def norm_sideband(self):
        """(k, k * buckets) per-chunk norm sideband of the retained
        pseudo-gradient rows (admission layer / localization)."""
        if self.aborted:
            raise SyncAbortedError("norm_sideband on an aborted sync")
        return self.op.norm_sideband()

    def sanitize(self, slots) -> None:
        """Zero the retained rows of ``slots`` so a subsequent
        ``restart`` re-reduces only clean contributions.

        Zero-WEIGHTING a corrupted row is NOT enough: ``NaN * 0 == NaN``
        and the op's staged accumulators were built from the raw rows,
        so after sanitizing the caller must RESTART the reduction (the
        staged partial state is contaminated and is discarded by
        ``restart``), never ``finish`` it.
        """
        if self.aborted:
            raise SyncAbortedError("sanitize on an aborted sync")
        if not slots:
            return
        idx = jnp.asarray(sorted(slots), dtype=jnp.int32)
        op = self.op
        op.xs = op.xs.at[idx].set(0.0)
        if op.fused_src is not None:
            # fused first-hop tx reads (anchor, thetas): a zero row in
            # pg-space means theta == anchor for that slot
            a_flat, thetas = op.fused_src
            thetas = thetas.at[idx].set(a_flat)
            op.fused_src = (a_flat, thetas)
        if self.cfg.error_feedback:
            # the EF rewrite folded the corrupted rows into the new
            # residuals — a poisoned residual would re-inject NaNs into
            # the NEXT boundary's pseudo-gradients
            self.new_residuals = self.new_residuals.at[idx].set(0.0)


def begin_outer_sync_sim(stacked_params, state: OuterState,
                         cfg: DiLoCoConfig,
                         ring_order: Sequence[int] | None = None,
                         weights: jnp.ndarray | None = None,
                         ef_slot: int = 0) -> OuterSyncHandle:
    """Boundary front half: compute + quantize the pseudo-gradients and
    stage the ring as a steppable op. Nothing is applied yet.

    ``ef_slot`` (two-slot EF under the delayed overlap): the residual
    lineage this boundary belongs to. The trainer alternates 0/1 per
    begin, so boundary t reads the residual written by boundary t-2 —
    whose sync has, with at most one handle in flight, always landed by
    then. (``state.outer_step`` parity is NOT usable as the slot: the
    first two begins both observe outer_step == 0.)"""
    k, _, a_flat, pgs, new_residuals, fused_src = _sim_pseudograds(
        stacked_params, state, cfg, ef_slot=ef_slot)
    if weights is None:
        weights = jnp.ones((k,), jnp.float32)
    op = RingSyncOp(pgs, ring_order=ring_order, cfg=cfg.ring,
                    weights=weights, fused_src=fused_src)
    return OuterSyncHandle(op, cfg, a_flat, new_residuals, weights, k,
                           ef_slot=ef_slot)


def _finish_apply(handle: OuterSyncHandle, reduced, stacked_params,
                  state: OuterState):
    if handle.aborted:
        raise SyncAbortedError("apply on an aborted sync")
    with span("sync.apply"):
        any_params = jax.tree.map(lambda p: p[0], stacked_params)
        res = _commit_residual(state, handle.new_residuals,
                               handle.ef_slot)
        new_params, new_state = _apply_outer(
            reduced[0], any_params, state._replace(residual=res),
            handle.cfg, res, handle.a_flat)
        stacked_new = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (handle.k,) + p.shape),
            new_params)
    return stacked_new, new_state


def finish_outer_sync_sim(handle: OuterSyncHandle, stacked_params,
                          state: OuterState):
    """Drain the remaining hops and apply the reduced pseudo-gradient
    to the anchor SNAPSHOT it was computed against (the handle's
    ``a_flat``, flat-space Nesterov), then reset every worker to the
    new tip.

    This is deliberate and NOT the "apply to the current anchor"
    stale-gradient convention. Under the trainer's boundary order
    (begin new -> finish old), the anchor at finish time has already
    absorbed the PREVIOUS boundary's delta, so tip t is built as
    ``T_t = Nesterov(T_{t-2}, Delta_{t-1})`` — two interleaved
    lineages, each advanced by exactly the synchronous DiLoCo rule
    (every delta applies to the very anchor its pseudo-gradients are
    rooted at, zero base-mismatch; workers hop to the newest tip each
    boundary, so the next pseudo-gradient re-derives from it and no
    signal is lost; the shared outer momentum threads sequentially
    through every apply and mixes the lineages). The alternative —
    applying Delta_{t-1} on top of tip T_{t-1} — compounds two
    same-rooted progress segments under the 0.7/0.9 outer Nesterov and
    measurably overshoots: 40–120% worse held-out anchor loss on the
    BENCH_sync overlap scenario, vs ~3% for this formulation
    (delayed-vs-synchronous, same data/steps)."""
    if handle.aborted:
        raise SyncAbortedError("finish on an aborted sync")
    return _finish_apply(handle, handle.op.finish(), stacked_params,
                         state)


def resync_outer_sim(handle: OuterSyncHandle, stacked_params,
                     state: OuterState, weights: jnp.ndarray):
    """Torn-overlap fallback: a participant died while the reduction
    was on the wire, so the partial accumulator can never be applied
    (it absorbed hops the dead worker will not forward). Re-reduce the
    RETAINED pseudo-gradients synchronously over the survivors
    (``weights`` with the dead workers zeroed) and apply — every
    survivor derives the identical result from identical retained
    inputs, so recovery is bit-consistent."""
    if handle.aborted:
        raise SyncAbortedError("resync on an aborted sync")
    return _finish_apply(handle, handle.op.restart(weights),
                         stacked_params, state)


def sync_wire_bytes(params, n_workers: int, cfg: DiLoCoConfig) -> int:
    """Per-worker wire bytes of ONE outer sync (benchmark helper)."""
    n = sum(l.size for l in jax.tree.leaves(params))
    return ring_wire_bytes(n, n_workers, cfg.quant,
                           buckets=cfg.sync_buckets)


def bandwidth_reduction_factor(cfg: DiLoCoConfig,
                               dp_bytes_per_step: float = 4.0) -> float:
    """Communication-volume reduction vs per-step fp32 data-parallel
    (paper: 400x at H=100/int8, ~2000x at H=500)."""
    bytes_per_elem = {"int8": 1.0, "int4": 0.5, "fp32": 4.0}[cfg.quant]
    return cfg.inner_steps * dp_bytes_per_step / bytes_per_elem
