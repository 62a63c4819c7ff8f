"""ElasticTrainer: the full PRIME training loop.

Per outer step t (paper Alg. 1 + §2.4):
  1. ``ClusterSimulator.begin_outer_step`` applies membership events
     (join / graceful leave / crash / straggler) — heartbeat sweep
     evicts silent nodes; joiners are admitted at this boundary and
     P2P-fetch the latest checkpoint (blocking or non-blocking mode);
  2. every live worker runs H inner AdamW steps on its data shard;
  3. the bandwidth monitor re-solves the max-min ring order if links
     drifted (a changed order recompiles the sync step — same cost the
     paper pays re-rendezvousing process groups);
  4. the int8 ring all-reduce averages pseudo-gradients over live
     workers (weight 0 for joiners/stragglers) with the RetryPolicy
     excluding workers that die mid-collective;
  5. the shared Nesterov outer step updates the anchor; all workers
     reset to it; async checkpoint.

This class runs the *stacked single-process simulation* (k workers on
one device) so the complete protocol is testable on CPU; the
distributed path shares every component (see train/step.py builders +
launch/train.py) and the two are bit-equivalence-tested in
tests/test_distributed.py.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import diloco as dl
from repro.core import ring_reduce as rr
from repro.core import topology
from repro.core import validation as vd
from repro.core.elastic_mesh import SlotAssignment
from repro.core.fault_tolerance import (ClusterSimulator,
                                        CommOverlapLedger, RetryPolicy)
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.optim.adamw import AdamW
from repro.tracing import span


@dataclasses.dataclass
class TrainerConfig:
    diloco: dl.DiLoCoConfig
    inner_lr: float | Callable = 7.5e-5
    ckpt_dir: str | None = None
    ckpt_every_outer: int = 1
    # 'flat'  — npy-per-leaf dirs (seed layout, CheckpointServer-served)
    # 'store' — content-addressed chunk store (dedup + swarm-fetchable)
    # 'delta' — chunk store + int8/int4 delta chain between base anchors
    ckpt_engine: str = "flat"
    ckpt_delta_base_every: int = 8
    ckpt_codec: str = "int8"       # delta codec: 'int8' | 'int4'
    ckpt_chunk_bytes: int = 1 << 20
    # retention: keep the newest N store/delta checkpoints, gc the rest
    # (ChunkStore.gc keeps delta chains restorable; runs FIFO behind
    # the async persists). None = keep everything (seed behavior).
    ckpt_keep: int | None = None
    max_workers: int = 16
    blocking_join: bool = True     # paper used blocking in production
    seconds_per_outer_step: float = 60.0
    # inner phase as C jitted scan chunks instead of one monolithic
    # scan: the gaps between chunks are the host's interleave points
    # where in-flight ring hops are dispatched (diloco.overlap =
    # 'delayed'). <=2 distinct chunk lengths -> <=2 compilations.
    inner_chunks: int = 1
    # modeled WAN link for the CommOverlapLedger's logical-time
    # hidden/exposed accounting (paper: ~4 Gb/s internet links);
    # used only until the BandwidthMonitor has observed every edge of
    # the current ring — then the ring's actual bottleneck link rules
    sync_link_bytes_per_s: float = 500e6
    # unit conversion for BandwidthMonitor matrices (Gb/s -> bytes/s)
    link_bytes_per_gbps: float = 125e6
    # contribution-admission layer (untrusted-contributor defense):
    # None disables it; with a ValidationConfig every outer sync's
    # pseudo-gradients pass the admission gates BEFORE any reduced
    # value is applied, flagged contributors are sanitized out of the
    # reduce and quarantined via the ClusterSimulator's reputation
    # state machine (see core/validation.py, docs/sync_pipeline.md)
    validation: vd.ValidationConfig | None = None


class ElasticTrainer:
    def __init__(self, model, cfg: TrainerConfig, data_cfg: DataConfig,
                 init_params, sim: ClusterSimulator, *,
                 batch_provider: Callable | None = None,
                 boundary_hook: Callable | None = None,
                 sync_backend=None):
        self.model = model
        self.cfg = cfg
        self.data_cfg = data_cfg
        self.sim = sim
        # sync_backend (train.step.DistSyncBackend) stages the outer
        # sync as real per-hop shard_map collectives over a mesh's
        # DiLoCo axis instead of the single-device simulator ring;
        # bit-identical by construction, so everything downstream of
        # begin() is shared
        self.sync_backend = sync_backend
        # batch_provider(global_step, h, k) -> stacked (H, k, ...) batch
        # pytree: replaces the TokenPipeline feed (the RL tier's
        # rollout-buffer batcher plugs in here); boundary_hook(t, self)
        # runs after each outer boundary's sync + bookkeeping — the RL
        # PolicyPublisher ships the fresh anchor from it
        self.batch_provider = batch_provider
        self.boundary_hook = boundary_hook
        self.optimizer = AdamW(lr=cfg.inner_lr)
        self.retry = RetryPolicy()
        live = sim.hb.live_ids()
        self.slots = SlotAssignment(cfg.max_workers)
        for nid in live:
            self.slots.assign(nid)
        k = cfg.max_workers
        self.k = k
        # stacked worker state (slot-major)
        stack = lambda t: jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (k,) + x.shape), t)
        self.params = stack(init_params)
        self.opt_state = jax.vmap(self.optimizer.init)(self.params)
        self.outer = dl.init_outer_state_sim(init_params, cfg.diloco, k)
        self.bw = topology.BandwidthMonitor(k)
        self.ring_order = tuple(range(k))
        self.inner_phase_jit = jax.jit(self._inner_phase)
        # overlapped outer sync (diloco.overlap == 'delayed'): the
        # in-flight handle spans one inner phase; its ring hops are
        # dispatched between scan chunks and the reduced result is
        # applied at the NEXT boundary
        self.overlap = cfg.diloco.overlap == "delayed"
        if self.overlap and cfg.inner_chunks <= 1:
            import warnings
            warnings.warn(
                "overlap='delayed' with inner_chunks<=1: the inner "
                "phase has no interleave points, so all but the first "
                "ring hop drain EXPOSED at the boundary — you pay the "
                "delayed-application schedule without hiding the "
                "communication. Set TrainerConfig.inner_chunks >= "
                f"2*(k-1)+1 = {2 * (cfg.max_workers - 1) + 1} to hide "
                "the whole ring.", stacklevel=2)
        self._inflight: dl.OuterSyncHandle | None = None
        # two-slot EF lineage counter: alternates 0/1 per begin so each
        # overlapped boundary reads/writes its own residual slot (see
        # diloco.begin_outer_sync_sim; persists across run() calls)
        self._ef_begins = 0
        self.comm_ledger = CommOverlapLedger()
        # bandwidth-honest ledger window: the sim ring dispatches
        # 2*(k-1) hops, but only the live workers' 2*(n_live-1) carry
        # bytes on a real cluster — the rest are charged 0s
        self._live_hops = 0
        self._window_hop_i = 0
        self.reorders = 0            # accepted ring reorders (recompiles)
        # contribution admission: running cross-step norm statistics +
        # a log of every sanitize/quarantine decision
        self._adm_stats = (vd.AdmissionStats(cfg.validation)
                           if cfg.validation is not None else None)
        self.quarantine_events: list[dict] = []
        self.history: list[dict] = []
        self._pipelines = {}
        self.ckpt_store = None
        self.snapshotter = None
        self._ckpt_steps: list[int] = []
        self.persisted_steps: list[int] = []   # on disk, not just queued
        self._stream_join = None               # in-flight StreamingFetcher
        if cfg.ckpt_dir and cfg.ckpt_engine != "flat":
            from repro.checkpointing import (AsyncSnapshotter, ChunkStore,
                                             DeltaCheckpointer,
                                             DeltaConfig)
            self.ckpt_store = ChunkStore(
                cfg.ckpt_dir, chunk_bytes=cfg.ckpt_chunk_bytes)
            if cfg.ckpt_engine == "delta":
                writer = DeltaCheckpointer(
                    self.ckpt_store,
                    DeltaConfig(base_every=cfg.ckpt_delta_base_every,
                                codec=cfg.ckpt_codec,
                                quant_impl=cfg.diloco.quant_impl))
                write_fn = writer.save
            elif cfg.ckpt_engine == "store":
                write_fn = self.ckpt_store.save_tree
            else:
                raise ValueError(
                    f"unknown ckpt_engine {cfg.ckpt_engine!r}")
            # double-buffered: persists overlap the next inner phase,
            # bounded memory, FIFO so the delta reference chain is
            # written in step order; on_persist tracks what is actually
            # on disk — the retention gc keep-set reads it at task
            # execution time, so gc can never count an in-flight save
            self.snapshotter = AsyncSnapshotter(
                write_fn,
                on_persist=lambda step, _m:
                    self.persisted_steps.append(step))

    # -- inner phase ----------------------------------------------------------

    def _inner_step(self, params, opt_state, batch, active):
        """One vmapped inner step; inactive slots are frozen."""
        def one(p, o, b):
            (_, metrics), g = jax.value_and_grad(
                self.model.loss, has_aux=True)(p, b)
            new_p, new_o = self.optimizer.update(g, o, p)
            return new_p, new_o, metrics

        new_p, new_o, metrics = jax.vmap(one)(params, opt_state, batch)
        keep = lambda new, old: jax.tree.map(
            lambda n, o: jnp.where(
                active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
            new, old)
        return keep(new_p, params), keep(new_o, opt_state), metrics

    def _inner_phase(self, params, opt_state, batches, active):
        """All H inner steps as ONE ``lax.scan`` over pre-stacked
        (H, k, ...) batches: a single jit dispatch per outer step, and
        only the (H, k) loss trace is retained on device instead of H
        full metric pytrees."""
        def body(carry, batch):
            p, o = carry
            p, o, metrics = self._inner_step(p, o, batch, active)
            return (p, o), metrics["loss"]

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), batches)
        return params, opt_state, losses

    def _run_inner_phase(self, batches, active):
        """Run the inner phase as ``cfg.inner_chunks`` jitted scan
        chunks (near-equal lengths: at most 2 distinct shapes, so at
        most 2 compilations). The gap after each chunk is a host
        interleave point: one in-flight ring hop is dispatched there,
        hiding the outer sync's communication under compute. Chunking
        only moves the jit boundary — the per-step scan body is
        unchanged, so the loss trajectory is bit-identical to the
        monolithic scan (tested)."""
        h = jax.tree.leaves(batches)[0].shape[0]
        c = max(1, min(int(self.cfg.inner_chunks), h))
        sec_per_step = self.cfg.seconds_per_outer_step / max(1, h)
        if c == 1:
            self.params, self.opt_state, losses = self.inner_phase_jit(
                self.params, self.opt_state, batches, active)
            if self.overlap:
                self.comm_ledger.compute(h * sec_per_step)
            return losses
        bounds = np.linspace(0, h, c + 1).astype(int)
        losses = []
        for ci in range(c):
            lo, hi = int(bounds[ci]), int(bounds[ci + 1])
            if hi == lo:
                continue
            part = jax.tree.map(lambda x: x[lo:hi], batches)
            self.params, self.opt_state, l = self.inner_phase_jit(
                self.params, self.opt_state, part, active)
            losses.append(l)
            if self.overlap:
                self.comm_ledger.compute((hi - lo) * sec_per_step)
                if self._inflight is not None and self._inflight.step():
                    self._dispatch_ledger_hop()
        return jnp.concatenate(losses, axis=0)

    def _pipeline(self, slot: int) -> TokenPipeline:
        if slot not in self._pipelines:
            self._pipelines[slot] = TokenPipeline(
                self.data_cfg, slot, self.k)
        return self._pipelines[slot]

    def _batches(self, step: int):
        bs = [self._pipeline(s).batch_at(step) for s in range(self.k)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *bs)

    # -- outer loop -----------------------------------------------------------

    def run(self, n_outer_steps: int, *, inner_steps: int | None = None,
            bandwidth_sampler=None) -> list[dict]:
        h = inner_steps or self.cfg.diloco.inner_steps
        global_step = int(self.outer.outer_step) * h
        for t in range(n_outer_steps):
            with span("train.outer_step", step=len(self.history)):
                global_step = self._outer_step(t, h, global_step,
                                               bandwidth_sampler)
        # drain: the last boundary's sync is still in flight — apply it
        # so the returned anchor includes the final phase's progress
        if self._inflight is not None:
            with span("train.outer_sync"):
                self._drain_hops(self._inflight)
                self.history[-1].setdefault("overlap", {})["drain"] = \
                    self.comm_ledger.finish_sync()
                self.params, self.outer = dl.finish_outer_sync_sim(
                    self._inflight, self.params, self.outer)
            self._inflight = None
            self.sim.note_sync_end()
        if self.snapshotter is not None:
            self.snapshotter.flush()
        return self.history

    def _outer_step(self, t: int, h: int, global_step: int,
                    bandwidth_sampler) -> int:
        """One outer step of ``run``: membership, feed, inner phase,
        outer sync, loss readback and bookkeeping. Returns the global
        inner-step count after it."""
        plan = self.sim.begin_outer_step(t)
        # a participant of the in-flight overlapped sync left the
        # cluster: the partial reduction is torn — fall back to a
        # synchronous re-reduction over the survivors BEFORE the
        # dead node's slot is released (we need its slot to zero
        # its weight)
        fallback_rec = None
        if self._inflight is not None and plan.get("sync_torn"):
            fallback_rec = self._fallback_resync(plan)
        live_slots = self._sync_membership(plan)
        active = jnp.asarray(
            self.slots.live_mask(plan["live"]), jnp.float32)

        with span("train.feed"):
            if self.batch_provider is not None:
                batches = self.batch_provider(global_step, h, self.k)
            else:
                batches = jax.tree.map(
                    lambda *xs: jnp.stack(xs),
                    *[self._batches(global_step + i) for i in range(h)])
        with span("train.inner_phase"):
            losses = self._run_inner_phase(batches, active)
        global_step += h

        with span("train.outer_sync"):
            # fault-harness POISON events corrupt the scheduled nodes'
            # contributions AFTER the inner phase, before the sync —
            # exactly what a faulty peer injects into the ring
            self._apply_poison(plan.get("poison", {}), t)

            # bandwidth-aware ring re-ordering (paper §2.5)
            if bandwidth_sampler is not None:
                self.bw.observe_matrix(bandwidth_sampler(t))
                changed, order = self.bw.maybe_reorder()
                if changed:
                    self.ring_order = order
                    self.reorders += 1

            # elastic weighted sync with mid-collective retry;
            # re-admitted (probation-complete) nodes re-enter like
            # joiners: zero weight for their first round
            weights = self.slots.live_mask(
                plan["live"],
                zero_weight_ids=plan["joined"] + plan["stragglers"]
                + list(plan.get("readmitted", ())))

            adm_report = None
            if self.overlap:
                overlap_rec = self._overlapped_boundary(t, weights)
                adm_report = overlap_rec.pop("_report", None)
                attempts = 1
            elif self._validation_on():
                overlap_rec = None
                adm_report = self._validated_outer_sync(t, weights)
                attempts = 1
            else:
                overlap_rec = None

                def attempt(live_set):
                    w = np.array(weights)
                    for nid, slot in self.slots.slot_of.items():
                        if nid not in live_set:
                            w[slot] = 0.0
                    return self._outer_sync(jnp.asarray(w))

                (self.params, self.outer), _, attempts = \
                    self.retry.run_collective(attempt, plan["live"])

        with span("train.readback"):
            mean_loss = float(losses[-1][
                jnp.asarray(weights) > 0].mean()) if np.any(
                np.asarray(weights) > 0) else float("nan")
        with span("train.bookkeeping"):
            rec = {"outer_step": t, "live": plan["live"],
                   "joined": plan["joined"], "left": plan["left"],
                   "loss": mean_loss, "ring_order": self.ring_order,
                   "attempts": attempts,
                   "wire_bytes": dl.sync_wire_bytes(
                       jax.tree.map(lambda p: p[0], self.params),
                       max(1, int(np.sum(np.asarray(weights) > 0))),
                       self.cfg.diloco)}
            if overlap_rec is not None:
                rec["overlap"] = overlap_rec
            if adm_report is not None:
                rec["admission"] = {
                    "accepted": adm_report.accepted,
                    "flagged": {s: list(r) for s, r in
                                adm_report.flagged.items()},
                    "quarantined": list(adm_report.quarantined_nodes)}
            if fallback_rec is not None:
                rec["sync_fallback"] = fallback_rec
            # streamed recovery that completed during this inner phase
            # is adopted HERE — the paper's overlapped onboarding: the
            # fetch ran under compute, admission costs one restore
            join_rec = self.poll_stream_join()
            if join_rec is not None:
                rec["stream_join"] = join_rec
            if self.boundary_hook is not None:
                hook_rec = self.boundary_hook(t, self)
                if hook_rec:
                    rec["boundary_hook"] = hook_rec
            self.history.append(rec)

            if self.cfg.ckpt_dir and \
                    (t + 1) % self.cfg.ckpt_every_outer == 0:
                tree = {"params": jax.tree.map(
                            lambda p: p[0], self.params),
                        "outer_momentum": self.outer.opt.momentum,
                        "anchor": self.outer.anchor}
                meta = {"outer_step": t + 1}
                if self.snapshotter is not None:
                    self.snapshotter.submit(global_step, tree, meta)
                    self._ckpt_steps.append(global_step)
                    if self.cfg.ckpt_keep and self.ckpt_store and \
                            len(self._ckpt_steps) > self.cfg.ckpt_keep:
                        # the keep set is computed when the task RUNS
                        # (FIFO behind every pending persist), from
                        # what is actually on disk by then — never
                        # from steps still in flight
                        keep = self.cfg.ckpt_keep
                        self.snapshotter.submit_task(
                            lambda k=keep: self.ckpt_store.gc(
                                keep_steps=tuple(
                                    self.persisted_steps[-k:])))
                else:
                    from repro.checkpointing import save_async
                    save_async(self.cfg.ckpt_dir, global_step, tree,
                               meta)
        return global_step

    def begin_stream_join(self, peers, *, store_root=None,
                          step: int | None = None,
                          range_chunks: int = 8, timeout: float = 20.0):
        """Start an overlapped streaming recovery from ``peers`` on a
        background thread (paper §2.4.2: recovery overlaps the inner
        phase). The fetch gossips chunk availability, streams the
        manifest chain into this node's store and assembles the delta
        chain incrementally; ``run()`` adopts the result at the first
        outer boundary where it is ready. Returns the fetcher (callers
        outside ``run()`` can ``wait_ready()`` it themselves)."""
        assert self._stream_join is None or self._stream_join.done, \
            "a streaming join is already in flight"
        from repro.checkpointing import ChunkStore, StreamingFetcher
        # an explicit store_root wins (the single-process simulation
        # plays both cluster and joiner: the joiner must stream into
        # its OWN store, not dedup against the serving one); a real
        # joiner defaults to its configured chunk store
        if store_root is not None:
            store = ChunkStore(store_root)
        else:
            store = self.ckpt_store
            assert store is not None, \
                "streaming join needs a chunk store: configure " \
                "ckpt_engine store|delta or pass store_root"
        self._stream_join = StreamingFetcher(
            peers, store, self.checkpoint_like(), step=step,
            range_chunks=range_chunks, timeout=timeout).start()
        return self._stream_join

    def poll_stream_join(self) -> dict | None:
        """Non-blocking: adopt a finished streaming recovery (called at
        every outer boundary by ``run()``). Returns the admission
        record, a failure record, or None while still streaming."""
        f = self._stream_join
        if f is None or not f.done:
            return None
        self._stream_join = None
        if f.failed:
            f.close()
            return {"admitted": False, "error": str(f.error),
                    "stats": f.stats()}
        tree, meta, stats = f.result()
        self.adopt_checkpoint(tree, meta)
        f.close()
        return {"admitted": True, "step": stats["step"],
                "outer_step": meta.get("outer_step"), "stats": stats}

    def checkpoint_like(self):
        """Template pytree matching what run() checkpoints (for
        ``ChunkStore.restore_tree`` / ``delta.restore`` /
        ``swarm.recover``)."""
        return {"params": jax.tree.map(lambda p: p[0], self.params),
                "outer_momentum": self.outer.opt.momentum,
                "anchor": self.outer.anchor}

    def serve_checkpoints(self, port: int = 0):
        """Expose this node's chunk store to joining peers (the
        paper's live-recovery serving side)."""
        from repro.checkpointing import ChunkPeer
        assert self.ckpt_store is not None, \
            "serve_checkpoints requires ckpt_engine 'store' or 'delta'"
        return ChunkPeer(self.ckpt_store, port=port)

    def adopt_checkpoint(self, tree, meta: dict) -> None:
        """Enter at the next outer boundary from a recovered
        checkpoint: every slot resets to the recovered anchor and the
        outer state resumes its momentum (paper §2.4.2 onboarding)."""
        anchor = jax.tree.map(
            lambda a: jnp.asarray(a, jnp.float32), tree["anchor"])
        from repro.core.sync_engine import SyncEngine
        eng = SyncEngine.for_tree(anchor)
        self.outer = self.outer._replace(
            anchor=anchor,
            opt=self.outer.opt._replace(
                momentum=jax.tree.map(
                    lambda m: jnp.asarray(m, jnp.float32),
                    tree["outer_momentum"])),
            outer_step=jnp.asarray(meta.get("outer_step", 0),
                                   jnp.int32),
            anchor_flat=eng.flatten(anchor))
        self.params = jax.tree.map(
            lambda stacked, p: jnp.broadcast_to(
                jnp.asarray(p, stacked.dtype)[None],
                stacked.shape),
            self.params, tree["params"])
        self.opt_state = jax.vmap(self.optimizer.init)(self.params)

    def _quarantined_slots(self) -> list[int]:
        return sorted(
            self.slots.slot_of[nid]
            for nid in self.sim.quarantined_ids()
            if nid in self.slots.slot_of)

    def _ring_for_sync(self) -> tuple[int, ...]:
        """Quarantine-aware ring order: quarantined slots move to the
        tail (zero-weighted rows don't sit between healthy peers).
        When they already are at the tail the order — and therefore
        the distributed hop programs — is unchanged."""
        order = tuple(self.ring_order[: self.k])
        q = self._quarantined_slots()
        return topology.exclude_slots(order, q) if q else order

    def _begin_sync(self, weights, ef_slot: int) -> dl.OuterSyncHandle:
        """Stage the outer sync: through the distributed backend when
        one is plugged in, the simulator ring otherwise (same handle
        surface either way)."""
        if self.sync_backend is not None:
            return self.sync_backend.begin(
                self.params, self.outer, self.cfg.diloco,
                ring_order=self._ring_for_sync(), weights=weights,
                ef_slot=ef_slot)
        return dl.begin_outer_sync_sim(
            self.params, self.outer, self.cfg.diloco,
            ring_order=self._ring_for_sync(), weights=weights,
            ef_slot=ef_slot)

    def _outer_sync(self, weights):
        if self.sync_backend is not None:
            # non-overlapped path through the distributed collectives:
            # begin + immediate finish (EF residual is slot-free here)
            h = self._begin_sync(jnp.asarray(weights), ef_slot=0)
            return dl.finish_outer_sync_sim(h, self.params, self.outer)
        return dl.outer_sync_sim(self.params, self.outer,
                                 self.cfg.diloco,
                                 ring_order=self._ring_for_sync(),
                                 weights=weights)

    # -- contribution admission (untrusted-contributor defense) ---------------

    def _validation_on(self) -> bool:
        v = self.cfg.validation
        return v is not None and v.enabled

    def _apply_poison(self, poison: dict, t: int) -> None:
        """Corrupt the scheduled LIVE nodes' post-phase params in
        pseudo-gradient space (``p' = a - poison(a - p)``) so the
        contribution the next sync stages is exactly what a faulty
        peer would inject. Seeded per (node, step) — deterministic."""
        if not poison:
            return
        from repro.core.sync_engine import SyncEngine
        any_params = jax.tree.map(lambda p: p[0], self.params)
        eng = SyncEngine.for_tree(any_params)
        a_flat = (self.outer.anchor_flat
                  if self.outer.anchor_flat is not None
                  else eng.flatten(self.outer.anchor))
        a_np = np.asarray(a_flat, np.float32)
        live = set(self.sim.hb.live_ids())
        for nid in sorted(poison):
            if nid not in live:
                # quarantined/dead nodes have no contribution to spoil
                continue
            slot = self.slots.slot_of.get(nid)
            if slot is None:
                continue
            p_flat = np.asarray(eng.flatten(
                jax.tree.map(lambda p: p[slot], self.params)),
                np.float32)
            rng = np.random.default_rng([nid, t])
            bad = vd.poison_pseudograd(a_np - p_flat, poison[nid], rng)
            new_p = eng.unflatten(jnp.asarray(a_np - bad),
                                  like=any_params)
            self.params = jax.tree.map(
                lambda stacked, leaf: stacked.at[slot].set(
                    leaf.astype(stacked.dtype)),
                self.params, new_p)

    def _admission_check(self, handle: dl.OuterSyncHandle,
                         t: int) -> vd.AdmissionReport:
        """Judge the staged pseudo-gradients BEFORE any reduced value
        is applied; quarantine flagged contributors and feed the
        accepted rows back into the cross-step statistics. Pure
        host-side float64 on the retained rows + the chunk-norm
        sideband, so the simulator and the distributed backend reach
        bit-identical decisions."""
        report = vd.validate_pseudograds(
            np.asarray(handle.op.xs, np.float64),
            np.asarray(handle.weights, np.float64),
            handle.norm_sideband(), self._adm_stats,
            self.cfg.validation)
        slot_node = {slot: nid
                     for nid, slot in self.slots.slot_of.items()}
        for slot in sorted(report.flagged):
            nid = slot_node.get(slot)
            if nid is not None and self.sim.record_violation(
                    nid, t, report.flagged[slot]):
                report.quarantined_nodes.append(nid)
        self.sim.record_clean(
            [slot_node[s] for s in report.accepted if s in slot_node])
        self._adm_stats.update(report)
        if report.sanitize:
            self.quarantine_events.append({
                "outer_step": t,
                "flagged": {s: list(r)
                            for s, r in report.flagged.items()},
                "bad_chunks": {s: list(c)
                               for s, c in report.bad_chunks.items()},
                "quarantined": list(report.quarantined_nodes)})
        return report

    def _validated_outer_sync(self, t: int, weights) -> vd.AdmissionReport:
        """Non-overlapped outer sync behind the admission gates:
        begin -> judge -> (sanitize + restart over the clean survivors)
        or finish. The staged accumulators already absorbed the raw
        rows (and NaN * 0 == NaN), so a rejected population is never
        finished — the sanitized rows are RE-REDUCED from scratch via
        the torn-reduction restart path."""
        w = jnp.asarray(np.asarray(weights), jnp.float32)
        h = self._begin_sync(w, ef_slot=0)
        report = self._admission_check(h, t)
        if report.sanitize:
            h.sanitize(report.sanitize)
            w2 = np.asarray(w, np.float32).copy()
            for slot in report.sanitize:
                if slot < len(w2):
                    w2[slot] = 0.0
            self.params, self.outer = dl.resync_outer_sim(
                h, self.params, self.outer, jnp.asarray(w2))
        else:
            self.params, self.outer = dl.finish_outer_sync_sim(
                h, self.params, self.outer)
        return report

    # -- teardown -------------------------------------------------------------

    def close(self, discard: bool = False) -> dict | None:
        """Tear down any in-flight overlapped sync so an interrupted
        run can't leave hop buffers or a torn accumulator behind.
        ``discard=False`` drains and applies it (clean finish);
        ``discard=True`` aborts it — the partial reduction is dropped
        and the handle poisoned (``SyncAbortedError`` on any further
        use). Pending async snapshots are flushed either way."""
        h, self._inflight = self._inflight, None
        rec = None
        if h is not None and not h.aborted:
            if discard:
                h.abort()
                rec = {"discarded": True,
                       "ledger": self.comm_ledger.tear_sync(
                           resync_hops=0)}
            else:
                self._drain_hops(h)
                rec = {"discarded": False,
                       "ledger": self.comm_ledger.finish_sync()}
                self.params, self.outer = dl.finish_outer_sync_sim(
                    h, self.params, self.outer)
            self.sim.note_sync_end()
        if self.snapshotter is not None:
            self.snapshotter.flush()
        return rec

    def __enter__(self) -> "ElasticTrainer":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        # an exception mid-overlap leaves the reduction torn: discard
        # it (a partial accumulator must never be applied); on a clean
        # exit the in-flight sync drains and applies
        self.close(discard=exc_type is not None)
        return False

    # -- overlapped outer sync (diloco.overlap == 'delayed') ------------------

    def _link_rate(self) -> float:
        """Bytes/s of the slowest link on the CURRENT ring, from the
        BandwidthMonitor's EWMA matrix — the hop that paces a ring
        all-reduce. Falls back to the uniform modeled link until every
        ring edge has an observation."""
        bn = self.bw.ring_bottleneck(self.ring_order[: self.k])
        if bn is None or bn <= 0:
            return self.cfg.sync_link_bytes_per_s
        return bn * self.cfg.link_bytes_per_gbps

    def _hop_seconds(self, weights) -> float:
        """Modeled wire time of ONE live ring hop: the actual per-hop
        bytes of the n_live-worker ring (chunk payload + codebook
        sideband, ``ring_reduce.ring_hop_bytes``) over the ring's
        bottleneck-link rate."""
        n_live = max(1, int(np.sum(np.asarray(weights) > 0)))
        numel = sum(int(np.prod(l.shape[1:], dtype=np.int64))
                    for l in jax.tree.leaves(self.params))
        ring = self.cfg.diloco.ring
        per_hop = rr.ring_hop_bytes(numel, n_live, quant=ring.quant,
                                    buckets=ring.buckets)
        return per_hop / self._link_rate()

    def _dispatch_ledger_hop(self) -> None:
        """Charge one dispatched hop to the ledger. The sim ring always
        walks 2*(k-1) hops, but only 2*(n_live-1) of them carry bytes
        on the real cluster — the dead-slot remainder is charged 0s so
        the ledger reflects what the wire actually moves."""
        if self._window_hop_i < self._live_hops:
            self.comm_ledger.dispatch_hop()
        else:
            self.comm_ledger.dispatch_hop(seconds=0.0)
        self._window_hop_i += 1

    def _participants(self, weights) -> frozenset:
        w = np.asarray(weights)
        return frozenset(nid for nid, slot in self.slots.slot_of.items()
                         if slot < len(w) and w[slot] > 0)

    def _overlapped_boundary(self, t: int, weights) -> dict:
        """Boundary protocol for the delayed overlap (paper §2.2):

          1. compute + quantize THIS phase's pseudo-gradients against
             the current anchor (the one every worker started from) and
             stage the ring — ``begin`` before ``finish`` so the new
             pseudo-gradient never sees the about-to-land update;
          2. drain + apply the PREVIOUS boundary's reduction (one-phase
             delay) — every worker resets to the updated anchor;
          3. dispatch the new sync's first hop so its transfer hides
             under the next inner phase from the very start.
        """
        w = jnp.asarray(np.asarray(weights), jnp.float32)
        h_new = self._begin_sync(w, ef_slot=self._ef_begins % 2)
        self._ef_begins += 1
        # admission gates run on the STAGED rows before any hop is
        # dispatched — a flagged contribution never rides the wire
        report = (self._admission_check(h_new, t)
                  if self._validation_on() else None)
        rec: dict = {"hops": h_new.hops_total, "_report": report}
        prev = self._inflight
        if prev is not None:
            self._drain_hops(prev)
            rec["prev"] = self.comm_ledger.finish_sync()
            self.params, self.outer = dl.finish_outer_sync_sim(
                prev, self.params, self.outer)
        else:
            # first boundary: nothing in flight to apply — reset every
            # worker to the (unchanged) anchor; this phase's progress
            # arrives via the delayed application at the next boundary
            self._reset_to_anchor()
        if report is not None and report.sanitize:
            # rejected population: sanitize the retained rows and apply
            # this boundary's sync RIGHT NOW as a synchronous re-reduce
            # over the clean survivors (to its own anchor snapshot —
            # the same lineage the delayed apply would have used). The
            # whole re-reduction is exposed comm, charged like a torn
            # sync; nothing stays in flight.
            h_new.sanitize(report.sanitize)
            w2 = np.asarray(h_new.weights, np.float32).copy()
            for slot in report.sanitize:
                if slot < len(w2):
                    w2[slot] = 0.0
            self.params, self.outer = dl.resync_outer_sim(
                h_new, self.params, self.outer, jnp.asarray(w2))
            self.comm_ledger.begin_sync(self._hop_seconds(weights))
            rec["rejected"] = self.comm_ledger.tear_sync(
                resync_hops=h_new.hops_total)
            self._inflight = None
            return rec
        self.sim.note_sync_begin(t, self._participants(weights))
        self._inflight = h_new
        self.comm_ledger.begin_sync(self._hop_seconds(weights))
        n_live = max(1, int(np.sum(np.asarray(weights) > 0)))
        self._live_hops = 2 * (n_live - 1)
        self._window_hop_i = 0
        if h_new.step():
            self._dispatch_ledger_hop()
        return rec

    def _fallback_resync(self, plan) -> dict:
        """A participant of the in-flight sync left: discard the torn
        partial reduction and synchronously re-reduce the retained
        pseudo-gradients with the dead workers' weights zeroed
        (bit-consistent: every survivor re-derives the same result
        from the same retained inputs)."""
        h = self._inflight
        self._inflight = None
        self.sim.note_sync_end()
        w = np.asarray(h.weights, np.float32).copy()
        for nid in plan["sync_torn"]:
            slot = self.slots.slot_of.get(nid)
            if slot is not None and slot < len(w):
                w[slot] = 0.0
        self.params, self.outer = dl.resync_outer_sim(
            h, self.params, self.outer, jnp.asarray(w))
        led = self.comm_ledger.tear_sync(resync_hops=h.hops_total)
        return {"torn_by": list(plan["sync_torn"]),
                "resync_hops": h.hops_total, "ledger": led}

    def _drain_hops(self, handle: dl.OuterSyncHandle) -> None:
        """Dispatch every remaining hop of ``handle`` (exposed comm:
        the boundary is waiting on the wire)."""
        while handle.step():
            self._dispatch_ledger_hop()

    def _reset_to_anchor(self) -> None:
        for_slot = self.outer.anchor
        self.params = jax.tree.map(
            lambda stacked, a: jnp.broadcast_to(
                a.astype(stacked.dtype)[None], stacked.shape),
            self.params, for_slot)

    def _sync_membership(self, plan) -> list[int]:
        for nid in plan["left"]:
            self.slots.release(nid)
        slots = []
        for nid in plan["live"]:
            slot = self.slots.assign(nid)
            slots.append(slot)
            if nid in plan["joined"]:
                # joiner adopts the anchor (P2P checkpoint in the
                # distributed path) and fresh optimizer state
                anchor = self.outer.anchor
                self.params = jax.tree.map(
                    lambda stacked, a: stacked.at[slot].set(
                        a.astype(stacked.dtype)),
                    self.params, anchor)
                fresh = self.optimizer.init(
                    jax.tree.map(lambda p: p[slot], self.params))
                self.opt_state = jax.tree.map(
                    lambda stacked, f: stacked.at[slot].set(f),
                    self.opt_state, fresh)
        return slots
