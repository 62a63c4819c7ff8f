"""Step builders: jit/shard_map-wired train, sync and serve steps.

The inner train step runs as a plain pjit program over (data, model)
*inside* a shard_map region manual over the DiLoCo axis (paper §2.3:
FSDP inside, DiLoCo outside). The outer sync step runs the int8 ring
all-reduce over the same manual axis. When the plan has no DiLoCo axis
(huge models on one pod; serving) everything is plain pjit.

Each builder returns (fn, sharding spec pytrees) so the dry-run can
lower against ShapeDtypeStructs and the trainer can device_put real
state identically.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import diloco as dl
from repro.core.sync_engine import SyncEngine, shard_flat_size
from repro.models import common
from repro.optim.adamw import AdamW, AdamWState
from repro.optim.nesterov import NesterovState
from repro.sharding import partition
from repro.train.state import TrainState


def mesh_axes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _constrain(mesh, tree, spec_tree):
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, s)),
        tree, spec_tree, is_leaf=lambda x: isinstance(x, P))


def param_specs(model, plan, mesh) -> Any:
    shapes, axes = common.eval_axes(model.init, jax.random.PRNGKey(0))
    return partition.param_pspecs(axes, shapes, plan, mesh_axes(mesh))


def _shard_flat_dims(shapes, pspecs, amap: dict,
                     diloco_axis: str | None) -> tuple[int, int]:
    """(padded_local_len, tile) of the per-shard flat anchor buffer a
    sharded plan threads through its sync region: the concat of each
    device's local anchor shards PLUS ONE SENTINEL element, tiled over
    the non-DiLoCo mesh axes. The sentinel makes the threaded layout's
    length provably distinct from a global flatten (numel) even when
    every leaf shards evenly — so ``sync()`` can always tell a
    global-layout buffer (e.g. ``init_outer_state``'s) from its own
    and rebuild instead of silently mis-reading it. Single source of
    truth for both ``build_outer_sync`` and ``flat_anchor_len``;
    callers pass their already-evaluated (shapes, pspecs)."""
    local = shard_flat_size(shapes, pspecs, amap) + 1
    tile = 1
    for a, n in amap.items():
        if a != diloco_axis:
            tile *= n
    return local, tile


def flat_anchor_len(model, plan, mesh) -> int:
    """GLOBAL length of the persistent flat anchor buffer the outer
    sync threads through its region (dry-run / device_put lockstep).

    Replicated-param plans thread the full flat anchor (numel).
    Sharded plans thread the PER-SHARD flat view: each device holds the
    concat of its local anchor shards plus a sentinel element (see
    ``_shard_flat_dims``), and the buffer's global shape is that local
    length tiled over the non-DiLoCo mesh axes (an opaque device-major
    concat, only ever interpreted inside the manual region)."""
    shapes, axes = common.eval_axes(model.init, jax.random.PRNGKey(0))
    amap = mesh_axes(mesh)
    pspecs = partition.param_pspecs(axes, shapes, plan, amap)
    sharded = any(s != P() for s in jax.tree.leaves(
        pspecs, is_leaf=lambda x: isinstance(x, P)))
    if not sharded:
        return sum(int(np.prod(s.shape, dtype=np.int64))
                   if s.shape else 1
                   for s in jax.tree.leaves(shapes))
    local, tile = _shard_flat_dims(shapes, pspecs, amap,
                                   plan.diloco_axis)
    return local * tile


def batch_pspecs(model, shape, plan, mesh, *, stacked: bool) -> Any:
    """Leading-batch-dim specs for every input leaf (+ worker dim)."""
    specs = model.input_specs(shape)
    per_worker = shape.global_batch // plan.n_workers
    bp = partition.batch_pspec(plan, per_worker, mesh_axes(mesh))
    if stacked and plan.diloco_axis:
        bp = P(plan.diloco_axis, *bp)
    return {k: bp for k in specs}


# -- train --------------------------------------------------------------------


def build_train_step(model, plan, mesh, optimizer: AdamW):
    """Returns (train_step, state_specs).

    state/batch carry a leading DiLoCo-worker dim iff plan.diloco_axis.
    train_step(state: TrainState, batch) -> (state, metrics)."""
    pspecs = param_specs(model, plan, mesh)

    bspec = partition.batch_pspec(plan)
    # (B, S, D) residual-stream spec: batch over the batch axes, seq
    # over the SP axis when the plan enables it
    batch_entry = bspec[0] if len(bspec) else None
    act_spec = P(batch_entry, plan.act_seq_axis) \
        if plan.act_seq_axis else None

    def _soft_constrain(tree):
        """Bare-spec grad constraints: work inside vmap (spmd_axis_name
        prepends the worker axis) and no-op without a mesh context."""
        def one(x, s):
            try:
                return jax.lax.with_sharding_constraint(x, s)
            except Exception:
                return x

        return jax.tree.map(one, tree, pspecs,
                            is_leaf=lambda x: isinstance(x, P))

    def grads_of(params, batch, hints: bool = True):
        """Microbatched (gradient-accumulation) value_and_grad with the
        activation hints active. ``hints=False`` traces the body with
        NO in-body sharding constraints at all — required inside plain
        vmap on data-sharded-params plans, where any constraint in the
        vmapped body lowers through XLA's manual-subgroup machinery and
        CHECK-crashes the compiler."""
        from repro.sharding.act_hints import activation_hints

        constrain = _soft_constrain if hints else (lambda t: t)
        with activation_hints(act_spec if hints else None):
            if plan.microbatches == 1:
                (_, metrics), grads = jax.value_and_grad(
                    model.loss, has_aux=True)(params, batch,
                                              remat=plan.remat)
                return constrain(grads), metrics
            nmb = plan.microbatches
            mb = jax.tree.map(
                lambda x: x.reshape((nmb, x.shape[0] // nmb)
                                    + x.shape[1:]), batch)

            def body(acc, b_i):
                (_, m), g = jax.value_and_grad(
                    model.loss, has_aux=True)(params, b_i,
                                              remat=plan.remat)
                g = constrain(g)
                acc = jax.tree.map(
                    lambda a, gg: a + gg.astype(jnp.float32), acc, g)
                return constrain(acc), m

            zeros = constrain(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params))
            grads, ms = jax.lax.scan(body, zeros, mb)
            grads = jax.tree.map(lambda g: g / nmb, grads)
            metrics = jax.tree.map(lambda m: m[-1], ms)
            return grads, metrics

    def inner(params, opt_state, batch):
        # anchor the activation batch sharding (FSDP-style: batch over
        # the data axes and, when divisible, 'model' too) + optional
        # sequence parallelism hint on the residual stream
        batch = jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, bspec)), batch)
        grads, metrics = grads_of(params, batch)
        grads = _constrain(mesh, grads, pspecs)
        params, opt_state = optimizer.update(grads, opt_state, params)
        params = _constrain(mesh, params, pspecs)
        return params, opt_state, metrics

    dax = plan.diloco_axis
    if dax is None:
        def step(state: TrainState, batch):
            params, opt, metrics = inner(state.params, state.opt, batch)
            return TrainState(params, opt), metrics

        state_specs = TrainState(pspecs,
                                 AdamWState(P(), pspecs, pspecs))
        return step, state_specs

    lead = lambda t: partition.with_leading(t, dax)
    state_specs = TrainState(
        lead(pspecs), AdamWState(P(dax), lead(pspecs), lead(pspecs)))

    # XLA's SPMD partitioner CHECK-fails (`Check failed:
    # sharding.IsManualSubgroup()`) whenever a constraint meets a
    # manual subgroup: a shard_map region manual over the DiLoCo axis
    # whose body constrains leaves over the remaining mesh axes needs
    # manual-subgroup shardings this XLA cannot partition, and
    # `vmap(spmd_axis_name=dax)` lowers through the same machinery.
    # Partitioner-safe formulation with NO manual axes at all:
    # plain-vmap the per-worker step (traced hint-free — any in-body
    # constraint reintroduces the crash) over the stacked leading dim
    # and constrain the STACKED trees at the vmap boundary; sharding is
    # driven entirely by the boundary constraints and pjit propagation.
    def step(state: TrainState, batch):
        batch = jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(dax, *bspec))), batch)

        grads, metrics = jax.vmap(
            lambda p, b: grads_of(p, b, hints=False))(
                state.params, batch)
        grads = _constrain(mesh, grads, lead(pspecs))
        params, opt = jax.vmap(optimizer.update)(
            grads, state.opt, state.params)
        params = _constrain(mesh, params, lead(pspecs))
        return TrainState(params, opt), metrics

    return step, state_specs


def build_outer_sync(model, plan, mesh, diloco_cfg: dl.DiLoCoConfig,
                     ring_order=None):
    """Returns (sync_step, outer_specs).

    sync_step(params_stacked, outer_state, weights)
        -> (params_stacked, outer_state).
    The outer state (fp32 anchor + Nesterov momentum) is SHARED
    (replicated over the DiLoCo axis, data/model-sharded like params —
    the paper's host-offloaded master copy; on TPU targets pass
    ``host_offload_outer=True`` to place it in pinned_host memory)."""
    pspecs = param_specs(model, plan, mesh)
    dax = plan.diloco_axis

    if dax is None:
        # degenerate DiLoCo (one worker): PER-LEAF pseudo-gradient +
        # outer update — flattening to one vector would concat sharded
        # leaves and force a full all-gather (observed: 1.8 TB/device
        # for dbrx)
        def sync_single(params, outer_state, weights):
            del weights
            delta = jax.tree.map(
                lambda a, p: a - p.astype(jnp.float32),
                outer_state.anchor, params)
            new_anchor, new_opt = diloco_cfg.outer_opt.update(
                delta, outer_state.opt, outer_state.anchor)
            new_params = jax.tree.map(
                lambda a, p: a.astype(p.dtype), new_anchor, params)
            return new_params, dl.OuterState(
                new_anchor, new_opt, outer_state.residual,
                outer_state.outer_step + 1)

        outer_specs = dl.OuterState(pspecs, NesterovState(pspecs),
                                    P(), P())
        return sync_single, outer_specs

    # Hybrid FSDP + DiLoCo (paper §2.3): "only ranks responsible for the
    # same shard communicate". The sync runs FULLY manual — every device
    # rings ITS OWN model-shard of the pseudo-gradient across the DiLoCo
    # axis; the 16 model columns run 16 parallel rings (the paper's
    # per-shard process groups / parallel TCP stores).
    sharded_params = any(
        s != P() for s in jax.tree.leaves(
            pspecs, is_leaf=lambda x: isinstance(x, P)))
    if diloco_cfg.error_feedback and sharded_params:
        raise NotImplementedError(
            "error feedback requires per-shard residual bookkeeping; "
            "supported with replicated-inner-params plans only")

    lead = lambda t: partition.with_leading(t, dax)

    if not sharded_params:
        # replicated-inner-params plans: thread the persistent flat
        # fp32 anchor THROUGH the shard_map region, so the
        # pseudo-gradient is one subtract off the buffer instead of a
        # per-sync anchor re-flatten, and the updated buffer flows back
        # out for the next outer step (sharded plans would need a
        # per-shard flat view first — the anchor leaves inside the
        # region are shards there).
        def per_worker(params, anchor, momentum, residual, outer_step,
                       a_flat, weights):
            p_i = jax.tree.map(lambda x: x[0], params)
            st = dl.OuterState(anchor, NesterovState(momentum),
                               residual[0], outer_step,
                               anchor_flat=a_flat)
            new_p, new_st = dl.outer_sync(
                p_i, st, diloco_cfg, dax, ring_order=ring_order,
                weight=weights[0])
            return (jax.tree.map(lambda x: x[None], new_p),
                    new_st.anchor, new_st.opt.momentum,
                    new_st.residual[None], new_st.outer_step,
                    new_st.anchor_flat)

        def sync(params_stacked, outer_state: dl.OuterState, weights):
            a_flat = outer_state.anchor_flat
            if a_flat is None:
                eng = SyncEngine.for_tree(outer_state.anchor)
                a_flat = eng.flatten(outer_state.anchor)
            new_p, anchor, momentum, residual, ostep, new_a_flat = \
                jax.shard_map(
                    per_worker, mesh=mesh,
                    in_specs=(lead(pspecs), pspecs, pspecs, P(dax),
                              P(), P(), P(dax)),
                    out_specs=(lead(pspecs), pspecs, pspecs, P(dax),
                               P(), P()),
                    check_vma=False)(
                        params_stacked, outer_state.anchor,
                        outer_state.opt.momentum, outer_state.residual,
                        outer_state.outer_step, a_flat, weights)
            return new_p, dl.OuterState(anchor, NesterovState(momentum),
                                        residual, ostep, new_a_flat)

        outer_specs = dl.OuterState(pspecs, NesterovState(pspecs),
                                    P(dax), P(), P())
        return sync, outer_specs

    # Sharded plans thread the PER-SHARD flat anchor view (the zero-
    # flatten fused path replicated plans got in PR 2): inside the
    # manual region every device's anchor leaves are LOCAL shards, so
    # the persistent buffer is the concat of those shards plus one
    # SENTINEL element (see _shard_flat_dims — it keeps the threaded
    # layout's length distinct from a global flatten, so a buffer from
    # init_outer_state can never be mis-read as per-shard). It rides
    # in/out of the region as an opaque device-major array whose first
    # dim is "sharded" over the non-DiLoCo mesh axes (and replicated
    # over the DiLoCo axis, like the anchor itself); sync() rebuilds
    # the view whenever the incoming buffer's length differs.
    nondax = tuple(a for a in mesh.axis_names if a != dax)
    flat_spec = P(nondax) if nondax else P()
    shapes, _ = common.eval_axes(model.init, jax.random.PRNGKey(0))
    padded_local, tile = _shard_flat_dims(shapes, pspecs,
                                          mesh_axes(mesh), dax)
    flat_global = padded_local * tile

    def _local_flatten(anchor):
        flat = SyncEngine.for_tree(anchor).flatten(anchor)
        return jnp.pad(flat, (0, 1))          # sentinel element

    flatten_local = jax.shard_map(
        _local_flatten, mesh=mesh, in_specs=(pspecs,),
        out_specs=flat_spec, check_vma=False)

    def per_worker(params, anchor, momentum, residual, outer_step,
                   a_flat, weights):
        p_i = jax.tree.map(lambda x: x[0], params)
        st = dl.OuterState(anchor, NesterovState(momentum),
                           residual[0], outer_step,
                           anchor_flat=a_flat[:-1])  # drop sentinel
        new_p, new_st = dl.outer_sync(
            p_i, st, diloco_cfg, dax, ring_order=ring_order,
            weight=weights[0])
        return (jax.tree.map(lambda x: x[None], new_p), new_st.anchor,
                new_st.opt.momentum, new_st.residual[None],
                new_st.outer_step, jnp.pad(new_st.anchor_flat, (0, 1)))

    def sync(params_stacked, outer_state: dl.OuterState, weights):
        a_flat = outer_state.anchor_flat
        if a_flat is None or tuple(a_flat.shape) != (flat_global,):
            # first sync (or a global-layout buffer from
            # init_outer_state): build the per-shard view once; the
            # updated buffer threads through every later sync
            a_flat = flatten_local(outer_state.anchor)
        new_p, anchor, momentum, residual, ostep, new_a_flat = \
            jax.shard_map(
                per_worker, mesh=mesh,
                in_specs=(lead(pspecs), pspecs, pspecs, P(dax), P(),
                          flat_spec, P(dax)),
                out_specs=(lead(pspecs), pspecs, pspecs, P(dax), P(),
                           flat_spec),
                check_vma=False)(
                    params_stacked, outer_state.anchor,
                    outer_state.opt.momentum, outer_state.residual,
                    outer_state.outer_step, a_flat, weights)
        return new_p, dl.OuterState(anchor, NesterovState(momentum),
                                    residual, ostep, new_a_flat)

    outer_specs = dl.OuterState(pspecs, NesterovState(pspecs),
                                P(dax), P(), flat_spec)
    return sync, outer_specs


# -- distributed overlapped outer sync (per-hop shard_map collectives) -------


class DistSyncPrograms:
    """Jitted per-hop ``shard_map`` collectives for the distributed
    outer-sync ring: one program per hop KIND (reduce-scatter, fused
    first hop, all-gather prologue, all-gather forward), the hop index
    riding traced so one compilation serves every hop.

    The hop BODIES are the simulator's (`ring_reduce._rs_hop_rows` /
    `_ag_hop_rows`), run at ONE ring position per device: inside the
    manual region ``positions = inv[axis_index(dax)][None]`` and the
    payload shift is the static ``ppermute`` along the bandwidth-
    ordered ring instead of ``jnp.roll``. Per-row math is identical and
    vmap over one row is bit-identical to the stacked vmap on XLA:CPU,
    so the distributed reduction is hop-for-hop bit-identical to the
    simulator (tested in tests/test_distributed.py). The in-flight
    accumulator and forwarded-code payloads thread BETWEEN programs as
    opaque flat shards (spec ``P(dax)`` / ``P(dax, local)``), like the
    PR 5 per-shard anchor buffer.

    Hierarchical mode (``core.elastic_mesh.HierarchySpec``, the paper's
    ElasticDeviceMesh split): each device rings only its intra-node
    slice (1/n_local of the vector) over the WAN axis, and the full
    vector is rebuilt with an intra-node ``all_gather`` at finalize —
    per-device WAN bytes drop by n_local. With replicated inner params
    every local copy of the pseudo-gradient is identical, so the slice
    by local rank IS the intra-node reduce-scatter (psum_scatter /
    n_local, exactly). Quantization codebooks become per-slice, so
    hierarchical results are bit-identical to the PER-SLICE simulator
    (concat of slice sims), not to the flat one.

    A changed ring order is a new static ``ppermute`` permutation:
    ``DistSyncBackend`` rebuilds these programs whenever
    ``BandwidthMonitor.maybe_reorder`` reports a change (the reorder ->
    recompile lifecycle; the paper pays the analogous process-group
    re-rendezvous cost).
    """

    def __init__(self, mesh, dax: str, size: int, cfg, ring_order=None,
                 hierarchy=None):
        from repro.core import ring_reduce as rr
        self.mesh, self.dax = mesh, dax
        self.cfg = cfg
        self.k = k = int(mesh.shape[dax])
        self.size = size
        order = (tuple(ring_order) if ring_order is not None
                 else tuple(range(k)))
        assert sorted(order) == list(range(k)), \
            "ring order must be a permutation of the DiLoCo slots"
        assert k > 1, "use RingSyncOp for the degenerate 1-worker ring"
        self.ring_order = order
        self.hier = hierarchy if (hierarchy is not None
                                  and hierarchy.split) else None
        lnames = self.hier.local_axes if self.hier else ()
        self.n_local = nl = self.hier.n_local if self.hier else 1
        self.slice_len = sl = -(-size // nl)
        nb = max(1, cfg.buckets)
        chunk = -(-sl // k)
        bsize = -(-chunk // nb)
        chunk = bsize * nb
        self.chunk, self.bsize, self.nb = chunk, bsize, nb
        self.padded = k * chunk

        inv = np.argsort(np.asarray(order))
        inv_dev = jnp.asarray(inv)
        perm_fwd = [(order[p], order[(p + 1) % k]) for p in range(k)]
        row_spec, acc_spec = partition.wan_ring_specs(dax, lnames)
        self._row_sharding = NamedSharding(mesh, row_spec)
        self._rep_sharding = NamedSharding(mesh, P())
        hier = self.hier is not None

        def _positions():
            # this device's ring position, as a 1-row batch for the
            # shared row-wise hop bodies
            return inv_dev[jax.lax.axis_index(dax)][None]

        def _shift(payload):
            # position p's payload moves to position p+1 — the ring's
            # static wire permutation (the sim's jnp.roll(+1) analogue)
            return tuple(jax.lax.ppermute(p, dax, perm_fwd)
                         for p in payload)

        # hierarchical buffers carry a local-slice dim the row-wise
        # bodies don't know about: squeeze/restore around each hop
        _sq = (lambda a: a[:, 0]) if hier else (lambda a: a)
        _usq = (lambda a: a[:, None]) if hier else (lambda a: a)
        _psq = (lambda p: jax.tree.map(lambda x: x[:, 0], p)) if hier \
            else (lambda p: p)
        _pusq = (lambda p: jax.tree.map(lambda x: x[:, None], p)) \
            if hier else (lambda p: p)
        geo = (k, chunk, bsize, nb, cfg)

        def rs_body(s, accs):
            return _usq(rr._rs_hop_rows(
                s, _sq(accs), *geo, positions=_positions(),
                shift=_shift))

        def rs_fused_body(s, accs, a_flat, t_row, w_row):
            return _usq(rr._rs_hop_rows(
                s, _sq(accs), *geo, (a_flat, t_row, w_row),
                positions=_positions(), shift=_shift))

        def ag_init_body(accs):
            a, p = rr._ag_init_rows(_sq(accs), *geo,
                                    positions=_positions())
            return _usq(a), _pusq(p)

        def ag_body(s, accs, payloads):
            a, p = rr._ag_hop_rows(s, _sq(accs), _psq(payloads), *geo,
                                   positions=_positions(), shift=_shift)
            return _usq(a), _pusq(p)

        def _local_rank():
            # row-major over the non-DiLoCo axes — must match
            # ElasticDeviceMesh.local_rank and the all_gather order
            r, stride = 0, 1
            for name in reversed(list(mesh.shape.keys())):
                if name == dax:
                    continue
                r = r + jax.lax.axis_index(name) * stride
                stride *= int(mesh.shape[name])
            return r

        def prep_hier_body(pg, w):
            # (1, size) worker row -> this device's weighted, ring-
            # padded intra-node slice (1, 1, padded)
            row = pg.astype(jnp.float32) * w[:, None]
            row = jnp.pad(row, ((0, 0), (0, nl * sl - size)))
            piece = jax.lax.dynamic_slice_in_dim(
                row, _local_rank() * sl, sl, axis=-1)
            piece = jnp.pad(piece, ((0, 0), (0, self.padded - sl)))
            return piece[:, None]

        def fin_hier_body(accs):
            # rebuild the full vector intra-node: gather every local
            # slice (valid region only) back into worker rows
            row = accs[:, 0, :sl]
            return jax.lax.all_gather(row, lnames, axis=1, tiled=True)

        def _sm(f, ins, outs):
            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=ins, out_specs=outs,
                check_vma=False))

        self.rs = _sm(rs_body, (P(), acc_spec), acc_spec)
        self.rs_fused = None if hier else _sm(
            rs_fused_body, (P(), acc_spec, P(), row_spec, row_spec),
            acc_spec)
        self.ag_init = _sm(ag_init_body, (acc_spec,),
                           (acc_spec, acc_spec))
        self.ag = _sm(ag_body, (P(), acc_spec, acc_spec),
                      (acc_spec, acc_spec))
        self._prep_hier = _sm(prep_hier_body, (row_spec, row_spec),
                              acc_spec) if hier else None
        self._fin_hier = _sm(fin_hier_body, (acc_spec,),
                             row_spec) if hier else None
        self._acc_sharding = NamedSharding(mesh, acc_spec)

    # -- buffer staging -------------------------------------------------------

    def prep(self, xs, weights):
        """Weighted, ring-padded accumulator rows, placed on the mesh
        (worker-major: row d = device d's position's accumulator)."""
        if self.hier:
            return self._prep_hier(
                jax.device_put(xs, self._row_sharding),
                jax.device_put(weights, self._row_sharding))
        accs = xs.astype(jnp.float32) * weights[:, None]
        accs = jnp.pad(accs, ((0, 0), (0, self.padded - self.size)))
        return jax.device_put(accs, self._acc_sharding)

    def prep_fused(self, a_flat, thetas, weights):
        """Ring-padded fused first-hop operands on the mesh (anchor
        replicated, theta/weight rows over the WAN axis)."""
        pad = self.padded - self.size
        a = jnp.pad(a_flat.astype(jnp.float32), (0, pad))
        t = jnp.pad(thetas.astype(jnp.float32), ((0, 0), (0, pad)))
        return (jax.device_put(a, self._rep_sharding),
                jax.device_put(t, self._row_sharding),
                jax.device_put(weights, self._row_sharding))

    def finalize(self, accs, total_w):
        """Post-all-gather accumulator -> (k, size) reduced rows on the
        default device (identical rows; same eager slice/divide as
        RingSyncOp.finish, so values are bit-identical to the sim)."""
        if self.hier:
            accs = self._fin_hier(accs)
        out = jnp.asarray(jax.device_get(accs))[:, : self.size]
        if self.cfg.average:
            out = out / jnp.maximum(total_w, 1e-20)
        return out


class DistRingSyncOp:
    """Distributed mirror of :class:`ring_reduce.RingSyncOp` with the
    same public surface (``step``/``finish``/``restart``/``pending``/
    ``hops_total``/``hops_done``), so ``diloco.OuterSyncHandle``,
    ``finish_outer_sync_sim`` and ``resync_outer_sim`` operate on it
    unchanged. Each ``step()`` dispatches ONE wire hop as a jitted
    shard_map collective and returns as soon as it is enqueued — no
    ``block_until_ready`` anywhere — so the transfer rides under the
    next inner-phase scan chunk. Like the sim op, it RETAINS its inputs
    for the torn-reduction fallback: ``restart`` re-reduces the
    retained rows over the survivors through the same distributed
    programs (bit-identical to the sim restart)."""

    def __init__(self, programs: DistSyncPrograms, xs,
                 weights=None, fused_src=None):
        pr = programs
        k, orig = xs.shape
        assert k == pr.k and orig == pr.size, \
            f"geometry mismatch: op ({k}, {orig}) vs programs " \
            f"({pr.k}, {pr.size})"
        self.programs = pr
        self.cfg = pr.cfg
        self.k, self.orig_size = k, orig
        self.ring_order = pr.ring_order
        self.xs = xs.astype(jnp.float32)
        self.weights = (jnp.ones((k,), jnp.float32) if weights is None
                        else weights)
        self.fused_src = fused_src
        self.hops_done = 0
        self._out = None
        self._total_w = jnp.sum(self.weights)
        self.hops_total = 2 * (k - 1)
        self._fused0 = (fused_src is not None and self.cfg.fused
                        and self.cfg.quant == "int8"
                        and pr.rs_fused is not None)
        self._accs = pr.prep(self.xs, self.weights)
        if self._fused0:
            a_flat, thetas = fused_src
            self._a_dev, self._t_dev, self._w_dev = pr.prep_fused(
                a_flat, thetas, self.weights)
        self._payloads = None

    @property
    def pending(self) -> bool:
        return self.hops_done < self.hops_total

    @property
    def row_shards(self) -> list:
        """(device, rows) of every shard of the staged accumulator —
        one row per ring position on its own device; empty once
        finished."""
        if self._accs is None:
            return []
        return [(s.device, s.data.shape[0])
                for s in self._accs.addressable_shards]

    def step(self) -> bool:
        """Dispatch ONE wire hop (async collective); True iff a hop was
        dispatched."""
        if self._out is not None or not self.pending:
            return False
        i, k, pr = self.hops_done, self.k, self.programs
        if i < k - 1:
            if i == 0 and self._fused0:
                self._accs = pr.rs_fused(
                    jnp.int32(0), self._accs, self._a_dev,
                    self._t_dev, self._w_dev)
            else:
                self._accs = pr.rs(jnp.int32(i), self._accs)
        else:
            s = i - (k - 1)
            if s == 0:
                self._accs, self._payloads = pr.ag_init(self._accs)
            self._accs, self._payloads = pr.ag(
                jnp.int32(s), self._accs, self._payloads)
        self.hops_done += 1
        return True

    def finish(self):
        if self._out is None:
            while self.pending:
                self.step()
            self._out = self.programs.finalize(self._accs,
                                               self._total_w)
            self._accs = self._payloads = None   # free in-flight state
        return self._out

    def restart(self, weights):
        """Torn-reduction fallback: synchronously re-reduce the
        RETAINED inputs over the survivors through the same distributed
        programs (no recompile — weights ride traced)."""
        return DistRingSyncOp(self.programs, self.xs, weights=weights,
                              fused_src=self.fused_src).finish()

    def norm_sideband(self):
        """Per-chunk norm sideband of the retained rows — the SAME
        host-side ``ring_reduce.chunk_norms`` the simulator op uses, so
        both paths judge bit-identical values (the admission layer's
        bit-identity hinges on this)."""
        from repro.core import ring_reduce as rr
        return rr.chunk_norms(self.xs, self.cfg.buckets)


class DistSyncBackend:
    """Plugs the per-hop distributed collectives into ``ElasticTrainer``
    (pass ``sync_backend=DistSyncBackend(mesh, dax)`` to the trainer).

    ``begin`` mirrors ``diloco.begin_outer_sync_sim`` — literally the
    same pseudo-gradient front half (``_sim_pseudograds``), including
    the slot-parity two-slot error-feedback residual — but stages the
    ring as a :class:`DistRingSyncOp` over the mesh's DiLoCo axis, so
    distributed ``overlap='delayed'`` is bit-identical to the simulator
    path on the same plan. Hop programs are rebuilt whenever the ring
    order (or geometry) changes — ``recompiles`` counts builds, the
    first one included."""

    def __init__(self, mesh, dax: str, hierarchical: bool | None = None):
        from repro.core import elastic_mesh
        self.mesh, self.dax = mesh, dax
        self._split = elastic_mesh.hierarchy(mesh, dax)
        # None -> follow DiLoCoConfig.hierarchical per begin() call
        self.hierarchical = hierarchical
        self.recompiles = 0
        self._programs: DistSyncPrograms | None = None
        self._key = None

    def _want_hier(self, cfg) -> bool:
        use = (cfg.hierarchical if self.hierarchical is None
               else self.hierarchical)
        return bool(use) and self._split.split

    def begin(self, stacked_params, state, cfg, ring_order=None,
              weights=None, ef_slot: int = 0) -> dl.OuterSyncHandle:
        """Distributed analogue of ``diloco.begin_outer_sync_sim``."""
        from repro.core.ring_reduce import RingSyncOp
        k, _, a_flat, pgs, new_residuals, fused_src = \
            dl._sim_pseudograds(stacked_params, state, cfg,
                                ef_slot=ef_slot)
        assert k == int(self.mesh.shape[self.dax]), \
            f"trainer has {k} DiLoCo slots but mesh axis " \
            f"{self.dax!r} has {self.mesh.shape[self.dax]}"
        if weights is None:
            weights = jnp.ones((k,), jnp.float32)
        if k == 1:
            op = RingSyncOp(pgs, ring_order=ring_order, cfg=cfg.ring,
                            weights=weights, fused_src=fused_src)
            return dl.OuterSyncHandle(op, cfg, a_flat, new_residuals,
                                      weights, k, ef_slot=ef_slot)
        hier = self._want_hier(cfg)
        if hier:
            # per-slice codebooks make the fused whole-vector transmit
            # inapplicable; the materialized slice is quantized instead
            # (bit-identical values — quantize_pseudograd(a,t,w) ==
            # quantize(w*(a-t)) is a tested invariant)
            fused_src = None
        order = (tuple(ring_order) if ring_order is not None
                 else tuple(range(k)))
        key = (k, pgs.shape[-1], cfg.ring, order, hier)
        if key != self._key:
            self._programs = DistSyncPrograms(
                self.mesh, self.dax, pgs.shape[-1], cfg.ring,
                ring_order=order,
                hierarchy=self._split if hier else None)
            self._key = key
            self.recompiles += 1
        op = DistRingSyncOp(self._programs, pgs, weights=weights,
                            fused_src=fused_src)
        return dl.OuterSyncHandle(op, cfg, a_flat, new_residuals,
                                  weights, k, ef_slot=ef_slot)


# -- serve --------------------------------------------------------------------


def build_serve_step(model, plan, mesh, kind: str):
    """kind in {'prefill', 'decode'}. Returns (fn, param_specs)."""
    pspecs = param_specs(model, plan, mesh)
    axes = mesh_axes(mesh)

    # prefill SP: when KV heads don't divide the model axis (MHA
    # archs), shard the 32k sequence over 'model' for the prefill
    # activations — the attention q-block tiles divide accordingly
    hint = None
    if (kind == "prefill"
            and model.cfg.n_kv_heads % axes.get("model", 1) != 0
            and model.cfg.family not in ("ssm", "hybrid")):
        b_entry = plan.batch_axes[0] if plan.batch_axes else None
        hint = P(b_entry, "model")

    if kind == "prefill":
        def fn(params, batch, cache):
            from repro.sharding.act_hints import activation_hints
            with activation_hints(hint):
                return model.prefill(params, batch, cache)
    else:
        def fn(params, token, cache):
            return model.decode(params, token, cache)

    return fn, pspecs
