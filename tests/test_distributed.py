"""Distributed-path equivalence tests, run in subprocesses with 8 forced
host devices (the main test process must keep seeing ONE device)."""
import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

_SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def _run(body: str) -> str:
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, {src!r})
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
    """).format(src=_SRC) + textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", prog],
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_distributed_ring_bit_matches_simulation():
    print(_run("""
        from repro.core import ring_reduce
        rng = np.random.default_rng(2)
        full = jnp.asarray(rng.normal(size=(8, 515)), jnp.float32)
        orders = {1: (0,), 2: (1, 0), 4: (2, 0, 3, 1),
                  8: (3, 1, 4, 0, 7, 5, 2, 6)}
        for k in [1, 2, 4, 8]:
            mesh = jax.sharding.Mesh(
                np.asarray(jax.devices()[:k]), ("dp",))
            xs = full[:k]
            order = orders[k]
            for quant in ["fp32", "int8", "int4"]:
                for buckets in ([1, 3] if quant == "int8" else [1]):
                    cfg = ring_reduce.RingConfig(quant=quant,
                                                 buckets=buckets)
                    def f(x):
                        return ring_reduce.ring_all_reduce(
                            x[0], "dp", ring_order=order, cfg=cfg)[None]
                    dist = jax.jit(jax.shard_map(
                        f, mesh=mesh, in_specs=P("dp"),
                        out_specs=P("dp"), check_vma=False))(xs)
                    sim = ring_reduce.simulate_ring_all_reduce(
                        xs, ring_order=order, cfg=cfg)
                    np.testing.assert_array_equal(
                        np.asarray(dist), np.asarray(sim),
                        err_msg=f"k={k} quant={quant} B={buckets}")
        print("RING-EQUIV-OK")
    """))


def test_distributed_outer_sync_matches_simulation():
    out = _run("""
        from repro.core import diloco
        mesh = make_mesh((8,), ("dp",))
        rng = np.random.default_rng(3)
        params = {"a": jnp.asarray(rng.normal(size=(8, 6, 7)),
                                   jnp.float32),
                  "b": jnp.asarray(rng.normal(size=(8, 11)),
                                   jnp.float32)}
        dcfg = diloco.DiLoCoConfig(quant="int8")
        p0 = jax.tree.map(lambda p: p[0], params)
        st = diloco.init_outer_state(p0, dcfg)
        def sync(p, anchor, mom):
            pi = jax.tree.map(lambda x: x[0], p)
            sti = diloco.OuterState(
                anchor, type(st.opt)(mom),
                jnp.zeros((0,), jnp.float32),
                jnp.zeros((), jnp.int32))
            np_, _ = diloco.outer_sync(pi, sti, dcfg, "dp")
            return jax.tree.map(lambda x: x[None], np_)
        dist_p = jax.jit(jax.shard_map(
            sync, mesh=mesh, in_specs=(P("dp"), P(), P()),
            out_specs=P("dp"), check_vma=False))(
                params, st.anchor, st.opt.momentum)
        st_sim = diloco.init_outer_state_sim(p0, dcfg, 8)
        sim_p, _ = diloco.outer_sync_sim(params, st_sim, dcfg)
        for k in ("a", "b"):
            np.testing.assert_allclose(
                np.asarray(dist_p[k]), np.asarray(sim_p[k]),
                rtol=3e-6, atol=3e-7)
        print("SYNC-EQUIV-OK")
    """)
    assert "SYNC-EQUIV-OK" in out


def test_shard_map_train_step_runs_and_reduces_loss():
    out = _run("""
        from repro.configs import CONFIGS
        from repro.models.registry import get_model
        from repro.optim.adamw import AdamW
        from repro.sharding import make_plan
        from repro.train import step as step_lib
        from repro.train.state import TrainState
        from repro.configs.base import ShapeConfig
        import dataclasses

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = CONFIGS["internlm2-1.8b"].reduced()
        shape = ShapeConfig("t", "train", 32, 8)
        plan = make_plan(cfg, shape, {"data": 4, "model": 2})
        assert plan.diloco_axis == "data"
        model = get_model(cfg)
        params, _ = model.init(jax.random.PRNGKey(0))
        k = plan.n_workers
        stack = lambda t: jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (k,) + x.shape), t)
        opt = AdamW(lr=1e-3)
        with mesh:
            step, specs = step_lib.build_train_step(model, plan, mesh,
                                                    opt)
            sp = stack(params)
            so = jax.vmap(opt.init)(sp)
            state = TrainState(sp, so)
            key = jax.random.PRNGKey(1)
            tokens = jax.random.randint(key, (k, 2, 33), 0, cfg.vocab)
            batch = {"tokens": tokens[..., :-1],
                     "targets": tokens[..., 1:],
                     "mask": jnp.ones((k, 2, 32), jnp.float32)}
            jitted = jax.jit(step)
            losses = []
            for i in range(8):
                state, metrics = jitted(state, batch)
                losses.append(float(metrics["loss"].mean()))
        assert losses[-1] < losses[0], losses
        print("TRAIN-STEP-OK", losses[0], losses[-1])
    """)
    assert "TRAIN-STEP-OK" in out


def test_replicated_plan_sync_threads_anchor_flat():
    """Replicated-inner-params plans thread the PERSISTENT flat fp32
    anchor through the shard_map sync (ROADMAP follow-up from PR 1):
    the returned state carries the updated buffer, it matches a fresh
    flatten of the anchor, and chaining two syncs off it matches the
    simulation."""
    out = _run("""
        from repro.core import diloco
        from repro.core.sync_engine import SyncEngine
        from repro.models.registry import get_model
        from repro.configs import CONFIGS
        from repro.configs.base import ShapeConfig
        from repro.sharding import make_plan
        from repro.train import step as step_lib

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = CONFIGS["mamba2-130m"].reduced()
        shape = ShapeConfig("t", "train", 32, 8)
        plan = make_plan(cfg, shape, {"data": 4, "model": 2})
        assert plan.diloco_axis == "data"
        model = get_model(cfg)
        params, _ = model.init(jax.random.PRNGKey(0))
        k = plan.n_workers
        stacked = jax.tree.map(
            lambda x: jnp.stack([x + 0.01 * i for i in range(k)]),
            params)
        dcfg = diloco.DiLoCoConfig(quant="fp32")
        st = diloco.init_outer_state(params, dcfg)
        st = st._replace(residual=jnp.zeros((k, 0), jnp.float32))
        with mesh:
            sync, outer_specs = step_lib.build_outer_sync(
                model, plan, mesh, dcfg)
            # replicated plan => the flat anchor IS threaded
            assert outer_specs.anchor_flat is not None
            w = jnp.ones((k,), jnp.float32)
            jsync = jax.jit(sync)
            new_p, new_st = jsync(stacked, st, w)
            assert new_st.anchor_flat is not None
            # the threaded buffer equals a fresh flatten of the anchor
            eng = SyncEngine.for_tree(new_st.anchor)
            np.testing.assert_array_equal(
                np.asarray(new_st.anchor_flat),
                np.asarray(eng.flatten(new_st.anchor)))
            # chain a second sync off the returned buffer
            new_p2, new_st2 = jsync(new_p, new_st, w)
        sim_st = diloco.init_outer_state_sim(params, dcfg, k)
        sim_p, sim_st = diloco.outer_sync_sim(stacked, sim_st, dcfg)
        sim_p2, _ = diloco.outer_sync_sim(sim_p, sim_st, dcfg)
        for got, want in (((new_p), (sim_p)), ((new_p2), (sim_p2))):
            np.testing.assert_allclose(
                np.asarray(got["embed"], np.float32),
                np.asarray(want["embed"], np.float32),
                rtol=1e-5, atol=1e-6)
        print("ANCHOR-FLAT-OK")
    """)
    assert "ANCHOR-FLAT-OK" in out


def test_sharded_plan_threads_per_shard_anchor_flat():
    """Sharded plans thread the PER-SHARD flat anchor view through the
    manual sync region (PR 5): the concat of each device's local anchor
    shards rides in/out as an opaque buffer, so the pseudo-gradient is
    one subtract off the persistent buffer — and the result is
    BIT-EXACT against the tree-path sync that re-flattens the local
    anchor every call."""
    out = _run("""
        from repro.core import diloco
        from repro.configs import CONFIGS
        from repro.configs.base import ShapeConfig
        from repro.sharding.plans import ParallelismPlan
        from repro.train import step as step_lib
        from repro.models.registry import get_model
        from jax.sharding import PartitionSpec as P

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = CONFIGS["internlm2-1.8b"].reduced()
        # reduced() configs all take the inner-DP (replicated) rules;
        # force real TP sharding so the per-shard path is exercised
        plan = ParallelismPlan(
            diloco_axis="data",
            rules=(("vocab", "model"), ("heads", "model"),
                   ("ff", "model"), ("experts", "model"),
                   ("embed", None), ("layers", None)),
            batch_axes=(), seq_axis=None, remat=False, n_workers=4)
        model = get_model(cfg)
        pspecs = step_lib.param_specs(model, plan, mesh)
        specs = jax.tree.leaves(
            pspecs, is_leaf=lambda x: isinstance(x, P))
        assert any(s != P() for s in specs), "plan must shard params"
        params, _ = model.init(jax.random.PRNGKey(0))
        k = 4
        stacked = jax.tree.map(
            lambda x: jnp.stack([x + 0.01 * i for i in range(k)]),
            params)
        dcfg = diloco.DiLoCoConfig(quant="fp32")
        st = diloco.init_outer_state(params, dcfg)
        st = st._replace(residual=jnp.zeros((k, 0), jnp.float32),
                         anchor_flat=None)
        numel = sum(l.size for l in jax.tree.leaves(params))
        with mesh:
            sync, outer_specs = step_lib.build_outer_sync(
                model, plan, mesh, dcfg)
            # sharded plan => a per-shard flat spec is threaded
            assert outer_specs.anchor_flat is not None
            flat_len = step_lib.flat_anchor_len(model, plan, mesh)
            assert flat_len > numel  # replicated leaves concat per dev
            w = jnp.ones((k,), jnp.float32)
            jsync = jax.jit(sync)
            p1, st1 = jsync(stacked, st, w)
            assert st1.anchor_flat.shape == (flat_len,)
            # chained: threaded buffer vs tree-path rebuild, bit-exact
            p2a, st2a = jsync(p1, st1, w)
            p2b, st2b = jsync(p1, st1._replace(anchor_flat=None), w)
            for a, b in zip(jax.tree.leaves(p2a), jax.tree.leaves(p2b)):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))
            np.testing.assert_array_equal(
                np.asarray(st2a.anchor_flat),
                np.asarray(st2b.anchor_flat))
        # and the sharded sync still equals the unsharded simulation
        sim_st = diloco.init_outer_state_sim(params, dcfg, k)
        sim_p, sim_st = diloco.outer_sync_sim(stacked, sim_st, dcfg)
        sim_p2, _ = diloco.outer_sync_sim(p1, sim_st, dcfg)
        np.testing.assert_allclose(
            np.asarray(p1["embed"], np.float32),
            np.asarray(sim_p["embed"], np.float32),
            rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(p2a["embed"], np.float32),
            np.asarray(sim_p2["embed"], np.float32),
            rtol=1e-4, atol=1e-5)
        print("SHARD-ANCHOR-FLAT-OK")
    """)
    assert "SHARD-ANCHOR-FLAT-OK" in out


def test_full_manual_sync_with_sharded_params():
    """Hybrid FSDP+DiLoCo: per-shard rings on a 2x2 mesh equal the
    unsharded simulation."""
    out = _run("""
        from repro.core import diloco
        from repro.sharding import partition
        from repro.sharding.plans import ParallelismPlan
        from repro.train import step as step_lib
        from repro.models.registry import get_model
        from repro.configs import CONFIGS
        from repro.configs.base import ShapeConfig
        from repro.sharding import make_plan

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = CONFIGS["internlm2-1.8b"].reduced()
        shape = ShapeConfig("t", "train", 32, 8)
        plan = make_plan(cfg, shape, {"data": 4, "model": 2})
        model = get_model(cfg)
        params, _ = model.init(jax.random.PRNGKey(0))
        k = plan.n_workers
        rng = np.random.default_rng(0)
        stacked = jax.tree.map(
            lambda x: jnp.stack([x + 0.01 * i for i in range(k)]),
            params)
        # fp32 ring -> exact equivalence (int8 per-SHARD stats
        # legitimately differ from the sim's per-worker chunk stats)
        dcfg = diloco.DiLoCoConfig(quant="fp32")
        st = diloco.init_outer_state(params, dcfg)
        # the distributed sync expects a stacked per-worker residual
        st = st._replace(residual=jnp.zeros((k, 0), jnp.float32))
        with mesh:
            sync, outer_specs = step_lib.build_outer_sync(
                model, plan, mesh, dcfg)
            w = jnp.ones((k,), jnp.float32)
            new_p, new_st = jax.jit(sync)(stacked, st, w)
        sim_st = diloco.init_outer_state_sim(params, dcfg, k)
        sim_p, _ = diloco.outer_sync_sim(stacked, sim_st, dcfg)
        a = np.asarray(new_p["embed"], np.float32)
        b = np.asarray(sim_p["embed"], np.float32)
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
        print("FULL-MANUAL-SYNC-OK")
    """)
    assert "FULL-MANUAL-SYNC-OK" in out


def test_dist_ring_op_bit_matches_sim_op():
    """Per-hop distributed ring (DistRingSyncOp over jitted shard_map
    hop programs) is bit-identical to the simulator ring — plain,
    fused first hop, non-identity ring order, partial weights, and the
    torn-reduction restart path."""
    out = _run("""
        from repro.core import ring_reduce as rr
        from repro.train import step as ts
        k, size = 4, 37
        mesh = make_mesh((k,), ("data",),
                                devices=np.asarray(jax.devices())[:k])
        cfg = rr.RingConfig(quant="int8", buckets=3)
        rng = np.random.default_rng(0)
        w = jnp.asarray([1.0, 0.0, 1.0, 1.0], jnp.float32)
        order = (2, 0, 3, 1)
        pr = ts.DistSyncPrograms(mesh, "data", size, cfg,
                                 ring_order=order)
        a = jnp.asarray(rng.normal(size=(size,)), jnp.float32)
        thetas = jnp.asarray(rng.normal(size=(k, size)), jnp.float32)
        pgs = a[None] - thetas
        # plain
        ref = rr.simulate_ring_all_reduce(pgs, cfg=cfg,
                                          ring_order=order, weights=w)
        op = ts.DistRingSyncOp(pr, pgs, weights=w)
        hops = 0
        while op.step():
            hops += 1
        assert hops == op.hops_total == 2 * (k - 1)
        np.testing.assert_array_equal(np.asarray(ref),
                                      np.asarray(op.finish()))
        # fused first-hop transmit
        ref2 = rr.simulate_ring_all_reduce(
            pgs, cfg=cfg, ring_order=order, weights=w,
            fused_src=(a, thetas))
        op2 = ts.DistRingSyncOp(pr, pgs, weights=w,
                                fused_src=(a, thetas))
        np.testing.assert_array_equal(np.asarray(ref2),
                                      np.asarray(op2.finish()))
        # restart (torn reduction): re-reduce retained inputs over the
        # survivors, mid-flight state discarded
        op3 = ts.DistRingSyncOp(pr, pgs, weights=w,
                                fused_src=(a, thetas))
        op3.step(); op3.step()
        w2 = jnp.asarray([1.0, 0.0, 1.0, 0.0], jnp.float32)
        ref3 = rr.simulate_ring_all_reduce(
            pgs, cfg=cfg, ring_order=order, weights=w2,
            fused_src=(a, thetas))
        np.testing.assert_array_equal(np.asarray(ref3),
                                      np.asarray(op3.restart(w2)))
        print("DIST-OP-OK")
    """)
    assert "DIST-OP-OK" in out


def test_hierarchical_ring_matches_per_slice_sim():
    """Hierarchical mode ((4, 2) mesh: WAN ring over 'data', intra-node
    split over 'model') is bit-identical to the PER-SLICE simulator:
    each 1/n_local slice ringed independently (its own codebooks), then
    concatenated — the documented equivalence class for the paper's
    ElasticDeviceMesh split."""
    out = _run("""
        from repro.core import elastic_mesh as em
        from repro.core import ring_reduce as rr
        from repro.train import step as ts
        k, size = 4, 37
        mesh = make_mesh((k, 2), ("data", "model"),
                                devices=np.asarray(jax.devices())[:8])
        hier = em.hierarchy(mesh, "data")
        assert hier.split and hier.n_local == 2
        cfg = rr.RingConfig(quant="int8", buckets=3)
        rng = np.random.default_rng(1)
        pgs = jnp.asarray(rng.normal(size=(k, size)), jnp.float32)
        w = jnp.asarray([1.0, 0.0, 1.0, 1.0], jnp.float32)
        order = (2, 0, 3, 1)
        pr = ts.DistSyncPrograms(mesh, "data", size, cfg,
                                 ring_order=order, hierarchy=hier)
        out_h = ts.DistRingSyncOp(pr, pgs, weights=w).finish()
        sl = pr.slice_len
        pad = jnp.pad(pgs, ((0, 0), (0, hier.n_local * sl - size)))
        parts = [rr.simulate_ring_all_reduce(
                     pad[:, i * sl:(i + 1) * sl], cfg=cfg,
                     ring_order=order, weights=w)
                 for i in range(hier.n_local)]
        ref = jnp.concatenate(parts, axis=1)[:, :size]
        np.testing.assert_array_equal(np.asarray(ref),
                                      np.asarray(out_h))
        print("HIER-OK")
    """)
    assert "HIER-OK" in out


def test_dist_backend_trainer_bit_identical_to_sim():
    """The acceptance test: an ElasticTrainer running overlap='delayed'
    through DistSyncBackend (real per-hop shard_map collectives over a
    4-way mesh) is bit-identical to the simulator trainer over 4 outer
    steps — including a worker CRASHING mid-overlap at step 2, which
    takes the torn-reduction fallback on both paths."""
    out = _run("""
        from repro.configs import CONFIGS
        from repro.core import diloco as dl
        from repro.core.fault_tolerance import (ClusterSimulator,
                                                EventKind, NodeEvent)
        from repro.data.pipeline import DataConfig
        from repro.models.registry import get_model
        from repro.train import step as ts
        from repro.train.loop import ElasticTrainer, TrainerConfig

        K, INNER, CHUNKS, STEPS = 4, 5, 7, 4

        def make_trainer(backend=None):
            cfg = CONFIGS["mamba2-130m"].reduced()
            model = get_model(cfg)
            params, _ = model.init(jax.random.PRNGKey(0))
            dcfg = DataConfig(vocab=cfg.vocab, seq_len=32,
                              batch_per_worker=2,
                              total_steps=INNER * 32)
            tcfg = TrainerConfig(
                diloco=dl.DiLoCoConfig(inner_steps=INNER, quant="int8",
                                       overlap="delayed",
                                       error_feedback=True),
                inner_lr=3e-3, max_workers=K, inner_chunks=CHUNKS)
            ev = [NodeEvent(2, EventKind.CRASH, 1)]
            return ElasticTrainer(
                model, tcfg, dcfg, params,
                ClusterSimulator(list(range(K)), events=ev),
                sync_backend=backend)

        t_sim = make_trainer()
        hist_sim = t_sim.run(STEPS)
        mesh = make_mesh((K,), ("data",),
                                devices=np.asarray(jax.devices())[:K])
        backend = ts.DistSyncBackend(mesh, "data")
        t_dist = make_trainer(backend=backend)
        hist_dist = t_dist.run(STEPS)

        torn = [("sync_fallback" in r) for r in hist_dist]
        assert torn == [("sync_fallback" in r) for r in hist_sim]
        assert any(torn), "crash at step 2 must tear the in-flight sync"
        for ls, ld in zip(jax.tree.leaves(t_sim.params),
                          jax.tree.leaves(t_dist.params)):
            np.testing.assert_array_equal(np.asarray(ls),
                                          np.asarray(ld))
        np.testing.assert_array_equal(
            np.asarray(t_sim.outer.anchor_flat),
            np.asarray(t_dist.outer.anchor_flat))
        assert all(r["loss"] == s["loss"]
                   for r, s in zip(hist_dist, hist_sim))
        assert backend.recompiles == 1   # stable ring order: one build
        print("TRAINER-EQUIV-OK")
    """)
    assert "TRAINER-EQUIV-OK" in out
