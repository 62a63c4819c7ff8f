#!/usr/bin/env python3
"""Chip smoke test: the DiLoCo trainer and the serving engines on a TPU,
with their Pallas kernels compiled (never interpreted).

    python chip_smoke.py               # one chip: train + serve phases
    python chip_smoke.py --four-chips  # four chips: distributed outer
                                       # sync against the simulator only

One process, no child processes. It exits non-zero before any work
when JAX finds no TPU, and non-zero when any check fails.

train  mamba2-130m at published widths: ``ElasticTrainer`` from the
       training launcher's own builder, 2 live DiLoCo workers (2 slots),
       seq 1024, 4 inner x 2 outer steps, int8 ring through the Pallas
       codec. Losses must be finite. On the last staged pseudo-gradient
       (one worker's full 129 M-element row) the Pallas quantize and
       dequant-add are checked against ``kernels/ref.py``.
serve  internlm2-1.8b at published widths: 8 slots x 4096 tokens, greedy
       requests of 1500-3000 prompt tokens (6-12 live 256-cell KV blocks
       per slot) through ``ContinuousEngine`` and ``PagedEngine`` from
       the serving launcher's builders with Pallas flash-decode. One
       decode step's logits are checked against the jnp
       ``decode_attention`` path; dense and paged greedy tokens must be
       equal.
four   ``ElasticTrainer`` (mamba2-130m widths cut to 4 layers, 4 slots)
       with ``DistSyncBackend`` over a 4-device mesh vs the single-device
       simulator trainer on the same seed: the last outer sync's staged
       inputs equal, a hop-by-hop replay of it through both ring paths
       equal after every hop, anchors bit-identical, ring accumulator
       rows on 4 distinct devices.

Every compiled program a check runs is asserted to hold a
``tpu_custom_call`` (a compiled Pallas kernel). Lines before the last
are information, not metrics. The last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

# -- tolerances ---------------------------------------------------------------
# Range: the kernel sums in float32 in two levels (per block, then over
# blocks) in a fixed order; its ends stay within a thousandth of a bucket
# width of the float64 range (a shift of d widths re-buckets about a
# fraction d of the elements, none by more than one bucket).
RANGE_TOL_WIDTHS = 1e-3
# Codes: the kernel buckets with (x - lo) * (1 / width), the reference with
# (x - lo) / width; the two roundings can disagree for a value that sits on
# a bucket edge, moving it by one bucket. Anything more is a fault.
CODE_MISMATCH_MAX_FRAC = 1e-5
CODE_MISMATCH_MAX_STEP = 1
# Codebook: bucket means from the kernel's lane-partial float32 sums vs
# exact (float64) means of the same codes; a hundredth of a bucket width
# is above float32 summation error and far below any real fault.
CODEBOOK_TOL_WIDTHS = 1e-2
# Dequantize / dequant-add copy codebook values and add once in float32 in
# both paths, so they agree to float32 rounding.
DEQUANT_RTOL = 1e-6
# Logits of one bf16 decode step, Pallas flash-decode vs jnp attention: the
# jnp path rounds attention scores to bf16 (2^-8 relative) where the kernel
# keeps float32, and 24 layers compound it. Relative L2 error bound:
LOGITS_REL_L2 = 5e-2

# 2 live workers in 2 slots: the inner phase compiles to 14.3e9 bytes
# at these sizes for a v5e, and the outer state adds 1.6e9
TRAIN_ARGS = ["--arch", "mamba2-130m", "--workers", "2",
              "--seq-len", "1024", "--batch-per-worker", "1",
              "--inner-steps", "4", "--outer-steps", "2",
              "--quant", "int8", "--quant-impl", "pallas"]
# 4 live workers in 4 slots, one per chip of the ring. The stacked
# trainer state stays on the first chip, so depth, sequence and steps
# are cut (widths unchanged): the phase checks the ring, not the model.
FOUR_CHIP_ARGS = ["--arch", "mamba2-130m", "--workers", "4",
                  "--seq-len", "256", "--batch-per-worker", "1",
                  "--inner-steps", "2", "--outer-steps", "2",
                  "--quant", "int8", "--quant-impl", "pallas"]
FOUR_CHIP_LAYERS = 4
# prompts of 1500-3000 tokens: every slot's decode streams 6-12 live
# 256-cell blocks, so flash-decode's cross-block rescale runs
SERVE_ARGS = ["--arch", "internlm2-1.8b", "--slots", "8",
              "--max-len", "4096", "--attn-impl", "pallas",
              "--requests", "8", "--prompt-len", "3000",
              "--max-new", "16", "--decode-chunk", "8"]
# dense flash-decode streams 256-cell blocks; a paged pool with the same
# block size runs the identical per-block accumulation (bitwise-equal by
# construction), so dense and paged greedy tokens must match exactly
PAGED_BLOCK = "256"


def info(msg: str) -> None:
    print(f"[info] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


class CompileClock:
    """Sums the backend compile durations JAX reports (information)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def compiled(fn, *args):
    """Lower + compile ``fn`` (jitted here unless it already is) at
    ``args``; returns the compiled program after asserting it holds a
    compiled Pallas kernel."""
    import jax
    if not hasattr(fn, "lower"):
        fn = jax.jit(fn)
    c = fn.lower(*args).compile()
    check("tpu_custom_call" in c.as_text(),
          f"{getattr(fn, '__name__', fn)}: no tpu_custom_call in the "
          f"compiled program")
    return c


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# -- train ---------------------------------------------------------------------


class _RecordingSim:
    """Sync backend that stages the simulator ring as the trainer does
    without one, and keeps the handle of outer sync number ``keep`` so
    its pseudo-gradients can be checked after the run (only that one:
    a retained handle holds two (slots, params) float32 buffers)."""

    def __init__(self, keep: int):
        self.keep, self.begins, self.handle = keep, 0, None

    def begin(self, *args, **kw):
        from repro.core import diloco as dl
        h = dl.begin_outer_sync_sim(*args, **kw)
        self.begins += 1
        if self.begins == self.keep:
            self.handle = h
        return h


def check_ring_hops(op) -> list[str]:
    """Compile the per-hop programs ``RingSyncOp.step`` dispatched for
    ``op``'s ring geometry (the same jitted objects) and assert each
    holds a compiled Pallas kernel; returns the hop kinds."""
    import jax
    import jax.numpy as jnp

    from repro.core import ring_reduce as rr

    k = op.k
    geo = (k, op._chunk, op._bsize, op._nb, op.cfg)
    hop = jax.ShapeDtypeStruct((), jnp.int32)
    accs = jax.ShapeDtypeStruct((k, k * op._chunk), jnp.float32)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    kinds = {"ag_init": (accs,)}
    if op._fused0:
        kinds["rs_fused"] = (hop, accs, like(op._a_flat), like(op._t_pos),
                             like(op._w_pos))
    if k - 1 > int(op._fused0):
        kinds["rs"] = (hop, accs)
    payloads = jax.eval_shape(rr._hop_jit("ag_init", *geo), accs)[1]
    kinds["ag"] = (hop, accs, payloads)
    for kind, args in kinds.items():
        compiled(rr._hop_jit(kind, *geo), *args)
    return sorted(kinds)


def _range_err_widths(lo, width, x64) -> float:
    """Largest shift of the range's two ends from the float64 range
    (mean -+ 6 std), in float64 bucket widths."""
    from repro.kernels import ref
    half = ref.CLIP_SIGMAS * x64.std()
    lo64, w64 = x64.mean() - half, 2.0 * half / ref.NUM_BUCKETS
    hi = float(lo) + ref.NUM_BUCKETS * float(width)
    return max(abs(float(lo) - lo64), abs(hi - (lo64 + 2.0 * half))) / w64


def check_int8_codec(a_flat, theta) -> dict:
    """Pallas int8 codec vs ``kernels/ref.py`` on one worker's staged
    pseudo-gradient ``a_flat - theta`` (the fused first-hop transmit)."""
    import numpy as np

    from repro.kernels import int8_quant, ref

    pg = a_flat - theta
    pg64 = np.asarray(pg, np.float64).reshape(-1)
    rng_prog = compiled(
        lambda a, t: int8_quant.pseudograd_quant_range(a, t), a_flat, theta)
    lo, width = rng_prog(a_flat, theta)
    range_err = _range_err_widths(lo, width, pg64)
    ref_range_err = _range_err_widths(*ref.quant_params(pg), pg64)
    info(f"codec: range (lo, width) error vs float64, in bucket widths: "
         f"kernel {range_err:.3g}, jnp reference {ref_range_err:.3g}")
    check(range_err <= RANGE_TOL_WIDTHS,
          f"quantization range off by {range_err:.3g} bucket widths")
    enc = compiled(
        lambda a, t, lo, w: int8_quant.pseudograd_encode_hist(
            a, t, lo, w), a_flat, theta, lo, width)
    codes, sums, counts = enc(a_flat, theta, lo, width)
    codes_ref = ref.encode(pg, lo, width)
    diff = np.abs(np.asarray(codes, np.int32)
                  - np.asarray(codes_ref, np.int32))
    n = pg.size
    frac = float(np.count_nonzero(diff)) / n
    info(f"codec: {n} elements, {np.count_nonzero(diff)} codes differ "
         f"from ref (max step {int(diff.max())})")
    check(frac <= CODE_MISMATCH_MAX_FRAC,
          f"codes differ from ref on {frac:.3g} of {n} elements")
    check(int(diff.max()) <= CODE_MISMATCH_MAX_STEP,
          f"a code differs from ref by {int(diff.max())} buckets")
    # the histogram of the kernel's own codes must be exact, and its
    # bucket means agree with exact means of those same codes (vs the
    # reference's own codes, one moved element can shift a sparse edge
    # bucket's mean by a whole width). The exact means are float64
    # bincounts on the host: at this size the reference's float32
    # scatter-add is itself off by more than the tolerance.
    sums_ref, counts_ref = ref.bucket_stats(pg, codes)
    check(np.array_equal(np.asarray(counts), np.asarray(counts_ref)),
          "histogram counts differ from ref")
    check(int(np.asarray(counts).sum()) == n,
          f"histogram counts sum to {int(np.asarray(counts).sum())}, "
          f"not {n}")
    c64 = np.asarray(codes).reshape(-1).astype(np.int64)
    counts64 = np.bincount(c64, minlength=ref.NUM_BUCKETS)
    sums64 = np.bincount(c64, weights=pg64, minlength=ref.NUM_BUCKETS)
    centers = (float(lo) + (np.arange(ref.NUM_BUCKETS) + 0.5)
               * float(width))
    exact = np.where(counts64 > 0, sums64 / np.maximum(counts64, 1),
                     centers)
    book = ref.make_codebook(sums, counts, lo, width)
    book_err = float(np.max(np.abs(np.asarray(book, np.float64) - exact))
                     / float(width))
    ref_err = float(np.max(np.abs(np.asarray(
        ref.make_codebook(sums_ref, counts_ref, lo, width), np.float64)
        - exact)) / float(width))
    info(f"codec: codebook error vs exact means, in bucket widths: "
         f"kernel {book_err:.3g}, jnp reference {ref_err:.3g}")
    check(book_err <= CODEBOOK_TOL_WIDTHS,
          f"codebook off by {book_err:.3g} bucket widths")

    dec = compiled(lambda c, b, a: int8_quant.decode_add(c, b, a),
                   codes, book, theta)
    got = np.asarray(dec(codes, book, theta))
    want = np.asarray(theta + ref.dequantize(ref.Quantized(codes, book)))
    np.testing.assert_allclose(got, want, rtol=DEQUANT_RTOL, atol=0)
    deq_err = float(np.max(np.abs(got - want)))
    return {"elements": n, "range_err_widths": range_err,
            "ref_range_err_widths": ref_range_err,
            "code_mismatch_frac": frac,
            "codebook_err_widths": book_err,
            "ref_codebook_err_widths": ref_err,
            "dequant_add_max_abs": deq_err}


def train_phase(argv: list[str]) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import train

    args = train.build_parser().parse_args(argv)
    t0 = time.perf_counter()
    rec = _RecordingSim(keep=args.outer_steps)
    trainer = train.build_trainer(args, sync_backend=rec,
                                  max_workers=args.workers)
    jax.block_until_ready(trainer.params)
    t_build = time.perf_counter() - t0
    t0 = time.perf_counter()
    hist = trainer.run(args.outer_steps, inner_steps=args.inner_steps)
    jax.block_until_ready(trainer.outer.anchor_flat)
    t_run = time.perf_counter() - t0
    losses = [h["loss"] for h in hist]
    info(f"train: build {t_build:.1f} s, {len(hist)} outer steps "
         f"{t_run:.1f} s, losses {losses}")
    check(all(np.isfinite(losses)), f"non-finite losses {losses}")
    check(bool(jnp.isfinite(trainer.outer.anchor_flat).all()),
          "non-finite anchor")

    h = rec.handle
    k = h.k
    hops = check_ring_hops(h.op)
    info(f"train: ring hop programs {hops} hold compiled kernels")
    a_flat, thetas = h.op.fused_src
    slot = int(np.argmax(np.asarray(h.weights) > 0))
    theta = thetas[slot]
    n_params = int(a_flat.size)
    chunk = int(h.op._chunk)
    del trainer, rec, h, thetas
    gc.collect()
    codec = check_int8_codec(a_flat, theta)
    return {"params": n_params, "slots": k, "losses": losses,
            "build_s": t_build, "run_s": t_run, "ring_hops": hops,
            "ring_chunk_elements": chunk, **codec}


# -- serve --------------------------------------------------------------------


def serve_phase(argv: list[str], paged_block: str) -> dict:
    import dataclasses

    import jax
    import numpy as np

    from repro.launch import serve
    from repro.models.registry import get_model

    args = serve.build_parser().parse_args(argv)
    t0 = time.perf_counter()
    cfg, model, params = serve.build_model(args)
    jax.block_until_ready(params)
    t_build = time.perf_counter() - t0

    # admissions prefill one request at a time, as the paged engine's
    # do: a batched prefill is not bitwise equal to a B=1 one (ROADMAP
    # D6), and the dense == paged check below is bitwise
    t0 = time.perf_counter()
    dense = serve.build_engine(args, model, params, batch_admit=False)
    reqs = serve.submit_requests(dense, args, cfg.vocab)
    dense.run_until_drained()
    t_dense = time.perf_counter() - t0
    toks_dense = [list(r.out_tokens) for r in reqs]
    check(all(len(t) == args.max_new for t in toks_dense),
          f"dense engine emitted {[len(t) for t in toks_dense]} tokens")
    lens = np.asarray(dense.cache["scan"].length[0])
    blocks = -(-lens // int(paged_block))
    info(f"serve: cache lengths {lens.tolist()}, live KV blocks per slot "
         f"{int(blocks.min())}-{int(blocks.max())}")
    check(int(blocks.min()) >= 2,
          f"a slot spans {int(blocks.min())} KV block(s); the decode "
          f"check needs several")

    # one decode step on the engine's live cache, Pallas vs jnp
    jnp_model = get_model(dataclasses.replace(cfg, decode_attn_impl="jnp"))
    step = compiled(lambda p, t, c: model.decode(p, t, c)[0],
                    params, dense.tokens, dense.cache)
    got = np.asarray(step(params, dense.tokens, dense.cache), np.float64)
    want = np.asarray(jax.jit(lambda p, t, c: jnp_model.decode(p, t, c)[0])(
        params, dense.tokens, dense.cache), np.float64)
    check(np.isfinite(got).all(), "non-finite Pallas decode logits")
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    check(rel <= LOGITS_REL_L2,
          f"decode logits rel L2 {rel:.3g} > {LOGITS_REL_L2}")
    argmax_agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    del dense, step
    gc.collect()

    t0 = time.perf_counter()
    pargs = serve.build_parser().parse_args(
        argv + ["--engine", "paged", "--block-size", paged_block])
    paged = serve.build_engine(pargs, model, params)
    preqs = serve.submit_requests(paged, pargs, cfg.vocab)
    paged.run_until_drained()
    t_paged = time.perf_counter() - t0
    toks_paged = [list(r.out_tokens) for r in preqs]
    check(toks_paged == toks_dense,
          "paged greedy tokens differ from the dense engine's")
    compiled(lambda p, t, c: model.decode(p, t, c)[0],
             params, paged.tokens, paged.cache)
    return {"params": int(sum(x.size for x in jax.tree.leaves(params))),
            "requests": len(reqs), "tokens": sum(map(len, toks_dense)),
            "live_kv_blocks": [int(blocks.min()), int(blocks.max())],
            "logits_rel_l2": rel, "logits_argmax_agree": argmax_agree,
            "build_s": t_build, "dense_s": t_dense, "paged_s": t_paged}


# -- four chips ---------------------------------------------------------------


def _bits(a):
    """Host copy of ``a`` viewed as unsigned ints of its width, so
    equality is bit equality."""
    import numpy as np
    a = np.asarray(a)
    return a.view(f"u{a.dtype.itemsize}")


def replay_last_sync(h_dist) -> list[dict]:
    """Re-run the distributed trainer's last outer sync from its staged
    inputs through a fresh simulator ``RingSyncOp`` and a fresh
    ``DistRingSyncOp``, one wire hop at a time. After every hop, count
    the accumulator elements whose bits differ (and, once the
    all-gather payloads exist, differing codes and codebook entries).
    Simulator rows are ring positions, distributed rows are workers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ring_reduce as rr
    from repro.train.step import DistRingSyncOp

    src = h_dist.op
    inv = jnp.asarray(np.argsort(np.asarray(src.ring_order)))
    sim = rr.RingSyncOp(src.xs, ring_order=src.ring_order, cfg=src.cfg,
                        weights=src.weights, fused_src=src.fused_src)
    dist = DistRingSyncOp(src.programs, src.xs, weights=src.weights,
                          fused_src=src.fused_src)
    dev = jax.devices()[0]

    def ndiff(by_pos, by_worker) -> int:
        a, b = by_pos[inv], jax.device_put(by_worker, dev)
        if a.dtype == jnp.float32:
            a = jax.lax.bitcast_convert_type(a, jnp.uint32)
            b = jax.lax.bitcast_convert_type(b, jnp.uint32)
        return int(jnp.count_nonzero(a != b))

    hops = []
    while sim.pending:
        sim.step()
        dist.step()
        hop = {"accs": ndiff(sim._accs, dist._accs)}
        if sim._payloads is not None:
            hop["codes"] = sum(ndiff(p[0], q[0]) for p, q in
                               zip(sim._payloads, dist._payloads))
            hop["codebook"] = sum(ndiff(p[1], q[1]) for p, q in
                                  zip(sim._payloads, dist._payloads))
        hops.append(hop)
    return hops


def four_chip_phase(argv: list[str], n_layers: int) -> dict:
    import jax
    import numpy as np

    from repro.launch import train
    from repro.launch.mesh import make_mesh
    from repro.train.step import DistSyncBackend

    class Recording(DistSyncBackend):
        handle = None

        def begin(self, *a, **kw):
            self.handle = super().begin(*a, **kw)
            self.row_shards = self.handle.op.row_shards
            return self.handle

    args = train.build_parser().parse_args(argv)
    t0 = time.perf_counter()
    rec = _RecordingSim(keep=args.outer_steps)
    sim = train.build_trainer(args, sync_backend=rec, n_layers=n_layers,
                              max_workers=args.workers)
    losses_sim = [h["loss"] for h in sim.run(args.outer_steps,
                                             inner_steps=args.inner_steps)]
    anchor_sim = np.asarray(sim.outer.anchor_flat)
    k = sim.k
    del sim
    gc.collect()
    t_sim = time.perf_counter() - t0
    info(f"four chips: simulator ring {t_sim:.1f} s, losses {losses_sim}")

    t0 = time.perf_counter()
    mesh = make_mesh((k,), ("diloco",), devices=jax.devices()[:k])
    backend = Recording(mesh, "diloco")
    dist = train.build_trainer(args, sync_backend=backend,
                               n_layers=n_layers, max_workers=args.workers)
    losses = [h["loss"] for h in dist.run(args.outer_steps,
                                          inner_steps=args.inner_steps)]
    anchor_dist = np.asarray(dist.outer.anchor_flat)
    del dist
    gc.collect()
    t_dist = time.perf_counter() - t0
    info(f"four chips: distributed ring {t_dist:.1f} s, losses {losses}")

    # where a difference enters: the last sync's staged inputs (from the
    # inner phases and earlier syncs), then that sync hop by hop
    h_sim, h_dist = rec.handle, backend.handle
    staged = {name: int(np.count_nonzero(_bits(a) != _bits(b)))
              for name, a, b in [("pseudograds", h_sim.op.xs, h_dist.op.xs),
                           ("thetas", h_sim.op.fused_src[1],
                            h_dist.op.fused_src[1])]}
    t0 = time.perf_counter()
    hops = replay_last_sync(h_dist)
    info(f"four chips: last sync staged inputs differing {staged}; "
         f"replayed hop by hop ({time.perf_counter() - t0:.1f} s), "
         f"differing per hop {hops}")
    n_anchor = int(np.count_nonzero(_bits(anchor_dist)
                                    != _bits(anchor_sim)))
    info(f"four chips: {n_anchor} of {anchor_sim.size} anchor elements "
         f"differ")
    check(all(np.isfinite(losses)), f"non-finite losses {losses}")
    check(not any(staged.values()),
          f"last sync's staged inputs differ: {staged}")
    check(not any(any(h.values()) for h in hops),
          f"ring replay differs between the paths: {hops}")
    check(n_anchor == 0,
          f"distributed anchor differs from the simulator's: {n_anchor} "
          f"elements")
    devs = {d for d, _ in backend.row_shards}
    check(len(devs) == k and all(r == 1 for _, r in backend.row_shards),
          f"ring rows not one per device: {backend.row_shards}")
    return {"slots": k, "layers": n_layers, "mesh_devices": len(devs),
            "losses": losses, "anchor_elements": int(anchor_sim.size),
            "bit_identical": True, "replayed_hops": len(hops),
            "sim_s": t_sim, "dist_s": t_dist}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip distributed outer-sync "
                         "check (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache
    info(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    seed = ["--seed", str(args.seed)]
    phases = ([("four_chips",
                lambda: four_chip_phase(FOUR_CHIP_ARGS + seed,
                                        FOUR_CHIP_LAYERS))]
              if args.four_chips else
              [("train", lambda: train_phase(TRAIN_ARGS + seed)),
               ("serve", lambda: serve_phase(SERVE_ARGS + seed,
                                             PAGED_BLOCK))])
    for name, run in phases:
        c0, t0 = clock.seconds, time.perf_counter()
        out = run()
        out["wall_s"] = time.perf_counter() - t0
        out["compile_s"] = clock.seconds - c0
        out["peak_bytes_in_use"] = peak_bytes()
        info(f"phase {name}: {json.dumps(out, default=str)}")
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
