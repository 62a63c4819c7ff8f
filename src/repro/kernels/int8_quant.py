"""Pallas TPU kernels for PRIME int8 pseudo-gradient quantization.

TPU adaptation of the paper's custom multithreaded C++ uint8 quantization
(INTELLECT-1 §2.2).  The GPU/CPU version scatter-adds into 256 histogram
bins; TPUs have no fast scatter or vector gather, so both directions are
written as per-bucket compare/select sweeps on the VPU that never move
data across lanes:

  * the histogram keeps a (256, 128) lane-partial accumulator per output
    (row b = bucket b, one partial per lane); each bucket's masked
    values are summed over the sublane axis only, and the last grid
    step sums the 128 lanes;
  * decode reads the codebook pre-broadcast to (256, 128) (row b =
    codebook[b] in every lane) and selects row b wherever code == b.

Layout: the flat tensor is padded and viewed as (rows, 128) with fp32
blocks of (BLOCK_ROWS, 128) staged through VMEM; inside a block the
kernels walk (SLAB_ROWS, 128) slabs through ``pl.ds`` ref slices. The
histogram accumulators are revisited by every grid step (constant index
map) and zeroed on the first one. Counts accumulate in int32, so they
stay exact past 2^24 elements, and the tail mask compares int32 element
indices against an int32 ``nvalid`` read from SMEM.

Every reduction that decides a quantization (range, histogram) runs
inside a kernel in a fixed order, so a row's codes and codebook are the
same bits whether it is quantized alone or in a vmapped batch, in one
program or another: the distributed ring (one row per device) matches
the single-device simulator (k rows) on TPU, where XLA may reduce a
batch in another order than one row.

Kernels:
  * ``quant_range``      — (lo, width): masked mean, then masked
                           centered sum of squares (two passes)
  * ``encode_hist``      — codes + per-bucket (sum, count) in one pass
  * ``pseudograd_encode``— fused (anchor - theta) + encode (+hist); saves
                           one HBM round-trip for the DiLoCo outer step
  * ``decode``           — codebook[codes] via per-bucket selects
  * ``decode_add``       — fused dequantize-accumulate for the fp32 ring
                           accumulator (one pass instead of two)

All kernels are validated against ``ref.py`` in interpret mode
(tests/test_kernels.py) and compiled for a described TPU v5e
(tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

LANE = 128           # TPU lane width
BLOCK_ROWS = 512     # (512, 128) fp32 = 256 KiB / block in VMEM
SLAB_ROWS = 64       # rows per in-block slab (8 fp32 vregs)
SUBLANE = 8          # rows of one fp32 vreg / (8, 128) tile
GROUP = SUBLANE      # buckets per (8, 128) histogram accumulator tile
NUM_BUCKETS = ref.NUM_BUCKETS


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# shared slab helpers
# ---------------------------------------------------------------------------


def _load_slab(x_ref, anchor_ref, r, scale):
    """fp32 (SLAB_ROWS, 128) slab at row ``r``: x, or the fused
    pseudo-gradient ``scale * (anchor - x)`` when ``anchor_ref`` is
    given."""
    x = x_ref[pl.ds(r, SLAB_ROWS), :].astype(jnp.float32)
    if anchor_ref is not None:
        a = anchor_ref[pl.ds(r, SLAB_ROWS), :].astype(jnp.float32)
        x = (a - x) * scale
    return x


def _valid(pid, block_rows: int, r, nvalid):
    """Tail mask of a slab: int32 global element index (exact to 2^31)
    below the int32 element count."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (SLAB_ROWS, LANE), 0) + (
        pid * block_rows + r)
    cols = jax.lax.broadcasted_iota(jnp.int32, (SLAB_ROWS, LANE), 1)
    return rows * LANE + cols < nvalid


def _split_refs(refs, fused_sub: bool):
    """(anchor_ref or None, output refs) of a kernel's trailing refs."""
    return (refs[0], refs[1:]) if fused_sub else (None, refs)


def _lane_rows(vals) -> jnp.ndarray:
    """(8, 128) tile whose row j holds scalar ``vals[j]`` in every lane
    (the rest zero) — how per-call scalars enter and leave the kernels:
    a vmapped call then carries one tile per batch row."""
    tile = jnp.zeros((8, LANE), jnp.float32)
    return tile.at[:len(vals)].set(
        jnp.stack([jnp.asarray(v, jnp.float32) for v in vals])[:, None])


# ---------------------------------------------------------------------------
# quantization range (mean / std -> lo, width)
# ---------------------------------------------------------------------------


def _stats_body(fscal_ref, n_ref, x_ref, *refs, block_rows: int,
                centered: bool, fused_sub: bool):
    """One grid step of a masked sum over the flat tensor: of x
    (``centered=False``) or of (x - mu)^2. The (8, 128) output block
    is the accumulator; the last step reduces it in a fixed order and
    writes [mean] or [lo, width] (see :func:`quant_range`)."""
    anchor_ref, (out_ref,) = _split_refs(refs, fused_sub)
    pid = pl.program_id(0)
    scale = fscal_ref[0:1, :]
    mu = fscal_ref[1:2, :]
    n = fscal_ref[2:3, :]
    nvalid = n_ref[0]

    @pl.when(pid == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def slab(i, acc):
        r = pl.multiple_of(i * SLAB_ROWS, SLAB_ROWS)
        v = _load_slab(x_ref, anchor_ref, r, scale)
        if centered:
            v = v - mu
        v = jnp.where(_valid(pid, block_rows, r, nvalid), v, 0.0)
        if centered:
            v = v * v
        for j in range(SLAB_ROWS // SUBLANE):
            acc = acc + v[j * SUBLANE:(j + 1) * SUBLANE]
        return acc

    out_ref[...] += jax.lax.fori_loop(
        0, block_rows // SLAB_ROWS, slab,
        jnp.zeros((SUBLANE, LANE), jnp.float32))

    @pl.when(pid == pl.num_programs(0) - 1)
    def _final():
        total = jnp.sum(jnp.sum(out_ref[...], axis=0, keepdims=True),
                        axis=1, keepdims=True)
        mean = total / n
        sel = jax.lax.broadcasted_iota(jnp.int32, (SUBLANE, LANE), 0)
        if centered:
            half = ref.CLIP_SIGMAS * jnp.sqrt(mean)
            width = jnp.maximum(2.0 * half / NUM_BUCKETS, ref.EPS)
            out = jnp.where(sel == 0, mu - half,
                            jnp.where(sel == 1, width, 0.0))
        else:
            out = jnp.where(sel == 0, mean, 0.0)
        out_ref[...] = out


def _stats_pass(x2d, a2d, nblocks: int, fscal, nvalid, *, block_rows: int,
                centered: bool, interpret: bool):
    fused_sub = a2d is not None
    in_specs = [
        pl.BlockSpec((SUBLANE, LANE), lambda i: (0, 0)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
    ]
    args = [fscal, nvalid, x2d]
    if fused_sub:
        in_specs.append(pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)))
        args.append(a2d)
    return pl.pallas_call(
        functools.partial(_stats_body, block_rows=block_rows,
                          centered=centered, fused_sub=fused_sub),
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((SUBLANE, LANE), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((SUBLANE, LANE), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*args)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "fused_sub", "interpret"))
def _range_call(x_flat, anchor_flat, scale, *, block_rows: int,
                fused_sub: bool, interpret: bool):
    x2d, nblocks = _pad_rows(x_flat, block_rows)
    a2d = _pad_rows(anchor_flat, block_rows)[0] if fused_sub else None
    nvalid = jnp.full((1,), x_flat.size, jnp.int32)
    n = float(x_flat.size)
    run = functools.partial(_stats_pass, x2d, a2d, nblocks, nvalid=nvalid,
                            block_rows=block_rows, interpret=interpret)
    mean = run(_lane_rows([scale, 0.0, n]), centered=False)[0, 0]
    rng = run(_lane_rows([scale, mean, n]), centered=True)
    return rng[0, 0], rng[1, 0]


def quant_range(x: jnp.ndarray, *, block_rows: int = BLOCK_ROWS,
                interpret: bool | None = None):
    """(lo, width) of ``x``'s clipped quantization range, as
    ``ref.quant_params`` defines it (mean +- 6 std over 256 buckets),
    with both reductions inside the kernel in a fixed order."""
    if interpret is None:
        interpret = _interpret_default()
    flat = x.astype(jnp.float32).reshape(-1)
    return _range_call(flat, flat, jnp.float32(1.0), block_rows=block_rows,
                       fused_sub=False, interpret=interpret)


def pseudograd_quant_range(anchor: jnp.ndarray, theta: jnp.ndarray, *,
                           scale=None, block_rows: int = BLOCK_ROWS,
                           interpret: bool | None = None):
    """:func:`quant_range` of ``scale * (anchor - theta)``, read off
    (anchor, theta) without materializing the pseudo-gradient."""
    if interpret is None:
        interpret = _interpret_default()
    return _range_call(
        theta.astype(jnp.float32).reshape(-1),
        anchor.astype(jnp.float32).reshape(-1),
        jnp.float32(1.0 if scale is None else scale),
        block_rows=block_rows, fused_sub=True, interpret=interpret)


# ---------------------------------------------------------------------------
# encode (+ fused pseudo-gradient) + histogram
# ---------------------------------------------------------------------------


def _encode_hist_body(fscal_ref, n_ref, x_ref, *refs, block_rows: int,
                      fused_sub: bool):
    """One grid step: encode a (block_rows, 128) tile and add its
    per-bucket lane partials into the (256, 128) accumulators; the last
    step sums each bucket's lanes (every lane then holds the total)."""
    anchor_ref, (codes_ref, sums_ref, counts_ref) = _split_refs(
        refs, fused_sub)
    pid = pl.program_id(0)
    # (1, 128) lane-broadcast rows: a vmapped ring hop gives every
    # worker row its own (lo, width, scale), which a VMEM tile carries
    lo = fscal_ref[0:1, :]
    inv_width = 1.0 / fscal_ref[1:2, :]
    scale = fscal_ref[2:3, :]
    nvalid = n_ref[0]

    @pl.when(pid == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    sel = jax.lax.broadcasted_iota(jnp.int32, (GROUP, LANE), 0)

    def slab(i, carry):
        r = pl.multiple_of(i * SLAB_ROWS, SLAB_ROWS)
        x = _load_slab(x_ref, anchor_ref, r, scale)
        valid = _valid(pid, block_rows, r, nvalid)

        idx = jnp.floor((x - lo) * inv_width)
        idx = jnp.clip(idx, 0.0, float(NUM_BUCKETS - 1))
        codes = jnp.where(valid, idx, 0.0).astype(jnp.int32)
        codes_ref[pl.ds(r, SLAB_ROWS), :] = codes
        # tail padding gets a code no bucket matches
        hcodes = jnp.where(valid, codes, NUM_BUCKETS)

        def group(g, c):
            b0 = g * GROUP
            s_tile = jnp.zeros((GROUP, LANE), jnp.float32)
            c_tile = jnp.zeros((GROUP, LANE), jnp.int32)
            for j in range(GROUP):
                m = hcodes == b0 + j
                ps = jnp.sum(jnp.where(m, x, 0.0), axis=0, keepdims=True)
                pc = jnp.sum(jnp.where(m, 1, 0), axis=0, keepdims=True)
                s_tile = jnp.where(sel == j, ps, s_tile)
                c_tile = jnp.where(sel == j, pc, c_tile)
            rb = pl.multiple_of(b0, GROUP)
            sums_ref[pl.ds(rb, GROUP), :] += s_tile
            counts_ref[pl.ds(rb, GROUP), :] += c_tile
            return c

        return jax.lax.fori_loop(0, NUM_BUCKETS // GROUP, group, carry)

    jax.lax.fori_loop(0, block_rows // SLAB_ROWS, slab, 0)

    @pl.when(pid == pl.num_programs(0) - 1)
    def _lane_totals():
        sums_ref[...] = jnp.broadcast_to(
            jnp.sum(sums_ref[...], axis=1, keepdims=True), sums_ref.shape)
        counts_ref[...] = jnp.broadcast_to(
            jnp.sum(counts_ref[...], axis=1, keepdims=True),
            counts_ref.shape)


def _pad_rows(flat: jnp.ndarray, block_rows: int) -> tuple[jnp.ndarray, int]:
    n = flat.size
    per_block = block_rows * LANE
    nblocks = max(1, -(-n // per_block))
    padded = nblocks * per_block
    flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(nblocks * block_rows, LANE), nblocks


@functools.partial(
    jax.jit, static_argnames=("block_rows", "fused_sub", "interpret"))
def _encode_hist_call(x_flat, anchor_flat, lo, width, scale, *,
                      block_rows: int, fused_sub: bool, interpret: bool):
    x2d, nblocks = _pad_rows(x_flat, block_rows)
    fscal = _lane_rows([lo, width, scale])
    nvalid = jnp.full((1,), x_flat.size, jnp.int32)

    in_specs = [
        pl.BlockSpec((SUBLANE, LANE), lambda i: (0, 0)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
    ]
    args = [fscal, nvalid, x2d]
    if fused_sub:
        in_specs.append(pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)))
        args.append(_pad_rows(anchor_flat, block_rows)[0])

    codes2d, sums, counts = pl.pallas_call(
        functools.partial(_encode_hist_body, block_rows=block_rows,
                          fused_sub=fused_sub),
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((NUM_BUCKETS, LANE), lambda i: (0, 0)),
            pl.BlockSpec((NUM_BUCKETS, LANE), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2d.shape, jnp.int32),
            jax.ShapeDtypeStruct((NUM_BUCKETS, LANE), jnp.float32),
            jax.ShapeDtypeStruct((NUM_BUCKETS, LANE), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*args)
    return codes2d, sums[:, 0], counts[:, 0].astype(jnp.float32)


def encode_hist(x: jnp.ndarray, lo, width, *, block_rows: int = BLOCK_ROWS,
                interpret: bool | None = None):
    """codes (uint8, x.shape) + per-bucket (sums, counts)."""
    if interpret is None:
        interpret = _interpret_default()
    flat = x.astype(jnp.float32).reshape(-1)
    codes2d, sums, counts = _encode_hist_call(
        flat, flat, jnp.float32(lo), jnp.float32(width), jnp.float32(1.0),
        block_rows=block_rows, fused_sub=False, interpret=interpret)
    codes = codes2d.reshape(-1)[: flat.size].reshape(x.shape)
    return codes.astype(jnp.uint8), sums, counts


def pseudograd_encode_hist(anchor: jnp.ndarray, theta: jnp.ndarray, lo, width,
                           *, scale=None, block_rows: int = BLOCK_ROWS,
                           interpret: bool | None = None):
    """Fused ``scale * (anchor - theta)`` encode: codes + histogram in one
    HBM pass over (anchor, theta) — the pseudo-gradient never hits HBM."""
    if interpret is None:
        interpret = _interpret_default()
    tf = theta.astype(jnp.float32).reshape(-1)
    af = anchor.astype(jnp.float32).reshape(-1)
    codes2d, sums, counts = _encode_hist_call(
        tf, af, jnp.float32(lo), jnp.float32(width),
        jnp.float32(1.0 if scale is None else scale),
        block_rows=block_rows, fused_sub=True, interpret=interpret)
    codes = codes2d.reshape(-1)[: tf.size].reshape(theta.shape)
    return codes.astype(jnp.uint8), sums, counts


# ---------------------------------------------------------------------------
# decode (+ fused accumulate)
# ---------------------------------------------------------------------------


def _decode_body(codes_ref, book_ref, out_ref, *, block_rows: int,
                 accumulate: bool, acc_ref=None):
    """codebook[codes] for one block: for every bucket b, select row b
    of the lane-broadcast codebook wherever code == b (exact copies of
    the codebook values — no arithmetic on them)."""
    def slab(i, carry):
        r = pl.multiple_of(i * SLAB_ROWS, SLAB_ROWS)
        codes = codes_ref[pl.ds(r, SLAB_ROWS), :]

        def group(g, out):
            b0 = g * GROUP
            tile = book_ref[pl.ds(pl.multiple_of(b0, GROUP), GROUP), :]
            for j in range(GROUP):
                out = jnp.where(codes == b0 + j, tile[j:j + 1, :], out)
            return out

        out = jax.lax.fori_loop(0, NUM_BUCKETS // GROUP, group,
                                jnp.zeros((SLAB_ROWS, LANE), jnp.float32))
        if accumulate:
            out = acc_ref[pl.ds(r, SLAB_ROWS), :].astype(jnp.float32) + out
        out_ref[pl.ds(r, SLAB_ROWS), :] = out
        return carry

    jax.lax.fori_loop(0, block_rows // SLAB_ROWS, slab, 0)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "accumulate", "interpret"))
def _decode_call(codes_flat, codebook, acc_flat, *, block_rows: int,
                 accumulate: bool, interpret: bool):
    c2d, nblocks = _pad_rows(codes_flat.astype(jnp.int32), block_rows)
    book = jnp.broadcast_to(
        codebook.astype(jnp.float32).reshape(NUM_BUCKETS, 1),
        (NUM_BUCKETS, LANE))
    in_specs = [
        pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        pl.BlockSpec((NUM_BUCKETS, LANE), lambda i: (0, 0)),
    ]
    args = [c2d, book]
    if accumulate:
        a2d, _ = _pad_rows(acc_flat.astype(jnp.float32), block_rows)
        in_specs.append(pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)))
        args.append(a2d)

        def kernel(codes_ref, book_ref, acc_ref, out_ref):
            _decode_body(codes_ref, book_ref, out_ref,
                         block_rows=block_rows, accumulate=True,
                         acc_ref=acc_ref)
    else:
        kernel = functools.partial(
            _decode_body, block_rows=block_rows, accumulate=False)

    out = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(c2d.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*args)
    return out


def decode(codes: jnp.ndarray, codebook: jnp.ndarray, *,
           block_rows: int = BLOCK_ROWS,
           interpret: bool | None = None) -> jnp.ndarray:
    """codebook[codes] as fp32 (per-bucket selects; no vector gather)."""
    if interpret is None:
        interpret = _interpret_default()
    flat = codes.reshape(-1)
    out = _decode_call(flat, codebook, flat, block_rows=block_rows,
                       accumulate=False, interpret=interpret)
    return out.reshape(-1)[: flat.size].reshape(codes.shape)


def decode_add(codes: jnp.ndarray, codebook: jnp.ndarray, acc: jnp.ndarray,
               *, block_rows: int = BLOCK_ROWS,
               interpret: bool | None = None) -> jnp.ndarray:
    """acc + codebook[codes] fused in one VMEM pass (ring accumulator)."""
    if interpret is None:
        interpret = _interpret_default()
    flat = codes.reshape(-1)
    out = _decode_call(flat, codebook, acc.reshape(-1),
                       block_rows=block_rows, accumulate=True,
                       interpret=interpret)
    return out.reshape(-1)[: flat.size].reshape(acc.shape).astype(acc.dtype)
