"""Pallas TPU flash-decode: grouped-query single-token attention
against the slotted KV cache.

The serve engine's hot loop is one decode step per live slot against a
(B, S_max, Hk, dh) cache with PER-SLOT lengths. The jnp path
materializes the full (B, Hk, G, 1, S_max) score tensor in HBM and
reads the cache twice (scores, then values). This kernel streams the
cache through VMEM once per (slot, kv-head) in S-blocks with an online
softmax (flash-decoding), carrying (m, l, acc) in VMEM scratch across
the sequential TPU grid — no score tensor ever hits HBM, and the
per-slot length/SWA-ring masking happens on the in-VMEM block.

Design notes:
  * grid = (B, Hk, S_blocks); the innermost S dimension revisits the
    same output block (constant index map), so the fp32 accumulator
    lives in the output ref itself — only m and l need scratch.
  * the cache is read through a free (B, S, Hk * dh) view: head h is
    the lane-aligned column block h, so a (1, S_BLK, dh) KV block
    meets the TPU (8, 128) tiling rule whatever Hk is.
  * masks are ONE-HOT-FREE: live cells are found from a broadcasted
    iota of cell indices vs the slot's length (and, for SWA, the ring
    write-cursor arithmetic mirrored from
    ``attention.decode_valid_mask``), never by gathering.
  * q heads are blocked (1, 1, G, dh) and the cache (1, S_BLK, dh):
    the two MXU contractions per block are (G, dh)x(dh, S_BLK) and
    (G, S_BLK)x(S_BLK, dh).
  * dh pads to the 128 lane width, G to the 8-row fp32 sublane tile,
    S to a whole number of blocks — padded cells are masked like any
    dead cell, padded q rows are sliced off on the way out.

Validated against the jnp ``decode_attention`` path in interpret mode
(tests/test_flash_decode.py) and compiled for a described TPU v5e
(tests/test_tpu_compile.py). Selection follows the repo convention:
``impl="pallas" | "jnp"`` (ArchConfig.decode_attn_impl).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANE = 128          # TPU lane width: dh pads to a multiple of this
SUBLANE = 8         # fp32 sublane tile: G pads to a multiple of this
S_BLOCK = 256       # KV cells streamed through VMEM per grid step


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _body(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
          s_blk: int, s_max: int, window: int | None, scale: float):
    b = pl.program_id(0)
    s_i = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(s_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[0, 0]              # (G_p, dh_p)
    k = k_ref[0]                 # (S_BLK, dh_p)
    v = v_ref[0]
    length = len_ref[b]

    # which cells of this block are live for this slot (per-slot
    # length; SWA recovers absolute positions from the ring cursor —
    # same arithmetic as attention.decode_valid_mask)
    cell = s_i * s_blk + jax.lax.broadcasted_iota(
        jnp.int32, (1, s_blk), 1)
    if window is None:
        valid = (cell < length) & (cell < s_max)
    else:
        rem = length % s_max
        abs_pos = jnp.where(
            length > s_max,
            jnp.where(cell < rem, length - rem + cell,
                      length - rem - s_max + cell),
            cell)
        valid = ((abs_pos < length) & (abs_pos >= length - window)
                 & (cell < s_max))

    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (G_p, S_BLK)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                                  # (G_p, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)                      # (G_p, 1)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                              keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (G_p, dh_p)
    o_ref[0, 0] = o_ref[0, 0] * alpha + pv
    m_ref[...] = m_new

    @pl.when(s_i == ns - 1)
    def _finalize():
        o_ref[0, 0] = o_ref[0, 0] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("window", "s_blk",
                                             "interpret"))
def _flash_decode_call(qg, k, v, length, *, window: int | None,
                       s_blk: int, interpret: bool):
    """qg: (B, Hk, G, dh); k/v: (B, S, Hk, dh); length: (B,) int32."""
    b, hk, g, dh = qg.shape
    s_max = k.shape[1]
    g_p = _pad_to(g, SUBLANE)
    dh_p = _pad_to(dh, LANE)
    s_p = _pad_to(s_max, s_blk)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_p - g), (0, dh_p - dh)))
    # (B, S_p, Hk * dh_p): a free view when no padding is needed
    k = jnp.pad(k, ((0, 0), (0, s_p - s_max), (0, 0), (0, dh_p - dh))
                ).reshape(b, s_p, hk * dh_p)
    v = jnp.pad(v, ((0, 0), (0, s_p - s_max), (0, 0), (0, dh_p - dh))
                ).reshape(b, s_p, hk * dh_p)

    kernel = functools.partial(_body, s_blk=s_blk, s_max=s_max,
                               window=window, scale=dh ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid=(b, hk, s_p // s_blk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g_p, dh_p), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, s_blk, dh_p), lambda b, h, s: (b, s, h)),
            pl.BlockSpec((1, s_blk, dh_p), lambda b, h, s: (b, s, h)),
        ],
        out_specs=pl.BlockSpec((1, 1, g_p, dh_p),
                               lambda b, h, s: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hk, g_p, dh_p), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((g_p, 1), jnp.float32),   # running max m
            pltpu.VMEM((g_p, 1), jnp.float32),   # running denom l
        ],
        interpret=interpret,
    )(length.astype(jnp.int32), qg, k, v)
    return out[:, :, :g, :dh]


def _paged_body(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                m_ref, l_ref, *, blk: int, s_max: int,
                window: int | None, scale: float):
    """Same online-softmax math as `_body`, but the KV block streamed
    this grid step is whichever PHYSICAL pool block the slot's table
    maps for virtual block s — the gather happens in the BlockSpec
    index map (scalar-prefetched table), so the kernel body only ever
    sees contiguous (blk, dh) tiles. Virtual cell indices (for length /
    SWA-ring masking) are reconstructed from the grid position, which
    also masks trash-block reads (unmapped entries clamp to block 0 but
    their virtual cells are always >= the slot's length)."""
    b = pl.program_id(0)
    s_i = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(s_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[0, 0]              # (G_p, dh_p)
    k = k_ref[0]                 # (blk, dh_p)
    v = v_ref[0]
    length = len_ref[b]

    cell = s_i * blk + jax.lax.broadcasted_iota(
        jnp.int32, (1, blk), 1)
    if window is None:
        valid = (cell < length) & (cell < s_max)
    else:
        rem = length % s_max
        abs_pos = jnp.where(
            length > s_max,
            jnp.where(cell < rem, length - rem + cell,
                      length - rem - s_max + cell),
            cell)
        valid = ((abs_pos < length) & (abs_pos >= length - window)
                 & (cell < s_max))

    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (G_p, blk)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                              keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0, 0] = o_ref[0, 0] * alpha + pv
    m_ref[...] = m_new

    @pl.when(s_i == ns - 1)
    def _finalize():
        o_ref[0, 0] = o_ref[0, 0] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def _flash_decode_paged_call(qg, k, v, table, length, *,
                             window: int | None, interpret: bool):
    """qg: (B, Hk, G, dh); k/v: (N_blocks, blk, Hk, dh) physical pool;
    table: (B, nb) int32 (-1 = unmapped); length: (B,)."""
    b, hk, g, dh = qg.shape
    blk = k.shape[1]
    nb = table.shape[1]
    s_max = nb * blk
    g_p = _pad_to(g, SUBLANE)
    dh_p = _pad_to(dh, LANE)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_p - g), (0, dh_p - dh)))
    n_blocks = k.shape[0]
    k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, dh_p - dh))
                ).reshape(n_blocks, blk, hk * dh_p)
    v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dh_p - dh))
                ).reshape(n_blocks, blk, hk * dh_p)
    tbl = jnp.maximum(table, 0).astype(jnp.int32)   # clamp to trash blk

    kernel = functools.partial(_paged_body, blk=blk, s_max=s_max,
                               window=window, scale=dh ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hk, nb),
        in_specs=[
            pl.BlockSpec((1, 1, g_p, dh_p),
                         lambda b, h, s, tbl, ln: (b, h, 0, 0)),
            # the block-gather stage: virtual block s of slot b streams
            # physical pool block tbl[b, s] through VMEM
            pl.BlockSpec((1, blk, dh_p),
                         lambda b, h, s, tbl, ln: (tbl[b, s], 0, h)),
            pl.BlockSpec((1, blk, dh_p),
                         lambda b, h, s, tbl, ln: (tbl[b, s], 0, h)),
        ],
        out_specs=pl.BlockSpec((1, 1, g_p, dh_p),
                               lambda b, h, s, tbl, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g_p, 1), jnp.float32),
            pltpu.VMEM((g_p, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, g_p, dh_p), jnp.float32),
        interpret=interpret,
    )(tbl, length.astype(jnp.int32), qg, k, v)
    return out[:, :, :g, :dh]


def flash_decode_paged(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       table: jnp.ndarray, length: jnp.ndarray, *,
                       window: int | None = None,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Flash-decode against a paged (block-table) KV pool.

    q: (B, 1, Hq, dh); k/v: (N_blocks, blk, Hk, dh); table: (B, nb)
    block ids; length: (B,) per-slot lengths. Bitwise-equivalent to
    `flash_decode` with ``s_blk = blk`` on the dense gathered view
    (identical per-block accumulation order)."""
    if interpret is None:
        interpret = _interpret_default()
    b, t, hq, dh = q.shape
    hk = k.shape[2]
    qg = q.reshape(b, hk, hq // hk, dh)
    out = _flash_decode_paged_call(qg, k, v, table, length,
                                   window=window, interpret=interpret)
    return out.reshape(b, t, hq, dh).astype(q.dtype)


def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 length: jnp.ndarray, *, window: int | None = None,
                 s_blk: int = S_BLOCK,
                 interpret: bool | None = None) -> jnp.ndarray:
    """Drop-in for the jnp decode_attention body.

    q: (B, 1, Hq, dh); k/v: (B, S_max, Hk, dh); length: (B,) per-slot
    lengths. Returns (B, 1, Hq, dh) in q's dtype."""
    if interpret is None:
        interpret = _interpret_default()
    b, t, hq, dh = q.shape
    hk = k.shape[2]
    qg = q.reshape(b, hk, hq // hk, dh)   # head h = k_head * G + g
    s_blk = min(s_blk, _pad_to(k.shape[1], SUBLANE * 2))
    out = _flash_decode_call(qg, k, v, length, window=window,
                             s_blk=s_blk, interpret=interpret)
    return out.reshape(b, t, hq, dh).astype(q.dtype)
