"""Attention: GQA/MHA, sliding-window (SWA), cross-attention, and
KV-cache decode.

Implementation notes (these are sharding-load-bearing):

* **Grouped-query einsums, never expanded KV.** K/V stay (B, S, Hk, dh)
  and Q is viewed as (B, T, Hk, G, dh); a `jnp.repeat` of KV to Hq heads
  lowers to broadcast_in_dim, which breaks XLA SPMD's partial-reduction
  path and forces a full cache all-gather per layer on seq-sharded
  decode caches (observed: 25 GB/layer/token). With the grouped form the
  score/value contractions reduce over the sharded seq dim locally and
  XLA inserts only tiny (B,Hk,G,T) all-reduces — cross-device
  flash-decoding for free.

* **Chunked prefill.** lax.scan over query blocks so the (S, S) score
  matrix never materializes (32k prefill would need terabytes). SWA
  additionally slices K/V to [block start - window, block end) making
  training truly sub-quadratic, which is what qualifies h2o-danube for
  long_500k.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jnp.ndarray        # (B, S_max, Hk, dh)
    v: jnp.ndarray        # (B, S_max, Hk, dh)
    length: jnp.ndarray   # (B,) int32 — tokens written PER SLOT (absolute)

    @classmethod
    def init(cls, batch: int, max_len: int, n_kv: int, head_dim: int,
             dtype=jnp.bfloat16) -> "KVCache":
        z = jnp.zeros((batch, max_len, n_kv, head_dim), dtype)
        return cls(z, jnp.copy(z), jnp.zeros((batch,), jnp.int32))


class PagedKVCache(NamedTuple):
    """Block-table KV cache: a global physical block pool shared by all
    slots, indexed per slot through a block table.

    Virtual cell ``c`` of slot ``b`` lives at physical cell
    ``(table[b, c // blk], c % blk)``. Physical block 0 is a reserved
    TRASH block — never mapped by any table — so writes past a slot's
    allocation (done slots padding out a decode chunk, pad tails of a
    bucketed prefill) land harmlessly instead of corrupting a neighbor;
    ``-1`` table entries mean "unmapped" and clamp to the trash block.
    Block allocation/refcounting is host-side (serving.paging.BlockPool);
    the device only ever sees the materialized table."""
    k: jnp.ndarray        # (N_blocks, blk, Hk, dh) physical pool
    v: jnp.ndarray        # (N_blocks, blk, Hk, dh)
    table: jnp.ndarray    # (B, nb) int32 block ids, -1 = unmapped
    length: jnp.ndarray   # (B,) int32 — tokens written PER SLOT (absolute)

    @classmethod
    def init(cls, n_blocks: int, block: int, n_kv: int, head_dim: int,
             batch: int, max_blocks: int,
             dtype=jnp.bfloat16) -> "PagedKVCache":
        z = jnp.zeros((n_blocks, block, n_kv, head_dim), dtype)
        return cls(z, jnp.copy(z),
                   jnp.full((batch, max_blocks), -1, jnp.int32),
                   jnp.zeros((batch,), jnp.int32))

    @property
    def block_size(self) -> int:
        return self.k.shape[-3]

    @property
    def s_max(self) -> int:
        """Virtual per-slot capacity in cells."""
        return self.table.shape[-1] * self.k.shape[-3]


def paged_view(cache: PagedKVCache) -> KVCache:
    """Gather the pool through the table into a dense per-slot view.

    Cell-for-cell identical to the dense cache a `KVCache` of the same
    virtual capacity would hold (unmapped blocks read the trash block;
    those cells are masked by ``length`` everywhere downstream), so any
    dense consumer is bitwise-correct on the view."""
    tbl = jnp.maximum(cache.table, 0)                 # (B, nb)
    b = tbl.shape[0]
    k = cache.k[tbl].reshape(b, -1, *cache.k.shape[2:])
    v = cache.v[tbl].reshape(b, -1, *cache.v.shape[2:])
    return KVCache(k, v, cache.length)


def paged_cache_update(cache: PagedKVCache, k_new: jnp.ndarray,
                       v_new: jnp.ndarray, *,
                       rolling: bool = False) -> PagedKVCache:
    """Append S_new tokens through the block table.

    Same per-slot write-cursor semantics as the dense `cache_update`
    (start at ``length``, SWA wraps mod the virtual ring size); the
    scatter routes each (slot, cell) to (table[slot, cell // blk],
    cell % blk). Cells past the virtual capacity or landing on an
    unmapped (-1) entry are redirected to the trash block — duplicate
    trash indices are the only scatter collisions, and their values are
    never read."""
    s_new = k_new.shape[1]
    phys, off = _paged_cells(cache.table, cache.length, s_new,
                             cache.block_size, rolling)
    k = cache.k.at[phys, off].set(k_new.astype(cache.k.dtype))
    v = cache.v.at[phys, off].set(v_new.astype(cache.v.dtype))
    return PagedKVCache(k, v, cache.table, cache.length + s_new)


def _paged_cells(table: jnp.ndarray, length: jnp.ndarray, s_new: int,
                 blk: int, rolling: bool):
    """Physical (block, offset) of the ``s_new`` cells each slot writes
    next: (B, S_new) each, past-capacity and unmapped cells on the
    trash block."""
    nb = table.shape[1]
    s_max = nb * blk
    start = length % s_max if rolling else length                # (B,)
    cells = start[:, None] + jnp.arange(s_new)[None, :]          # (B, S)
    if rolling:
        cells = cells % s_max
    live = cells < s_max
    bi = jnp.clip(cells // blk, 0, nb - 1)
    phys = jnp.take_along_axis(table, bi, axis=1)                # (B, S)
    phys = jnp.where(live & (phys >= 0), phys, 0)
    off = jnp.where(live, cells % blk, 0)
    return phys, off


def _grouped(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """(B, T, Hq, dh) -> (B, T, Hk, G, dh)."""
    b, t, hq, dh = q.shape
    return q.reshape(b, t, n_kv, hq // n_kv, dh)


def _sdpa_block(qg, k, v, mask):
    """One (q-block x kv-range) grouped attention, fp32 softmax.

    qg: (B, T, Hk, G, dh); k, v: (B, S, Hk, dh); mask: (T, S) bool.
    Returns (B, T, Hk, G, dh)."""
    scale = qg.shape[-1] ** -0.5
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(jnp.float32)
    s = s * scale
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(mask[None, None, None], p, 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(denom, 1e-30)
    out = jnp.einsum("bkgts,bskd->btkgd", p.astype(v.dtype), v)
    return out


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = True, window: int | None = None,
              q_offset: int = 0, block_q: int = 512) -> jnp.ndarray:
    """Chunked multi-head GQA attention.

    q: (B, Sq, Hq, dh); k/v: (B, Sk, Hk, dh) with Hq % Hk == 0.
    ``q_offset``: absolute position of q[0] relative to k[0].
    ``window``: SWA width (None = full causal)."""
    b, sq, hq, dh = q.shape
    sk = k.shape[1]
    hk = k.shape[2]
    qg = _grouped(q, hk)

    def finish(out):
        return out.reshape(b, -1, hq, dh).astype(q.dtype)

    if sq <= block_q:
        qpos = q_offset + jnp.arange(sq)[:, None]
        kpos = jnp.arange(sk)[None, :]
        mask = jnp.ones((sq, sk), bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        return finish(_sdpa_block(qg, k, v, mask))

    nblk = -(-sq // block_q)
    pad = nblk * block_q - sq
    qp = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    qblocks = qp.reshape(b, nblk, block_q, hk, hq // hk, dh)
    qblocks = jnp.moveaxis(qblocks, 1, 0)

    # flash-semantics: checkpoint each q-block so the (block_q, S) score
    # tile is RECOMPUTED in backward instead of being stacked across the
    # scan (a 40L x 32k model would otherwise save terabytes of probs —
    # this is what fused flash kernels do on real hardware)
    if window is not None:
        # sub-quadratic: each q block sees [start - lookback, end)
        lookback = (-(-window // block_q)) * block_q
        span = lookback + block_q
        kpad = jnp.pad(k, ((0, 0), (lookback, pad), (0, 0), (0, 0)))
        vpad = jnp.pad(v, ((0, 0), (lookback, pad), (0, 0), (0, 0)))

        @jax.checkpoint
        def body(_, i):
            qb = qblocks[i]
            start = i * block_q
            kb = jax.lax.dynamic_slice_in_dim(kpad, start, span, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(vpad, start, span, axis=1)
            qpos = q_offset + start + jnp.arange(block_q)[:, None]
            kpos = start - lookback + jnp.arange(span)[None, :] \
                + q_offset
            mask = (kpos >= q_offset) & (kpos <= qpos) & \
                (kpos > qpos - window)
            return None, _sdpa_block(qb, kb, vb, mask)

        _, outs = jax.lax.scan(body, None, jnp.arange(nblk))
    else:
        @jax.checkpoint
        def body(_, i):
            qb = qblocks[i]
            qpos = q_offset + i * block_q + jnp.arange(block_q)[:, None]
            kpos = jnp.arange(sk)[None, :]
            mask = kpos <= qpos if causal else \
                jnp.ones((block_q, sk), bool)
            return None, _sdpa_block(qb, k, v, mask)

        _, outs = jax.lax.scan(body, None, jnp.arange(nblk))

    out = jnp.moveaxis(outs, 0, 1)        # (B, nblk, block_q, ...)
    out = out.reshape(b, nblk * block_q, hk, hq // hk, dh)[:, :sq]
    return out.reshape(b, sq, hq, dh).astype(q.dtype)


def decode_valid_mask(length: jnp.ndarray, s_max: int,
                      window: int | None) -> jnp.ndarray:
    """(B,) per-slot lengths -> (B, S_max) bool mask of live cache cells.

    Full-causal: cell s is live while s < length. SWA: the cache is a
    rolling ring of size s_max; recover each cell's absolute position
    from the write cursor and keep the last ``window`` positions."""
    length = length[:, None].astype(jnp.int32)        # (B, 1)
    slot = jnp.arange(s_max)[None, :]                 # (1, S)
    if window is None:
        return slot < length
    wrap = length > s_max
    rem = length % s_max
    abs_pos = jnp.where(
        wrap,
        jnp.where(slot < rem, length - rem + slot,
                  length - rem - s_max + slot),
        slot)
    return (abs_pos < length) & (abs_pos >= length - window)


def decode_attention(q: jnp.ndarray, cache: KVCache, *,
                     window: int | None = None,
                     impl: str = "jnp") -> jnp.ndarray:
    """Single-token grouped attention against the per-slot cache.

    q: (B, 1, Hq, dh); ``cache.length`` is (B,) so every slot masks its
    own live prefix — slots at different sequence lengths decode
    together (continuous batching). With a seq-sharded cache the
    contractions reduce locally per shard and XLA merges partials
    (flash-decoding). For SWA the cache is a rolling buffer of size >=
    window. ``impl="pallas"`` selects the fused flash-decode TPU kernel
    (interpret mode off-TPU). Paged caches attend through the block
    table: the Pallas path gathers blocks inside the kernel via
    scalar-prefetched table lookups, the jnp path through a dense
    gathered view (bitwise identical to the dense cache by
    construction)."""
    if isinstance(cache, PagedKVCache):
        if impl == "pallas":
            from repro.kernels import flash_decode
            return flash_decode.flash_decode_paged(
                q, cache.k, cache.v, cache.table, cache.length,
                window=window)
        cache = paged_view(cache)
    if impl == "pallas":
        from repro.kernels import flash_decode
        return flash_decode.flash_decode(q, cache.k, cache.v,
                                         cache.length, window=window)
    b, t, hq, dh = q.shape
    s_max = cache.k.shape[1]
    hk = cache.k.shape[2]
    qg = _grouped(q, hk)
    scale = dh ** -0.5
    s = jnp.einsum("btkgd,bskd->bkgts", qg, cache.k).astype(
        jnp.float32) * scale

    valid = decode_valid_mask(cache.length, s_max, window)   # (B, S)
    s = jnp.where(valid[:, None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(valid[:, None, None, None], p, 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(denom, 1e-30)
    out = jnp.einsum("bkgts,bskd->btkgd", p.astype(cache.v.dtype),
                     cache.v)
    return out.reshape(b, t, hq, dh).astype(q.dtype)


def cache_update(cache: KVCache, k_new: jnp.ndarray,
                 v_new: jnp.ndarray, *, rolling: bool = False) -> KVCache:
    """Append S_new tokens (prefill write or single decode step).

    Per-slot write offsets: each slot writes at its own ``length`` (a
    vmapped dynamic_update_slice, lowered to a batched scatter), so a
    freshly prefilled slot can sit next to slots deep into decode.
    Rolling mode wraps into a window-sized ring buffer; for prefill
    writes larger than the buffer, slice to the last s_max tokens and
    bump ``length`` before calling (see transformer.prefill). Paged
    caches dispatch to the block-table scatter."""
    if isinstance(cache, PagedKVCache):
        return paged_cache_update(cache, k_new, v_new, rolling=rolling)
    s_max = cache.k.shape[1]
    s_new = k_new.shape[1]
    start = cache.length % s_max if rolling else cache.length   # (B,)

    def write(buf, new, st):
        return jax.lax.dynamic_update_slice_in_dim(
            buf, new.astype(buf.dtype), st, axis=0)

    k = jax.vmap(write)(cache.k, k_new, start)
    v = jax.vmap(write)(cache.v, v_new, start)
    return KVCache(k, v, cache.length + s_new)


def cache_append_layer(cache, layer, k_new: jnp.ndarray,
                       v_new: jnp.ndarray, *, rolling: bool = False):
    """Write one decode step's K/V rows, (B, 1, Hk, dh), into layer
    ``layer`` of a layer-stacked cache (leaves with a leading L axis).

    Only the B new rows are written, by one scatter into the stacked
    buffers, so a cache carried through the layer scan is updated in
    place. Each slot writes where ``cache_update`` would on the layer's
    own cache: at ``length`` (mod the ring size for SWA), a full slot
    clamped to the last cell as ``dynamic_update_slice`` clamps; paged
    caches through the block table (``paged_cache_update``). The stacked
    lengths are left for the caller to advance once per step.

    Returns ``(cache, layer_cache)``: the stacked cache with the rows
    written, and layer ``layer`` as ``cache_update`` returns it (its
    slabs sliced from the stack, lengths advanced by one)."""
    length = cache.length[layer]                                 # (B,)
    if isinstance(cache, PagedKVCache):
        table = cache.table[layer]
        phys, off = _paged_cells(table, length, 1, cache.block_size,
                                 rolling)
        idx = (layer, phys[:, 0], off[:, 0])
    else:
        s_max = cache.k.shape[2]
        start = length % s_max if rolling else length
        idx = (layer, jnp.arange(length.shape[0]),
               jnp.minimum(start, s_max - 1))
    k = cache.k.at[idx].set(k_new[:, 0].astype(cache.k.dtype))
    v = cache.v.at[idx].set(v_new[:, 0].astype(cache.v.dtype))
    cache = cache._replace(k=k, v=v)
    layer_cache = jax.tree.map(lambda x: x[layer], cache)
    return cache, layer_cache._replace(length=length + 1)
