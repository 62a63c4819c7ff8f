"""Paged KV serving tier: global block pool, per-slot block tables,
copy-on-write shared prefixes.

The dense ``ContinuousEngine`` allocates one ``max_len``-wide KV cache
per slot, so device memory — not compute — caps concurrency, and every
co-batched request re-prefills its shared system prompt. This tier
replaces the per-slot cache with ONE physical block pool per layer
(``attention.PagedKVCache``) indexed through per-slot block tables:

* **BlockPool** (host): free-list alloc/release with refcounts over the
  physical block ids. Block 0 is the reserved trash block (writes from
  done/overflowing slots land there); every other block is owned by the
  requests whose tables map it. A request's worst-case block need is
  allocated AT ADMISSION, so pool pressure is a typed
  ``BlockPoolExhaustedError`` on admission — never a silent corruption
  or a mid-decode hang.

* **Content-addressed prefix sharing**: full prompt blocks are chain-
  hashed (sha256 over (parent digest, block tokens) — the chunk-store
  idiom from ``checkpointing/store.py``), so requests with a common
  system prompt (and GRPO groups with a common question) map the SAME
  physical blocks, refcounted. A full-prompt hit additionally reuses
  the registered last-token logits and admits with ZERO prefill
  FLOPs. The index holds no refs of its own: a block's index entries
  die with the block when its last user retires (refcount reaches zero
  exactly at retire).

* **Copy-on-write**: a partially-filled tail block adopted from the
  index is written at its first decode step, so admission reserves a
  fork target and ``_before_chunk`` copies the block just-in-time —
  only if it is still shared (a sole survivor adopts in place). Full
  blocks are never written after prefill, and appends past a sharer's
  prefix length are masked for every reader, so one appender + N
  readers per physical block is safe without a fork.

* **Chunked/paged prefill**: prompts longer than one dense bucket (or
  ``max_len`` itself, with ``capacity_blocks``) admit via
  ``model.prefill_extend`` segments that write straight into pool
  blocks.

The dense engine stays as the bit-identity foil: with the default pool
sizing, paged greedy output is asserted bitwise equal to
``ContinuousEngine`` across the model zoo (tests/test_paging.py).
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict, deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as attn
from repro.serving.engine import (ContinuousEngine, Request,
                                  tree_insert_slot)

_HASH_ROOT = b"repro-paged-prefix-v1"


class BlockPoolExhaustedError(RuntimeError):
    """No free KV blocks for an allocation. Raised at ADMISSION (the
    failed request is re-queued at the front) — decode never allocates,
    so an admitted request can always run to its budget."""


class BlockPool:
    """Host-side free-list allocator with refcounts over the physical
    KV block pool. Block 0 (trash) is never handed out.

    ``on_pressure(pool, short)`` is the eviction hook: called before an
    allocation fails, it may release blocks (e.g. by evicting cold
    prefix blocks, or preempting a low-priority stream); allocation is
    re-checked after. ``on_free(bid, tags)`` fires when a block leaves
    the pool's ownership — the engine uses it to drop the block's
    prefix-index entries.

    With ``retain_tagged=True`` a tagged (prefix-indexed) block whose
    refcount reaches zero is PARKED on the ``cold`` LRU list instead of
    freed: its KV and index entries survive, so a later request with
    the same prefix revives it via :meth:`incref` at zero cost.
    ``evict``/``evict_cold`` turn cold blocks back into free ones (and
    only then fire ``on_free``)."""

    def __init__(self, n_blocks: int, *, on_pressure=None, on_free=None,
                 retain_tagged: bool = False):
        if n_blocks < 2:
            raise ValueError("pool needs >= 2 blocks (one is trash)")
        self.n_blocks = n_blocks
        self.free: deque[int] = deque(range(1, n_blocks))
        self.ref = np.zeros((n_blocks,), np.int32)
        self.tags: dict[int, list] = {}
        self.on_pressure = on_pressure
        self.on_free = on_free
        self.retain_tagged = retain_tagged
        # zero-ref tagged blocks, oldest-parked first (LRU eviction
        # order); values are unused
        self.cold: OrderedDict[int, None] = OrderedDict()
        self.stats = {"allocs": 0, "frees": 0, "peak_used": 0,
                      "exhausted": 0, "parked": 0, "revived": 0,
                      "evicted": 0}

    @property
    def used(self) -> int:
        return self.n_blocks - 1 - len(self.free)

    @property
    def cold_count(self) -> int:
        return len(self.cold)

    def alloc(self, n: int) -> list[int]:
        if n <= 0:
            return []
        if len(self.free) < n and self.on_pressure is not None:
            self.on_pressure(self, n - len(self.free))
        if len(self.free) < n:
            self.stats["exhausted"] += 1
            raise BlockPoolExhaustedError(
                f"need {n} KV blocks, {len(self.free)} free "
                f"(pool size {self.n_blocks - 1})")
        ids = [self.free.popleft() for _ in range(n)]
        for b in ids:
            self.ref[b] = 1
        self.stats["allocs"] += n
        self.stats["peak_used"] = max(self.stats["peak_used"], self.used)
        return ids

    def incref(self, bid: int) -> None:
        if not self.ref[bid] and bid in self.cold:
            # prefix match on a parked block: revive it from the cold
            # list — the whole point of retaining
            del self.cold[bid]
            self.ref[bid] = 1
            self.stats["revived"] += 1
            return
        assert self.ref[bid] > 0, f"incref on free block {bid}"
        self.ref[bid] += 1

    def decref(self, bid: int) -> bool:
        """Drop one ref. At zero the block frees (firing ``on_free``
        with its tags) — unless ``retain_tagged`` and it carries tags,
        in which case it parks on the cold LRU list with index entries
        intact. Returns True if freed."""
        assert self.ref[bid] > 0, f"decref on free block {bid}"
        self.ref[bid] -= 1
        if self.ref[bid]:
            return False
        if self.retain_tagged and self.tags.get(bid):
            self.cold[bid] = None        # most-recently-parked at end
            self.stats["parked"] += 1
            return False
        self._free_block(bid)
        return True

    def _free_block(self, bid: int) -> None:
        tags = self.tags.pop(bid, [])
        if self.on_free is not None:
            self.on_free(bid, tags)
        self.free.append(bid)
        self.stats["frees"] += 1

    def evict(self, bid: int) -> None:
        """Free one cold block: drops its index entries (``on_free``)
        and returns it to the free list."""
        assert bid in self.cold, f"evict on non-cold block {bid}"
        del self.cold[bid]
        self._free_block(bid)
        self.stats["evicted"] += 1

    def evict_cold(self, n: int) -> int:
        """Evict up to ``n`` cold blocks, oldest-parked first (LRU).
        Returns the number evicted — the standard ``on_pressure``
        policy when prefix retention is on."""
        evicted = 0
        while evicted < n and self.cold:
            bid, _ = self.cold.popitem(last=False)
            self._free_block(bid)
            self.stats["evicted"] += 1
            evicted += 1
        return evicted

    def tag(self, bid: int, item) -> None:
        self.tags.setdefault(bid, []).append(item)


class PrefixIndex:
    """Content-addressed registry of shared prefix blocks.

    ``blocks``: chain digest of prompt blocks [0, i] -> physical block
    id holding block i's KV. ``tails``: digest of (last full-block
    chain digest, tail tokens) -> (tail block id or None, cached
    last-token logits row) — the full-prompt entry that makes an exact
    repeat admit with zero prefill. Entries hold NO refs; they are
    dropped when their block is freed."""

    def __init__(self):
        self.blocks: dict[bytes, int] = {}
        self.tails: dict[bytes, tuple[int | None, object]] = {}

    def clear(self) -> None:
        self.blocks.clear()
        self.tails.clear()


def chain_digests(prompt: np.ndarray, blk: int) -> tuple[list[bytes],
                                                         bytes]:
    """sha256 chain over the prompt's full blocks, plus the tail
    digest. ``digests[i]`` commits to tokens [0, (i+1)*blk) — matching
    it guarantees the indexed block holds exactly the KV a fresh
    prefill of this prompt would write there (full-causal attention:
    block content depends only on its prefix)."""
    p = np.ascontiguousarray(np.asarray(prompt, np.int32))
    h = _HASH_ROOT
    digests = []
    f = len(p) // blk
    for i in range(f):
        h = hashlib.sha256(h + p[i * blk:(i + 1) * blk].tobytes()).digest()
        digests.append(h)
    tail = hashlib.sha256(h + b"|tail|" + p[f * blk:].tobytes()).digest()
    return digests, tail


def build_paged_cache(model, slots: int, shape, *, block_size: int,
                      n_blocks: int | None = None,
                      capacity_blocks: int | None = None,
                      rolling: bool = False):
    """Materialize the model's cache pytree with every ``KVCache`` leaf
    replaced by a ``PagedKVCache`` over a shared physical pool
    (``jax.eval_shape`` template — the dense cache is never allocated).
    Non-KV leaves (SSM states, conv rings) stay dense per-slot: they
    are O(1) in sequence length, paging buys nothing.

    Returns (cache, table_width, n_blocks). ``n_blocks=None`` sizes the
    pool to exactly the dense engine's capacity (slots * table_width
    blocks + trash) — the bit-identity-foil configuration."""
    template = jax.eval_shape(lambda: model.init_cache(slots, shape))
    return paged_cache_from_template(
        template, slots=slots, block_size=block_size,
        n_blocks=n_blocks, capacity_blocks=capacity_blocks,
        rolling=rolling)


def paged_cache_from_template(template, *, slots: int, block_size: int,
                              n_blocks: int | None = None,
                              capacity_blocks: int | None = None,
                              rolling: bool = False):
    """Core of :func:`build_paged_cache` over an abstract cache
    template (also used by the swarm stage servers, whose cache trees
    come from ``StageDef.init_cache`` rather than a ``ModelDef``)."""
    widths: list[int] = []

    def width(leaf):
        s_max = leaf.k.shape[-3]
        if s_max % block_size:
            raise ValueError(
                f"block_size {block_size} must divide the cache width "
                f"{s_max} (max_len / SWA ring)")
        nb = s_max // block_size
        if capacity_blocks is not None and not rolling:
            nb = max(nb, capacity_blocks)
        return nb

    is_kv = lambda x: isinstance(x, attn.KVCache)
    for leaf in jax.tree.leaves(template, is_leaf=is_kv):
        if isinstance(leaf, attn.KVCache):
            widths.append(width(leaf))
    if len(set(widths)) > 1:
        raise ValueError(f"non-uniform paged table widths {set(widths)}")
    nb = widths[0] if widths else 0
    if n_blocks is None:
        n_blocks = slots * nb + 1 if nb else 2

    def conv(leaf):
        if not isinstance(leaf, attn.KVCache):
            return jnp.zeros(leaf.shape, leaf.dtype)
        ks = leaf.k.shape
        hk, dh = ks[-2], ks[-1]
        if len(ks) == 5:            # stacked (L, B, S, Hk, dh)
            z = jnp.zeros((ks[0], n_blocks, block_size, hk, dh),
                          leaf.k.dtype)
            tbl = jnp.full((ks[0], slots, nb), -1, jnp.int32)
            ln = jnp.zeros((ks[0], slots), jnp.int32)
        else:                       # (B, S, Hk, dh)
            z = jnp.zeros((n_blocks, block_size, hk, dh), leaf.k.dtype)
            tbl = jnp.full((slots, nb), -1, jnp.int32)
            ln = jnp.zeros((slots,), jnp.int32)
        return attn.PagedKVCache(z, jnp.copy(z), tbl, ln)

    cache = jax.tree.map(conv, template, is_leaf=is_kv)
    return cache, nb, n_blocks


class PagedEngine(ContinuousEngine):
    """``ContinuousEngine`` with the per-slot dense cache swapped for
    the paged block pool. The decode loop is UNCHANGED (the paged
    ``cache_update`` / ``decode_attention`` dispatch inside the same
    jitted chunk); admission allocates blocks, matches content-
    addressed prefixes, and splices either a paginated scratch prefill,
    an extend-resumed suffix, or (full hit) nothing at all."""
    kind = "paged"

    def __init__(self, model, params, *, block_size: int = 16,
                 pool_blocks: int | None = None,
                 capacity_blocks: int | None = None,
                 share_prefix: bool = True,
                 cache_prefixes: bool = False,
                 prefill_chunk: int | None = None, **kw):
        kw.pop("overlap_admission", None)   # admission is host-stateful
        kw.pop("batch_admit", None)         # per-request (block alloc)
        super().__init__(model, params, batch_admit=False, **kw)
        family = getattr(self.cfg, "family", "")
        if family == "encdec":
            raise ValueError("paged serving unsupported for family "
                             "'encdec' (cross caches page per source, "
                             "not per token)")
        self.rolling = getattr(self.cfg, "sliding_window",
                               None) is not None
        self.blk = int(block_size)
        self.cache, self.nb, n_blocks = build_paged_cache(
            model, self.slots, self.shape, block_size=self.blk,
            n_blocks=pool_blocks, capacity_blocks=capacity_blocks,
            rolling=self.rolling)
        self.capacity = self.nb * self.blk if self.nb else self.max_len
        # cache_prefixes: keep zero-ref prefix-tagged blocks parked on
        # the pool's cold LRU list so repeat prompts hit even after
        # every sharer retired; admission under pressure evicts the
        # coldest instead of raising. Off by default: the dense foil
        # invariant is refcount-zero-frees-exactly-at-retire.
        self.cache_prefixes = bool(cache_prefixes)
        self.pool = BlockPool(
            n_blocks, on_free=self._on_block_free,
            retain_tagged=self.cache_prefixes,
            on_pressure=(self._on_pool_pressure if self.cache_prefixes
                         else None))
        self._extend = None
        if model.prefill_extend is not None and not self.rolling:
            self._extend = jax.jit(model.prefill_extend)
        self.prefix = PrefixIndex() if (
            share_prefix and not self.rolling and self.nb
            and family in ("dense", "moe", "vlm")) else None
        self.prefill_chunk = int(prefill_chunk or self.max_len)
        self._tables = np.full((self.slots, max(self.nb, 1)), -1,
                               np.int32)
        self._tbl_dirty = True
        self._slot_blocks: list[list[int]] = [[] for _ in
                                              range(self.slots)]
        # (table index to check, reserved fork target) per slot
        self._cow_pending: list[tuple[int, int] | None] = \
            [None] * self.slots
        self._paginate_jit = jax.jit(self._paginate_fn)
        self._paged_admit_jit = jax.jit(self._paged_admit_fn)
        self._admit_hit_jit = jax.jit(self._admit_hit_fn)
        self._set_len_jit = jax.jit(self._set_len_fn)
        self._settbl_jit = jax.jit(self._settbl_fn)
        self._fork_jit = jax.jit(self._fork_fn)
        self._extend_slot_jit = jax.jit(self._extend_slot_fn)
        self.stats.update(prefix_lookups=0, prefix_hits=0,
                          prefix_hit_tokens=0, prompt_tokens=0,
                          cow_forks=0, paged_extends=0,
                          admit_deferred=0)

    # -- device-side pieces ---------------------------------------------------

    @staticmethod
    def _is_paged(x) -> bool:
        return isinstance(x, attn.PagedKVCache)

    def _settbl_fn(self, cache, tbl):
        def leaf(c):
            if isinstance(c, attn.PagedKVCache):
                t = tbl.astype(jnp.int32)
                if c.table.ndim == 3:
                    t = jnp.broadcast_to(t[None], c.table.shape)
                return c._replace(table=t)
            return c
        return jax.tree.map(leaf, cache, is_leaf=self._is_paged)

    def _set_len_fn(self, cache, slot, plen):
        def leaf(c):
            if isinstance(c, attn.PagedKVCache):
                val = jnp.reshape(plen, (1,)).astype(jnp.int32)
                if c.length.ndim == 2:
                    v2 = jnp.broadcast_to(val[None],
                                          (c.length.shape[0], 1))
                    return c._replace(length=jax.lax.dynamic_update_slice(
                        c.length, v2, (0, slot)))
                return c._replace(length=jax.lax.dynamic_update_slice(
                    c.length, val, (slot,)))
            return c
        return jax.tree.map(leaf, cache, is_leaf=self._is_paged)

    def _paginate_leaf(self, bg, sb, row, slot):
        """Copy one dense scratch leaf (B=1, width S) into the pool
        blocks table row ``row`` maps; splice the slot's table/length
        rows. Cells whose row entry is -1 (scratch wider than the
        allocation) go to the trash block."""
        blk = self.blk
        nb = bg.table.shape[-1]
        s = sb.k.shape[-3]
        w = min(s, nb * blk)
        cells = jnp.arange(w)
        phys = row[cells // blk]
        phys = jnp.where(phys >= 0, phys, 0)
        off = cells % blk
        rown = row[None, :]
        if bg.k.ndim == 5:
            k = bg.k.at[:, phys, off].set(
                sb.k[:, 0, :w].astype(bg.k.dtype))
            v = bg.v.at[:, phys, off].set(
                sb.v[:, 0, :w].astype(bg.v.dtype))
            tbl = jax.lax.dynamic_update_slice(
                bg.table,
                jnp.broadcast_to(rown[None],
                                 (bg.table.shape[0], 1, nb)),
                (0, slot, 0))
            ln = jax.lax.dynamic_update_slice(
                bg.length, sb.length[:, :1].astype(jnp.int32), (0, slot))
        else:
            k = bg.k.at[phys, off].set(sb.k[0, :w].astype(bg.k.dtype))
            v = bg.v.at[phys, off].set(sb.v[0, :w].astype(bg.v.dtype))
            tbl = jax.lax.dynamic_update_slice(bg.table, rown, (slot, 0))
            ln = jax.lax.dynamic_update_slice(
                bg.length, sb.length.astype(jnp.int32), (slot,))
        return attn.PagedKVCache(k, v, tbl, ln)

    def _paginate_fn(self, cache, sub, row, slot):
        """Splice a dense B=1 scratch prefill into the paged slot:
        paged leaves scatter through the table, dense leaves (SSM
        state, conv rings) take the ordinary batch-axis insert."""
        is_cache = lambda x: isinstance(x, (attn.KVCache,
                                            attn.PagedKVCache))
        bl, bdef = jax.tree_util.tree_flatten(cache, is_leaf=is_cache)
        sl, _ = jax.tree_util.tree_flatten(sub, is_leaf=is_cache)
        out = []
        for bg, sb in zip(bl, sl):
            if isinstance(bg, attn.PagedKVCache):
                out.append(self._paginate_leaf(bg, sb, row, slot))
            else:
                out.append(tree_insert_slot(bg, sb, slot, self.slots))
        return jax.tree_util.tree_unflatten(bdef, out)

    def _paged_admit_fn(self, cache, tokens, done, remaining, temps,
                        slot_keys, sub_cache, logits, slot, budget,
                        temp, rid, row):
        cache = self._paginate_fn(cache, sub_cache, row, slot)
        return self._admit_state(cache, tokens, done, remaining, temps,
                                 slot_keys, logits, slot, budget, temp,
                                 rid)

    def _admit_hit_fn(self, cache, tokens, done, remaining, temps,
                      slot_keys, logits, slot, budget, temp, rid, plen):
        """Admission with no cache write at all (full prefix hit, or an
        extend path that already wrote through the table) — set the
        slot's lengths and splice the scheduler state."""
        cache = self._set_len_fn(cache, slot, plen)
        return self._admit_state(cache, tokens, done, remaining, temps,
                                 slot_keys, logits, slot, budget, temp,
                                 rid)

    def _fork_fn(self, cache, src, dst):
        def leaf(c):
            if isinstance(c, attn.PagedKVCache):
                axis = 1 if c.k.ndim == 5 else 0
                ks = jax.lax.dynamic_slice_in_dim(c.k, src, 1, axis=axis)
                vs = jax.lax.dynamic_slice_in_dim(c.v, src, 1, axis=axis)
                return c._replace(
                    k=jax.lax.dynamic_update_slice_in_dim(
                        c.k, ks, dst, axis=axis),
                    v=jax.lax.dynamic_update_slice_in_dim(
                        c.v, vs, dst, axis=axis))
            return c
        return jax.tree.map(leaf, cache, is_leaf=self._is_paged)

    def _extend_slot_fn(self, params, cache, tokens, slot, start,
                        seg_len):
        """One chunked-prefill segment for ``slot``: extract its B=1
        paged view (tables/lengths sliced, pool arrays shared), run
        ``prefill_extend`` (which writes the segment's KV through the
        table), merge the new pool arrays + the slot's length back."""
        def take(c):
            if isinstance(c, attn.PagedKVCache):
                ax = 1 if c.table.ndim == 3 else 0
                return c._replace(
                    table=jax.lax.dynamic_slice_in_dim(
                        c.table, slot, 1, axis=ax),
                    length=jax.lax.dynamic_slice_in_dim(
                        c.length, slot, 1, axis=ax))
            return c
        sub = jax.tree.map(take, cache, is_leaf=self._is_paged)
        logits, new_sub = self.model.prefill_extend(
            params, {"tokens": tokens, "start": start,
                     "seg_len": seg_len}, sub)

        def put(c, nc):
            if isinstance(c, attn.PagedKVCache):
                if c.length.ndim == 2:
                    ln = jax.lax.dynamic_update_slice(
                        c.length, nc.length.astype(jnp.int32), (0, slot))
                else:
                    ln = jax.lax.dynamic_update_slice(
                        c.length, nc.length.astype(jnp.int32), (slot,))
                return attn.PagedKVCache(nc.k, nc.v, c.table, ln)
            return c
        merged = jax.tree.map(put, cache, new_sub,
                              is_leaf=self._is_paged)
        return logits, merged

    # -- host-side admission --------------------------------------------------

    def _budget(self, req: Request) -> int:
        if self.rolling or not self.nb:
            return super()._budget(req)
        return max(1, min(req.max_new_tokens,
                          self.capacity - len(req.prompt)))

    def _row_dev(self, row: list[int]) -> jnp.ndarray:
        r = np.full((self.nb,), -1, np.int32)
        r[:len(row)] = row
        return jnp.asarray(r)

    def _admit(self) -> None:
        """Fill free slots one request at a time (block allocation is
        per-request). On pool exhaustion the request is back at the
        queue head: if anything is still decoding, its retire will free
        blocks — defer and retry at the next chunk boundary, keeping
        FIFO order. Only a request that cannot fit an EMPTY pool
        escalates the typed error to the caller."""
        free = [s for s in range(self.slots) if self.active[s] is None]
        while free and self.queue:
            req = self.queue.popleft()
            try:
                self._admit_one(req, free.pop(0))
            except BlockPoolExhaustedError:
                if not any(r is not None for r in self.active):
                    raise
                self.stats["admit_deferred"] += 1
                return

    def _match_prefix(self, prompt: np.ndarray):
        """Greedy longest content-addressed match: full blocks along
        the chain hash, then the full-prompt tail entry. Returns
        (shared block ids (ref'd), matched prefix length H, cached
        last-token logits or None, tail block adopted?, digests,
        tail digest)."""
        digests, tail_digest = chain_digests(prompt, self.blk)
        if self.prefix is None:
            return [], 0, None, False, digests, tail_digest
        self.stats["prefix_lookups"] += 1
        plen = len(prompt)
        ids: list[int] = []
        for d in digests:
            bid = self.prefix.blocks.get(d)
            if bid is None:
                break
            ids.append(bid)
        m = len(ids)
        H = m * self.blk
        hit_logits, tail_shared = None, False
        if m == len(digests):
            ent = self.prefix.tails.get(tail_digest)
            if ent is not None:
                tail_bid, hit_logits = ent
                if tail_bid is not None:
                    ids.append(tail_bid)
                    tail_shared = True
                H = plen
        if H >= plen and hit_logits is None and m:
            # whole-prompt block coverage but no cached logits: the
            # last block must be re-run, and a shared block can't be
            # the write target — drop it from the match
            ids.pop()
            m -= 1
            H = m * self.blk
        for bid in ids:
            self.pool.incref(bid)
        if H:
            self.stats["prefix_hits"] += 1
            self.stats["prefix_hit_tokens"] += H
        return ids, H, hit_logits, tail_shared, digests, tail_digest

    def _register_prefix(self, prompt: np.ndarray, row: list[int],
                         matched: int, digests: list[bytes],
                         tail_digest: bytes, logits) -> None:
        if self.prefix is None:
            return
        for i in range(matched, len(digests)):
            if digests[i] not in self.prefix.blocks:
                self.prefix.blocks[digests[i]] = row[i]
                self.pool.tag(row[i], ("block", digests[i]))
        if tail_digest not in self.prefix.tails:
            f = len(digests)
            tail_bid = row[f] if len(prompt) % self.blk else None
            self.prefix.tails[tail_digest] = (tail_bid, logits)
            if tail_bid is not None:
                self.pool.tag(tail_bid, ("tail", tail_digest))

    def _admit_one(self, req: Request, slot: int) -> None:
        plen = len(req.prompt)
        if not self.nb:
            # no KV leaves (pure SSM): paging degenerates to the dense
            # path — the "paged" cache IS the dense cache
            super()._admit_group([req], [slot])
            return
        if self.rolling:
            # ring semantics: the scratch prefill keeps the last window
            # regardless of prompt length, exactly like the dense foil
            limit = self.max_len
        elif self._extend is not None:
            limit = self.capacity
        else:
            limit = min(self.max_len, self.capacity)
        assert 1 <= plen <= limit, \
            f"prompt length {plen} vs paged capacity {limit}"
        prompt = np.asarray(req.prompt, np.int32)
        self.stats["prompt_tokens"] += plen
        budget = self._budget(req)
        if self.rolling:
            n_total = self.nb          # the whole ring, private
            hit, H, hit_lg, tail_shared = [], 0, None, False
            digests, tail_digest = [], b""
        else:
            cells = min(plen + budget, self.capacity)
            n_total = -(-cells // self.blk)
            (hit, H, hit_lg, tail_shared,
             digests, tail_digest) = self._match_prefix(prompt)
        need = (n_total - len(hit)) + \
            (1 if tail_shared and plen % self.blk else 0)
        try:
            fresh = self.pool.alloc(need)
        except BlockPoolExhaustedError:
            for bid in hit:
                self.pool.decref(bid)
            self.queue.appendleft(req)
            raise
        spare = fresh.pop() if tail_shared and plen % self.blk else None
        row = hit + fresh
        self._tables[slot, :] = -1
        self._tables[slot, :len(row)] = row
        self._tbl_dirty = True
        self._slot_blocks[slot] = row + ([spare] if spare is not None
                                         else [])
        self._cow_pending[slot] = (plen // self.blk, spare) \
            if spare is not None else None
        self._push_tables()
        matched = len(hit) - (1 if tail_shared else 0)

        if H == plen:                    # full hit: zero prefill
            logits = hit_lg
            out = self._admit_hit_jit(
                self.cache, self.tokens, self.done, self.remaining,
                self.temps, self.slot_keys, logits, jnp.int32(slot),
                budget - 1, float(req.temperature), jnp.int32(req.rid),
                jnp.int32(plen))
            self._finish_install(req, slot, out)
        elif H == 0:
            # dense scratch prefill of the leading window — the exact
            # bucketed call the dense foil makes — then paginate
            w0 = min(plen, self.max_len)
            padded = self._padded_len(w0)
            toks = np.full((1, padded), self.pad_id, np.int32)
            toks[0, :w0] = prompt[:w0]
            logits, sub = self._prefill_call(req.rid, toks, [w0],
                                             self._pcache0)
            row_dev = self._row_dev(row)
            if w0 == plen:
                out = self._paged_admit_jit(
                    self.cache, self.tokens, self.done, self.remaining,
                    self.temps, self.slot_keys, sub, logits,
                    jnp.int32(slot), budget - 1,
                    float(req.temperature), jnp.int32(req.rid), row_dev)
                self._finish_install(req, slot, out)
            else:                        # prompt exceeds one bucket
                self.cache = self._paginate_jit(self.cache, sub,
                                                row_dev,
                                                jnp.int32(slot))
                logits = self._extend_to(slot, prompt, w0)
                out = self._admit_hit_jit(
                    self.cache, self.tokens, self.done, self.remaining,
                    self.temps, self.slot_keys, logits,
                    jnp.int32(slot), budget - 1,
                    float(req.temperature), jnp.int32(req.rid),
                    jnp.int32(plen))
                self._finish_install(req, slot, out)
        else:                            # partial hit: resume at H
            self.cache = self._set_len_jit(self.cache, jnp.int32(slot),
                                           jnp.int32(H))
            logits = self._extend_to(slot, prompt, H)
            out = self._admit_hit_jit(
                self.cache, self.tokens, self.done, self.remaining,
                self.temps, self.slot_keys, logits, jnp.int32(slot),
                budget - 1, float(req.temperature), jnp.int32(req.rid),
                jnp.int32(plen))
            self._finish_install(req, slot, out)
        if not self.rolling:
            self._register_prefix(prompt, row, matched, digests,
                                  tail_digest, logits)

    def _extend_to(self, slot: int, prompt: np.ndarray,
                   start: int) -> jnp.ndarray:
        """Run ``prefill_extend`` segments until the whole prompt is in
        the cache; returns the last-token logits."""
        assert self._extend is not None, \
            "prefix resume / long prompts need model.prefill_extend"
        plen = len(prompt)
        logits = None
        pos = start
        while pos < plen:
            w = min(self.prefill_chunk, plen - pos)
            padded = self._padded_len(w)
            toks = np.full((1, padded), self.pad_id, np.int32)
            toks[0, :w] = prompt[pos:pos + w]
            self.stats["prefill_widths"].add(padded)
            self.stats["paged_extends"] += 1
            logits, self.cache = self._extend_slot_jit(
                self.params, self.cache, jnp.asarray(toks),
                jnp.int32(slot), jnp.int32(pos), jnp.int32(w))
            pos += w
        return logits

    # -- scheduler seams ------------------------------------------------------

    def _push_tables(self) -> None:
        if not self._tbl_dirty or not self.nb:
            return
        self.cache = self._settbl_jit(self.cache,
                                      jnp.asarray(self._tables))
        self._tbl_dirty = False

    def _before_chunk(self) -> None:
        """Copy-on-write fork point: a slot that adopted a shared,
        partially-filled tail block appends to it on its first decode
        write — fork the physical block just-in-time if it is still
        shared, else adopt it in place."""
        for slot, req in enumerate(self.active):
            pend = self._cow_pending[slot]
            if req is None or pend is None:
                continue
            bi, spare = pend
            self._cow_pending[slot] = None
            bid = int(self._tables[slot, bi])
            if self.pool.ref[bid] > 1:
                self.cache = self._fork_jit(self.cache, jnp.int32(bid),
                                            jnp.int32(spare))
                self._tables[slot, bi] = spare
                self._tbl_dirty = True
                self._slot_blocks[slot].remove(bid)
                self.pool.decref(bid)
                self.stats["cow_forks"] += 1
            else:
                # every other sharer retired: sole owner, append in
                # place; the reserved fork target goes back
                self._slot_blocks[slot].remove(spare)
                self.pool.decref(spare)
        self._push_tables()

    def _release_slot(self, slot: int) -> None:
        """Retire: drop the slot's refs — blocks (and their index
        entries) free exactly when their LAST sharer retires."""
        for bid in self._slot_blocks[slot]:
            self.pool.decref(bid)
        self._slot_blocks[slot] = []
        self._cow_pending[slot] = None
        self._tables[slot, :] = -1
        self._tbl_dirty = True

    def _on_block_free(self, bid: int, tags: list) -> None:
        if self.prefix is None:
            return
        for kind, key in tags:
            if kind == "block":
                self.prefix.blocks.pop(key, None)
            else:
                self.prefix.tails.pop(key, None)

    def _on_pool_pressure(self, pool: BlockPool, short: int) -> None:
        pool.evict_cold(short)

    def flush_prefix_cache(self) -> None:
        """Invalidate all content-addressed prefix state. REQUIRED
        after a params swap (RL policy adoption): cached KV and logits
        are policy-dependent. Live requests keep their blocks; parked
        cold blocks (whose KV is now stale) free outright."""
        self.pool.evict_cold(len(self.pool.cold))
        if self.prefix is not None:
            self.prefix.clear()
        self.pool.tags.clear()

    def perf_summary(self) -> dict:
        s = super().perf_summary()
        prompt_toks = self.stats["prompt_tokens"]
        s.update(
            block_size=self.blk,
            pool_blocks=self.pool.n_blocks - 1,
            blocks_peak=self.pool.stats["peak_used"],
            prefix_hits=self.stats["prefix_hits"],
            prefix_hit_tokens=self.stats["prefix_hit_tokens"],
            prefix_hit_rate=(self.stats["prefix_hit_tokens"]
                             / prompt_toks if prompt_toks else 0.0),
            cow_forks=self.stats["cow_forks"],
            paged_extends=self.stats["paged_extends"],
            cold_blocks=self.pool.cold_count,
            blocks_revived=self.pool.stats["revived"],
            blocks_evicted=self.pool.stats["evicted"])
        return s
