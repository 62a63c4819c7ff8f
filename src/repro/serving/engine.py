"""Serving engines over the model zoo's prefill/decode steps.

Two schedulers share one protocol (submit / step / run_until_drained):

* ``WaveEngine`` — the legacy static batcher, kept as the A/B foil:
  admission only at wave boundaries (a finished request's slot idles
  until the WHOLE wave drains) and one host round-trip per slot per
  decoded token (``int(next_tok[slot])``).

* ``ContinuousEngine`` — slot-level continuous batching with the decode
  loop kept on device:
    - the (B-slot) cache is allocated ONCE and stays resident: the
      decode chunk and the admit step take it DONATED, so each updates
      it in place (the decode step writes one K/V row per slot per
      layer, ``transformer.stage_decode``) and the engine's previous
      ``cache`` handle is dead after every call; per-slot cache lengths
      (``KVCache.length`` is (B,)) let a new request prefill into a
      free slot while the other slots keep decoding — no wave barrier;
    - admission prefills ONE request (batch 1, prompt right-padded to a
      power-of-two bucket so prefill recompiles are capped at
      O(log max_len); exact per-slot semantics via ``prompt_len``) and
      inserts the filled sub-cache into its slot with a jitted
      tree-wide dynamic_update_slice (in place: the cache is donated);
    - decoding runs N steps as one jitted ``lax.scan`` with ON-DEVICE
      sampling (greedy + temperature/top-k), per-slot EOS/budget done
      flags, and a single device->host transfer of the (N, B) token
      block — the per-token sync cost is amortized N-fold.

Both engines produce BIT-IDENTICAL greedy outputs (right-padded exact
prefill everywhere; tests assert it), so the A/B benchmark in
``benchmarks/serve_bench.py`` measures pure scheduling + sync overhead.
MoE capacity is forced to no-drop on the serving paths so expert
contention never couples slots (see moe.apply_moe).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeConfig
from repro.tracing import span

MIN_BUCKET = 8        # smallest prompt pad bucket
NEG_INF = -1e30


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0      # 0 = greedy (wave engine is greedy-only)
    out_tokens: list = dataclasses.field(default_factory=list)
    # log-prob of each emitted token under the SAMPLING distribution
    # (logits/temperature, pre-top-k; temp==0 scores the unscaled
    # softmax). Aligned 1:1 with out_tokens; filled only when the
    # engine runs with capture_logprobs=True (the RL rollout path).
    out_logprobs: list = dataclasses.field(default_factory=list)
    done: bool = False
    t_submit: float | None = None
    t_first: float | None = None  # first token available
    t_done: float | None = None


def bucket_len(n: int) -> int:
    """Next power of two >= n (floor MIN_BUCKET)."""
    b = MIN_BUCKET
    while b < n:
        b *= 2
    return b


def nucleus_mask(scaled: jnp.ndarray, top_p: float) -> jnp.ndarray:
    """(B, V) temperature-scaled logits -> bool keep-mask of the
    smallest token set whose probability mass reaches ``top_p``.

    On-device sorted-cumsum: sort descending, softmax, keep tokens
    while the mass BEFORE them is < top_p (so the top-1 token always
    survives and the set is minimal); the kept set maps back to vocab
    order via a per-row logit threshold (ties at the threshold are all
    kept)."""
    srt = jnp.sort(scaled, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(srt, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_p
    thresh = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                     keepdims=True)
    return scaled >= thresh


def sample_tokens(logits: jnp.ndarray, key, temps: jnp.ndarray,
                  top_k: int = 0, top_p: float = 0.0) -> jnp.ndarray:
    """On-device per-slot sampling. logits (B, V), temps (B,).

    temp == 0 -> greedy (bitwise argmax, matching the wave engine);
    temp > 0 -> categorical over logits/temp, optionally top-k- and/or
    nucleus (top-p)-masked (nucleus applies first, on the scaled
    distribution; top-k then picks from the surviving set).
    ``key`` is either one key for the whole batch (legacy: categorical
    draws independent gumbels per row, but the draw depends on the
    slot's NEIGHBORS) or a (B, 2) stack of PER-SLOT keys — each slot
    then consumes its own deterministic key stream, so a request's
    sampled tokens are reproducible regardless of slot placement or
    co-batched traffic."""
    lg = logits.astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    safe = jnp.where(temps > 0, temps, 1.0)[:, None]
    if top_p and top_p > 0.0:
        lg = jnp.where(nucleus_mask(lg / safe, top_p), lg, NEG_INF)
    per_slot = key.ndim == 2
    if top_k and top_k > 0:
        vals, idx = jax.lax.top_k(lg, top_k)
        scaled = vals / safe
        if per_slot:
            choice = jax.vmap(jax.random.categorical)(key, scaled)
        else:
            choice = jax.random.categorical(key, scaled)
        sampled = jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0]
    elif per_slot:
        sampled = jax.vmap(jax.random.categorical)(key, lg / safe)
    else:
        sampled = jax.random.categorical(key, lg / safe)
    return jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)


def chosen_logprob(logits: jnp.ndarray, toks: jnp.ndarray,
                   temps: jnp.ndarray) -> jnp.ndarray:
    """Log-prob of ``toks`` (B,) under softmax(logits/temp) per slot —
    the behavior-policy score an RL trainer needs next to each sampled
    token. temp==0 slots score the unscaled distribution (greedy picks
    the argmax, so this is its actual, finite log-mass)."""
    lg = logits.astype(jnp.float32)
    safe = jnp.where(temps > 0, temps, 1.0)[:, None]
    logp = jax.nn.log_softmax(lg / safe, axis=-1)
    return jnp.take_along_axis(
        logp, toks[:, None].astype(jnp.int32), axis=-1)[:, 0]


def bucket_batch(n: int) -> int:
    """Next power of two >= n (floor 1) — admission prefill batch
    buckets, so batched admission adds O(log slots) compiles, not one
    per occupancy pattern."""
    b = 1
    while b < n:
        b *= 2
    return b


def tree_take_slot(big, like1, idx, batch: int):
    """Extract row ``idx`` of a B-batch cache pytree as a batch-1
    pytree (the inverse of ``tree_insert_slot``): per leaf, a
    dynamic_slice along the batch axis, statically inferred as the
    unique axis where the big leaf has B and the batch-1 template leaf
    has 1."""
    def leaf(bl, ll):
        if batch == 1 and bl.shape == ll.shape:
            return bl
        for a in range(bl.ndim):
            if (bl.shape[a] == batch and ll.shape[a] == 1
                    and bl.shape[:a] == ll.shape[:a]
                    and bl.shape[a + 1:] == ll.shape[a + 1:]):
                return jax.lax.dynamic_slice_in_dim(bl, idx, 1, axis=a)
        raise ValueError(
            f"no batch axis: big {bl.shape} vs template {ll.shape}")
    return jax.tree.map(leaf, big, like1)


def tree_insert_slot(big, sub, slot, batch: int):
    """Insert a batch-1 cache pytree into slot ``slot`` of a B-slot
    cache: per leaf, a dynamic_update_slice along the (statically
    inferred) batch axis. Works across families — stacked KV (L, B, S,
    Hk, dh), per-slot lengths (L, B)/(B,), SSM states (L, B, H, P, N),
    conv rings, cross caches — because the batch axis is the unique
    axis where the big leaf has B and the sub leaf has 1."""
    def leaf(bl, sl):
        if batch == 1 and bl.shape == sl.shape:
            return sl.astype(bl.dtype)
        for a in range(bl.ndim):
            if (bl.shape[a] == batch and sl.shape[a] == 1
                    and bl.shape[:a] == sl.shape[:a]
                    and bl.shape[a + 1:] == sl.shape[a + 1:]):
                return jax.lax.dynamic_update_slice_in_dim(
                    bl, sl.astype(bl.dtype), slot, axis=a)
        raise ValueError(
            f"no batch axis: big {bl.shape} vs sub {sl.shape}")
    return jax.tree.map(leaf, big, sub)


class _EngineBase:
    kind = ""

    def __init__(self, model, params, *, batch_slots: int = 4,
                 max_len: int = 512, eos_id: int = 1, pad_id: int = 0,
                 bucket_prompts: bool = True):
        self.model = model
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.bucket_prompts = bucket_prompts
        self.cfg = getattr(model, "cfg", None)
        self.shape = ShapeConfig("serve", "decode", max_len, batch_slots)
        self.queue: deque[Request] = deque()
        self.active: list[Request | None] = [None] * batch_slots
        self.latencies: list[float] = []
        self.wall: float = 0.0
        self.stats = {"decode_steps": 0, "tokens_out": 0,
                      "host_syncs": 0, "admitted": 0,
                      "busy_slot_steps": 0, "total_slot_steps": 0,
                      "prefill_widths": set()}

    def submit(self, req: Request) -> None:
        req.t_submit = time.perf_counter()
        self.queue.append(req)

    def reset_metrics(self) -> None:
        """Zero counters/latencies (keeps compiled functions and device
        state) — lets benchmarks time a post-warmup run."""
        for k, v in self.stats.items():
            self.stats[k] = set() if isinstance(v, set) else 0
        self.latencies = []
        self.wall = 0.0

    # -- admission helpers ----------------------------------------------------

    def _padded_len(self, n: int) -> int:
        """Pad width for an n-token prompt: power-of-two bucket so the
        prefill jit cache stays O(log max_len) entries. Safe for SWA
        rings at any width — the rolling prefill write gathers each
        slot's newest in-window positions (see transformer.prefill)."""
        if not self.bucket_prompts:
            return n
        return max(min(bucket_len(n), self.max_len), n)

    def _budget(self, req: Request) -> int:
        """Total tokens this request may emit (cache-capacity-clamped
        for non-rolling attention caches; SSM state and SWA rings are
        O(1)/wrapping, so no cap there)."""
        cfg = self.cfg
        capless = (getattr(cfg, "sliding_window", None) is not None
                   or (getattr(cfg, "family", "") in ("ssm", "hybrid")
                       and not getattr(cfg, "attn_every", None)))
        if capless:
            return max(1, req.max_new_tokens)
        return max(1, min(req.max_new_tokens,
                          self.max_len - len(req.prompt)))

    def _retire(self, req: Request) -> None:
        req.done = True
        req.t_done = time.perf_counter()
        self.latencies.append(req.t_done - req.t_submit)

    # -- protocol -------------------------------------------------------------

    def step(self) -> int:
        raise NotImplementedError

    def run_until_drained(self, max_steps: int = 10_000) -> None:
        t0 = time.perf_counter()
        for _ in range(max_steps):
            if self.step() == 0 and not self.queue:
                break
        self.wall += time.perf_counter() - t0

    def perf_summary(self) -> dict:
        lat = sorted(self.latencies)
        pct = (lambda p: lat[min(len(lat) - 1,
                                 int(p / 100 * len(lat)))]) if lat \
            else (lambda p: float("nan"))
        occ = (self.stats["busy_slot_steps"]
               / max(1, self.stats["total_slot_steps"]))
        return {
            "engine": self.kind,
            "requests": len(lat),
            "tokens_out": self.stats["tokens_out"],
            "decode_steps": self.stats["decode_steps"],
            "wall_s": self.wall,
            "tokens_per_s": self.stats["tokens_out"] / self.wall
            if self.wall else float("nan"),
            "latency_p50_s": pct(50),
            "latency_p95_s": pct(95),
            "slot_occupancy": occ,
            "host_syncs": self.stats["host_syncs"],
            "prefill_widths": sorted(self.stats["prefill_widths"]),
        }


# -- wave (static) batching ---------------------------------------------------


class WaveEngine(_EngineBase):
    """Wave-scheduled static batching (the seed engine, modernized to
    the per-slot cache): all-free admission, lockstep decode, one host
    sync per slot per token. Greedy-only."""
    kind = "wave"

    def __init__(self, model, params, **kw):
        super().__init__(model, params, **kw)
        self.cache = None
        self.tokens = None
        self.remaining = np.zeros((self.slots,), np.int64)
        self._decode = jax.jit(lambda p, t, c: model.decode(p, t, c))
        self._prefill = jax.jit(lambda p, b, c: model.prefill(p, b, c))
        self._cache0 = model.init_cache(self.slots, self.shape)
        self.stats["waves"] = 0

    def submit(self, req: Request) -> None:
        if req.temperature > 0:
            raise ValueError(
                "WaveEngine is greedy-only (it exists as the A/B "
                "foil); use ContinuousEngine for sampled requests")
        super().submit(req)

    def _admit_wave(self) -> bool:
        if not self.queue:
            return False
        wave: list[Request] = []
        while self.queue and len(wave) < self.slots:
            wave.append(self.queue.popleft())
        for w in wave:
            assert 1 <= len(w.prompt) <= self.max_len, \
                f"prompt length {len(w.prompt)} vs max_len {self.max_len}"
        padded = self._padded_len(max(len(w.prompt) for w in wave))
        tokens = np.full((self.slots, padded), self.pad_id, np.int32)
        plen = np.ones((self.slots,), np.int32)
        for i, w in enumerate(wave):
            tokens[i, :len(w.prompt)] = w.prompt        # RIGHT-pad
            plen[i] = len(w.prompt)
        self.stats["prefill_widths"].add(padded)
        logits, self.cache = self._prefill(
            self.params,
            {"tokens": jnp.asarray(tokens),
             "prompt_len": jnp.asarray(plen)},
            self._cache0)
        first = jnp.argmax(logits, axis=-1)
        self.tokens = first[:, None].astype(jnp.int32)
        now = time.perf_counter()
        for i in range(self.slots):
            req = wave[i] if i < len(wave) else None
            self.active[i] = req
            self.remaining[i] = 0
            if req is None:
                continue
            tok = int(first[i])
            req.out_tokens.append(tok)
            req.t_first = now
            self.stats["tokens_out"] += 1
            budget = self._budget(req)
            self.remaining[i] = budget - 1
            if tok == self.eos_id or budget <= 1:
                self._retire(req)
                self.active[i] = None
        self.stats["waves"] += 1
        self.stats["admitted"] += len(wave)
        return True

    def step(self) -> int:
        """One engine iteration; returns number of active slots."""
        if not any(r is not None for r in self.active):
            if not self._admit_wave():
                return 0
        logits, self.cache = self._decode(self.params, self.tokens,
                                          self.cache)
        next_tok = jnp.argmax(logits, axis=-1)
        self.stats["decode_steps"] += 1
        self.stats["total_slot_steps"] += self.slots
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            tok = int(next_tok[slot])           # host sync PER TOKEN
            self.stats["host_syncs"] += 1
            self.stats["busy_slot_steps"] += 1
            req.out_tokens.append(tok)
            self.stats["tokens_out"] += 1
            self.remaining[slot] -= 1
            if tok == self.eos_id or self.remaining[slot] <= 0:
                self._retire(req)
                self.active[slot] = None        # idles until wave drains
        self.tokens = next_tok[:, None].astype(jnp.int32)
        return sum(r is not None for r in self.active)


# -- continuous (per-slot) batching -------------------------------------------


class ContinuousEngine(_EngineBase):
    """Slot-level continuous batching with a device-resident decode
    loop. ``decode_chunk`` is the scheduling quantum: admissions and
    retirements happen between chunks; within a chunk the device runs
    ``lax.scan`` over decode+sample steps and ships one (N, B) token
    block to the host."""
    kind = "continuous"

    def __init__(self, model, params, *, decode_chunk: int = 8,
                 top_k: int = 0, top_p: float = 0.0, seed: int = 0,
                 batch_admit: bool = True, overlap_admission: bool = False,
                 capture_logprobs: bool = False, **kw):
        super().__init__(model, params, **kw)
        self.decode_chunk = decode_chunk
        self.top_k = top_k
        self.top_p = top_p
        self.batch_admit = batch_admit
        # overlap admission prefill with the in-flight decode chunk:
        # after the chunk is DISPATCHED (before its blocking host
        # read), queued requests prefill into B=1 sub-caches the device
        # can overlap with the running scan; they splice at the next
        # chunk boundary. Bit-identical to serial admission — per-rid
        # PRNG streams and exact right-padded prefill are placement-
        # and timing-independent.
        self.overlap_admission = overlap_admission
        self._prepped: deque = deque()
        # RL rollout mode: the decode scan additionally emits each
        # sampled token's log-prob (one extra (N, B) row in the same
        # host transfer). Off by default — the serving path's compiled
        # program is unchanged when disabled.
        self.capture_logprobs = capture_logprobs
        self.cache = model.init_cache(self.slots, self.shape)
        self._pcache0 = model.init_cache(1, self.shape)  # prefill template
        self._pcaches = {1: self._pcache0}   # per-batch-bucket templates
        self.tokens = jnp.full((self.slots, 1), self.pad_id, jnp.int32)
        self.done = jnp.ones((self.slots,), bool)
        self.remaining = jnp.zeros((self.slots,), jnp.int32)
        self.temps = jnp.zeros((self.slots,), jnp.float32)
        # per-slot PRNG streams: each request's stream is seeded from
        # (engine seed, request id) at admission, so its temperature /
        # top-k draws are reproducible REGARDLESS of which slot it
        # lands in or what else is co-batched
        self.base_key = jax.random.PRNGKey(seed)
        self.slot_keys = jnp.zeros((self.slots, 2), jnp.uint32)
        self._pending_first: list = [None] * self.slots
        self._prefill = jax.jit(lambda p, b, c: model.prefill(p, b, c))
        # the slot cache is donated: a chunk or an install updates it in
        # place instead of copying it (the prefill templates are not:
        # every admission reuses them)
        self._admit_jit = jax.jit(self._admit_fn,
                                  donate_argnames=("cache",))
        self._chunk_jit = jax.jit(self._chunk_fn, static_argnames=("n",),
                                  donate_argnames=("cache",))
        self.stats["decode_chunks"] = 0
        self.stats["prefills"] = 0

    # -- device-side pieces ---------------------------------------------------

    def _admit_fn(self, cache, tokens, done, remaining, temps,
                  slot_keys, sub_cache, logits, slot, budget, temp,
                  rid):
        """Insert a freshly prefilled request into ``slot``: cache
        splice + first-token sample + per-slot state reset, one jit.
        The request's PRNG stream is derived from (engine seed, rid) —
        slot placement never enters the key chain."""
        cache = tree_insert_slot(cache, sub_cache, slot, self.slots)
        return self._admit_state(cache, tokens, done, remaining, temps,
                                 slot_keys, logits, slot, budget, temp,
                                 rid)

    def _admit_state(self, cache, tokens, done, remaining, temps,
                     slot_keys, logits, slot, budget, temp, rid):
        """Post-splice half of admission: first-token sample + per-slot
        scheduler state reset (shared by the dense splice and the paged
        engine's block-table paths)."""
        req_key = jax.random.fold_in(self.base_key, rid)
        k_first, k_stream = jax.random.split(req_key)
        first = sample_tokens(logits, k_first[None, :],
                              jnp.reshape(temp, (1,)).astype(jnp.float32),
                              self.top_k, self.top_p)     # (1,)
        tokens = jax.lax.dynamic_update_slice(
            tokens, first.reshape(1, 1).astype(jnp.int32), (slot, 0))
        budget = jnp.reshape(budget, (1,)).astype(jnp.int32)
        first_done = (first == self.eos_id) | (budget <= 0)
        done = jax.lax.dynamic_update_slice(done, first_done, (slot,))
        remaining = jax.lax.dynamic_update_slice(remaining, budget,
                                                 (slot,))
        temps = jax.lax.dynamic_update_slice(
            temps, jnp.reshape(temp, (1,)).astype(jnp.float32), (slot,))
        slot_keys = jax.lax.dynamic_update_slice(
            slot_keys, k_stream[None, :].astype(slot_keys.dtype),
            (slot, 0))
        out = (cache, tokens, done, remaining, temps, slot_keys,
               first[0])
        if self.capture_logprobs:
            lp = chosen_logprob(
                logits, first,
                jnp.reshape(temp, (1,)).astype(jnp.float32))
            out = out + (lp[0],)
        return out

    def _chunk_fn(self, params, cache, tokens, done, remaining, temps,
                  slot_keys, *, n: int):
        """N decode+sample steps as one lax.scan; emits the (N, B)
        sampled-token block (-1 for slots already done at step start).
        Every slot advances its OWN key chain one split per step, so a
        request's draw sequence depends only on (engine seed, rid,
        token index) — never on chunk boundaries or sibling slots."""
        def body(carry, _):
            tokens, cache, done, remaining, keys = carry
            logits, cache = self.model.decode(params, tokens, cache)
            nk = jax.vmap(jax.random.split)(keys)        # (B, 2, 2)
            step_keys, keys = nk[:, 0], nk[:, 1]
            nxt = sample_tokens(logits, step_keys, temps, self.top_k,
                                self.top_p)
            remaining = remaining - jnp.where(done, 0, 1)
            newly = (~done) & ((nxt == self.eos_id) | (remaining <= 0))
            emit = jnp.where(done, -1, nxt)
            if self.capture_logprobs:
                lp = chosen_logprob(logits, nxt, temps)
                emit = (emit, jnp.where(done, 0.0, lp))
            done = done | newly
            return (nxt[:, None].astype(jnp.int32), cache, done,
                    remaining, keys), emit

        (tokens, cache, done, remaining, slot_keys), toks = jax.lax.scan(
            body, (tokens, cache, done, remaining, slot_keys), None,
            length=n)
        return cache, tokens, done, remaining, slot_keys, toks

    # -- host-side scheduler --------------------------------------------------

    def _pcache(self, nb: int):
        c = self._pcaches.get(nb)
        if c is None:
            c = self._pcaches[nb] = self.model.init_cache(
                nb, self.shape)
        return c

    def _admit(self) -> None:
        """Fill every free slot from the queue. With ``batch_admit``
        the waiting requests are prefilled in ONE bucketed call
        (batch padded to a power of two with throwaway rows, prompts
        right-padded to the longest bucket) and each row's batch-1
        sub-cache is spliced into its slot — a burst of B admissions
        costs one prefill instead of B. Per-row outputs are identical
        to the B=1 path (rows never interact: causal attention +
        no-drop MoE capacity on serving paths), which the equivalence
        test asserts bitwise."""
        free = [s for s in range(self.slots)
                if self.active[s] is None]
        while free and self._prepped:       # overlap-prefilled splice
            req, sub, logits = self._prepped.popleft()
            self._install(req, free.pop(0), sub, logits)
        n = min(len(free), len(self.queue))
        if n == 0:
            return
        reqs = [self.queue.popleft() for _ in range(n)]
        groups = [reqs] if self.batch_admit else [[r] for r in reqs]
        taken = 0
        for grp in groups:
            slots = free[taken:taken + len(grp)]
            taken += len(grp)
            self._admit_group(grp, slots)

    def _prep_admissions(self) -> None:
        """Dispatch B=1 admission prefills for queued requests while
        the decode chunk is still running on device (called between
        chunk dispatch and its blocking host read). The results wait in
        ``_prepped`` and splice at the next chunk boundary."""
        while self.queue and len(self._prepped) < self.slots:
            req = self.queue.popleft()
            assert 1 <= len(req.prompt) <= self.max_len, \
                f"prompt length {len(req.prompt)} vs {self.max_len}"
            padded = self._padded_len(len(req.prompt))
            tokens = np.full((1, padded), self.pad_id, np.int32)
            tokens[0, :len(req.prompt)] = req.prompt
            logits, sub = self._prefill_call(
                req.rid, tokens, [len(req.prompt)], self._pcache0)
            self._prepped.append((req, sub, logits))

    def _prefill_call(self, rid: int, tokens: np.ndarray, plen,
                      cache):
        """Dispatch one bucketed prefill of right-padded ``tokens``
        (rows, width) with per-row prompt lengths ``plen`` into
        ``cache``, and count it; ``rid`` names the first request.
        Returns (last-token logits, filled sub-cache)."""
        width = tokens.shape[1]
        self.stats["prefill_widths"].add(width)
        self.stats["prefills"] += 1
        with span("engine.prefill", rid=rid, width=width):
            return self._prefill(
                self.params,
                {"tokens": jnp.asarray(tokens),
                 "prompt_len": jnp.asarray(plen, np.int32)},
                cache)

    def _admit_group(self, reqs: list, slots: list) -> None:
        for req in reqs:
            assert 1 <= len(req.prompt) <= self.max_len, \
                f"prompt length {len(req.prompt)} vs {self.max_len}"
        nb = bucket_batch(len(reqs))
        padded = self._padded_len(max(len(r.prompt) for r in reqs))
        tokens = np.full((nb, padded), self.pad_id, np.int32)
        plen = np.ones((nb,), np.int32)    # dummy rows: 1-token pads
        for i, r in enumerate(reqs):
            tokens[i, :len(r.prompt)] = r.prompt         # RIGHT-pad
            plen[i] = len(r.prompt)
        logits, sub = self._prefill_call(reqs[0].rid, tokens, plen,
                                         self._pcache(nb))
        for i, (req, slot) in enumerate(zip(reqs, slots)):
            sub_i = sub if nb == 1 else tree_take_slot(
                sub, self._pcache0, i, nb)
            self._install(req, slot, sub_i, logits[i:i + 1])

    def _install(self, req: Request, slot: int, sub_cache,
                 logits) -> None:
        """Splice one prefilled request (batch-1 sub-cache + last-token
        logits row) into ``slot`` via the jitted admit step."""
        with span("engine.install", rid=req.rid, slot=slot):
            out = self._admit_jit(
                self.cache, self.tokens, self.done, self.remaining,
                self.temps, self.slot_keys, sub_cache, logits,
                jnp.int32(slot), self._budget(req) - 1,
                float(req.temperature), jnp.int32(req.rid))
            self._finish_install(req, slot, out)

    def _finish_install(self, req: Request, slot: int, out) -> None:
        (self.cache, self.tokens, self.done, self.remaining,
         self.temps, self.slot_keys) = out[:6]
        # (first token, logprob-or-None): fetched at drain
        self._pending_first[slot] = (
            out[6], out[7] if self.capture_logprobs else None)
        self.active[slot] = req
        self.stats["admitted"] += 1

    def _drain(self, toks_np: np.ndarray,
               lps_np: np.ndarray | None = None) -> None:
        n = toks_np.shape[0]
        now = time.perf_counter()
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            budget = self._budget(req)
            if self._pending_first[slot] is not None:
                first_dev, lp_dev = self._pending_first[slot]
                first = int(np.asarray(first_dev))
                self._pending_first[slot] = None
                req.out_tokens.append(first)
                if lp_dev is not None:
                    req.out_logprobs.append(float(np.asarray(lp_dev)))
                req.t_first = now
                self.stats["tokens_out"] += 1
                if first == self.eos_id or len(req.out_tokens) >= budget:
                    self._retire(req)
                    self.active[slot] = None
                    self._release_slot(slot)
                    continue
            for t in range(n):
                tok = int(toks_np[t, slot])
                if tok < 0:      # slot was done before this step
                    break
                req.out_tokens.append(tok)
                if lps_np is not None:
                    req.out_logprobs.append(float(lps_np[t, slot]))
                self.stats["tokens_out"] += 1
                if tok == self.eos_id or len(req.out_tokens) >= budget:
                    self._retire(req)
                    self.active[slot] = None
                    self._release_slot(slot)
                    break

    # -- scheduler seams (paged engine overrides) -----------------------------

    def _release_slot(self, slot: int) -> None:
        """Called when ``slot`` retires — the paged engine releases its
        block refs here."""

    def _before_chunk(self) -> None:
        """Called after admission, before the decode chunk is
        dispatched — the paged engine's copy-on-write fork point."""

    def _after_chunk(self, n: int) -> None:
        """Called after the chunk's host read — bookkeeping that must
        mirror the device write cursors (every slot's cache length
        advanced by ``n``)."""

    def step(self) -> int:
        """One scheduling quantum: admit into free slots, run one
        decode chunk on device, drain its token block (the single
        device->host transfer), retire finished requests."""
        with span("engine.step"):
            with span("engine.admit"):
                self._admit()
            if not any(r is not None for r in self.active):
                return 0
            self._before_chunk()
            n = self.decode_chunk
            with span("engine.decode_chunk"):
                (self.cache, self.tokens, self.done, self.remaining,
                 self.slot_keys, toks) = self._chunk_jit(
                    self.params, self.cache, self.tokens, self.done,
                    self.remaining, self.temps, self.slot_keys, n=n)
            if self.overlap_admission:
                # the chunk above is dispatched but not yet read back:
                # admission prefills ride the gap
                self._prep_admissions()
            with span("engine.readback"):
                lps_np = None
                if self.capture_logprobs:
                    toks, lps = toks
                    lps_np = np.asarray(lps)   # same chunk-granular sync
                toks_np = np.asarray(toks)     # ONE host sync per chunk
            self.stats["host_syncs"] += 1
            self.stats["decode_chunks"] += 1
            self.stats["decode_steps"] += n
            self.stats["total_slot_steps"] += n * self.slots
            self.stats["busy_slot_steps"] += int((toks_np >= 0).sum())
            self._after_chunk(n)
            with span("engine.drain"):
                self._drain(toks_np, lps_np)
            return sum(r is not None for r in self.active)


# legacy name: the wave engine was the original ServeEngine
ServeEngine = WaveEngine


def make_engine(kind: str, model, params, **kw):
    if kind == "wave":
        for k in ("decode_chunk", "top_k", "top_p", "seed",
                  "batch_admit", "overlap_admission"):
            kw.pop(k, None)
        return WaveEngine(model, params, **kw)
    if kind == "continuous":
        return ContinuousEngine(model, params, **kw)
    if kind == "paged":
        from repro.serving.paging import PagedEngine
        return PagedEngine(model, params, **kw)
    raise ValueError(f"unknown engine kind {kind!r}")
