"""Serving engines: continuous-batching correctness (slot insert /
retire, bucketed exact prefill, on-device sampling loop) and wave-vs-
continuous greedy bit-equivalence, plus the SWA rolling-cache wrap
boundary in decode_attention."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import CONFIGS
from repro.configs.base import ShapeConfig
from repro.models import attention as attn
from repro.models.registry import get_model
from repro.serving.engine import (ContinuousEngine, Request, WaveEngine,
                                  bucket_len)


@pytest.fixture(scope="module")
def setup():
    cfg = CONFIGS["mamba2-130m"].reduced()
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _mixed_requests(cfg, n, seed=0, long_new=17):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(20, 45)) if i % 3 == 2 else \
            int(rng.integers(3, 20))
        reqs.append(Request(
            i, (rng.integers(2, cfg.vocab, size=plen)).astype(np.int32),
            max_new_tokens=long_new if i % 3 == 2 else 4))
    return reqs


# -- wave engine (legacy behavior preserved) ----------------------------------


def test_wave_engine_drains_all_requests(setup):
    cfg, model, params = setup
    engine = WaveEngine(model, params, batch_slots=3, max_len=128)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(2, cfg.vocab, size=8).astype(
        np.int32), max_new_tokens=5) for i in range(7)]
    for r in reqs:
        engine.submit(r)
    engine.run_until_drained()
    assert all(r.done for r in reqs)
    assert all(1 <= len(r.out_tokens) <= 5 for r in reqs)
    assert engine.stats["waves"] >= 3     # 7 requests / 3 slots


def test_wave_greedy_matches_manual_decode(setup):
    """Engine output == manual prefill+decode loop (same greedy path)."""
    cfg, model, params = setup
    prompt = np.arange(2, 10).astype(np.int32)
    engine = WaveEngine(model, params, batch_slots=1, max_len=64)
    req = Request(0, prompt, max_new_tokens=4)
    engine.submit(req)
    engine.run_until_drained()

    shape = ShapeConfig("m", "decode", 64, 1)
    cache = model.init_cache(1, shape)
    logits, cache = model.prefill(params, {"tokens": prompt[None]},
                                  cache)
    toks = [int(np.argmax(np.asarray(logits[0])))]
    for _ in range(3):
        logits, cache = model.decode(
            params, np.asarray([[toks[-1]]], np.int32), cache)
        toks.append(int(np.argmax(np.asarray(logits[0]))))
    assert req.out_tokens == toks


# -- continuous engine --------------------------------------------------------


def test_continuous_drains_and_reuses_slots(setup):
    cfg, model, params = setup
    engine = ContinuousEngine(model, params, batch_slots=2,
                              max_len=128, decode_chunk=4)
    reqs = _mixed_requests(cfg, 9)
    for r in reqs:
        engine.submit(r)
    engine.run_until_drained()
    assert all(r.done for r in reqs)
    assert all(1 <= len(r.out_tokens) <= r.max_new_tokens
               for r in reqs)
    assert engine.stats["admitted"] == 9        # 9 requests, 2 slots
    # one host sync per CHUNK, not per token
    assert engine.stats["host_syncs"] == engine.stats["decode_chunks"]
    assert engine.stats["tokens_out"] > engine.stats["host_syncs"]


def test_continuous_greedy_matches_manual_decode(setup):
    cfg, model, params = setup
    prompt = np.arange(2, 13).astype(np.int32)
    engine = ContinuousEngine(model, params, batch_slots=3,
                              max_len=64, decode_chunk=5)
    req = Request(0, prompt, max_new_tokens=7)
    engine.submit(req)
    engine.run_until_drained()

    shape = ShapeConfig("m", "decode", 64, 1)
    cache = model.init_cache(1, shape)
    logits, cache = model.prefill(params, {"tokens": prompt[None]},
                                  cache)
    toks = [int(np.argmax(np.asarray(logits[0])))]
    for _ in range(6):
        logits, cache = model.decode(
            params, np.asarray([[toks[-1]]], np.int32), cache)
        toks.append(int(np.argmax(np.asarray(logits[0]))))
    assert req.out_tokens == toks


@pytest.mark.parametrize("arch", ["mamba2-130m", "internlm2-1.8b",
                                  "h2o-danube-1.8b"])
def test_continuous_bit_identical_to_wave_greedy(arch):
    """Acceptance: greedy outputs bit-identical between engines on a
    mixed-length trace (SSM, dense GQA, SWA families)."""
    cfg = CONFIGS[arch].reduced()
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    a = _mixed_requests(cfg, 8)
    b = _mixed_requests(cfg, 8)
    w = WaveEngine(model, params, batch_slots=3, max_len=128)
    c = ContinuousEngine(model, params, batch_slots=3, max_len=128,
                         decode_chunk=5)
    for r in a:
        w.submit(r)
    for r in b:
        c.submit(r)
    busy_at_install = []
    install = c._install

    def counting_install(req, slot, sub, logits):
        busy_at_install.append(sum(r is not None for r in c.active))
        install(req, slot, sub, logits)

    c._install = counting_install
    w.run_until_drained()
    c.run_until_drained()
    for x, y in zip(a, b):
        assert x.out_tokens == y.out_tokens, x.rid
    # the trace admits requests mid-stream, into the donated cache of
    # slots that are still decoding
    assert max(busy_at_install) > 0


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "mamba2-130m"])
def test_continuous_engine_donates_cache(arch):
    """The decode chunk and the install take the slot cache donated:
    after each, the engine's previous cache buffers are deleted (the
    update happened in place)."""
    cfg = CONFIGS[arch].reduced()
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    engine = ContinuousEngine(model, params, batch_slots=2, max_len=64,
                              decode_chunk=4)
    engine.submit(Request(0, np.arange(2, 10).astype(np.int32),
                          max_new_tokens=12))
    before = jax.tree.leaves(engine.cache)
    engine.step()                       # install, then a decode chunk
    assert all(x.is_deleted() for x in before)
    engine.submit(Request(1, np.arange(3, 7).astype(np.int32),
                          max_new_tokens=3))
    before = jax.tree.leaves(engine.cache)
    engine._admit()                     # install alone
    assert engine.active[1] is not None
    assert all(x.is_deleted() for x in before)
    assert all(not x.is_deleted() for x in
               jax.tree.leaves((engine.cache, engine._pcache0)))
    engine.run_until_drained()


def test_prefill_widths_are_bucketed(setup):
    cfg, model, params = setup
    engine = ContinuousEngine(model, params, batch_slots=2,
                              max_len=128, decode_chunk=4)
    for r in _mixed_requests(cfg, 12, seed=3):
        engine.submit(r)
    engine.run_until_drained()
    widths = engine.stats["prefill_widths"]
    assert all(w == bucket_len(w) for w in widths)    # powers of two
    assert len(widths) <= 4       # capped recompiles on 3..45 prompts


def test_sampling_deterministic_and_top1_is_greedy(setup):
    cfg, model, params = setup

    def run(seed, temperature, top_k):
        engine = ContinuousEngine(model, params, batch_slots=2,
                                  max_len=64, decode_chunk=4,
                                  top_k=top_k, seed=seed)
        reqs = [Request(i, np.arange(2, 8 + i).astype(np.int32),
                        max_new_tokens=6, temperature=temperature)
                for i in range(4)]
        for r in reqs:
            engine.submit(r)
        engine.run_until_drained()
        return [r.out_tokens for r in reqs]

    assert run(0, 1.0, 0) == run(0, 1.0, 0)       # same rng -> same
    assert run(0, 1.0, 0) != run(1, 1.0, 0)       # different rng
    assert all(0 <= t < cfg.padded_vocab
               for out in run(0, 1.0, 0) for t in out)
    # top_k=1 collapses sampling to argmax == greedy
    assert run(0, 5.0, 1) == run(0, 0.0, 0)


def test_per_slot_seed_reproducible_across_slot_placement(setup):
    """A sampled request's token stream is seeded from (engine seed,
    rid): the SAME request must produce the SAME tokens whether it is
    served alone in slot 0 or admitted mid-stream into a busy engine's
    last free slot next to other sampled traffic."""
    cfg, model, params = setup
    prompt = np.arange(2, 9).astype(np.int32)

    solo_req = Request(7, prompt.copy(), max_new_tokens=6,
                       temperature=0.9)
    solo = ContinuousEngine(model, params, batch_slots=1, max_len=64,
                            decode_chunk=4, seed=3)
    solo.submit(solo_req)
    solo.run_until_drained()

    busy = ContinuousEngine(model, params, batch_slots=3, max_len=64,
                            decode_chunk=4, seed=3)
    for i, t in ((100, 1.3), (101, 0.7)):     # different rids/temps
        busy.submit(Request(i, np.arange(3, 12).astype(np.int32),
                            max_new_tokens=20, temperature=t))
    busy.step()                                # both decode a chunk
    late = Request(7, prompt.copy(), max_new_tokens=6,
                   temperature=0.9)
    busy.submit(late)                          # lands in slot 2
    busy.run_until_drained()
    assert late.out_tokens == solo_req.out_tokens

    # different engine seed -> different stream for the same rid
    other = ContinuousEngine(model, params, batch_slots=1, max_len=64,
                             decode_chunk=4, seed=4)
    req2 = Request(7, prompt.copy(), max_new_tokens=6, temperature=0.9)
    other.submit(req2)
    other.run_until_drained()
    assert req2.out_tokens != solo_req.out_tokens


def test_mid_stream_admission_uses_per_slot_positions(setup):
    """A request admitted while another slot is deep into decode must
    produce the same tokens as when served alone."""
    cfg, model, params = setup
    long_req = Request(0, np.arange(2, 10).astype(np.int32),
                       max_new_tokens=24)
    late_req = Request(1, np.arange(3, 9).astype(np.int32),
                       max_new_tokens=5)

    solo = Request(9, late_req.prompt.copy(), max_new_tokens=5)
    e1 = ContinuousEngine(model, params, batch_slots=1, max_len=64,
                          decode_chunk=4)
    e1.submit(solo)
    e1.run_until_drained()

    e2 = ContinuousEngine(model, params, batch_slots=2, max_len=64,
                          decode_chunk=4)
    e2.submit(long_req)
    e2.step()                      # long_req decodes a chunk alone
    e2.submit(late_req)            # admitted mid-stream
    e2.run_until_drained()
    assert late_req.out_tokens == solo.out_tokens
    assert long_req.done and late_req.done


# -- SWA rolling-cache wrap boundary ------------------------------------------


def _brute_swa_reference(q, written, window, dtype=jnp.float32):
    """Dense attention over the chronological last-`window` tokens."""
    ks = jnp.stack([k for k, _ in written[-window:]], axis=1)
    vs = jnp.stack([v for _, v in written[-window:]], axis=1)
    b = q.shape[0]
    lengths = jnp.full((b,), ks.shape[1], jnp.int32)
    flat = attn.KVCache(ks, vs, lengths)
    return attn.decode_attention(q, flat)


@pytest.mark.parametrize("length", [31, 32, 33, 40])
def test_swa_rolling_wrap_boundary(length):
    """decode_attention on the ring at length == s_max and s_max + 1
    (the wrap boundary) must equal dense attention over the
    chronological window."""
    s_max, window, b, hk, g, dh = 32, 24, 2, 2, 2, 16
    rng = np.random.default_rng(length)
    cache = attn.KVCache.init(b, s_max, hk, dh, jnp.float32)
    written = []
    for _ in range(length):
        k = jnp.asarray(rng.normal(size=(b, 1, hk, dh)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, 1, hk, dh)), jnp.float32)
        cache = attn.cache_update(cache, k, v, rolling=True)
        written.append((k[:, 0], v[:, 0]))
    assert int(cache.length[0]) == length
    q = jnp.asarray(rng.normal(size=(b, 1, hk * g, dh)), jnp.float32)
    out = attn.decode_attention(q, cache, window=window)
    ref = _brute_swa_reference(q, written, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_per_slot_lengths_mask_independently():
    """Slots at different lengths in ONE cache must each match their
    own single-slot computation."""
    s_max, b, hk, g, dh = 16, 3, 2, 2, 8
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.normal(size=(b, s_max, hk, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s_max, hk, dh)), jnp.float32)
    lengths = jnp.asarray([3, 9, 16], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, 1, hk * g, dh)), jnp.float32)
    out = attn.decode_attention(q, attn.KVCache(k, v, lengths))
    for i in range(b):
        solo = attn.decode_attention(
            q[i:i + 1], attn.KVCache(k[i:i + 1], v[i:i + 1],
                                     lengths[i:i + 1]))
        np.testing.assert_allclose(np.asarray(out[i]),
                                   np.asarray(solo[0]),
                                   rtol=1e-6, atol=1e-6)


def _stacked_cache(rng, n_layers, lengths, *, paged):
    """A layer-stacked cache with random contents: dense (L, B, 8, 2, 4),
    or paged with 4-cell blocks, 2 per slot, slot 1's second unmapped."""
    b, hk, dh = len(lengths), 2, 4
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32),
                               (n_layers, b))
    if not paged:
        kv = rng.normal(size=(2, n_layers, b, 8, hk, dh))
        return attn.KVCache(jnp.asarray(kv[0], jnp.float32),
                            jnp.asarray(kv[1], jnp.float32), lengths)
    kv = rng.normal(size=(2, n_layers, 2 * b + 1, 4, hk, dh))
    table = np.arange(1, 2 * b + 1).reshape(b, 2)
    table[1, 1] = -1
    return attn.PagedKVCache(
        jnp.asarray(kv[0], jnp.float32), jnp.asarray(kv[1], jnp.float32),
        jnp.broadcast_to(jnp.asarray(table, jnp.int32), (n_layers, b, 2)),
        lengths)


@pytest.mark.parametrize("paged,rolling,lengths", [
    (False, False, [0, 3, 5, 7]),       # per-slot offsets
    (False, False, [8, 2, 8, 6]),       # done slots at s_max: clamped
    (False, True, [7, 8, 9, 21]),       # SWA ring wrap
    (True, False, [2, 5, 7, 0]),        # block table; slot 1 unmapped
    (True, True, [9, 3, 12, 17]),       # paged ring wrap
])
def test_cache_append_layer_matches_cache_update(paged, rolling, lengths):
    """Writing a decode step's rows into layer l of the stacked cache
    gives, bit for bit, what ``cache_update`` gives on that layer's own
    cache, and leaves every other layer and the stacked lengths alone."""
    rng = np.random.default_rng(len(lengths) + sum(lengths))
    n_layers, layer = 3, 1
    stack = _stacked_cache(rng, n_layers, lengths, paged=paged)
    k, v = (jnp.asarray(rng.normal(size=(len(lengths), 1, 2, 4)),
                        jnp.float32) for _ in range(2))
    append = jax.jit(attn.cache_append_layer, static_argnames="rolling")
    new, view = append(stack, jnp.int32(layer), k, v, rolling=rolling)
    want = attn.cache_update(jax.tree.map(lambda x: x[layer], stack),
                             k, v, rolling=rolling)
    for got, ref in zip(view, want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(new.k[layer]),
                                  np.asarray(want.k))
    np.testing.assert_array_equal(np.asarray(new.v[layer]),
                                  np.asarray(want.v))
    others = [i for i in range(n_layers) if i != layer]
    for got, ref in ((new.k, stack.k), (new.v, stack.v)):
        np.testing.assert_array_equal(np.asarray(got)[others],
                                      np.asarray(ref)[others])
    np.testing.assert_array_equal(np.asarray(new.length),
                                  np.asarray(stack.length))
